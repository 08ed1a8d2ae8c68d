"""Inference engine tests (analogue of reference tests/unit/inference/).

Key invariant both engines must satisfy: greedy generation from a KV-cached
decode loop must exactly match greedy generation recomputing the full
sequence each step (the no-cache reference).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference import DeepSpeedInferenceConfig, InferenceEngine
from deepspeed_tpu.inference.v2 import BlockedAllocator, InferenceEngineV2
from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
from deepspeed_tpu.models import forward, get_config, init_params
from deepspeed_tpu.parallel.topology import Topology, reset_topology, set_topology


# jitted once, shape-keyed: the eager per-token full forward dominated the
# V1 suite's runtime, and the module-scoped tiny_model means compiled shapes
# are shared across tests
_jit_forward = jax.jit(forward, static_argnames=("config",))


def _greedy_reference(cfg, params, prompt, n_new):
    """No-cache greedy loop: full forward each step."""
    toks = list(np.asarray(prompt, np.int32).reshape(-1))
    for _ in range(n_new):
        logits, _ = _jit_forward(params, jnp.asarray([toks]), cfg)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return np.asarray(toks, np.int32)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("tiny", n_layers=2, dtype="float32", max_seq_len=512)
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


@functools.lru_cache(maxsize=None)
def _geometry(name):
    """(cfg, params, prompts, n_new, greedy references) of a tiny model:
    ``mha`` 4:4 heads, ``gqa_4_2`` 4 query heads over 2 kv heads, ``experts``
    8 experts of which 2 a token, dropless. The references are the no-cache
    forward's, computed once a geometry."""
    from deepspeed_tpu.models import TransformerConfig

    cfg = {
        "mha": lambda: get_config("tiny", n_layers=2, dtype="float32", max_seq_len=512),
        "gqa_4_2": lambda: TransformerConfig(
            vocab_size=128, hidden_size=64, n_layers=2, n_heads=4, n_kv_heads=2,
            max_seq_len=256, dtype="float32"),
        "experts": lambda: TransformerConfig(
            vocab_size=64, hidden_size=32, n_layers=2, n_heads=4, ffn_hidden_size=24,
            n_experts=8, moe_top_k=2, moe_drop_tokens=False, moe_norm_topk_prob=False,
            max_seq_len=64, dtype="float32"),
    }[name]()
    params = init_params(cfg, jax.random.key(0))
    prompts, n_new = [np.arange(1, 9), np.arange(20, 25), np.arange(40, 52)], 5
    return cfg, params, prompts, n_new, [_greedy_reference(cfg, params, p, n_new) for p in prompts]


def _with_empty_chunk_rows(engine, inputs, tq, rows=1):
    """A split step's inputs on a grid of ``rows`` more chunk rows of ``tq``
    slots, each EMPTY (what the split step padded to before its grid followed
    the batch): a decode-only step's inputs as a chunk shape takes them, or a
    one-row step's as the two-row shape does."""
    kv = engine.config.kv_cache
    B, trash = kv.max_blocks_per_seq, kv.num_blocks
    spare = engine._state_slots - 1

    def more(name, shape, fill):
        pad = np.full(shape, fill, np.int32)
        return np.concatenate([inputs[name], pad]) if name in inputs else pad

    out = {
        **inputs,
        "tokens": more("tokens", rows * tq, 0), "positions": more("positions", rows * tq, 0),
        "blk": more("blk", rows * tq, trash), "row": more("row", rows * tq, 0),
        "chk_tables": more("chk_tables", (rows, B), trash),
        "chk_pos": more("chk_pos", (rows, tq), -1),
        "chk_start": more("chk_start", rows, 0), "chk_last": more("chk_last", rows, 0),
        "chk_uids": more("chk_uids", rows, 0),
    }
    if engine._beside:  # a second kind of cache: padding points at the spare slot
        out["chk_slots"] = more("chk_slots", rows, spare)
    if "wblk" in inputs:
        out["wblk"] = more("wblk", rows * tq, spare * engine._win_blocks)
    return out


@functools.lru_cache(maxsize=None)
def _family(name):
    """(cfg, params) of a tiny model of each family the split step serves:
    ``_geometry``'s three, ``gdn`` (DeltaNet layers + their state pools),
    ``window`` (window rings beside the block pool), ``latent`` (a pool of one
    plane behind a dense lead layer and grouped experts)."""
    from deepspeed_tpu.analysis.verify import _tiny_model_config

    if name in ("mha", "gqa_4_2", "experts"):
        cfg = dataclasses.replace(_geometry(name)[0], max_seq_len=512)
    else:
        cfg = _tiny_model_config(name)  # ``dstpu lint --verify``'s model of that kind
    return cfg, init_params(cfg, jax.random.key(0))


def _two_row_engine(cfg, params, chunk=160):
    """An engine whose scheduler may cut TWO prompt chunks a step, of up to
    ``chunk`` tokens: R = 4 decode rows, tables of 32 blocks of 16."""
    return InferenceEngineV2(cfg, params, RaggedInferenceEngineConfig.from_dict({
        "dtype": "float32", "prompt_chunk": chunk, "max_prompt_chunks": 2,
        "kv_cache": {"block_size": 16, "num_blocks": 96, "max_blocks_per_seq": 32},
        "state_manager": {"max_ragged_batch_size": 2 * chunk + 4,
                          "max_ragged_sequence_count": 4, "max_context": 512}}))


_POOLS = ("_k_cache", "_v_cache", "_rec_state", "_rec_conv", "_wk_cache", "_wv_cache")


class TestInferenceV1:
    @pytest.mark.parametrize("kw", [{"greedy": True}, {"greedy": False, "temperature": 0.9}])
    def test_fused_decode_steps_matches_per_step(self, tiny_model, kw):
        """v1 decode_steps: fused rounds are bit-identical to the per-step
        loop (greedy AND sampled — the rng folds by absolute step index),
        including a round count that doesn't divide max_new_tokens and EOS."""
        cfg, params = tiny_model
        prompt = np.arange(1, 9, dtype=np.int32)[None].repeat(2, 0)

        def run(ds, **gen_kw):
            engine = deepspeed_tpu.init_inference(
                model=(cfg, params),
                config={"dtype": "float32", "max_out_tokens": 64, "decode_steps": ds},
            )
            return engine.generate(prompt, max_new_tokens=11, seed=3, **gen_kw)

        ref = run(1, **kw)
        np.testing.assert_array_equal(run(4, **kw), ref)
        # EOS mid-round: pick a token the reference emits
        eos = int(ref[0, 8 + 4])
        ref_eos = run(1, eos_token_id=eos, **kw)
        np.testing.assert_array_equal(run(4, eos_token_id=eos, **kw), ref_eos)

    def test_greedy_matches_no_cache_reference(self, tiny_model):
        cfg, params = tiny_model
        prompt = np.arange(1, 9, dtype=np.int32)  # 8 tokens
        ref = _greedy_reference(cfg, params, prompt, 8)

        engine = deepspeed_tpu.init_inference(
            model=(cfg, params),
            config={"dtype": "float32", "max_out_tokens": 8, "max_tokens": 256},
        )
        out = engine.generate(prompt[None], max_new_tokens=8)
        np.testing.assert_array_equal(out[0], ref)

    def test_batched_generation(self, tiny_model):
        cfg, params = tiny_model
        prompts = np.stack([np.arange(1, 9), np.arange(11, 19)]).astype(np.int32)
        engine = InferenceEngine(
            (cfg, params), DeepSpeedInferenceConfig.from_dict({"dtype": "float32"})
        )
        out = engine.generate(prompts, max_new_tokens=4)
        assert out.shape == (2, 12)
        for i in range(2):
            ref = _greedy_reference(cfg, params, prompts[i], 4)
            np.testing.assert_array_equal(out[i], ref)

    def test_max_tokens_guard(self, tiny_model):
        cfg, params = tiny_model
        engine = InferenceEngine(
            (cfg, params),
            DeepSpeedInferenceConfig.from_dict({"dtype": "float32", "max_tokens": 16}),
        )
        with pytest.raises(ValueError):
            engine.generate(np.arange(12)[None], max_new_tokens=8)

    def test_tp_sharded_inference(self, tiny_model, devices8):
        cfg, params = tiny_model
        ref = _greedy_reference(cfg, params, np.arange(1, 9), 4)
        reset_topology()
        topo = Topology(model=4, data=2)
        engine = InferenceEngine(
            (cfg, params),
            DeepSpeedInferenceConfig.from_dict({"dtype": "float32"}),
            topology=topo,
        )
        out = engine.generate(np.arange(1, 9)[None], max_new_tokens=4)
        np.testing.assert_array_equal(out[0], ref)


class TestBlockedAllocator:
    def test_allocate_free_cycle(self):
        a = BlockedAllocator(8)
        b1 = a.allocate(3)
        assert a.free_blocks == 5
        b2 = a.allocate(5)
        assert a.free_blocks == 0
        assert sorted([*b1, *b2]) == list(range(8))
        with pytest.raises(ValueError):
            a.allocate(1)
        a.free(b1)
        assert a.free_blocks == 3
        b3 = a.allocate(2)
        assert set(b3) <= set(b1)

    def test_invalid_free(self):
        a = BlockedAllocator(4)
        with pytest.raises(ValueError):
            a.free([7])

    def test_double_free_rejected(self):
        """Double frees must raise instead of silently forking the free
        list (two sequences would later be handed the same block and write
        each other's KV)."""
        a = BlockedAllocator(8)
        blocks = a.allocate(3)
        a.free(blocks)
        with pytest.raises(ValueError, match="double free"):
            a.free(blocks)
        with pytest.raises(ValueError, match="double free"):
            a.free([int(blocks[0])])
        # duplicate ids within ONE free() call are caught before mutation
        b = a.allocate(2)
        with pytest.raises(ValueError):
            a.free([int(b[0]), int(b[0])])
        a.free(b)  # failed call above must not have freed anything
        assert a.free_blocks == 8
        # pool still consistent: every block allocatable exactly once
        assert sorted(int(x) for x in a.allocate(8)) == list(range(8))


class TestRaggedScheduler:
    def _stack(self, **kw):
        from deepspeed_tpu.inference.config import KVCacheConfig, StateManagerConfig
        from deepspeed_tpu.inference.v2.ragged_manager import DSStateManager
        from deepspeed_tpu.inference.v2.scheduler import RaggedScheduler

        kv = KVCacheConfig(block_size=4, num_blocks=32, max_blocks_per_seq=8)
        sm = StateManagerConfig(max_tracked_sequences=8, max_ragged_batch_size=64,
                                max_ragged_sequence_count=4, max_context=128, **kw)
        mgr = DSStateManager(sm, kv)
        return RaggedScheduler(sm, mgr, prompt_chunk=4), mgr

    def test_resubmit_after_finish_starts_fresh(self):
        """A finished uid resubmitted must get a FRESH sequence, not extend
        the flushed one (stale seen_tokens would corrupt start positions)."""
        sched, mgr = self._stack()
        sched.submit(7, np.arange(1, 5, dtype=np.int32))
        assert sched.next_batch() is not None
        sched.feedback(7, 99)
        sched.finish(7)
        sched.submit(7, np.asarray([41, 42], np.int32))
        seq = mgr.get_sequence(7)
        assert not seq.finished
        assert seq.tokens == [41, 42] and seq.seen_tokens == 0
        batch = sched.next_batch()
        assert batch.uids == [7]
        assert batch.start_positions == [0]
        np.testing.assert_array_equal(batch.tokens[0], [41, 42])

    def test_finish_mid_prefill_drops_pending_chunks(self):
        """Cancel while prompt chunks are still pending: the stale chunks
        must not crash next_batch or prepend the old prompt on resubmit."""
        sched, mgr = self._stack()
        sched.submit(3, np.arange(1, 11, dtype=np.int32))  # 10 toks, chunk=4
        first = sched.next_batch()
        assert first.is_prompt_chunk == [True]  # 6 tokens still pending
        sched.finish(3)  # cancel mid-prefill
        assert not sched.has_work()
        assert mgr.free_blocks == 32
        assert sched.next_batch() is None
        sched.submit(3, np.asarray([70, 71], np.int32))
        batch = sched.next_batch()
        np.testing.assert_array_equal(batch.tokens[0], [70, 71])
        assert batch.start_positions == [0]


class TestInferenceV2:
    def _engine(self, cfg, params, **kv):
        rc = RaggedInferenceEngineConfig.from_dict(
            {
                "dtype": "float32",
                "kv_cache": {"block_size": 16, "num_blocks": 64, "max_blocks_per_seq": 8, **kv},
                "state_manager": {"max_ragged_batch_size": 64, "max_ragged_sequence_count": 4},
            }
        )
        return InferenceEngineV2(cfg, params, rc)

    def test_single_sequence_matches_reference(self, tiny_model):
        cfg, params = tiny_model
        engine = self._engine(cfg, params)
        prompt = np.arange(1, 9, dtype=np.int32)
        ref = _greedy_reference(cfg, params, prompt, 6)
        out = engine.generate([prompt], max_new_tokens=6)
        np.testing.assert_array_equal(out[0], ref)

    def test_tensor_parallel_matches_tp1(self, tiny_model, devices8):
        """v2 tensor parallelism (VERDICT round-3 missing #1; reference
        config_v2.py:16 tp_size): the SAME continuous-batching run under tp=2
        must reproduce the single-chip tokens — params sharded by the TP
        specs, KV cache sharded on kv-heads, paged attention in a shard_map
        island."""
        from deepspeed_tpu.parallel.topology import Topology, reset_topology, set_topology

        cfg, params = tiny_model
        prompts = [np.arange(1, 9), np.arange(21, 33), np.arange(5, 10)]
        refs = [_greedy_reference(cfg, params, p, 5) for p in prompts]
        reset_topology()
        try:
            set_topology(Topology(data=4, model=2))
            rc = RaggedInferenceEngineConfig.from_dict(
                {
                    "dtype": "float32",
                    "tp_size": 2,
                    "kv_cache": {"block_size": 16, "num_blocks": 64, "max_blocks_per_seq": 8},
                    "state_manager": {"max_ragged_batch_size": 64, "max_ragged_sequence_count": 4},
                }
            )
            engine = InferenceEngineV2(cfg, params, rc)
            # params actually sharded over the model axis (not replicated)
            wq = engine.params["layers"]["wq"]
            assert len(wq.sharding.device_set) == 8
            assert engine._k_cache.sharding.spec[3] is not None  # [L, NB, bs, nkv, d]
            outs = engine.generate(prompts, max_new_tokens=5)
            for o, r in zip(outs, refs):
                np.testing.assert_array_equal(o, r)
        finally:
            reset_topology()

    def test_tp_requires_matching_topology(self, tiny_model, devices8):
        from deepspeed_tpu.parallel.topology import reset_topology

        cfg, params = tiny_model
        reset_topology()
        rc = RaggedInferenceEngineConfig.from_dict(
            {"dtype": "float32", "tp_size": 2,
             "kv_cache": {"block_size": 16, "num_blocks": 64, "max_blocks_per_seq": 8},
             "state_manager": {"max_ragged_batch_size": 64, "max_ragged_sequence_count": 4}}
        )
        with pytest.raises(ValueError, match="tp_size"):
            InferenceEngineV2(cfg, params, rc)
        reset_topology()

    @pytest.mark.parametrize("geometry", ["tiny", "gqa_4_2"])
    def test_continuous_batching_multi_sequence(self, tiny_model, geometry):
        """Batched generate() (the split step, several sequences a call)
        against an oracle that shares no code with it: the no-cache full
        forward. gqa_4_2: 4 query heads over 2 kv heads, prompts of 8, 5 and
        12 tokens."""
        if geometry == "tiny":
            cfg, params = tiny_model
            prompts, n_new = [np.arange(1, 9), np.arange(21, 33), np.arange(5, 10)], 5
        else:
            from deepspeed_tpu.models import TransformerConfig

            cfg = TransformerConfig(
                vocab_size=128, hidden_size=64, n_layers=2, n_heads=4, n_kv_heads=2,
                max_seq_len=256, dtype="float32",
            )
            params = init_params(cfg, jax.random.key(0))
            prompts, n_new = [np.arange(1, 9), np.arange(20, 25), np.arange(40, 52)], 6
        engine = self._engine(cfg, params)
        refs = [_greedy_reference(cfg, params, p, n_new) for p in prompts]
        outs = engine.generate(prompts, max_new_tokens=n_new)
        for o, r in zip(outs, refs):
            np.testing.assert_array_equal(o, r)

    @pytest.mark.parametrize("pool", ["bf16", "int8"])
    @pytest.mark.parametrize("geometry", ["mha", "gqa_4_2", "experts"])
    def test_decode_only_shape_equals_the_chunk_shape(self, geometry, pool):
        """A step with no prompt chunk runs the split step's decode-only
        shape: the grid of its R decode slots, no ``chk_*`` input. Its
        tokens and logits are those of the chunk shape fed the same rows
        beside empty chunk rows (what such a step ran before), and the
        streams both shapes serve in turn are the no-cache forward's."""
        cfg, params, prompts, n_new, refs = _geometry(geometry)
        engine = self._engine(cfg, params, kv_cache_dtype=pool)
        tq = min(128, engine.scheduler.prompt_chunk)
        for out, ref in zip(engine.generate(prompts, max_new_tokens=n_new), refs):
            np.testing.assert_array_equal(out, ref)
        assert sorted(engine._programs) == [("split", (0, 0)), ("split", (1, tq))]

        # the prompts again, up to a batch of three decode rows and no chunk
        sched = engine.scheduler
        first = {}
        for uid, p in enumerate(prompts):
            sched.submit(uid, p)
        while sched.has_pending():
            first.update(engine.step_tokens())
        for uid, tok in first.items():
            sched.feedback(uid, tok)
        batch = sched.next_batch()
        assert all(batch.is_decode) and len(batch.uids) == len(prompts)
        key, inputs = engine._stage_split(
            batch.total_tokens,
            list(zip(batch.uids, batch.tokens, batch.start_positions, batch.token_src)), [])
        assert key == ("split", (0, 0)) and set(batch.token_src) == {-1}
        assert sorted(inputs) == ["blk", "dec_pos", "dec_tables", "dec_uids", "last_tokens",
                                  "positions", "row", "tok_src", "tokens"]
        assert {len(inputs[k]) for k in ("tokens", "positions", "blk", "row", "dec_pos")} == {4}
        # both programs read the pool below the rows' positions and write
        # the same K/V at them: run one after the other on the same pools
        logits0, no_logits, toks0, no_toks, last0 = engine._launch(key, inputs)
        logits1, _, toks1, _, last1 = engine._launch(
            ("split", (1, tq)), _with_empty_chunk_rows(engine, inputs, tq))
        assert no_logits is None and no_toks is None
        # the tokens by output slot, for the next step to read on the device:
        # decode slots, then chunk rows (zeros in the shape that has none)
        np.testing.assert_array_equal(np.asarray(last0)[:4], np.asarray(toks0))
        np.testing.assert_array_equal(np.asarray(last1)[:4], np.asarray(toks1))
        assert not np.asarray(last0)[4:].any() and last0.shape == last1.shape
        live = slice(0, len(batch.uids))
        np.testing.assert_array_equal(np.asarray(toks0)[live], np.asarray(toks1)[live])
        np.testing.assert_allclose(
            np.asarray(logits0)[live], np.asarray(logits1)[live], atol=2e-5, rtol=2e-5)
        for i, uid in enumerate(batch.uids):
            assert int(toks0[i]) == refs[uid][len(prompts[uid]) + 1]

    @pytest.mark.parametrize("tail", [40, 140])
    @pytest.mark.parametrize("family", ["mha", "gqa_4_2", "experts", "gdn", "window", "latent"])
    def test_one_row_shape_equals_the_two_row_shape(self, family, tail):
        """A batch with ONE chunk row runs the split step on a grid of one
        chunk row, though the scheduler could have cut two. Its tokens and
        logits are those of the two-row shape fed the same rows beside an
        EMPTY second row (what such a step ran before), in both buckets (a
        chunk of 40 tokens: 128 slots, a bucket the engine gives one row alone
        and the builder any count; of 140: ``prompt_chunk`` = 160) and for
        every kind of cache: the chunk is a prompt's SECOND, over 160 tokens
        of pool, state or ring, beside a decode row."""
        cfg, params = _family(family)
        engine = _two_row_engine(cfg, params)
        sched, R = engine.scheduler, 4
        tq = 128 if tail <= 128 else sched.prompt_chunk
        sched.submit(0, np.arange(1, 21, dtype=np.int32))
        sched.feedback(0, engine.step_tokens()[0])
        sched.submit(1, (np.arange(160 + tail, dtype=np.int32) * 7) % (cfg.vocab_size - 1) + 1)
        sched.feedback(0, engine.step_tokens()[0])  # the prompt's first 160 beside the decode row
        batch = sched.next_batch()
        assert batch.is_decode == [True, False] and len(batch.tokens[1]) == tail
        dec, chk = (
            [(u, t, s, x)] for u, t, s, x in zip(
                batch.uids, batch.tokens, batch.start_positions,
                [batch.token_src[0], batch.is_prompt_chunk[1]]))
        key, inputs = engine._stage_split(batch.total_tokens, dec, chk)
        assert key == ("split", (1, tq)) and chk[0][2] == 160
        assert engine.last_step.grid_slots == R + tq == len(inputs["tokens"])
        # both programs start from the same pools: a DeltaNet layer's state
        # is read AND written by the step
        before = {n: jnp.copy(getattr(engine, n)) for n in _POOLS
                  if getattr(engine, n, None) is not None}
        logits1, chk_logits1, toks1, chk_toks1, last1 = engine._launch(key, inputs)
        for n, pool in before.items():
            setattr(engine, n, pool)
        logits2, chk_logits2, toks2, chk_toks2, last2 = engine._launch(
            ("split", (2, tq)), _with_empty_chunk_rows(engine, inputs, tq))
        assert chk_logits1.shape[0] == 1 and chk_logits2.shape[0] == 2
        np.testing.assert_allclose(
            np.asarray(logits1)[0], np.asarray(logits2)[0], atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(
            np.asarray(chk_logits1)[0], np.asarray(chk_logits2)[0], atol=2e-5, rtol=2e-5)
        assert int(toks1[0]) == int(toks2[0]) and int(chk_toks1[0]) == int(chk_toks2[0])
        # the tokens by output slot keep their length whatever the shape: R
        # decode slots, the chunk row at R, zeros where the shape has no row
        assert last1.shape == last2.shape == (R + 2,)
        np.testing.assert_array_equal(np.asarray(last1)[: R + 1], np.asarray(last2)[: R + 1])
        assert int(last1[R]) == int(chk_toks1[0]) and int(last1[R + 1]) == 0

    @pytest.mark.parametrize("rows", [0, 1, 2])
    def test_grid_follows_the_chunk_rows(self, tiny_model, rows):
        """``StepStats.grid_slots`` is R + rc x tq for the ``rc`` chunk rows
        the scheduler cut, and ``chunk_table_slots`` what a dense walk of
        those rows covers: rc x (32 table slots + 160 / 16 chunk blocks), by
        hand for no row, one of two and both, beside one decode row."""
        cfg, params = tiny_model
        engine = _two_row_engine(cfg, params)
        sched = engine.scheduler
        sched.submit(0, np.arange(1, 21, dtype=np.int32))
        sched.feedback(0, engine.step_tokens()[0])
        lengths = [140, 30][:rows]
        for uid, n in enumerate(lengths, start=1):
            sched.submit(uid, np.arange(n, dtype=np.int32) + 10 * uid)
        assert 0 in engine.step_tokens()
        step, tq = engine.last_step, 160 if rows else 0
        assert ("split", (rows, tq)) in engine._programs
        assert (step.grid_slots, step.scheduled_tokens, step.prefill_tokens) == (
            4 + rows * tq, 1 + sum(lengths), sum(lengths))
        # 140 tokens are 9 key blocks of 16 and 30 are 2, nothing below either
        assert step.chunk_live_blocks == [0, 9, 11][rows]
        assert step.chunk_table_slots == rows * (32 + 10)
        assert (step.paged_live_blocks, step.paged_table_slots) == (2, 4 * 32)

    @pytest.mark.parametrize("G", [1, 2, 4])
    def test_paged_programs_are_the_rows_blocks_by_groups(self, tiny_model, G):
        """``_count_paged``: a row of ``n`` blocks is ``ceil(n / G)`` programs
        of the decode kernel, ``G`` what the kernel's own rule gives the
        engine's block pool: an inactive slot and a row of nothing count none,
        rows of 1 and of G blocks one, a row of G + 1 two; ``calls`` walks of
        the rows multiply all three numbers. Blocks of 16 tokens, tables of 8."""
        from deepspeed_tpu.ops.attention.paged_pallas import blocks_a_program

        cfg, params = tiny_model
        engine = self._engine(cfg, params)
        # 16 tokens x 2 KV heads x (16 + 16) wide x 4 bytes: far below a megabyte
        assert engine._blocks_a_program == blocks_a_program(16 * 2 * 32 * 4) == 4
        engine._blocks_a_program = G
        tokens = np.array([-1, 0, 1, 16 * G, 16 * G + 1])
        blocks = 0 + 0 + 1 + G + (G + 1)
        assert engine._count_paged(tokens) == {
            "paged_live_blocks": blocks, "paged_table_slots": 5 * 8, "paged_programs": 1 + 1 + 2}
        assert engine._count_paged(tokens, calls=3) == {
            "paged_live_blocks": 3 * blocks, "paged_table_slots": 3 * 5 * 8, "paged_programs": 3 * 4}
        engine._blocks_a_program = 0  # a latent pool: another kernel's walks
        assert engine._count_paged(tokens)["paged_programs"] == 0

    @pytest.mark.parametrize("entry", ["step_tokens", "step_tokens_experts", "spec_round"])
    def test_step_stats_filled_by(self, tiny_model, entry):
        """Every entry point leaves ONE fresh record of its step in
        ``last_step``: what the grid was sized to, what it carried, and what
        one layer's decode attention held (R = 4 rows, tables of 8 blocks of
        16, one chunk of 64 slots a split step). The values are the ones the
        six ``last_*`` attributes held before the record replaced them."""
        from deepspeed_tpu.inference.v2.engine_v2 import StepStats

        cfg, params = _geometry("experts")[:2] if entry == "step_tokens_experts" else tiny_model
        engine = self._engine(cfg, params)
        engine.scheduler.submit(0, np.arange(1, 21, dtype=np.int32))
        toks = engine.step_tokens()  # the prompt's 20 tokens: no decode row yet
        prefill = engine.last_step
        # ... and its chunk attention: no pool block below the chunk + 2 of
        # its own, of the 8 table slots + 4 chunk blocks a dense walk covers
        assert dataclasses.replace(prefill, moe=None) == StepStats(
            4 + 64, 20, 20, 0, 4 * 8, chunk_live_blocks=2, chunk_table_slots=8 + 4,
            kv_global_blocks=2, kv_context_tokens=20)  # the cache as the step found it
        engine.scheduler.feedback(0, toks[0])
        if entry.startswith("step_tokens"):
            engine.scheduler.feedback(0, engine.step_tokens()[0])
            # a step with no chunk is sized to its R decode slots alone; one
            # decode row whose pool window holds 20 tokens = 2 blocks
            moe = None
            if cfg.n_experts:
                # what _count_moe reads off the grid: L layer calls, each the
                # row's top-2 pairs in tiles sized for R x 2 pairs
                from deepspeed_tpu.parallel.moe import grouped

                tile = grouped.row_tile(4 * cfg.moe_top_k, 4)
                # (one row's two pairs hit two experts a layer; every expert is held)
                moe = {"routed": 2 * 2, "computed": 2 * 2 * tile, "hot": 2, "calls": 2,
                       "hit": 2 * 2}
                assert prefill.moe["routed"] == 20 * 2 * 2
            # ... waited for where it was launched: not ahead, no row dropped
            assert engine.last_step == StepStats(
                4, 1, 0, 2, 4 * 8, moe, kv_global_blocks=2, kv_context_tokens=21,
                paged_programs=1,  # (blocks this small: four to a program)
                ahead=False, ahead_rows_dropped=0)
        else:
            assert 1 <= len(engine.spec_round(2, drafts={0: [5]})[0]) <= 2
            # the pending token and one draft on a grid of R x (k + 1)
            assert engine.last_step == StepStats(
                4 * 3, 2, 0, 3 * 2, 3 * 4 * 8, kv_global_blocks=2, kv_context_tokens=22,
                paged_programs=3)
        assert engine.last_step is not prefill and prefill.prefill_tokens == 20
        # a step with nothing to schedule starts from zeros again
        engine.scheduler.finish(0)
        assert engine.step_tokens() == {} and engine.last_step == StepStats()

    def test_windowed_model_serves_v2(self, tiny_model):
        """A uniform sliding-window model (mistral-v0.1/starcoder2 class)
        serves through v2: paged attention applies the band, greedy output
        matches the dense forward."""
        import dataclasses

        cfg, params = tiny_model
        wcfg = dataclasses.replace(cfg, sliding_window=24)

        prompt = np.arange(1, 33, dtype=np.int32)  # 32 tokens > window 24
        toks = list(prompt)
        for _ in range(6):
            lg, _ = _jit_forward(params, jnp.asarray([toks]), wcfg)
            toks.append(int(jnp.argmax(lg[0, -1])))
        rc = RaggedInferenceEngineConfig.from_dict(
            {
                "dtype": "float32",
                "kv_cache": {"block_size": 16, "num_blocks": 64, "max_blocks_per_seq": 8},
                "state_manager": {"max_ragged_batch_size": 64, "max_ragged_sequence_count": 4},
            }
        )
        engine = InferenceEngineV2(wcfg, params, rc)
        out = engine.generate([prompt], max_new_tokens=6)[0]
        np.testing.assert_array_equal(np.asarray(out), np.asarray(toks))

    def test_prompt_splitting_across_steps(self, tiny_model):
        """Prompt longer than the per-step token budget is split (SplitFuse)."""
        cfg, params = tiny_model
        rc = RaggedInferenceEngineConfig.from_dict(
            {
                "dtype": "float32",
                "kv_cache": {"block_size": 16, "num_blocks": 64, "max_blocks_per_seq": 8},
                "state_manager": {"max_ragged_batch_size": 16, "max_ragged_sequence_count": 2},
            }
        )
        engine = InferenceEngineV2(cfg, params, rc)
        prompt = np.arange(1, 41, dtype=np.int32)  # 40 tokens > 16 budget
        ref = _greedy_reference(cfg, params, prompt, 4)
        out = engine.generate([prompt], max_new_tokens=4)
        np.testing.assert_array_equal(out[0], ref)

    def test_blocks_released_on_finish(self, tiny_model):
        cfg, params = tiny_model
        engine = self._engine(cfg, params)
        free0 = engine.state_manager.free_blocks
        engine.generate([np.arange(1, 20)], max_new_tokens=3)
        assert engine.state_manager.free_blocks == free0

    def test_continuation_submit_while_running(self, tiny_model):
        """Submitting more tokens for a uid with an outstanding decode token
        folds the pending token into the prompt chunk (no double KV write)."""
        cfg, params = tiny_model
        engine = self._engine(cfg, params)
        prompt = np.arange(1, 9, dtype=np.int32)
        res = engine.put([0], [prompt])  # prefill -> logits for uid 0
        nxt = int(np.argmax(res[0]))
        engine.scheduler.feedback(0, nxt)  # uid 0 now running
        extra = np.arange(11, 15, dtype=np.int32)
        res2 = engine.put([0], [extra])  # continuation while running
        assert 0 in res2
        seq = engine.state_manager.get_sequence(0)
        # KV holds prompt + pending + extra exactly once
        assert seq.seen_tokens == len(prompt) + 1 + len(extra)
        # matches the dense reference over the same token history
        full = np.concatenate([prompt, [nxt], extra])
        ref = _greedy_reference(cfg, params, full, 1)
        np.testing.assert_array_equal(
            np.concatenate([full, [int(np.argmax(res2[0]))]]), ref
        )

    def test_inadmissible_prompt_rejected_at_submit(self, tiny_model):
        """Liveness: a prompt that could never fit (per-seq block cap) raises
        at submit instead of busy-looping generate() forever."""
        cfg, params = tiny_model
        engine = self._engine(cfg, params)  # 8 blocks x 16 = 128-token cap
        with pytest.raises(ValueError):
            engine.scheduler.submit(0, np.arange(1, 200, dtype=np.int32))

    def test_max_context_enforced_at_submit(self, tiny_model):
        cfg, params = tiny_model
        rc = RaggedInferenceEngineConfig.from_dict(
            {
                "dtype": "float32",
                "kv_cache": {"block_size": 16, "num_blocks": 64, "max_blocks_per_seq": 8},
                "state_manager": {"max_context": 32},
            }
        )
        engine = InferenceEngineV2(cfg, params, rc)
        with pytest.raises(ValueError, match="max_context"):
            engine.scheduler.submit(0, np.arange(1, 40, dtype=np.int32))

    def test_decode_capped_at_block_limit_finishes(self, tiny_model):
        """A sequence whose decode hits max_blocks_per_seq ends like a
        max-length stop; generate() terminates and reports it as capped."""
        cfg, params = tiny_model
        rc = RaggedInferenceEngineConfig.from_dict(
            {
                "dtype": "float32",
                "kv_cache": {"block_size": 16, "num_blocks": 64, "max_blocks_per_seq": 1},
                "state_manager": {"max_ragged_batch_size": 64},
            }
        )
        engine = InferenceEngineV2(cfg, params, rc)
        prompt = np.arange(1, 11, dtype=np.int32)  # 10 tokens; block cap = 16
        out = engine.generate([prompt], max_new_tokens=50)
        # 16-token block fills: 10 prompt + 6 generated, then capped stop
        assert len(out[0]) <= 16 + 1  # +1: last sampled token is host-side
        assert 0 in engine.last_capped


def test_v1_fused_decode_overshoot_preserves_cache():
    """decode_steps not dividing max_new-1: the final fused round's
    overshoot KV writes must land in allocated spare slots, not clamp onto
    the last in-range entry (round-4 advisor). Proof: generation with a
    non-dividing decode_steps is token-identical to per-step decoding even
    when the total lands exactly on a cache bucket boundary."""
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models import TransformerConfig, init_params

    mc = TransformerConfig(
        vocab_size=128, hidden_size=64, n_layers=2, n_heads=4,
        max_seq_len=256, dtype="float32",
    )
    params = init_params(mc, jax.random.key(3))
    prompt = np.arange(1, 25, dtype=np.int32)[None]  # s=24
    # s + max_new = 32 = exact bucket edge; decode_steps=5 !| max_new-1=7
    ref = InferenceEngine(
        mc, DeepSpeedInferenceConfig.from_dict({"dtype": "float32"}), params
    ).generate(prompt, max_new_tokens=8)
    out = InferenceEngine(
        mc,
        DeepSpeedInferenceConfig.from_dict({"dtype": "float32", "decode_steps": 5}),
        params,
    ).generate(prompt, max_new_tokens=8)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_the_v2_engine_has_two_step_programs_and_no_decode_steps():
    """One way to hide the host between two decode steps (one step in flight):
    the step programs are the split step and the verify step, and nothing of
    the engine, its config or the serving entry point takes ``decode_steps``."""
    import inspect

    from deepspeed_tpu.inference import cli
    from deepspeed_tpu.inference.v2 import engine_v2
    from deepspeed_tpu.serving import ServingDriver
    from deepspeed_tpu.serving.cluster.core import EngineCore

    assert sorted(engine_v2._BUILDERS) == ["split", "verify"]
    assert all(hasattr(InferenceEngineV2, b) for b in engine_v2._BUILDERS.values())
    assert list(inspect.signature(InferenceEngineV2.warm_trace).parameters) == [
        "self", "spec_k", "uid"]
    for owner in (ServingDriver, EngineCore):
        assert "decode_steps" not in inspect.signature(owner.__init__).parameters
    assert "decode_steps" not in RaggedInferenceEngineConfig().to_dict()
    with pytest.raises(SystemExit):  # argparse: an unknown flag
        cli.serve_parse_args(["--model", "", "--decode-steps", "4"])
    assert not hasattr(cli.serve_parse_args(["--model", ""]), "decode_steps")
