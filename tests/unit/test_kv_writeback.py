"""The serving step's one pool write (engine_v2._scatter_kv, after the layer
loop): every live (layer, block, row) slot holds the K/V a dense forward of
the same tokens computes, every slot the step did not schedule is
byte-identical to before the step, and the served tokens equal generate()'s.
The run mixes decode rows, two prompt chunks in one step, a prefix-cache hit
and padded (trash) slots; cases: bf16 and int8 pools, an alternating-window
stack (the unrolled layer loop; its window layer's K/V live in the window pool:
the block pool is its global layer's, the ring's rows that a later query can see
are held to the dense K/V after every step, and the prefix cache is off) and
tp=2 on the virtual CPU devices."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import get_config, init_params
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops.quantizer.block_quant import quantize_kv

BS = 4  # block size
MAX_NEW = 5

CASES = {
    "bf16": {},
    "int8": {"kv_dtype": "int8"},
    "alternating": {"model": {"sliding_window": 6, "attn_layer_pattern": (1, 0)}},
    "tp2": {"tp": 2},
}


def _engine(cfg, params, kv_dtype="bf16", tp=1):
    rc = RaggedInferenceEngineConfig.from_dict({
        "dtype": "float32", "tp_size": tp, "prompt_chunk": 8, "max_prompt_chunks": 2,
        "kv_cache": {"block_size": BS, "num_blocks": 48, "max_blocks_per_seq": 16,
                     "prefix_cache": True, "kv_cache_dtype": kv_dtype},
        "state_manager": {"max_tracked_sequences": 8, "max_ragged_batch_size": 64,
                          "max_ragged_sequence_count": 4, "max_context": 64},
    })
    return InferenceEngineV2(cfg, params, rc)


def _prompts(vocab):
    rng = np.random.default_rng(7)
    a = rng.integers(1, vocab, size=13).astype(np.int32)
    # b shares a's first two blocks (the prefix-cache hit); c shares nothing
    b = np.concatenate([a[:2 * BS], rng.integers(1, vocab, size=6)]).astype(np.int32)
    c = rng.integers(1, vocab, size=11).astype(np.int32)
    return {0: a, 1: b, 2: c}


def _pools(eng):
    """The block pools (and int8 scale planes) on the host, as [L, NBp, bs, nkv(, d)]."""
    return [np.asarray(p) for p in eng._kv_pool_planes().values()]


def _rows(pools, table, n):
    """Rows 0..n-1 of a sequence out of each pool: [L, n, nkv(, d)]."""
    pos = np.arange(n)
    blk = np.asarray(table)[pos // BS]
    return [p[:, blk, pos % BS] for p in pools]


def _ring_rows(eng, seq):
    """What a window layer's ring holds of ``seq`` that a later query can see,
    positions ``p0 .. seen - 1``: (p0, [Lw, seen - p0, nkv, d] of K, of V)."""
    wb, n = eng._win_blocks, seq.seen_tokens
    pos = np.arange(max(0, n - eng._mc.sliding_window + 1), n)
    blk = seq.state_slot * wb + (pos // BS) % wb
    return (int(pos[0]),) + tuple(
        np.asarray(p)[:, blk, pos % BS] for p in (eng._wk_cache, eng._wv_cache))


def _dense_kv(cfg, params, tokens):
    """K/V of every layer from a dense forward of ``tokens``: [L, n, nkv, d]."""
    n = len(tokens)
    _, (k, v, _) = T.decode_step(
        params, jnp.asarray(tokens, jnp.int32)[None], cfg, T.init_kv_cache(cfg, 1, n),
        jnp.arange(n, dtype=jnp.int32)[None])
    return [np.asarray(a[:, 0]).transpose(0, 2, 1, 3) for a in (k, v)]


def _serve(eng, prompts):
    """A at step 0; B and C together once A decodes. Checks after every step
    that only scheduled slots (and the trash block) changed. Returns the
    streams, each sequence's last pool rows, what the steps mixed, the prefix
    hit, and for a window pool each sequence's ring rows after every step."""
    sm, sched = eng.state_manager, eng.scheduler
    trash = eng.config.kv_cache.num_blocks
    streams = {u: list(p) for u, p in prompts.items()}
    live, rows, mixed, shared = {0}, {}, [], None
    rings = {u: [] for u in prompts}
    sched.submit(0, prompts[0])
    for _ in range(40):
        if not live:
            break
        if shared is None and len(streams[0]) > len(prompts[0]):  # A decodes: B and C arrive
            for u in (1, 2):
                sched.submit(u, prompts[u])
                live.add(u)
            seq_a, seq_b = sm.get_sequence(0), sm.get_sequence(1)
            shared = (list(seq_a.block_table[:2]), list(seq_b.block_table[:2]), seq_b.seen_tokens)
        before = _pools(eng)
        seen0 = {u: sm.get_sequence(u).seen_tokens for u in live}
        out = eng.step_tokens()
        after = _pools(eng)
        # slots this step was asked to write: positions seen0..seen of each row
        written = np.zeros(after[0].shape[1:3], bool)
        written[trash] = True
        chunk_rows = decode_rows = 0
        for u in live:
            seq = sm.get_sequence(u)
            pos = np.arange(seen0[u], seq.seen_tokens)
            written[np.asarray(seq.block_table)[pos // BS], pos % BS] = True
            if len(pos):
                chunk_rows += seen0[u] < len(prompts[u])
                decode_rows += seen0[u] >= len(prompts[u])
        mixed.append((chunk_rows, decode_rows))
        for b, a in zip(before, after):
            np.testing.assert_array_equal(b[:, ~written], a[:, ~written])
        for u in sorted(live):
            seq = sm.get_sequence(u)
            new = _rows(after, seq.block_table, seq.seen_tokens)
            for old, cur in zip(rows.get(u, []), new):  # older rows never change
                np.testing.assert_array_equal(old, cur[:, :old.shape[1]])
            rows[u] = new
            if eng._windowed and seq.seen_tokens:
                rings[u].append(_ring_rows(eng, seq))
        for u, tok in out.items():
            streams[u].append(int(tok))
            if len(streams[u]) - len(prompts[u]) >= MAX_NEW:
                sched.finish(u)
                live.discard(u)
            else:
                sched.feedback(u, int(tok))
    assert not live, f"sequences still running: {live}"
    return streams, rows, mixed, shared, rings


@pytest.mark.parametrize("case", list(CASES))
def test_pool_holds_dense_kv_and_only_scheduled_slots_change(case, devices8):
    from deepspeed_tpu.parallel.topology import Topology, reset_topology, set_topology

    spec = CASES[case]
    cfg = get_config("tiny", n_layers=2, dtype="float32", max_seq_len=512, **spec.get("model", {}))
    params = init_params(cfg, jax.random.key(0))
    prompts = _prompts(cfg.vocab_size)
    kv_dtype, tp = spec.get("kv_dtype", "bf16"), spec.get("tp", 1)
    reset_topology()
    try:
        if tp > 1:
            set_topology(Topology(data=8 // tp, model=tp))
        eng = _engine(cfg, params, kv_dtype, tp)
        if tp > 1:
            assert eng._k_cache.sharding.spec[3] is not None
        if "model" in spec:
            assert isinstance(eng._layer_windows(), list)  # the unrolled loop
        streams, rows, mixed, shared, rings = _serve(eng, prompts)
        oracle = _engine(cfg, params, kv_dtype, tp).generate(
            [prompts[u] for u in sorted(prompts)], max_new_tokens=MAX_NEW)
    finally:
        reset_topology()

    # what the run was meant to mix
    assert any(c >= 2 and d >= 1 for c, d in mixed), mixed  # two chunks beside a decode row
    blocks_a, blocks_b, cached = shared
    if eng._windowed:  # a hit would skip the window pool: the cache is off
        assert eng.prefix_cache is None and cached == 0
    else:
        assert blocks_a == blocks_b and cached == 2 * BS  # the prefix-cache hit
    assert any(len(p) % 8 for p in prompts.values())  # a chunk shorter than its bucket

    for u, want in zip(sorted(prompts), oracle):
        np.testing.assert_array_equal(np.asarray(streams[u], np.int32), want)

    for u in sorted(prompts):
        n = rows[u][0].shape[1]
        assert n == len(streams[u]) - 1  # all but the pending token are in the pool
        ref_k, ref_v = _dense_kv(cfg, params, streams[u][:n])
        kinds = T.cache_kinds(cfg)
        ring = [i for i, kind in enumerate(kinds) if kind == "window"]
        assert bool(ring) == bool(rings[u]) == (case == "alternating")
        for p0, got_k, got_v in rings[u]:  # the window pool's layers, after every step
            m = got_k.shape[1]
            np.testing.assert_allclose(got_k, ref_k[ring][:, p0:p0 + m], rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(got_v, ref_v[ring][:, p0:p0 + m], rtol=1e-4, atol=1e-5)
        if rings[u]:  # ... through a wrap of the ring and up to the last step
            assert rings[u][-1][0] + rings[u][-1][1].shape[1] == n > eng._win_blocks * BS
        held = [i for i, kind in enumerate(kinds) if kind == "full"]
        ref_k, ref_v = ref_k[held], ref_v[held]  # the block pool's layers
        if kv_dtype == "int8":
            for ref, q, s in ((ref_k, rows[u][0], rows[u][2]), (ref_v, rows[u][1], rows[u][3])):
                # layer 0 sees no quantized context: its stored bytes are the
                # dense K/V's, quantized (a float ulp may move a rounding)
                rq, rs = (np.asarray(a) for a in quantize_kv(jnp.asarray(ref[0])))
                assert np.abs(q[0].astype(np.int32) - rq.astype(np.int32)).max() <= 1
                np.testing.assert_allclose(s[0], rs, rtol=1e-4, atol=1e-7)
                # deeper layers attend over int8 context: close, not equal
                np.testing.assert_allclose(q * s[..., None], ref, atol=0.05 * np.abs(ref).max())
        else:
            np.testing.assert_allclose(rows[u][0], ref_k, rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(rows[u][1], ref_v, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("program", ["ahead", "verify"])
def test_steps_ahead_and_verify_write_dense_kv(program, devices8):
    """Decode steps launched one ahead (a row's token read on the device,
    never through the host) and the speculative verify step write through the
    same write-back: the live slots of every sequence hold the dense forward's
    K/V, and the tokens are generate()'s."""
    cfg = get_config("tiny", n_layers=2, dtype="float32", max_seq_len=512)
    params = init_params(cfg, jax.random.key(0))
    prompts = _prompts(cfg.vocab_size)
    oracle = _engine(cfg, params).generate(
        [prompts[u] for u in sorted(prompts)], max_new_tokens=12)
    eng = _engine(cfg, params)
    streams = {u: list(p) for u, p in prompts.items()}
    for u, p in prompts.items():
        eng.scheduler.submit(u, p)
    while eng.scheduler.has_pending():
        for u, tok in eng.step_tokens().items():
            streams[u].append(int(tok))
            eng.scheduler.feedback(u, int(tok))
    if program == "ahead":
        res = {u: [] for u in prompts}
        flights = [eng.launch_ahead(None, None)]
        for more in (True, True, False):
            if more:
                flights.append(eng.launch_ahead(flights[-1], lambda u: True))
            for u, tok in eng.collect_step(flights.pop(0)).items():
                res[u].append(tok)
                eng.scheduler.feedback(u, tok)
        assert all(len(toks) == 3 for toks in res.values())
    else:
        # the right next tokens for row 0, wrong ones for row 1, none for row 2
        n0 = len(streams[0])
        res = eng.spec_round(k=3, drafts={0: list(oracle[0][n0:n0 + 3]), 1: [1, 1]})
        assert len(res[0]) == 4 and len(res[2]) == 1
    assert set(res) == set(prompts)
    pools = _pools(eng)
    for u in sorted(prompts):
        streams[u].extend(int(t) for t in res[u])
        np.testing.assert_array_equal(streams[u], oracle[u][:len(streams[u])])
        seq = eng.state_manager.get_sequence(u)
        assert seq.seen_tokens == len(streams[u]) - 1
        k, v = _rows(pools, seq.block_table, seq.seen_tokens)
        ref_k, ref_v = _dense_kv(cfg, params, streams[u][:seq.seen_tokens])
        np.testing.assert_allclose(k, ref_k, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(v, ref_v, rtol=1e-4, atol=1e-5)
