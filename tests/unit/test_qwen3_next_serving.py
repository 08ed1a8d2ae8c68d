"""Qwen3-Next through the paged engine at a tiny size, seeded weights, on the
CPU: Gated DeltaNet layers with the recurrent-state pool beside the K/V pool, a
gated-attention layer, and one chip's share of the experts.

The oracle is ``benchmarks/reference/qwen3_next.py`` (plain float32
``jax.numpy``, token by token, no cache): prefill in chunks and then decode
through both pools must give the reference's full forward pass, on LOGITS."""

import dataclasses
import importlib
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.hf import config_from_hf

ref = importlib.import_module("benchmarks.reference.qwen3_next")

# two periods of 3 DeltaNet + 1 full attention; 16 experts of which share 1
# of 4 (experts 4-7) is held; value heads 2 a key head
HF = dict(
    model_type="qwen3_next", vocab_size=256, hidden_size=64, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32, intermediate_size=160,
    moe_intermediate_size=32, shared_expert_intermediate_size=32, num_experts=4,
    num_experts_per_tok=3, norm_topk_prob=True, full_attention_interval=4,
    linear_conv_kernel_dim=4, linear_key_head_dim=16, linear_value_head_dim=16,
    linear_num_key_heads=2, linear_num_value_heads=4, partial_rotary_factor=0.25,
    rms_norm_eps=1e-6, rope_theta=1e7, tie_word_embeddings=False,
    max_position_embeddings=512, decoder_sparse_step=1, mlp_only_layers=[],
    deployment_share={"num_experts": 16, "chips_per_layer": 4, "share_index": 1},
)
# prompts that make every shape of the split step: a short one (the 128
# bucket), one of a single prompt_chunk, one of three chunks with a short tail
PROMPT_LENS = (5, 70, 160, 330)


def _model(hf=HF, dtype="float32", seed=0):
    cfg = dataclasses.replace(config_from_hf(hf), dtype=dtype, remat=False)
    params = T.init_params(cfg, jax.random.key(seed))
    # norm weights off their identity: (1 + w) read as w would not show at zeros
    key = jax.random.key(seed + 1)

    def move(path, a):
        name = str(path[-1])
        if "norm" not in name:
            return a
        return a + (0.1 * jax.random.normal(jax.random.fold_in(key, sum(map(ord, name))), a.shape)).astype(a.dtype)

    return cfg, jax.tree_util.tree_map_with_path(move, params)


def _engine(cfg, params, dtype="float32", **extra):
    rc = {
        "dtype": dtype, "prompt_chunk": 160, "max_prompt_chunks": 2,
        "kv_cache": {"block_size": 16, "num_blocks": 64, "max_blocks_per_seq": 32},
        "state_manager": {"max_tracked_sequences": 6, "max_ragged_batch_size": 512,
                          "max_ragged_sequence_count": 4, "max_context": 512},
    }
    for k, v in extra.items():
        rc[k] = {**rc.get(k, {}), **v} if isinstance(v, dict) else v
    return InferenceEngineV2(cfg, params, RaggedInferenceEngineConfig.from_dict(rc))


def _serve_logits(eng, prompts, n_new, late=(), steps=None):
    """Each prompt's logits at its last prompt token and at ``n_new - 1``
    greedy tokens after it, as the engine's steps return them. The prompts
    numbered in ``late`` are submitted after the first step; ``steps`` collects
    every step's ``StepStats``."""
    for uid, p in enumerate(prompts):
        if uid not in late:
            eng.scheduler.submit(uid, p)
    got = {uid: [] for uid in range(len(prompts))}
    for i in range(40):
        if i == 1:
            for uid in late:
                eng.scheduler.submit(uid, prompts[uid])
        out = eng.step()
        if steps is not None:
            steps.append(eng.last_step)
        for uid, lg in out.items():
            got[uid].append(np.asarray(lg, np.float32))
            if len(got[uid]) < n_new:
                eng.scheduler.feedback(uid, int(np.argmax(lg)))
            else:
                eng.scheduler.finish(uid)
        if not eng.scheduler.has_work():
            break
    return {uid: np.stack(v) for uid, v in got.items()}


def _reference_logits(params, hf, prompt, served):
    """The reference's full forward over prompt + the served greedy tokens."""
    toks = np.concatenate([prompt, np.argmax(served[:-1], -1).astype(np.int32)])
    return np.asarray(ref.logits(params, toks, hf))[len(prompt) - 1:]


@pytest.mark.parametrize("gdn_impl", ["jnp", "interpret"])
def test_engine_equals_the_reference_on_logits_float32(gdn_impl, monkeypatch):
    """float32 weights and compute. The engine and the reference differ by the
    order of float32 sums alone (chunks of 64 against token by token, paged
    attention against dense, sorted experts against masked ones): logits of
    scale 1 within 5e-5 (measured 2e-6), where a wrong state, conv input,
    position or expert moves them by tenths to whole units. ``interpret`` runs the
    Pallas kernels ``dstpu_gdn_decode`` and ``dstpu_gdn_chunk`` themselves on
    the state pool. Then two more prompts, the second arriving after the first
    step: that step's TWO chunk rows are the first prompt's tail, continued from
    its slot's state, and a fresh row; a step's ``recurrent_chunk_tokens`` are the
    live prompt tokens its chunk rows carried."""
    from deepspeed_tpu.ops.linear_attention import delta_chunk

    cfg, params = _model()
    eng = _engine(cfg, params)
    eng._rec_impl = gdn_impl
    traced, kernel = set(), delta_chunk.delta_chunk
    monkeypatch.setattr(delta_chunk, "delta_chunk",
                        lambda q, *a, **kw: traced.add(q.shape[:2]) or kernel(q, *a, **kw))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 256, size=n).astype(np.int32) for n in PROMPT_LENS]
    more = [rng.integers(1, 256, size=n).astype(np.int32) for n in (200, 40)]
    steps = []
    with jax.default_matmul_precision("highest"):
        served = _serve_logits(eng, prompts, n_new=5, steps=steps)
        later = _serve_logits(eng, more, n_new=3, late=(1,), steps=steps)
        # 160 fresh | 40 continued + 40 fresh | decode steps
        assert [st.prefill_tokens for st in steps[-4:-1]] == [160, 80, 0]
        assert steps[-3].grid_slots == 4 + 2 * 160
        # (chunk rows, tq) of the programs that went through the chunk kernel
        assert traced == ({(1, 128), (1, 160), (2, 160)} if gdn_impl == "interpret" else set())
        for st in steps:
            assert st.recurrent_chunk_tokens == st.prefill_tokens
        for uid, p in enumerate(more):
            np.testing.assert_allclose(
                later[uid], _reference_logits(params, HF, p, later[uid]), atol=5e-5, rtol=0)
        # every shape of the split step served it: no chunk row, one in
        # either bucket, two (which share the one bucket)
        assert set(eng._programs) == {
            ("split", shape) for shape in [(0, 0), (1, 128), (1, 160), (2, 160)]}
        for uid, p in enumerate(prompts):
            want = _reference_logits(params, HF, p, served[uid])
            np.testing.assert_allclose(served[uid], want, atol=5e-5, rtol=0)
    acct = eng.state_manager.state_slot_accounting()
    assert acct == {"total": 6, "free": 6, "live": 0}


def test_engine_equals_the_reference_on_bf16_weights():
    """bf16 WEIGHTS (what ``dstpu serve`` loads), float32 compute: the engine
    and the reference both widen the same bf16 values, so the float32 limit
    holds unchanged; a tree read in the wrong dtype somewhere would not."""
    cfg, params = _model(dtype="bfloat16")
    cfg = dataclasses.replace(cfg, dtype="float32")
    eng = _engine(cfg, params)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 256, size=n).astype(np.int32) for n in (5, 200)]
    with jax.default_matmul_precision("highest"):
        served = _serve_logits(eng, prompts, n_new=5)
        for uid, p in enumerate(prompts):
            want = _reference_logits(params, HF, p, served[uid])
            np.testing.assert_allclose(served[uid], want, atol=5e-5, rtol=0)


def test_engine_in_bf16_equals_the_no_cache_forward_in_bf16():
    """bf16 weights AND compute. Against the float32 reference a model this
    small says little: a rounding that turns one of 3 experts of 16, each a
    third of a layer's output at a width of 64, moves logits by more than any
    limit that would catch a fault (at the published widths the chip's
    comparison holds the served tokens to the reference). So every expert is chosen here (top 4 of
    the 4 held, no share: no decision to turn), and the bf16 engine is held
    to the no-cache ``forward()`` in bf16 on the same weights: prefill in
    chunks, decode through the state pool (float32) and the conv pool (bf16)
    and paged attention round at other places than one dense pass does, and
    nothing else may differ. One period of layers; measured 0.014 on logits
    of scale 1 (the oracle's own logits are bf16: 0.008 of that), limit 0.05."""
    hf = {**HF, "num_hidden_layers": 4, "num_experts_per_tok": 4, "deployment_share": None}
    cfg, params = _model(hf, dtype="bfloat16")
    eng = _engine(cfg, params, dtype="bfloat16")
    assert eng._rec_state.dtype == jnp.float32 and eng._rec_conv.dtype == jnp.bfloat16
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 256, size=n).astype(np.int32) for n in (5, 200)]
    served = _serve_logits(eng, prompts, n_new=6)
    for uid, p in enumerate(prompts):
        toks = np.concatenate([p, np.argmax(served[uid][:-1], -1).astype(np.int32)])
        want = np.asarray(T.forward(params, jnp.asarray(toks)[None], cfg)[0][0], np.float32)
        np.testing.assert_allclose(served[uid], want[len(p) - 1:], atol=0.05, rtol=0)


def test_a_bf16_state_pool_fails_the_float32_comparison():
    """The float32 comparison is tight enough to catch a recurrent state kept
    in bf16 (the precision below what the configuration states): the same
    engine with its state pool cast to bf16 misses the reference by a hundred
    times the limit (measured 5.5e-3 against 5e-5; the sound engine 2e-6)."""
    cfg, params = _model()
    eng = _engine(cfg, params)
    eng._rec_state = eng._rec_state.astype(jnp.bfloat16)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 256, size=n).astype(np.int32) for n in (70, 330)]
    with jax.default_matmul_precision("highest"):
        served = _serve_logits(eng, prompts, n_new=5)
        worst = max(np.abs(served[uid] - _reference_logits(params, HF, p, served[uid])).max()
                    for uid, p in enumerate(prompts))
    assert worst > 20 * 5e-5, worst


def test_a_state_lost_between_two_steps_fails_the_comparison():
    """The seeded gates keep a head's state over tens of tokens (``init_params``
    draws them as such layers are trained from), so the comparison sees the
    state pool: with every slot's recurrent state zeroed after the prompt, as
    a wrong slot or a lost hand-over from the chunked rule to the one-token
    update would leave it, the next token's logits miss the reference by whole
    units (measured 3.1 on logits of scale 1, limit 5e-5), where the token
    before the loss agrees."""
    cfg, params = _model()
    g = params["layers"]["gdn"]
    decay = jnp.exp(-jnp.exp(g["gdn_a_log"]) * jax.nn.softplus(g["gdn_dt_bias"]))
    assert float(jnp.median(decay)) > 0.7   # a token leaves most of the state standing
    eng = _engine(cfg, params)
    prompt = np.random.default_rng(4).integers(1, 256, size=70).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        eng.scheduler.submit(0, prompt)
        first = np.asarray(eng.step()[0], np.float32)
        eng.scheduler.feedback(0, int(np.argmax(first)))
        eng._rec_state = jnp.zeros_like(eng._rec_state)
        second = np.asarray(eng.step()[0], np.float32)
        eng.scheduler.finish(0)
        want = _reference_logits(params, HF, prompt, np.stack([first, second]))
    np.testing.assert_allclose(first, want[0], atol=5e-5, rtol=0)
    assert np.abs(second - want[1]).max() > 0.5


@pytest.mark.parametrize("sampling", [{}, {"greedy": False, "temperature": 0.9, "seed": 7}],
                         ids=["greedy", "sampled"])
def test_generate_equals_the_driven_core_and_carries_the_state(sampling):
    """``generate()`` is the served step (the state pools ride its carry from
    one step to the next): the same prompts through the serving driver give
    the same tokens, greedy and sampled, and the greedy ones agree with the
    reference's choice at every position."""
    from tests.unit.simple_model import served_tokens

    cfg, params = _model()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 256, size=n).astype(np.int32) for n in (5, 70, 200)]
    outs = _engine(cfg, params, **sampling).generate(prompts, max_new_tokens=7)
    driven = served_tokens(_engine(cfg, params, **sampling), prompts, 7)
    for a, got, p in zip(outs, driven, prompts):
        assert [int(t) for t in a[len(p):]] == got
        if not sampling:
            lg = np.asarray(ref.logits(params, a[:-1], HF))[len(p) - 1:]
            chosen = lg[np.arange(len(lg)), a[len(p):]]
            np.testing.assert_allclose(chosen, lg.max(-1), atol=5e-5)


def test_a_reused_slot_poisoned_with_nan_starts_from_zero():
    """A chunk at position 0 takes a zero state whatever its slot holds: with
    every slot of both state pools filled with NaN (what a finished sequence
    may leave), a fresh prompt is served as from a clean pool, and the spare
    slot the grid's padding points at stays finite."""
    cfg, params = _model()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 256, size=n).astype(np.int32) for n in (40, 200)]
    with jax.default_matmul_precision("highest"):
        clean = _engine(cfg, params).generate(prompts, max_new_tokens=5)
        eng = _engine(cfg, params)
        spare = np.arange(cfg.kind_count("gdn")) * eng._state_slots + eng._state_slots - 1
        keep = jnp.zeros(eng._rec_state.shape[0], bool).at[spare].set(True)
        eng._rec_state = jnp.where(keep[:, None, None, None], eng._rec_state, jnp.nan)
        eng._rec_conv = jnp.where(keep[:, None], eng._rec_conv, jnp.nan)
        poisoned = eng.generate(prompts, max_new_tokens=5)
    for a, b in zip(clean, poisoned):
        np.testing.assert_array_equal(a, b)
    assert bool(jnp.isfinite(eng._rec_state[spare]).all())


def test_the_shares_parts_add_up_to_the_uncut_layer():
    """The SHARE test: each of the 4 chips that share a layer routes over all
    16 experts and computes its own 4; the four partial results, with what
    every chip computes alike (the shared expert) counted once, add up to the
    reference's uncut layer over all 16."""
    from deepspeed_tpu.parallel.moe import moe_mlp
    from deepspeed_tpu.parallel.moe.sharded_moe import _moe_tail

    uncut_hf = {**HF, "num_experts": 16, "deployment_share": None, "num_hidden_layers": 4}
    cfg_all, params = _model(uncut_hf)
    lp_all = T.take_layer(params["layers"], cfg_all, 0, lambda a, i: a[i])
    x = jax.random.normal(jax.random.key(7), (1, 24, 64))
    with jax.default_matmul_precision("highest"):
        m = T._norm(x, lp_all["mlp_norm"], None, cfg_all.norm, cfg_all.norm_eps)
        parts = []
        for share in range(4):
            cfg = dataclasses.replace(cfg_all, n_experts=4, moe_experts_total=16, moe_expert_shard=share)
            lp = {k: (v[4 * share: 4 * share + 4] if k in ("w_up", "w_gate", "w_down") else v)
                  for k, v in lp_all.items()}
            out, _, counts = moe_mlp(cfg, lp, m)
            parts.append((out[0], counts))
        shared = _moe_tail(cfg_all, lp_all, m[0], jnp.zeros_like(m[0]))
        total = sum(o - shared for o, _ in parts) + shared
        lp32 = {k: v.astype(jnp.float32) for k, v in lp_all.items()}
        want = ref.experts(x[0], lp32, top_k=3, first=0, eps=1e-6) - x[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=1e-5)
    # every (token, expert) pair is some share's: 24 tokens x 3
    assert sum(int(c.sum()) for _, c in parts) == 24 * 3


def test_state_slots_follow_admit_finish_cancel_and_expiry():
    """One slot a tracked sequence, taken at admission and given back however
    the request ends: finished, cancelled while decoding, expired; no slot
    leaks, the K/V blocks balance, and health() reports both kinds of cache."""
    from deepspeed_tpu.serving import SamplingParams, ServingDriver
    from deepspeed_tpu.serving.request import RequestState

    cfg, params = _model()
    eng = _engine(cfg, params)
    mgr = eng.state_manager
    seq = mgr.get_or_create_sequence(99)
    assert seq.state_slot == 0 and mgr.state_slot_accounting()["live"] == 1
    mgr.flush_sequence(99)
    with ServingDriver(eng) as driver:
        assert driver.health()["state_slots_total"] == 7
        long = SamplingParams(max_new_tokens=400, ignore_eos=True)
        done = driver.submit(np.arange(1, 9, dtype=np.int32),
                             params=SamplingParams(max_new_tokens=4, ignore_eos=True))
        victim = driver.submit(np.arange(11, 31, dtype=np.int32), params=long)
        late = driver.submit(np.arange(41, 51, dtype=np.int32), params=long, timeout_s=0.3)
        victim.stream.get(timeout=60)  # decoding: it holds a slot
        assert driver.health()["state_slots_in_use"] >= 1
        assert driver.cancel(victim.uid)
        assert done.wait(60) and victim.wait(60) and late.wait(60)
        assert (done.state, victim.state) == (RequestState.FINISHED, RequestState.CANCELLED)
        assert late.state == RequestState.TIMED_OUT
        deadline = time.monotonic() + 10
        while mgr.n_tracked_sequences and time.monotonic() < deadline:
            time.sleep(0.01)
        assert driver.health()["state_slots_in_use"] == 0
        c = driver.metrics.counters
        assert c["gdn_decode_rows_total"] > 0
        # the prompts' tokens, through ONE layer's chunk rule (the late one may never be admitted)
        assert 8 + 20 <= c["gdn_chunk_tokens_total"] <= 8 + 20 + 10
        assert c["kda_chunk_tokens_total"] == c["mamba_chunk_tokens_total"] == 0
        assert driver.metrics.gauges["state_slots_in_use"] == 0
        # this chip holds 4 of the layer's experts: fewer pairs than tokens x top-k x layers
        assert 0 < c["moe_routed_rows_total"] < c["scheduled_tokens_total"] * 3 * 8
        assert 0 < c["moe_experts_hit_total"] <= c["moe_layer_calls_total"] * 4
    assert mgr.state_slot_accounting() == {"total": 6, "free": 6, "live": 0}
    acct = mgr.kv_block_accounting()
    assert acct["free"] == acct["total"] == 64 and acct["live"] == 0
    info = eng.kv_pool_info()
    assert info["state_slots"] == 7 and info["state_bytes_per_slot"] == 6 * (4 * 16 * 16 * 4 + 3 * 128 * 4)


def test_pool_bytes_pay_for_state_slots_first():
    """``--kv-pool-bytes`` buys the state slots (one a tracked sequence and a
    spare) first and K/V blocks over the layers that HAVE keys and values
    with the rest: the published widths' arithmetic of the benchmark's cell."""
    import json
    import os

    from deepspeed_tpu.inference.v2 import kv_pool

    here = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    hf = json.load(open(os.path.join(here, "benchmarks", "configs", "qwen3-next-80b-a3b.json")))
    cfg = config_from_hf(hf)
    assert (cfg.n_layers, cfg.kv_layers, cfg.kind_count("gdn")) == (12, 3, 9)
    assert (cfg.n_experts, cfg.router_width, cfg.moe_top_k, cfg.moe_expert_shard) == (128, 512, 10, 0)
    slot = kv_pool.state_slot_bytes(cfg)
    assert slot == 9 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)  # 2.0 MiB + 48 KiB a layer
    per_block = kv_pool.bytes_per_block(128, cfg.kv_heads, cfg.head_dim, cfg.kv_layers)
    assert per_block == 128 * 6 * 1024  # 6 KiB a token
    n = kv_pool.blocks_for_budget(2_000_000_000, 128, cfg.kv_heads, cfg.head_dim, cfg.kv_layers,
                                  state_bytes=33 * slot)
    assert n == (2_000_000_000 - 33 * slot) // per_block - 1 == 1731
    with pytest.raises(ValueError, match="holds no blocks"):
        kv_pool.blocks_for_budget(33 * slot, 128, 2, 256, 3, state_bytes=33 * slot)


def _refusal(match, **kw):
    cfg, params = _model()
    with pytest.raises(NotImplementedError, match=match):
        _engine(cfg, params, **kw)


@pytest.mark.parametrize("what,kw", [
    ("kv_cache_dtype", {"kv_cache": {"kv_cache_dtype": "int8"}}),
    ("host block tier", {"kv_cache": {"prefix_cache": True, "host_tier_bytes": 1 << 20}}),
    ("speculative decoding", {"spec_k": 2}),
    ("quantized weights", {"quant": {"enabled": True, "bits": 8}}),
    ("tp_size=2", {"tp_size": 2}),
])
def test_what_cannot_carry_the_state_is_refused_at_build(what, kw):
    _refusal(what, **kw)


@pytest.mark.parametrize("call", [
    lambda e: e.export_kv_blocks([0]),
    lambda e: e.import_kv_blocks([0], {}),
    lambda e: e.import_kv_blocks_chunked([0], {}),
    lambda e: e.export_kv_blocks_device([0]),
    lambda e: e.export_kv_blocks_windows([0]),
    lambda e: e.import_kv_blocks_device([0], [], 8),
    lambda e: e.spec_round(2),
    lambda e: e._build_verify_step(2),
], ids=["export", "import", "import_chunked", "export_device", "export_windows",
        "import_device", "spec_round", "verify_step"])
def test_movers_of_kv_blocks_raise_rather_than_drop_the_state(call):
    cfg, params = _model()
    eng = _engine(cfg, params)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        call(eng)


def test_prefix_cache_is_switched_off_with_one_log_line():
    cfg, params = _model()
    lines = []

    class Catch(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())

    from deepspeed_tpu.utils.logging import logger

    handler = Catch()
    logger.addHandler(handler)
    try:
        eng = _engine(cfg, params, kv_cache={"prefix_cache": True})
    finally:
        logger.removeHandler(handler)
    assert eng.prefix_cache is None
    assert sum("prefix cache switched off" in m for m in lines) == 1
    # the same prompt twice: the second is prefilled whole, not seeded from a trie
    p = np.arange(1, 41, dtype=np.int32)
    a = eng.generate([p], max_new_tokens=3)[0]
    b = eng.generate([p], max_new_tokens=3)[0]
    np.testing.assert_array_equal(a, b)
