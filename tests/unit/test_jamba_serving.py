"""Jamba (the dense form) through the paged engine at a tiny size, seeded
weights, on the CPU: Mamba layers with the state pool beside the K/V pool, and
attention layers of 4 query heads on ONE key head without positions.

The oracle is ``benchmarks/reference/jamba.py`` (plain float32 ``jax.numpy``,
token by token, no cache), itself held to ``transformers``' ``JambaForCausalLM``
by ``benchmarks/tests/test_reference_jamba.py``: prefill in chunks and then
decode through both pools must give the reference's full forward pass, on
LOGITS. The controls at the end are faults that the comparison must catch."""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.kv_pool import state_slot_bytes
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.hf import config_from_hf

ref = importlib.import_module("benchmarks.reference.jamba")

# two periods of (mamba, mamba, attention, mamba); 256 channels: two rows of
# 128 lanes, so the kernels run their real layout
HF = dict(
    model_type="jamba", vocab_size=256, hidden_size=128, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=1, intermediate_size=192,
    attn_layer_period=4, attn_layer_offset=2, num_experts=1, num_experts_per_tok=1,
    mamba_d_state=16, mamba_dt_rank=8, mamba_expand=2, mamba_d_conv=4,
    mamba_conv_bias=True, mamba_proj_bias=False, rms_norm_eps=1e-6,
    tie_word_embeddings=True, max_position_embeddings=512,
)
# prompts that make every shape of the split step: a short one (the 128
# bucket), one of a single prompt_chunk, one of three chunks with a short tail
PROMPT_LENS = (5, 70, 160, 330)
# float32 engine against float32 reference: the order of float32 sums alone
# differs (measured 2e-6 on logits of scale 0.35, as the seeded head gives them)
ATOL = 5e-5


def _model(hf=HF, dtype="float32", seed=0):
    cfg = dataclasses.replace(config_from_hf(hf), dtype=dtype, remat=False)
    params = T.init_params(cfg, jax.random.key(seed))
    # norm weights off their identity: a norm left out would not show at ones
    key = jax.random.key(seed + 1)

    def move(path, a):
        name = str(path[-1])
        if "norm" not in name:
            return a
        return a + (0.2 * jax.random.normal(jax.random.fold_in(key, sum(map(ord, name))), a.shape)).astype(a.dtype)

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


def _serve_logits(eng, prompts, n_new):
    """Each prompt's logits at its last prompt token and at ``n_new - 1``
    greedy tokens after it, as the engine's steps return them."""
    for uid, p in enumerate(prompts):
        eng.scheduler.submit(uid, p)
    got = {uid: [] for uid in range(len(prompts))}
    for _ in range(60):
        for uid, lg in eng.step().items():
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


def _worst(eng, params, lens=(70, 330), seed=0, hf=HF):
    """The largest difference from the reference on logits over ``lens``."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, 256, size=n).astype(np.int32) for n in lens]
    with jax.default_matmul_precision("highest"):
        served = _serve_logits(eng, prompts, n_new=5)
        return max(np.abs(served[uid] - _reference_logits(params, hf, p, served[uid])).max()
                   for uid, p in enumerate(prompts))


@pytest.mark.parametrize("impl", ["jnp", "interpret"])
def test_engine_equals_the_reference_on_logits_float32(impl):
    """float32 weights and compute: prefill by chunks (a chunk continued from
    the slot's state), then decode through the slots and the K/V blocks, for
    more sequences (4) than a step has rows for new ones, so that slots are
    taken, finished and handed on inside one run. ``interpret`` runs the Pallas
    kernels ``dstpu_mamba_scan`` and ``dstpu_mamba_decode`` themselves."""
    cfg, params = _model()
    eng = _engine(cfg, params)
    eng._rec_impl = impl
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 256, size=n).astype(np.int32) for n in PROMPT_LENS]
    with jax.default_matmul_precision("highest"):
        served = _serve_logits(eng, prompts, n_new=5)
        assert set(eng._programs) == {
            ("split", shape) for shape in [(0, 0), (1, 128), (1, 160), (2, 160)]}
        for uid, p in enumerate(prompts):
            want = _reference_logits(params, HF, p, served[uid])
            np.testing.assert_allclose(served[uid], want, atol=ATOL, rtol=0)
    assert eng.state_manager.state_slot_accounting() == {"total": 6, "free": 6, "live": 0}
    assert eng.last_step.recurrent_decode_rows >= 0


def test_a_long_run_of_mamba_layers_is_one_looped_body():
    """Two periods of (five Mamba layers, attention, Mamba): a run of
    ``RUN_LOOP`` layers of one kind is a ``fori_loop`` in the step programs,
    the layer, its ordinal in its kind's stack and its slots all traced, and
    serves what the unrolled stack would: the reference's logits."""
    from deepspeed_tpu.inference.v2 import engine_v2

    assert engine_v2.RUN_LOOP == 5
    hf = {**HF, "num_hidden_layers": 14, "attn_layer_period": 7, "attn_layer_offset": 5}
    cfg, params = _model(hf)
    assert cfg.layer_kinds == (("mamba",) * 5 + ("full", "mamba")) * 2
    eng = _engine(cfg, params)
    assert _worst(eng, params, lens=(40, 200), seed=6, hf=hf) < ATOL


def test_slots_handed_on_serve_later_sequences_from_zero():
    """Two waves through a pool of TWO slots: the second wave's sequences take
    the slots the first wave's gave back, states and conv inputs still in
    them, and are served as from a clean pool (a chunk at position 0 starts
    from zero whatever its slot holds)."""
    cfg, params = _model()
    eng = _engine(cfg, params, state_manager={"max_tracked_sequences": 2})
    rng = np.random.default_rng(5)
    with jax.default_matmul_precision("highest"):
        for lens in ((70, 200), (40, 170)):
            prompts = [rng.integers(1, 256, size=n).astype(np.int32) for n in lens]
            served = _serve_logits(eng, prompts, n_new=4)
            for uid, p in enumerate(prompts):
                np.testing.assert_allclose(
                    served[uid], _reference_logits(params, HF, p, served[uid]), atol=ATOL, rtol=0)
            assert float(jnp.abs(eng._rec_state).max()) > 0  # the slots are NOT clean
    assert eng.state_manager.state_slot_accounting()["live"] == 0


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
            np.testing.assert_allclose(chosen, lg.max(-1), atol=ATOL)


def test_a_reused_slot_poisoned_with_nan_starts_from_zero():
    """With every slot of both state pools but the spare filled with NaN, a
    fresh prompt is served as from a clean pool, and the spare slot the grid's
    padding points at stays finite."""
    cfg, params = _model()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 256, size=n).astype(np.int32) for n in (40, 200)]
    with jax.default_matmul_precision("highest"):
        clean = _engine(cfg, params).generate(prompts, max_new_tokens=5)
        eng = _engine(cfg, params)
        spare = np.arange(cfg.kind_count("mamba")) * eng._state_slots + eng._state_slots - 1
        keep = jnp.zeros(eng._rec_state.shape[0], bool).at[spare].set(True)
        eng._rec_state = jnp.where(keep[:, None, None, None], eng._rec_state, jnp.nan)
        eng._rec_conv = jnp.where(keep[:, None], eng._rec_conv, jnp.nan)
        poisoned = eng.generate(prompts, max_new_tokens=5)
    for a, b in zip(clean, poisoned):
        np.testing.assert_array_equal(a, b)
    assert bool(jnp.isfinite(eng._rec_state[spare]).all())


def test_engine_in_bf16_equals_the_no_cache_forward_in_bf16():
    """bf16 weights AND compute: the bf16 engine is held to the no-cache
    ``forward()`` in bf16 on the same weights: prefill in chunks, decode
    through the state pool (float32) and the conv pool (bf16) and paged
    attention round at other places than one dense pass does, and nothing else
    may differ. Measured 0.09 on logits of up to 3.5 over two periods of
    layers (the oracle's own logits are bf16: 0.016 apart there); limit 0.15,
    where a wrong state, slot or conv input moves logits by halves and more."""
    cfg, params = _model(dtype="bfloat16")
    eng = _engine(cfg, params, dtype="bfloat16")
    assert eng._rec_state.dtype == jnp.float32 and eng._rec_conv.dtype == jnp.bfloat16
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 256, size=n).astype(np.int32) for n in (5, 200)]
    served = _serve_logits(eng, prompts, n_new=6)
    for uid, p in enumerate(prompts):
        toks = np.concatenate([p, np.argmax(served[uid][:-1], -1).astype(np.int32)])
        want = np.asarray(T.forward(params, jnp.asarray(toks)[None], cfg)[0][0], np.float32)
        np.testing.assert_allclose(served[uid], want[len(p) - 1:], atol=0.15, rtol=0)


def test_the_slot_and_the_report_count_the_mamba_state():
    """The slot's bytes follow from the kind's description: at the published
    widths 26 layers x (16 x 5,120 float32 + 3 x 5,120 bf16) = 9.32 MB, and a
    token's K/V over the 2 attention layers is 1 KiB; the engine's report names
    the kind, the slots and their bytes."""
    import json
    import os

    from deepspeed_tpu.inference.v2.kv_pool import bytes_per_block, pool_geometry

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    hf = json.load(open(os.path.join(root, "benchmarks", "configs", "jamba2-3b.json")))
    big = config_from_hf(hf)
    assert big.layer_kinds.count("mamba") == 26 and big.layer_kinds.index("full") == 7
    assert state_slot_bytes(big) == 26 * (16 * 5120 * 4 + 3 * 5120 * 2) == 9_318_400
    heads, dim, planes = pool_geometry(big)
    assert bytes_per_block(128, heads, dim, big.kv_layers, planes=planes) == 128 * 1024
    cfg, params = _model()
    eng = _engine(cfg, params)
    info = eng.kv_pool_info()
    assert info["state_kind"] == "mamba" and info["state_slots"] == 7
    assert info["state_bytes_per_slot"] == state_slot_bytes(cfg, 4) == 6 * (16 * 256 * 4 + 3 * 256 * 4)
    assert info["state_pool_bytes"] == 7 * info["state_bytes_per_slot"]


def test_what_a_deltanet_model_is_refused_this_one_is_too():
    """The refusals speak of this kind's state and refuse the same things."""
    cfg, params = _model()
    for extra in ({"spec_k": 2}, {"kv_cache": {"kv_cache_dtype": "int8"}},
                  {"kv_cache": {"host_tier_bytes": 1 << 20}}):
        with pytest.raises(NotImplementedError, match="Mamba layers keep a selective state-space"):
            _engine(cfg, params, **extra)
    eng = _engine(cfg, params, kv_cache={"prefix_cache": True})
    assert eng.state_manager.prefix_cache is None   # switched off, with its log line
    with pytest.raises(NotImplementedError, match="Mamba layers keep"):
        eng.export_kv_blocks([0])
    with pytest.raises(NotImplementedError, match="Mamba layers keep"):
        eng.spec_round(2)


# --- controls: faults the comparison has to catch ----------------------------
def test_control_a_bf16_state_pool_fails():
    """The state kept in bf16 (the precision below what the configuration
    states) misses the reference by fifty times the limit (measured 2.7e-3
    against 5e-5; the sound engine 2e-6)."""
    cfg, params = _model()
    eng = _engine(cfg, params)
    eng._rec_state = eng._rec_state.astype(jnp.bfloat16)
    assert _worst(eng, params) > 20 * ATOL


def _without(params, key, value):
    """The tree with a Mamba layer's ``key`` set to ``value`` everywhere."""
    m = dict(params["layers"]["mamba"])
    m[key] = jnp.full_like(m[key], value)
    return {**params, "layers": {**params["layers"], "mamba": m}}


@pytest.mark.parametrize("key,value", [
    ("mamba_conv_b", 0.0),      # the conv's bias left out
    ("mamba_d", 0.0),           # the D term left out
    ("mamba_dt_norm", 1.0),     # a norm's weight read as 1 (the norm left half out)
])
def test_control_a_term_left_out_of_the_served_model_fails(key, value):
    """The engine serves a tree without the term; the reference has it
    (measured on logits of scale 0.35: 1.5 without the conv's bias, 1.6
    without D, 0.27 with the dt norm's weight read as 1; limit 5e-5)."""
    cfg, params = _model()
    assert _worst(_engine(cfg, _without(params, key, value)), params) > 200 * ATOL


def test_control_the_norms_on_dt_b_and_c_left_out_fail(monkeypatch):
    """Plain Mamba-1 (no norm on dt, B and C) in the served program:
    measured 1.0 on logits of scale 0.35."""
    cfg, params = _model()
    norm = T._norm
    monkeypatch.setattr(T, "_norm", lambda x, w, b, kind, eps: (
        x if x.shape[-1] in (HF["mamba_dt_rank"], HF["mamba_d_state"]) else norm(x, w, b, kind, eps)))
    assert _worst(_engine(cfg, params), params) > 200 * ATOL


def test_control_rotary_in_the_attention_layers_fails():
    """Jamba's attention has no position term: the same engine with rotary
    applied (what every other decoder here has) misses the reference
    (measured 1.1 on logits of scale 0.35)."""
    cfg, params = _model()
    eng = _engine(dataclasses.replace(cfg, position="rope"), params)
    assert _worst(eng, params) > 200 * ATOL


def test_control_a_state_lost_between_two_steps_fails():
    """With every slot's state zeroed after the prompt, as a wrong slot or a
    lost hand-over from the chunked scan to the one-token update would leave
    it, the next token's logits miss the reference; the token before agrees."""
    cfg, params = _model()
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
    np.testing.assert_allclose(first, want[0], atol=ATOL, rtol=0)
    assert np.abs(second - want[1]).max() > 200 * ATOL
