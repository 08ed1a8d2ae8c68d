"""Kimi Linear through the paged engine at a tiny size, seeded weights, on the
CPU: Kimi Delta Attention layers with the state pool BESIDE a latent pool (the
latent layers' planes alone), behind a dense lead layer, with a share of
sigmoid-routed experts. ``_latent`` and ``_hybrid`` are both true in one engine.

The oracle is ``benchmarks/reference/kimi_linear.py`` (plain float32
``jax.numpy``, token by token, no cache): ``forward()`` and prefill in chunks
then decode through both pools must give the reference's full forward pass, on
LOGITS. The faults that the comparison must catch are in
``tests/unit/test_kimi_linear_controls.py`` (a file is one worker's under
``--dist loadfile``: two files keep each under a minute and a half)."""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.kv_pool import bytes_per_block, pool_geometry, state_slot_bytes
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.hf import config_from_hf

ref = importlib.import_module("benchmarks.reference.kimi_linear")

# (kda, latent, kda, kda, latent), layer 1 with the dense MLP: two latent planes
# beside three layers of state slots, few layers because the stack is unrolled
# and every engine below compiles it; share 1 of 2 of 8 experts (numbers 4-7)
HF = dict(
    model_type="kimi_linear", vocab_size=256, hidden_size=128, intermediate_size=192,
    moe_intermediate_size=64, num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=4,
    num_experts=4, num_experts_per_token=2, num_shared_experts=1, first_k_dense_replace=1,
    moe_layer_freq=1, moe_renormalize=True, moe_router_activation_func="sigmoid",
    num_expert_group=1, topk_group=1, use_grouped_topk=True, routed_scaling_factor=2.446,
    kv_lora_rank=64, q_lora_rank=None, qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
    mla_use_nope=True, rms_norm_eps=1e-5, rope_theta=10000, rope_scaling=None,
    tie_word_embeddings=False, hidden_act="silu", head_dim=32, model_max_length=512,
    num_nextn_predict_layers=0,
    linear_attn_config=dict(full_attn_layers=[2, 5], kda_layers=[1, 3, 4], head_dim=32,
                            num_heads=4, short_conv_kernel_size=4),
    deployment_share=dict(num_experts=8, chips_per_layer=2, share_index=1),
)
PROMPT_LENS = (5, 70, 160, 330)
# float32 engine against float32 reference: the order of float32 sums alone
# differs (measured 3e-6 on logits of up to 3.5)
ATOL = 5e-5


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(config_from_hf(HF), dtype="float32", remat=False)
    params = T.init_params(cfg, jax.random.key(0))
    key = jax.random.key(1)

    def move(path, a):  # norm weights off their identity: a norm left out would not show at ones
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


@pytest.fixture(scope="module")
def engine(model):
    """ONE engine for the tests that serve sound weights: a step shape compiles once."""
    return _engine(*model)


def _serve_logits(eng, prompts, n_new, late=(), steps=None):
    """Each prompt's logits at its last prompt token and at ``n_new - 1``
    greedy tokens after it, as the engine's steps return them. The prompts
    numbered in ``late`` are submitted after the first step; ``steps`` collects
    every step's ``StepStats``."""
    for uid, p in enumerate(prompts):
        if uid not in late:
            eng.scheduler.submit(uid, p)
    got = {uid: [] for uid in range(len(prompts))}
    for i in range(60):
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


def _reference_logits(params, prompt, served, hf=HF):
    toks = np.concatenate([prompt, np.argmax(served[:-1], -1).astype(np.int32)])
    return np.asarray(ref.logits(params, toks, hf))[len(prompt) - 1:]


def _worst(eng, params, lens=(70,), seed=0, n_new=3):
    """The largest difference from the reference on logits over ``lens`` (one
    prompt of one chunk by default: two step shapes to compile)."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, 256, size=n).astype(np.int32) for n in lens]
    with jax.default_matmul_precision("highest"):
        served = _serve_logits(eng, prompts, n_new=n_new)
        return max(np.abs(served[uid] - _reference_logits(params, p, served[uid])).max()
                   for uid, p in enumerate(prompts))


@pytest.mark.parametrize("sampling", [{}, {"greedy": False, "temperature": 0.9, "seed": 7}],
                         ids=["greedy", "sampled"])
def test_generate_equals_the_driven_core(model, engine, sampling):
    """``generate()`` is the served step (KDA states beside the latent plane):
    the same prompts through the serving driver, on the same engine once
    ``generate()`` has left it idle, give the same tokens, greedy and sampled."""
    from tests.unit.simple_model import served_tokens

    eng = _engine(*model, **sampling) if sampling else engine
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 256, size=n).astype(np.int32) for n in (5, 70)]
    outs = eng.generate(prompts, max_new_tokens=6)
    driven = served_tokens(eng, prompts, 6)
    for p, out, got in zip(prompts, outs, driven):
        assert [int(t) for t in out[len(p):]] == got
    assert eng.state_manager.state_slot_accounting()["live"] == 0


def test_forward_equals_the_reference(model):
    """``models.forward`` (every layer unrolled out of its own sub-stacks, the
    chunked rule, the expanded latent attention) against the reference's scan."""
    cfg, params = model
    toks = np.random.default_rng(7).integers(1, 256, size=(1, 100)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(T.forward(params, jnp.asarray(toks), cfg)[0])
        for b in range(1):
            # (the chunked rule's sums against the scan's over 100 tokens: measured 7e-5)
            np.testing.assert_allclose(got[b], np.asarray(ref.logits(params, toks[b], HF)), atol=4 * ATOL)


def test_engine_equals_the_reference_on_logits_float32(model, engine):
    """Prefill by chunks (a chunk continued from the slot's state, the latent
    planes at the latent layers' ordinals), then decode through the slots and
    the latent blocks, for more sequences (4) than a step has rows for new ones."""
    cfg, params = model
    assert engine._latent and engine._hybrid
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 256, size=n).astype(np.int32) for n in PROMPT_LENS]
    with jax.default_matmul_precision("highest"):
        served = _serve_logits(engine, prompts, n_new=5)
        for uid, p in enumerate(prompts):
            np.testing.assert_allclose(
                served[uid], _reference_logits(params, p, served[uid]), atol=ATOL, rtol=0)
    assert {k for k in engine._programs if k[0] == "split"} == {
        ("split", shape) for shape in [(0, 0), (1, 128), (1, 160), (2, 160)]}
    assert engine.state_manager.state_slot_accounting() == {"total": 6, "free": 6, "live": 0}
    assert engine.last_step.recurrent_decode_rows >= 0


def test_the_interpreted_kernels_serve_the_same(model, monkeypatch):
    """Every kernel of the served path interpreted in ONE engine:
    ``dstpu_kda_decode`` and ``dstpu_kda_chunk`` (``_rec_impl``) and, under
    ``paged_attention_impl: kernel``, ``dstpu_mla_decode`` / ``dstpu_mla_chunk``
    / the pool write at 4 heads with unrotated shared dims. The second prompt
    arrives after the first step, so the second step's TWO chunk rows are the
    first prompt's tail, continued from its slot's state, and a fresh row (one
    chunk program for both steps); a step's ``recurrent_chunk_tokens`` are the live
    prompt tokens its chunk rows carried."""
    from deepspeed_tpu.ops.linear_attention import delta_chunk

    cfg, params = model
    eng = _engine(cfg, params, paged_attention_impl="kernel",
                  kv_cache={"block_size": 128, "num_blocks": 12, "max_blocks_per_seq": 4})
    eng._rec_impl = "interpret"
    traced, kernel = [], delta_chunk.delta_chunk
    monkeypatch.setattr(delta_chunk, "delta_chunk",
                        lambda q, *a, **kw: traced.append(q.shape[:2]) or kernel(q, *a, **kw))
    rng = np.random.default_rng(8)
    prompts = [rng.integers(1, 256, size=n).astype(np.int32) for n in (200, 40, 30)]
    steps = []
    with jax.default_matmul_precision("highest"):
        served = _serve_logits(eng, prompts, n_new=3, late=(1,), steps=steps)
        for uid, p in enumerate(prompts):
            np.testing.assert_allclose(
                served[uid], _reference_logits(params, p, served[uid]), atol=ATOL, rtol=0)
    # 160 + 30, both fresh | 40 continued + 40 fresh | decode steps
    assert [st.prefill_tokens for st in steps[:3]] == [190, 80, 0]
    assert steps[1].grid_slots == 4 + 2 * 160
    assert traced == [(2, 160)] * cfg.kind_count("kda")              # (chunk rows, tq) a KDA layer
    for st in steps:
        assert st.recurrent_chunk_tokens == st.prefill_tokens


def test_a_reused_slot_poisoned_with_nan_starts_from_zero(model, engine):
    """With every slot of both state pools but the spare filled with NaN, and
    the latent pool with NaN too, a fresh prompt is served as from a clean pool
    (a chunk at position 0 starts from zero whatever its slot holds; a block is
    read below a row's position alone), and the spare slot stays finite."""
    cfg, params = model
    spare = np.arange(cfg.kind_count("kda")) * engine._state_slots + engine._state_slots - 1
    keep = jnp.zeros(engine._rec_state.shape[0], bool).at[spare].set(True)
    engine._rec_state = jnp.where(keep[:, None, None, None], engine._rec_state, jnp.nan)
    engine._rec_conv = jnp.where(keep[:, None], engine._rec_conv, jnp.nan)
    assert _worst(engine, params, lens=(40, 200), seed=3) < ATOL
    assert bool(jnp.isfinite(engine._rec_state[spare]).all())


def test_engine_in_bf16_equals_the_no_cache_forward_in_bf16(model):
    """bf16 weights AND compute against the no-cache ``forward()`` in bf16 on
    the same weights: the state pool stays float32, the conv pool and the latent
    planes bf16. Limit 0.15 on logits of up to 3.5, where a wrong state, slot
    or plane moves logits by halves and more."""
    cfg, params = model
    cfg16 = dataclasses.replace(cfg, dtype="bfloat16")
    p16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    eng = _engine(cfg16, p16, dtype="bfloat16")
    assert eng._rec_state.dtype == jnp.float32 and eng._rec_conv.dtype == jnp.bfloat16
    assert eng._k_cache.dtype == jnp.bfloat16 and eng._v_cache is None
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 256, size=n).astype(np.int32) for n in (5, 40)]
    served = _serve_logits(eng, prompts, n_new=3)
    fwd = jax.jit(lambda p, t: T.forward(p, t, cfg16)[0][0])
    for uid, p in enumerate(prompts):
        toks = np.concatenate([p, np.argmax(served[uid][:-1], -1).astype(np.int32)])
        want = np.asarray(fwd(p16, jnp.asarray(toks)[None]), np.float32)
        np.testing.assert_allclose(served[uid], want[len(p) - 1:], atol=0.15, rtol=0)


def test_both_pools_are_counted_side_by_side(model, engine):
    """At the published widths a slot is 9 x (32 x 128 x 128 float32 + 3 x 12,288
    bf16) = 19,537,920 bytes and a token 3 planes x 576 bf16 = 3,456 bytes; the
    budget pays the slots first, then blocks of the latent layers ALONE."""
    import json
    import os

    from deepspeed_tpu.inference.v2.kv_pool import blocks_for_budget, slot_bytes

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    hf = json.load(open(os.path.join(root, "benchmarks", "configs", "kimi-linear-48b-a3b.json")))
    big = config_from_hf(hf)
    assert big.layer_kinds == ("kda", "kda", "kda", "full") * 3 and big.moe_dense_lead == 1
    assert (big.kv_layers, big.latent_dim, big.n_experts, big.moe_experts_total) == (3, 576, 32, 256)
    assert state_slot_bytes(big) == 9 * (32 * 128 * 128 * 4 + 3 * 12288 * 2) == 19_537_920
    heads, dim, planes = pool_geometry(big)
    per_block = bytes_per_block(128, heads, dim, big.kv_layers, planes=planes)
    assert per_block == 128 * 3456
    n = blocks_for_budget(3_500_000_000, 128, heads, dim, big.kv_layers,
                          state_bytes=33 * slot_bytes(big, 128), planes=planes)
    assert n == (3_500_000_000 - 33 * 19_537_920) // per_block - 1 == 6453
    cfg, params = model
    info = engine.kv_pool_info()
    assert info["state_kind"] == "kda" and info["state_slots"] == 7
    assert info["state_bytes_per_slot"] == state_slot_bytes(cfg, 4) == 3 * (4 * 32 * 32 * 4 + 3 * 384 * 4)
    assert info["kv_pool_geometry"] == {"layers": 2, "kv_heads": 1, "plane_widths": [80]}
    assert engine._k_cache.shape == (2, 65, 80, 16)
    assert engine._rec_state.shape == (3 * 7, 4, 32, 32) and engine._rec_conv.shape == (3 * 7, 3 * 384)


def test_what_a_recurrent_and_what_a_latent_model_are_refused_this_one_is_too(model):
    """Everything refused for a recurrent model, with its words, and everything
    refused for a latent model; the prefix cache goes off with its log line."""
    cfg, params = model
    for extra in ({"spec_k": 2}, {"kv_cache": {"kv_cache_dtype": "int8"}},
                  {"kv_cache": {"host_tier_bytes": 1 << 20}}):
        with pytest.raises(NotImplementedError, match="Kimi Delta Attention layers keep a recurrent"):
            _engine(cfg, params, **extra)
    eng = _engine(cfg, params, kv_cache={"prefix_cache": True})
    assert eng.state_manager.prefix_cache is None   # switched off, with its log line
    with pytest.raises(NotImplementedError, match="Kimi Delta Attention layers keep"):
        eng.export_kv_blocks([0])
    with pytest.raises(NotImplementedError, match="Kimi Delta Attention layers keep"):
        eng.spec_round(2)


def test_the_engine_has_no_branch_on_the_kinds_name():
    """``"kda"`` is an entry of ``RECURRENT``; neither the engine's source nor
    the serving core's and the metrics' (a kind's counters are named from the
    table) names it (nor its two wide projections' keys but in the list of
    stacks read in place)."""
    import inspect

    from deepspeed_tpu.inference.v2 import engine_v2
    from deepspeed_tpu.serving import metrics
    from deepspeed_tpu.serving.cluster import core

    assert "kda" in T.RECURRENT
    for module in (engine_v2, core, metrics):
        src = inspect.getsource(module)
        assert '"kda"' not in src and "'kda'" not in src and "kda_decode_rows" not in src


# --- one share test: the shares add up to the uncut layer ---------------------
def test_eight_shares_expert_sums_add_up_to_the_uncut_layer():
    """An expert layer's block over ALL experts equals the sum over the 8
    shares of each share's partial sum, with the shared expert counted once:
    in the reference and in the program's ``moe_mlp`` alike."""
    from deepspeed_tpu.parallel.moe import moe_mlp

    hf = {**HF, "num_experts": 16, "deployment_share": None}
    whole = dataclasses.replace(config_from_hf(hf), dtype="float32", remat=False)
    params = T.init_params(whole, jax.random.key(4))
    moe = params["layers"]["sparse"]
    x = jax.random.normal(jax.random.key(5), (1, 24, 128), jnp.float32)
    kw = dict(top_k=2, scale=2.446)
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a[2], moe)     # the third expert layer's block
        full_ref = ref.sparse_mlp(x[0], moe, 2, first=0, **kw)
        full_sys = moe_mlp(whole, lp, x)[0][0]
        np.testing.assert_allclose(np.asarray(full_sys), np.asarray(full_ref), atol=ATOL)
        shared = ref.swiglu(x[0], lp["shared_gate"], lp["shared_up"], lp["shared_down"])
        sums = {"ref": -7 * shared, "sys": -7 * shared}     # the shared expert counted once
        for s in range(8):
            share = dataclasses.replace(whole, n_experts=2, moe_experts_total=16, moe_expert_shard=s)
            held = {k: (v[:, 2 * s: 2 * s + 2] if k in ("w_up", "w_gate", "w_down") else v)
                    for k, v in moe.items()}
            sums["ref"] = sums["ref"] + ref.sparse_mlp(x[0], held, 2, first=2 * s, **kw)
            sums["sys"] = sums["sys"] + moe_mlp(share, jax.tree.map(lambda a: a[2], held), x)[0][0]
        for got in sums.values():
            np.testing.assert_allclose(np.asarray(got), np.asarray(full_ref), atol=ATOL)
