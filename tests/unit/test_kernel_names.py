"""Every Mosaic kernel of the main path carries a stable name: the constant in
the kernel's file is the ``kernel_name`` of the custom call it lowers to, which
is what a device trace shows (``%dstpu_paged_decode.1 = ... custom-call``) and
what the benchmark's kernel-share readers look for. Lowering only: the Mosaic
lowering for the TPU platform needs no chip and compiles nothing.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import fused_ce
from deepspeed_tpu.ops.adam import fused_adam
from deepspeed_tpu.ops.attention import flash_pallas, paged_pallas
from deepspeed_tpu.ops.normalization import fused_norm


def _tpu_text(fn, *shapes):
    """StableHLO of ``fn`` lowered for the TPU platform (from the CPU)."""
    return jax.jit(fn).trace(*shapes).lower(lowering_platforms=("tpu",)).as_text()


def _kernel_names(text):
    return {part.split('"')[1] for part in text.split("kernel_name = ")[1:]}


def _s(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("s, backward", [
    (256, {flash_pallas.FLASH_BWD_FUSED}),
    # a head's dq accumulator above DQ_RESIDENT_BYTES: the two streaming kernels
    (8192, {flash_pallas.FLASH_BWD_DQ, flash_pallas.FLASH_BWD_DKV}),
], ids=["dq_resident", "above_the_budget"])
def test_flash_forward_and_backward_are_named(s, backward):
    q, kv = _s((1, 4, s, 128)), _s((1, 2, s, 128))

    def loss(q, k, v):
        return flash_pallas.flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    fwd = _kernel_names(_tpu_text(lambda q, k, v: flash_pallas.flash_attention(q, k, v), q, kv, kv))
    assert fwd == {flash_pallas.FLASH_FWD}
    both = _kernel_names(_tpu_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv))
    assert both == {flash_pallas.FLASH_FWD} | backward
    # the readers tell forward from backward by these prefixes
    assert flash_pallas.FLASH_FWD.startswith("dstpu_flash_fwd")
    assert all(n.startswith("dstpu_flash_bwd")
               for n in (flash_pallas.FLASH_BWD_DQ, flash_pallas.FLASH_BWD_DKV,
                         flash_pallas.FLASH_BWD_FUSED))


def test_flash_ring_chunks_are_named():
    q, kv = _s((1, 4, 256, 128)), _s((1, 2, 256, 128))
    f32, lanes = jnp.float32, flash_pallas.LANES
    carry = (_s((1, 4, 256, 128), f32), _s((1, 4, 256, lanes), f32), _s((1, 4, 256, lanes), f32))
    names = _kernel_names(_tpu_text(
        lambda q, k, v, c: flash_pallas.flash_fwd_chunk(q, k, v, c, causal=False), q, kv, kv, carry))
    assert names == {flash_pallas.FLASH_FWD_CHUNK}


def test_paged_decode_is_named(monkeypatch):
    # the kernel asks on_tpu() whether to interpret itself; lowering for the
    # TPU from here, the test answers for it
    monkeypatch.setattr(paged_pallas, "on_tpu", lambda: True)
    T, nh, nkv, d, NB, bs, B = 8, 16, 8, 128, 12, 128, 4
    names = _kernel_names(_tpu_text(
        lambda q, k, v, bt, pos: paged_pallas.paged_attention(q, k, v, bt, pos, NB, impl="kernel"),
        _s((T, nh, d)), _s((NB + 1, bs, nkv, d)), _s((NB + 1, bs, nkv, d)),
        _s((T, B), jnp.int32), _s((T,), jnp.int32)))
    assert names == {paged_pallas.PAGED_DECODE} == {"dstpu_paged_decode"}


def test_rmsnorm_is_named(monkeypatch):
    monkeypatch.setattr(fused_norm, "on_tpu", lambda: True)

    def loss(x, w):
        return fused_norm.fused_rms_norm(x, w).astype(jnp.float32).sum()

    names = _kernel_names(_tpu_text(jax.value_and_grad(loss), _s((256, 1024)), _s((1024,))))
    assert names == {fused_norm.RMSNORM_FWD, fused_norm.RMSNORM_BWD}


def test_fused_ce_is_named():
    def loss(x, w, y):
        return fused_ce.fused_ce_loss(x, w, y).sum()

    names = _kernel_names(_tpu_text(
        jax.grad(loss, argnums=(0, 1)), _s((256, 256)), _s((256, 2048)), _s((256,), jnp.int32)))
    assert names == {fused_ce.CE_FWD, fused_ce.CE_BWD_DX, fused_ce.CE_BWD_DW}


def test_fused_adam_is_named():
    p = _s((8 * 2048,), jnp.float32)
    names = _kernel_names(_tpu_text(
        lambda p, g, m, v: fused_adam.fused_adam_step(p, g, m, v, 1), p, p, p, p))
    assert names == {fused_adam.FUSED_ADAM}


@pytest.mark.parametrize("module, constants", [
    (flash_pallas, ("FLASH_FWD", "FLASH_BWD_DQ", "FLASH_BWD_DKV", "FLASH_BWD_FUSED",
                    "FLASH_FWD_CHUNK", "FLASH_BWD_DQ_CHUNK", "FLASH_BWD_DKV_CHUNK")),
    (paged_pallas, ("PAGED_DECODE",)),
])
def test_names_are_distinct_trace_safe_identifiers(module, constants):
    names = [getattr(module, c) for c in constants]
    assert len(set(names)) == len(names)
    for n in names:
        # no dot or space: benchmarks/harness/xplane.py short_name() keeps the
        # instruction's name up to its numeric suffix
        assert n.startswith("dstpu_") and n.replace("_", "").isalnum()


def test_gdn_decode_is_named_and_updates_the_pool_in_place():
    """The one-token state update at Qwen3-Next's widths: the custom call
    carries the name the benchmark's readers look for, and the state pool is
    aliased to its output (one read and one write of a row's state)."""
    from deepspeed_tpu.ops.linear_attention import gated_delta

    R, nk, nv, dk, dv, slots = 32, 16, 32, 128, 128, 9 * 33
    f32 = jnp.float32
    text = _tpu_text(
        lambda q, k, v, g, b, pool, s: gated_delta.gdn_decode(q, k, v, g, b, pool, s, impl="kernel"),
        _s((R, nk, dk), f32), _s((R, nk, dk), f32), _s((R, nv, dv), f32), _s((R, nv), f32),
        _s((R, nv), f32), _s((slots, nv, dk, dv), f32), _s((R,), jnp.int32))
    assert _kernel_names(text) == {gated_delta.GDN_DECODE} == {"dstpu_gdn_decode"}
    assert "output_operand_aliases" in text or "operand_index = 6" in text


def test_kda_decode_is_named_apart_from_gdn_decode():
    """The same kernel body with a decay a key channel (``g [R, H, dk]``) at Kimi
    Linear's widths: its custom call carries a name of its own, which a trace
    tells from Gated DeltaNet's, and the state pool is aliased to its output."""
    from deepspeed_tpu.ops.linear_attention import gated_delta, kda

    R, H, d, slots = 32, 32, 128, 9 * 33
    f32 = jnp.float32
    text = _tpu_text(
        lambda q, k, v, g, b, pool, s: kda.kda_decode(q, k, v, g, b, pool, s, impl="kernel"),
        _s((R, H, d), f32), _s((R, H, d), f32), _s((R, H, d), f32), _s((R, H, d), f32),
        _s((R, H), f32), _s((slots, H, d, d), f32), _s((R,), jnp.int32))
    assert _kernel_names(text) == {gated_delta.KDA_DECODE} == {"dstpu_kda_decode"}
    assert gated_delta.KDA_DECODE != gated_delta.GDN_DECODE
    assert "output_operand_aliases" in text or "operand_index = 6" in text


@pytest.mark.parametrize("rule, name", [("kda", "dstpu_kda_chunk"), ("gdn", "dstpu_gdn_chunk")])
def test_the_chunk_rules_are_named_apart(rule, name):
    """A prompt chunk's delta rule at the published widths (Kimi Linear: 32
    heads; Qwen3-Next: 16 key heads serving 32 value heads), two rows of 512
    tokens: ONE kernel body under two names, by the decay's shape, so that a
    trace tells the two models apart; no XLA body beside it."""
    from deepspeed_tpu.ops import linear_attention as L

    r, t, nv, d, f32 = 2, 512, 32, 128, jnp.float32
    nk, fn, g = (nv, L.kda_chunked, (r, t, nv, d)) if rule == "kda" else (16, L.gdn_chunked, (r, t, nv))
    text = _tpu_text(
        lambda q, k, v, g, b, S: fn(q, k, v, g, b, S, impl="kernel"),
        _s((r, t, nk, d), f32), _s((r, t, nk, d), f32), _s((r, t, nv, d), f32), _s(g, f32),
        _s((r, t, nv), f32), _s((r, nv, d, d), f32))
    assert _kernel_names(text) == {getattr(L, f"{rule.upper()}_CHUNK")} == {name}
    assert "while" not in text  # the scan over chunks went into the kernel's grid
    assert len({L.KDA_CHUNK, L.GDN_CHUNK, L.KDA_DECODE, L.GDN_DECODE}) == 4


@pytest.mark.parametrize("kind", ["kda", "gdn"])
def test_forward_of_a_delta_stack_differentiates_with_impl_left_alone(kind, monkeypatch):
    """Training goes through ``models.forward``, whose cacheless recurrent block
    names the XLA body for the two delta rules: ``jax.grad`` of a toy stack is
    finite even where the platform would pick the kernel (a ``pallas_call`` has
    no gradient), and no kernel is in the lowered program."""
    import dataclasses

    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.models.hf import config_from_hf
    from deepspeed_tpu.ops.linear_attention import gated_delta, kda

    # the serving tests' toys, cut to one recurrent layer and one attention layer / one period
    if kind == "kda":
        hf = importlib.import_module("tests.unit.test_kimi_linear_serving").HF
        hf = {**hf, "num_hidden_layers": 2,
              "linear_attn_config": {**hf["linear_attn_config"], "kda_layers": [1], "full_attn_layers": [2]}}
    else:
        hf = {**importlib.import_module("tests.unit.test_qwen3_next_serving").HF, "num_hidden_layers": 4}
    cfg = dataclasses.replace(config_from_hf(hf), dtype="float32", remat=False)
    assert cfg.recurrent_kind == kind
    params = T.init_params(cfg, jax.random.key(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(1, 256, size=(1, 70)), jnp.int32)
    # as on a TPU: a rule left to pick would pick its kernel
    monkeypatch.setattr(gated_delta, "on_tpu", lambda: True)
    monkeypatch.setattr(kda, "on_tpu", lambda: True)

    def loss(p):
        return jnp.mean(T.forward(p, toks, cfg)[0].astype(jnp.float32) ** 2)

    grads = jax.jit(jax.grad(loss))(params)
    leaves = jax.tree.leaves(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in leaves)
    assert any(float(jnp.abs(g).max()) > 0 for g in leaves)


def test_the_state_space_kernels_are_named_and_the_pool_is_updated_in_place():
    """The chunked scan and the one-token update at Jamba2-3B's widths: the
    custom calls carry the names the benchmark's readers look for, and the
    decode kernel's state pool is aliased to its output."""
    from deepspeed_tpu.ops.state_space import mamba

    d, n, R, slots = 5120, 16, 32, 26 * 33
    f32 = jnp.float32
    row = (_s((R, d), f32), _s((R, d), f32), _s((R, n), f32), _s((R, n), f32), _s((R, d), f32))
    text = _tpu_text(
        lambda u, dt, B, C, z, A, D, pool, s: mamba.mamba_decode(u, dt, B, C, z, A, D, pool, s, impl="kernel"),
        *row, _s((n, d), f32), _s((d,), f32), _s((slots,) + mamba.state_shape(d, n), f32),
        _s((R,), jnp.int32))
    assert _kernel_names(text) == {mamba.MAMBA_DECODE} == {"dstpu_mamba_decode"}
    assert "output_operand_aliases" in text or "operand_index = 8" in text
    chunk = (_s((2, 512, d), f32), _s((2, 512, d), f32), _s((2, 512, n), f32), _s((2, 512, n), f32),
             _s((2, 512, d), f32))
    text = _tpu_text(
        lambda u, dt, B, C, z, A, D, S: mamba.mamba_scan(u, dt, B, C, z, A, D, S, impl="kernel"),
        *chunk, _s((n, d), f32), _s((d,), f32), _s((2,) + mamba.state_shape(d, n), f32))
    assert _kernel_names(text) == {mamba.MAMBA_SCAN} == {"dstpu_mamba_scan"}
    # never the [tokens, d, N] tensor of the published loop
    assert f"{512}x{d}x{n}" not in text and f"{d}x{n}x512" not in text
