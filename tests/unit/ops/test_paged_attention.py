"""Paged block-table attention kernel numerics (interpret mode on CPU) and
the batched engine step's call count (analogue of reference
tests/unit/inference/v2 ragged_ops kernel tests; the batched step's tokens
are held to the no-cache reference in test_inference.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.attention.paged_pallas import (
    paged_attention,
    paged_attention_reference,
)


@pytest.mark.parametrize("nh,nkv", [(8, 8), (8, 4), (4, 1)])
def test_paged_kernel_matches_reference(nh, nkv):
    rng = np.random.default_rng(0)
    T, d, bs, NB, B = 8, 64, 16, 12, 3
    trash = NB - 1
    q = jnp.asarray(rng.normal(size=(T, nh, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(NB, bs, nkv, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(NB, bs, nkv, d)), jnp.float32)
    bt = np.full((T, B), trash, np.int32)
    bt[0:4] = [0, 1, 2]  # seq A: 3 blocks
    bt[4:7] = [3, 4, trash]  # seq B: 2 blocks
    qpos = np.array([5, 20, 33, 40, 3, 10, 17, 0], np.int32)
    ref = paged_attention_reference(q, kc, vc, jnp.asarray(bt), jnp.asarray(qpos), trash)
    out = paged_attention(
        q, kc, vc, jnp.asarray(bt), jnp.asarray(qpos), trash, impl="kernel", interpret=True
    )
    # full batch including row 7 (all-trash padding token): both impls emit 0
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(out[7]), 0.0, atol=1e-6)


def test_paged_kernel_window():
    """Static sliding-window band in the paged kernel: the windowed kernel
    must match a hand-banded dense softmax, and differ from the unwindowed
    kernel for tokens deeper than the window."""
    rng = np.random.default_rng(2)
    T, nh, nkv, d, bs, NB, B = 4, 4, 2, 64, 16, 8, 3
    trash = NB - 1
    window = 12
    q = jnp.asarray(rng.normal(size=(T, nh, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(NB, bs, nkv, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(NB, bs, nkv, d)), jnp.float32)
    bt = np.full((T, B), trash, np.int32)
    bt[:] = [0, 1, 2]
    qpos = np.array([5, 20, 33, 40], np.int32)
    ref = paged_attention_reference(
        q, kc, vc, jnp.asarray(bt), jnp.asarray(qpos), trash, window=window
    )
    out = paged_attention(
        q, kc, vc, jnp.asarray(bt), jnp.asarray(qpos), trash,
        impl="kernel", interpret=True, window=window,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # tokens past the window must see a different (banded) context
    full = paged_attention(
        q, kc, vc, jnp.asarray(bt), jnp.asarray(qpos), trash,
        impl="kernel", interpret=True,
    )
    assert np.abs(np.asarray(out[1:]) - np.asarray(full[1:])).max() > 1e-3
    # inside the window (qpos 5 < 12) nothing changes
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(full[0]), atol=1e-6)


def test_paged_kernel_bf16():
    rng = np.random.default_rng(1)
    T, nh, nkv, d, bs, NB, B = 4, 4, 2, 128, 32, 8, 2
    trash = NB - 1
    q = jnp.asarray(rng.normal(size=(T, nh, d)), jnp.bfloat16)
    kc = jnp.asarray(rng.normal(size=(NB, bs, nkv, d)), jnp.bfloat16)
    vc = jnp.asarray(rng.normal(size=(NB, bs, nkv, d)), jnp.bfloat16)
    bt = np.tile(np.array([[0, 1]], np.int32), (T, 1))
    qpos = np.array([0, 17, 40, 63], np.int32)
    ref = paged_attention_reference(q, kc, vc, jnp.asarray(bt), jnp.asarray(qpos), trash)
    out = paged_attention(
        q, kc, vc, jnp.asarray(bt), jnp.asarray(qpos), trash, impl="kernel", interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )


# ---------------------------------------------------------------------------
# engine: one device call a step
# ---------------------------------------------------------------------------
def _make_engine(seed=0):
    from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import TransformerConfig, init_params

    mc = TransformerConfig(
        vocab_size=128, hidden_size=64, n_layers=2, n_heads=4, n_kv_heads=2,
        max_seq_len=256, dtype="float32",
    )
    params = init_params(mc, jax.random.key(seed))
    cfg = RaggedInferenceEngineConfig()
    cfg.dtype = "float32"
    cfg.kv_cache.block_size = 16
    cfg.kv_cache.num_blocks = 64
    cfg.kv_cache.max_blocks_per_seq = 8
    return InferenceEngineV2(mc, params, cfg), mc


class _CountingJit:
    def __init__(self, fn):
        self.fn = fn
        self.calls = 0

    def __call__(self, *a, **kw):
        self.calls += 1
        return self.fn(*a, **kw)


def test_batched_step_is_one_device_call():
    """Multi-sequence decode must be ONE device call per engine step,
    however many sequences it carries (call count, not wall clock, so CI
    noise cannot flake it): at n_seq=8 a call a sequence would be 8 a step."""
    n_seq, steps = 8, 6
    prompts = [np.arange(1 + i, 9 + i, dtype=np.int32) for i in range(n_seq)]

    eng_a, _ = _make_engine()
    split_counters = {}
    orig_split = eng_a._build_split_step

    def counting_split(tq):
        c = _CountingJit(orig_split(tq))
        split_counters[tq] = c
        return c

    eng_a._build_split_step = counting_split
    eng_a.generate([p.copy() for p in prompts], max_new_tokens=steps)
    batched_calls = sum(c.calls for c in split_counters.values())
    assert 0 < batched_calls <= steps + n_seq + 2, batched_calls


# ---------------------------------------------------------------------------
# XLA-dense decode / chunk attention (the serving hot paths)
# ---------------------------------------------------------------------------
from deepspeed_tpu.ops.attention.paged_pallas import (
    paged_chunk_attention,
    paged_decode_attention_dense,
)


@pytest.mark.parametrize("kw", [{}, {"window": 12}, {"scale": 1.0}])
def test_decode_dense_matches_reference(kw):
    rng = np.random.default_rng(6)
    R, nh, nkv, d, bs, NB, B = 5, 8, 4, 64, 16, 12, 3
    trash = NB - 1
    q = jnp.asarray(rng.normal(size=(R, nh, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(NB, bs, nkv, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(NB, bs, nkv, d)), jnp.float32)
    bt = np.full((R, B), trash, np.int32)
    bt[0] = [0, 1, 2]
    bt[1] = [3, 4, trash]
    bt[2] = [5, trash, trash]
    bt[3] = [6, 7, 8]
    qpos = np.array([40, 20, 3, 47, 0], np.int32)  # row 4 inactive
    ref = paged_attention_reference(
        q, kc, vc, jnp.asarray(bt), jnp.asarray(qpos), trash, **kw
    )
    out = paged_decode_attention_dense(
        q, kc, vc, jnp.asarray(bt), jnp.asarray(qpos), trash, **kw
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(out[4]), 0.0, atol=1e-6)


@pytest.mark.parametrize("kw", [{}, {"window": 12}, {"scale": 1.0}])
def test_chunk_attention_matches_reference(kw):
    """Chunk rows vs the per-token reference: expand each row's table/
    positions to per-token form; padded tail (q_pos=-1) emits zero."""
    rng = np.random.default_rng(7)
    Rc, tq, nh, nkv, d, bs, NB, B = 2, 8, 4, 2, 64, 16, 12, 3
    trash = NB - 1
    q = jnp.asarray(rng.normal(size=(Rc, tq, nh, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(NB, bs, nkv, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(NB, bs, nkv, d)), jnp.float32)
    row_tables = np.array([[0, 1, 2], [3, 4, trash]], np.int32)
    # row 0: tokens at positions 18..25 (mid-prefill); row 1: 5 valid + 3 pad
    q_pos = np.stack([
        np.arange(18, 18 + tq, dtype=np.int32),
        np.array([3, 4, 5, 6, 7, -1, -1, -1], np.int32),
    ])
    out = paged_chunk_attention(
        q, kc, vc, jnp.asarray(row_tables), jnp.asarray(q_pos), trash, **kw
    )
    # flatten to the per-token reference form
    flat_q = q.reshape(Rc * tq, nh, d)
    flat_bt = np.repeat(row_tables, tq, axis=0)
    flat_pos = q_pos.reshape(-1)
    # reference has no -1 convention: route padded tokens to an all-trash row
    flat_bt[flat_pos < 0] = trash
    ref = paged_attention_reference(
        flat_q, kc, vc, jnp.asarray(flat_bt),
        jnp.asarray(np.maximum(flat_pos, 0)), trash, **kw
    ).reshape(Rc, tq, nh, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(out[1, 5:]), 0.0, atol=1e-6)


def test_decode_dense_extra_kv_equals_post_write():
    """Pre-write pool + extra_kv (the write-after-read decode form) must
    equal the legacy form where the tokens are already in the pool."""
    rng = np.random.default_rng(8)
    R, nh, nkv, d, bs, NB, B = 4, 8, 4, 64, 16, 12, 3
    trash = NB - 1
    q = jnp.asarray(rng.normal(size=(R, nh, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(NB, bs, nkv, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(NB, bs, nkv, d)), jnp.float32)
    bt = np.array([[0, 1, 2], [3, 4, trash], [5, trash, trash], [6, 7, 8]], np.int32)
    # each row: 2 "round" tokens at positions pos0, pos0+1; query = 2nd one
    pos0 = np.array([20, 3, 8, 40], np.int32)
    qpos = pos0 + 1
    ke = jnp.asarray(rng.normal(size=(R, 2, nkv, d)), jnp.float32)
    ve = jnp.asarray(rng.normal(size=(R, 2, nkv, d)), jnp.float32)
    epos = np.stack([pos0, pos0 + 1], axis=1).astype(np.int32)
    # legacy oracle: write the extra tokens into a copy of the pool
    kc2, vc2 = np.asarray(kc).copy(), np.asarray(vc).copy()
    for r in range(R):
        for j in range(2):
            p = int(epos[r, j])
            blk = int(bt[r, p // bs])
            kc2[blk, p % bs] = np.asarray(ke)[r, j]
            vc2[blk, p % bs] = np.asarray(ve)[r, j]
    ref = paged_decode_attention_dense(
        q, jnp.asarray(kc2), jnp.asarray(vc2), jnp.asarray(bt),
        jnp.asarray(qpos), trash,
    )
    out = paged_decode_attention_dense(
        q, kc, vc, jnp.asarray(bt), jnp.asarray(qpos), trash,
        extra_kv=(ke, ve, jnp.asarray(epos)),
        pool_limit=jnp.asarray(pos0),
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # invalid extra slots (epos -1) change nothing
    epos_inv = epos.copy(); epos_inv[:, 1] = -1
    kc3, vc3 = np.asarray(kc).copy(), np.asarray(vc).copy()
    for r in range(R):
        p = int(epos[r, 0])
        blk = int(bt[r, p // bs])
        kc3[blk, p % bs] = np.asarray(ke)[r, 0]
        vc3[blk, p % bs] = np.asarray(ve)[r, 0]
    ref1 = paged_decode_attention_dense(
        q, jnp.asarray(kc3), jnp.asarray(vc3), jnp.asarray(bt),
        jnp.asarray(qpos), trash,
        # slot pos0+1 was never written: cap the pool at the written prefix
        pool_limit=jnp.asarray(pos0 + 1),
    )
    out1 = paged_decode_attention_dense(
        q, kc, vc, jnp.asarray(bt), jnp.asarray(qpos), trash,
        extra_kv=(ke, ve, jnp.asarray(epos_inv)),
        pool_limit=jnp.asarray(pos0),
    )
    # qpos = pos0+1 but slot 1 invalid: only slot 0 contributes
    np.testing.assert_allclose(np.asarray(out1), np.asarray(ref1), atol=2e-5)


def test_chunk_attention_new_kv_equals_post_write():
    """Pre-write pool + in-chunk causal new_kv must equal the legacy form
    with the chunk already written to the pool."""
    rng = np.random.default_rng(9)
    Rc, tq, nh, nkv, d, bs, NB, B = 2, 6, 4, 2, 64, 16, 12, 3
    trash = NB - 1
    q = jnp.asarray(rng.normal(size=(Rc, tq, nh, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(NB, bs, nkv, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(NB, bs, nkv, d)), jnp.float32)
    bt = np.array([[0, 1, 2], [3, 4, trash]], np.int32)
    start = np.array([18, 3], np.int32)
    # row 1: only 4 valid tokens
    q_pos = np.stack([
        np.arange(18, 18 + tq, dtype=np.int32),
        np.array([3, 4, 5, 6, -1, -1], np.int32),
    ])
    ke = jnp.asarray(rng.normal(size=(Rc, tq, nkv, d)), jnp.float32)
    ve = jnp.asarray(rng.normal(size=(Rc, tq, nkv, d)), jnp.float32)
    kc2, vc2 = np.asarray(kc).copy(), np.asarray(vc).copy()
    for r in range(Rc):
        for j in range(tq):
            p = int(q_pos[r, j])
            if p < 0:
                continue
            blk = int(bt[r, p // bs])
            kc2[blk, p % bs] = np.asarray(ke)[r, j]
            vc2[blk, p % bs] = np.asarray(ve)[r, j]
    ref = paged_chunk_attention(
        q, jnp.asarray(kc2), jnp.asarray(vc2), jnp.asarray(bt),
        jnp.asarray(q_pos), trash,
    )
    out = paged_chunk_attention(
        q, kc, vc, jnp.asarray(bt), jnp.asarray(q_pos), trash,
        new_kv=(ke, ve), pool_limit=jnp.asarray(start),
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(out[1, 4:]), 0.0, atol=1e-6)


# ---------------------------------------------------------------------------
# int8 KV pools (in-kernel dequant) + impl dispatch
# ---------------------------------------------------------------------------
from deepspeed_tpu.ops.quantizer.block_quant import quantize_kv

# Error budget for int8-KV attention OUTPUT vs the unquantized pool, on
# N(0,1) payloads. Per-vector symmetric quantization bounds the per-element
# payload error by scale/2 = absmax/254 (absmax over d samples of N(0,1) is
# ~3-4, so <~0.02); the softmax-weighted sum keeps the output deviation the
# same order (measured <~2e-2 max on the shapes below). 6e-2 gives 3x slack
# without masking a broken dequant (which errs at O(absmax) ~ 1e0).
INT8_KV_MAX_ABS_ERR = 6e-2


def _quantized_pool(rng, NB, bs, nkv, d):
    kc = jnp.asarray(rng.normal(size=(NB, bs, nkv, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(NB, bs, nkv, d)), jnp.float32)
    kq, ks = quantize_kv(kc)
    vq, vs = quantize_kv(vc)
    return kc, vc, kq, ks, vq, vs


@pytest.mark.parametrize("impl", ["kernel", "dense", "reference"])
def test_paged_int8_matches_dequant_oracle(impl):
    """Every impl must attend over EXACTLY dequantize(payload, scale): the
    oracle is the fp32 reference run on a host-dequantized pool. Also bound
    the quantization error itself against the unquantized-pool reference."""
    rng = np.random.default_rng(10)
    T, nh, nkv, d, bs, NB, B = 8, 8, 4, 64, 16, 12, 3
    trash = NB - 1
    q = jnp.asarray(rng.normal(size=(T, nh, d)), jnp.float32)
    kc, vc, kq, ks, vq, vs = _quantized_pool(rng, NB, bs, nkv, d)
    bt = np.full((T, B), trash, np.int32)
    bt[0:4] = [0, 1, 2]
    bt[4:7] = [3, 4, trash]
    qpos = np.array([5, 20, 33, 40, 3, 10, 17, 0], np.int32)
    kdq = jnp.asarray(kq, jnp.float32) * ks[..., None]
    vdq = jnp.asarray(vq, jnp.float32) * vs[..., None]
    oracle = paged_attention_reference(
        q, kdq, vdq, jnp.asarray(bt), jnp.asarray(qpos), trash
    )
    out = paged_attention(
        q, kq, vq, jnp.asarray(bt), jnp.asarray(qpos), trash,
        impl=impl, interpret=True, k_scale=ks, v_scale=vs,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle), atol=2e-5)
    # bounded error vs the ORIGINAL (unquantized) pool
    exact = paged_attention_reference(
        q, kc, vc, jnp.asarray(bt), jnp.asarray(qpos), trash
    )
    err = np.abs(np.asarray(out) - np.asarray(exact)).max()
    assert err < INT8_KV_MAX_ABS_ERR, err
    assert err > 0.0  # quantization is real, not a silent bf16 passthrough


def test_paged_kernel_scale_override():
    """Softmax scale override must thread through the kernel path."""
    rng = np.random.default_rng(11)
    T, nh, nkv, d, bs, NB, B = 4, 4, 2, 64, 16, 8, 2
    trash = NB - 1
    q = jnp.asarray(rng.normal(size=(T, nh, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(NB, bs, nkv, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(NB, bs, nkv, d)), jnp.float32)
    bt = np.tile(np.array([[0, 1]], np.int32), (T, 1))
    qpos = np.array([0, 9, 17, 31], np.int32)
    ref = paged_attention_reference(
        q, kc, vc, jnp.asarray(bt), jnp.asarray(qpos), trash, scale=1.0
    )
    out = paged_attention(
        q, kc, vc, jnp.asarray(bt), jnp.asarray(qpos), trash,
        impl="kernel", interpret=True, scale=1.0,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("G", [None, 1, 2])  # (None: what the pool's block gives, 4 here)
@pytest.mark.parametrize("int8", [False, True])
def test_paged_kernel_extra_kv_matches_dense(int8, G):
    """The kernel's extras grid step (write-after-read decode form: pre-write
    pool + per-row extra tokens + pool_limit cap) must match the dense path,
    whose own correctness vs the post-write oracle is pinned above."""
    rng = np.random.default_rng(12)
    R, nh, nkv, d, bs, NB, B, E = 4, 8, 4, 64, 16, 12, 3, 2
    trash = NB - 1
    q = jnp.asarray(rng.normal(size=(R, nh, d)), jnp.float32)
    kc, vc, kq, ks, vq, vs = _quantized_pool(rng, NB, bs, nkv, d)
    bt = np.array([[0, 1, 2], [3, 4, trash], [5, trash, trash], [6, 7, 8]], np.int32)
    pos0 = np.array([20, 3, 8, 40], np.int32)
    qpos = pos0 + 1
    ke = jnp.asarray(rng.normal(size=(R, E, nkv, d)), jnp.float32)
    ve = jnp.asarray(rng.normal(size=(R, E, nkv, d)), jnp.float32)
    epos = jnp.asarray(np.stack([pos0, pos0 + 1], axis=1).astype(np.int32))
    kw = dict(
        extra_kv=(ke, ve, epos), pool_limit=jnp.asarray(pos0),
    )
    if int8:
        kw.update(k_scale=ks, v_scale=vs)
        pk, pv = kq, vq
    else:
        pk, pv = kc, vc
    ref = paged_attention(
        q, pk, pv, jnp.asarray(bt), jnp.asarray(qpos), trash, impl="dense", **kw
    )
    if G is None:
        from deepspeed_tpu.ops.attention.paged_pallas import blocks_a_program

        assert blocks_a_program(bs * nkv * 2 * d * pk.dtype.itemsize) == 4
        out = paged_attention(
            q, pk, pv, jnp.asarray(bt), jnp.asarray(qpos), trash,
            impl="kernel", interpret=True, **kw,
        )
    else:
        out = _kernel(q, pk, pv, jnp.asarray(bt), jnp.asarray(qpos), trash, G, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_paged_attention_impl_and_scale_validation():
    rng = np.random.default_rng(13)
    T, nh, nkv, d, bs, NB, B = 2, 4, 2, 64, 16, 4, 2
    trash = NB - 1
    q = jnp.asarray(rng.normal(size=(T, nh, d)), jnp.float32)
    kc, vc, kq, ks, vq, vs = _quantized_pool(rng, NB, bs, nkv, d)
    bt = jnp.zeros((T, B), jnp.int32)
    qpos = jnp.zeros((T,), jnp.int32)
    with pytest.raises(ValueError, match="unknown impl"):
        paged_attention(q, kc, vc, bt, qpos, trash, impl="fused")
    with pytest.raises(ValueError, match="k_scale and v_scale"):
        paged_attention(q, kq, vq, bt, qpos, trash, impl="dense")
    with pytest.raises(ValueError, match="not int8"):
        paged_attention(q, kc, vc, bt, qpos, trash, impl="dense",
                        k_scale=ks, v_scale=vs)


# ---------------------------------------------------------------------------
# the kernel visits only the blocks a row's context covers: ragged rows in
# one call, the bounds at a block's edges, and what lies outside a context
# ---------------------------------------------------------------------------
from deepspeed_tpu.ops.attention.paged_pallas import _paged_kernel_call, _visit_list

RAGGED_BS, RAGGED_B = 16, 4
# tokens the pool holds for each row: nothing, one, a block less one, a whole
# block, a block and one, two blocks and a part, a full table (0, 1, 1, 1, 2, 3
# and 4 blocks: every residue of a program's 2 and of its 4); the last row is
# an inactive slot
RAGGED_CONTEXTS = (0, 1, RAGGED_BS - 1, RAGGED_BS, RAGGED_BS + 1, 2 * RAGGED_BS + 5,
                   RAGGED_B * RAGGED_BS)
# blocks a program of dstpu_paged_decode reads (paged_pallas.blocks_a_program
# gives a pool's; a test's small blocks would all read 4)
PROGRAM_BLOCKS = (1, 2, 4)


def _kernel(q, kc, vc, bt, qpos, trash, G, **kw):
    """``paged_attention(impl="kernel", interpret=True)`` with ``G`` blocks a
    program: the kernel call's private argument."""
    return _paged_kernel_call(q, kc, vc, bt, qpos, trash, interpret=True, blocks=G, **kw)


def _ragged_call(rng, nh, nkv, d, int8=False):
    """Rows of RAGGED_CONTEXTS plus one inactive slot, every row on blocks of
    its own. Returns (q, pools, scales kwargs, tables, contexts, trash)."""
    bs, B = RAGGED_BS, RAGGED_B
    ctx = np.array(RAGGED_CONTEXTS + (-1,), np.int32)
    T = len(ctx)
    NB = T * B + 1
    trash = NB - 1
    bt = np.full((T, B), trash, np.int32)
    for t, c in enumerate(ctx):
        n = min(max(int(c), 0) // bs + 1, B)  # the block the next token lands in too
        if c >= 0:
            bt[t, :n] = t * B + np.arange(n)
    q = jnp.asarray(rng.normal(size=(T, nh, d)), jnp.float32)
    kc, vc, kq, ks, vq, vs = _quantized_pool(rng, NB, bs, nkv, d)
    pools, kw = ((kq, vq), dict(k_scale=ks, v_scale=vs)) if int8 else ((kc, vc), {})
    return q, pools, kw, bt, ctx, trash


@pytest.mark.parametrize("G", PROGRAM_BLOCKS)
@pytest.mark.parametrize("form", ["plain", "split", "split-int8"])
# (16, 2, 256): Qwen3-Next's full-attention layers, 8 query heads a KV head
# (20, 1, 128): Jamba2-3B's attention layers, 20 query heads on ONE KV head
@pytest.mark.parametrize("nh,nkv,d", [(16, 8, 128), (16, 16, 128), (4, 1, 64), (16, 2, 256),
                                      (20, 1, 128)])
def test_paged_kernel_ragged_rows_in_one_call(nh, nkv, d, form, G):
    """Contexts 0, 1, bs-1, bs, bs+1, 2 bs + 5 and a full table beside an
    inactive slot in ONE call, ``G`` blocks a program. ``plain``: the query is the context's last token, against
    the per-token reference. ``split``: the engine's split-step form (the pool
    holds the context, the query's own K/V rides as the extra column),
    against the dense form; ``split-int8`` the same over an int8 pool."""
    rng = np.random.default_rng(20)
    q, (pk, pv), kw, bt, ctx, trash = _ragged_call(rng, nh, nkv, d, int8=form.endswith("int8"))
    T = len(ctx)
    if form == "plain":
        qpos = jnp.asarray(ctx - 1)  # context 0 and the inactive slot: nothing to see
        ref = paged_attention_reference(
            q, pk, pv, jnp.asarray(np.where(ctx[:, None] > 0, bt, trash)),
            jnp.maximum(qpos, 0), trash)
        out = _kernel(q, pk, pv, jnp.asarray(bt), qpos, trash, G)
        for t in np.flatnonzero(ctx <= 0):
            np.testing.assert_array_equal(np.asarray(out[t]), 0.0)
    else:
        ke = jnp.asarray(rng.normal(size=(T, 1, nkv, d)), jnp.float32)
        ve = jnp.asarray(rng.normal(size=(T, 1, nkv, d)), jnp.float32)
        kw.update(extra_kv=(ke, ve, jnp.asarray(ctx[:, None])),
                  pool_limit=jnp.asarray(np.maximum(ctx, 0)))
        ref = paged_attention(q, pk, pv, jnp.asarray(bt), jnp.asarray(ctx), trash,
                              impl="dense", **kw)
        out = _kernel(q, pk, pv, jnp.asarray(bt), jnp.asarray(ctx), trash, G, **kw)
        # context 0 sees its own token alone; the inactive slot sees nothing
        np.testing.assert_allclose(
            np.asarray(out[0]), np.repeat(np.asarray(ve[0, 0]), nh // nkv, axis=0), atol=1e-6)
        np.testing.assert_array_equal(np.asarray(out[-1]), 0.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("G", PROGRAM_BLOCKS)
@pytest.mark.parametrize("form", ["plain", "split"])
def test_paged_kernel_window_starts_past_block_zero(form, G):
    """A window whose first block in band is not block 0: the kernel starts
    its walk there, whatever ``G`` (a group starts at the row's first slot,
    not at a multiple of ``G``), and a row still inside the window starts at
    0. Rows of 2, 2, 2, 1 and 3 blocks in band."""
    rng = np.random.default_rng(21)
    T, nh, nkv, d, bs, NB, B, window = 5, 4, 2, 64, 16, 26, 5, 20
    trash = NB - 1
    q = jnp.asarray(rng.normal(size=(T, nh, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(NB, bs, nkv, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(NB, bs, nkv, d)), jnp.float32)
    bt = jnp.asarray(np.arange(T * B, dtype=np.int32).reshape(T, B))
    qpos = np.array([60, 36, 35, 7, 66], np.int32)  # first block in band: 2, 1, 1, 0, 2
    _, vrow, vslot, _, _ = _visit_list(jnp.asarray(qpos), jnp.asarray(qpos + 1), bs, B, window, G)
    first = [int(vslot[np.flatnonzero(np.asarray(vrow) == t)[0]]) for t in range(T)]
    assert first == [2, 1, 1, 0, 2]
    if form == "plain":
        ref = paged_attention_reference(q, kc, vc, bt, jnp.asarray(qpos), trash, window=window)
        out = _kernel(q, kc, vc, bt, jnp.asarray(qpos), trash, G, window=window)
    else:
        ke = jnp.asarray(rng.normal(size=(T, 1, nkv, d)), jnp.float32)
        ve = jnp.asarray(rng.normal(size=(T, 1, nkv, d)), jnp.float32)
        kw = dict(extra_kv=(ke, ve, jnp.asarray(qpos[:, None])), pool_limit=jnp.asarray(qpos),
                  window=window)
        ref = paged_attention(q, kc, vc, bt, jnp.asarray(qpos), trash, impl="dense", **kw)
        out = _kernel(q, kc, vc, bt, jnp.asarray(qpos), trash, G, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("G", PROGRAM_BLOCKS)
@pytest.mark.parametrize("int8", [False, True])
def test_paged_kernel_verify_round_per_token_form(int8, G):
    """The speculative verify round's call: T = R x K1 queries, each carrying
    its ROW's table and pool window, the row's K1 fresh K/V as shared extra
    columns whose mask is the in-chunk causal one; padded slots (q_pos -1)
    emit 0. Against the dense form on the same arguments."""
    rng = np.random.default_rng(22)
    R, K1, nh, nkv, d, bs, B = 3, 4, 8, 4, 64, 16, 3
    NB = R * B + 1
    trash = NB - 1
    pos0 = np.array([37, 16, 5], np.int32)        # tokens each row has cached
    n_new = np.array([4, 2, 3], np.int32)         # live tokens of its K1
    tables = np.full((R, B), trash, np.int32)
    for r in range(R):
        n = (pos0[r] + K1 - 1) // bs + 1
        tables[r, :n] = r * B + np.arange(n)
    qpos = np.where(np.arange(K1)[None] < n_new[:, None], pos0[:, None] + np.arange(K1)[None], -1)
    q = jnp.asarray(rng.normal(size=(R * K1, nh, d)), jnp.float32)
    kc, vc, kq, ks, vq, vs = _quantized_pool(rng, NB, bs, nkv, d)
    (pk, pv), kw = ((kq, vq), dict(k_scale=ks, v_scale=vs)) if int8 else ((kc, vc), {})
    k_new = rng.normal(size=(R, K1, nkv, d)).astype(np.float32)
    v_new = rng.normal(size=(R, K1, nkv, d)).astype(np.float32)
    rep = lambda a: jnp.asarray(np.repeat(a, K1, axis=0))
    kw.update(extra_kv=(rep(k_new), rep(v_new), rep(qpos.astype(np.int32))),
              pool_limit=rep(pos0))
    args = (q, pk, pv, rep(tables), jnp.asarray(qpos.reshape(-1).astype(np.int32)), trash)
    ref = paged_attention(*args, impl="dense", **kw)
    out = _kernel(*args, G, **kw)
    # the dense form has no padded-slot convention of its own (the engine's
    # alternative there is paged_chunk_attention): compare the live slots
    live = qpos.reshape(-1) >= 0
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(ref)[live], atol=2e-5)
    np.testing.assert_array_equal(np.asarray(out)[~live], 0.0)


@pytest.mark.parametrize("G", PROGRAM_BLOCKS)
@pytest.mark.parametrize("row", range(len(RAGGED_CONTEXTS) + 1))
@pytest.mark.parametrize("form", ["plain", "split"])
def test_paged_kernel_ignores_what_a_row_does_not_hold(form, row, G):
    """NaN in every pool block row ``row`` does not hold (the absent partners
    of its last group among them: the blocks its table names past its
    context, and whatever those operands fetched last), in the trash block,
    and in the rows of its last block at or beyond its context: the row's
    output is finite, equal to the clean pool's bit for bit and to the
    reference's."""
    rng = np.random.default_rng(23)
    nh, nkv, d, bs = 8, 4, 64, RAGGED_BS
    q, (kc, vc), _, bt, ctx, trash = _ragged_call(rng, nh, nkv, d)
    T = len(ctx)
    c = int(max(ctx[row], 0))

    def poisoned(pool):
        bad = np.full(pool.shape, np.nan, np.float32)
        for j in range(-(-c // bs)):  # the blocks the row's context covers
            keep = min(bs, c - j * bs)
            bad[bt[row, j], :keep] = np.asarray(pool)[bt[row, j], :keep]
        return jnp.asarray(bad)

    if form == "plain":  # an empty context has no query: q_pos -1, the output 0
        kw, qpos = {}, jnp.asarray(ctx - 1)
    else:
        ke = jnp.asarray(rng.normal(size=(T, 1, nkv, d)), jnp.float32)
        ve = jnp.asarray(rng.normal(size=(T, 1, nkv, d)), jnp.float32)
        kw = dict(extra_kv=(ke, ve, jnp.asarray(ctx[:, None])),
                  pool_limit=jnp.asarray(np.maximum(ctx, 0)))
        qpos = jnp.asarray(ctx)
    run = lambda k, v: np.asarray(_kernel(q, k, v, jnp.asarray(bt), qpos, trash, G, **kw))[row]
    clean, dirty = run(kc, vc), run(poisoned(kc), poisoned(vc))
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty, clean)
    if form == "plain" and c == 0:
        np.testing.assert_array_equal(dirty, 0.0)
    elif form == "split":  # (the plain reference has no query for an empty context)
        ref = paged_attention(q, kc, vc, jnp.asarray(bt), qpos, trash, impl="dense", **kw)
        np.testing.assert_allclose(dirty, np.asarray(ref)[row], atol=2e-5)
    else:
        ref = paged_attention_reference(q, kc, vc, jnp.asarray(bt), qpos, trash)
        np.testing.assert_allclose(dirty, np.asarray(ref)[row], atol=2e-5)


@pytest.mark.parametrize("G", PROGRAM_BLOCKS)
@pytest.mark.parametrize("window", [0, 20])
def test_visit_list_against_a_hand_count(window, G):
    """The kernel's programs, from the bounds alone: each row's slots
    lo..hi in order, ``G`` to a program: ``ceil(n / G)`` programs for a row
    that holds ``n`` blocks and one for a row that holds none, each from its
    first slot on, flagged 1 / 8 / 16 / 32 where the group's first / second /
    third / fourth slot holds a block to fold (so the count of live blocks it
    carries), 2 on the row's first program and 4 on its last; and where each
    block of a group after the first points: its own slot where the row holds
    it, else where that block pointed last."""
    from deepspeed_tpu.ops.attention.paged_pallas import _GROUP_BITS

    bs, B = 16, 5
    qpos = np.array([-1, 0, 15, 16, 40, 79, 63, 70], np.int32)
    limit = np.array([0, 1, 16, 17, 40, 80, 0, 70], np.int32)  # row 6: an empty pool window
    n, vrow, vslot, vflag, fetch = _visit_list(jnp.asarray(qpos), jnp.asarray(limit), bs, B, window, G)
    assert len(fetch) == 2 * (G - 1)
    want, points = [], []
    last = [(0, 0)] * G  # where block i of a group pointed last: (row, slot)
    for t, (p, lim) in enumerate(zip(qpos, limit)):
        hi = min(-(-int(lim) // bs), B)
        lo = max(int(p) - window + 1, 0) // bs if window else 0
        slots = list(range(lo, hi))
        groups = [slots[i:i + G] for i in range(0, len(slots), G)] or [[]]
        for j, grp in enumerate(groups):
            flag = (2 if j == 0 else 0) | (4 if j == len(groups) - 1 else 0)
            for i, s_ in enumerate(grp):
                flag |= _GROUP_BITS[i]
                last[i] = (t, s_)
            # one program all the same for a row of nothing: the extra columns, or zeros
            want.append((t, grp[0] if grp else min(lo, B - 1), flag))
            points.append(list(last[1:]))
    assert int(n) == len(want)
    got = [np.asarray(a)[: len(want)].tolist() for a in (vrow, vslot, vflag)]
    assert list(zip(*got)) == want
    for i in range(1, G):
        got = list(zip(np.asarray(fetch[2 * i - 2])[: len(want)].tolist(),
                       np.asarray(fetch[2 * i - 1])[: len(want)].tolist()))
        assert got == [pt[i - 1] for pt in points], i
    programs = len(qpos) * -(-B // G)
    assert all(a.shape == (programs,) for a in (vrow, vslot, vflag, *fetch))
    live = [bin(f >> 3).count("1") + (f & 1) for _, _, f in want]
    assert all(1 <= c <= G for c, (_, _, f) in zip(live, want) if f & 1)
    # by hand, window 0: rows 0 and 6 hold nothing, row 5 its whole table of 5
    if not window and G == 1:
        assert int(n) == 1 + 1 + 1 + 2 + 3 + 5 + 1 + 5
        assert want[:3] == [(0, 0, 6), (1, 0, 7), (2, 0, 7)]
        assert want[3:5] == [(3, 0, 3), (3, 1, 5)]
    if not window and G == 2:
        # rows of 0, 1, 1, 2, 3, 5, 0, 5 blocks: 1, 1, 1, 1, 2, 3, 1, 3 programs
        assert int(n) == 13 and live == [0, 1, 1, 2, 2, 1, 2, 2, 1, 0, 2, 2, 1]
        assert want[3] == (3, 0, 1 | 8 | 2 | 4)                        # two blocks, first and last
        assert want[4:6] == [(4, 0, 1 | 8 | 2), (4, 2, 1 | 4)]        # three: a pair, then one
        assert want[6:9] == [(5, 0, 11), (5, 2, 9), (5, 4, 5)]
        # the second block of a group: nothing to point at until row 3 holds one (0, 0);
        # row 4's lone third block leaves it at (4, 1), row 6's nothing at row 5's (5, 3)
        assert [pt[0] for pt in points] == [
            (0, 0), (0, 0), (0, 0), (3, 1), (4, 1), (4, 1), (5, 1), (5, 3), (5, 3), (5, 3),
            (7, 1), (7, 3), (7, 3)]


@pytest.mark.parametrize("nkv,dk,dv,itemsize,want", [
    (16, 128, 128, 2, 1),   # OLMoE: a block of K and V is a megabyte already
    (8, 128, 128, 2, 2),    # Qwen3, K-EXAONE's both kinds: 512 KiB
    (8, 192, 128, 2, 2),    # MiMo-V2-Flash's window layers: 640 KiB
    (4, 192, 128, 2, 4),    # ... and its full layers: 320 KiB
    (2, 256, 256, 2, 4),    # Qwen3-Next's full layers: 256 KiB
    (8, 128, 128, 1, 4),    # an int8 pool of Qwen3's: its payload alone, 256 KiB
    (32, 128, 128, 2, 1),   # past a megabyte: one
    (1, 128, 128, 2, 4),    # Jamba2-3B's one KV head: 64 KiB, four at most
], ids=["olmoe", "qwen3", "mimo_window", "mimo_full", "qwen3_next", "qwen3_int8", "two_mib",
        "jamba"])
def test_blocks_a_program_at_the_cells_geometries(nkv, dk, dv, itemsize, want):
    """As many 128-token pool blocks as make a megabyte, four at most: the
    rule's one constant is set from kernel-alone times at these geometries
    (paged_pallas.PROGRAM_BYTES; PERF.md section 6, PR 46)."""
    from deepspeed_tpu.ops.attention.paged_pallas import blocks_a_program

    assert blocks_a_program(128 * nkv * (dk + dv) * itemsize) == want


# the cells' three geometries (Qwen3 / K-EXAONE's KV side, OLMoE, Qwen3-Next)
# in the dtype they serve: a bf16 pool and bf16 queries against the float32
# reference on the same (bf16-valued) numbers
# unit-normal q, k, v: it reads 0.007-0.009 here, of which rounding the output
# itself to bf16 is 0.0075 (the dense form on the same call)
BF16_FOLD_ATOL = 2e-2


@pytest.mark.parametrize("E", [0, 1, 4])
@pytest.mark.parametrize("nh,nkv,d", [(16, 8, 128), (16, 16, 128), (16, 2, 256)])
def test_paged_kernel_bf16_pool_against_float32_reference(nh, nkv, d, E):
    """Operands enter the products as the pool stores them (bf16), scores,
    softmax state and accumulator in float32: contexts of nothing, one key,
    a part block, exactly one block, several, a full table, and a padded
    slot in one call, with ``E`` extra columns (0: the plain form)."""
    rng = np.random.default_rng(30)
    q, (kc, vc), _, bt, ctx, trash = _ragged_call(rng, nh, nkv, d)
    T = len(ctx)
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    qb, kb, vb = bf(q), bf(kc), bf(vc)
    if E == 0:
        qpos = jnp.asarray(ctx - 1)
        ref = paged_attention_reference(
            f32(qb), f32(kb), f32(vb), jnp.asarray(np.where(ctx[:, None] > 0, bt, trash)),
            jnp.maximum(qpos, 0), trash)
        out = paged_attention(qb, kb, vb, jnp.asarray(bt), qpos, trash,
                              impl="kernel", interpret=True)
        dead = ctx <= 0
    else:
        # the row's E fresh tokens at ctx .. ctx + E - 1, the query the last
        ke, ve = bf(rng.normal(size=(T, E, nkv, d))), bf(rng.normal(size=(T, E, nkv, d)))
        epos = np.where(ctx[:, None] >= 0, ctx[:, None] + np.arange(E)[None], -1).astype(np.int32)
        qpos = jnp.asarray(np.where(ctx >= 0, ctx + E - 1, -1).astype(np.int32))
        lim = jnp.asarray(np.maximum(ctx, 0))
        ref = paged_attention(
            f32(qb), f32(kb), f32(vb), jnp.asarray(bt), qpos, trash, impl="dense",
            extra_kv=(f32(ke), f32(ve), jnp.asarray(epos)), pool_limit=lim)
        out = paged_attention(
            qb, kb, vb, jnp.asarray(bt), qpos, trash, impl="kernel", interpret=True,
            extra_kv=(ke, ve, jnp.asarray(epos)), pool_limit=lim)
        dead = ctx < 0
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               atol=BF16_FOLD_ATOL)
    np.testing.assert_array_equal(np.asarray(out, np.float32)[dead], 0.0)


@pytest.mark.parametrize("G", PROGRAM_BLOCKS)
@pytest.mark.parametrize("case", ["only the extra column", "exactly one block", "all trash"])
def test_paged_kernel_rows_of_one_program(case, G):
    """A row whose first visit is its last. ``only the extra column``: an
    empty pool and E = 1, the first token after a prompt of a block's
    multiple was written elsewhere: the output is that column's value.
    ``exactly one block``: first = last visit, the block masked or whole.
    ``all trash``: every row padded: one program a row, zeros out."""
    rng = np.random.default_rng(31)
    T, nh, nkv, d, bs, B = 3, 8, 4, 64, 16, 3
    NB = T * B + 1
    trash = NB - 1
    q = jnp.asarray(rng.normal(size=(T, nh, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(NB, bs, nkv, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(NB, bs, nkv, d)), jnp.float32)
    ke = jnp.asarray(rng.normal(size=(T, 1, nkv, d)), jnp.float32)
    ve = jnp.asarray(rng.normal(size=(T, 1, nkv, d)), jnp.float32)
    bt = np.full((T, B), trash, np.int32)
    if case == "only the extra column":
        ctx = np.array([0, 0, 0], np.int32)
        bt[:, 0] = np.arange(T) * B  # the block the token will land in: nothing of it is held
    elif case == "exactly one block":
        ctx = np.array([bs, 5, 1], np.int32)  # whole; masked; one key
        bt[:, 0] = np.arange(T) * B
    else:
        ctx = np.array([-1, -1, -1], np.int32)
    kw = dict(extra_kv=(ke, ve, jnp.asarray(ctx[:, None])), pool_limit=jnp.asarray(np.maximum(ctx, 0)))
    args = (q, kc, vc, jnp.asarray(bt), jnp.asarray(ctx), trash)
    n, vrow, _, vflag, _ = _visit_list(jnp.asarray(ctx), kw["pool_limit"], bs, B, 0, G)
    assert int(n) == T and np.asarray(vrow)[:T].tolist() == [0, 1, 2]
    assert np.asarray(vflag)[:T].tolist() == [7 if case == "exactly one block" else 6] * T
    out = np.asarray(_kernel(*args, G, **kw))
    if case == "all trash":
        np.testing.assert_array_equal(out, 0.0)
        return
    ref = np.asarray(paged_attention(*args, impl="dense", **kw))
    np.testing.assert_allclose(out, ref, atol=2e-5)
    if case == "only the extra column":
        np.testing.assert_allclose(out, np.repeat(np.asarray(ve[:, 0]), nh // nkv, axis=1), atol=1e-6)


# ---------------------------------------------------------------------------
# dstpu_paged_chunk: a chunk's queries by tiles against the blocks its row
# holds and the chunk's own K/V, against the dense form on the same call
# ---------------------------------------------------------------------------
from deepspeed_tpu.ops.attention.paged_pallas import _chunk_visit_list, chunk_kernel_takes

CHUNK_BS, CHUNK_B = 16, 8


def _chunk_call(rng, nh, nkv, d, tq, rows, int8=False):
    """Chunk rows ``(start, n)``: ``n`` live queries at positions from
    ``start`` over a pool that holds the ``start`` tokens below them, every
    row on blocks of its own; ``n == 0`` is an empty row (an all-trash
    table). Returns (positional arguments, keyword arguments, tables)."""
    bs, B = CHUNK_BS, CHUNK_B
    Rc = len(rows)
    NB = Rc * B + 1
    trash = NB - 1
    bt = np.full((Rc, B), trash, np.int32)
    qpos = np.full((Rc, tq), -1, np.int32)
    for r, (start, n) in enumerate(rows):
        if n:
            nb = -(-(start + n) // bs)  # the blocks its own tokens land in too
            bt[r, :nb] = r * B + np.arange(nb)
            qpos[r, :n] = start + np.arange(n)
    q = jnp.asarray(rng.normal(size=(Rc, tq, nh, d)), jnp.float32)
    kc, vc, kq, ks, vq, vs = _quantized_pool(rng, NB, bs, nkv, d)
    pools, kw = ((kq, vq), dict(k_scale=ks, v_scale=vs)) if int8 else ((kc, vc), {})
    kw.update(new_kv=(jnp.asarray(rng.normal(size=(Rc, tq, nkv, d)), jnp.float32),
                      jnp.asarray(rng.normal(size=(Rc, tq, nkv, d)), jnp.float32)),
              pool_limit=jnp.asarray(np.array([s for s, _ in rows], np.int32)))
    return (q, *pools, jnp.asarray(bt), jnp.asarray(qpos), trash), kw, bt


def _chunk_rows(tq):
    """In ONE call: a prompt's first chunk (an empty pool), a chunk that
    starts inside a block and has a padded tail, one over several whole
    blocks, one that fills its table, and an empty row."""
    S = CHUNK_BS * CHUNK_B
    return [(0, tq), (5, tq - 3), (3 * CHUNK_BS, tq - CHUNK_BS // 2), (S - tq, tq), (0, 0)]


# the serving cells' geometries: Qwen3, OLMoE, Qwen3-Next's and Jamba's attention layers
@pytest.mark.parametrize("nh,nkv,d", [(16, 8, 128), (16, 16, 128), (16, 2, 256), (20, 1, 128)])
@pytest.mark.parametrize("tq,tile", [(16, 16), (64, 16), (64, 32)])  # one query tile, four, two
def test_chunk_kernel_matches_dense(nh, nkv, d, tq, tile):
    rng = np.random.default_rng(30)
    rows = _chunk_rows(tq)
    args, kw, _ = _chunk_call(rng, nh, nkv, d, tq, rows)
    ref = paged_chunk_attention(*args, **kw)
    out = paged_chunk_attention(*args, impl="kernel", interpret=True, tile=tile, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    for r, (_, n) in enumerate(rows):  # a padded tail and an empty row: zeros, as the dense form's
        np.testing.assert_array_equal(np.asarray(out[r, n:]), 0.0)


@pytest.mark.parametrize("nkv", [2, 4])
def test_chunk_kernel_bf16_operands(nkv):
    """bf16 as the cells hold it: the products take bf16 operands, scores and
    softmax state are float32. Against the dense form, which gathers in
    float32: the error is bf16's rounding of q x scale and of the weights."""
    rng = np.random.default_rng(34)
    args, kw, _ = _chunk_call(rng, 8, nkv, 64, 32, _chunk_rows(32))
    bf = lambda a: a.astype(jnp.bfloat16)
    args = (bf(args[0]), bf(args[1]), bf(args[2])) + args[3:]
    kw["new_kv"] = tuple(bf(a) for a in kw["new_kv"])
    ref = paged_chunk_attention(*args, **kw)
    out = paged_chunk_attention(*args, impl="kernel", interpret=True, tile=16, **kw)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2)


@pytest.mark.parametrize("kw", [{"window": 12}, {"window": 40}, {"scale": 1.0},
                                {"window": 24, "scale": 0.5}])
def test_chunk_kernel_window_and_scale(kw):
    """A window shorter than a tile, one that reaches into the pool past its
    first block, and the softmax scale override."""
    rng = np.random.default_rng(31)
    args, call_kw, _ = _chunk_call(rng, 4, 2, 64, 64, _chunk_rows(64))
    ref = paged_chunk_attention(*args, **call_kw, **kw)
    out = paged_chunk_attention(*args, impl="kernel", interpret=True, tile=32, **call_kw, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("case", ["int8 pool", "chunk already in the pool"])
def test_chunk_kernel_leaves_to_the_dense_form(case):
    """What ``chunk_kernel_takes`` does not: an int8 pool and the form without
    ``new_kv``. ``impl="kernel"`` then IS the dense form, bit for bit."""
    rng = np.random.default_rng(32)
    args, kw, _ = _chunk_call(rng, 4, 2, 64, 32, _chunk_rows(32), int8=case.startswith("int8"))
    if not case.startswith("int8"):
        kw = {}
    assert not chunk_kernel_takes(args[0].shape, args[1].shape, args[1].dtype,
                                  "new_kv" in kw, True)
    ref = paged_chunk_attention(*args, **kw)
    out = paged_chunk_attention(*args, impl="kernel", interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    with pytest.raises(ValueError, match="unknown impl"):
        paged_chunk_attention(*args, impl="flash", **kw)


@pytest.mark.parametrize("nh,nkv,d,bs,takes", [
    (16, 8, 128, 128, True), (16, 16, 128, 128, True), (16, 2, 256, 128, True),  # the cells'
    (8, 4, 128, 128, True),
    (18, 6, 128, 128, False),   # 6 KV heads in a tile of 8: the pool is copied
    (20, 1, 128, 128, True),    # ONE head, read as the row it is (paged_pallas.one_head, PR 53)
    (16, 8, 64, 128, False),    # a head of 64, half a lane tile: the pool is copied
    (16, 8, 128, 16, False),    # 16 keys a block
])
def test_chunk_kernel_takes_what_reads_the_pool_in_place(nh, nkv, d, bs, takes):
    """The geometries at which the described v5e's compiler reads the pool in
    place and those at which it copies it first (PR 30), as the rule the
    dispatch reads; interpreted, every geometry runs."""
    shapes = ((2, 512, nh, d), (64, bs, nkv, d), jnp.bfloat16, True)
    assert chunk_kernel_takes(*shapes, False) == takes
    assert chunk_kernel_takes(*shapes, True)
    assert not chunk_kernel_takes((2, 520, nh, d), *shapes[1:], True)  # not whole blocks


@pytest.mark.parametrize("row", range(5))
def test_chunk_kernel_ignores_what_a_row_does_not_hold(row):
    """NaN in every pool block the row does not hold below its chunk, in the
    trash block, and in its last block from the chunk's start on: the row's
    output is finite and equal to the clean pool's, bit for bit."""
    rng = np.random.default_rng(33)
    tq, bs = 32, CHUNK_BS
    rows = _chunk_rows(tq)
    (q, kc, vc, bt, qpos, trash), kw, tables = _chunk_call(rng, 8, 4, 64, tq, rows)
    start = rows[row][0]

    def poisoned(pool):
        bad = np.full(pool.shape, np.nan, np.float32)
        for j in range(-(-start // bs)):  # the blocks below the chunk
            keep = min(bs, start - j * bs)
            bad[tables[row, j], :keep] = np.asarray(pool)[tables[row, j], :keep]
        return jnp.asarray(bad)

    run = lambda k, v: np.asarray(paged_chunk_attention(
        q, k, v, bt, qpos, trash, impl="kernel", interpret=True, tile=16, **kw))[row]
    clean, dirty = run(kc, vc), run(poisoned(kc), poisoned(vc))
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty, clean)


@pytest.mark.parametrize("window", [0, 20])
def test_chunk_visit_list_against_a_hand_count(window):
    """The kernel's programs from the bounds alone: for each tile of each
    row the pool slots lo..hi, then the chunk's key blocks up to the tile's
    last live query; a tile with no live query is one program."""
    bs, B, tq, tile = 16, 8, 64, 32
    n = np.array([64, 40, 0, 7], np.int32)          # live queries a row
    start = np.array([0, 37, 0, 96], np.int32)      # = the pool's limit
    got = _chunk_visit_list(jnp.asarray(n), jnp.asarray(start), jnp.asarray(start),
                            bs, B, tq, tile, window)
    want = []  # (row, tile, pool slot or None, chunk block or None)
    for r in range(len(n)):
        for t in range(tq // tile):
            i0 = t * tile
            live = min(max(int(n[r]) - i0, 0), tile)
            if not live:
                want.append((r, t, None, None))
                continue
            lo = max(int(start[r]) + i0 - window + 1, 0) // bs if window else 0
            want += [(r, t, s, None) for s in range(lo, -(-int(start[r]) // bs))]
            klo = max(i0 - window + 1, 0) // bs if window else 0
            want += [(r, t, None, k) for k in range(klo, (i0 + live - 1) // bs + 1)]
    count, vrow, vqt, vpool, vkt, vflag = (np.asarray(a) for a in got)
    assert int(count) == len(want)
    # no window. Row 0, an empty pool: its tiles see 2 and 4 chunk blocks. Row
    # 1, 3 pool blocks a tile: + 2 chunk blocks, + 3 (8 live queries in the
    # second tile). The empty row: a program a tile. Row 3: 6 pool blocks + 1,
    # then a tile with no live query
    if not window:
        assert len(want) == (2 + 4) + (3 + 2 + 3 + 3) + 2 + (6 + 1 + 1)
    for g, (r, t, slot, k) in enumerate(want):
        assert (vrow[g], vqt[g], bool(vflag[g] & 1)) == (r, t, slot is not None)
        if slot is not None:
            assert vpool[g] == slot
        elif k is not None:
            assert vkt[g] == k
        first = g == 0 or want[g - 1][:2] != (r, t)
        last = g == len(want) - 1 or want[g + 1][:2] != (r, t)
        assert (bool(vflag[g] & 2), bool(vflag[g] & 4)) == (first, last)
    assert vrow.shape == (len(n) * (tq // tile) * (B + tq // bs),)


def test_chunked_prefill_through_the_kernel_equals_the_dense_form():
    """The engine's split step with ``paged_attention_impl="kernel"`` (both
    kernels interpreted on the CPU): a prompt of three chunks beside a short
    one streams the tokens of the dense form. The later chunks read the
    pool blocks the earlier ones wrote."""
    from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import TransformerConfig, init_params

    mc = TransformerConfig(vocab_size=128, hidden_size=128, n_layers=2, n_heads=2,
                           n_kv_heads=1, max_seq_len=512, dtype="float32")
    params = init_params(mc, jax.random.key(3))

    def tokens(impl):
        rc = RaggedInferenceEngineConfig.from_dict({
            "dtype": "float32", "paged_attention_impl": impl, "prompt_chunk": 128,
            "kv_cache": {"block_size": 16, "num_blocks": 64, "max_blocks_per_seq": 24},
            "state_manager": {"max_tracked_sequences": 4, "max_ragged_batch_size": 256,
                              "max_ragged_sequence_count": 4, "max_context": 384},
        })
        eng = InferenceEngineV2(mc, params, rc)
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, 128, size=(n,)).astype(np.int32) for n in (300, 40)]
        return eng.generate(prompts, max_new_tokens=4)

    for a, b in zip(tokens("dense"), tokens("kernel")):
        np.testing.assert_array_equal(a, b)


# -- keys of one width, values of another, heads by call, a sink (mimo_v2_flash) ----------
def _geometry_case(nkv, sink, flat, window, seed=0):
    """A call at K 192 / V 128: (q, K pool, V pool, tables, positions, sinks),
    keys a row a head or (``flat``) a token a row, as the engine stores them."""
    rng = np.random.default_rng(seed)
    nh, d, dv, bs, NB, B, T = 16, 192, 128, 16, 12, 4, 5
    q = jnp.asarray(rng.normal(size=(T, nh, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(NB + 1, bs, nkv, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(NB + 1, bs, nkv, dv)), jnp.float32)
    pos = jnp.asarray([0, 3, bs * 2 + 1, bs * 4 - 1, -1], jnp.int32)
    tb = jnp.asarray(rng.integers(0, NB - 2, (T, B)), jnp.int32)  # (the last two blocks stay free)
    tb = jnp.where(jnp.arange(B)[None] * bs <= jnp.maximum(pos, 0)[:, None], tb, NB)
    sinks = jnp.asarray(rng.normal(size=(nh,)) * 2, jnp.float32) if sink else None
    return q, (kc.reshape(NB + 1, bs, nkv * d) if flat else kc), vc, tb, pos, sinks, NB


@pytest.mark.parametrize("flat", [False, True], ids=["row_a_head", "token_a_row"])
@pytest.mark.parametrize("sink", [False, True], ids=["no_sink", "sink"])
@pytest.mark.parametrize("G", PROGRAM_BLOCKS)
@pytest.mark.parametrize("nkv,window", [(4, 0), (8, 16)], ids=["4_heads_full", "8_heads_window"])
def test_paged_kernels_at_a_key_of_192_and_a_value_of_128(nkv, window, sink, flat, G):
    """``dstpu_paged_decode`` and ``dstpu_paged_chunk`` (interpreted) and their
    dense forms against ``paged_attention_reference`` at keys of 192 beside
    values of 128, 4 and 8 KV heads, with and without the heads' sinks in the
    softmax's denominator, the keys stored either way; the split forms (this
    step's K/V beside the pool) kernel against dense."""
    from deepspeed_tpu.ops.attention.paged_pallas import paged_chunk_attention

    q, kc, vc, tb, pos, sinks, NB = _geometry_case(nkv, sink, flat, window)
    T, nh, d = q.shape
    dv, bs = vc.shape[-1], vc.shape[1]
    ref = paged_attention_reference(q, kc, vc, tb, pos, NB, window=window, sinks=sinks)
    assert ref.shape == (T, nh, dv)
    for out in (paged_attention(q, kc, vc, tb, pos, NB, impl="dense", window=window, sinks=sinks),
                _kernel(q, kc, vc, tb, pos, NB, G, window=window, sinks=sinks)):
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    if sink:  # the sink takes mass: the same call without it reads otherwise
        bare = _kernel(q, kc, vc, tb, pos, NB, G, window=window)
        assert float(jnp.abs(bare - ref).max()) > 1e-2
    rng = np.random.default_rng(1)
    ke = jnp.asarray(rng.normal(size=(T, 1, nkv, d)), jnp.float32)
    ve = jnp.asarray(rng.normal(size=(T, 1, nkv, dv)), jnp.float32)
    kw = dict(window=window, sinks=sinks, extra_kv=(ke, ve, pos[:, None]), pool_limit=pos)
    np.testing.assert_allclose(
        np.asarray(_kernel(q, kc, vc, tb, pos, NB, G, **kw)),
        np.asarray(paged_attention(q, kc, vc, tb, pos, NB, impl="dense", **kw)), atol=2e-5)
    if G != PROGRAM_BLOCKS[0]:
        return  # (the chunk kernel has no such parameter: once)
    # prompt chunks: two rows of two blocks, one below a pool context, one from position 0
    Rc, tq = 2, 2 * bs
    qc = jnp.asarray(rng.normal(size=(Rc, tq, nh, d)), jnp.float32)
    start = jnp.asarray([bs * 2, 0], jnp.int32)
    n = jnp.asarray([tq - 3, bs + 1])
    qpos = jnp.where(jnp.arange(tq)[None] < n[:, None], start[:, None] + jnp.arange(tq)[None], -1)
    tbc = jnp.where(jnp.arange(tb.shape[1])[None] * bs < start[:, None], tb[:Rc], NB)
    new = (jnp.asarray(rng.normal(size=(Rc, tq, nkv, d)), jnp.float32),
           jnp.asarray(rng.normal(size=(Rc, tq, nkv, dv)), jnp.float32))
    kw = dict(window=window, new_kv=new, pool_limit=start, sinks=sinks)
    dense = paged_chunk_attention(qc, kc, vc, tbc, qpos, NB, impl="dense", **kw)
    kern = paged_chunk_attention(qc, kc, vc, tbc, qpos, NB, impl="kernel", interpret=True, tile=bs, **kw)
    assert kern.shape == (Rc, tq, nh, dv)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense), atol=2e-5)
    # ... and the dense chunk form against the per-token reference over a pool that holds the chunk
    r = 0
    live = int(n[r])
    blocks = jnp.asarray([[NB - 1 - j for j in range(2)]], jnp.int32)
    k4 = kc.reshape(kc.shape[0], bs, nkv, d)
    pool_k = k4.at[blocks[0]].set(new[0][r].reshape(2, bs, nkv, d))
    pool_v = vc.at[blocks[0]].set(new[1][r].reshape(2, bs, nkv, dv))
    table = jnp.concatenate([tbc[r][: int(start[r]) // bs], blocks[0]])[None]
    want = paged_attention_reference(
        qc[r, :live], pool_k, pool_v, jnp.broadcast_to(table, (live, table.shape[1])),
        qpos[r, :live], NB, window=window, sinks=sinks)
    np.testing.assert_allclose(np.asarray(dense[r, :live]), np.asarray(want), atol=2e-5)
