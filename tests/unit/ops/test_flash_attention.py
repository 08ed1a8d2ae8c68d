"""Flash-attention kernel numerics vs jnp reference (interpret mode on CPU).
Analogue of reference tests/unit/ops kernel-vs-torch numerics tests."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import flash_pallas, mha_reference
from deepspeed_tpu.ops.attention.flash_pallas import flash_attention


def _qkv(b=2, h=4, h_kv=None, s=256, d=64, dtype=jnp.float32, seed=0):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    h_kv = h_kv or h
    q = jax.random.normal(kq, (b, h, s, d), dtype)
    k = jax.random.normal(kk, (b, h_kv, s, d), dtype)
    v = jax.random.normal(kv, (b, h_kv, s, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal, None, None, True)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_gqa_forward():
    q, k, v = _qkv(h=8, h_kv=2)
    out = flash_attention(q, k, v, True, None, None, True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_grads_match_reference(causal):
    q, k, v = _qkv(b=1, h=2, s=128, d=64)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.square(flash_attention(q, k, v, causal, None, None, True)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(mha_reference(q, k, v, causal=causal)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=5e-4, atol=5e-4, err_msg=f"d{name}"
        )


def test_sharded_flash_matches_reference(devices8):
    """Flash under fully-manual shard_map (batch over data, heads over model)
    — the multi-device dispatch path of ops.attention.core._flash_sharded."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.parallel.topology import Topology, reset_topology, set_topology

    reset_topology()
    topo = Topology(data=2, model=4)
    set_topology(topo)
    q, k, v = _qkv(b=2, h=4, s=256, d=64)
    spec = P(("data", "expert"), ("model", "sequence"), None, None)
    fn = jax.shard_map(
        lambda q_, k_, v_: flash_attention(q_, k_, v_, True, None, None, True),
        mesh=topo.mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        axis_names=set(topo.mesh.axis_names),
        check_vma=False,
    )
    out = jax.jit(fn)(q, k, v)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)
    reset_topology()


def _packed_segments(b, s, n_seg, seed=7):
    """Random packed-sequence segment ids: contiguous runs 0..n_seg-1."""
    rng = np.random.default_rng(seed)
    out = np.zeros((b, s), np.int32)
    for i in range(b):
        cuts = np.sort(rng.choice(np.arange(1, s), size=n_seg - 1, replace=False))
        seg = np.zeros(s, np.int32)
        for j, c in enumerate(cuts):
            seg[c:] = j + 1
        out[i] = seg
    return jnp.asarray(out)


@pytest.mark.parametrize("causal", [True, False])
def test_segment_ids_forward_matches_reference(causal):
    """Packed-sequence masking happens IN the kernel (VERDICT weak #8):
    tokens must not attend across segment boundaries."""
    q, k, v = _qkv(b=2, h=2, s=256, d=64)
    seg = _packed_segments(2, 256, n_seg=3)
    out = flash_attention(q, k, v, causal=causal, segment_ids=seg, interpret=True)
    ref = mha_reference(q, k, v, causal=causal, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_segment_ids_grads_match_reference():
    q, k, v = _qkv(b=1, h=2, s=128, d=64)
    seg = _packed_segments(1, 128, n_seg=2)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.square(
            flash_attention(q, k, v, causal=True, segment_ids=seg, interpret=True)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(mha_reference(q, k, v, causal=True, segment_ids=seg)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=5e-4, atol=5e-4, err_msg=f"d{name}"
        )


def test_segment_ids_isolation():
    """Output for a segment must be identical to running that segment alone."""
    q, k, v = _qkv(b=1, h=2, s=256, d=64)
    seg = jnp.asarray(np.repeat([0, 1], 128)[None, :].astype(np.int32))
    out = flash_attention(q, k, v, causal=True, segment_ids=seg, interpret=True)
    solo = flash_attention(
        q[:, :, :128], k[:, :, :128], v[:, :, :128], causal=True, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out[:, :, :128]), np.asarray(solo), rtol=2e-4, atol=2e-4
    )


def test_gqa_grads():
    q, k, v = _qkv(b=1, h=4, h_kv=2, s=128, d=64)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.square(flash_attention(q, k, v, True, None, None, True)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(mha_reference(q, k, v, causal=True)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=5e-4, atol=5e-4, err_msg=f"d{name}"
        )


@pytest.mark.slow  # ~35s of interpret-mode 16k scan; the no-VMEM-residency
# property is the scale leg — test_long_seq_grads_4k keeps it tier-1 at 4k
def test_dense_16k_forward():
    """The kv-pipelined kernel has no sequence-length VMEM residency: a 16k
    dense causal sequence (impossible with whole-K/V-resident programs) must
    match the reference. Head dim kept small so interpret mode stays fast."""
    q, k, v = _qkv(b=1, h=1, s=16384, d=64)
    out = flash_attention(q, k, v, True, None, None, True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_long_seq_grads_4k(monkeypatch):
    """Backward streams q/do/o blocks too — check grads at 4k with explicit
    512 blocks (8x8 grid) so the streamed multi-block path is exercised
    regardless of the DSTPU_FLASH_BLOCK default."""
    monkeypatch.setenv("DSTPU_FLASH_BLOCK", "512")
    q, k, v = _qkv(b=1, h=1, s=4096, d=64)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.square(flash_attention(q, k, v, True, None, None, True)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(mha_reference(q, k, v, causal=True)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=5e-3, atol=5e-3, err_msg=f"d{name}"
        )


@pytest.mark.parametrize("causal", [True, False])
def test_segment_ids_multiblock(monkeypatch, causal):
    """Segment planes stream through the clamped BlockSpecs only when the
    grid has multiple kv blocks — force 128 blocks at s=512 (4x4 grid) so the
    seg_q/seg_k index-map clamps are actually exercised (fwd + grads)."""
    monkeypatch.setenv("DSTPU_FLASH_BLOCK", "128")
    q, k, v = _qkv(b=1, h=2, s=512, d=64)
    seg = _packed_segments(1, 512, n_seg=3)
    out = flash_attention(q, k, v, causal=causal, segment_ids=seg, interpret=True)
    ref = mha_reference(q, k, v, causal=causal, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.square(
            flash_attention(q, k, v, causal=causal, segment_ids=seg, interpret=True)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(mha_reference(q, k, v, causal=causal, segment_ids=seg)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=5e-4, atol=5e-4, err_msg=f"d{name}"
        )


@pytest.mark.parametrize("causal", [True, False])
def test_alibi_forward_matches_reference(causal):
    """ALiBi folded into the kernel (rank-1 slope*key_pos) must match the
    reference's dense-bias form exactly (bloom parity path)."""
    from deepspeed_tpu.models.transformer import alibi_slopes

    q, k, v = _qkv(h=4, s=256)
    slopes = jnp.asarray(alibi_slopes(4))
    out = flash_attention(q, k, v, causal, None, None, True, alibi_slopes=slopes)
    bias = slopes[None, :, None, None] * jnp.arange(256, dtype=jnp.float32)[None, None, None, :]
    ref = mha_reference(q, k, v, causal=causal, bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_alibi_gqa_and_custom_positions():
    from deepspeed_tpu.models.transformer import alibi_slopes

    q, k, v = _qkv(h=8, h_kv=2, s=256)
    slopes = jnp.asarray(alibi_slopes(8))
    pos = jnp.broadcast_to(jnp.arange(256, dtype=jnp.int32)[None] + 5, (2, 256))
    out = flash_attention(
        q, k, v, True, None, None, True, alibi_slopes=slopes, alibi_positions=pos
    )
    ref = mha_reference(
        q, k, v, causal=True, alibi_slopes=slopes, alibi_positions=pos
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_alibi_grads_match_reference():
    from deepspeed_tpu.models.transformer import alibi_slopes

    q, k, v = _qkv(b=1, h=2, s=128, d=64)
    slopes = jnp.asarray(alibi_slopes(2))

    def loss_flash(q, k, v):
        return jnp.sum(jnp.square(
            flash_attention(q, k, v, True, None, None, True, alibi_slopes=slopes)
        ))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(
            mha_reference(q, k, v, causal=True, alibi_slopes=slopes)
        ))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=5e-4, atol=5e-4, err_msg=f"d{name}"
        )


def test_alibi_multiblock_and_segment_combo(monkeypatch):
    """Multi-block regime (block 128 over s=512 → 4 kv blocks): exercises the
    per-block key-position index maps across blocks AND the causal clamp,
    combined with segment-id masking (both extra-operand families at once)."""
    from deepspeed_tpu.models.transformer import alibi_slopes

    monkeypatch.setenv("DSTPU_FLASH_BLOCK", "128")
    q, k, v = _qkv(h=4, s=512)
    slopes = jnp.asarray(alibi_slopes(4))
    seg = jnp.concatenate(
        [jnp.zeros((2, 256), jnp.int32), jnp.ones((2, 256), jnp.int32)], axis=1
    )
    out = flash_attention(
        q, k, v, True, seg, None, True, alibi_slopes=slopes
    )
    ref = mha_reference(q, k, v, causal=True, segment_ids=seg, alibi_slopes=slopes)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_alibi_multiblock_grads(monkeypatch):
    from deepspeed_tpu.models.transformer import alibi_slopes

    monkeypatch.setenv("DSTPU_FLASH_BLOCK", "128")
    q, k, v = _qkv(b=1, h=2, s=384, d=64)
    slopes = jnp.asarray(alibi_slopes(2))

    def loss_flash(q, k, v):
        return jnp.sum(jnp.square(
            flash_attention(q, k, v, True, None, None, True, alibi_slopes=slopes)
        ))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(
            mha_reference(q, k, v, causal=True, alibi_slopes=slopes)
        ))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=5e-4, atol=5e-4, err_msg=f"d{name}"
        )


# ---------------------------------------------------------------------------
# sliding-window (banded) attention — mistral/starcoder2/gpt_neo local
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("window", [8, 100, 256])
def test_window_forward_matches_reference(window):
    """Static window (every layer banded): in-kernel band mask, including
    windows smaller than, not dividing, and equal to the block size."""
    q, k, v = _qkv(s=256)
    out = flash_attention(q, k, v, True, None, None, True, window=window)
    ref = mha_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_window_multiblock_prunes_and_matches(monkeypatch):
    """128-blocks at s=512 (4x4 grid) with window 128: out-of-band kv blocks
    are pruned via the clamped index maps — parity proves the pruning drops
    no in-band block (fwd + grads through both bwd kernels)."""
    monkeypatch.setenv("DSTPU_FLASH_BLOCK", "128")
    q, k, v = _qkv(b=1, h=2, s=512, d=64)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.square(
            flash_attention(q, k, v, True, None, None, True, window=128)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(mha_reference(q, k, v, causal=True, window=128)))

    out = flash_attention(q, k, v, True, None, None, True, window=128)
    ref = mha_reference(q, k, v, causal=True, window=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=5e-4, atol=5e-4, err_msg=f"d{name}"
        )


def test_window_odd_band_multiblock(monkeypatch):
    """A window (96) that straddles block boundaries: partial blocks keep
    in-kernel masking while whole out-of-band blocks are pruned."""
    monkeypatch.setenv("DSTPU_FLASH_BLOCK", "128")
    q, k, v = _qkv(b=1, h=2, s=512, d=64)
    out = flash_attention(q, k, v, True, None, None, True, window=96)
    ref = mha_reference(q, k, v, causal=True, window=96)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("flag", [0, 1])
def test_window_traced_flag(flag, monkeypatch):
    """Traced per-layer flag (gpt_neo alternating): flag=1 == banded
    reference, flag=0 == plain causal — through jit so the flag is traced."""
    monkeypatch.setenv("DSTPU_FLASH_BLOCK", "128")
    q, k, v = _qkv(b=1, h=2, s=256, d=64)

    @jax.jit
    def run(f):
        return flash_attention(q, k, v, True, None, None, True,
                               window=64, window_flag=f)

    out = run(jnp.int32(flag))
    ref = mha_reference(q, k, v, causal=True, window=64 if flag else 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_window_traced_flag_grads(monkeypatch):
    monkeypatch.setenv("DSTPU_FLASH_BLOCK", "128")
    q, k, v = _qkv(b=1, h=2, s=256, d=64)

    def loss_flash(q, k, v, f):
        return jnp.sum(jnp.square(
            flash_attention(q, k, v, True, None, None, True, window=64, window_flag=f)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(mha_reference(q, k, v, causal=True, window=64)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v, jnp.int32(1))
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=5e-4, atol=5e-4, err_msg=f"d{name}"
        )


def test_window_gqa_segments_combo(monkeypatch):
    """Window + GQA + packed segments compose in one kernel call."""
    monkeypatch.setenv("DSTPU_FLASH_BLOCK", "128")
    q, k, v = _qkv(b=1, h=4, h_kv=2, s=256, d=64)
    seg = _packed_segments(1, 256, n_seg=2)
    out = flash_attention(q, k, v, True, seg, None, True, window=64)
    ref = mha_reference(q, k, v, causal=True, segment_ids=seg, window=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# the backward: one kernel where a head's dq accumulator may stay in VMEM,
# the dq and dk/dv kernels above that; same sums in the same order
# ---------------------------------------------------------------------------
_BWD_VARIANTS = {
    "plain": {},
    "gqa": {"h_kv": 2},
    "segments": {"segments": True},
    "alibi": {"alibi": True},
    "window": {"window": 160},           # straddles the 128 blocks: pruning + masks
    "window_flag0": {"window": 160, "window_flag": 0},
    "window_flag1": {"window": 160, "window_flag": 1},
}


@pytest.mark.parametrize("causal, variant", [
    (causal, name) for causal in (True, False) for name in _BWD_VARIANTS
    if causal or "window" not in name])     # a window needs causal
def test_fused_backward_equals_the_two_kernels_bitwise(monkeypatch, causal, variant):
    """dq, dk, dv of ``dstpu_flash_bwd_fused`` against those of
    ``dstpu_flash_bwd_dq`` + ``dstpu_flash_bwd_dkv`` at one block size (128 at
    s = 512: a 4 x 4 grid, so every accumulator sums over several blocks):
    equal to the last bit, whatever masks the pair carries."""
    from deepspeed_tpu.models.transformer import alibi_slopes

    monkeypatch.setenv("DSTPU_FLASH_BLOCK", "128")
    spec = dict(_BWD_VARIANTS[variant])
    q, k, v = _qkv(b=1, h=4, h_kv=spec.pop("h_kv", None), s=512, d=64)
    g = jax.random.normal(jax.random.key(11), q.shape, q.dtype)
    kw = {}
    if spec.pop("segments", False):
        kw["segment_ids"] = _packed_segments(1, 512, n_seg=3)
    if spec.pop("alibi", False):
        kw["alibi_slopes"] = jnp.asarray(alibi_slopes(4))
    if "window_flag" in spec:
        spec["window_flag"] = jnp.int32(spec["window_flag"])
    kw.update(spec)

    def grads(budget):
        monkeypatch.setattr(flash_pallas, "DQ_RESIDENT_BYTES", budget)
        run = lambda q, k, v: flash_attention(q, k, v, causal=causal, interpret=True, **kw)
        text = str(jax.make_jaxpr(lambda q, k, v: jax.vjp(run, q, k, v)[1](g))(q, k, v))
        return jax.vjp(run, q, k, v)[1](g), text

    fused, fused_text = grads(flash_pallas.DQ_RESIDENT_BYTES)
    two, two_text = grads(0)
    assert flash_pallas.FLASH_BWD_FUSED in fused_text and flash_pallas.FLASH_BWD_DQ not in fused_text
    assert flash_pallas.FLASH_BWD_DKV in two_text and flash_pallas.FLASH_BWD_FUSED not in two_text
    for a, b_, name in zip(fused, two, "qkv"):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_), err_msg=f"d{name}")
    assert all(np.abs(np.asarray(a)).max() > 0 for a in fused)


@pytest.mark.parametrize("s, d, kernels", [
    (4096, 128, {flash_pallas.FLASH_BWD_FUSED}),                       # 2 MiB: at the budget
    (8192, 64, {flash_pallas.FLASH_BWD_FUSED}),
    (8192, 128, {flash_pallas.FLASH_BWD_DQ, flash_pallas.FLASH_BWD_DKV}),   # 4 MiB: above it
    (16384, 64, {flash_pallas.FLASH_BWD_DQ, flash_pallas.FLASH_BWD_DKV}),
])
def test_the_backward_kernels_are_chosen_by_the_shape(s, d, kernels):
    """Which backward kernels the traced gradient holds is read off the shape:
    the fused one where a head's [s, d] f32 dq accumulator fits
    ``DQ_RESIDENT_BYTES``, the two streaming kernels above it. Traced only."""
    import re

    q = jax.ShapeDtypeStruct((1, 2, s, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 1, s, d), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True).astype(jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv))
    assert (s * d * 4 <= flash_pallas.DQ_RESIDENT_BYTES) == (kernels == {flash_pallas.FLASH_BWD_FUSED})
    assert set(re.findall(r"dstpu_flash_bwd\w*", text)) == kernels
