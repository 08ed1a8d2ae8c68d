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


# -- the three classes of a causal block pair (PR 52) -------------------------------------------
# Blocks of 256 are cut into strips of 128 (two tiles a side) and blocks of 512
# into four: at 3 x 3 and 2 x 2 blocks a sequence has pairs above, under and on
# the diagonal. The backward kernels class and cut; the forward runs every pair
# whole under the mask, as before.

@pytest.mark.parametrize("s, bq, bk, want", [
    # the train cells: 6 / 6 / 4, strips of 256, 40 of 64 tiles, 8.5 of 10 pairs' products
    (4096, 1024, 1024, (6, 6, 4, 256, 40, 64, 8.5)),
    (768, 256, 256, (3, 3, 3, 128, 9, 12, 5.25)),
    (1024, 512, 512, (1, 1, 2, 128, 20, 32, 2.25)),
    # blocks that differ are not cut: the pairs the diagonal crosses run whole
    (4096, 1024, 512, (12, 12, 8, 0, 8, 8, 20.0)),
    # a block too small to hold two 128-lane tiles runs whole too
    (512, 128, 128, (6, 6, 4, 0, 4, 4, 10.0)),
    (384, 384, 384, (0, 0, 1, 128, 6, 9, 6 / 9)),   # not a power of two: three strips
    (320, 320, 320, (0, 0, 1, 0, 1, 1, 1.0)),       # 128 does not divide it
])
def test_causal_pair_classes(s, bq, bk, want):
    got = flash_pallas.causal_pair_classes(s, bq, bk)
    assert tuple(got) + (got.pairs_of_products,) == want
    assert got.pruned + got.under + got.diagonal == (s // bq) * (s // bk)


def _classes_traced(monkeypatch):
    """Record, while the kernels are traced, the class of every body they build
    and the number of rectangles it is computed by."""
    seen = []
    inner = flash_pallas._pair_strips

    def spy(cls, *a, **kw):
        strips = inner(cls, *a, **kw)
        seen.append((cls, len(strips)))
        return strips

    monkeypatch.setattr(flash_pallas, "_pair_strips", spy)
    return seen


_CLASS_VARIANTS = {
    "causal": {},
    "segments": {"segments": True},
    "alibi": {"alibi": True},
    "gqa": {"h_kv": 2},
    "gqa_segments_alibi": {"h_kv": 2, "segments": True, "alibi": True},
}


def _class_case(variant, s):
    from deepspeed_tpu.models.transformer import alibi_slopes

    spec = dict(_CLASS_VARIANTS[variant])
    q, k, v = _qkv(b=1, h=4, h_kv=spec.pop("h_kv", None), s=s, d=64, seed=3)
    kw = {}
    if spec.pop("segments", False):
        kw["segment_ids"] = _packed_segments(1, s, n_seg=3)
    if spec.pop("alibi", False):
        kw["alibi_slopes"] = jnp.asarray(alibi_slopes(4))
    return q, k, v, kw


@pytest.mark.parametrize("block, s", [(256, 768), (512, 1024)])
@pytest.mark.parametrize("variant", list(_CLASS_VARIANTS))
def test_causal_classes_match_reference(monkeypatch, variant, block, s):
    """Forward and the three gradients against the float32 reference where a
    sequence has pairs of all three classes and a diagonal pair is cut into
    strips by the backward kernels: what a strip leaves out is an exact zero."""
    monkeypatch.setenv("DSTPU_FLASH_BLOCK", str(block))
    classes = flash_pallas.causal_pair_classes(s, block, block)
    assert min(classes.pruned, classes.under, classes.diagonal) >= 1
    assert classes.strip and block // classes.strip >= 2
    seen = _classes_traced(monkeypatch)
    q, k, v, kw = _class_case(variant, s)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=True, **kw)
        return jnp.sum(jnp.square(out)), out

    def loss_ref(q, k, v):
        out = mha_reference(q, k, v, causal=True, **kw)
        return jnp.sum(jnp.square(out)), out

    gf, out = jax.grad(loss_flash, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    gr, ref = jax.grad(loss_ref, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=1e-3, atol=1e-3, err_msg=f"d{name}")
    # the backward built two bodies: no causal mask under the diagonal, strips on
    # it (the forward classes nothing: every pair whole, in one body)
    assert set(seen) == {(None, 1), ("cut", block // classes.strip)}


@pytest.mark.parametrize("window_flag", [None, 0, 1], ids=["static", "wflag0", "wflag1"])
def test_a_window_keeps_the_whole_pair_path(monkeypatch, window_flag):
    """A window, static or toggled by ``wflag``, needs the positions in every
    pair: blocks that would be cut run whole under the masks, as before, and
    still match the reference."""
    monkeypatch.setenv("DSTPU_FLASH_BLOCK", "256")
    s, window = 768, 200
    assert flash_pallas.causal_pair_classes(s, 256, 256).strip == 128
    seen = _classes_traced(monkeypatch)
    q, k, v = _qkv(b=1, h=2, s=s, d=64, seed=5)
    kw = {} if window_flag is None else {"window_flag": jnp.int32(window_flag)}
    ref_window = 0 if window_flag == 0 else window

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=window, interpret=True, **kw)
        return jnp.sum(jnp.square(out)), out

    def loss_ref(q, k, v):
        out = mha_reference(q, k, v, causal=True, window=ref_window)
        return jnp.sum(jnp.square(out)), out

    gf, out = jax.grad(loss_flash, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    gr, ref = jax.grad(loss_ref, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=1e-3, atol=1e-3, err_msg=f"d{name}")
    assert set(seen) == {("masked", 1)}


@pytest.mark.parametrize("variant", list(_CLASS_VARIANTS))
def test_the_backward_kernels_share_the_strips_bitwise(monkeypatch, variant):
    """The bitwise test above runs blocks of 128, which are not cut. Here the
    diagonal pairs go by strips (blocks of 256 at s = 768): the fused kernel
    against the dq and dk/dv kernels, which take their products from the same
    strips in the same order, to the last bit."""
    monkeypatch.setenv("DSTPU_FLASH_BLOCK", "256")
    q, k, v, kw = _class_case(variant, 768)
    g = jax.random.normal(jax.random.key(11), q.shape, q.dtype)
    run = lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=True, **kw)

    def grads(budget):
        monkeypatch.setattr(flash_pallas, "DQ_RESIDENT_BYTES", budget)
        return jax.vjp(run, q, k, v)[1](g)

    fused = grads(flash_pallas.DQ_RESIDENT_BYTES)
    two = grads(0)
    for a, b_, name in zip(fused, two, "qkv"):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_), err_msg=f"d{name}")
    assert all(np.abs(np.asarray(a)).max() > 0 for a in fused)


@pytest.mark.parametrize("layout, shape, kw", [
    ("head_major", (1, 2, 768, 64), {}),
    ("head_major", (1, 2, 768, 128), {}),
    ("token_major", (1, 768, 2 * 128), {"head_dim": 128}),
    ("head_major", (1, 768, 2 * 64), {"head_dim": 64}),   # a head of 64 is re-laid
], ids=["bhsd_64", "bhsd_128", "bs_hd_128", "bs_hd_64"])
def test_a_traced_backward_records_its_pair_classes(monkeypatch, layout, shape, kw):
    """Once a traced causal backward the tracer gets ``flash.causal_pairs`` with
    the call's count and the ``layout`` its kernels took; a forward alone
    classes nothing and says its layout in ``flash.layout``, and a windowed
    call, whose pairs all run whole, records no classes."""
    from deepspeed_tpu.observability import tracing

    monkeypatch.setenv("DSTPU_FLASH_BLOCK", "256")
    old = tracing.get_tracer()
    tracer = tracing.set_tracer(tracing.SpanTracer())
    try:
        q = jax.ShapeDtypeStruct(shape, jnp.float32)

        def loss(q, k, v, **more):
            return flash_attention(q, k, v, causal=True, interpret=True, **kw, **more).sum()

        jax.eval_shape(loss, q, q, q)
        jax.eval_shape(jax.grad(functools.partial(loss, window=200)), q, q, q)
        assert [(sp.name, sp.args) for sp in tracer.ring_spans()] == [("flash.layout", {
            "kernel": flash_pallas.FLASH_FWD, "s": 768, "block": 256, "layout": layout})] * 2
        jax.eval_shape(jax.grad(loss), q, q, q)
        spans = [sp for sp in tracer.ring_spans() if sp.name == "flash.causal_pairs"]
    finally:
        tracing.set_tracer(old)
    assert [sp.args for sp in spans] == [{
        "s": 768, "block": 256, "pruned": 3, "under": 3, "diagonal": 3, "strip": 128,
        "live_tiles": 9, "tiles": 12, "pairs_of_products": 5.25, "layout": layout}]


# -- the two operand layouts (PR 59) --------------------------------------------------------------
# Rank 4 is head-major [b, h, s, d]; rank 3 is token-major [b, s, h * d], what the
# projections write, and a head of whole lanes (d % 128 == 0) is indexed out of
# it in place. One set of kernels: the cases below run both forms of each.

_token_major = flash_pallas.token_major


def _head_major(x, d):
    return flash_pallas.head_major(x, d)


_LAYOUT_VARIANTS = {
    "gqa_16_8": {"h": 16, "h_kv": 8},
    "mha_8_8": {"h": 8, "h_kv": 8},
    "segments": {"segments": True},
    "window": {"window": 160},            # static: straddles the 128 blocks, prunes and masks
    "window_flag0": {"window": 160, "window_flag": 0},
    "window_flag1": {"window": 160, "window_flag": 1},
    "alibi": {"alibi": True},
    "above_dq_budget": {"budget": 0},     # dq and dk/dv kernels, as a long sequence takes
}


def _layout_case(variant, d=128, s=256):
    from deepspeed_tpu.models.transformer import alibi_slopes

    spec = dict(_LAYOUT_VARIANTS[variant])
    h = spec.pop("h", 4)
    q, k, v = _qkv(b=2, h=h, h_kv=spec.pop("h_kv", 2), s=s, d=d, seed=9)
    kw, budget = {}, spec.pop("budget", None)
    if spec.pop("segments", False):
        kw["segment_ids"] = _packed_segments(2, s, n_seg=3)
    if spec.pop("alibi", False):
        kw["alibi_slopes"] = jnp.asarray(alibi_slopes(h))
    if "window_flag" in spec:
        spec["window_flag"] = jnp.int32(spec["window_flag"])
    kw.update(spec)
    return q, k, v, kw, budget


def _run_layout(layout, q, k, v, g, d, **kw):
    """(out, dq, dk, dv) of one call in ``layout``, handed back head-major."""
    if layout == "head_major":
        out, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v, interpret=True, **kw), q, k, v)
        return (out,) + tuple(vjp(g))
    out, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v, interpret=True, head_dim=d, **kw),
                       *map(_token_major, (q, k, v)))
    assert out.shape == (q.shape[0], q.shape[2], q.shape[1] * d)
    return tuple(_head_major(x, d) for x in (out,) + tuple(vjp(_token_major(g))))


@pytest.mark.parametrize("variant", list(_LAYOUT_VARIANTS))
@pytest.mark.parametrize("layout", ["head_major", "token_major"])
def test_parity_with_the_reference_in_both_layouts(monkeypatch, layout, variant):
    """Forward and dq / dk / dv against ``mha_reference`` at a head of 128, the
    operands head-major or where the projections wrote them: GQA 16:8 and 8:8,
    packed segments, a static and a flagged window, ALiBi, and the backward on
    each side of ``DQ_RESIDENT_BYTES``."""
    monkeypatch.setenv("DSTPU_FLASH_BLOCK", "128")
    q, k, v, kw, budget = _layout_case(variant)
    if budget is not None:
        monkeypatch.setattr(flash_pallas, "DQ_RESIDENT_BYTES", budget)
    g = jax.random.normal(jax.random.key(12), q.shape, q.dtype)
    got = _run_layout(layout, q, k, v, g, 128, causal=True, **kw)
    ref_kw = dict(kw)
    if "window_flag" in ref_kw and int(ref_kw.pop("window_flag")) == 0:
        ref_kw["window"] = 0
    ref, vjp = jax.vjp(lambda q, k, v: mha_reference(q, k, v, causal=True, **ref_kw), q, k, v)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref), rtol=2e-4, atol=2e-4)
    for a, b_, name in zip(got[1:], vjp(g), "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-3, atol=1e-3,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("variant", ["gqa_16_8", "segments", "window_flag1", "alibi",
                                     "above_dq_budget"])
def test_the_two_layouts_are_bit_equal(monkeypatch, variant):
    """The same blocks through the same kernel bodies, and the group's sum over
    the same terms in the same order: outputs and all three gradients of the
    token-major call equal the head-major call's to the last bit (bf16
    operands, so that a different order of a sum would show). Blocks of 256 at
    s = 768: pairs of all three classes, the diagonal ones cut."""
    monkeypatch.setenv("DSTPU_FLASH_BLOCK", "256")
    q, k, v, kw, budget = _layout_case(variant, s=768)
    if budget is not None:
        monkeypatch.setattr(flash_pallas, "DQ_RESIDENT_BYTES", budget)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    g = jax.random.normal(jax.random.key(12), q.shape, q.dtype)
    head = _run_layout("head_major", q, k, v, g, 128, causal=True, **kw)
    token = _run_layout("token_major", q, k, v, g, 128, causal=True, **kw)
    for a, b_, name in zip(head, token, ("out", "dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b_, np.float32),
                                      err_msg=name)
        assert float(jnp.abs(a.astype(jnp.float32)).max()) > 0


def test_a_head_of_64_keeps_the_head_major_kernels():
    """A head that is not whole lanes cannot be a column of tiles: rank-3
    operands at d = 64 are re-laid to [b, h, s, d] in front of the same
    head-major kernels (every per-head operand of the traced calls is rank 4)
    and the results are the head-major call's, bit for bit."""
    q, k, v = _qkv(b=1, h=4, h_kv=2, s=256, d=64)
    g = jax.random.normal(jax.random.key(12), q.shape, q.dtype)
    head = _run_layout("head_major", q, k, v, g, 64, causal=True)
    token = _run_layout("token_major", q, k, v, g, 64, causal=True)
    for a, b_ in zip(head, token):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))

    def calls(d):
        x = _token_major(_qkv(b=1, h=4, h_kv=2, s=256, d=d)[0])
        kv = x[..., : 2 * d]
        jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=True, head_dim=d).sum(), argnums=(0, 1, 2)))(x, kv, kv)
        return [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]

    assert {e.invars[0].aval.shape for e in calls(64)} == {(1, 4, 256, 64)}
    assert {e.invars[0].aval.shape for e in calls(128)} == {(1, 256, 4 * 128)}
    with pytest.raises(ValueError, match="head_dim"):
        flash_attention(*(_token_major(x) for x in (q, k, v)), causal=True, interpret=True)


def test_heads_view_is_the_same_numbers_a_head_at_a_time():
    """``heads_view`` / ``heads_flat`` / ``tokens_view``: [b, s, heads * d] as
    [b, s / 8, heads, 8, d] and back, a token's value beside its rows; a
    sequence that is not whole tiles takes [b, s, heads, d]."""
    x = jnp.arange(2 * 24 * 3 * 4, dtype=jnp.float32).reshape(2, 24, 12)
    view = flash_pallas.heads_view(x, 3)
    assert view.shape == (2, 3, 3, 8, 4)
    pos = flash_pallas.tokens_view(jnp.arange(24)[None], 24)
    assert pos.shape == (1, 3, 8)
    for t in (0, 7, 8, 23):
        assert int(pos[0, t // 8, t % 8]) == t
        np.testing.assert_array_equal(np.asarray(view[1, t // 8, 2, t % 8]), np.asarray(x[1, t, 8:]))
    np.testing.assert_array_equal(np.asarray(flash_pallas.heads_flat(view)), np.asarray(x))
    odd = x[:, :21]
    assert flash_pallas.heads_view(odd, 3).shape == (2, 21, 3, 4)
    assert flash_pallas.tokens_view(jnp.arange(21)[None], 21).shape == (1, 21)
    np.testing.assert_array_equal(np.asarray(flash_pallas.heads_flat(flash_pallas.heads_view(odd, 3))),
                                  np.asarray(odd))


@pytest.mark.parametrize("layout", ["head_major", "token_major"])
def test_head_sharded_flash_takes_both_layouts(devices8, layout):
    """``head_sharded_flash`` over data = 2 x model = 2 (the batch over the one,
    runs of whole heads over the other, a GQA group inside a shard): rank-4
    operands pinned on the head axis, rank-3 ones on the lanes, the same
    kernels inside the ``shard_map``; forward and gradients against the
    reference."""
    from deepspeed_tpu.ops.attention.sharded import head_sharded_flash
    from deepspeed_tpu.parallel.topology import Topology, reset_topology, set_topology

    reset_topology()
    set_topology(Topology(data=2, model=2, devices=jax.devices()[:4]))
    try:
        q, k, v = _qkv(b=2, h=4, h_kv=2, s=256, d=128)
        seg = _packed_segments(2, 256, n_seg=2)
        g = jax.random.normal(jax.random.key(12), q.shape, q.dtype)
        lay, back = ((lambda x: x), (lambda x: x)) if layout == "head_major" else (
            _token_major, functools.partial(_head_major, d=128))
        kw = {} if layout == "head_major" else {"head_dim": 128}

        @jax.jit
        def run(q, k, v, g):
            out, vjp = jax.vjp(lambda q, k, v: head_sharded_flash(
                q, k, v, causal=True, segment_ids=seg, interpret=True, **kw), q, k, v)
            return (out,) + tuple(vjp(g))

        got = [back(x) for x in run(*map(lay, (q, k, v, g)))]
    finally:
        reset_topology()
    ref, vjp = jax.vjp(lambda q, k, v: mha_reference(q, k, v, causal=True, segment_ids=seg), q, k, v)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref), rtol=2e-4, atol=2e-4)
    for a, b_, name in zip(got[1:], vjp(g), "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-3, atol=1e-3, err_msg=f"d{name}")
