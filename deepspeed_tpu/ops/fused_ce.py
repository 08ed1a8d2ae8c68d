"""Fused cross-entropy over a tiled vocabulary projection (Pallas).

The lm-head + loss is HBM-bound: materializing [b*s, V] logits (V=32k) costs
~6 GB of traffic per step at the bench config (PERF.md item 3). This kernel
fuses the head matmul with an online log-softmax, flash-attention style:
the grid walks (row-block, vocab-tile) with the vocab dimension minor, so
only one [h, bv] weight tile is VMEM-resident at a time while the running
max / sum-exp / target-logit accumulators live in the output blocks (which
Pallas keeps resident across the inner vocab iterations).

Reference analogue: the fused softmax/CE losses in the reference's training
kernels (csrc/transformer/ softmax + the ALST TiledFusedLogitsLoss
runtime/sequence_parallel/ulysses_sp.py:960, which tiles at the jnp level;
this is the kernel-level version).

fwd:  loss_i = lse_i - logit_i[label_i]   (per row; caller masks/means)
bwd:  dx = (softmax - onehot) @ Wᵀ · dloss ; dW = xᵀ (softmax - onehot)·dloss
      — recomputed tile-by-tile from the saved lse, two passes like flash.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
# names of the Mosaic custom calls in a device trace (metadata only)
CE_FWD = "dstpu_ce_fwd"
CE_BWD_DX = "dstpu_ce_bwd_dx"
CE_BWD_DW = "dstpu_ce_bwd_dw"


def _pick(n, target, multiple=1):
    """Largest divisor of n that is <= target and a multiple of ``multiple``
    (Pallas TPU wants block dims divisible by (8, 128)); falls back to the
    largest plain divisor (== n covers the 'whole array' escape hatch)."""
    best = 0
    d = 1
    while d * d <= n:
        if n % d == 0:
            for c in (d, n // d):
                if c <= target and c % multiple == 0:
                    best = max(best, c)
        d += 1
    if best:
        return best
    # no aligned divisor: largest divisor <= target (tiny/odd test shapes)
    best = 1
    d = 1
    while d * d <= n:
        if n % d == 0:
            for c in (d, n // d):
                if c <= target:
                    best = max(best, c)
        d += 1
    return best


def _fwd_kernel(x_ref, w_ref, lbl_ref, loss_ref, lse_ref, acc_ref, *, bn, bv, nv):
    # grid (rows, vocab); vocab minor. x_ref: [bn, h]; w_ref: [h, bv] (tile j)
    # lbl_ref: [1, bn]; acc_ref (scratch, persists over j): [bn, 3*LANES]
    # holding [m | l | tgt] in its three LANES-wide columns.
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:, :LANES] = jnp.full((bn, LANES), -1e30, jnp.float32)
        acc_ref[:, LANES:] = jnp.zeros((bn, 2 * LANES), jnp.float32)

    # feed the MXU the native (bf16) operands with an fp32 accumulator —
    # fp32 VMEM copies of x/w would blow the scoped-vmem budget
    x = x_ref[:]
    w = w_ref[:]
    lbl = lbl_ref[0, :]
    logits = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [bn, bv] fp32
    m = acc_ref[:, 0]
    l = acc_ref[:, LANES]
    tgt = acc_ref[:, 2 * LANES]
    m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(jnp.exp(logits - m_new[:, None]), axis=-1)
    cols = j * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    hit = cols == lbl[:, None]
    tgt_new = tgt + jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
    acc_ref[:, :LANES] = jnp.broadcast_to(m_new[:, None], (bn, LANES))
    acc_ref[:, LANES:2 * LANES] = jnp.broadcast_to(l_new[:, None], (bn, LANES))
    acc_ref[:, 2 * LANES:] = jnp.broadcast_to(tgt_new[:, None], (bn, LANES))

    @pl.when(j == nv - 1)
    def _done():
        lse = m_new + jnp.log(jnp.maximum(l_new, 1e-30))
        loss_ref[:] = jnp.broadcast_to((lse - tgt_new)[:, None], (bn, LANES))
        lse_ref[:] = jnp.broadcast_to(lse[:, None], (bn, LANES))


def _bwd_dx_kernel(x_ref, w_ref, lbl_ref, lse_ref, g_ref, dx_ref, acc_ref, *, bn, bv, nv):
    # grid (rows, vocab); fp32 scratch accumulates across vocab tiles — a
    # bf16 += per tile would round 100+ times and corrupt the gradient
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x = x_ref[:]
    w = w_ref[:]
    lbl = lbl_ref[0, :]
    lse = lse_ref[:, 0]
    g = g_ref[:, 0]
    logits = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    p = jnp.exp(logits - lse[:, None])
    cols = j * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    d = (p - (cols == lbl[:, None]).astype(jnp.float32)) * g[:, None]
    # d in the operand dtype (matches what XLA autodiff of a bf16 matmul
    # feeds its transpose); accumulation stays fp32 in scratch
    acc_ref[:] += jax.lax.dot_general(
        d.astype(w.dtype), w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(j == nv - 1)
    def _done():
        dx_ref[:] = acc_ref[:].astype(dx_ref.dtype)


def _bwd_dw_kernel(x_ref, w_ref, lbl_ref, lse_ref, g_ref, dw_ref, acc_ref, *, bn, bv, nr):
    # grid (vocab, rows); fp32 scratch accumulates across row blocks
    vj = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x = x_ref[:]
    w = w_ref[:]
    lbl = lbl_ref[0, :]
    lse = lse_ref[:, 0]
    g = g_ref[:, 0]
    logits = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    p = jnp.exp(logits - lse[:, None])
    cols = vj * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    d = (p - (cols == lbl[:, None]).astype(jnp.float32)) * g[:, None]
    acc_ref[:] += jax.lax.dot_general(
        x, d.astype(x.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(i == nr - 1)
    def _done():
        dw_ref[:] = acc_ref[:].astype(dw_ref.dtype)


def fused_ce_loss(x: jax.Array, w: jax.Array, labels: jax.Array,
                  interpret: bool = False) -> jax.Array:
    """Per-row cross-entropy of ``softmax(x @ w)`` against ``labels`` without
    materializing the [n, V] logits. x: [n, h]; w: [h, V]; labels: [n] int32
    → loss [n] fp32. Differentiable in x and w."""
    return _ce_core(x, w, labels, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ce_core(x, w, labels, interpret):
    out, _ = _ce_fwd(x, w, labels, interpret)
    return out


def _ce_call(x, w, labels, interpret):
    from jax.experimental.pallas import tpu as pltpu

    n, h = x.shape
    V = w.shape[1]
    bn = _pick(n, 256, multiple=8)
    bv = _pick(V, 2048, multiple=128)
    nv = V // bv
    kernel = functools.partial(_fwd_kernel, bn=bn, bv=bv, nv=nv)
    loss, lse = pl.pallas_call(
        kernel,
        grid=(n // bn, nv),
        in_specs=[
            pl.BlockSpec((bn, h), lambda i, j: (i, 0)),
            pl.BlockSpec((h, bv), lambda i, j: (0, j)),
            # [1, n] layout: 1-D int32 blocks trip Mosaic's tiling; a
            # lanes-minor 2-D block matches the XLA layout
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((bn, LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, LANES), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, LANES), jnp.float32),
            jax.ShapeDtypeStruct((n, LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bn, 3 * LANES), jnp.float32)],
        interpret=interpret,
        name=CE_FWD,
    )(x, w, labels.astype(jnp.int32).reshape(1, -1))
    return loss[:, 0], lse


def _ce_fwd(x, w, labels, interpret):
    loss, lse = _ce_call(x, w, labels, interpret)
    return loss, (x, w, labels, lse)


def _ce_bwd(interpret, res, g):
    from jax.experimental.pallas import tpu as pltpu

    x, w, labels, lse = res
    n, h = x.shape
    V = w.shape[1]
    bn = _pick(n, 256, multiple=8)
    bv = _pick(V, 2048, multiple=128)
    nv = V // bv
    # the dW pass holds an [h, bv] fp32 scratch accumulator — cap its vocab
    # tile so scratch + weight tile fit scoped VMEM
    bv_w = _pick(V, 512, multiple=128)
    nv_w = V // bv_w
    nr = n // bn
    g2 = jnp.broadcast_to(g.astype(jnp.float32)[:, None], (n, LANES))
    lbl2 = labels.astype(jnp.int32).reshape(1, -1)

    dx = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, bn=bn, bv=bv, nv=nv),
        grid=(nr, nv),
        in_specs=[
            pl.BlockSpec((bn, h), lambda i, j: (i, 0)),
            pl.BlockSpec((h, bv), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
            pl.BlockSpec((bn, LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, LANES), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, h), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((bn, h), jnp.float32)],
        interpret=interpret,
        name=CE_BWD_DX,
    )(x, w, lbl2, lse, g2)

    dw = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, bn=bn, bv=bv_w, nr=nr),
        grid=(nv_w, nr),
        in_specs=[
            pl.BlockSpec((bn, h), lambda j, i: (i, 0)),
            pl.BlockSpec((h, bv_w), lambda j, i: (0, j)),
            pl.BlockSpec((1, bn), lambda j, i: (0, i)),
            pl.BlockSpec((bn, LANES), lambda j, i: (i, 0)),
            pl.BlockSpec((bn, LANES), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((h, bv_w), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct(w.shape, w.dtype),
        scratch_shapes=[pltpu.VMEM((h, bv_w), jnp.float32)],
        interpret=interpret,
        name=CE_BWD_DW,
    )(x, w, lbl2, lse, g2)
    return dx, dw, None  # labels get no cotangent


_ce_core.defvjp(_ce_fwd, _ce_bwd)


def fused_ce_reference(x, w, labels):
    """Dense jnp reference for numerics tests."""
    logits = (x.astype(jnp.float32) @ w.astype(jnp.float32))
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, labels[:, None].astype(jnp.int32), axis=1)[:, 0]
    return lse - tgt
