"""Block-sparse flash attention as a Pallas TPU kernel (fwd + bwd).

TPU-native replacement for the reference Triton blocksparse kernels
(``deepspeed/ops/sparse_attention/matmul.py`` sdd/dsd + ``softmax.py``,
backing ``SparseSelfAttention``). Same online-softmax structure as
``ops/attention/flash_pallas.py``, but the kv loop is guarded by a STATIC
per-head block layout: inactive (q-block, k-block) pairs take a
``lax.cond`` branch that skips both MXU matmuls, so sparsity is skipped
work — the compute cost scales with the number of active blocks, not s².

Layout: int32 [h, nq, nk] (see config.py). Causal masking (within-block)
composes with the layout; configs with attention="unidirectional" already
zero the upper-triangular blocks so those are skipped entirely.

NOTE: this is now the ``reference`` oracle. Inactive blocks here still
cost a grid step and K/V streaming (full [s, d] VMEM residency); the
production path is splash_pallas.py, whose compacted schedule never
visits them at all. Parity tests pin the two against each other.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
LANES = 128
# names of the Mosaic custom calls in a device trace (metadata only)
SPARSE_FWD = "dstpu_sparse_fwd"
SPARSE_BWD_DQ = "dstpu_sparse_bwd_dq"
SPARSE_BWD_DKV = "dstpu_sparse_bwd_dkv"


def _sparse_fwd_kernel(q_ref, k_ref, v_ref, lay_ref, o_ref, lse_ref, *, scale, causal, bq, bk):
    # q_ref: [bq, d]; k/v_ref: [s, d]; lay_ref: [nk] int32 (this q-block's row)
    qi = pl.program_id(2)
    s = k_ref.shape[0]
    d = q_ref.shape[1]
    nk = s // bk

    q = q_ref[:].astype(jnp.float32) * scale
    row = lay_ref[:]  # [nk]
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    def compute(ki, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(ki * bk, bk), :].astype(jnp.float32)
        v = v_ref[pl.ds(ki * bk, bk), :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if causal:
            k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            logits = jnp.where(q_pos >= k_pos, logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        # guard: when every visited logit is still NEG_INF, logits - m_new
        # is 0 and exp() would emit 1s — a fully-masked row would then
        # average the masked V instead of producing zeros
        p = jnp.where(logits > NEG_INF / 2, jnp.exp(logits - m_new[:, None]), 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    def body(ki, carry):
        active = jax.lax.dynamic_index_in_dim(row, ki, keepdims=False) != 0
        return jax.lax.cond(active, lambda c: compute(ki, c), lambda c: c, carry)

    m, l, acc = jax.lax.fori_loop(0, nk, body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[:] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[:] = jnp.broadcast_to((m + jnp.log(l_safe))[:, None], (bq, LANES))


def _sparse_bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, lay_ref, dq_ref,
                          *, scale, causal, bq, bk):
    qi = pl.program_id(2)
    s = k_ref.shape[0]
    d = q_ref.shape[1]
    nk = s // bk

    q = q_ref[:].astype(jnp.float32) * scale
    do = do_ref[:].astype(jnp.float32)
    lse = lse_ref[:, 0]
    delta = jnp.sum(do * o_ref[:].astype(jnp.float32), axis=-1)
    row = lay_ref[:]
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    def compute(ki, dq):
        k = k_ref[pl.ds(ki * bk, bk), :].astype(jnp.float32)
        v = v_ref[pl.ds(ki * bk, bk), :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if causal:
            k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            logits = jnp.where(q_pos >= k_pos, logits, NEG_INF)
        p = jnp.where(logits > NEG_INF / 2, jnp.exp(logits - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None])
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    def body(ki, dq):
        active = jax.lax.dynamic_index_in_dim(row, ki, keepdims=False) != 0
        return jax.lax.cond(active, lambda c: compute(ki, c), lambda c: c, dq)

    dq = jax.lax.fori_loop(0, nk, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[:] = (dq * scale).astype(dq_ref.dtype)


def _sparse_bwd_dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, col_ref,
                           dk_ref, dv_ref, *, scale, causal, bq, bk):
    ki = pl.program_id(2)
    sq = q_ref.shape[0]
    d = k_ref.shape[1]
    nq = sq // bq

    k = k_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)
    col = col_ref[:]  # [nq] — which q blocks attend this kv block
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    def compute(qj, carry):
        dk, dv = carry
        q = q_ref[pl.ds(qj * bq, bq), :].astype(jnp.float32) * scale
        do = do_ref[pl.ds(qj * bq, bq), :].astype(jnp.float32)
        o = o_ref[pl.ds(qj * bq, bq), :].astype(jnp.float32)
        lse = lse_ref[pl.ds(qj * bq, bq), 0]
        delta = jnp.sum(do * o, axis=-1)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if causal:
            q_pos = qj * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            logits = jnp.where(q_pos >= k_pos, logits, NEG_INF)
        p = jnp.where(logits > NEG_INF / 2, jnp.exp(logits - lse[:, None]), 0.0)
        dv_new = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None])
        dk_new = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return dk_new, dv_new

    def body(qj, carry):
        active = jax.lax.dynamic_index_in_dim(col, qj, keepdims=False) != 0
        return jax.lax.cond(active, lambda c: compute(qj, c), lambda c: c, carry)

    zeros = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, nq, body, (zeros, zeros))
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _reject_bias(bias, where):
    if bias is not None:
        raise NotImplementedError(
            f"{where}: additive bias is not supported on the block-sparse "
            "kernel path, and the oracle must match the kernel exactly — "
            "use sparse_attention_with_bias (dense fallback) for rpe/"
            "padding/attention masks")


def sparse_attention(q, k, v, layout, block: int, causal: bool = False,
                     scale: Optional[float] = None, interpret: bool = False,
                     bias=None):
    """Block-sparse attention. q/k/v: [b, h, s, d]; layout: [h, nq, nk] int32.

    ``block`` is the layout's block size; kernel blocks equal it (the layout
    IS the tiling). Fully-masked q rows (no active block) produce zeros.
    ``bias`` raises: the kernel cannot honor it, and its oracle
    (``sparse_attention_reference``) refuses it for the same reason."""
    _reject_bias(bias, "sparse_attention")
    layout = jnp.asarray(layout, jnp.int32)
    return _sparse_core(q, k, v, layout, block, causal, scale, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _sparse_core(q, k, v, layout, block, causal, scale, interpret):
    out, _ = _sparse_fwd(q, k, v, layout, block, causal, scale, interpret)
    return out


def _sparse_fwd(q, k, v, layout, block, causal, scale, interpret):
    b, h, s, d = q.shape
    if k.shape[1] != h:
        raise ValueError("sparse kernel expects matched head counts (expand GQA first)")
    if layout.shape != (h, s // block, s // block):
        raise ValueError(f"layout shape {layout.shape} != expected "
                         f"{(h, s // block, s // block)}")
    bq = bk = block
    scale_v = scale if scale is not None else d**-0.5
    kernel = functools.partial(_sparse_fwd_kernel, scale=scale_v, causal=causal, bq=bq, bk=bk)

    out, lse = pl.pallas_call(
        lambda qr, kr, vr, lr_, orf, lsr: kernel(
            qr.at[0, 0], kr.at[0, 0], vr.at[0, 0], lr_.at[0, 0], orf.at[0, 0], lsr.at[0, 0]
        ),
        grid=(b, h, s // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, s, d), lambda b_, h_, i: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, s, d), lambda b_, h_, i: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, s // bk), lambda b_, h_, i: (h_, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bq, LANES), lambda b_, h_, i: (b_, h_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, s, LANES), jnp.float32),
        ],
        interpret=interpret,
        name=SPARSE_FWD,
    )(q, k, v, layout)
    return out, (q, k, v, layout, out, lse)


def _sparse_bwd(block, causal, scale, interpret, res, g):
    q, k, v, layout, out, lse = res
    b, h, s, d = q.shape
    bq = bk = block
    scale_v = scale if scale is not None else d**-0.5

    dq_kernel = functools.partial(_sparse_bwd_dq_kernel, scale=scale_v, causal=causal, bq=bq, bk=bk)
    dq = pl.pallas_call(
        lambda qr, kr, vr, orf, dor, lsr, lr_, dqr: dq_kernel(
            qr.at[0, 0], kr.at[0, 0], vr.at[0, 0], orf.at[0, 0], dor.at[0, 0],
            lsr.at[0, 0], lr_.at[0, 0], dqr.at[0, 0]
        ),
        grid=(b, h, s // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, s, d), lambda b_, h_, i: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, s, d), lambda b_, h_, i: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bq, LANES), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, s // bk), lambda b_, h_, i: (h_, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name=SPARSE_BWD_DQ,
    )(q, k, v, out, g, lse, layout)

    layout_t = jnp.swapaxes(layout, 1, 2)  # [h, nk, nq]
    dkv_kernel = functools.partial(_sparse_bwd_dkv_kernel, scale=scale_v, causal=causal, bq=bq, bk=bk)
    dk, dv = pl.pallas_call(
        lambda qr, kr, vr, orf, dor, lsr, cr, dkr, dvr: dkv_kernel(
            qr.at[0, 0], kr.at[0, 0], vr.at[0, 0], orf.at[0, 0], dor.at[0, 0],
            lsr.at[0, 0], cr.at[0, 0], dkr.at[0, 0], dvr.at[0, 0]
        ),
        grid=(b, h, s // bk),
        in_specs=[
            pl.BlockSpec((1, 1, s, d), lambda b_, h_, i: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, s, d), lambda b_, h_, i: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, s, d), lambda b_, h_, i: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, s, LANES), lambda b_, h_, i: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, s // bq), lambda b_, h_, i: (h_, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i: (b_, h_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(q.shape, q.dtype),
        ],
        interpret=interpret,
        name=SPARSE_BWD_DKV,
    )(q, k, v, out, g, lse, layout_t)
    return dq, dk, dv, None  # layout gets no cotangent


_sparse_core.defvjp(_sparse_fwd, _sparse_bwd)


def sparse_attention_reference(q, k, v, layout, block, causal=False, scale=None, bias=None):
    """Dense jnp oracle for the kernel path: expands the block layout to a
    token mask. ``bias`` raises — the kernel cannot honor it, so accepting
    it here would let oracle and kernel silently diverge; the biased dense
    path lives in ``sparse_attention_with_bias``."""
    _reject_bias(bias, "sparse_attention_reference")
    return _sparse_dense(q, k, v, layout, block, causal, scale, None)


def sparse_attention_with_bias(q, k, v, layout, block, causal=False,
                               scale=None, bias=None):
    """Dense block-masked attention WITH additive bias (broadcastable to
    [b, h, s, s]) — the rpe / key-padding / attention-mask fallback used by
    ``SparseSelfAttention``. Deliberately a separate entry point from the
    kernel oracle so the no-bias pair stays bit-comparable."""
    return _sparse_dense(q, k, v, layout, block, causal, scale, bias)


def _sparse_dense(q, k, v, layout, block, causal, scale, bias):
    h, nq, nk = layout.shape
    mask = jnp.repeat(jnp.repeat(jnp.asarray(layout, bool), block, 1), block, 2)
    d = q.shape[-1]
    scale = scale if scale is not None else d**-0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        s = q.shape[2]
        cm = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(cm[None, None], logits, NEG_INF)
    logits = jnp.where(mask[None], logits, NEG_INF)
    # fully-masked rows: softmax would be uniform garbage; zero them like the kernel
    alive = jnp.any(logits > NEG_INF / 2, axis=-1)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", w.astype(v.dtype), v)
    return jnp.where(alive[..., None], out, 0.0)
