"""Evoformer attention (DS4Science) as a Pallas TPU kernel.

Reference: ``csrc/deepspeed4science/evoformer_attn/`` (CUTLASS fwd/bwd,
~15k LoC) wrapped by ``deepspeed/ops/deepspeed4science/evoformer_attn.py``
(``DS4Sci_EvoformerAttention(Q, K, V, [bias1, bias2])``). Evoformer MSA-row /
triangle attention is softmax(QKᵀ·scale + bias₁ + bias₂)V where bias₁ is a
per-row padding mask [b, 1, 1, s] and bias₂ the pair-representation bias
[b or 1, h, s, s]; both need gradients (bias₂'s grad feeds the pair stack).

TPU-native: flash-style online softmax with the combined bias streamed in
per q-block row ([bq, s] slab — evoformer s is hundreds, so VMEM-friendly),
plus a bwd pass that also emits dBias (= dS) row slabs. Broadcasting of each
input bias and the corresponding gradient reduction happen at the jnp level.
"""

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
LANES = 128
# names of the Mosaic custom calls in a device trace (metadata only)
EVOFORMER_FWD = "dstpu_evoformer_fwd"
EVOFORMER_BWD_DQ = "dstpu_evoformer_bwd_dq"
EVOFORMER_BWD_DKV = "dstpu_evoformer_bwd_dkv"


def _fwd_kernel(q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref, *, scale, bq, bk):
    # q_ref: [bq, d]; k/v_ref: [s, d]; b_ref: [bq, s]; outputs like flash
    s = k_ref.shape[0]
    d = q_ref.shape[1]
    nk = s // bk
    q = q_ref[:].astype(jnp.float32) * scale

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)

    def body(ki, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(ki * bk, bk), :].astype(jnp.float32)
        v = v_ref[pl.ds(ki * bk, bk), :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) + b_ref[:, pl.ds(ki * bk, bk)].astype(jnp.float32)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, nk, body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[:] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[:] = jnp.broadcast_to((m + jnp.log(l_safe))[:, None], (bq, LANES))


def _bwd_dq_kernel(q_ref, k_ref, v_ref, b_ref, o_ref, do_ref, lse_ref, dq_ref, db_ref,
                   *, scale, bq, bk):
    s = k_ref.shape[0]
    d = q_ref.shape[1]
    nk = s // bk
    q = q_ref[:].astype(jnp.float32) * scale
    do = do_ref[:].astype(jnp.float32)
    lse = lse_ref[:, 0]
    delta = jnp.sum(do * o_ref[:].astype(jnp.float32), axis=-1)

    def body(ki, dq):
        k = k_ref[pl.ds(ki * bk, bk), :].astype(jnp.float32)
        v = v_ref[pl.ds(ki * bk, bk), :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) + b_ref[:, pl.ds(ki * bk, bk)].astype(jnp.float32)
        p = jnp.exp(logits - lse[:, None])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None])  # [bq, bk] — also the bias gradient
        db_ref[:, pl.ds(ki * bk, bk)] = ds.astype(db_ref.dtype)
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    dq = jax.lax.fori_loop(0, nk, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[:] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, b_ref, o_ref, do_ref, lse_ref, dk_ref, dv_ref,
                    *, scale, bq, bk):
    ki = pl.program_id(2)
    sq = q_ref.shape[0]
    d = k_ref.shape[1]
    nq = sq // bq
    k = k_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)

    def body(qj, carry):
        dk, dv = carry
        q = q_ref[pl.ds(qj * bq, bq), :].astype(jnp.float32) * scale
        do = do_ref[pl.ds(qj * bq, bq), :].astype(jnp.float32)
        o = o_ref[pl.ds(qj * bq, bq), :].astype(jnp.float32)
        lse = lse_ref[pl.ds(qj * bq, bq), 0]
        delta = jnp.sum(do * o, axis=-1)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) + b_ref[pl.ds(qj * bq, bq), :].astype(jnp.float32)
        p = jnp.exp(logits - lse[:, None])
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

    zeros = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, nq, body, (zeros, zeros))
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _pick_block(s):
    b = min(256, s)
    while s % b:
        b //= 2
    return max(b, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _evo_core(q, k, v, bias, scale, interpret):
    out, _ = _evo_fwd(q, k, v, bias, scale, interpret)
    return out


def _evo_call(q, k, v, bias, scale, interpret):
    b, h, s, d = q.shape
    bq = _pick_block(s)
    bk = _pick_block(s)
    kernel = functools.partial(_fwd_kernel, scale=scale, bq=bq, bk=bk)
    out, lse = pl.pallas_call(
        lambda qr, kr, vr, br, orf, lr: kernel(
            qr.at[0, 0], kr.at[0, 0], vr.at[0, 0], br.at[0, 0], orf.at[0, 0], lr.at[0, 0]
        ),
        grid=(b, h, s // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, s, d), lambda b_, h_, i: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, s, d), lambda b_, h_, i: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, bq, s), lambda b_, h_, i: (b_, h_, i, 0)),
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
        name=EVOFORMER_FWD,
    )(q, k, v, bias)
    return out, lse


def _evo_fwd(q, k, v, bias, scale, interpret):
    out, lse = _evo_call(q, k, v, bias, scale, interpret)
    return out, (q, k, v, bias, out, lse)


def _evo_bwd(scale, interpret, res, g):
    q, k, v, bias, out, lse = res
    b, h, s, d = q.shape
    bq = _pick_block(s)
    bk = _pick_block(s)

    dq_kernel = functools.partial(_bwd_dq_kernel, scale=scale, bq=bq, bk=bk)
    dq, dbias = pl.pallas_call(
        lambda qr, kr, vr, br, orf, dor, lr, dqr, dbr: dq_kernel(
            qr.at[0, 0], kr.at[0, 0], vr.at[0, 0], br.at[0, 0], orf.at[0, 0],
            dor.at[0, 0], lr.at[0, 0], dqr.at[0, 0], dbr.at[0, 0]
        ),
        grid=(b, h, s // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, s, d), lambda b_, h_, i: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, s, d), lambda b_, h_, i: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, bq, s), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bq, LANES), lambda b_, h_, i: (b_, h_, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bq, s), lambda b_, h_, i: (b_, h_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, s, s), jnp.float32),
        ],
        interpret=interpret,
        name=EVOFORMER_BWD_DQ,
    )(q, k, v, bias, out, g, lse)

    dkv_kernel = functools.partial(_bwd_dkv_kernel, scale=scale, bq=bq, bk=bk)
    dk, dv = pl.pallas_call(
        lambda qr, kr, vr, br, orf, dor, lr, dkr, dvr: dkv_kernel(
            qr.at[0, 0], kr.at[0, 0], vr.at[0, 0], br.at[0, 0], orf.at[0, 0],
            dor.at[0, 0], lr.at[0, 0], dkr.at[0, 0], dvr.at[0, 0]
        ),
        grid=(b, h, s // bk),
        in_specs=[
            pl.BlockSpec((1, 1, s, d), lambda b_, h_, i: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, s, bk), lambda b_, h_, i: (b_, h_, 0, i)),
            pl.BlockSpec((1, 1, s, d), lambda b_, h_, i: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, s, d), lambda b_, h_, i: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, s, LANES), lambda b_, h_, i: (b_, h_, 0, 0)),
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
        name=EVOFORMER_BWD_DKV,
    )(q, k, v, bias, out, g, lse)
    return dq, dk, dv, dbias


_evo_core.defvjp(_evo_fwd, _evo_bwd)


def DS4Sci_EvoformerAttention(Q, K, V, biases: Optional[List] = None,
                              interpret: bool = False):
    """Reference-parity entry (ops/deepspeed4science/evoformer_attn.py):
    Q/K/V: [*, s, h, d] with arbitrary leading batch dims (MSA layout);
    ``biases``: up to two additive biases broadcastable to [*, h, s, s]
    (padding mask + pair bias). Returns [*, s, h, d]; bias gradients flow
    (reduced over broadcast dims by JAX's transpose of broadcast_to)."""
    biases = biases or []
    *lead, s, h, d = Q.shape
    b = 1
    for x in lead:
        b *= x
    # [*, s, h, d] -> [b, h, s, d]
    q = jnp.moveaxis(Q.reshape(b, s, h, d), 1, 2)
    k = jnp.moveaxis(K.reshape(b, s, h, d), 1, 2)
    v = jnp.moveaxis(V.reshape(b, s, h, d), 1, 2)
    bias = jnp.zeros((b, h, s, s), jnp.float32)
    for extra in biases:
        # reference bias shapes broadcast against [*lead, h, s, s]
        eb = jnp.broadcast_to(extra.astype(jnp.float32), tuple(lead) + (h, s, s))
        bias = bias + eb.reshape(b, h, s, s)
    scale = d**-0.5
    out = _evo_core(q, k, v, bias, scale, interpret)
    return jnp.moveaxis(out, 1, 2).reshape(*lead, s, h, d)


def evoformer_reference(Q, K, V, biases=None):
    """Dense jnp reference for numerics tests."""
    biases = biases or []
    *lead, s, h, d = Q.shape
    q = jnp.einsum("...shd->...hsd", Q)
    k = jnp.einsum("...shd->...hsd", K)
    v = jnp.einsum("...shd->...hsd", V)
    logits = jnp.einsum("...hqd,...hkd->...hqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * (d**-0.5)
    for bb in biases:
        logits = logits + bb.astype(jnp.float32)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("...hqk,...hkd->...hqd", w.astype(v.dtype), v)
    return jnp.einsum("...hsd->...shd", out)
