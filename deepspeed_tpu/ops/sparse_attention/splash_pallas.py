"""Splash-style scheduled block-sparse flash attention (fwd + bwd).

Where sparse_pallas.py (kept as the ``reference`` oracle) iterates EVERY
kv block and skips inactive ones under ``lax.cond`` — paying a grid step
and an HBM stream per masked block — this kernel iterates a compacted
schedule (schedule.py): the fwd grid is ``(b, h, nq, width)`` with
``width`` = the densest row's active-block count, and a scalar-prefetched
``kv_index`` array drives the K/V BlockSpec index maps. A fully-masked
block is never scheduled, never streamed; cost scales with layout
density, not s².

Per-step ``step_kind`` ∈ {0 skip, 1 partial, 2 full}:
  * skip — padding up to ``width``; kv_index repeats the previous block so
    the index map output is unchanged and Pallas elides the copy;
  * partial — the analytic token predicate (causal edge / window band /
    segment equality) is applied in-kernel;
  * full — no mask application at all. When a schedule has zero partial
    steps the masking code is not even compiled (``has_partial`` is
    static).

K/V are streamed one ``[bk, d]`` block per grid step — there is no
full-K/V VMEM residency, which is also what lets the dense-causal s≥16k
configuration fit (the CausalMask schedule IS the dense long-seq path);
``vmem_limit_bytes`` caps the compiler's scoped-vmem budget per kernel.

Backward runs the same machinery: dq over the row schedule, dk/dv over
the transposed (per-kv-block) schedule, GQA group-reduced like
flash_pallas.
"""

import dataclasses
import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator.device import on_tpu
from deepspeed_tpu.ops.sparse_attention.mask import FULL
from deepspeed_tpu.ops.sparse_attention.schedule import BlockSchedule

NEG_INF = -1e30
LANES = 128
# names of the Mosaic custom calls in a device trace (metadata only)
SPLASH_FWD = "dstpu_splash_fwd"
SPLASH_BWD_DQ = "dstpu_splash_bwd_dq"
SPLASH_BWD_DKV = "dstpu_splash_bwd_dkv"


def _default_vmem_limit() -> Optional[int]:
    mb = int(os.environ.get("DSTPU_SPLASH_VMEM_MB", "128"))
    return mb << 20 if mb > 0 else None


@dataclasses.dataclass(frozen=True)
class _SplashParams:
    """Static kernel configuration — hashable, so one compiled program per
    distinct config (custom_vjp nondiff arg)."""

    bq: int
    bk: int
    causal: bool
    window: int
    scale: float
    has_partial: bool   # False -> mask code is not compiled at all
    seg_mode: str       # 'none' | 'schedule' (partial steps) | 'all' (every step)
    interpret: bool
    vmem_limit: Optional[int]


def _compiler_kwargs(params: _SplashParams):
    if params.interpret:
        return {}
    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=params.vmem_limit,
        )
    }


def _partial_mask(logits, kind, q_pos, k_pos, segq_ref, segk_ref, params):
    """Mask for PARTIAL steps. FULL steps pass through untouched at run
    time; when the schedule holds no partial step this is never called."""
    keep = None

    def _and(a, b):
        return b if a is None else jnp.logical_and(a, b)

    if params.causal:
        keep = _and(keep, q_pos >= k_pos)
    if params.window:
        # THE shared band convention (core.window_too_far): out iff q-k >= w
        keep = _and(keep, (q_pos - k_pos) < params.window)
    if params.seg_mode == "schedule":
        keep = _and(keep, segq_ref[:, :1] == segk_ref[:])
    if keep is None:
        return logits
    return jnp.where(jnp.logical_or(kind == FULL, keep), logits, NEG_INF)


def _splash_fwd_kernel(kvi_ref, kind_ref, base_ref, *refs, params, hs_shared,
                       width):
    if params.seg_mode != "none":
        q_ref, k_ref, v_ref, segq_ref, segk_ref = refs[:5]
        rest = refs[5:]
        segq_ref, segk_ref = segq_ref.at[0], segk_ref.at[0]
    else:
        q_ref, k_ref, v_ref = refs[:3]
        segq_ref = segk_ref = None
        rest = refs[3:]
    o_ref, lse_ref, m_sc, l_sc, acc_sc = rest
    q_ref, k_ref, v_ref = q_ref.at[0, 0], k_ref.at[0, 0], v_ref.at[0, 0]
    o_ref, lse_ref = o_ref.at[0, 0], lse_ref.at[0, 0]

    h_ = pl.program_id(1)
    i = pl.program_id(2)
    j = pl.program_id(3)
    hs = 0 if hs_shared else h_
    kind = kind_ref[hs, i, j]
    bq, bk = params.bq, params.bk

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    @pl.when(kind > 0)
    def _step():
        q = q_ref[:].astype(jnp.float32) * params.scale
        k = k_ref[:].astype(jnp.float32)
        v = v_ref[:].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        if params.has_partial:
            q_pos = base_ref[0] + i * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = kvi_ref[hs, i, j] * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            logits = _partial_mask(logits, kind, q_pos, k_pos,
                                   segq_ref, segk_ref, params)
        if params.seg_mode == "all":
            # traced ids the schedule knows nothing about: every step masks
            logits = jnp.where(segq_ref[:, :1] == segk_ref[:], logits, NEG_INF)
        m = m_sc[:, 0]
        l = l_sc[:, 0]
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        # guard: a row whose every visited logit is masked must emit zeros,
        # not exp(NEG_INF - NEG_INF) = 1 garbage
        p = jnp.where(logits > NEG_INF / 2,
                      jnp.exp(logits - m_new[:, None]), 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_sc[:] = acc_sc[:] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_sc[:] = jnp.broadcast_to(m_new[:, None], m_sc.shape)
        l_sc[:] = jnp.broadcast_to(l_new[:, None], l_sc.shape)

    @pl.when(j == width - 1)
    def _flush():
        l_safe = jnp.maximum(l_sc[:, 0], 1e-30)
        o_ref[:] = (acc_sc[:] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[:] = jnp.broadcast_to(
            (m_sc[:, 0] + jnp.log(l_safe))[:, None], (bq, LANES))


def _splash_bwd_dq_kernel(kvi_ref, kind_ref, base_ref, *refs, params,
                          hs_shared, width):
    if params.seg_mode != "none":
        q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, segq_ref, segk_ref = refs[:8]
        rest = refs[8:]
        segq_ref, segk_ref = segq_ref.at[0], segk_ref.at[0]
    else:
        q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref = refs[:6]
        segq_ref = segk_ref = None
        rest = refs[6:]
    dq_ref, dq_acc, delta_sc = rest
    q_ref, k_ref, v_ref = q_ref.at[0, 0], k_ref.at[0, 0], v_ref.at[0, 0]
    o_ref, do_ref, lse_ref = o_ref.at[0, 0], do_ref.at[0, 0], lse_ref.at[0, 0]
    dq_ref = dq_ref.at[0, 0]

    h_ = pl.program_id(1)
    i = pl.program_id(2)
    j = pl.program_id(3)
    hs = 0 if hs_shared else h_
    kind = kind_ref[hs, i, j]
    bq, bk = params.bq, params.bk

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        delta = jnp.sum(
            do_ref[:].astype(jnp.float32) * o_ref[:].astype(jnp.float32),
            axis=-1)
        delta_sc[:] = jnp.broadcast_to(delta[:, None], delta_sc.shape)

    @pl.when(kind > 0)
    def _step():
        q = q_ref[:].astype(jnp.float32) * params.scale
        k = k_ref[:].astype(jnp.float32)
        v = v_ref[:].astype(jnp.float32)
        do = do_ref[:].astype(jnp.float32)
        lse = lse_ref[:, 0]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if params.has_partial:
            q_pos = base_ref[0] + i * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = kvi_ref[hs, i, j] * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            logits = _partial_mask(logits, kind, q_pos, k_pos,
                                   segq_ref, segk_ref, params)
        if params.seg_mode == "all":
            logits = jnp.where(segq_ref[:, :1] == segk_ref[:], logits, NEG_INF)
        p = jnp.where(logits > NEG_INF / 2,
                      jnp.exp(logits - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta_sc[:, 0][:, None])
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == width - 1)
    def _flush():
        dq_ref[:] = (dq_acc[:] * params.scale).astype(dq_ref.dtype)


def _splash_bwd_dkv_kernel(qi_ref, kind_ref, base_ref, *refs, params,
                           hs_shared, width):
    if params.seg_mode != "none":
        q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, segq_ref, segk_ref = refs[:8]
        rest = refs[8:]
        segq_ref, segk_ref = segq_ref.at[0], segk_ref.at[0]
    else:
        q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref = refs[:6]
        segq_ref = segk_ref = None
        rest = refs[6:]
    dk_ref, dv_ref, dk_acc, dv_acc = rest
    q_ref, k_ref, v_ref = q_ref.at[0, 0], k_ref.at[0, 0], v_ref.at[0, 0]
    o_ref, do_ref, lse_ref = o_ref.at[0, 0], do_ref.at[0, 0], lse_ref.at[0, 0]
    dk_ref, dv_ref = dk_ref.at[0, 0], dv_ref.at[0, 0]

    h_ = pl.program_id(1)
    i = pl.program_id(2)   # kv block
    j = pl.program_id(3)   # schedule step over q blocks
    hs = 0 if hs_shared else h_
    kind = kind_ref[hs, i, j]
    bq, bk = params.bq, params.bk

    @pl.when(j == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(kind > 0)
    def _step():
        q = q_ref[:].astype(jnp.float32) * params.scale
        k = k_ref[:].astype(jnp.float32)
        v = v_ref[:].astype(jnp.float32)
        do = do_ref[:].astype(jnp.float32)
        o = o_ref[:].astype(jnp.float32)
        lse = lse_ref[:, 0]
        delta = jnp.sum(do * o, axis=-1)  # [bq]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if params.has_partial:
            q_pos = base_ref[0] + qi_ref[hs, i, j] * bq + \
                jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = i * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            logits = _partial_mask(logits, kind, q_pos, k_pos,
                                   segq_ref, segk_ref, params)
        if params.seg_mode == "all":
            logits = jnp.where(segq_ref[:, :1] == segk_ref[:], logits, NEG_INF)
        p = jnp.where(logits > NEG_INF / 2,
                      jnp.exp(logits - lse[:, None]), 0.0)
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == width - 1)
    def _flush():
        # q was pre-scaled, so ds already carries one factor of scale; dk
        # needs dlogits/dk = scale * q_raw = the pre-scaled q — nothing more
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _seg_ops_specs(seg, bq, q_map, bk, k_map):
    """Segment-id operands + specs, streamed per block. ``q_map``/``k_map``
    give (batch, block) indices. Same layout as flash_pallas._seg_specs (a
    (1, block) window of a [b, s] plane is not a legal Mosaic block once
    b > 1): q ids lane-broadcast [b, s, LANES], k ids a [b, 1, s] row, and
    the kernels compare [bq, 1] == [1, bk] by broadcast."""
    if seg is None:
        return [], []

    def q_idx(*a):
        b_, i = q_map(*a)
        return b_, i, 0

    def k_idx(*a):
        b_, j = k_map(*a)
        return b_, 0, j

    ops = [jnp.broadcast_to(seg[:, :, None], seg.shape + (LANES,)),
           seg[:, None, :]]
    specs = [pl.BlockSpec((1, bq, LANES), q_idx), pl.BlockSpec((1, 1, bk), k_idx)]
    return ops, specs


def _splash_fwd_call(q, k, v, seg, kvi, kind, base, params: _SplashParams):
    b, h, sq, d = q.shape
    h_kv, sk = k.shape[1], k.shape[2]
    group = h // h_kv
    bq, bk = params.bq, params.bk
    nq, width = kvi.shape[1], kvi.shape[2]
    hs_shared = kvi.shape[0] == 1

    def hsi(h_):
        return 0 if hs_shared else h_

    qm = lambda b_, h_, i, j, kvi_, kind_, base_: (b_, h_, i, 0)
    km = lambda b_, h_, i, j, kvi_, kind_, base_: (
        b_, h_ // group, kvi_[hsi(h_), i, j], 0)
    seg_ops, seg_specs = _seg_ops_specs(
        seg, bq, lambda b_, h_, i, j, kvi_, kind_, base_: (b_, i),
        bk, lambda b_, h_, i, j, kvi_, kind_, base_: (b_, kvi_[hsi(h_), i, j]))

    kernel = functools.partial(
        _splash_fwd_kernel, params=params, hs_shared=hs_shared, width=width)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, h, nq, width),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d), qm),
                pl.BlockSpec((1, 1, bk, d), km),
                pl.BlockSpec((1, 1, bk, d), km),
                *seg_specs,
            ],
            out_specs=[
                pl.BlockSpec((1, 1, bq, d), qm),
                pl.BlockSpec((1, 1, bq, LANES), qm),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, LANES), jnp.float32),
                pltpu.VMEM((bq, LANES), jnp.float32),
                pltpu.VMEM((bq, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, LANES), jnp.float32),
        ],
        interpret=params.interpret,
        name=SPLASH_FWD,
        **_compiler_kwargs(params),
    )(kvi, kind, base, q, k, v, *seg_ops)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(9,))
def _splash_core(q, k, v, seg, kvi, kind, kvi_t, kind_t, base, params):
    out, _ = _splash_vjp_fwd(q, k, v, seg, kvi, kind, kvi_t, kind_t, base,
                             params)
    return out


def _splash_vjp_fwd(q, k, v, seg, kvi, kind, kvi_t, kind_t, base, params):
    out, lse = _splash_fwd_call(q, k, v, seg, kvi, kind, base, params)
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, "flash_out")
    # one lane of the lane-broadcast plane, as flash_pallas._flash_fwd keeps it:
    # a remat policy that saves the tag pays b·h·s·4 bytes, not x LANES
    lse = checkpoint_name(lse[..., :1], "flash_lse")
    q = checkpoint_name(q, "flash_qkv")
    k = checkpoint_name(k, "flash_qkv")
    v = checkpoint_name(v, "flash_qkv")
    return out, (q, k, v, seg, kvi, kind, kvi_t, kind_t, base, out, lse)


def _splash_vjp_bwd(params: _SplashParams, res, g):
    q, k, v, seg, kvi, kind, kvi_t, kind_t, base, out, lse = res
    lse = jnp.broadcast_to(lse, lse.shape[:-1] + (LANES,))
    b, h, sq, d = q.shape
    h_kv, sk = k.shape[1], k.shape[2]
    group = h // h_kv
    bq, bk = params.bq, params.bk
    nq, width = kvi.shape[1], kvi.shape[2]
    nk, width_t = kvi_t.shape[1], kvi_t.shape[2]
    hs_shared = kvi.shape[0] == 1

    def hsi(h_):
        return 0 if hs_shared else h_

    # ---- dq: row schedule, same grid as forward
    qm = lambda b_, h_, i, j, kvi_, kind_, base_: (b_, h_, i, 0)
    km = lambda b_, h_, i, j, kvi_, kind_, base_: (
        b_, h_ // group, kvi_[hsi(h_), i, j], 0)
    seg_ops, seg_specs = _seg_ops_specs(
        seg, bq, lambda b_, h_, i, j, kvi_, kind_, base_: (b_, i),
        bk, lambda b_, h_, i, j, kvi_, kind_, base_: (b_, kvi_[hsi(h_), i, j]))
    dq_kernel = functools.partial(
        _splash_bwd_dq_kernel, params=params, hs_shared=hs_shared, width=width)
    dq = pl.pallas_call(
        dq_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, h, nq, width),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d), qm),
                pl.BlockSpec((1, 1, bk, d), km),
                pl.BlockSpec((1, 1, bk, d), km),
                pl.BlockSpec((1, 1, bq, d), qm),
                pl.BlockSpec((1, 1, bq, d), qm),
                pl.BlockSpec((1, 1, bq, LANES), qm),
                *seg_specs,
            ],
            out_specs=pl.BlockSpec((1, 1, bq, d), qm),
            scratch_shapes=[
                pltpu.VMEM((bq, d), jnp.float32),
                pltpu.VMEM((bq, LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=params.interpret,
        name=SPLASH_BWD_DQ,
        **_compiler_kwargs(params),
    )(kvi, kind, base, q, k, v, out, g, lse, *seg_ops)

    # ---- dk/dv: transposed schedule — per kv block, visit the q blocks
    # that touch it. Output is per q head; GQA group-reduces below.
    qm_t = lambda b_, h_, i, j, qi_, kind_, base_: (
        b_, h_, qi_[hsi(h_), i, j], 0)
    km_t = lambda b_, h_, i, j, qi_, kind_, base_: (b_, h_ // group, i, 0)
    om_t = lambda b_, h_, i, j, qi_, kind_, base_: (b_, h_, i, 0)
    seg_ops_t, seg_specs_t = _seg_ops_specs(
        seg, bq, lambda b_, h_, i, j, qi_, kind_, base_: (b_, qi_[hsi(h_), i, j]),
        bk, lambda b_, h_, i, j, qi_, kind_, base_: (b_, i))
    dkv_kernel = functools.partial(
        _splash_bwd_dkv_kernel, params=params, hs_shared=hs_shared,
        width=width_t)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, h, nk, width_t),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d), qm_t),
                pl.BlockSpec((1, 1, bk, d), km_t),
                pl.BlockSpec((1, 1, bk, d), km_t),
                pl.BlockSpec((1, 1, bq, d), qm_t),
                pl.BlockSpec((1, 1, bq, d), qm_t),
                pl.BlockSpec((1, 1, bq, LANES), qm_t),
                *seg_specs_t,
            ],
            out_specs=[
                pl.BlockSpec((1, 1, bk, d), om_t),
                pl.BlockSpec((1, 1, bk, d), om_t),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sk, d), q.dtype),
        ],
        interpret=params.interpret,
        name=SPLASH_BWD_DKV,
        **_compiler_kwargs(params),
    )(kvi_t, kind_t, base, q, k, v, out, g, lse, *seg_ops_t)
    if group > 1:
        dk = dk.reshape(b, h_kv, group, sk, d).sum(2).astype(k.dtype)
        dv = dv.reshape(b, h_kv, group, sk, d).sum(2).astype(v.dtype)
    return dq, dk, dv, None, None, None, None, None, None


_splash_core.defvjp(_splash_vjp_fwd, _splash_vjp_bwd)


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return not on_tpu()
    return bool(interpret)


def splash_attention(q, k, v, schedule: BlockSchedule, *,
                     segment_ids=None, scale: Optional[float] = None,
                     interpret: Optional[bool] = None,
                     vmem_limit_bytes: Optional[int] = None):
    """Scheduled block-sparse attention. q: [b, h, sq, d]; k/v:
    [b, h_kv, sk, d] (GQA handled in the index maps — kv is NEVER
    replicated in HBM). ``schedule`` is a trace-time-constant
    BlockSchedule (schedule.py); its arrays become scalar-prefetch
    operands, so the compiled grid is (b, h, nq, width).

    ``segment_ids`` ([b, s] int32, may be traced): when the schedule was
    built WITHOUT segment pruning (DocumentMask absent), the predicate is
    applied on every scheduled step; when the schedule already carries
    static ids, they mask partial steps only. Differentiable (custom_vjp).
    """
    b, h, sq, d = q.shape
    h_kv, sk = k.shape[1], k.shape[2]
    if h % h_kv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {h_kv}")
    if (schedule.seq_q, schedule.seq_kv) != (sq, sk):
        raise ValueError(f"schedule is for seq {(schedule.seq_q, schedule.seq_kv)}, "
                         f"got {(sq, sk)}")
    if schedule.num_heads not in (1, h):
        raise ValueError(f"schedule has {schedule.num_heads} heads, q has {h}")
    seg_mode = "none"
    seg = None
    if schedule.segment_ids is not None:
        if sq != sk:
            raise ValueError("segment masking requires square attention")
        seg_mode = "schedule"
        seg = jnp.broadcast_to(
            jnp.asarray(schedule.segment_ids, jnp.int32)[None], (b, sq))
        if segment_ids is not None:
            raise ValueError("schedule already carries segment ids; passing "
                             "runtime segment_ids too would silently compose")
    elif segment_ids is not None:
        if sq != sk:
            raise ValueError("segment masking requires square attention")
        seg_mode = "all"
        seg = jnp.asarray(segment_ids, jnp.int32)
    params = _SplashParams(
        bq=schedule.block_q, bk=schedule.block_kv,
        causal=schedule.causal, window=schedule.window,
        scale=float(scale if scale is not None else d ** -0.5),
        has_partial=schedule.num_partial > 0,
        seg_mode=seg_mode,
        interpret=_auto_interpret(interpret),
        vmem_limit=(vmem_limit_bytes if vmem_limit_bytes is not None
                    else _default_vmem_limit()),
    )
    kvi = jnp.asarray(schedule.kv_index)
    kind = jnp.asarray(schedule.step_kind)
    kvi_t = jnp.asarray(schedule.q_index)
    kind_t = jnp.asarray(schedule.step_kind_t)
    base = jnp.zeros((1,), jnp.int32)
    return _splash_core(q, k, v, seg, kvi, kind, kvi_t, kind_t, base, params)
