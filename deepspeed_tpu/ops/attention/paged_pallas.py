"""Paged (block-table) attention for the ragged inference batch.

TPU-native analogue of the reference blocked-flash ragged kernels
(``inference/v2/kernels/ragged_ops/blocked_flash``, ``linear_blocked_kv_rotary``):
every query token carries its own block table and context length, so one
call serves a fused batch of decode tokens and prompt chunks from different
sequences (the Dynamic SplitFuse execution model).

Layout:
  q            [T, nh, d]       — packed new-token queries
  k/v pool     [NB, bs, nkv, d] — paged block pool (token-major). The engine
                 passes a FLAT multi-layer view ([L*NBp, bs, nkv, d]) with
                 layer-offset block tables, so the pool never needs a
                 per-layer slice, and reads it as the step received it:
                 the engine writes a step's K/V once, after its layer loop
  block_tables per token [T, B] or per row [R, B]
  q_pos        global position of each query in its sequence

Implementations:
  * ``paged_decode_attention_dense`` / ``paged_chunk_attention`` — plain XLA
    (block gather + masked einsum). Profiled fastest on the bench shapes:
    per-Pallas-program launch overhead (~9 us) dominates grid kernels at
    serving grids, while the gather is one fused op.
  * ``paged_attention`` — the (T, B)-grid Pallas kernel (one program per
    (token, context-block), scalar-prefetched DMA). Kept for the per-token
    fused path and as the ``kernel`` impl option.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator.device import on_tpu

NEG_INF = -1e30
# The kernels' names in a device trace (``pallas_call(name=...)`` names the Mosaic
# custom call); metadata only. benchmarks/metrics readers find them by these.
PAGED_DECODE = "dstpu_paged_decode"


def paged_attention_reference(q, k_cache, v_cache, block_tables, q_pos, trash_block,
                              window: int = 0, scale=None, k_scale=None,
                              v_scale=None):
    """jnp reference: per-token context gather + masked softmax, mapped over
    tokens so peak memory is one context window ([S, nkv, d]) rather than T
    of them. Shapes as module docstring; returns [T, nh, d]. ``window``:
    static sliding-window band over sequence positions (mistral/starcoder2;
    band convention shared via core.window_too_far). ``k_scale``/``v_scale``
    [NB, bs, nkv]: per-vector fp32 dequant planes for an int8 pool
    (block_quant.quantize_kv)."""
    T, nh, d = q.shape
    NB, bs, nkv, _ = k_cache.shape
    B = block_tables.shape[1]
    S = B * bs
    group = nh // nkv
    kpos = jnp.arange(S, dtype=jnp.int32)

    def one_token(args):
        qt, bt, pos = args  # [nh, d], [B], scalar
        k_ctx = k_cache[bt].reshape(S, nkv, d).astype(jnp.float32)
        v_ctx = v_cache[bt].reshape(S, nkv, d).astype(jnp.float32)
        if k_scale is not None:
            k_ctx = k_ctx * k_scale[bt].reshape(S, nkv)[..., None]
            v_ctx = v_ctx * v_scale[bt].reshape(S, nkv)[..., None]
        blk_valid = jnp.repeat(bt != trash_block, bs)
        mask = (kpos <= pos) & blk_valid  # [S]
        if window:
            from deepspeed_tpu.ops.attention.core import window_too_far

            mask = mask & jnp.logical_not(window_too_far(pos, kpos, window))
        qg = qt.reshape(nkv, group, d).astype(jnp.float32)
        scores = jnp.einsum("ngd,snd->ngs", qg, k_ctx) * (scale if scale is not None else d**-0.5)
        scores = jnp.where(mask[None, None], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        # fully-masked token (all-trash padding): return 0 like the kernel
        # does, not the uniform-softmax mean of trash V
        w = jnp.where(jnp.any(mask), w, 0.0)
        return jnp.einsum("ngs,snd->ngd", w, v_ctx).reshape(nh, d)

    out = jax.lax.map(one_token, (q, block_tables, q_pos), batch_size=min(T, 32))
    return out.astype(q.dtype)


def _paged_kernel(
    *refs, bs, nh, nkv, d, B, E=0, window=0, scale=None, int8=False,
    has_limit=False
):
    """(T, B [+1])-grid kernel body. ``refs`` layout — scalar prefetch
    (SMEM): bt [T, B], qpos [T], trash [1], limit [T] if ``has_limit`` —
    then tensor blocks (VMEM): epos (1, 1, E) if ``E``, q (1, nh, d),
    k (1, bs, nkv, d), v, ks/vs scale planes (1, bs, nkv) if ``int8``,
    ke/ve (1, E, nkv, d) if ``E`` — then o (1, nh, d) and the m/l/acc
    flash scratch.

    ``trash`` rides as a prefetch operand (not a static kwarg) because the
    engine's flat multi-layer views use layer-offset trash ids — traced
    values inside the fori_loop layer driver. ``E`` extra columns are this
    step/round's NOT-YET-CACHED K/V (the write-after-read protocol), kept
    in compute dtype — only the pool payload is int8; dequant happens here
    in fp32 right after the halved-HBM block DMA, so the VPU multiply
    hides under the transfer (the EQuARX argument applied to HBM)."""
    it = iter(refs)
    bt_ref, qpos_ref, trash_ref = next(it), next(it), next(it)
    limit_ref = next(it) if has_limit else None
    epos_ref = next(it) if E else None
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    ks_ref = next(it) if int8 else None
    vs_ref = next(it) if int8 else None
    ke_ref = next(it) if E else None
    ve_ref = next(it) if E else None
    o_ref = next(it)
    m_scr, l_scr, acc_scr = next(it), next(it), next(it)

    t = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    group = nh // nkv
    scale = scale if scale is not None else d**-0.5
    trash = trash_ref[0]
    qpos = qpos_ref[t]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale  # [nh, d]

    def flash_accum(k, v, valid):
        """One online-softmax accumulation sweep: k/v [nk, nkv, d] fp32,
        valid [1, nk]. Disjoint per-kv-head scratch slices, so reading
        m/l once up front is safe."""
        m_prev = m_scr[...]  # [nh, 128] (col 0 meaningful)
        l_prev = l_scr[...]
        for n in range(nkv):
            qn = q[n * group : (n + 1) * group]  # [group, d]
            kn = k[:, n, :]  # [nk, d]
            vn = v[:, n, :]
            s = jax.lax.dot_general(
                qn, kn, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )  # [group, nk]
            s = jnp.where(valid, s, NEG_INF)
            m_p = m_prev[n * group : (n + 1) * group, :1]  # [group, 1]
            m_new = jnp.maximum(m_p, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_p - m_new)
            p = jnp.exp(s - m_new)  # [group, nk]
            l_p = l_prev[n * group : (n + 1) * group, :1]
            l_new = l_p * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc = acc_scr[n * group : (n + 1) * group, :]  # [group, d]
            acc_scr[n * group : (n + 1) * group, :] = acc * alpha + jax.lax.dot_general(
                p, vn, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            m_scr[n * group : (n + 1) * group, :1] = m_new
            l_scr[n * group : (n + 1) * group, :1] = l_new

    def pool_block():
        jb = jnp.minimum(j, B - 1)  # clamped: the j == B step is the extras
        blk = bt_ref[t, jb]
        kpos = jb * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)  # [1, bs]
        if has_limit:
            # explicit pool window (write-after-read: the pool holds only
            # positions below the step/round start); qpos < 0 marks padded
            # query slots that must see nothing
            valid = (kpos < limit_ref[t]) & (qpos >= 0)
        else:
            valid = kpos <= qpos
        valid = valid & (blk != trash)
        if window:
            from deepspeed_tpu.ops.attention.core import window_too_far

            valid = valid & jnp.logical_not(window_too_far(qpos, kpos, window))
        k = k_ref[0].astype(jnp.float32)  # [bs, nkv, d]
        v = v_ref[0].astype(jnp.float32)
        if int8:
            k = k * ks_ref[0][..., None]
            v = v * vs_ref[0][..., None]
        flash_accum(k, v, valid)

    if E:
        @pl.when(j < B)
        def _pool():
            pool_block()

        @pl.when(j == B)
        def _extra():
            epos = epos_ref[0]  # [1, E]
            valid = (epos >= 0) & (epos <= qpos)
            if window:
                from deepspeed_tpu.ops.attention.core import window_too_far

                valid = valid & jnp.logical_not(window_too_far(qpos, epos, window))
            flash_accum(
                ke_ref[0].astype(jnp.float32), ve_ref[0].astype(jnp.float32), valid
            )
    else:
        pool_block()

    @pl.when(j == nj - 1)
    def _finish():
        l = l_scr[:, :1]
        # fully-masked token (all-trash padding): m never left NEG_INF and
        # every p degenerated to exp(0) — emit 0, matching the reference
        any_valid = m_scr[:, :1] > NEG_INF * 0.5
        out = jnp.where(any_valid, acc_scr[...] / jnp.maximum(l, 1e-30), 0.0)
        o_ref[0] = out.astype(o_ref.dtype)


PAGED_ATTENTION_IMPLS = ("auto", "kernel", "dense", "reference")


def paged_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    block_tables: jax.Array,
    q_pos: jax.Array,
    trash_block,
    impl: Optional[str] = None,
    interpret: bool = False,
    window: int = 0,
    scale: Optional[float] = None,
    k_scale=None,
    v_scale=None,
    extra_kv=None,
    pool_limit=None,
) -> jax.Array:
    """Dispatching entry point for paged decode attention — the engine's
    decode hot paths (single-step, fused rounds, spec verify) all call this.

    ``impl``: "auto" (None) resolves to the Pallas kernel on TPU for
    kernel-tiled head dims and the dense XLA gather elsewhere; "kernel",
    "dense", "reference" force a path; anything else raises (a typo must
    not silently fall back — the seam that kept the kernel unreachable).
    ``trash_block`` may be a traced scalar (layer-offset trash ids).
    ``k_scale``/``v_scale`` [NB, bs, nkv] fp32: dequant planes, required
    iff the pool payload is int8 (block_quant.quantize_kv) — the kernel
    dequantizes in-VMEM after the halved block DMA. ``extra_kv`` =
    (ke [T, E, nkv, d], ve, epos [T, E]) and ``pool_limit`` [T]: the
    write-after-read protocol (see paged_decode_attention_dense); extras
    stay in compute dtype. ``window``: static sliding-window band;
    ``scale``: softmax scale override (gpt_neo's unscaled logits)."""
    T, nh, d = q.shape
    NB, bs, nkv, _ = k_cache.shape
    int8_pool = k_cache.dtype == jnp.int8
    if int8_pool and (k_scale is None or v_scale is None):
        raise ValueError(
            "paged_attention: int8 k/v pools need k_scale and v_scale planes"
        )
    if not int8_pool and (k_scale is not None or v_scale is not None):
        raise ValueError(
            "paged_attention: k_scale/v_scale given but the pool payload is "
            f"{k_cache.dtype}, not int8"
        )
    if impl is None or impl == "auto":
        impl = "kernel" if (on_tpu() and d in (64, 128, 256)) else "dense"
    if impl == "reference":
        if extra_kv is not None or pool_limit is not None:
            raise ValueError(
                "paged_attention: impl='reference' serves the plain parity "
                "form only (no extra_kv/pool_limit)"
            )
        return paged_attention_reference(
            q, k_cache, v_cache, block_tables, q_pos, trash_block,
            window=window, scale=scale, k_scale=k_scale, v_scale=v_scale,
        )
    if impl == "dense":
        return paged_decode_attention_dense(
            q, k_cache, v_cache, block_tables, q_pos, trash_block,
            window=window, scale=scale, extra_kv=extra_kv,
            pool_limit=pool_limit, k_scale=k_scale, v_scale=v_scale,
        )
    if impl != "kernel":
        raise ValueError(
            f"paged_attention: unknown impl {impl!r} "
            f"(expected one of {PAGED_ATTENTION_IMPLS})"
        )

    # kernel path; off-TPU it only runs interpreted (CPU tests)
    interpret = bool(interpret) or not on_tpu()
    B = block_tables.shape[1]
    has_limit = pool_limit is not None
    E = 0 if extra_kv is None else int(extra_kv[0].shape[1])
    num_scalar = 3 + (1 if has_limit else 0)

    if E:
        blk_idx = lambda t, j, *s: (s[0][t, jnp.minimum(j, B - 1)], 0, 0, 0)
    else:
        blk_idx = lambda t, j, *s: (s[0][t, j], 0, 0, 0)
    in_specs = []
    if E:
        # [T, 1, E]: a (1, E) window of a [T, E] plane is not a legal Mosaic
        # block (last two dims must be (8, 128)-aligned or whole)
        in_specs.append(pl.BlockSpec((1, 1, E), lambda t, j, *s: (t, 0, 0)))
    in_specs.append(pl.BlockSpec((1, nh, d), lambda t, j, *s: (t, 0, 0)))
    in_specs.append(pl.BlockSpec((1, bs, nkv, d), blk_idx))
    in_specs.append(pl.BlockSpec((1, bs, nkv, d), blk_idx))
    if int8_pool:
        scale_idx = lambda t, j, *s: blk_idx(t, j, *s)[:3]
        in_specs.append(pl.BlockSpec((1, bs, nkv), scale_idx))
        in_specs.append(pl.BlockSpec((1, bs, nkv), scale_idx))
    if E:
        in_specs.append(pl.BlockSpec((1, E, nkv, d), lambda t, j, *s: (t, 0, 0, 0)))
        in_specs.append(pl.BlockSpec((1, E, nkv, d), lambda t, j, *s: (t, 0, 0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar,
        grid=(T, B + (1 if E else 0)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nh, d), lambda t, j, *s: (t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nh, 128), jnp.float32),
            pltpu.VMEM((nh, 128), jnp.float32),
            pltpu.VMEM((nh, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, bs=bs, nh=nh, nkv=nkv, d=d, B=B, E=E,
        window=int(window), scale=scale, int8=int8_pool, has_limit=has_limit,
    )
    operands = [
        block_tables.astype(jnp.int32),
        q_pos.astype(jnp.int32),
        jnp.asarray(trash_block, jnp.int32).reshape(1),
    ]
    if has_limit:
        operands.append(jnp.asarray(pool_limit, jnp.int32).reshape(T))
    if E:
        operands.append(jnp.asarray(extra_kv[2], jnp.int32).reshape(T, 1, E))
    operands.append(q)
    operands.extend([k_cache, v_cache])
    if int8_pool:
        operands.extend([k_scale, v_scale])
    if E:
        operands.extend([extra_kv[0], extra_kv[1]])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, nh, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # tokens are independent (scratch re-inits at j==0) → megacore
            # can split the T dim; only the block dim accumulates
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name=PAGED_DECODE,
    )(*operands)


def paged_decode_attention_dense(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    block_tables: jax.Array,
    q_pos: jax.Array,
    trash_block,
    window: int = 0,
    scale: Optional[float] = None,
    extra_kv=None,
    pool_limit=None,
    k_scale=None,
    v_scale=None,
) -> jax.Array:
    """Decode attention as plain XLA (block gather + masked einsum) — no
    Pallas. On the profile (PERF.md serving roofline) per-program launch
    overhead (~9 us x grid size) dominates grid kernels at decode shapes,
    while the whole-table gather is a single fused op; the gather over-reads
    unallocated (trash) slots but stays ahead until contexts are long.
    GSPMD shards it (cache on the kv-head dim) without a shard_map island.
    q [R, nh, d], tables [R, B] per-row; ``trash_block`` may be traced
    (layer-offset trash ids).

    ``extra_kv`` = (ke [R, E, nkv, d], ve, epos [R, E]): NOT-YET-CACHED
    tokens (this step's / this round's K/V), appended as extra score
    columns; epos are their global positions, -1 = invalid. ``pool_limit``
    [R]: pool positions >= pool_limit are masked (default q_pos + 1, i.e.
    the causal <=). The pool is gathered BEFORE this step's writes: the
    engine scatters a step's K/V once, after its layer loop
    (engine_v2._scatter_kv), so no read ever depends on a write of the
    same step and XLA copies no pool.
    ``k_scale``/``v_scale`` [NB, bs, nkv] fp32: int8-pool dequant planes
    (extras stay in compute dtype — only the pool payload is quantized).
    """
    R, nh, d = q.shape
    NB, bs, nkv, _ = k_cache.shape
    B = block_tables.shape[1]
    S = B * bs
    group = nh // nkv
    k_ctx = (
        k_cache[block_tables].transpose(0, 3, 1, 2, 4).reshape(R, nkv, S, d)
    ).astype(jnp.float32)
    v_ctx = (
        v_cache[block_tables].transpose(0, 3, 1, 2, 4).reshape(R, nkv, S, d)
    ).astype(jnp.float32)
    if k_scale is not None:
        k_ctx = k_ctx * k_scale[block_tables].transpose(0, 3, 1, 2).reshape(
            R, nkv, S
        )[..., None]
        v_ctx = v_ctx * v_scale[block_tables].transpose(0, 3, 1, 2).reshape(
            R, nkv, S
        )[..., None]
    kpos = jnp.arange(S, dtype=jnp.int32)
    limit = (q_pos + 1) if pool_limit is None else pool_limit
    mask = (kpos[None] < limit[:, None]) & jnp.repeat(
        block_tables != trash_block, bs, axis=1
    )  # [R, S]
    if window:
        from deepspeed_tpu.ops.attention.core import window_too_far

        mask = mask & jnp.logical_not(
            window_too_far(q_pos[:, None], kpos[None], window)
        )
    qg = q.reshape(R, nkv, group, d).astype(jnp.float32) * (
        scale if scale is not None else d**-0.5
    )
    s = jnp.einsum("rngd,rnsd->rngs", qg, k_ctx)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    if extra_kv is not None:
        ke, ve, epos = extra_kv
        E = ke.shape[1]
        emask = (epos >= 0) & (epos <= q_pos[:, None])  # [R, E]
        if window:
            from deepspeed_tpu.ops.attention.core import window_too_far

            emask = emask & jnp.logical_not(
                window_too_far(q_pos[:, None], epos, window)
            )
        ke32 = ke.transpose(0, 2, 1, 3).astype(jnp.float32)  # [R, nkv, E, d]
        ve32 = ve.transpose(0, 2, 1, 3).astype(jnp.float32)
        se = jnp.einsum("rngd,rned->rnge", qg, ke32)
        se = jnp.where(emask[:, None, None], se, NEG_INF)
        s = jnp.concatenate([s, se], axis=-1)
        any_valid = jnp.any(mask, axis=1) | jnp.any(emask, axis=1)
        w = jax.nn.softmax(s, axis=-1)
        w = jnp.where(any_valid[:, None, None, None], w, 0.0)
        out = jnp.einsum("rngs,rnsd->rngd", w[..., :S], v_ctx) + jnp.einsum(
            "rnge,rned->rngd", w[..., S:], ve32
        )
        return out.reshape(R, nh, d).astype(q.dtype)
    w = jax.nn.softmax(s, axis=-1)
    w = jnp.where(jnp.any(mask, axis=1)[:, None, None, None], w, 0.0)
    out = jnp.einsum("rngs,rnsd->rngd", w, v_ctx)
    return out.reshape(R, nh, d).astype(q.dtype)


def paged_chunk_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    row_tables: jax.Array,
    q_pos: jax.Array,
    trash_block,
    window: int = 0,
    scale: Optional[float] = None,
    new_kv=None,
    pool_limit=None,
    k_scale=None,
    v_scale=None,
) -> jax.Array:
    """Prefill-chunk attention: Rc rows x tq new tokens each, every row's
    tokens sharing that ROW's block table (q [Rc, tq, nh, d],
    row_tables [Rc, B], q_pos [Rc, tq] global positions, -1 = padding).
    One context gather per ROW (not per token — the (T, B)-grid kernel's
    launch-overhead failure mode at prefill grids) then a dense masked
    softmax; chunk MXU work is real matmuls. Padded tail tokens (q_pos < 0)
    emit exactly 0.

    ``new_kv`` = (ke [Rc, tq, nkv, d], ve): THIS chunk's not-yet-cached
    K/V — in-chunk attention runs causally over them while the pool covers
    only positions < ``pool_limit`` [Rc] (the chunk's start). Without
    new_kv the pool is assumed to already hold the chunk (legacy form) and
    pool_limit defaults to the causal <=. ``k_scale``/``v_scale``
    [NB, bs, nkv] fp32: int8-pool dequant planes (new_kv stays in compute
    dtype)."""
    Rc, tq, nh, d = q.shape
    NB, bs, nkv, _ = k_cache.shape
    B = row_tables.shape[1]
    S = B * bs
    group = nh // nkv
    k_ctx = (
        k_cache[row_tables].transpose(0, 3, 1, 2, 4).reshape(Rc, nkv, S, d)
    ).astype(jnp.float32)
    v_ctx = (
        v_cache[row_tables].transpose(0, 3, 1, 2, 4).reshape(Rc, nkv, S, d)
    ).astype(jnp.float32)
    if k_scale is not None:
        k_ctx = k_ctx * k_scale[row_tables].transpose(0, 3, 1, 2).reshape(
            Rc, nkv, S
        )[..., None]
        v_ctx = v_ctx * v_scale[row_tables].transpose(0, 3, 1, 2).reshape(
            Rc, nkv, S
        )[..., None]
    kpos = jnp.arange(S, dtype=jnp.int32)
    blk_valid = jnp.repeat(row_tables != trash_block, bs, axis=1)  # [Rc, S]
    if pool_limit is None:
        pool_ok = kpos[None, None] <= q_pos[:, :, None]
    else:
        pool_ok = jnp.broadcast_to(
            (kpos[None] < pool_limit[:, None])[:, None], (Rc, tq, S)
        )
    mask = pool_ok & (q_pos[:, :, None] >= 0) & blk_valid[:, None]  # [Rc, tq, S]
    if window:
        from deepspeed_tpu.ops.attention.core import window_too_far

        mask = mask & jnp.logical_not(
            window_too_far(q_pos[:, :, None], kpos[None, None], window)
        )
    qg = q.reshape(Rc, tq, nkv, group, d).astype(jnp.float32) * (
        scale if scale is not None else d**-0.5
    )
    s = jnp.einsum("rtngd,rnsd->rntgs", qg, k_ctx)
    s = jnp.where(mask[:, None, :, None], s, NEG_INF)
    if new_kv is not None:
        ke, ve = new_kv
        # in-chunk causal: key j visible to query i iff 0 <= pos_j <= pos_i
        cmask = (
            (q_pos[:, None, :] >= 0)
            & (q_pos[:, :, None] >= 0)
            & (q_pos[:, None, :] <= q_pos[:, :, None])
        )  # [Rc, tq(i), tq(j)]
        if window:
            from deepspeed_tpu.ops.attention.core import window_too_far

            cmask = cmask & jnp.logical_not(
                window_too_far(q_pos[:, :, None], q_pos[:, None, :], window)
            )
        ke32 = ke.transpose(0, 2, 1, 3).astype(jnp.float32)  # [Rc, nkv, tq, d]
        ve32 = ve.transpose(0, 2, 1, 3).astype(jnp.float32)
        sc = jnp.einsum("rtngd,rnjd->rntgj", qg, ke32)
        sc = jnp.where(cmask[:, None, :, None], sc, NEG_INF)
        s = jnp.concatenate([s, sc], axis=-1)
        any_valid = jnp.any(mask, axis=2) | jnp.any(cmask, axis=2)  # [Rc, tq]
        w = jax.nn.softmax(s, axis=-1)
        w = jnp.where(any_valid[:, None, :, None, None], w, 0.0)
        out = jnp.einsum("rntgs,rnsd->rtngd", w[..., :S], v_ctx) + jnp.einsum(
            "rntgj,rnjd->rtngd", w[..., S:], ve32
        )
        return out.reshape(Rc, tq, nh, d).astype(q.dtype)
    w = jax.nn.softmax(s, axis=-1)
    w = jnp.where(jnp.any(mask, axis=2)[:, None, :, None, None], w, 0.0)
    out = jnp.einsum("rntgs,rnsd->rtngd", w, v_ctx)
    return out.reshape(Rc, tq, nh, d).astype(q.dtype)
