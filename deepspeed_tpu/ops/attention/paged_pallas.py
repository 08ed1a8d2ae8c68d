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

Implementations (``paged_attention(impl=...)``; ``auto`` is the kernel on a
TPU for head sizes 64 / 128 / 256 and the dense form elsewhere):
  * ``kernel`` — the Pallas kernel ``dstpu_paged_decode``. Its grid is the
    list of visits the call's rows need (``_visit_list``): for each query
    row the table slots its context covers, in order; the row's LAST visit
    also folds the extra columns and writes the row out, and a row that
    holds no block is one program (the extra columns alone; a padded slot
    writes zeros). A slot a row does not hold is no program, so a call
    costs what its rows hold, not ``T x B``. A visit takes the
    ``[bs, nkv, d]`` block as it lies in the pool, swaps it to head-major
    once and folds it into the row's flash state in ONE product batched
    over the KV heads: operands in the queries' dtype (the pool's, in every
    served model; an int8 pool dequantises into it in VMEM), float32
    scores, softmax state and accumulator; the queries come in scaled. The
    block of a visit comes in through a scalar-prefetched index map; the
    list is computed from ``q_pos`` / ``pool_limit`` / ``window`` by a few
    small XLA fusions in front of the call.
  * ``dense`` (``paged_decode_attention_dense``) — plain XLA: gather every
    slot of every table, then a masked einsum. What runs off the TPU and at
    ``tp_size > 1`` (GSPMD shards it on the kv-head dim); it reads the whole
    table whatever the rows hold.
  * ``reference`` — the per-token jnp oracle of the tests.

Prompt chunks (``paged_chunk_attention(impl=...)``: Rc rows of tq queries
that share their row's table; the engine passes the impl it resolved for
the decode rows):
  * ``kernel`` — the Pallas flash kernel ``dstpu_paged_chunk``. A program
    holds a tile of a row's queries, every head, against one block of keys:
    the pool blocks the row holds below the chunk, read in place off its
    table, then the chunk's own K/V, causal by tiles (``_chunk_visit_list``).
    Scores, softmax state and the accumulator never leave VMEM; an empty
    row and a chunk's padded tail cost a program that writes zeros.
  * ``dense`` — the gather of whole tables and a masked softmax over
    ``B x bs + tq`` columns a query. Off the TPU, at ``tp_size > 1``, in the
    verify step's dense branch, and for what ``chunk_kernel_takes`` leaves
    (an int8 pool, a head of 64, blocks that do not fill Mosaic's tiles).
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
PAGED_CHUNK = "dstpu_paged_chunk"


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


def _paged_kernel(*refs, bs, E=0, window=0, int8=False):
    """One program a VISIT: the grid is the list of (row, table slot) pairs
    the rows' contexts cover, each row's pool blocks in order, so a table
    slot a row does not hold costs nothing; a row's LAST visit also folds the
    extra columns and writes the row out. ``refs`` layout — scalar prefetch
    (SMEM): bt [T, B], qpos [T], trash [1], limit [T], vrow / vslot / vflag
    [G] (the visit list, ``_visit_list``) — then tensor blocks (VMEM): epos
    (1, 1, E) if ``E``, q (1, nkv, group, d) scaled, k (1, bs, nkv, d), v,
    ks/vs scale planes (1, bs, nkv) if ``int8``, ke/ve (1, E, nkv, d) if
    ``E`` — then o (1, nkv, group, d) and the m / l / acc flash scratch
    [nkv, group, .].

    A block is folded as it lies in the pool: swapped to head-major
    ``[nkv, bs, d]`` once and multiplied in ONE product batched over the KV
    heads, operands in the queries' dtype (what the pool stores, in every
    served model), float32 scores, softmax state and accumulator, read and
    written whole once a visit (an operation a head costs a visit what its
    head count costs, not what its bytes cost: ledger, PRs 32 and 34).

    ``trash`` rides as a prefetch operand (not a static kwarg) because the
    engine's flat multi-layer views use layer-offset trash ids — traced
    values inside the fori_loop layer driver. ``E`` extra columns are this
    step/round's NOT-YET-CACHED K/V (the write-after-read protocol), kept
    in compute dtype — only the pool payload is int8; dequant happens here
    right after the halved-HBM block DMA, so the VPU multiply hides under
    the transfer (the EQuARX argument applied to HBM)."""
    it = iter(refs)
    bt_ref, qpos_ref, trash_ref, limit_ref = next(it), next(it), next(it), next(it)
    vrow_ref, vslot_ref, vflag_ref = next(it), next(it), next(it)
    epos_ref = next(it) if E else None
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    ks_ref = next(it) if int8 else None
    vs_ref = next(it) if int8 else None
    ke_ref = next(it) if E else None
    ve_ref = next(it) if E else None
    o_ref = next(it)
    m_scr, l_scr, acc_scr = next(it), next(it), next(it)

    g = pl.program_id(0)
    t, slot, flag = vrow_ref[g], vslot_ref[g], vflag_ref[g]
    qpos = qpos_ref[t]

    @pl.when((flag & 2) != 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]  # [nkv, group, d]

    def fold(k, v, valid):
        """One online-softmax step over keys k / values v [nkv, nk, d] in the
        queries' dtype; ``valid`` [1, 1, nk], or None where every key is the
        row's."""
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        )  # [nkv, group, nk]
        if valid is not None:
            s = jnp.where(valid, s, NEG_INF)
        m_p = m_scr[:, :, :1]  # col 0 meaningful
        m_new = jnp.maximum(m_p, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_p - m_new)
        p = jnp.exp(s - m_new)
        l_scr[:, :, :1] = l_scr[:, :, :1] * alpha + jnp.sum(p, axis=2, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_scr[:, :, :1] = m_new

    # the pool holds the row's keys below ``limit`` (the explicit pool window
    # of the write-after-read protocol, else the causal <=; 0 for a padded
    # query slot, which must see nothing). A block wholly inside the row's
    # context takes no mask: all but a row's last (under a window, and its
    # first) are such
    limit = limit_ref[t]
    live = bt_ref[t, slot] != trash_ref[0]
    whole = live & ((slot + 1) * bs <= limit)
    if window:
        from deepspeed_tpu.ops.attention.core import window_too_far

        whole = whole & (qpos - slot * bs < window)

    def in_context(kpos):
        ok = (kpos < limit) & live
        if window:
            ok = ok & jnp.logical_not(window_too_far(qpos, kpos, window))
        return ok

    def pool_block(masked):
        k, v = k_ref[0], v_ref[0]  # [bs, nkv, d], as the pool stores them
        if int8:
            k = k.astype(jnp.float32) * ks_ref[0][..., None]
            v = v.astype(jnp.float32) * vs_ref[0][..., None]
        k = jnp.swapaxes(k.astype(q.dtype), 0, 1)  # head-major [nkv, bs, d]
        v = jnp.swapaxes(v.astype(q.dtype), 0, 1)
        valid = None
        if masked:
            # a masked key's weight is exactly 0, and 0 x NaN is not: what the
            # block holds outside the row's context must not reach the sum
            krow = slot * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs, 1), 1)
            v = jnp.where(in_context(krow), v, jnp.zeros_like(v))
            valid = in_context(slot * bs + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2))
        fold(k, v, valid)

    # a row that holds no block is one program: nothing of the pool to fold
    holds = (flag & 1) != 0
    pl.when(holds & whole)(lambda: pool_block(masked=False))
    pl.when(holds & jnp.logical_not(whole))(lambda: pool_block(masked=True))

    @pl.when((flag & 4) != 0)
    def _finish():
        if E:
            epos = epos_ref[...]  # [1, 1, E]
            valid = (epos >= 0) & (epos <= qpos)
            if window:
                valid = valid & jnp.logical_not(window_too_far(qpos, epos, window))
            fold(jnp.swapaxes(ke_ref[0], 0, 1).astype(q.dtype),
                 jnp.swapaxes(ve_ref[0], 0, 1).astype(q.dtype), valid)
        # fully-masked token (all-trash padding): m never left NEG_INF and
        # every p degenerated to exp(0) — emit 0, matching the reference
        any_valid = m_scr[:, :, :1] > NEG_INF * 0.5
        out = jnp.where(any_valid, acc_scr[...] / jnp.maximum(l_scr[:, :, :1], 1e-30), 0.0)
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
    E = 0 if extra_kv is None else int(extra_kv[0].shape[1])
    q_pos = q_pos.astype(jnp.int32)
    if pool_limit is None:
        limit = q_pos + 1  # the causal <=
    else:
        limit = jnp.where(q_pos >= 0, jnp.asarray(pool_limit, jnp.int32).reshape(T), 0)
    n_visits, vrow, vslot, vflag = _visit_list(q_pos, limit, bs, B, int(window))
    group = nh // nkv
    # the queries go in scaled, a KV head's query heads together: the fold
    # batches over the KV heads and scales nothing a visit
    qs = (q.astype(jnp.float32) * (scale if scale is not None else d**-0.5)).astype(q.dtype)

    # index maps see (g, bt, qpos, trash, limit, vrow, vslot, vflag): a row's
    # operands follow vrow[g]; the pool's follow the table. A row that holds
    # no block points at a slot of its table all the same (a padded row: the
    # trash block, which the row before it already fetched if it was padded)
    def per_row(*shape):
        return pl.BlockSpec((1,) + shape, lambda g, *s: (s[4][g],) + (0,) * len(shape))

    def per_block(*shape):
        return pl.BlockSpec(
            (1,) + shape, lambda g, *s: (s[0][s[4][g], s[5][g]],) + (0,) * len(shape))

    in_specs = []
    if E:
        # [T, 1, E]: a (1, E) window of a [T, E] plane is not a legal Mosaic
        # block (last two dims must be (8, 128)-aligned or whole)
        in_specs.append(per_row(1, E))
    in_specs.append(per_row(nkv, group, d))
    in_specs.extend([per_block(bs, nkv, d), per_block(bs, nkv, d)])
    if int8_pool:
        in_specs.extend([per_block(bs, nkv), per_block(bs, nkv)])
    if E:
        in_specs.extend([per_row(E, nkv, d), per_row(E, nkv, d)])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(n_visits,),
        in_specs=in_specs,
        out_specs=per_row(nkv, group, d),
        scratch_shapes=[
            pltpu.VMEM((nkv, group, 128), jnp.float32),
            pltpu.VMEM((nkv, group, 128), jnp.float32),
            pltpu.VMEM((nkv, group, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, bs=bs, E=E, window=int(window), int8=int8_pool)
    operands = [
        block_tables.astype(jnp.int32),
        q_pos,
        jnp.asarray(trash_block, jnp.int32).reshape(1),
        limit,
        vrow,
        vslot,
        vflag,
    ]
    if E:
        operands.append(jnp.asarray(extra_kv[2], jnp.int32).reshape(T, 1, E))
    operands.append(qs.reshape(T, nkv, group, d))
    operands.extend([k_cache, v_cache])
    if int8_pool:
        operands.extend([k_scale, v_scale])
    if E:
        operands.extend([extra_kv[0], extra_kv[1]])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, nkv, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # one flat axis of visits: a row's programs follow one another
            # and accumulate into the same scratch
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name=PAGED_DECODE,
    )(*operands).reshape(T, nh, d)


def _visit_list(q_pos, limit, bs: int, B: int, window: int):
    """The programs of one kernel call, from what the call is given. Row
    ``t``'s context covers table slots ``lo..hi``: ``hi = ceil(limit / bs)``
    and ``lo`` the block of the first key a sliding ``window`` still admits
    (core.window_too_far: ``q_pos - window + 1``), else 0. Its programs are
    those slots in order, the last of them also its finish; a row that holds
    no block (a padded slot; a row whose only key is an extra column) is one
    program that folds nothing of the pool. Returns (the number of programs,
    then three [T * B] arrays): program ``g`` works for row ``vrow[g]`` on
    table slot ``vslot[g]`` under ``vflag[g]``: 1 a pool block to fold, 2
    the row's first program, 4 its last. Entries past the number of programs
    are never run."""
    T = q_pos.shape[0]
    hi = jnp.clip((limit + bs - 1) // bs, 0, B)
    lo = jnp.maximum(q_pos - window + 1, 0) // bs if window else jnp.zeros_like(hi)
    n = jnp.maximum(hi - lo, 0)
    cnt = jnp.maximum(n, 1)
    n_visits, j, of_row = _programs_of(cnt, T * B)
    n_g, cnt_g, lo_g = of_row(n), of_row(cnt), of_row(lo)
    vslot = jnp.clip(lo_g + j, 0, B - 1)
    flags = (j < n_g) + 2 * (j == 0) + 4 * (j == cnt_g - 1)
    return n_visits, of_row(jnp.arange(T, dtype=jnp.int32)), vslot, flags.astype(jnp.int32)


def _programs_of(cnt, G: int):
    """A flat axis of programs from how many each owner has (``cnt`` [T], an
    owner's programs following one another). Returns (the number of
    programs, ``j`` [G]: a program's ordinal among its owner's, ``of``: a
    function that takes a [T] array of the owners to [G], each program
    reading its owner's entry). Program g is owner t's iff ``starts[t] <= g
    < ends[t]``; a [G, T] compare and a sum stand in for the gathers, which
    the TPU runs an element at a time. Entries past the number of programs
    read 0."""
    ends = jnp.cumsum(cnt)
    starts = ends - cnt
    g = jnp.arange(G, dtype=jnp.int32)
    mine = (g[:, None] >= starts[None]) & (g[:, None] < ends[None])
    of = lambda x: jnp.sum(jnp.where(mine, x[None], 0), axis=1)
    return ends[-1], g - of(starts), of


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
    Pallas. One fused gather over every slot of every table: it over-reads
    unallocated (trash) slots, which the kernel skips, so it is the path
    for where the kernel does not run (off the TPU, tp_size > 1).
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


def chunk_kernel_takes(q_shape, pool_shape, pool_dtype, split_form: bool, interpret: bool) -> bool:
    """Whether ``dstpu_paged_chunk`` serves a call, from its shapes alone. It
    serves the split step's form (the chunk's own K/V beside the pool, read
    below ``pool_limit``) with the chunk a whole number of pool blocks. On
    the chip the blocks must fill Mosaic's tiles as well: 128 keys a block, a
    head of 128 or 256, and 2, 4 or a multiple of 8 KV heads: where the
    compiler for the chip reads a ``[bs, nkv, d]`` block of the pool in place
    (at 6 heads, at one, at a head of 64 it copies the whole pool into
    another layout first: described v5e, PR 30). An int8 pool (its scale
    planes), such geometries and the form whose pool already holds the chunk
    stay on the dense form."""
    _, tq, nh, d = q_shape
    bs, nkv = pool_shape[1], pool_shape[2]
    if not split_form or jnp.dtype(pool_dtype) == jnp.int8 or tq % bs or nh % nkv:
        return False
    return interpret or (bs % 128 == 0 and d % 128 == 0 and (nkv in (2, 4) or nkv % 8 == 0))


def _chunk_tile(tq: int, bs: int, nh: int, d: int) -> int:
    """Queries a program of ``dstpu_paged_chunk`` holds: up to 256, as many
    blocks of the chunk as keep the float32 accumulator ``[tile x nh, d]``
    within 4 MiB (256 rows of 16 heads of 256), at least one block. A pool
    block is then read once for 256 queries; tiles of 128 ran 10-30% longer
    at the cells' geometries, 512 no shorter (my chip run, PR 30)."""
    tile = bs
    while tile * 2 <= 256 and tq % (tile * 2) == 0 and tile * 2 * nh * d * 4 <= 4 << 20:
        tile *= 2
    return tile


def _chunk_visit_list(n, q0, limit, bs: int, B: int, tq: int, tile: int, window: int):
    """The programs of one ``dstpu_paged_chunk`` call. A UNIT is one tile of
    one row's queries (``tq / tile`` a row); its programs are the pool slots
    its row holds below ``limit`` (``lo..hi``, ``lo`` the block of the first
    key a sliding ``window`` admits to the tile's first query), then the
    chunk's own key blocks at or below the tile's last live query, the last
    of them also the finish. A tile with no live query (``n`` live queries a
    row, at consecutive positions from ``q0``) is one program that emits
    zeros. Returns (the number of programs, then five [G] arrays): the row,
    the tile, the table slot the pool's index map points at (for a chunk
    visit: where the row's walk ended, so nothing is fetched), the chunk key
    block the side values' index map points at (for a pool visit: the first
    the unit will need), and flags: 1 a pool visit, 2 the unit's first
    program, 4 its last. Entries past the number of programs never run."""
    Rc = n.shape[0]
    nqt, nkt = tq // tile, tq // bs
    i0 = jnp.tile(jnp.arange(nqt, dtype=jnp.int32) * tile, Rc)  # [U], U = Rc * nqt
    row = jnp.repeat(jnp.arange(Rc, dtype=jnp.int32), nqt)
    n_u, q0_u, lim_u = n[row], q0[row], limit[row]
    rows = jnp.clip(n_u - i0, 0, tile)
    hi = jnp.clip((lim_u + bs - 1) // bs, 0, B)
    lo = jnp.maximum(q0_u + i0 - window + 1, 0) // bs if window else jnp.zeros_like(hi)
    n_pool = jnp.where(rows > 0, jnp.maximum(hi - lo, 0), 0)
    khi = jnp.minimum((i0 + rows - 1) // bs + 1, nkt)
    klo = jnp.maximum(i0 - window + 1, 0) // bs if window else jnp.zeros_like(khi)
    n_chunk = jnp.where(rows > 0, khi - klo, 1)
    cnt = n_pool + n_chunk
    n_visits, j, of_unit = _programs_of(cnt, Rc * nqt * (B + nkt))
    np_g, cnt_g, lo_g, klo_g = of_unit(n_pool), of_unit(cnt), of_unit(lo), of_unit(klo)
    is_pool = j < np_g
    # a chunk visit keeps the pool's window where the unit's walk ended
    vpool = jnp.clip(jnp.where(is_pool, lo_g + j, lo_g + np_g - 1), 0, B - 1)
    vkt = jnp.clip(jnp.where(is_pool, klo_g, klo_g + j - np_g), 0, nkt - 1)
    flags = is_pool + 2 * (j == 0) + 4 * (j == cnt_g - 1)
    return n_visits, of_unit(row), of_unit(i0) // tile, vpool, vkt, flags.astype(jnp.int32)


def _chunk_kernel(*refs, bs, tile, nh, nkv, d, window):
    """One program of ``dstpu_paged_chunk``: a tile of one row's queries
    against one block of keys, flash state in scratch across the unit's
    programs (``_chunk_visit_list``). ``refs`` — scalar prefetch (SMEM): bt
    [Rc, B], n / q0 / limit [Rc], trash [1], vrow / vqt / vpool / vkt / vflag
    [G] — then tensor blocks (VMEM): q (1, nh, tile, d) head-major and
    scaled, the pool's k / v (1, bs, nkv, d), the chunk's own ke / ve
    (1, bs, nkv, d) — then o (1, nh, tile, d) and the m / l / acc scratch
    [nkv, M, .], a KV head's M rows its query heads' tiles. Operands enter
    the MXU in the queries' dtype; scores, softmax state and the accumulator
    are float32 and stay here."""
    (bt_ref, n_ref, q0_ref, limit_ref, trash_ref, vrow_ref, vqt_ref, vpool_ref, vkt_ref,
     vflag_ref, q_ref, k_ref, v_ref, ke_ref, ve_ref, o_ref, m_scr, l_scr, acc_scr) = refs
    g = pl.program_id(0)
    r, flag = vrow_ref[g], vflag_ref[g]
    i0 = vqt_ref[g] * tile
    group = nh // nkv
    M = group * tile  # score rows of one KV head: its query heads, tile rows each
    n, q0, limit = n_ref[r], q0_ref[r], limit_ref[r]
    rows = jnp.clip(n - i0, 0, tile)  # live queries of the tile

    @pl.when((flag & 2) != 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # One body serves both kinds of visit: which block of keys it reads and
    # what a query may see of it are scalars. ``base``: the position of the
    # block's first key. ``bound``: keys at or past it are not the row's (the
    # pool's limit; the chunk's live tokens: a row's live queries are its
    # first n, at consecutive positions from q0). ``causal``: a chunk's key is
    # seen from its own position on, a pool's by every query
    is_pool = (flag & 1) != 0
    slot, kt = vpool_ref[g], vkt_ref[g]
    held = bt_ref[r, slot] != trash_ref[0]
    base = jnp.where(is_pool, slot * bs, q0 + kt * bs)
    bound = jnp.where(is_pool, jnp.where(held, limit, 0), q0 + n)
    causal = jnp.logical_not(is_pool)
    first_pos, last_pos = q0 + i0, q0 + i0 + rows - 1  # the tile's live queries
    # no key of the block is masked for any live query of the tile
    whole = (base + bs <= bound) & (is_pool | (base + bs - 1 <= first_pos))
    if window:
        from deepspeed_tpu.ops.attention.core import window_too_far

        whole = whole & (last_pos - base < window)

    def visit(masked):
        """Fold the block into the flash state, every KV head in one batched
        product, [nkv, M, d] x [nkv, bs, d] (an operation a head is traced
        and lowered a head, 1.4 s a step shape at 16 KV heads, and ran 15-25%
        longer: my chip runs, PR 30). ``masked``: scores of keys a query may
        not see go to NEG_INF, and the values of keys NO query of the tile
        may see to 0: a masked key's weight is exactly 0, and 0 x NaN is not."""
        qa = q_ref[0].reshape(nkv, M, d)
        # [bs, nkv, d] from where the keys live, then head-major [nkv, bs, d]
        ka = jnp.swapaxes(jnp.where(is_pool, k_ref[0], ke_ref[0]), 0, 1).astype(qa.dtype)
        va = jnp.swapaxes(jnp.where(is_pool, v_ref[0], ve_ref[0]), 0, 1).astype(qa.dtype)
        if masked:
            q_pos = q0 + i0 + (jax.lax.broadcasted_iota(jnp.int32, (1, M, bs), 1) & (tile - 1))
            k_pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, M, bs), 2)
            valid = (k_pos < bound) & (jnp.logical_not(causal) | (k_pos <= q_pos))
            key_pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, bs, 1), 1)
            key_ok = key_pos < bound
            if window:
                valid = valid & jnp.logical_not(window_too_far(q_pos, k_pos, window))
                # the tile's first query reaches furthest back
                key_ok = key_ok & jnp.logical_not(window_too_far(first_pos, key_pos, window))
            va = jnp.where(key_ok, va, jnp.zeros_like(va))
        s = jax.lax.dot_general(
            qa, ka, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32)  # [nkv, M, bs]
        if masked:
            s = jnp.where(valid, s, NEG_INF)
        m_p = m_scr[:, :, :1]
        m_new = jnp.maximum(m_p, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_p - m_new)
        p = jnp.exp(s - m_new)
        l_scr[:, :, :1] = l_scr[:, :, :1] * alpha + jnp.sum(p, axis=2, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(qa.dtype), va, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_scr[:, :, :1] = m_new

    # a tile with no live query is one program: nothing to fold, zeros out
    pl.when((rows > 0) & whole)(lambda: visit(masked=False))
    pl.when((rows > 0) & jnp.logical_not(whole))(lambda: visit(masked=True))

    @pl.when((flag & 4) != 0)
    def _finish():
        # a padded query, and one no key reached (m never left NEG_INF), emit 0
        i = i0 + (jax.lax.broadcasted_iota(jnp.int32, (1, M, 1), 1) & (tile - 1))
        live = (i < n) & (m_scr[:, :, :1] > NEG_INF * 0.5)
        out = jnp.where(live, acc_scr[...] / jnp.maximum(l_scr[:, :, :1], 1e-30), 0.0)
        o_ref[0] = out.reshape(nh, tile, d).astype(o_ref.dtype)


def _paged_chunk_kernel_call(q, k_cache, v_cache, row_tables, q_pos, trash_block, new_kv,
                             pool_limit, *, window, scale, interpret, tile):
    """``paged_chunk_attention`` through ``dstpu_paged_chunk``: the pool read
    in place, a ``[bs, nkv, d]`` block a visit off the row's table, the
    chunk's own K/V from ``new_kv`` in blocks of the same shape, the walk
    bounded by what the row holds and causal by tiles. The queries go in
    head-major and scaled and the output comes back head-major: two small
    transposes XLA fuses into their neighbours."""
    Rc, tq, nh, d = q.shape
    NB, bs, nkv, _ = k_cache.shape
    B = row_tables.shape[1]
    tile = int(tile) if tile else _chunk_tile(tq, bs, nh, d)
    if tq % tile or tile % bs or tile & (tile - 1):
        raise ValueError(f"paged_chunk_attention: tile {tile} for tq {tq}, block size {bs}")
    q_pos = q_pos.astype(jnp.int32)
    n = jnp.sum(q_pos >= 0, axis=1, dtype=jnp.int32)
    q0 = jnp.maximum(q_pos[:, 0], 0)
    limit = jnp.where(n > 0, jnp.asarray(pool_limit, jnp.int32).reshape(Rc), 0)
    n_visits, vrow, vqt, vpool, vkt, vflag = _chunk_visit_list(
        n, q0, limit, bs, B, tq, tile, window)
    qs = (q.astype(jnp.float32) * (scale if scale is not None else d**-0.5)).astype(q.dtype)
    ke, ve = new_kv

    # index maps see (g, bt, n, q0, limit, trash, vrow, vqt, vpool, vkt, vflag)
    q_spec = pl.BlockSpec((1, nh, tile, d), lambda g, *s: (s[5][g], 0, s[6][g], 0))
    pool_spec = pl.BlockSpec(
        (1, bs, nkv, d), lambda g, *s: (s[0][s[5][g], s[7][g]], 0, 0, 0))
    # [Rc * tq / bs, bs, nkv, d]: the chunk's own K/V as blocks of the pool's shape
    side_spec = pl.BlockSpec(
        (1, bs, nkv, d), lambda g, *s: (s[5][g] * (tq // bs) + s[8][g], 0, 0, 0))
    M = nh // nkv * tile
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, bs=bs, tile=tile, nh=nh, nkv=nkv, d=d, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=10,
            grid=(n_visits,),
            in_specs=[q_spec, pool_spec, pool_spec, side_spec, side_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((nkv, M, 128), jnp.float32),
                pltpu.VMEM((nkv, M, 128), jnp.float32),
                pltpu.VMEM((nkv, M, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Rc, nh, tq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # one flat axis of visits: a unit's programs follow one another
            # and accumulate into the same scratch
            dimension_semantics=("arbitrary",),
            # the flash state of 256 queries x 16 heads of 256 is 8 MiB, the
            # query and output tiles twice 2 MiB each: over the 16 MiB default
            vmem_limit_bytes=64 << 20,
        ),
        interpret=interpret,
        name=PAGED_CHUNK,
    )(
        row_tables.astype(jnp.int32), n, q0, limit,
        jnp.asarray(trash_block, jnp.int32).reshape(1), vrow, vqt, vpool, vkt, vflag,
        qs.transpose(0, 2, 1, 3), k_cache, v_cache,
        ke.reshape(Rc * tq // bs, bs, nkv, d), ve.reshape(Rc * tq // bs, bs, nkv, d),
    )
    return out.transpose(0, 2, 1, 3)


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
    impl: str = "dense",
    interpret: bool = False,
    tile: Optional[int] = None,
) -> jax.Array:
    """Prefill-chunk attention: Rc rows x tq new tokens each, every row's
    tokens sharing that ROW's block table (q [Rc, tq, nh, d],
    row_tables [Rc, B], q_pos [Rc, tq] global positions, -1 = padding).
    Padded tail tokens (q_pos < 0) emit exactly 0.

    ``impl="kernel"``: the Pallas flash kernel ``dstpu_paged_chunk``
    (``_paged_chunk_kernel_call``) where ``chunk_kernel_takes`` the call,
    the dense form otherwise. It takes a row's live queries to be its first
    n, at consecutive positions (what ``_stage_split`` makes; the dense form
    reads every ``q_pos`` for itself); ``tile`` overrides its query tile
    (tests: several tiles at small sizes).
    ``impl="dense"``: one context gather per ROW (not per token: the decode
    kernel would walk the row's context once for every token of the chunk)
    then a dense masked softmax over the whole table.

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
    if impl not in ("dense", "kernel"):
        raise ValueError(f"paged_chunk_attention: unknown impl {impl!r} (expected 'dense' or 'kernel')")
    interpret = bool(interpret) or not on_tpu()
    if impl == "kernel" and chunk_kernel_takes(
            q.shape, k_cache.shape, k_cache.dtype, new_kv is not None and pool_limit is not None,
            interpret):
        return _paged_chunk_kernel_call(
            q, k_cache, v_cache, row_tables, q_pos, trash_block, new_kv, pool_limit,
            window=int(window), scale=scale, interpret=interpret, tile=tile)
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
