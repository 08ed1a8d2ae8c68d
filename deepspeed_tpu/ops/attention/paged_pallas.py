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
    list of programs the call's rows need (``_visit_list``): for each query
    row the table slots its context covers, in order, ``G`` consecutive slots
    to a program, ``G`` as many pool blocks as make about a megabyte
    (``blocks_a_program``: the geometry's alone, fixed when the call is
    built); the row's LAST program also folds the extra columns and writes
    the row out, and a row that holds no block is one program (the extra
    columns alone; a padded slot writes zeros). A slot a row does not hold is
    neither fetched nor folded, so a call costs what its rows hold, not
    ``T x B``. A program takes its ``[bs, nkv, d]`` blocks as they lie in the
    pool, joins them, swaps them to head-major once and folds them into the
    row's flash state in ONE online-softmax step (``_fold``), one product
    batched over the KV heads: operands in the queries' dtype (the pool's, in
    every served model; an int8 pool dequantises into it in VMEM), float32
    scores, softmax state and accumulator; the queries come in scaled. The
    blocks of a program come in through scalar-prefetched index maps, the
    pool given once a block of the group; the list is computed from ``q_pos``
    / ``pool_limit`` / ``window`` by a few small XLA fusions in front of the
    call.
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
# A program of ``dstpu_paged_decode`` reads pool blocks until they make this
# many bytes (``blocks_a_program``), four at most: the one threshold, set from
# the kernel alone on a v5e at the cells' geometries and rows (us a live block
# of 128 tokens at 1 / 2 / 4 blocks a program; my chip run, PR 46):
#   16 KV heads of 128, 1 MiB (OLMoE)           1.51 / 1.49 / 1.60  -> 1
#   8 x 128, 512 KiB (Qwen3, 5 blocks a row)    0.98 / 0.85 / 0.88  -> 2
#   8 x 128 (K-EXAONE, 24 blocks a row)         0.90 / 0.75 / 0.72  -> 2
#   8 x (192 + 128), 640 KiB (MiMo's rings)     1.28 / 1.04 / 1.11  -> 2
#   4 x (192 + 128), 320 KiB (MiMo's full)      0.75 / 0.59 / 0.50  -> 4
#   2 x 256, 256 KiB (Qwen3-Next)               1.00 / 0.77 / 0.77  -> 4
# A program costs ~0.35 us whatever it reads, beside 0.64 us for the bytes of a
# 512 KiB block. Four such blocks a program gain 4% over two on rows of 24
# blocks and lose 3% on rows of 5 (more index maps and a longer join cost what
# the fewer programs save), and a block that is a megabyte keeps the program
# it has: the chip reads such a program at 85% of its bytes, 8 x 128 at two
# blocks a program at 75-85% (65-71% at one).
PROGRAM_BYTES = 1 << 20
# ``_visit_list``'s flags: the group's first, second, .. slot holds a block of
# the row (2: the row's first program, 4: its last)
_GROUP_BITS = (1, 8, 16, 32)
MAX_BLOCKS_A_PROGRAM = len(_GROUP_BITS)


def paged_attention_reference(q, k_cache, v_cache, block_tables, q_pos, trash_block,
                              window: int = 0, scale=None, k_scale=None,
                              v_scale=None, sinks=None):
    """jnp reference: per-token context gather + masked softmax, mapped over
    tokens so peak memory is one context window ([S, nkv, d]) rather than T
    of them. Shapes as module docstring; returns [T, nh, d]. ``window``:
    static sliding-window band over sequence positions (mistral/starcoder2;
    band convention shared via core.window_too_far). ``k_scale``/``v_scale``
    [NB, bs, nkv]: per-vector fp32 dequant planes for an int8 pool
    (block_quant.quantize_kv). The values may be of another width than the
    keys (``v_cache [NB, bs, nkv, dv]``: the output is ``[T, nh, dv]``).
    ``sinks`` [nh] float32: a learned logit a head that joins the softmax's
    denominator and carries no value (``_softmax_with_sink``)."""
    T, nh, d = q.shape
    NB, bs, nkv, dv = v_cache.shape
    k_cache = k_cache.reshape(NB, bs, nkv, d)  # (keys a token a row: keys_flat)
    B = block_tables.shape[1]
    S = B * bs
    group = nh // nkv
    kpos = jnp.arange(S, dtype=jnp.int32)
    sink = None if sinks is None else jnp.asarray(sinks, jnp.float32).reshape(nkv, group)

    def one_token(args):
        qt, bt, pos = args  # [nh, d], [B], scalar
        k_ctx = k_cache[bt].reshape(S, nkv, d).astype(jnp.float32)
        v_ctx = v_cache[bt].reshape(S, nkv, dv).astype(jnp.float32)
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
        w = _softmax_with_sink(scores, sink)
        # fully-masked token (all-trash padding): return 0 like the kernel
        # does, not the uniform-softmax mean of trash V
        w = jnp.where(jnp.any(mask), w, 0.0)
        return jnp.einsum("ngs,snd->ngd", w, v_ctx).reshape(nh, dv)

    out = jax.lax.map(one_token, (q, block_tables, q_pos), batch_size=min(T, 32))
    return out.astype(q.dtype)


def keys_flat(head_dim: int) -> bool:
    """Whether a pool stores a token's keys as ONE ROW of ``nkv x head_dim``
    (``[NB, bs, nkv * d]``) and not a row a head (``[NB, bs, nkv, d]``): where
    a head is wider than the 128 lanes and does not fill them whole (192: what
    the kernels take of such widths, ``kernels_take``; a head of 64 or a test's
    16 keeps the row a head). The chip's compiler lays a ``[bs, nkv,
    192]`` pool out tokens-minor to spare the pad to 256 lanes and then copies
    the whole pool in front of every kernel call (described v5e, PR 39); a row
    of 4 x 192 = 768 or 8 x 192 = 1,536 is lane-exact, so the pool holds the
    bytes ``kv_pool`` counts and the call reads it in place. The engine shapes
    its K pools by this, the kernels take either form."""
    return head_dim > 128 and head_dim % 128 != 0


def _key_windows(nkv: int, d: int):
    """For keys stored a token a row: (the lane-aligned start of each head's
    window in the row, the head's offset inside it, the windows' width). A
    head's ``d`` lanes at ``h * d`` sit inside an aligned window of ``W``
    lanes that starts at the 128 below them (192: offsets 0 and 64, W 256);
    the queries carry zeros on the window's other lanes, so a product over
    the window is the product over the head, on whole vregs."""
    row = nkv * d
    W = min(row, max(-(-(h * d % 128 + d) // 128) * 128 for h in range(nkv)))
    # (a row's last window ends with the row: a small test size's one window)
    starts = [min(h * d // 128 * 128, row - W) for h in range(nkv)]
    offs = [h * d - w for h, w in enumerate(starts)]
    return starts, offs, W


def _queries_to_windows(q, nkv: int, d: int):
    """``q [..., nkv, G, d]`` -> ``[..., nkv, G, W]``: each KV head's queries
    at their head's offset in its window (``_key_windows``), zeros beside."""
    _, offs, W = _key_windows(nkv, d)
    pad = [(0, 0)] * (q.ndim - 2)
    return jnp.stack([jnp.pad(q[..., h, :, :], pad + [(o, W - d - o)])
                      for h, o in enumerate(offs)], axis=-3)


def _head_major(k, nkv: int, d: int):
    """A block of keys (or values) head-major ``[nkv, nk, .]``, inside a
    kernel: ``[nk, nkv, d]`` swapped once, or a token a row ``[nk, nkv * d]``
    as each head's aligned window of the row (``_key_windows``)."""
    if k.ndim == 3:
        return jnp.swapaxes(k, 0, 1)
    starts, _, W = _key_windows(nkv, d)
    return jnp.stack([k[:, w:w + W] for w in starts], axis=0)


def _values_head_major(v):
    """A block of values head-major ``[nkv, nk, dv]``, inside a kernel: ``[nk,
    nkv, dv]`` swapped once, or ONE head's ``[nk, dv]`` as it lies (``one_head``)."""
    return v[None] if v.ndim == 2 else jnp.swapaxes(v, 0, 1)


def one_head(k_cache, v_cache):
    """The pools as the kernels read ONE KV head (20 query heads on it, say):
    a token's head is its row, ``[NB, bs, d]``, which is what the bytes already
    are. Read a row a head, ``[bs, 1, d]`` a block, the chip's compiler wants
    the pool tiled two tokens a tile and copies it whole in front of every call
    (described v5e, PR 53: two copies of 173 M elements a decode step); a
    reshape that drops the axis of one is no copy. K then takes the kernels'
    keys-a-token-a-row form (``keys_flat``), V the same through
    ``_values_head_major``. Returns (k, v) unchanged for any other pool (an
    int8 pool's scale planes have no such form)."""
    if v_cache.shape[2] != 1 or v_cache.dtype == jnp.int8:
        return k_cache, v_cache
    return (k_cache.reshape(k_cache.shape[:2] + (-1,)), v_cache.reshape(v_cache.shape[:2] + (-1,)))


def _softmax_with_sink(scores, sink):
    """Softmax over the last axis of ``scores [..., nkv, group, S]`` with a
    head's learned ``sink [nkv, group]`` (or None) as one more logit in the
    denominator: it takes mass and adds no value, so its column is dropped."""
    if sink is None:
        return jax.nn.softmax(scores, axis=-1)
    col = jnp.broadcast_to(sink[..., None], scores.shape[:-1] + (1,))
    return jax.nn.softmax(jnp.concatenate([scores, col], axis=-1), axis=-1)[..., :-1]


def _fold_sink(m, l, acc, sink):
    """A flash state (m, l, acc) with the row's ``sink`` logit folded in as a
    key of value 0: the last fold of a row that has one."""
    m_new = jnp.maximum(m, sink)
    alpha = jnp.exp(m - m_new)
    return l * alpha + jnp.exp(sink - m_new), acc * alpha


def blocks_a_program(block_bytes: int) -> int:
    """How many consecutive table slots of its row a program of
    ``dstpu_paged_decode`` reads, from the bytes of ONE pool block of K and V as
    the pool stores them (``bs x (K row + V row) x itemsize``, an int8 pool's
    scale planes left out): the smallest power of two that brings a program to
    ``PROGRAM_BYTES``, at most ``MAX_BLOCKS_A_PROGRAM``. The engine's counter of
    programs (``_count_paged``) asks the same function."""
    g = 1
    while g < MAX_BLOCKS_A_PROGRAM and g * block_bytes < PROGRAM_BYTES:
        g *= 2
    return g


def _fold(q, k, v, valid, m_scr, l_scr, acc_scr):
    """One online-softmax step of a flash state held in scratch (``m_scr`` /
    ``l_scr`` [nkv, M, 128], column 0 meaningful, ``acc_scr`` [nkv, M, dv]):
    queries ``q`` [nkv, M, d] against keys ``k`` / values ``v`` [nkv, nk, .],
    every KV head in ONE batched product, operands as they come (the queries'
    dtype), float32 scores, state and accumulator, read and written once.
    ``valid`` [.., nk] broadcastable to the scores, or None where no key is
    masked; a masked key's VALUE is the caller's to zero (its weight is
    exactly 0, and 0 x NaN is not)."""
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )  # [nkv, M, nk]
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


def _paged_kernel(*refs, bs, nkv, d, G=1, E=0, window=0, int8=False, sink=False):
    """One program a GROUP of ``G`` consecutive table slots of one row: the
    grid is the list of groups the rows' contexts cover (``_visit_list``), each
    row's in order, so a table slot a row does not hold costs nothing; a row's
    LAST program also folds the extra columns and writes the row out. ``refs``
    layout — scalar prefetch (SMEM): bt [T, B], qpos [T], trash [1], limit
    [T], vrow / vslot / vflag [P] (the program's row, its first slot and its
    flags), then ``frow`` / ``fslot`` [P] for each block of the group after the
    first (where its index map points: read by the maps alone) — then tensor
    blocks (VMEM): epos (1, 1, E) if ``E``, q (1, nkv, group, d) scaled, G
    blocks of k (1, bs, nkv, d), G of v, G + G scale planes ks / vs (1, bs,
    nkv) if ``int8``, ke/ve (1, E, nkv, d) if ``E``, the sinks (nkv, group,
    128) float32 if ``sink`` (a head's logit on every lane; folded into a
    row's state at its finish) — then o (1, nkv, group, dv) and the m / l /
    acc flash scratch [nkv, group, .]. The values' width ``dv`` is the V
    block's own: a key of 192 beside a value of 128.

    The program's live blocks are folded as they lie in the pool, TOGETHER:
    joined to ``[n x bs, nkv, d]``, swapped to head-major once and folded in
    ONE online-softmax step (``_fold``), so the state is read and written once
    a program, not once a block. A block of the group that the row does not
    hold (its last program, where the blocks do not fill it) is neither
    fetched (its index repeats what that operand fetched last) nor folded.

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
    for _ in range(2 * (G - 1)):
        next(it)  # frow / fslot: the index maps' alone
    epos_ref = next(it) if E else None
    q_ref = next(it)
    k_refs, v_refs = [next(it) for _ in range(G)], [next(it) for _ in range(G)]
    ks_refs = [next(it) for _ in range(G)] if int8 else None
    vs_refs = [next(it) for _ in range(G)] if int8 else None
    ke_ref = next(it) if E else None
    ve_ref = next(it) if E else None
    sink_ref = next(it) if sink else None
    o_ref = next(it)
    m_scr, l_scr, acc_scr = next(it), next(it), next(it)
    state = (m_scr, l_scr, acc_scr)

    g = pl.program_id(0)
    t, slot, flag = vrow_ref[g], vslot_ref[g], vflag_ref[g]
    qpos = qpos_ref[t]
    B = bt_ref.shape[1]

    @pl.when((flag & 2) != 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]  # [nkv, group, d]

    # the pool holds the row's keys below ``limit`` (the explicit pool window
    # of the write-after-read protocol, else the causal <=; 0 for a padded
    # query slot, which must see nothing). A group wholly inside the row's
    # context takes no mask: all but a row's last (under a window, and its
    # first) are such
    limit = limit_ref[t]
    slots = [slot] + [jnp.minimum(slot + i, B - 1) for i in range(1, G)]
    live = [bt_ref[t, s] != trash_ref[0] for s in slots]
    whole = functools.reduce(jnp.logical_and, live) & ((slot + G) * bs <= limit)
    if window:
        from deepspeed_tpu.ops.attention.core import window_too_far

        whole = whole & (qpos - slot * bs < window)

    def in_context(kpos, n):
        """Which of the keys at ``kpos`` (the first ``n`` blocks' of the
        group) are the row's."""
        held = live[0]
        for i in range(1, n):  # (Mosaic selects no booleans)
            past = kpos >= (slot + i) * bs
            held = (past & live[i]) | (jnp.logical_not(past) & held)
        ok = (kpos < limit) & held
        if window:
            ok = ok & jnp.logical_not(window_too_far(qpos, kpos, window))
        return ok

    def pool_blocks(n, masked):
        """Fold the group's first ``n`` blocks, as the pool stores them."""
        def joined(blocks, scales):
            x = [r[0] for r in blocks[:n]]  # [bs, nkv, d] each
            if int8:
                x = [a.astype(jnp.float32) * s[0][..., None] for a, s in zip(x, scales)]
            return (x[0] if n == 1 else jnp.concatenate(x, axis=0)).astype(q.dtype)

        k, v = joined(k_refs, ks_refs), joined(v_refs, vs_refs)
        k = _head_major(k, nkv, d)  # [nkv, n x bs, d]
        v = _values_head_major(v)
        valid = None
        if masked:
            # a masked key's weight is exactly 0, and 0 x NaN is not: what the
            # blocks hold outside the row's context must not reach the sum
            krow = slot * bs + jax.lax.broadcasted_iota(jnp.int32, (1, n * bs, 1), 1)
            v = jnp.where(in_context(krow, n), v, jnp.zeros_like(v))
            valid = in_context(
                slot * bs + jax.lax.broadcasted_iota(jnp.int32, (1, 1, n * bs), 2), n)
        _fold(q, k, v, valid, *state)

    # a row that holds no block is one program: nothing of the pool to fold.
    # Flag 1: the group's first block is the row's; 8, 16, ..: its second,
    # third, .. are. A group that is not whole folds as many as it carries
    has = [(flag & b) != 0 for b in _GROUP_BITS[:G]]
    pl.when(has[0] & whole)(lambda: pool_blocks(G, masked=False))
    ragged = jnp.logical_not(whole)
    for n in range(1, G + 1):
        carries = has[n - 1] if n == G else has[n - 1] & jnp.logical_not(has[n])
        pl.when(carries & ragged)(functools.partial(pool_blocks, n, masked=True))

    @pl.when((flag & 4) != 0)
    def _finish():
        if E:
            epos = epos_ref[...]  # [1, 1, E]
            valid = (epos >= 0) & (epos <= qpos)
            if window:
                valid = valid & jnp.logical_not(window_too_far(qpos, epos, window))
            _fold(q, _head_major(ke_ref[0], nkv, d).astype(q.dtype),
                  _values_head_major(ve_ref[0]).astype(q.dtype), valid, *state)
        # fully-masked token (all-trash padding): m never left NEG_INF and
        # every p degenerated to exp(0) — emit 0, matching the reference
        m, l, acc = m_scr[:, :, :1], l_scr[:, :, :1], acc_scr[...]
        any_valid = m > NEG_INF * 0.5
        if sink:
            l, acc = _fold_sink(m, l, acc, sink_ref[:, :, :1])
        out = jnp.where(any_valid, acc / jnp.maximum(l, 1e-30), 0.0)
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
    sinks=None,
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
    ``scale``: softmax scale override (gpt_neo's unscaled logits). The V
    pool's width is its own (``v_cache [NB, bs, nkv, dv]``, the extras' values
    alike): the output is ``[T, nh, dv]``. ``sinks`` [nh] float32: a learned
    logit a head in the softmax's denominator (a window layer of
    mimo_v2_flash); None for a layer that has none."""
    T, nh, d = q.shape
    NB, bs, nkv, dv = v_cache.shape
    flat = k_cache.ndim == 3  # keys a token a row [NB, bs, nkv * d] (keys_flat)
    int8_pool = k_cache.dtype == jnp.int8
    if flat and int8_pool:
        raise ValueError("paged_attention: an int8 pool with keys a token a row has no form")
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
        impl = "kernel" if on_tpu() and kernels_take(nkv, d, dv) else "dense"
    if impl == "reference":
        if extra_kv is not None or pool_limit is not None:
            raise ValueError(
                "paged_attention: impl='reference' serves the plain parity "
                "form only (no extra_kv/pool_limit)"
            )
        return paged_attention_reference(
            q, k_cache, v_cache, block_tables, q_pos, trash_block,
            window=window, scale=scale, k_scale=k_scale, v_scale=v_scale, sinks=sinks,
        )
    if impl == "dense":
        return paged_decode_attention_dense(
            q, k_cache, v_cache, block_tables, q_pos, trash_block,
            window=window, scale=scale, extra_kv=extra_kv,
            pool_limit=pool_limit, k_scale=k_scale, v_scale=v_scale, sinks=sinks,
        )
    if impl != "kernel":
        raise ValueError(
            f"paged_attention: unknown impl {impl!r} "
            f"(expected one of {PAGED_ATTENTION_IMPLS})"
        )

    return _paged_kernel_call(
        q, k_cache, v_cache, block_tables, q_pos, trash_block, window=int(window), scale=scale,
        k_scale=k_scale, v_scale=v_scale, extra_kv=extra_kv, pool_limit=pool_limit, sinks=sinks,
        interpret=interpret)


def _paged_kernel_call(q, k_cache, v_cache, block_tables, q_pos, trash_block, *, window=0,
                       scale=None, k_scale=None, v_scale=None, extra_kv=None, pool_limit=None,
                       sinks=None, interpret=False, blocks: Optional[int] = None):
    """``paged_attention`` through ``dstpu_paged_decode`` (arguments as there,
    already checked). ``blocks``: how many table slots a program reads; None,
    what every caller but a test passes, is ``blocks_a_program`` of the pool's
    own block."""
    T, nh, d = q.shape
    NB, bs, nkv, dv = v_cache.shape
    k_cache, v_cache = one_head(k_cache, v_cache)
    flat = k_cache.ndim == 3  # keys a token a row (keys_flat; one_head)
    v_row = (nkv, dv) if v_cache.ndim == 4 else (dv,)
    int8_pool = k_cache.dtype == jnp.int8
    # off the TPU the kernel only runs interpreted (CPU tests)
    interpret = bool(interpret) or not on_tpu()
    B = block_tables.shape[1]
    E = 0 if extra_kv is None else int(extra_kv[0].shape[1])
    G = int(blocks) if blocks else blocks_a_program(bs * nkv * (d + dv) * k_cache.dtype.itemsize)
    q_pos = q_pos.astype(jnp.int32)
    if pool_limit is None:
        limit = q_pos + 1  # the causal <=
    else:
        limit = jnp.where(q_pos >= 0, jnp.asarray(pool_limit, jnp.int32).reshape(T), 0)
    n_programs, vrow, vslot, vflag, fetch = _visit_list(q_pos, limit, bs, B, int(window), G)
    group = nh // nkv
    # the queries go in scaled, a KV head's query heads together: the fold
    # batches over the KV heads and scales nothing a program
    qs = (q.astype(jnp.float32) * (scale if scale is not None else d**-0.5)).astype(q.dtype)
    qs = qs.reshape(T, nkv, group, d)
    k_row = (nkv, d)
    if flat:
        # a head's queries at its offset in its window of the row (_key_windows)
        qs, k_row = _queries_to_windows(qs, nkv, d), (nkv * d,)

    # index maps see (g, bt, qpos, trash, limit, vrow, vslot, vflag, then frow,
    # fslot of the group's second block, its third, ..): a row's operands
    # follow vrow[g]; the pool is given once a block of the group, block i
    # through the table at (frow, fslot)[i], the first at (vrow, vslot). A row
    # that holds no block points at a slot of its table all the same (a padded
    # row: the trash block, which the row before it already fetched if it was
    # padded)
    def per_row(*shape):
        return pl.BlockSpec((1,) + shape, lambda g, *s: (s[4][g],) + (0,) * len(shape))

    def per_block(*shape):
        at = [(4, 5)] + [(5 + 2 * i, 6 + 2 * i) for i in range(1, G)]  # (vrow, vslot), (frow, fslot)..
        return [pl.BlockSpec(
            (1,) + shape, lambda g, *s, r=r, c=c: (s[0][s[r][g], s[c][g]],) + (0,) * len(shape))
            for r, c in at]

    in_specs = []
    if E:
        # [T, 1, E]: a (1, E) window of a [T, E] plane is not a legal Mosaic
        # block (last two dims must be (8, 128)-aligned or whole)
        in_specs.append(per_row(1, E))
    in_specs.append(per_row(*qs.shape[1:]))
    in_specs.extend(per_block(bs, *k_row) + per_block(bs, *v_row))
    if int8_pool:
        in_specs.extend(per_block(bs, nkv) + per_block(bs, nkv))
    if E:
        in_specs.extend([per_row(E, *k_row), per_row(E, *v_row)])
    if sinks is not None:
        # whole, the same for every program: fetched once
        in_specs.append(pl.BlockSpec((nkv, group, 128), lambda g, *s: (0, 0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7 + len(fetch),
        grid=(n_programs,),
        in_specs=in_specs,
        out_specs=per_row(nkv, group, dv),
        scratch_shapes=[
            pltpu.VMEM((nkv, group, 128), jnp.float32),
            pltpu.VMEM((nkv, group, 128), jnp.float32),
            pltpu.VMEM((nkv, group, dv), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, bs=bs, nkv=nkv, d=d, G=G, E=E, window=int(window), int8=int8_pool,
        sink=sinks is not None)
    operands = [
        block_tables.astype(jnp.int32),
        q_pos,
        jnp.asarray(trash_block, jnp.int32).reshape(1),
        limit,
        vrow,
        vslot,
        vflag,
        *fetch,
    ]
    if E:
        operands.append(jnp.asarray(extra_kv[2], jnp.int32).reshape(T, 1, E))
    operands.append(qs)
    operands.extend([k_cache] * G + [v_cache] * G)
    if int8_pool:
        operands.extend([k_scale] * G + [v_scale] * G)
    if E:
        operands.extend([extra_kv[0].reshape((T, E) + k_row), extra_kv[1].reshape((T, E) + v_row)])
    if sinks is not None:
        operands.append(_sink_lanes(sinks, nkv, group))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, nkv, group, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # one flat axis of programs: a row's follow one another and
            # accumulate into the same scratch
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name=PAGED_DECODE,
    )(*operands).reshape(T, nh, dv)


def _sink_lanes(sinks, nkv: int, group: int):
    """The heads' sink logits as the kernels take them: float32 [nkv, group,
    128], a head's logit on every lane (the softmax state's own layout)."""
    return jnp.broadcast_to(
        jnp.asarray(sinks, jnp.float32).reshape(nkv, group, 1), (nkv, group, 128))


def kernels_take(kv_heads: int, head_dim: int, v_head_dim: Optional[int] = None) -> bool:
    """Whether a pool of this geometry goes to the paged kernels on the chip:
    THE predicate of the engine's ``auto`` gate, of ``paged_attention``'s and
    of ``chunk_kernel_takes``, so that both kernels or neither serve a pool.
    A key of 128, 192 or 256, a value of 128 or 256, and 1, 2, 4 or a
    multiple of 8 KV heads: where the compiler for the chip reads a ``[bs,
    nkv, d]`` block of the pool in place (ONE head as the row it is:
    ``one_head``). At 6 heads and at a head of 64 it copies the whole pool into
    another layout in front of every call (described v5e, PRs 30 and 35): such
    a pool takes the dense form."""
    dv = v_head_dim or head_dim
    return (head_dim in (128, 192, 256) and dv in (128, 256)
            and (kv_heads in (1, 2, 4) or kv_heads % 8 == 0))


def _visit_list(q_pos, limit, bs: int, B: int, window: int, G: int = 1):
    """The programs of one kernel call, from what the call is given. Row
    ``t``'s context covers table slots ``lo..hi``: ``hi = ceil(limit / bs)``
    and ``lo`` the block of the first key a sliding ``window`` still admits
    (core.window_too_far: ``q_pos - window + 1``), else 0. Its programs are
    those ``n`` slots in order, ``G`` to a program: ``ceil(n / G)`` of them,
    the last also its finish, and the last alone may carry fewer than ``G``;
    a row that holds no block (a padded slot; a row whose only key is an
    extra column) is one program that folds nothing of the pool. Returns (the
    number of programs, three [P] arrays, ``P = T x ceil(B / G)``, and a list
    of ``2 (G - 1)`` more): program ``g`` works for row ``vrow[g]`` from table
    slot ``vslot[g]`` on under ``vflag[g]``: 1 the slot holds a block to fold,
    2 the row's first program, 4 its last, then 8, 16, .. where the second,
    third, .. slot of the group holds one too. The list is ``frow, fslot`` of
    the group's second block, then of its third, ..: the entry of the table
    that block's index map reads. Where the row holds the block that is its
    own slot; where it does not, the entry that block of a group pointed at
    LAST (the row's program before, or an earlier row's), so the pipeline
    finds the index unchanged and fetches nothing. Entries past the number of
    programs are never run."""
    T = q_pos.shape[0]
    hi = jnp.clip((limit + bs - 1) // bs, 0, B)
    lo = jnp.maximum(q_pos - window + 1, 0) // bs if window else jnp.zeros_like(hi)
    n = jnp.maximum(hi - lo, 0)
    cnt = jnp.maximum(-(-n // G), 1)
    n_programs, j, of_row = _programs_of(cnt, T * -(-B // G))
    n_g, cnt_g, lo_g = of_row(n), of_row(cnt), of_row(lo)
    row = of_row(jnp.arange(T, dtype=jnp.int32))
    first = lo_g + j * G
    flags = (j * G < n_g) + 2 * (j == 0) + 4 * (j == cnt_g - 1)
    fetch = []
    for i in range(1, G):
        held = j * G + i < n_g
        flags = flags + _GROUP_BITS[i] * held
        # the entry block i pointed at last, a row: its last slot of ordinal i
        # in a group, in the latest row down to this one that has such a slot
        # (row x B + slot grows with the row: a running maximum finds it)
        last = jnp.arange(T, dtype=jnp.int32) * B + lo + (n - 1 - i) // G * G + i
        last = jnp.maximum(jax.lax.cummax(jnp.where(n > i, last, -1)), 0)
        last_g = of_row(last)
        fetch += [jnp.where(held, row, last_g // B), jnp.where(held, first + i, last_g % B)]
    return n_programs, row, jnp.clip(first, 0, B - 1), flags.astype(jnp.int32), fetch


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
    sinks=None,
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
    The values' width is the V pool's own; ``sinks`` [nh]: a learned logit a
    head in the denominator (``_softmax_with_sink``).
    """
    R, nh, d = q.shape
    NB, bs, nkv, dv = v_cache.shape
    k_cache = k_cache.reshape(NB, bs, nkv, d)  # (keys a token a row: keys_flat)
    B = block_tables.shape[1]
    S = B * bs
    group = nh // nkv
    sink = None if sinks is None else jnp.asarray(sinks, jnp.float32).reshape(nkv, group)
    k_ctx = (
        k_cache[block_tables].transpose(0, 3, 1, 2, 4).reshape(R, nkv, S, d)
    ).astype(jnp.float32)
    v_ctx = (
        v_cache[block_tables].transpose(0, 3, 1, 2, 4).reshape(R, nkv, S, dv)
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
        w = _softmax_with_sink(s, sink)
        w = jnp.where(any_valid[:, None, None, None], w, 0.0)
        out = jnp.einsum("rngs,rnsd->rngd", w[..., :S], v_ctx) + jnp.einsum(
            "rnge,rned->rngd", w[..., S:], ve32
        )
        return out.reshape(R, nh, dv).astype(q.dtype)
    w = _softmax_with_sink(s, sink)
    w = jnp.where(jnp.any(mask, axis=1)[:, None, None, None], w, 0.0)
    out = jnp.einsum("rngs,rnsd->rngd", w, v_ctx)
    return out.reshape(R, nh, dv).astype(q.dtype)


def chunk_kernel_takes(q_shape, pool_shape, pool_dtype, split_form: bool, interpret: bool,
                       v_head_dim: Optional[int] = None) -> bool:
    """Whether ``dstpu_paged_chunk`` serves a call, from its shapes alone. It
    serves the split step's form (the chunk's own K/V beside the pool, read
    below ``pool_limit``) with the chunk a whole number of pool blocks. On
    the chip the blocks must fill Mosaic's tiles as well: 128 keys a block
    and a geometry the paged kernels take (``kernels_take``: the one
    predicate, the engine's ``auto`` gate too). An int8 pool (its scale
    planes), other geometries and the form whose pool already holds the chunk
    stay on the dense form."""
    _, tq, nh, d = q_shape
    bs, nkv = pool_shape[1], pool_shape[2]
    if not split_form or jnp.dtype(pool_dtype) == jnp.int8 or tq % bs or nh % nkv:
        return False
    return interpret or (bs % 128 == 0 and kernels_take(nkv, d, v_head_dim))


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


def _chunk_kernel(*refs, bs, tile, nh, nkv, d, dv, window, sink):
    """One program of ``dstpu_paged_chunk``: a tile of one row's queries
    against one block of keys, flash state in scratch across the unit's
    programs (``_chunk_visit_list``). ``refs`` — scalar prefetch (SMEM): bt
    [Rc, B], n / q0 / limit [Rc], trash [1], vrow / vqt / vpool / vkt / vflag
    [G] — then tensor blocks (VMEM): q (1, nh, tile, d) head-major and
    scaled, the pool's k (1, bs, nkv, d) / v (1, bs, nkv, dv), the chunk's own
    ke / ve of the same shapes, the sinks (nkv, group, 128) float32 if
    ``sink`` — then o (1, nh, tile, dv) and the m / l / acc scratch
    [nkv, M, .], a KV head's M rows its query heads' tiles. Operands enter
    the MXU in the queries' dtype; scores, softmax state and the accumulator
    are float32 and stay here."""
    (bt_ref, n_ref, q0_ref, limit_ref, trash_ref, vrow_ref, vqt_ref, vpool_ref, vkt_ref,
     vflag_ref, q_ref, k_ref, v_ref, ke_ref, ve_ref) = refs[:15]
    sink_ref = refs[15] if sink else None
    o_ref, m_scr, l_scr, acc_scr = refs[-4:]
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
        qa = q_ref[0].reshape(nkv, M, q_ref.shape[-1])
        # [bs, nkv, d] from where the keys live, then head-major [nkv, bs, d]
        ka = _head_major(jnp.where(is_pool, k_ref[0], ke_ref[0]), nkv, d).astype(qa.dtype)
        va = _values_head_major(jnp.where(is_pool, v_ref[0], ve_ref[0])).astype(qa.dtype)
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
        _fold(qa, ka, va, valid if masked else None, m_scr, l_scr, acc_scr)

    # a tile with no live query is one program: nothing to fold, zeros out
    pl.when((rows > 0) & whole)(lambda: visit(masked=False))
    pl.when((rows > 0) & jnp.logical_not(whole))(lambda: visit(masked=True))

    @pl.when((flag & 4) != 0)
    def _finish():
        # a padded query, and one no key reached (m never left NEG_INF), emit 0
        i = i0 + (jax.lax.broadcasted_iota(jnp.int32, (1, M, 1), 1) & (tile - 1))
        m, l, acc = m_scr[:, :, :1], l_scr[:, :, :1], acc_scr[...]
        live = (i < n) & (m > NEG_INF * 0.5)
        if sink:
            # a query head's logit for each of its tile's rows
            b = jnp.broadcast_to(sink_ref[:, :, :1][:, :, None], (nkv, group, tile, 1))
            l, acc = _fold_sink(m, l, acc, b.reshape(nkv, M, 1))
        out = jnp.where(live, acc / jnp.maximum(l, 1e-30), 0.0)
        o_ref[0] = out.reshape(nh, tile, dv).astype(o_ref.dtype)


def _paged_chunk_kernel_call(q, k_cache, v_cache, row_tables, q_pos, trash_block, new_kv,
                             pool_limit, *, window, scale, interpret, tile, sinks=None):
    """``paged_chunk_attention`` through ``dstpu_paged_chunk``: the pool read
    in place, a ``[bs, nkv, d]`` block a visit off the row's table, the
    chunk's own K/V from ``new_kv`` in blocks of the same shape, the walk
    bounded by what the row holds and causal by tiles. The queries go in
    head-major and scaled and the output comes back head-major: two small
    transposes XLA fuses into their neighbours."""
    Rc, tq, nh, d = q.shape
    NB, bs, nkv, dv = v_cache.shape
    k_cache, v_cache = one_head(k_cache, v_cache)
    flat = k_cache.ndim == 3  # keys a token a row (keys_flat; one_head)
    v_row = (nkv, dv) if v_cache.ndim == 4 else (dv,)
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
    qs = qs.transpose(0, 2, 1, 3)  # head-major [Rc, nh, tq, d]
    ke, ve = new_kv
    k_row = (nkv, d)
    if flat:
        # a head's queries at its offset in its window of the row (_key_windows)
        qs = _queries_to_windows(qs.reshape(Rc, nkv, -1, d), nkv, d)
        qs, k_row = qs.reshape(Rc, nh, tq, qs.shape[-1]), (nkv * d,)

    # index maps see (g, bt, n, q0, limit, trash, vrow, vqt, vpool, vkt, vflag)
    def rows_spec(w):  # a tile of a row's queries (or outputs), head-major
        return pl.BlockSpec((1, nh, tile, w), lambda g, *s: (s[5][g], 0, s[6][g], 0))

    def pool_spec(*row):
        return pl.BlockSpec(
            (1, bs) + row, lambda g, *s: (s[0][s[5][g], s[7][g]],) + (0,) * (1 + len(row)))

    # [Rc * tq / bs, bs, ..]: the chunk's own K/V as blocks of the pool's shape
    def side_spec(*row):
        return pl.BlockSpec(
            (1, bs) + row,
            lambda g, *s: (s[5][g] * (tq // bs) + s[8][g],) + (0,) * (1 + len(row)))

    group = nh // nkv
    M = group * tile
    in_specs = [rows_spec(qs.shape[-1]), pool_spec(*k_row), pool_spec(*v_row),
                side_spec(*k_row), side_spec(*v_row)]
    operands = [qs, k_cache, v_cache, ke.reshape((Rc * tq // bs, bs) + k_row),
                ve.reshape((Rc * tq // bs, bs) + v_row)]
    if sinks is not None:
        in_specs.append(pl.BlockSpec((nkv, group, 128), lambda g, *s: (0, 0, 0)))
        operands.append(_sink_lanes(sinks, nkv, group))
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, bs=bs, tile=tile, nh=nh, nkv=nkv, d=d, dv=dv,
                          window=window, sink=sinks is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=10,
            grid=(n_visits,),
            in_specs=in_specs,
            out_specs=rows_spec(dv),
            scratch_shapes=[
                pltpu.VMEM((nkv, M, 128), jnp.float32),
                pltpu.VMEM((nkv, M, 128), jnp.float32),
                pltpu.VMEM((nkv, M, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Rc, nh, tq, dv), q.dtype),
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
        *operands,
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
    sinks=None,
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
    dtype). The values' width is the V pool's own (output ``[Rc, tq, nh,
    dv]``); ``sinks`` [nh] float32: a learned logit a head in the softmax's
    denominator, None for a layer that has none."""
    Rc, tq, nh, d = q.shape
    NB, bs, nkv, dv = v_cache.shape
    B = row_tables.shape[1]
    if impl not in ("dense", "kernel"):
        raise ValueError(f"paged_chunk_attention: unknown impl {impl!r} (expected 'dense' or 'kernel')")
    interpret = bool(interpret) or not on_tpu()
    if impl == "kernel" and chunk_kernel_takes(
            q.shape, v_cache.shape, k_cache.dtype, new_kv is not None and pool_limit is not None,
            interpret, dv):
        return _paged_chunk_kernel_call(
            q, k_cache, v_cache, row_tables, q_pos, trash_block, new_kv, pool_limit,
            window=int(window), scale=scale, interpret=interpret, tile=tile, sinks=sinks)
    k_cache = k_cache.reshape(NB, bs, nkv, d)  # (keys a token a row: keys_flat)
    S = B * bs
    group = nh // nkv
    # [nkv, 1, group]: beside scores laid out [Rc, nkv, tq, group, keys]
    sink = None if sinks is None else jnp.asarray(sinks, jnp.float32).reshape(nkv, 1, group)
    k_ctx = (
        k_cache[row_tables].transpose(0, 3, 1, 2, 4).reshape(Rc, nkv, S, d)
    ).astype(jnp.float32)
    v_ctx = (
        v_cache[row_tables].transpose(0, 3, 1, 2, 4).reshape(Rc, nkv, S, dv)
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
        w = _softmax_with_sink(s, sink)
        w = jnp.where(any_valid[:, None, :, None, None], w, 0.0)
        out = jnp.einsum("rntgs,rnsd->rtngd", w[..., :S], v_ctx) + jnp.einsum(
            "rntgj,rnjd->rtngd", w[..., S:], ve32
        )
        return out.reshape(Rc, tq, nh, dv).astype(q.dtype)
    w = _softmax_with_sink(s, sink)
    w = jnp.where(jnp.any(mask, axis=2)[:, None, :, None, None], w, 0.0)
    out = jnp.einsum("rntgs,rnsd->rtngd", w, v_ctx)
    return out.reshape(Rc, tq, nh, dv).astype(q.dtype)
