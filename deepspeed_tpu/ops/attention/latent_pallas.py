"""Paged attention over a LATENT pool (DeepseekV3 / A.X-K1 latent attention).

A token's keys and values are one vector every head shares: the normed latent
``c`` [rank] and the rotated key dims ``k_r`` [rope] (``D = rank + rope``: 512 +
64). A head's keys and values are ``kv_b_proj`` of the latent (``W_UK``, ``W_UV``:
``wkv_b`` split by head), and there are two ways to attend over the cache:

  * ABSORBED: ``W_UK`` is moved to the query (``q_lat = q_nope W_UK``, then ``q =
    [q_lat | q_rope]`` [nh, D]) and ``W_UV`` behind the output (``o = (p c)
    W_UV``), so every head scores against the same ``[keys, D]`` matrix and sums
    the same ``[keys, rank]`` values: one "KV head" of D for scores whose first
    ``rank`` dims are its values. A score costs ``2 (D + rank)`` = 2,176
    operations, and a cached token ``nh x (D + rank) x 2`` of them for its ``2 D``
    bytes: ~121 a byte at 64 heads, half the v5e's ridge, where the per-head
    paged kernels do 2-16.
  * EXPANDED (the model's own form: ``models.transformer._latent_attention_block``):
    a head's keys ``[W_UK_h^T c | k_r]`` of 192 and values ``W_UV_h^T c`` of 128
    are made of the cached latent, ``2 x rank x 256`` operations a (key, head)
    shared by every query that sees the key, and a score costs ``2 (192 + 128)``
    = 640.

Which form is the cheaper is a matter of the QUERIES A KEY: ``2,176 = 640 +
262,144 / Q`` at ``Q`` = 171. A decode row is one query a key and attends
absorbed (``dstpu_mla_decode``). A chunk row of ``prompt_chunk`` (512) slots pays
``640 + 512`` = 1,152 a score expanded against 2,176, 1.9x fewer, with a flash
accumulator a quarter as wide: ``latent_chunk`` takes rows of ``EXPAND_FROM``
slots or more through the expanded body and the 128-slot bucket (one row of at
most 128 tokens: a prompt's tail, half full on average, whose dead query tiles
the absorbed body skips while the expansion is paid whatever the fill) through
the absorbed one. Timed alone on a v5e (``tools/mla_kernels.py``; PERF.md section
6, PR 62): at 512 slots the expanded form takes 50-57% of the absorbed form's
time at 2k-32k cached tokens, 64 heads or 32; at 128 slots a FULL row is 3-9%
faster expanded, a half-full one 1.8x slower, a quarter-full one 3.3x.

Layout:
  pool    [P, D, bs]  a block is D ROWS of ``bs`` tokens (the latent dim on the
          sublanes, the block's tokens on the lanes). D = 576 is not a multiple
          of the 128 lanes: token-major ``[bs, 576]`` blocks are padded to 640
          in HBM or re-laid-out by XLA in front of every kernel call (the chip's
          compiler picks ``{1,2,0}`` for ``bf16[N,128,576]``); ``[576, 128]`` is
          whole tiles, and it is also the operand both forms want (``q [nh, D] @
          block [D, bs]``; ``[W_UK_h | W_UV_h]^T @ block[:rank]``) with no
          transpose of it in the kernel. The engine passes a flat multi-layer
          view ``[L * NBp, D, bs]`` with layer-offset tables, read as the step
          received it.
  q       absorbed: [T, nh, D], one a decode row (``latent_decode``), or [Rc, tq,
          nh, D] for chunk rows (``latent_chunk_absorbed``); out [.., nh, rank]:
          softmax(q k^T) c, the caller applies ``W_UV``.
          expanded (``latent_chunk_expanded``): the query projection AS WRITTEN,
          [Rc, tq, nh x 192] (a head's ``[nope | rope]`` on adjacent lanes: the
          kernel takes the nope dims where they lie), the rotated rope dims [Rc,
          tq, nh, 64], and ``wkv_b`` [rank, nh x 256] as the checkpoint stores
          it, a head group's columns indexed out by the BlockSpec; out [Rc, tq,
          nh x 128], what the output projection takes.

Kernels (on a TPU; interpreted in the tests), each beside a dense XLA form that
runs off the TPU and is its oracle:
  * ``dstpu_mla_decode``: one program a (row, ``VISIT_BLOCKS`` table slots the
    row's context covers), the visit list of ``paged_pallas._visit_list`` over
    slots that wide; the row's last program folds the step's own not-yet-cached
    vectors and writes the row.
  * ``dstpu_mla_chunk``, whichever body attends a latent layer's chunk rows
    (the readers of a trace find it by this one name). Both walk
    ``VISIT_BLOCKS`` blocks a program: the pool blocks the row holds below the
    chunk, then the chunk's own vectors, causal
    (``paged_pallas._chunk_visit_list``). ABSORBED: a tile of a row's queries,
    every head (``tile x nh`` score rows). EXPANDED: a row's whole chunk and
    ``EXPANDED_HEADS`` heads a program on a grid (head groups, visits), the
    head-group axis OUTSIDE the visits so that a group's weights stay in VMEM
    (transposed and scaled once a group) across the rows' walks; a head's keys
    and values live in VMEM for the one visit they serve, nothing context-sized
    is written to HBM.
  * ``dstpu_mla_write``: THE pool write of a step. XLA's scatter of a column
    into ``[D, bs]`` blocks transposes the whole pool in front of it and back
    behind it (two pool-sized copies a step: the chip's compiler, PERF.md
    section 6, PR 37), so the step's new vectors are merged into the blocks they
    land in by a kernel that aliases the pool: one program a (block, tile of 128
    new tokens) pair, the tokens put on their lanes by a 0/1 matrix product.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator.device import on_tpu
from deepspeed_tpu.ops.attention.paged_pallas import NEG_INF, _chunk_visit_list, _visit_list
from deepspeed_tpu.ops.stack_matmul import Stacked

# The kernels' names in a device trace; benchmarks/metrics readers find them by these.
MLA_DECODE = "dstpu_mla_decode"
MLA_CHUNK = "dstpu_mla_chunk"
MLA_WRITE = "dstpu_mla_write"
# new tokens a write visit merges: one lane tile of the transposed side buffer
WRITE_TILE = 128
# pool blocks a decode or chunk program folds at once: a program of ONE [576, 128]
# block is a grid step of ~0.45 us for 147 KB (the decode kernel read the pool at
# 330 GB/s) and, for a chunk's 2,048 score rows, rescales a 4 MiB accumulator for
# 128 keys (41% of the MXU's peak); four blocks side by side on the lanes amortise
# both (my chip runs, PR 37: PERF.md section 6)
VISIT_BLOCKS = 4
# chunk rows of this many slots or more attend EXPANDED (module header: past ~170
# queries a key the expansion is the cheaper; the step's buckets are 128 and
# ``prompt_chunk``: engine_v2._chunk_bucket)
EXPAND_FROM = 256
# heads a program of the expanded chunk kernel (my chip runs, PR 62: PERF.md section 6)
EXPANDED_HEADS = 4


def _flash_fold(s, values_t, m_scr, l_scr, acc_scr, col=0):
    """One online-softmax step: scores ``s`` [M, nk] float32 (masked entries at
    NEG_INF) and the keys' values TRANSPOSED ``values_t`` [width, nk] into the
    state in scratch (m / l [M, 128] with column ``col`` meaningful, acc [M,
    width])."""
    at = slice(col, col + 1)
    m_p = m_scr[:, at]
    m_new = jnp.maximum(m_p, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_p - m_new)
    p = jnp.exp(s - m_new)
    l_scr[:, at] = l_scr[:, at] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p.astype(values_t.dtype), values_t, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[:, at] = m_new


def _held_below(bt_ref, trash_ref, t, slot, limit, bs, kb, B):
    """Keys of row ``t``'s table slots ``slot * kb ..`` that are the row's: those
    below ``limit``, and below the first of the ``kb`` slots that names the trash
    block (none does inside a live row's context; a scalar a slot)."""
    for i in range(kb):
        j = jnp.minimum(slot * kb + i, B - 1)
        limit = jnp.where(bt_ref[t, j] == trash_ref[0], jnp.minimum(limit, (slot * kb + i) * bs),
                          limit)
    return limit


def _chunk_visit_bounds(bt_ref, trash_ref, r, flag, slot, kt, n, q0, limit, first_pos, bs, kb, B):
    """What a chunk program's ``kb x bs`` keys are to row ``r``'s queries, as
    scalars (one body serves a pool visit and a visit of the chunk's own keys,
    as in paged_pallas._chunk_kernel): (is_pool; ``base``, the position of the
    visit's first key; ``bound``, keys at or past it are not the row's: the
    pool's limit and its first trash slot, or the chunk's ``n`` live tokens from
    ``q0``; ``whole``: every query from ``first_pos`` on sees every key, so no
    mask is built)."""
    wide = kb * bs
    is_pool = (flag & 1) != 0
    base = jnp.where(is_pool, slot * wide, q0 + kt * wide)
    bound = jnp.where(is_pool, _held_below(bt_ref, trash_ref, r, slot, limit, bs, kb, B), q0 + n)
    whole = (base + wide <= bound) & (is_pool | (base + wide - 1 <= first_pos))
    return is_pool, base, bound, whole


def _mla_decode_kernel(*refs, bs, rank, E, kb, B):
    """One program a VISIT (``_visit_list`` over slots of ``kb`` blocks).
    ``refs``: scalar prefetch bt [T, B], qpos [T], trash [1], limit [T], vrow /
    vslot / vflag [G]; then epos (1, 1, E) if ``E``, q (1, nh, D) scaled, ``kb``
    pool blocks (1, D, bs) (table slots ``vslot * kb + i``), the step's own
    vectors ke (1, E, D) if ``E``; then o (1, nh, rank) and the m / l / acc
    scratch. The blocks are folded as they lie in the pool, side by side on the
    lanes: scores ``q @ [D, kb x bs]``, values their first ``rank`` rows."""
    it = iter(refs)
    bt_ref, qpos_ref, trash_ref, limit_ref = next(it), next(it), next(it), next(it)
    vrow_ref, vslot_ref, vflag_ref = next(it), next(it), next(it)
    epos_ref = next(it) if E else None
    q_ref = next(it)
    k_refs = [next(it) for _ in range(kb)]
    ke_ref = next(it) if E else None
    o_ref = next(it)
    m_scr, l_scr, acc_scr = next(it), next(it), next(it)

    g = pl.program_id(0)
    t, slot, flag = vrow_ref[g], vslot_ref[g], vflag_ref[g]
    qpos, limit = qpos_ref[t], limit_ref[t]

    @pl.when((flag & 2) != 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]  # [nh, D]
    limit = _held_below(bt_ref, trash_ref, t, slot, limit, bs, kb, B)
    whole = (slot + 1) * kb * bs <= limit

    def pool_block(masked):
        k_t = jnp.concatenate([r[0] for r in k_refs], axis=1) if kb > 1 else k_refs[0][0]
        if masked:
            # a masked key's weight is exactly 0, and 0 x NaN is not: what the
            # blocks hold outside the row's context must not reach the sum
            ok = (slot * kb * bs + jax.lax.broadcasted_iota(jnp.int32, (1, kb * bs), 1)) < limit
            k_t = jnp.where(ok, k_t, jnp.zeros_like(k_t))
        s = jnp.dot(q, k_t, preferred_element_type=jnp.float32)  # [nh, bs]
        if masked:
            s = jnp.where(ok, s, NEG_INF)
        _flash_fold(s, k_t[:rank], m_scr, l_scr, acc_scr)

    holds = (flag & 1) != 0
    pl.when(holds & whole)(lambda: pool_block(masked=False))
    pl.when(holds & jnp.logical_not(whole))(lambda: pool_block(masked=True))

    @pl.when((flag & 4) != 0)
    def _finish():
        # the step's own vectors, one column each: a product of one column is
        # the VPU's (the MXU would pad it to a tile)
        qf = q.astype(jnp.float32)
        for e in range(E):
            epos = epos_ref[0, :, e:e + 1]  # [1, 1]
            ke = ke_ref[0, e:e + 1, :].astype(jnp.float32)  # [1, D]
            valid = (epos >= 0) & (epos <= qpos)
            s = jnp.where(valid, jnp.sum(qf * ke, axis=1, keepdims=True), NEG_INF)  # [nh, 1]
            m_p = m_scr[:, :1]
            m_new = jnp.maximum(m_p, s)
            alpha = jnp.exp(m_p - m_new)
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l_scr[:, :1] = l_scr[:, :1] * alpha + p
            acc_scr[...] = acc_scr[...] * alpha + p * ke[:, :rank]
            m_scr[:, :1] = m_new
        # a padded slot: m never left NEG_INF; emit 0 like the dense form
        any_valid = m_scr[:, :1] > NEG_INF * 0.5
        out = jnp.where(any_valid, acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30), 0.0)
        o_ref[0] = out.astype(o_ref.dtype)


def latent_decode_dense(q, pool, tables, q_pos, trash_block, *, rank, scale, extra=None,
                        pool_limit=None):
    """``latent_decode`` as plain XLA: gather every slot of every table, then a
    masked softmax. Off the TPU, and the kernel's oracle."""
    R, nh, D = q.shape
    B, bs = tables.shape[1], pool.shape[2]
    ctx = pool[tables].transpose(0, 1, 3, 2).reshape(R, B * bs, D).astype(jnp.float32)
    kpos = jnp.arange(B * bs, dtype=jnp.int32)
    limit = q_pos + 1 if pool_limit is None else jnp.asarray(pool_limit, jnp.int32)
    valid = ((kpos[None] < limit[:, None]) & jnp.repeat(tables != trash_block, bs, axis=1)
             & (q_pos >= 0)[:, None])
    qs = q.astype(jnp.float32) * scale
    s = jnp.where(valid[:, None], jnp.einsum("rhd,rsd->rhs", qs, ctx), NEG_INF)
    vals = jnp.where(valid[..., None], ctx[..., :rank], 0.0)
    if extra is not None:
        ke, epos = extra[0].astype(jnp.float32), extra[1]
        ok = (epos >= 0) & (epos <= q_pos[:, None])
        s = jnp.concatenate(
            [s, jnp.where(ok[:, None], jnp.einsum("rhd,red->rhe", qs, ke), NEG_INF)], axis=-1)
        vals = jnp.concatenate([vals, jnp.where(ok[..., None], ke[..., :rank], 0.0)], axis=1)
        valid = jnp.concatenate([valid, ok], axis=1)
    w = jnp.where(jnp.any(valid, axis=1)[:, None, None], jax.nn.softmax(s, axis=-1), 0.0)
    return jnp.einsum("rhs,rsc->rhc", w, vals).astype(q.dtype)


def latent_decode(q, pool, tables, q_pos, trash_block, *, rank: int, scale: float,
                  extra=None, pool_limit=None, impl: str = "dense", interpret: bool = False):
    """Absorbed decode attention over the latent pool. q [T, nh, D] (one query a
    row), pool [P, D, bs], tables [T, B] (layer-offset), q_pos [T] (-1: a padded
    slot, which emits 0), ``trash_block`` (may be traced). ``extra`` = (ke [T, E,
    D], epos [T, E]): the step's not-yet-cached vectors and their positions;
    ``pool_limit`` [T]: the pool is read below it (default the causal
    ``q_pos + 1``). Returns [T, nh, rank] in q's dtype. ``impl``: "kernel"
    (``dstpu_mla_decode``) or "dense"."""
    if impl not in ("kernel", "dense"):
        raise ValueError(f"latent_decode: unknown impl {impl!r} (expected 'kernel' or 'dense')")
    q_pos = q_pos.astype(jnp.int32)
    if impl == "dense":
        return latent_decode_dense(q, pool, tables, q_pos, trash_block, rank=rank, scale=scale,
                                   extra=extra, pool_limit=pool_limit)
    interpret = bool(interpret) or not on_tpu()
    T, nh, D = q.shape
    bs, B = pool.shape[2], tables.shape[1]
    E = 0 if extra is None else int(extra[0].shape[1])
    limit = q_pos + 1 if pool_limit is None else jnp.where(
        q_pos >= 0, jnp.asarray(pool_limit, jnp.int32).reshape(T), 0)
    kb = VISIT_BLOCKS
    n_visits, vrow, vslot, vflag, _ = _visit_list(q_pos, limit, bs * kb, -(-B // kb), 0)
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)

    def per_row(*shape):
        return pl.BlockSpec((1,) + shape, lambda g, *s: (s[4][g],) + (0,) * len(shape))

    def pool_block(i):  # table slot vslot * kb + i of the visit's row
        return pl.BlockSpec((1, D, bs), lambda g, *s: (
            s[0][s[4][g], jnp.minimum(s[5][g] * kb + i, B - 1)], 0, 0))

    in_specs = ([per_row(1, E)] if E else []) + [per_row(nh, D)] + [
        pool_block(i) for i in range(kb)]
    operands = [tables.astype(jnp.int32), q_pos, jnp.asarray(trash_block, jnp.int32).reshape(1),
                limit, vrow, vslot, vflag]
    if E:
        operands.append(jnp.asarray(extra[1], jnp.int32).reshape(T, 1, E))
    operands += [qs] + [pool] * kb
    if E:
        in_specs.append(per_row(E, D))
        operands.append(extra[0].astype(q.dtype))
    return pl.pallas_call(
        functools.partial(_mla_decode_kernel, bs=bs, rank=rank, E=E, kb=kb, B=B),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7, grid=(n_visits,), in_specs=in_specs,
            out_specs=per_row(nh, rank),
            scratch_shapes=[pltpu.VMEM((nh, 128), jnp.float32), pltpu.VMEM((nh, 128), jnp.float32),
                            pltpu.VMEM((nh, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((T, nh, rank), q.dtype),
        # one flat axis of visits: a row's programs follow one another and
        # accumulate into the same scratch
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=MLA_DECODE,
    )(*operands)


def _mla_chunk_kernel(*refs, bs, tile, nh, rank, kb, B):
    """One program of ``dstpu_mla_chunk``: a tile of one row's queries, every
    head, against ``kb x bs`` keys; flash state in scratch across the unit's
    programs (``_chunk_visit_list`` over slots of ``kb`` blocks). ``refs``:
    scalar prefetch bt [Rc, B], n / q0 / limit [Rc], trash [1], vrow / vqt /
    vpool / vkt / vflag [G]; q (1, tile, nh, D) scaled, ``kb`` pool blocks (1, D,
    bs), the chunk's own keys (1, D, kb x bs); o (1, tile, nh, rank); m / l / acc
    scratch [tile * nh, .]. Score row ``m`` is query ``m // nh`` of the tile,
    head ``m % nh``."""
    (bt_ref, n_ref, q0_ref, limit_ref, trash_ref, vrow_ref, vqt_ref, vpool_ref, vkt_ref,
     vflag_ref, q_ref) = refs[:11]
    k_refs = refs[11: 11 + kb]
    ke_ref, o_ref, m_scr, l_scr, acc_scr = refs[11 + kb:]
    pool_bs, bs = bs, kb * bs  # below, a "block" is the kb the program folds
    g = pl.program_id(0)
    r, flag = vrow_ref[g], vflag_ref[g]
    i0 = vqt_ref[g] * tile
    M = tile * nh
    n, q0, limit = n_ref[r], q0_ref[r], limit_ref[r]
    rows = jnp.clip(n - i0, 0, tile)  # live queries of the tile

    @pl.when((flag & 2) != 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    is_pool, base, bound, whole = _chunk_visit_bounds(
        bt_ref, trash_ref, r, flag, vpool_ref[g], vkt_ref[g], n, q0, limit, q0 + i0, pool_bs, kb, B)
    causal = jnp.logical_not(is_pool)

    def visit(masked):
        qa = q_ref[0].reshape(M, q_ref.shape[-1])
        pool_t = jnp.concatenate([ref[0] for ref in k_refs], axis=1) if kb > 1 else k_refs[0][0]
        k_t = jnp.where(is_pool, pool_t, ke_ref[0])  # [D, kb x bs]
        if masked:
            k_pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
            # keys NO query of the tile may see: their values to 0 (0 x NaN)
            k_t = jnp.where(k_pos < bound, k_t, jnp.zeros_like(k_t))
        s = jnp.dot(qa, k_t, preferred_element_type=jnp.float32)  # [M, bs]
        if masked:
            q_pos = q0 + i0 + jax.lax.broadcasted_iota(jnp.int32, (M, 1), 0) // nh
            s = jnp.where((k_pos < bound) & (jnp.logical_not(causal) | (k_pos <= q_pos)),
                          s, NEG_INF)
        _flash_fold(s, k_t[:rank], m_scr, l_scr, acc_scr)

    pl.when((rows > 0) & whole)(lambda: visit(masked=False))
    pl.when((rows > 0) & jnp.logical_not(whole))(lambda: visit(masked=True))

    @pl.when((flag & 4) != 0)
    def _finish():
        i = i0 + jax.lax.broadcasted_iota(jnp.int32, (M, 1), 0) // nh
        live = (i < n) & (m_scr[:, :1] > NEG_INF * 0.5)
        out = jnp.where(live, acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30), 0.0)
        o_ref[0] = out.reshape(tile, nh, rank).astype(o_ref.dtype)


def _latent_keys(pool, row_tables, trash_block, new, q_pos, pool_limit):
    """What the dense chunk forms attend: (keys [Rc, S + tq, D] float32, the
    pool's gathered tables then the chunk's own vectors; valid [Rc, tq, S +
    tq]: which of them a query sees)."""
    Rc, tq = q_pos.shape
    B, bs, D = row_tables.shape[1], pool.shape[2], pool.shape[1]
    ctx = pool[row_tables].transpose(0, 1, 3, 2).reshape(Rc, B * bs, D).astype(jnp.float32)
    kpos = jnp.arange(B * bs, dtype=jnp.int32)
    live = q_pos >= 0  # [Rc, tq]
    pool_ok = ((kpos[None] < jnp.asarray(pool_limit, jnp.int32)[:, None])
               & jnp.repeat(row_tables != trash_block, bs, axis=1))  # [Rc, S]
    own_ok = live[:, None, :] & live[:, :, None] & (q_pos[:, None, :] <= q_pos[:, :, None])
    valid = jnp.concatenate(
        [jnp.broadcast_to((pool_ok[:, None] & live[:, :, None]), (Rc, tq, B * bs)), own_ok], axis=2)
    return jnp.concatenate([ctx, new.astype(jnp.float32)], axis=1), valid


def latent_chunk_dense(q, pool, row_tables, q_pos, trash_block, new, pool_limit, *, rank, scale):
    """``latent_chunk_absorbed`` as plain XLA: whole tables gathered, a masked
    softmax over ``B x bs + tq`` columns a query. Off the TPU, and the kernel's
    oracle."""
    keys, valid = _latent_keys(pool, row_tables, trash_block, new, q_pos, pool_limit)
    qs = q.astype(jnp.float32) * scale
    s = jnp.where(valid[:, :, None], jnp.einsum("rthd,rsd->rths", qs, keys), NEG_INF)
    w = jnp.where(jnp.any(valid, axis=2)[:, :, None, None], jax.nn.softmax(s, axis=-1), 0.0)
    # a column no query of the row may see holds what it holds: out of the sum
    vals = jnp.where(jnp.any(valid, axis=1)[..., None], keys[..., :rank], 0.0)
    return jnp.einsum("rths,rsc->rthc", w, vals).astype(q.dtype)


def chunk_tile(tq: int, nh: int) -> int:
    """Queries of one program of the absorbed chunk kernel: ``tile x nh`` score
    rows, 2,048 of them where the chunk has that many (the flash state of 2,048
    rows of 512 is 4 MiB)."""
    tile = max(1, 2048 // nh)
    while tq % tile:
        tile //= 2
    return max(tile, 1)


def _visit_blocks(tq: int, bs: int) -> int:
    """Pool blocks a chunk program folds: VISIT_BLOCKS, or as many as the chunk
    has of its own."""
    return max(k for k in (VISIT_BLOCKS, 2, 1) if (tq // bs) % k == 0)


def _own_blocks(new, dtype, wide: int):
    """The chunk's own vectors ``new`` [Rc, tq, D] as the pool lays a visit's
    blocks, side by side on the lanes: [Rc * tq / wide, D, wide]."""
    Rc, tq, D = new.shape
    return new.astype(dtype).reshape(Rc, tq // wide, wide, D).transpose(0, 1, 3, 2).reshape(
        Rc * (tq // wide), D, wide)


def _row_scalars(q_pos, pool_limit):
    """Of chunk rows ``q_pos`` [Rc, tq] (-1 padding): (n, live queries a row; q0,
    the first one's position; limit, the pool is read below it: ``pool_limit``,
    0 for a row with no query)."""
    n = jnp.sum(q_pos >= 0, axis=1, dtype=jnp.int32)
    limit = jnp.where(n > 0, jnp.asarray(pool_limit, jnp.int32).reshape(n.shape), 0)
    return n, jnp.maximum(q_pos[:, 0], 0), limit


def latent_chunk_absorbed(q, pool, row_tables, q_pos, trash_block, new, pool_limit, *, rank: int,
                          scale: float, impl: str = "dense", interpret: bool = False,
                          tile: Optional[int] = None):
    """ABSORBED attention of prompt chunks over the latent pool and their own
    vectors. q [Rc, tq, nh, D] (``[q_nope W_UK | q_rope]``); row_tables [Rc, B];
    q_pos [Rc, tq] (-1 padding: a row's live queries are its first n, at
    consecutive positions); ``new`` [Rc, tq, D]: the chunk's own vectors,
    attended causally; the pool is read below ``pool_limit`` [Rc] (the chunk's
    start). Returns [Rc, tq, nh, rank]: the caller applies ``W_UV``."""
    Rc, tq, nh, D = q.shape
    bs, B = pool.shape[2], row_tables.shape[1]
    q_pos = q_pos.astype(jnp.int32)
    if impl == "dense" or tq % bs:
        return latent_chunk_dense(q, pool, row_tables, q_pos, trash_block, new, pool_limit,
                                  rank=rank, scale=scale)
    interpret = bool(interpret) or not on_tpu()
    tile = int(tile) if tile else chunk_tile(tq, nh)
    if tq % tile or tile & (tile - 1):
        raise ValueError(f"latent_chunk: tile {tile} for tq {tq}")
    n, q0, limit = _row_scalars(q_pos, pool_limit)
    kb = _visit_blocks(tq, bs)
    wide = kb * bs
    n_visits, vrow, vqt, vpool, vkt, vflag = _chunk_visit_list(
        n, q0, limit, wide, -(-B // kb), tq, tile, 0)
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    M = tile * nh

    # index maps see (g, bt, n, q0, limit, trash, vrow, vqt, vpool, vkt, vflag)
    def q_spec(width):
        return pl.BlockSpec((1, tile, nh, width), lambda g, *s: (s[5][g], s[6][g], 0, 0))

    def pool_block(i):  # table slot vpool * kb + i of the visit's row
        return pl.BlockSpec((1, D, bs), lambda g, *s: (
            s[0][s[5][g], jnp.minimum(s[7][g] * kb + i, B - 1)], 0, 0))

    return pl.pallas_call(
        functools.partial(_mla_chunk_kernel, bs=bs, tile=tile, nh=nh, rank=rank, kb=kb, B=B),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=10, grid=(n_visits,),
            in_specs=[q_spec(D)] + [pool_block(i) for i in range(kb)] + [
                pl.BlockSpec((1, D, wide), lambda g, *s: (s[5][g] * (tq // wide) + s[8][g], 0, 0)),
            ],
            out_specs=q_spec(rank),
            scratch_shapes=[pltpu.VMEM((M, 128), jnp.float32), pltpu.VMEM((M, 128), jnp.float32),
                            pltpu.VMEM((M, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((Rc, tq, nh, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # 2,048 score rows against 512 keys: the query tile twice 2.4 MiB,
            # the flash state 6 MiB, the output tile twice 2 MiB, scores and
            # weights 6 MiB, the keys four times 0.6 MiB
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name=MLA_CHUNK,
    )(row_tables.astype(jnp.int32), n, q0, limit, jnp.asarray(trash_block, jnp.int32).reshape(1),
      vrow, vqt, vpool, vkt, vflag, qs, *([pool] * kb), _own_blocks(new, q.dtype, wide))


def _mla_chunk_expanded_kernel(*refs, bs, tq, hg, rank, dn, dv, kb, B, scale):
    """One program of ``dstpu_mla_chunk`` in the EXPANDED form: one row's whole
    chunk (``tq`` queries), ``hg`` heads, against ``kb x bs`` keys, on a grid
    (head groups, visits): ``_chunk_visit_list`` at one tile a row, walked once
    a head group. ``refs``: scalar prefetch bt [Rc, B], n / q0 / limit [Rc],
    trash [1], vrow / vpool / vkt / vflag [G], the weight's layer [1] (its index
    map's alone); the group's lanes of the query projection as written (1, tq,
    hg x (dn + dr)), a head's ``[nope | rope]`` side by side, and of the rotated
    rope dims (1, tq, hg x dr), neither scaled; the group's columns of the
    layer's ``wkv_b`` (1, rank, hg x (dn + dv)), a head's ``W_UK | W_UV``; ``kb``
    pool blocks (1, D, bs); the chunk's own vectors (1, D, kb x bs); o (1, tq,
    hg x dv); scratch: the group's weights transposed [hg x (dn + dv), rank],
    ``W_UK``'s rows times the softmax scale (made at the group's first visit,
    kept across its walk), the row's nope dims [tq, hg x dn] (taken out of the
    projection's lanes at the row's first visit), m / l [tq, 128] (column ``h``
    is head ``h``'s) and acc [hg, tq, dv] float32. A head's keys and values are
    made of the visit's latents here, ``[W_UK_h | W_UV_h]^T c_t`` (float32 sums,
    rounded to the operands' dtype as the published form rounds ``kv_b_proj``'s
    output), and never leave VMEM."""
    (bt_ref, n_ref, q0_ref, limit_ref, trash_ref, vrow_ref, vpool_ref, vkt_ref, vflag_ref, _,
     q_ref, qr_ref, w_ref) = refs[:13]
    k_refs = refs[13: 13 + kb]
    ke_ref, o_ref, wt_scr, qn_scr, m_scr, l_scr, acc_scr = refs[13 + kb:]
    pool_bs, bs = bs, kb * bs  # below, a "block" is the kb the program folds
    dr = qr_ref.shape[-1] // hg
    g = pl.program_id(1)
    r, flag = vrow_ref[g], vflag_ref[g]
    n, q0, limit = n_ref[r], q0_ref[r], limit_ref[r]

    @pl.when(g == 0)
    def _weights():
        # the softmax scale rides on W_UK (here, once a head group) and on the
        # visit's rotary key dims (below): the queries come as they were written
        of_keys = jax.lax.broadcasted_iota(jnp.int32, (wt_scr.shape[0], 1), 0) % (dn + dv) < dn
        wt_scr[...] = (w_ref[0].T.astype(jnp.float32) * jnp.where(of_keys, scale, 1.0)).astype(wt_scr.dtype)

    @pl.when((flag & 2) != 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        # a head's dims without rotary out of the projection's lanes ([nope |
        # rope] a head, the rope dims there not yet rotated), once a row
        for h in range(hg):
            qn_scr[:, h * dn: (h + 1) * dn] = q_ref[0, :, h * (dn + dr): h * (dn + dr) + dn]

    is_pool, base, bound, whole = _chunk_visit_bounds(
        bt_ref, trash_ref, r, flag, vpool_ref[g], vkt_ref[g], n, q0, limit, q0, pool_bs, kb, B)

    def visit(masked):
        pool_t = jnp.concatenate([ref[0] for ref in k_refs], axis=1) if kb > 1 else k_refs[0][0]
        k_t = jnp.where(is_pool, pool_t, ke_ref[0])  # [D, kb x bs]
        if masked:
            k_pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
            # keys NO query of the row may see: their latents to 0 (0 x NaN)
            k_t = jnp.where(k_pos < bound, k_t, jnp.zeros_like(k_t))
            q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
            seen = (k_pos < bound) & (is_pool | (k_pos <= q_pos))  # [tq, kb x bs]
        c_t = k_t[:rank]
        kr_t = (k_t[rank:].astype(jnp.float32) * scale).astype(k_t.dtype)

        def scores(h):  # head h's keys and values of the visit's latents, and its scores
            kv_t = jnp.dot(wt_scr[h * (dn + dv): (h + 1) * (dn + dv), :], c_t,
                           preferred_element_type=jnp.float32).astype(c_t.dtype)  # [dn + dv, keys]
            s = (jnp.dot(qn_scr[:, h * dn: (h + 1) * dn], kv_t[:dn],
                         preferred_element_type=jnp.float32)
                 + jnp.dot(qr_ref[0, :, h * dr: (h + 1) * dr], kr_t,
                           preferred_element_type=jnp.float32))  # [tq, keys]
            return (jnp.where(seen, s, NEG_INF) if masked else s), kv_t[dn:]

        # head h + 1's products stand in front of head h's softmax: in that order
        # the compiler runs the one's MXU work under the other's VPU work (14%
        # of the kernel at every head-group width: my chip runs, PR 62)
        ahead = scores(0)
        for h in range(hg):
            s, v_t = ahead
            if h + 1 < hg:
                ahead = scores(h + 1)
            _flash_fold(s, v_t, m_scr, l_scr, acc_scr.at[h], col=h)

    pl.when((n > 0) & whole)(lambda: visit(masked=False))
    pl.when((n > 0) & jnp.logical_not(whole))(lambda: visit(masked=True))

    @pl.when((flag & 4) != 0)
    def _finish():
        i = jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
        for h in range(hg):
            live = (i < n) & (m_scr[:, h: h + 1] > NEG_INF * 0.5)
            out = jnp.where(live, acc_scr[h] / jnp.maximum(l_scr[:, h: h + 1], 1e-30), 0.0)
            o_ref[0, :, h * dv: (h + 1) * dv] = out.astype(o_ref.dtype)


def _layer_of(w):
    """``w`` itself, or the layer a ``Stacked`` names, sliced."""
    return w.stack[w.index] if isinstance(w, Stacked) else w


def _nope(q, nh, dr):
    """The dims without rotary of a query projection as written, q [Rc, tq, nh x
    (dn + dr)] (a head's ``[nope | rope]`` on adjacent lanes): [Rc, tq, nh, dn]."""
    return q.reshape(q.shape[:2] + (nh, -1))[..., : q.shape[-1] // nh - dr]


def latent_chunk_expanded_dense(q, q_rope, wkv_b, pool, row_tables, q_pos, trash_block, new,
                                pool_limit, *, scale):
    """``latent_chunk_expanded`` as plain XLA: whole tables gathered, every
    head's keys and values made of them (``kv_b_proj`` of the cached latent,
    rounded to the queries' dtype), a masked softmax over ``B x bs + tq``
    columns a query. Off the TPU, and the kernel's oracle. Returns [Rc, tq, nh x
    dv] as the kernel does."""
    Rc, tq, nh, dr = q_rope.shape
    q_nope = _nope(q, nh, dr)
    dn, rank = q_nope.shape[-1], wkv_b.shape[0]
    keys, valid = _latent_keys(pool, row_tables, trash_block, new, q_pos, pool_limit)
    # a column no query of the row may see holds what it holds: out of the sums
    keys = jnp.where(jnp.any(valid, axis=1)[..., None], keys, 0.0)
    w = wkv_b.astype(jnp.float32).reshape(rank, nh, -1)
    kv = jnp.einsum("rsc,chd->rshd", keys[..., :rank], w).astype(q.dtype).astype(jnp.float32)
    s = (jnp.einsum("rthd,rshd->rths", q_nope.astype(jnp.float32), kv[..., :dn])
         + jnp.einsum("rthd,rsd->rths", q_rope.astype(jnp.float32), keys[..., rank:])) * scale
    s = jnp.where(valid[:, :, None], s, NEG_INF)
    p = jnp.where(jnp.any(valid, axis=2)[:, :, None, None], jax.nn.softmax(s, axis=-1), 0.0)
    return jnp.einsum("rths,rshd->rthd", p, kv[..., dn:]).astype(q.dtype).reshape(Rc, tq, -1)


def latent_chunk_expanded(q, q_rope, wkv_b, pool, row_tables, q_pos, trash_block, new,
                          pool_limit, *, scale: float, impl: str = "dense",
                          interpret: bool = False, heads: int = EXPANDED_HEADS):
    """EXPANDED attention of prompt chunks over the latent pool and their own
    vectors. ``q`` [Rc, tq, nh x (dn + dr)]: the query projection AS WRITTEN
    (``models.transformer.latent_q``: a head's ``[nope | rope]`` dims on
    adjacent lanes; the kernel takes the nope dims where they lie, the rope
    dims there are not yet rotated and are not read); ``q_rope`` [Rc, tq, nh,
    dr] rotated, as ``latent_qkv`` makes it; ``wkv_b`` [rank, nh x (dn + dv)]
    as the checkpoint stores it (a head's ``W_UK | W_UV`` on adjacent columns),
    or ``Stacked(stack [layers, rank, ..], layer)``, the layer static or traced:
    the kernel's BlockSpec indexes a head group's columns of the layer out of
    the stack, where a slice in front of the call would be a copy a step;
    the rest as ``latent_chunk_absorbed``. A head's keys and values are made of
    the cached latents inside the kernel, ``heads`` heads a program. Returns
    [Rc, tq, nh x dv]: the attention's output, a head's dims on adjacent lanes
    (what the output projection takes), nothing left to apply."""
    Rc, tq, nh, dr = q_rope.shape
    stack, layer = wkv_b if isinstance(wkv_b, Stacked) else (wkv_b[None], 0)
    dn, rank = q.shape[-1] // nh - dr, stack.shape[1]
    dv = stack.shape[2] // nh - dn
    D, bs, B = pool.shape[1], pool.shape[2], row_tables.shape[1]
    q_pos = q_pos.astype(jnp.int32)
    if impl == "dense" or tq % bs:
        return latent_chunk_expanded_dense(q, q_rope, _layer_of(wkv_b), pool, row_tables, q_pos,
                                           trash_block, new, pool_limit, scale=scale)
    interpret = bool(interpret) or not on_tpu()
    hg = min(int(heads), nh)
    if nh % hg:
        raise ValueError(f"latent_chunk: {hg} heads a program for {nh} heads")
    dtype = q.dtype
    n, q0, limit = _row_scalars(q_pos, pool_limit)
    kb = _visit_blocks(tq, bs)
    wide = kb * bs
    # one unit a row: its whole chunk is the tile
    n_visits, vrow, _, vpool, vkt, vflag = _chunk_visit_list(
        n, q0, limit, wide, -(-B // kb), tq, tq, 0)

    # index maps see (i, g, bt, n, q0, limit, trash, vrow, vpool, vkt, vflag,
    # layer): head group i, visit g
    def heads_block(width):  # the group's lanes of the visit's row
        return pl.BlockSpec((1, tq, hg * width), lambda i, g, *s: (s[5][g], 0, i))

    def pool_block(j):  # table slot vpool * kb + j of the visit's row
        return pl.BlockSpec((1, D, bs), lambda i, g, *s: (
            s[0][s[5][g], jnp.minimum(s[6][g] * kb + j, B - 1)], 0, 0))

    return pl.pallas_call(
        functools.partial(_mla_chunk_expanded_kernel, bs=bs, tq=tq, hg=hg, rank=rank, dn=dn, dv=dv,
                          kb=kb, B=B, scale=float(scale)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=10, grid=(nh // hg, n_visits),
            in_specs=[heads_block(dn + dr), heads_block(dr),
                      pl.BlockSpec((1, rank, hg * (dn + dv)), lambda i, g, *s: (s[9][0], 0, i))]
            + [pool_block(j) for j in range(kb)] + [
                pl.BlockSpec((1, D, wide), lambda i, g, *s: (s[5][g] * (tq // wide) + s[7][g], 0, 0)),
            ],
            out_specs=heads_block(dv),
            scratch_shapes=[pltpu.VMEM((hg * (dn + dv), rank), dtype), pltpu.VMEM((tq, hg * dn), dtype),
                            pltpu.VMEM((tq, 128), jnp.float32), pltpu.VMEM((tq, 128), jnp.float32),
                            pltpu.VMEM((hg, tq, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((Rc, tq, nh * dv), dtype),
        # the visits of a head group follow one another and keep its weights
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name=MLA_CHUNK,
    )(row_tables.astype(jnp.int32), n, q0, limit, jnp.asarray(trash_block, jnp.int32).reshape(1),
      vrow, vpool, vkt, vflag, jnp.asarray(layer, jnp.int32).reshape(1),
      q, q_rope.astype(dtype).reshape(Rc, tq, nh * dr), stack.astype(dtype),
      *([pool] * kb), _own_blocks(new, dtype, wide))


def chunk_expands(tq: int) -> bool:
    """Whether chunk rows of ``tq`` slots attend expanded (module header)."""
    return tq >= EXPAND_FROM


def latent_chunk(q, q_rope, wkv_b, pool, row_tables, q_pos, trash_block, new, pool_limit, *,
                 scale: float, impl: str = "dense", interpret: bool = False):
    """Attention of prompt chunks over the latent pool and their own vectors,
    in the form the chunk's length pays for (module header): ``tq`` queries a
    row below ``EXPAND_FROM`` absorbed (``q_nope W_UK`` in front of
    ``latent_chunk_absorbed``, ``W_UV`` behind it), from there on expanded
    (``latent_chunk_expanded``). Operands as ``latent_chunk_expanded`` takes
    them. Returns [Rc, tq, nh x dv]."""
    if impl not in ("kernel", "dense"):
        raise ValueError(f"latent_chunk: unknown impl {impl!r} (expected 'kernel' or 'dense')")
    args = (pool, row_tables, q_pos, trash_block, new, pool_limit)
    if chunk_expands(q.shape[1]):
        return latent_chunk_expanded(q, q_rope, wkv_b, *args, scale=scale, impl=impl,
                                     interpret=interpret)
    nh, dr = q_rope.shape[2:]
    q_nope = _nope(q, nh, dr)
    wkv_b = _layer_of(wkv_b)
    rank, dn = wkv_b.shape[0], q_nope.shape[-1]
    w = wkv_b.reshape(rank, nh, -1)
    qa = jnp.concatenate([jnp.einsum("rthd,chd->rthc", q_nope, w[..., :dn]), q_rope], axis=-1)
    out = latent_chunk_absorbed(qa, *args, rank=rank, scale=scale, impl=impl, interpret=interpret)
    return jnp.einsum("rthc,chd->rthd", out, w[..., dn:]).reshape(out.shape[:2] + (-1,))


def write_visits(blk, trash: int, n_visits: int):
    """The programs of one ``dstpu_mla_write`` call, on the host (numpy): ``blk``
    [n] is each new token's pool block (``trash``: a padded slot, which is
    written nowhere). A visit merges the tokens of one ``WRITE_TILE``-token tile
    of the step's grid into one block; a block's visits follow one another.
    Returns (vblk, vtile, vflag) [n_visits] int32: flag 1 the visit has tokens,
    2 it is its block's first (the program then takes the block from the pool;
    later ones keep what the last left in VMEM). Entries past the real visits
    repeat the last with flag 0."""
    blk = np.asarray(blk, np.int64)
    tiles = np.arange(len(blk)) // WRITE_TILE
    pairs = np.unique(np.stack([blk, tiles], axis=1)[blk != trash], axis=0)  # sorted by block
    if len(pairs) > n_visits:
        raise RuntimeError(f"latent pool write: {len(pairs)} (block, tile) pairs in a step "
                           f"sized for {n_visits}")
    vblk = np.full(n_visits, trash, np.int32)
    vtile = np.zeros(n_visits, np.int32)
    vflag = np.zeros(n_visits, np.int32)
    k = len(pairs)
    if k:
        vblk[:k], vtile[:k] = pairs[:, 0], pairs[:, 1]
        vflag[:k] = 1 + 2 * np.concatenate([[True], pairs[1:, 0] != pairs[:-1, 0]])
        vblk[k:], vtile[k:] = pairs[-1, 0], pairs[-1, 1]
    else:
        vflag[0] = 2  # nothing to write: the trash block onto itself
    return vblk, vtile, vflag


def _mla_write_kernel(vblk_ref, vtile_ref, vflag_ref, pool_ref, new_ref, blk_ref, row_ref, o_ref):
    """One program a visit (``write_visits``), layer-major: the block (1, D, bs)
    from the pool on its first visit, then the tile's tokens that land in it
    (``blk`` / ``row`` (WRITE_TILE, 1) of each) put on their lanes by a 0/1
    matrix product with the tile's vectors TRANSPOSED ``new`` (1, D, WRITE_TILE):
    exact, a token's vector times one."""
    g = pl.program_id(1)
    flag = vflag_ref[g]

    @pl.when((flag & 2) != 0)
    def _take():
        o_ref[...] = pool_ref[...]

    @pl.when((flag & 1) != 0)
    def _merge():
        bs = o_ref.shape[-1]
        lanes = jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        sel = (blk_ref[...] == vblk_ref[g]) & (row_ref[...] == lanes)  # [tile tokens, bs]
        placed = jnp.dot(new_ref[0], sel.astype(new_ref.dtype), preferred_element_type=jnp.float32)
        lands = jnp.sum(sel.astype(jnp.int32), axis=0, keepdims=True) > 0  # [1, bs]
        o_ref[0] = jnp.where(lands, placed.astype(o_ref.dtype), o_ref[0])


def latent_write(pool, new, blk, row, visits=None, *, impl: str = "dense",
                 interpret: bool = False):
    """The step's new vectors into the pool. pool [L, NBp, D, bs] (donated by the
    caller: updated in place); new [L, n, D]; ``blk`` / ``row`` [n]: each token's
    block and its row in it, the same for every layer (a padded slot names the
    trash block, the pool's last). ``impl`` "dense": XLA's scatter (off the TPU;
    on it the scatter copies the pool twice). "kernel": ``dstpu_mla_write`` under
    ``visits`` = (vblk, vtile, vflag) from ``write_visits`` (host-staged)."""
    L, NBp, D, bs = pool.shape
    n = blk.shape[0]
    if impl == "dense":
        p = (jnp.arange(L, dtype=jnp.int32)[:, None] * NBp + blk[None]).reshape(L * n)
        r = jnp.broadcast_to(row[None], (L, n)).reshape(L * n)
        return pool.reshape(L * NBp, D, bs).at[p, :, r].set(
            new.reshape(L * n, D).astype(pool.dtype)).reshape(pool.shape)
    if impl != "kernel":
        raise ValueError(f"latent_write: unknown impl {impl!r} (expected 'kernel' or 'dense')")
    interpret = bool(interpret) or not on_tpu()
    vblk, vtile, vflag = visits
    pad = -n % WRITE_TILE
    new_t = jnp.pad(new.astype(pool.dtype), ((0, 0), (0, pad), (0, 0))).transpose(0, 2, 1)
    blk_c = jnp.pad(blk.astype(jnp.int32), (0, pad), constant_values=-1)[:, None]
    row_c = jnp.pad(row.astype(jnp.int32), (0, pad))[:, None]

    # index maps see (l, g, vblk, vtile, vflag)
    block = pl.BlockSpec((1, D, bs), lambda l, g, vb, vt, vf: (l * NBp + vb[g], 0, 0))
    token = pl.BlockSpec((WRITE_TILE, 1), lambda l, g, vb, vt, vf: (vt[g], 0))
    return pl.pallas_call(
        _mla_write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(L, vblk.shape[0]),
            in_specs=[block,
                      pl.BlockSpec((1, D, WRITE_TILE), lambda l, g, vb, vt, vf: (l, 0, vt[g])),
                      token, token],
            out_specs=block),
        out_shape=jax.ShapeDtypeStruct((L * NBp, D, bs), pool.dtype),
        # the pool in, the pool out: blocks no visit names stay as they are
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=MLA_WRITE,
    )(jnp.asarray(vblk, jnp.int32), jnp.asarray(vtile, jnp.int32), jnp.asarray(vflag, jnp.int32),
      pool.reshape(L * NBp, D, bs), new_t, blk_c, row_c).reshape(pool.shape)
