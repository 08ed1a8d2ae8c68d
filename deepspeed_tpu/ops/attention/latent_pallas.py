"""Paged attention over a LATENT pool (DeepseekV3 / A.X-K1 latent attention).

A token's keys and values are one vector every head shares: the normed latent
``c`` [rank] and the rotated key dims ``k_r`` [rope] (``D = rank + rope``: 512 +
64). Served attention runs in the ABSORBED form: a head's key matrix ``W_UK``
is moved to the query (``q_lat = q_nope W_UK``, then ``q = [q_lat | q_rope]``
[nh, D]) and its value matrix ``W_UV`` behind the output (``o = (p c) W_UV``),
so every head scores against the same ``[keys, D]`` matrix and sums the same
``[keys, rank]`` values: one "KV head" of D for scores whose first ``rank`` dims
are its values. A cached token costs ``nh x (D + rank) x 2`` operations for its
``2 D`` bytes: ~121 operations a byte at 64 heads, half the v5e's ridge, where
the per-head paged kernels do 2-16.

Layout:
  pool    [P, D, bs]  a block is D ROWS of ``bs`` tokens (the latent dim on the
          sublanes, the block's tokens on the lanes). D = 576 is not a multiple
          of the 128 lanes: token-major ``[bs, 576]`` blocks are padded to 640
          in HBM or re-laid-out by XLA in front of every kernel call (the chip's
          compiler picks ``{1,2,0}`` for ``bf16[N,128,576]``); ``[576, 128]`` is
          whole tiles, and it is also the operand the score product wants
          (``q [nh, D] @ block [D, bs]``) with no transpose in the kernel. The
          engine passes a flat multi-layer view ``[L * NBp, D, bs]`` with
          layer-offset tables, read as the step received it.
  q       [T, nh, D]  absorbed queries, one a decode row (``latent_decode``), or
          [Rc, tq, nh, D] for prompt chunks (``latent_chunk``)
  out     [.., nh, rank]  softmax(q k^T) c: the caller applies ``W_UV``

Kernels (on a TPU; interpreted in the tests), each beside a dense XLA form that
runs off the TPU and is its oracle:
  * ``dstpu_mla_decode``: one program a (row, ``VISIT_BLOCKS`` table slots the
    row's context covers), the visit list of ``paged_pallas._visit_list`` over
    slots that wide; the row's last program folds the step's own not-yet-cached
    vectors and writes the row.
  * ``dstpu_mla_chunk``: a tile of a row's queries (every head: ``tile x nh``
    score rows) against ``VISIT_BLOCKS`` blocks: the pool blocks the row holds
    below the chunk, then the chunk's own vectors, causal by tiles
    (``paged_pallas._chunk_visit_list``).
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


def _flash_fold(s, values_t, m_scr, l_scr, acc_scr):
    """One online-softmax step: scores ``s`` [M, nk] float32 (masked entries at
    NEG_INF) and the keys' values TRANSPOSED ``values_t`` [rank, nk] into the
    state in scratch (m / l [M, 128] with column 0 meaningful, acc [M, rank])."""
    m_p = m_scr[:, :1]
    m_new = jnp.maximum(m_p, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_p - m_new)
    p = jnp.exp(s - m_new)
    l_scr[:, :1] = l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p.astype(values_t.dtype), values_t, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[:, :1] = m_new


def _held_below(bt_ref, trash_ref, t, slot, limit, bs, kb, B):
    """Keys of row ``t``'s table slots ``slot * kb ..`` that are the row's: those
    below ``limit``, and below the first of the ``kb`` slots that names the trash
    block (none does inside a live row's context; a scalar a slot)."""
    for i in range(kb):
        j = jnp.minimum(slot * kb + i, B - 1)
        limit = jnp.where(bt_ref[t, j] == trash_ref[0], jnp.minimum(limit, (slot * kb + i) * bs),
                          limit)
    return limit


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

    # as paged_pallas._chunk_kernel: one body for a pool block and a block of
    # the chunk's own keys; which it reads and what a query sees of it are scalars
    is_pool = (flag & 1) != 0
    slot, kt = vpool_ref[g], vkt_ref[g]
    base = jnp.where(is_pool, slot * bs, q0 + kt * bs)
    bound = jnp.where(is_pool, _held_below(bt_ref, trash_ref, r, slot, limit, pool_bs, kb, B),
                      q0 + n)
    causal = jnp.logical_not(is_pool)
    first_pos = q0 + i0
    whole = (base + bs <= bound) & (is_pool | (base + bs - 1 <= first_pos))

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


def latent_chunk_dense(q, pool, row_tables, q_pos, trash_block, new, pool_limit, *, rank, scale):
    """``latent_chunk`` as plain XLA: whole tables gathered, a masked softmax
    over ``B x bs + tq`` columns a query. Off the TPU, and the kernel's oracle."""
    Rc, tq, nh, D = q.shape
    B, bs = row_tables.shape[1], pool.shape[2]
    ctx = pool[row_tables].transpose(0, 1, 3, 2).reshape(Rc, B * bs, D).astype(jnp.float32)
    kpos = jnp.arange(B * bs, dtype=jnp.int32)
    live = q_pos >= 0  # [Rc, tq]
    pool_ok = ((kpos[None] < jnp.asarray(pool_limit, jnp.int32)[:, None])
               & jnp.repeat(row_tables != trash_block, bs, axis=1))  # [Rc, S]
    own_ok = live[:, None, :] & live[:, :, None] & (q_pos[:, None, :] <= q_pos[:, :, None])
    valid = jnp.concatenate(
        [jnp.broadcast_to((pool_ok[:, None] & live[:, :, None]), (Rc, tq, B * bs)), own_ok], axis=2)
    keys = jnp.concatenate([ctx, new.astype(jnp.float32)], axis=1)  # [Rc, S + tq, D]
    qs = q.astype(jnp.float32) * scale
    s = jnp.where(valid[:, :, None], jnp.einsum("rthd,rsd->rths", qs, keys), NEG_INF)
    w = jnp.where(jnp.any(valid, axis=2)[:, :, None, None], jax.nn.softmax(s, axis=-1), 0.0)
    # a column no query of the row may see holds what it holds: out of the sum
    vals = jnp.where(jnp.any(valid, axis=1)[..., None], keys[..., :rank], 0.0)
    return jnp.einsum("rths,rsc->rthc", w, vals).astype(q.dtype)


def chunk_tile(tq: int, nh: int) -> int:
    """Queries of one program: ``tile x nh`` score rows, 2,048 of them where the
    chunk has that many (the flash state of 2,048 rows of 512 is 4 MiB)."""
    tile = max(1, 2048 // nh)
    while tq % tile:
        tile //= 2
    return max(tile, 1)


def latent_chunk(q, pool, row_tables, q_pos, trash_block, new, pool_limit, *, rank: int,
                 scale: float, impl: str = "dense", interpret: bool = False,
                 tile: Optional[int] = None):
    """Absorbed attention of prompt chunks over the latent pool and their own
    vectors. q [Rc, tq, nh, D]; row_tables [Rc, B]; q_pos [Rc, tq] (-1 padding: a
    row's live queries are its first n, at consecutive positions); ``new`` [Rc,
    tq, D]: the chunk's own vectors, attended causally; the pool is read below
    ``pool_limit`` [Rc] (the chunk's start). Returns [Rc, tq, nh, rank]."""
    if impl not in ("kernel", "dense"):
        raise ValueError(f"latent_chunk: unknown impl {impl!r} (expected 'kernel' or 'dense')")
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
    n = jnp.sum(q_pos >= 0, axis=1, dtype=jnp.int32)
    q0 = jnp.maximum(q_pos[:, 0], 0)
    limit = jnp.where(n > 0, jnp.asarray(pool_limit, jnp.int32).reshape(Rc), 0)
    # blocks a program folds: VISIT_BLOCKS, or as many as the chunk has of its own
    kb = max(k for k in (VISIT_BLOCKS, 2, 1) if k <= VISIT_BLOCKS and (tq // bs) % k == 0)
    wide = kb * bs
    n_visits, vrow, vqt, vpool, vkt, vflag = _chunk_visit_list(
        n, q0, limit, wide, -(-B // kb), tq, tile, 0)
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    # the chunk's own vectors, kb blocks side by side: [Rc * tq / wide, D, wide]
    own = new.astype(q.dtype).reshape(Rc, tq // wide, wide, D).transpose(0, 1, 3, 2).reshape(
        Rc * (tq // wide), D, wide)
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
      vrow, vqt, vpool, vkt, vflag, qs, *([pool] * kb), own)


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
