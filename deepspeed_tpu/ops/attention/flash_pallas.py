"""Flash attention as a Pallas TPU kernel (forward + backward), splash-style.

The TPU-native replacement for the reference's fused attention kernels
(csrc/transformer/inference softmax/attention ops, evoformer_attn CUTLASS
kernels, blocked_flash in inference/v2/kernels/ragged_ops/blocked_flash):
online-softmax tiling so the [s, s] score matrix never materializes in HBM.

Design (round 3: kv-pipelined — nothing sequence-length-sized is ever VMEM
resident, lifting the former ~8k dense cap):
  * Two operand layouts, one set of kernels, told apart by the operands'
    rank. HEAD-MAJOR [b, h, s, d]: a head's block is (1, 1, rows, d) at
    (batch, head, row block, 0). TOKEN-MAJOR [b, s, h * d], which is where the
    q/k/v projections wrote them and where the output projection reads: a
    head is a column of (rows, 128) tiles, its block (1, rows, d) at (batch,
    row block, head), so nothing is re-laid between the projections and the
    kernels: q, k, v, the output, ``do`` and the three gradients all stay so.
    A head must be whole lanes for its column to be a block (``d % 128 ==
    0``); a head of 64 is run head-major. Only the BlockSpecs, the output
    shapes and the squeeze of a block's unit dims differ (``_head_spec`` /
    ``_head_ref``): the kernel bodies see the same [rows, d] refs either way.
    LSE stays [b, h, s, LANES]. What is computed a head at a time AROUND the
    kernels (a per-head norm, rope, the GQA group's sum) goes through
    ``heads_view``, the same bytes a head at a time: a plain [b, s, h, d]
    split is tiled differently in HBM and costs a copy each way.
  * Forward grid (b, h, nq, nk) with the kv block index
    minor: each program sees one [bq, d] q block and one [bk, d] k/v block;
    Pallas double-buffers the next kv block's HBM→VMEM copy behind the
    current block's MXU work. Softmax state (m, l) and the output
    accumulator live in VMEM scratch carried across the kv iterations; the
    output block is written once on the last iteration.
  * Causal pruning and classes. A (q, kv) grid point ABOVE the diagonal is
    pruned: it clamps its kv index map to the last active block — Pallas
    elides the copy when the block index is unchanged — and skips compute
    under ``pl.when``; it costs grid overhead only. The forward computes every
    other pair whole under the causal mask. The BACKWARD kernels class them by
    where the diagonal passes (``causal_pair_classes``), one ``pl.when`` body a
    class. Wholly UNDER it: the whole pair with no causal mask (no iotas, no
    compare, no select; segment and ALiBi terms stay). ON it: the pair goes by
    key-column strips of a quarter block (256 at 1,024) against the q rows that
    see them, unrolled, each under a compare of the strip, and what lies wholly
    above the diagonal is never computed: 10 tiles of 16, so a 4,096-token
    sequence at blocks of 1,024 costs the backward 8.5 pairs of products where
    its 10 active pairs held 10 (and 16 without pruning). A window (static or
    by ``wflag``), blocks that differ and a block too small to hold two
    128-wide strips keep every pair whole under the position masks. A dropped
    term is an exact zero, ``exp(NEG_INF - lse)``. The forward takes neither:
    measured on the v5e, every cut of a forward pair is slower than the pair,
    and the unmasked body gains it nothing (PERF.md, PR 52).
  * fp32 accumulators; the MXU sees bf16 inputs with
    ``preferred_element_type=jnp.float32``.
  * LSE is stored lane-broadcast as [b, h, s, LANES] to satisfy the TPU
    (8, 128) tiling rule for output blocks.
  * Backward: flash recompute — per-block p = exp(qk·scale − lse), ONE
    kernel (``dstpu_flash_bwd_fused``: the dk/dv kernel's body with dq beside
    it, grid (b, h, nk, nq), q/do/o/lse streamed): a block pair's scores,
    masks, p, dp and ds are built once and feed all three sums, five MXU
    products a pair. dk/dv accumulate in
    [bk, d] fp32 scratch over the q blocks; the head's whole dq accumulates
    in [s, d] fp32 scratch that stays in VMEM across the kv blocks and
    leaves as q.dtype once. delta = Σ do·o is computed in-kernel from the
    saved output, once a pair (once a strip of a cut pair).
  * The residency budget: that form runs where s·d·4 bytes fit
    ``DQ_RESIDENT_BYTES`` (2 MiB: s ≤ 4096 at d = 128). A longer sequence
    takes the two streaming kernels it fuses, which also serve the ring:
    dq (``dstpu_flash_bwd_dq``, grid (b, h, nq, nk), kv streamed) and dk/dv
    (``dstpu_flash_bwd_dkv``, grid (b, h, nk, nq)); each rebuilds the pair's
    scores and dp, seven products a pair, and nothing sequence-sized is
    resident. Which one is read off the shape, and the sums are the same in
    the same order: dq[i] over kv blocks ascending, dk[j]/dv[j] over q blocks
    ascending, a cut pair's strips ascending inside it through one shared
    body, so the three gradients are bit-identical either way (and to the
    ring's chunked stream).
  * GQA: kv-head index map h → h // (nh/nkv); no head replication in HBM.
    The backward writes dk/dv per QUERY head and sums the group outside the
    kernel (cast to the input dtype, then an fp32 sum): the ring's hand-over
    (``flash_dkv_finalize``) sums its per-head partials in that order, and
    its bitwise parity with one call rests on it.

Numerics validated against ops.attention.mha_reference in
tests/unit/ops/test_flash_attention.py (interpret mode on CPU), including a
16k-sequence dense case no longer possible with whole-K/V residency.
"""

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# The kernels' names in a device trace (``pallas_call(name=...)`` names the Mosaic
# custom call); metadata only. benchmarks/metrics readers find them by these.
FLASH_FWD = "dstpu_flash_fwd"
FLASH_BWD_DQ = "dstpu_flash_bwd_dq"
FLASH_BWD_DKV = "dstpu_flash_bwd_dkv"
FLASH_BWD_FUSED = "dstpu_flash_bwd_fused"
FLASH_FWD_CHUNK = "dstpu_flash_fwd_chunk"
FLASH_BWD_DQ_CHUNK = "dstpu_flash_bwd_dq_chunk"
FLASH_BWD_DKV_CHUNK = "dstpu_flash_bwd_dkv_chunk"


def _alibi_term(alibi_ref, kpos_ref, cols):
    """ALiBi additive logits term for one block: ``slope_h * key_position``
    (HF bloom's absolute-position convention — softmax-equivalent to the
    relative form under causal masking). alibi_ref: [1, 1, LANES] slope plane
    for this head; kpos_ref: [1, bk] int32 key positions, of which a strip
    of a pair takes its ``cols``."""
    return alibi_ref[0, 0, 0] * kpos_ref[:, cols].astype(jnp.float32)


def _apply_window(logits, window, wflag_ref, q_pos, k_pos):
    """Sliding-window band mask: query sees keys in (q - window, q]. With a
    ``wflag_ref`` ([1, LANES] int32 plane, traced per layer from
    attn_layer_pattern) the band only applies when the flag is set — the
    layer scan stays uniform while layers alternate local/global (gpt_neo).
    The band convention is the shared ``core.window_too_far``."""
    from deepspeed_tpu.ops.attention.core import window_too_far

    far = window_too_far(
        q_pos, k_pos, window, wflag_ref[0, 0] if wflag_ref is not None else None
    )
    return jnp.where(far, NEG_INF, logits)


class PairClasses(NamedTuple):
    """What a causal sequence's block pairs cost (:func:`causal_pair_classes`)."""

    pruned: int      # wholly above the diagonal: grid overhead only
    under: int       # wholly under it: computed whole, no causal mask
    diagonal: int    # the diagonal passes through them
    strip: int       # side of the tiles a diagonal pair is cut into; 0: run whole, masked
    live_tiles: int  # tiles of the diagonal pairs that are computed ...
    tiles: int       # ... of those they hold (a pair run whole is one tile of one)

    @property
    def pairs_of_products(self) -> float:
        """MXU work in whole pairs: ``under`` + the diagonal pairs' live share."""
        return self.under + self.diagonal * self.live_tiles / max(self.tiles, 1)


def causal_pair_classes(s, bq, bk) -> PairClasses:
    """Class every (q block, kv block) pair of a causal ``s``-token sequence by
    where the diagonal passes: the count of what the backward kernels below
    compute (the forward runs ``under + diagonal`` pairs whole). A pair on the
    diagonal goes by strips of ``strip`` (a quarter of the block, at least a
    128-lane tile, at least two a block) and computes only the tiles on or
    under the diagonal; where the blocks differ or are too small to cut,
    ``strip`` is 0 and the pair runs whole under the mask. 4,096 at 1,024:
    6 / 6 / 4, strips of 256, 40 of 64 tiles, 8.5 pairs of products for 10."""
    strip = max(bq // 4, LANES) if bq == bk else 0
    if strip and (bq < 2 * strip or bq % strip):
        strip = 0
    pruned = under = diagonal = 0
    for qi in range(s // bq):
        for ki in range(s // bk):
            if ki * bk > qi * bq + bq - 1:
                pruned += 1
            elif qi * bq >= ki * bk + bk - 1:
                under += 1
            else:
                diagonal += 1
    n = bq // strip if strip else 1
    return PairClasses(pruned, under, diagonal, strip,
                       diagonal * n * (n + 1) // 2, diagonal * n * n)


def _strip_for(causal, window, s, bq, bk):
    """A backward kernel's static ``strip``. None: the pairs it computes are all
    of one class (not causal: no causal mask; a window, static or by ``wflag``:
    every pair whole under the position masks). Else three classes, the
    diagonal pairs by strips of that side, or whole where it is 0."""
    if not causal or window:
        return None
    return causal_pair_classes(s, bq, bk).strip


def _pair_bodies(active, qi, ki, causal, bq, bk, strip):
    """[(condition, class)]: the bodies a backward kernel builds under ``pl.when``
    for the pairs it computes. The class is None (no causal mask), "masked" (the
    whole pair under the position masks, window included) or "cut" (a pair on
    the diagonal, by strips: :func:`_pair_strips`)."""
    if strip is None:
        return [(active, "masked" if causal else None)]
    under = qi * bq >= ki * bk + bk - 1  # implies active
    on = jnp.logical_and(active, jnp.logical_not(under))
    return [(under, None), (on, "cut" if strip else "masked")]


def _pair_strips(cls, strip, bq, bk):
    """[(q rows, key columns, masked)]: the rectangles a backward kernel computes
    a pair by, each through the same body. A "cut" pair (bq == bk) goes by
    key-column strips of ``strip`` against the q rows that see them, and so
    leaves out what lies wholly above the diagonal; dv, dk and dq take their
    products from the same strip's p and ds. The compare covers the strip:
    cutting the diagonal tile out to compare it alone read slower on the v5e.
    Any other pair is one rectangle, the pair."""
    if cls != "cut":
        return [(slice(0, bq), slice(0, bk), cls == "masked")]
    return [(slice(e, bq), slice(e, e + strip), True) for e in range(0, bq, strip)]


def _pair_logits(q, k, qi, ki, scale, bq, bk, window, rows, cols, masked,
                 seg_q_ref=None, seg_k_ref=None, alibi_ref=None, kpos_ref=None,
                 wflag_ref=None):
    """Masked logits f32 of one rectangle of a block pair (q block ``qi``, kv
    block ``ki``; ``q`` its ``rows``, ``k`` its ``cols``: :func:`_pair_strips`):
    scale * q k^T, the ALiBi term, then, where the rectangle is ``masked``, the
    causal and window masks by position, then the segment mask. The forward
    (always the whole pair) and every backward kernel build a pair's scores
    through this one body; the optional refs are a kernel's ``**mask_refs``."""
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if alibi_ref is not None:
        logits = logits + _alibi_term(alibi_ref, kpos_ref, cols)
    if masked:
        q_pos = qi * bq + rows.start + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
        k_pos = ki * bk + cols.start + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(q_pos >= k_pos, logits, NEG_INF)
        if window:
            logits = _apply_window(logits, window, wflag_ref, q_pos, k_pos)
    if seg_q_ref is not None:
        logits = jnp.where(seg_q_ref[rows, :1] == seg_k_ref[:, cols], logits, NEG_INF)
    return logits


def _pair_p_ds(q, k, v, do, lse, delta, *logits_args, **mask_refs):
    """(p, ds) f32 of one rectangle of a pair, the flash recompute every backward
    kernel shares: p = exp(logits - lse), ds = p * (do v^T - delta)."""
    logits = _pair_logits(q, k, *logits_args, **mask_refs)
    p = jnp.exp(logits - lse[:, None])
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    return p, p * (dp - delta[:, None])


def _kv_block_active(qi, ki, causal, bq, bk, window, mask_refs):
    """Does q block ``qi`` see kv block ``ki``? The pairs a q-major grid
    (forward, dq) computes; the rest cost grid overhead only."""
    hi = (qi * bq + bq - 1) // bk  # last kv block a causal q block touches
    active = (ki <= hi) if causal else (ki >= 0)
    if window and mask_refs.get("wflag_ref") is None:
        # static window (every layer banded): prune kv blocks fully behind it
        active = jnp.logical_and(active, ki >= jnp.maximum(0, qi * bq - window + 1) // bk)
    return active


def _q_block_active(ki, qj, causal, bq, bk, window, mask_refs):
    """The same set of pairs, asked from the kv block's side (the kv-major
    grids: dk/dv and the fused backward)."""
    lo = (ki * bk) // bq  # first q block that sees this kv block
    active = (qj >= lo) if causal else (qj >= 0)
    if window and mask_refs.get("wflag_ref") is None:
        # last q block inside the band for this kv block
        active = jnp.logical_and(active, qj <= (ki * bk + bk - 1 + window - 1) // bq)
    return active


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                scale, causal, bq, bk, nk, window=0, m_in_ref=None,
                l_in_ref=None, acc_in_ref=None, l_out_ref=None, **mask_refs):
    # q_ref: [bq, d]; k_ref/v_ref: [bk, d] (one streamed block);
    # o_ref: [bq, d]; lse_ref: [bq, LANES]; scratch m/l: [bq, LANES] f32,
    # acc: [bq, d] f32 — carried across the minor (kv) grid dimension.
    #
    # Carry mode (ring attention, ops/attention/sharded.py): ``m_in_ref``/
    # ``l_in_ref``/``acc_in_ref`` seed the softmax state from a previous
    # chunk instead of (-inf, 0, 0), and ``l_out_ref`` switches the flush to
    # RAW state output — (acc, m, l) via (o_ref, lse_ref, l_out_ref), no
    # normalization — so chunked streaming is bit-identical to one long
    # in-kernel stream.
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        if m_in_ref is None:
            m_ref[:] = jnp.full_like(m_ref, NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)
        else:
            m_ref[:] = m_in_ref[:]
            l_ref[:] = l_in_ref[:]
            acc_ref[:] = acc_in_ref[:].astype(jnp.float32)

    @pl.when(_kv_block_active(qi, ki, causal, bq, bk, window, mask_refs))
    def _step():
        q = q_ref[:]
        k = k_ref[:]
        v = v_ref[:]
        # every pair whole, a causal one under the position masks: ONE body
        # (on the v5e a second, unmasked one for the pairs under the diagonal
        # reads no shorter, 717 against 716 us a layer, and a cut pair longer)
        logits = _pair_logits(q, k, qi, ki, scale, bq, bk, window, slice(0, bq),
                              slice(0, bk), causal, **mask_refs)
        m = m_ref[:, 0]
        l = l_ref[:, 0]
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_ref[:] = acc_ref[:] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ki == nk - 1)
    def _flush():
        if l_out_ref is not None:
            # raw-state flush: the caller continues the stream (or finalizes
            # with flash_finalize, whose math mirrors the branch below)
            o_ref[:] = acc_ref[:]
            lse_ref[:] = m_ref[:]
            l_out_ref[:] = l_ref[:]
        else:
            l_safe = jnp.maximum(l_ref[:, 0], 1e-30)
            o_ref[:] = (acc_ref[:] / l_safe[:, None]).astype(o_ref.dtype)
            lse_ref[:] = jnp.broadcast_to(
                (m_ref[:, 0] + jnp.log(l_safe))[:, None], (bq, LANES)
            )


def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref,
                   delta_ref, dq_acc_ref, *, scale, causal, bq, bk, nk,
                   window=0, strip=None, dq_in_ref=None, raw_out=False, **mask_refs):
    # Carry mode (ring bwd): ``dq_in_ref`` seeds the accumulator from the
    # previous chunk's partial and ``raw_out`` flushes it unscaled in f32 —
    # the ring applies `* scale` once after the last chunk, exactly like the
    # single-kernel flush.
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        if dq_in_ref is None:
            dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)
        else:
            dq_acc_ref[:] = dq_in_ref[:]
        delta = jnp.sum(
            do_ref[:].astype(jnp.float32) * o_ref[:].astype(jnp.float32), axis=-1
        )
        delta_ref[:] = jnp.broadcast_to(delta[:, None], delta_ref.shape)

    def _step(cls):
        for rows, cols, masked in _pair_strips(cls, strip, bq, bk):
            k = k_ref[cols, :]
            _, ds = _pair_p_ds(
                q_ref[rows, :], k, v_ref[cols, :], do_ref[rows, :], lse_ref[rows, 0],
                delta_ref[rows, 0], qi, ki, scale, bq, bk, window, rows, cols, masked,
                **mask_refs)
            dq_acc_ref[rows, :] = dq_acc_ref[rows, :] + jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    active = _kv_block_active(qi, ki, causal, bq, bk, window, mask_refs)
    for when, cls in _pair_bodies(active, qi, ki, causal, bq, bk, strip):
        pl.when(when)(functools.partial(_step, cls))

    @pl.when(ki == nk - 1)
    def _flush():
        if raw_out:
            dq_ref[:] = dq_acc_ref[:]
        else:
            dq_ref[:] = (dq_acc_ref[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dk_ref,
                    dv_ref, dk_acc_ref, dv_acc_ref, *, scale, causal, bq, bk,
                    nq, window=0, strip=None, dk_in_ref=None, dv_in_ref=None,
                    raw_out=False, dq_ref=None, dq_acc_ref=None, nk=None, **mask_refs):
    # Carry mode mirrors _bwd_dq_kernel: seed accumulators from the previous
    # chunk's partials, flush raw f32 when ``raw_out``.
    #
    # Fused mode (``dq_ref``: the head's [s, d] output block; ``dq_acc_ref``:
    # [nq, bq, d] f32 scratch that stays in VMEM across the kv blocks): the
    # pair's scores, masks, p, dp and ds, built ONCE, feed dq too, five
    # products a pair where this kernel and the dq kernel do seven. Block
    # ``qj`` of the accumulator sums over the kv blocks ascending, exactly as
    # the dq kernel's does.
    ki = pl.program_id(2)
    qj = pl.program_id(3)

    @pl.when(qj == 0)
    def _init():
        if dk_in_ref is None:
            dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
            dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)
        else:
            dk_acc_ref[:] = dk_in_ref[:]
            dv_acc_ref[:] = dv_in_ref[:]

    if dq_ref is not None:
        @pl.when(ki == 0)
        def _init_dq():
            dq_acc_ref[qj] = jnp.zeros(dq_acc_ref.shape[1:], dq_acc_ref.dtype)

    def _step(cls):
        for rows, cols, masked in _pair_strips(cls, strip, bq, bk):
            k = k_ref[cols, :]
            q = q_ref[rows, :]
            do = do_ref[rows, :]
            # delta is recomputed per rectangle of a (kv, q) grid point: one
            # [rows, d] VPU reduce (~0.05% of the MXU products below) — cheaper
            # than a separate preprocess kernel or an HBM round-trip for
            # [b, h, s] deltas.
            delta = jnp.sum(
                do.astype(jnp.float32) * o_ref[rows, :].astype(jnp.float32), axis=-1
            )
            p, ds = _pair_p_ds(
                q, k, v_ref[cols, :], do, lse_ref[rows, 0], delta, qj, ki, scale,
                bq, bk, window, rows, cols, masked, **mask_refs)
            dv_acc_ref[cols, :] = dv_acc_ref[cols, :] + jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [cols, d]
            dk_acc_ref[cols, :] = dk_acc_ref[cols, :] + jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if dq_ref is not None:
                dq_acc_ref[qj, rows, :] = dq_acc_ref[qj, rows, :] + jax.lax.dot_general(
                    ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [rows, d]

    active = _q_block_active(ki, qj, causal, bq, bk, window, mask_refs)
    for when, cls in _pair_bodies(active, qj, ki, causal, bq, bk, strip):
        pl.when(when)(functools.partial(_step, cls))

    @pl.when(qj == nq - 1)
    def _flush():
        if raw_out:
            dk_ref[:] = dk_acc_ref[:]
            dv_ref[:] = dv_acc_ref[:]
        else:
            # scale moved onto the logits, so dk picks it up (dlogits/dk = scale*q)
            dk_ref[:] = (dk_acc_ref[:] * scale).astype(dk_ref.dtype)
            dv_ref[:] = dv_acc_ref[:].astype(dv_ref.dtype)

    if dq_ref is not None:
        @pl.when(ki == nk - 1)
        def _flush_dq():
            # the last kv block has passed: q block qj's sum is complete
            rows = pl.ds(pl.multiple_of(qj * bq, bq), bq)
            dq_ref[rows, :] = (dq_acc_ref[qj] * scale).astype(dq_ref.dtype)


# The fused backward (``_bwd_dkv_kernel`` with ``dq_ref``) keeps a head's whole
# dq accumulator ([s, d] f32) in VMEM. It runs where that fits this many bytes
# (s <= 4096 at d = 128, 8192 at 64); a longer sequence takes the dq kernel and
# the dk/dv kernel without it, which hold one block each.
# At that budget (s = 4096, d = 128, blocks of 1024) the kernel's blocks, double
# buffers, accumulators and temporaries come to 16.1 MiB token-major and 17.1
# head-major with no mask operand, on a described v5e with every operand in HBM:
# at the edge of the 16 MiB scoped-VMEM default, under it only where XLA happens
# to keep some operands in VMEM itself (13.6 MiB in the parent's train step). So
# the fused call states its own limit, with room for the mask operands (segment
# ids are 1 MiB more); a v5e has 128 MiB of VMEM.
DQ_RESIDENT_BYTES = 2 * 1024 * 1024
FUSED_VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def _pick_block(s, target=None):
    """Largest power-of-two block ≤ target dividing s. The default block is
    env-tunable (DSTPU_FLASH_BLOCK) for per-generation retuning; with the
    kv-pipelined kernel 1024 measured best on v5e at s=2048 (fwd+bwd 5.75 ms
    vs 6.93 at 512, 10.7 at 256; 2048 exceeds the 16M scoped-vmem limit).
    The block also sets the strip a backward kernel cuts a diagonal pair by
    (``causal_pair_classes``: a quarter of it, 128 at least; 256 measured best
    at 1024: 1.078 ms a layer against 1.110 at 512, 1.142 at 128, 1.223
    uncut), so a block under 256 is not cut."""
    if target is None:
        import os

        target = int(os.environ.get("DSTPU_FLASH_BLOCK", 1024))
        if target < 128 or target & (target - 1):
            raise ValueError(
                f"DSTPU_FLASH_BLOCK={target} invalid: need a power of two >= 128"
            )
    b = min(target, s)
    while s % b:
        b //= 2
    return max(b, 1)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    segment_ids=None,
    scale: Optional[float] = None,
    interpret: bool = False,
    alibi_slopes=None,
    alibi_positions=None,
    window: int = 0,
    window_flag=None,
    head_dim: Optional[int] = None,
) -> jax.Array:
    """Flash attention, in the layout its operands have. Rank 4 is HEAD-MAJOR:
    q [b, h, s, d]; k, v [b, h_kv, s, d] → [b, h, s, d]. Rank 3 is TOKEN-MAJOR,
    what a layer's projections write: q [b, s, h * head_dim]; k, v
    [b, s, h_kv * head_dim] → [b, s, h * head_dim] (``head_dim`` says where a
    head ends), and the gradients come back so. Where a head is whole lanes
    (``head_dim % 128 == 0``) the kernels index a head's lanes out of those
    arrays in place; a head that is not (64) cannot be a block's column and is
    re-laid to the head-major form here, as its caller used to. Same sums in
    the same order either way: outputs and gradients are bit-equal.

    ``segment_ids``: optional [b, s] int32 — packed-sequence masking happens
    IN the kernel (tokens attend only within their own segment), so packed
    pretraining keeps the flash path.

    ``alibi_slopes``: optional [h] fp32 — bloom-style ALiBi folds into the
    kernel as ``slope_h * key_position`` added to the logits (rank-1, so the
    [s, s] bias never materializes; the review of round 4 found alibi
    silently dropping to the O(s²)-HBM reference path). ``alibi_positions``
    ([b, s] or [s] int32) supplies the key positions; defaults to arange.
    Slopes are constants (non-learned) — no cotangent.

    ``window``: static sliding-window size — query i sees keys in
    (i - window, i] (mistral/starcoder2/gpt_neo). With ``window_flag`` None
    every layer is banded and out-of-band kv BLOCKS are pruned from the grid
    (compute and copies drop to O(s·window)); with ``window_flag`` (a traced
    0/1 scalar from attn_layer_pattern) the band toggles per layer via
    in-kernel masking (full causal grid, flash memory). Requires causal."""
    if window and not causal:
        raise ValueError("flash_attention: window > 0 requires causal=True")
    if q.ndim == 3 and not head_dim:
        raise ValueError("flash_attention: [b, s, heads * d] operands need head_dim")
    # which form the kernels take is read off the head: whole lanes or not
    head_lanes = head_dim if q.ndim == 3 and head_dim % LANES == 0 else 0
    relaid = q.ndim == 3 and not head_lanes
    if relaid:
        q, k, v = (head_major(x, head_dim) for x in (q, k, v))
    b, _, _, s, _ = _dims(q, k, head_lanes)
    alibi = None
    if alibi_slopes is not None:
        alibi = build_alibi_operand(alibi_slopes, alibi_positions, b, s)
    wflag = None
    if window and window_flag is not None:
        wflag = jnp.broadcast_to(
            jnp.asarray(window_flag, jnp.int32).reshape(1, 1), (1, LANES)
        )
    out = _flash_core(q, k, v, segment_ids, alibi, wflag, causal, scale,
                      int(window), interpret, head_lanes)
    return token_major(out) if relaid else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _flash_core(q, k, v, segment_ids, alibi, wflag, causal, scale, window, interpret,
                head_lanes):
    """``head_lanes`` 0: head-major operands, q [b, h, s, d]. Else token-major,
    q [b, s, h * head_lanes], k / v [b, s, h_kv * head_lanes], and the output,
    the residuals and the three gradients in that form too."""
    out, _ = _flash_fwd(q, k, v, segment_ids, alibi, wflag, causal, scale, window,
                        interpret, head_lanes)
    return out


def _dims(q, k, head_lanes):
    """(b, h, h_kv, s, d) of a call's q and k in either layout."""
    if head_lanes:
        b, s, lanes = q.shape
        return b, lanes // head_lanes, k.shape[2] // head_lanes, s, head_lanes
    b, h, s, d = q.shape
    return b, h, k.shape[1], s, d


SUBLANES = 8


def heads_view(x, heads):
    """x [b, s, heads * d] a head at a time WITHOUT leaving where it lies:
    [b, s / 8, heads, 8, d]. In HBM a [.., s, heads * d] array is (8, 128)
    tiles, eight tokens of one head's lanes; its default 4-D split [b, s,
    heads, d] tiles (heads, d) instead and is a copy away, in both directions,
    which is what a per-head norm, rope and their gradients around a
    token-major kernel would pay. This view's minor dims are the tile: the
    same bytes, so the chip's compiler reads and writes it in place. The head
    axis is 2; a position-wise operand broadcasts from ``tokens_view``. A
    sequence that is not whole tiles takes the plain [b, s, heads, d]: head
    axis 2 as well. :func:`heads_flat` is the way back."""
    b, s, lanes = x.shape
    if s % SUBLANES:
        return x.reshape(b, s, heads, lanes // heads)
    return x.reshape(b, s // SUBLANES, SUBLANES, heads, lanes // heads).transpose(0, 1, 3, 2, 4)


def tokens_view(positions, s):
    """[.., s] per-token values, shaped as :func:`heads_view` lays the tokens
    of a sequence of ``s``: [.., s / 8, 8], or as they are."""
    if s % SUBLANES:
        return positions
    return positions.reshape(positions.shape[:-1] + (s // SUBLANES, SUBLANES))


def heads_flat(x):
    """A :func:`heads_view` back as [b, s, heads * d]."""
    if x.ndim == 5:
        b, t, heads, r, d = x.shape
        return x.transpose(0, 1, 3, 2, 4).reshape(b, t * r, heads * d)
    return x.reshape(x.shape[:2] + (-1,))


def head_major(x, head_dim):
    """[b, s, heads * head_dim] re-laid as [b, heads, s, head_dim]: what every
    backend but the token-major kernels takes. A copy."""
    return x.reshape(x.shape[:2] + (-1, head_dim)).transpose(0, 2, 1, 3)


def token_major(x):
    """[b, heads, s, d] re-laid as [b, s, heads * d]: :func:`head_major` undone."""
    return x.transpose(0, 2, 1, 3).reshape(x.shape[0], x.shape[2], -1)


def _head_spec(head_lanes, rows, d, row_map, head_map=lambda h_: h_):
    """BlockSpec of one head's [rows, d] block of a per-head operand, at row
    block ``row_map(i, j)`` of head ``head_map(h_)``. Head-major: (1, 1, rows,
    d) of [b, heads, s, d]. Token-major: the head's d lanes out of [b, s,
    heads * d], (1, rows, d) at column block ``head``: a column of (rows, 128)
    tiles, so the block copy stays tile-granular."""
    if head_lanes:
        return pl.BlockSpec((1, rows, d),
                            lambda b_, h_, i, j: (b_, row_map(i, j), head_map(h_)))
    return pl.BlockSpec((1, 1, rows, d),
                        lambda b_, h_, i, j: (b_, head_map(h_), row_map(i, j), 0))


def _head_ref(head_lanes, ref):
    """A :func:`_head_spec` block with its unit dims squeezed: [rows, d]."""
    return ref.at[0] if head_lanes else ref.at[0, 0]


def _layout_name(head_lanes):
    return "token_major" if head_lanes else "head_major"


def _kv_clamp(causal, bq, bk, window=0, static_window=False):
    """kv-block index map value for grid point (i, j): masked points re-fetch
    the nearest active block (Pallas elides the unchanged copy). A static
    window additionally clamps from below — blocks fully behind the band are
    never fetched."""
    if not causal:
        return lambda i, j: j
    hi = lambda i: (i * bq + bq - 1) // bk
    if window and static_window:
        return lambda i, j: jnp.clip(j, jnp.maximum(0, i * bq - window + 1) // bk, hi(i))
    return lambda i, j: jnp.minimum(j, hi(i))


def _q_clamp(causal, bq, bk, window=0, static_window=False, nq=None):
    """q-block index map for the dk/dv grid (kv major, q minor)."""
    if not causal:
        return lambda i, j: j
    lo = lambda i: (i * bk) // bq
    if window and static_window:
        return lambda i, j: jnp.clip(
            j, lo(i), jnp.minimum(nq - 1, (i * bk + bk - 1 + window - 1) // bq)
        )
    return lambda i, j: jnp.maximum(j, lo(i))


def _seg_specs(segment_ids, q_block, q_map, k_block, k_map):
    """(extra operands, extra in_specs) for the [b, s] segment-id planes.
    ``q_map``/``k_map`` are (i, j) -> block-index functions — the same clamps
    used for the q and k/v tensor specs, so masked grid points re-fetch the
    previous seg block (copy elided) exactly like their tensors.

    ``segment_ids`` may be one [b, s] plane (self-attention: the same ids
    mask both sides) or a ``(seg_q, seg_k)`` pair of [b, sq]/[b, sk] planes —
    the ring path's chunks carry DIFFERENT q-side and k-side id planes (the
    k chunk rotates, the q chunk stays home)."""
    if segment_ids is None:
        return [], []
    if isinstance(segment_ids, tuple):
        seg_q, seg_k = segment_ids
    else:
        seg_q = seg_k = segment_ids
    # Mosaic wants a block's last two dims to be (8, 128)-aligned or whole:
    # a (1, block) window of a [b, s] plane is neither once b > 1, and a
    # [b, sq, 1] column cannot be sliced ("must be aligned to tiling (128)").
    # So the q ids ride lane-broadcast as [b, sq, LANES], like the LSE, and
    # the k ids as a [b, 1, sk] row: the kernel compares column 0 of the one
    # with the row of the other, [bq, 1] == [1, bk], by broadcast.
    seg_q = jnp.broadcast_to(
        seg_q.astype(jnp.int32)[:, :, None], seg_q.shape + (LANES,))
    seg_k = seg_k.astype(jnp.int32)[:, None, :]
    return [seg_q, seg_k], [
        pl.BlockSpec((1, q_block, LANES), lambda b_, h_, i, j: (b_, q_map(i, j), 0)),
        pl.BlockSpec((1, 1, k_block), lambda b_, h_, i, j: (b_, 0, k_map(i, j))),
    ]


def _alibi_specs(alibi, k_block, k_map):
    """(extra operands, extra in_specs) for ALiBi: the per-head slope plane
    [h, LANES] plus the [b, s] key-position plane (k-side blocks only). Both
    gain a unit middle dim for the same reason as the segment ids: a one-row
    window of a 2-D plane is not a legal Mosaic block."""
    if alibi is None:
        return [], []
    slopes_lane, kpos = alibi
    return [slopes_lane[:, None, :], kpos[:, None, :]], [
        pl.BlockSpec((1, 1, LANES), lambda b_, h_, i, j: (h_, 0, 0)),
        pl.BlockSpec((1, 1, k_block), lambda b_, h_, i, j: (b_, 0, k_map(i, j))),
    ]


def _wflag_specs(wflag):
    """(extra operands, extra in_specs) for the per-layer window flag plane."""
    if wflag is None:
        return [], []
    return [wflag], [pl.BlockSpec((1, LANES), lambda b_, h_, i, j: (0, 0))]


def _pop_mask_refs(rest, seg_ops, alibi_ops, wf_ops=()):
    """Split a kernel entry's trailing refs into the kernel's ``**mask_refs``
    (present in the order the ``*_specs`` helpers above append their operands)
    and what follows them: the call's outputs and scratch."""
    rest = list(rest)
    kw = {}
    if seg_ops:
        kw["seg_q_ref"] = rest.pop(0).at[0]
        kw["seg_k_ref"] = rest.pop(0).at[0]
    if alibi_ops:
        kw["alibi_ref"] = rest.pop(0)
        kw["kpos_ref"] = rest.pop(0).at[0]
    if wf_ops:
        kw["wflag_ref"] = rest.pop(0)
    return kw, rest


def _flash_call(q, k, v, segment_ids, alibi, wflag, causal, scale, window, interpret,
                head_lanes=0):
    b, h, h_kv, s, d = _dims(q, k, head_lanes)
    group = h // h_kv
    scale = scale if scale is not None else d ** -0.5
    bq = _pick_block(s)
    bk = _pick_block(s)
    nq, nk = s // bq, s // bk
    jc = _kv_clamp(causal, bq, bk, window, static_window=wflag is None)
    # once a traced forward: which form its operands took
    from deepspeed_tpu.observability.tracing import get_tracer

    get_tracer().instant("flash.layout", track="trace", args={
        "kernel": FLASH_FWD, "s": s, "block": bq, "layout": _layout_name(head_lanes)})
    head = functools.partial(_head_ref, head_lanes)
    q_spec = _head_spec(head_lanes, bq, d, lambda i, j: i)
    kv_spec = _head_spec(head_lanes, bk, d, jc, lambda h_: h_ // group)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk, window=window
    )

    seg_ops, seg_specs = _seg_specs(segment_ids, bq, lambda i, j: i, bk, jc)
    alibi_ops, alibi_specs = _alibi_specs(alibi, bk, jc)
    wf_ops, wf_specs = _wflag_specs(wflag)

    def entry(qr, kr, vr, *rest):
        kw, (orf, lr, mref, lref, aref) = _pop_mask_refs(
            rest, seg_ops, alibi_ops, wf_ops)
        kernel(head(qr), head(kr), head(vr), head(orf),
               lr.at[0, 0], mref, lref, aref, **kw)

    out, lse = pl.pallas_call(
        # refs arrive with the block's leading unit dims squeezed via .at
        entry,
        grid=(b, h, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec] + seg_specs + alibi_specs + wf_specs,
        out_specs=[
            q_spec,
            pl.BlockSpec((1, 1, bq, LANES), lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, s, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),  # running max m
            pltpu.VMEM((bq, LANES), jnp.float32),  # running sum l
            pltpu.VMEM((bq, d), jnp.float32),      # output accumulator
        ],
        interpret=interpret,
        name=FLASH_FWD,
    )(q, k, v, *seg_ops, *alibi_ops, *wf_ops)
    return out, lse


def _flash_fwd(q, k, v, segment_ids, alibi, wflag, causal, scale, window, interpret,
               head_lanes=0):
    out, lse = _flash_call(q, k, v, segment_ids, alibi, wflag, causal, scale, window,
                           interpret, head_lanes)
    # Residual LSE is narrowed to one lane (it is lane-broadcast) so saving it
    # costs b·h·s·4 bytes, not ×LANES; the backward re-broadcasts. The names
    # feed the remat policies (models.transformer.remat_policy: every one but
    # "nothing" keeps them): saving out+lse means a remat'd layer skips
    # re-running the attention forward.
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, "flash_out")
    lse1 = checkpoint_name(lse[..., :1], "flash_lse")
    # Residual q/k/v carry their own tag: the "flash_qkv" policy additionally
    # skips re-running the qkv projections + rope in a remat'd backward.
    q = checkpoint_name(q, "flash_qkv")
    k = checkpoint_name(k, "flash_qkv")
    v = checkpoint_name(v, "flash_qkv")
    return out, (q, k, v, segment_ids, alibi, wflag, out, lse1)


def _flash_bwd(causal, scale, window, interpret, head_lanes, res, g):
    q, k, v, segment_ids, alibi, wflag, out, lse = res
    lse = jnp.broadcast_to(lse, lse.shape[:-1] + (LANES,))
    b, h, h_kv, s, d = _dims(q, k, head_lanes)
    group = h // h_kv
    scale_v = scale if scale is not None else d ** -0.5
    args = (q, k, v, out, g, lse, segment_ids, alibi, wflag, causal, scale_v,
            window, interpret, head_lanes)
    # Which kernels is read off the shape: one pass where a head's dq
    # accumulator may stay in VMEM, the dq and dk/dv kernels above that.
    # Same sums in the same order either way.
    if s * d * 4 <= DQ_RESIDENT_BYTES:
        dk_h, dv_h, dq = _bwd_dkv_call(*args, with_dq=True)
    else:
        dq = _bwd_dq_call(*args)
        dk_h, dv_h = _bwd_dkv_call(*args, with_dq=False)
    # dk/dv come per q-head and are reduced over the GQA group here, outside
    # the kernel: the ring's hand-over (flash_dkv_finalize) sums the same way.
    # Token-major the group is split off the head axis of the tiles' view: the
    # same terms in the same order, and no re-laying around the sum.
    if group > 1 and head_lanes:
        def fold(x):
            x = heads_view(x, h)
            x = x.reshape(x.shape[:2] + (h_kv, group) + x.shape[3:]).astype(jnp.float32)
            return heads_flat(jnp.sum(x, axis=3).astype(k.dtype))
        dk, dv = fold(dk_h), fold(dv_h)
    elif group > 1:
        dk = jnp.sum(dk_h.reshape(b, h_kv, group, s, d).astype(jnp.float32), axis=2).astype(k.dtype)
        dv = jnp.sum(dv_h.reshape(b, h_kv, group, s, d).astype(jnp.float32), axis=2).astype(v.dtype)
    else:
        dk, dv = dk_h, dv_h
    return dq, dk, dv, None, None, None  # no cotangent for segment_ids / alibi / wflag


def _bwd_dq_call(q, k, v, out, g, lse, segment_ids, alibi, wflag, causal, scale,
                 window, interpret, head_lanes=0):
    """dq from the dq kernel: q-major, kv blocks streamed."""
    b, h, h_kv, s, d = _dims(q, k, head_lanes)
    group = h // h_kv
    bq = _pick_block(s)
    bk = _pick_block(s)
    nq, nk = s // bq, s // bk
    jc = _kv_clamp(causal, bq, bk, window, static_window=wflag is None)

    kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk,
        window=window, strip=_strip_for(causal, window, s, bq, bk),
    )
    seg_ops, seg_specs = _seg_specs(segment_ids, bq, lambda i, j: i, bk, jc)
    alibi_ops, alibi_specs = _alibi_specs(alibi, bk, jc)
    wf_ops, wf_specs = _wflag_specs(wflag)

    head = functools.partial(_head_ref, head_lanes)

    def entry(qr, kr, vr, orf, dor, lr, *rest):
        kw, (dqr, dref, aref) = _pop_mask_refs(rest, seg_ops, alibi_ops, wf_ops)
        kernel(head(qr), head(kr), head(vr), head(orf),
               head(dor), lr.at[0, 0], head(dqr), dref, aref, **kw)

    q_spec = _head_spec(head_lanes, bq, d, lambda i, j: i)
    kv_spec = _head_spec(head_lanes, bk, d, jc, lambda h_: h_ // group)
    return pl.pallas_call(
        entry,
        grid=(b, h, nq, nk),
        in_specs=[
            q_spec, kv_spec, kv_spec, q_spec, q_spec,
            pl.BlockSpec((1, 1, bq, LANES), lambda b_, h_, i, j: (b_, h_, i, 0)),
        ] + seg_specs + alibi_specs + wf_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),  # delta
            pltpu.VMEM((bq, d), jnp.float32),      # dq accumulator
        ],
        interpret=interpret,
        name=FLASH_BWD_DQ,
    )(q, k, v, out, g, lse, *seg_ops, *alibi_ops, *wf_ops)


def _bwd_dkv_call(q, k, v, out, g, lse, segment_ids, alibi, wflag, causal, scale,
                  window, interpret, head_lanes=0, *, with_dq):
    """(per-q-head dk, per-q-head dv) from the dk/dv kernel: kv-major, with
    the q/do/o/lse stream minor so one [bk, d] kv block stays resident.
    ``with_dq``: the fused backward, (dk, dv, dq) from that one pass, the
    head's whole dq accumulated in VMEM beside them."""
    b, h, h_kv, s, d = _dims(q, k, head_lanes)
    group = h // h_kv
    bq = _pick_block(s)
    bk = _pick_block(s)
    nq, nk = s // bq, s // bk
    qc = _q_clamp(causal, bq, bk, window, static_window=wflag is None, nq=nq)
    strip = _strip_for(causal, window, s, bq, bk)
    if strip is not None:
        # once a traced backward: what its pairs cost, by class
        from deepspeed_tpu.observability.tracing import get_tracer

        classes = causal_pair_classes(s, bq, bk)
        get_tracer().instant("flash.causal_pairs", track="trace", args={
            "s": s, "block": bq, **classes._asdict(),
            "pairs_of_products": classes.pairs_of_products,
            "layout": _layout_name(head_lanes)})

    kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nq=nq,
        nk=nk, window=window, strip=strip,
    )
    seg_ops, seg_specs = _seg_specs(segment_ids, bq, qc, bk, lambda i, j: i)
    # the grid is kv-major: the key-position block follows the kv index i
    alibi_ops, alibi_specs = _alibi_specs(alibi, bk, lambda i, j: i)
    wf_ops, wf_specs = _wflag_specs(wflag)

    head = functools.partial(_head_ref, head_lanes)

    def entry(qr, kr, vr, orf, dor, lr, *rest):
        kw, rest = _pop_mask_refs(rest, seg_ops, alibi_ops, wf_ops)
        if with_dq:
            dkr, dvr, dqr, dka, dva, dqa = rest
            kw.update(dq_ref=head(dqr), dq_acc_ref=dqa)
        else:
            dkr, dvr, dka, dva = rest
        kernel(head(qr), head(kr), head(vr), head(orf),
               head(dor), lr.at[0, 0], head(dkr), head(dvr),
               dka, dva, **kw)

    q_spec = _head_spec(head_lanes, bq, d, qc)
    kv_in_spec = _head_spec(head_lanes, bk, d, lambda i, j: i, lambda h_: h_ // group)
    kv_spec = _head_spec(head_lanes, bk, d, lambda i, j: i)
    # the head's whole dq: written back once, when the head changes
    dq_spec = _head_spec(head_lanes, s, d, lambda i, j: 0)
    return pl.pallas_call(
        entry,
        grid=(b, h, nk, nq),
        in_specs=[
            q_spec, kv_in_spec, kv_in_spec, q_spec, q_spec,
            pl.BlockSpec((1, 1, bq, LANES),
                         lambda b_, h_, i, j: (b_, h_, qc(i, j), 0)),
        ] + seg_specs + alibi_specs + wf_specs,
        out_specs=[kv_spec, kv_spec] + [dq_spec] * with_dq,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)] * (2 + with_dq),
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),  # dk accumulator
            pltpu.VMEM((bk, d), jnp.float32),  # dv accumulator
        ] + [pltpu.VMEM((nq, bq, d), jnp.float32)] * with_dq,  # the head's dq
        interpret=interpret,
        **({"compiler_params": pltpu.CompilerParams(vmem_limit_bytes=FUSED_VMEM_LIMIT_BYTES)}
           if with_dq else {}),
        name=FLASH_BWD_FUSED if with_dq else FLASH_BWD_DKV,
    )(q, k, v, out, g, lse, *seg_ops, *alibi_ops, *wf_ops)


_flash_core.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Chunked (ring) entry points — ops/attention/sharded.py
#
# The ring context-parallel layer streams k/v (forward, dq) or q/do (dk/dv)
# CHUNKS through the same kernels above, threading the raw softmax state /
# gradient accumulators between pallas_calls instead of carrying them in VMEM
# scratch across one long grid. Because chunk arrival order is arranged to
# match the single-kernel streaming order (ascending global blocks) and the
# block size matches, the chunked stream is BIT-IDENTICAL to one
# flash_attention call over the gathered sequence — the acceptance bar for
# the ring path (tests/unit/ops/test_sharded_attention.py, atol 0).
# ---------------------------------------------------------------------------


def build_alibi_operand(alibi_slopes, alibi_positions, b, s):
    """Kernel-ready ALiBi operand: ([h, LANES] lane-broadcast slope plane,
    [b, s] int32 key positions). ``alibi_positions`` defaults to arange —
    ring chunks pass their GLOBAL key positions so slope·kpos matches the
    unsharded kernel exactly."""
    slopes = jnp.asarray(alibi_slopes, jnp.float32)
    pos = (
        jnp.arange(s, dtype=jnp.int32)
        if alibi_positions is None
        else jnp.asarray(alibi_positions, jnp.int32)
    )
    if pos.ndim == 1:
        pos = jnp.broadcast_to(pos[None], (b, s))
    return (jnp.broadcast_to(slopes[:, None], (slopes.shape[0], LANES)), pos)


def flash_carry_init(b, h, s, d):
    """Initial (m, l, acc) softmax carry — identical to the kernel's ki==0
    seed (NEG_INF, not -inf: matches ``_fwd_kernel._init`` bitwise)."""
    return (
        jnp.full((b, h, s, LANES), NEG_INF, jnp.float32),
        jnp.zeros((b, h, s, LANES), jnp.float32),
        jnp.zeros((b, h, s, d), jnp.float32),
    )


def flash_finalize(carry, dtype):
    """Normalize a streamed carry into (out, lse[..., :1]) with math identical
    to the kernel's non-raw flush (``_fwd_kernel._flush``):
    ``out = acc / max(l, 1e-30)``; ``lse = m + log(max(l, 1e-30))``."""
    m, l, acc = carry
    l_safe = jnp.maximum(l[..., :1], 1e-30)
    out = (acc / l_safe).astype(dtype)
    lse = m[..., :1] + jnp.log(l_safe)
    return out, lse


def flash_fwd_chunk(q, k, v, carry, segment_ids=None, alibi=None,
                    causal=False, scale=None, block=None, interpret=False):
    """Stream ONE k/v chunk into a carried flash softmax state.

    q: [b, h, sq, d] (the home query shard); k, v: [b, h_kv, sk, d] (the
    chunk currently held by this ring step). ``carry`` is ``(m, l, acc)``
    from :func:`flash_carry_init` or a previous chunk. ``causal=True`` marks
    the DIAGONAL chunk (sq == sk, local positions — the global offset cancels
    on both sides of the mask). ``segment_ids`` is a ``(seg_q, seg_k)`` pair;
    ``alibi`` a :func:`build_alibi_operand` tuple whose kpos plane holds this
    chunk's GLOBAL key positions. ``block`` must equal the block size the
    equivalent single-device call would pick for bitwise parity.

    Returns the updated ``(m, l, acc)``.
    """
    b, h, sq, d = q.shape
    h_kv, sk = k.shape[1], k.shape[2]
    group = h // h_kv
    scale = scale if scale is not None else d ** -0.5
    if causal and sq != sk:
        raise ValueError("flash_fwd_chunk: causal=True is the diagonal chunk; needs sq == sk")
    bq = _pick_block(sq, target=block)
    bk = _pick_block(sk, target=block)
    nq, nk = sq // bq, sk // bk
    jc = _kv_clamp(causal, bq, bk)
    m, l, acc = carry

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk
    )
    seg_ops, seg_specs = _seg_specs(segment_ids, bq, lambda i, j: i, bk, jc)
    alibi_ops, alibi_specs = _alibi_specs(alibi, bk, jc)

    def entry(qr, kr, vr, mir, lir, air, *rest):
        kw, (aor, mor, lor, mref, lref, aref) = _pop_mask_refs(
            rest, seg_ops, alibi_ops)
        kernel(qr.at[0, 0], kr.at[0, 0], vr.at[0, 0], aor.at[0, 0],
               mor.at[0, 0], mref, lref, aref, m_in_ref=mir.at[0, 0],
               l_in_ref=lir.at[0, 0], acc_in_ref=air.at[0, 0],
               l_out_ref=lor.at[0, 0], **kw)

    q_spec = pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0))
    lane_spec = pl.BlockSpec((1, 1, bq, LANES), lambda b_, h_, i, j: (b_, h_, i, 0))
    acc_out, m_out, l_out = pl.pallas_call(
        entry,
        grid=(b, h, nq, nk),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, i, j: (b_, h_ // group, jc(i, j), 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, i, j: (b_, h_ // group, jc(i, j), 0)),
            lane_spec,  # m carry-in
            lane_spec,  # l carry-in
            q_spec,     # acc carry-in
        ] + seg_specs + alibi_specs,
        out_specs=[q_spec, lane_spec, lane_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, sq, LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, h, sq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        name=FLASH_FWD_CHUNK,
    )(q, k, v, m, l, acc, *seg_ops, *alibi_ops)
    return m_out, l_out, acc_out


def flash_dq_chunk(q, k, v, out, do, lse, dq_acc, segment_ids=None,
                   alibi=None, causal=False, scale=None, block=None,
                   interpret=False):
    """One ring hop of the dq backward: fold this k/v chunk's contribution
    into ``dq_acc`` ([b, h, sq, d] f32, UNSCALED). ``lse`` is the GLOBAL
    log-sum-exp ([..., 1] or lane-broadcast) — the flash recompute
    p = exp(qk·scale − lse) is exact per chunk, so chunk order only affects
    the dq sum, which :func:`flash_dq_finalize` scales/casts once at the end
    exactly like the single-kernel flush."""
    b, h, sq, d = q.shape
    h_kv, sk = k.shape[1], k.shape[2]
    group = h // h_kv
    scale = scale if scale is not None else d ** -0.5
    if causal and sq != sk:
        raise ValueError("flash_dq_chunk: causal=True is the diagonal chunk; needs sq == sk")
    bq = _pick_block(sq, target=block)
    bk = _pick_block(sk, target=block)
    nq, nk = sq // bq, sk // bk
    jc = _kv_clamp(causal, bq, bk)
    lse = jnp.broadcast_to(lse, lse.shape[:-1] + (LANES,))

    kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk,
        strip=_strip_for(causal, 0, sq, bq, bk), raw_out=True,
    )
    seg_ops, seg_specs = _seg_specs(segment_ids, bq, lambda i, j: i, bk, jc)
    alibi_ops, alibi_specs = _alibi_specs(alibi, bk, jc)

    def entry(qr, kr, vr, orf, dor, lr, dqi, *rest):
        kw, (dqr, dref, aref) = _pop_mask_refs(rest, seg_ops, alibi_ops)
        kernel(qr.at[0, 0], kr.at[0, 0], vr.at[0, 0], orf.at[0, 0],
               dor.at[0, 0], lr.at[0, 0], dqr.at[0, 0], dref, aref,
               dq_in_ref=dqi.at[0, 0], **kw)

    q_spec = pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, d),
                           lambda b_, h_, i, j: (b_, h_ // group, jc(i, j), 0))
    lane_spec = pl.BlockSpec((1, 1, bq, LANES), lambda b_, h_, i, j: (b_, h_, i, 0))
    return pl.pallas_call(
        entry,
        grid=(b, h, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec, lane_spec, q_spec]
        + seg_specs + alibi_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),  # delta
            pltpu.VMEM((bq, d), jnp.float32),      # dq accumulator
        ],
        interpret=interpret,
        name=FLASH_BWD_DQ_CHUNK,
    )(q, k, v, out, do, lse, dq_acc, *seg_ops, *alibi_ops)


def flash_dkv_chunk(q, k, v, out, do, lse, dk_acc, dv_acc, segment_ids=None,
                    alibi=None, causal=False, scale=None, block=None,
                    interpret=False):
    """One ring hop of the dk/dv backward: the HOME k/v chunk absorbs the
    contribution of a visiting q-side chunk (q/out/do/lse rotate; the
    accumulators stay put). ``dk_acc``/``dv_acc`` are [b, h, sk, d] f32
    PER-Q-HEAD partials (unscaled); :func:`flash_dkv_finalize` applies the
    scale/cast and GQA group reduction after the last chunk."""
    b, h, sq, d = q.shape
    h_kv, sk = k.shape[1], k.shape[2]
    group = h // h_kv
    scale = scale if scale is not None else d ** -0.5
    if causal and sq != sk:
        raise ValueError("flash_dkv_chunk: causal=True is the diagonal chunk; needs sq == sk")
    bq = _pick_block(sq, target=block)
    bk = _pick_block(sk, target=block)
    nq, nk = sq // bq, sk // bk
    qc = _q_clamp(causal, bq, bk, nq=nq)
    lse = jnp.broadcast_to(lse, lse.shape[:-1] + (LANES,))

    kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nq=nq,
        strip=_strip_for(causal, 0, sq, bq, bk), raw_out=True,
    )
    seg_ops, seg_specs = _seg_specs(segment_ids, bq, qc, bk, lambda i, j: i)
    alibi_ops, alibi_specs = _alibi_specs(alibi, bk, lambda i, j: i)

    def entry(qr, kr, vr, orf, dor, lr, dki, dvi, *rest):
        kw, (dkr, dvr, dka, dva) = _pop_mask_refs(rest, seg_ops, alibi_ops)
        kernel(qr.at[0, 0], kr.at[0, 0], vr.at[0, 0], orf.at[0, 0],
               dor.at[0, 0], lr.at[0, 0], dkr.at[0, 0], dvr.at[0, 0],
               dka, dva, dk_in_ref=dki.at[0, 0], dv_in_ref=dvi.at[0, 0], **kw)

    q_spec = pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, qc(i, j), 0))
    kv_in_spec = pl.BlockSpec((1, 1, bk, d),
                              lambda b_, h_, i, j: (b_, h_ // group, i, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i, j: (b_, h_, i, 0))
    lane_spec = pl.BlockSpec((1, 1, bq, LANES),
                             lambda b_, h_, i, j: (b_, h_, qc(i, j), 0))
    return pl.pallas_call(
        entry,
        grid=(b, h, nk, nq),
        in_specs=[q_spec, kv_in_spec, kv_in_spec, q_spec, q_spec, lane_spec,
                  kv_spec, kv_spec] + seg_specs + alibi_specs,
        out_specs=[kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
        name=FLASH_BWD_DKV_CHUNK,
    )(q, k, v, out, do, lse, dk_acc, dv_acc, *seg_ops, *alibi_ops)


def flash_dq_finalize(dq_acc, scale, dtype):
    """Scale + cast the streamed dq accumulator — identical to the
    single-kernel flush (``_bwd_dq_kernel._flush``, raw_out=False)."""
    return (dq_acc * scale).astype(dtype)


def flash_dkv_finalize(dk_acc, dv_acc, scale, dtype, h_kv):
    """Scale/cast the streamed per-q-head dk/dv partials and reduce the GQA
    group — the exact cast-then-f32-sum order of ``_flash_bwd``."""
    b, h, s, d = dk_acc.shape
    dk = (dk_acc * scale).astype(dtype)
    dv = dv_acc.astype(dtype)
    if h != h_kv:
        group = h // h_kv
        dk = jnp.sum(
            dk.reshape(b, h_kv, group, s, d).astype(jnp.float32), axis=2
        ).astype(dtype)
        dv = jnp.sum(
            dv.reshape(b, h_kv, group, s, d).astype(jnp.float32), axis=2
        ).astype(dtype)
    return dk, dv
