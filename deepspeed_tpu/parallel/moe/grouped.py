"""Drop-free expert layer: rows sorted by expert, matmuls grouped by expert.

What a served mixture-of-experts model computes as published (HF
``MixtralSparseMoeBlock`` / ``Qwen2MoeSparseMoeBlock`` / ``OlmoeSparseMoeBlock``):
every token goes to its top-k experts, whatever the load. The capacity-padded
einsum dispatch of ``sharded_moe.py`` can only say that with a capacity equal to
the token count, which costs O(t^2 E h) in dispatch and combine and makes every
expert multiply a ``[t, h]`` buffer. Here the ``t * k`` (token, expert) pairs are
sorted by expert (a stable sort, so a token's rows keep their order inside an
expert), each expert multiplies only its own rows, and the result is unsorted
and summed with the gate values:

  probs = softmax(x Wr)              float32, over the E experts
  p, e  = top_k(probs)               (renormalised where the model says so)
  rows  = x[token of each pair], pairs sorted by e; group_sizes[E]
  y     = gmm(silu(gmm(rows, Wg)) * gmm(rows, Wu), Wd)
  out_t = sum over the token's k pairs of p * y

Slots that are not ``live`` (the padding of a serving step's packed grid) are
given to no expert: their pairs sort behind the last group and are neither
multiplied nor counted.

The layer is told which experts it holds (``moe_experts_total`` /
``moe_expert_shard``: one chip's share under expert parallelism). The router is
as wide as the published count and the top-k is taken, and renormalised, over
all of them; a pair whose expert is held elsewhere sorts behind the last group
with the padding, so what comes back is this share's PART of the sum. On one
chip the layer runs without its exchange, and nothing stands in for the chips
that are not there.

Identity experts (``moe_zero_experts``, longcat_flash's zero-computation
experts): the router is that much wider than the experts there are, and a
chosen id behind them adds ``gate x the layer's input``. Such a pair has no
weight, so no row of the grouped matmul, no entry in the sort and no tile: a
token's identity pairs are ONE fused multiply-add over ``[t, h]`` with the sum
of their gates. It needs no dispatch either, so under an expert share every
chip adds it for its own tokens, like a shared expert.

``grouped_matmul`` is the one new operation. On a TPU it is the Pallas kernel
``dstpu_moe_gmm`` (the shape of ``jax.experimental.pallas.ops.tpu.megablox``: row
tiles aligned to group boundaries through scalar prefetch, a tile that straddles
a boundary visited once a group and stored under a row mask); elsewhere, and as
its oracle, ``jax.lax.ragged_dot``. Its backward is the plain ``ragged_dot``
forms: no train cell measures it yet.

What a visit reads (``_col_tile``, from ``(k, n, itemsize)`` alone): its expert's
whole ``[k, n]`` matrix where that is within 4 MiB, else column tiles ``[k,
tn]`` of the widest kind within 4 MiB, and never narrower than 512 columns of
bf16 (a kilobyte a row), whatever ``k``: at a hidden size of 6,144 or 7,168 that
floor decides (blocks of 6 and 7 MiB, four passes over the visits where the
budget alone gave ``[hidden, 256]`` and eight). The kernel is bound by its
weights' bytes, and a grid step costs it ~0.65 us that the DMA does not hide,
so fewer, larger blocks read faster; how far that was taken, and why not to the
whole matrix, is in PERF.md section 6 (PR 63; ``tools/moe_kernels.py`` is the
sweep).
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator.device import on_tpu

# The kernel's name in a device trace (``pallas_call(name=...)`` names the Mosaic
# custom call), beside the other ``dstpu_*`` names.
MOE_GMM = "dstpu_moe_gmm"
# one expert's weight block held in VMEM (twice: the pipeline's two buffers),
# unless the floor on a block's rows asks for more (``_col_tile``)
_WEIGHT_BLOCK_BYTES = 4 << 20
_MIN_ROW_BYTES = 1024
_VMEM_LIMIT_BYTES = 48 << 20


def row_tile(m_rows: int, itemsize: int) -> int:
    """Rows of one tile, from the shapes alone: the MXU's 128, or all the rows
    of a smaller problem rounded up to the dtype's sublane tile (8 rows of 4
    bytes, 16 of 2). One rule serves both regimes of a serving step because
    the weights' bytes bound both: on the v5e a decode step's 256 rows over 64
    experts of [2048, 1024] take 392 us a matmul under tiles of 16 and 374
    under tiles of 128 (717 GB/s of weights), a chunk step's 8,448 rows 1,238
    and 609 (PERF.md, PR 25): a tile's idle rows cost nothing beside its
    expert's 4 MB, a visit does."""
    lo = 8 * max(1, 4 // itemsize)
    return min(128, -(-m_rows // lo) * lo)


def _col_tile(k: int, n: int, itemsize: int) -> int:
    """Columns of the weight block ``[k, tn]`` a visit reads, from the shape
    alone: the whole of ``n`` where ``[k, n]`` fits the block budget, else the
    widest multiple of 128 that divides ``n`` and does, and never under
    ``_MIN_ROW_BYTES`` a row (512 columns of bf16) whatever ``k``, where ``n`` has
    such a divisor.

    The kernel alone on the v5e (``tools/moe_kernels.py``; PERF.md section 6, PR
    63) takes ``steps x ~0.65 us + bytes / ~760 GB/s``: the pipeline issues the
    next block's DMA from inside a grid step, so a step's own cost is never
    hidden, and a column tile multiplies the steps (``n // tn`` passes over the
    visits, skipped ones included). At 16 experts of ``[6144, 2048]`` the budget
    alone gave 256 columns: 623 us a call on a decode step's groups and 753 with
    a 512-token chunk, against 585 and 685 at 512 (K-EXAONE ``gen_tok_s`` +0.75
    to +2.7%). Wider still read a little faster alone (whole: 587 and 659) but
    the whole-matrix block under a 96 MiB VMEM limit cost MiMo-V2-Flash 12% of
    its ``gen_tok_s`` in the cell, so the floor stops at the narrowest block the
    cells already ran: every shape it does not reach keeps the block it had."""
    if n % 128 or k * n * itemsize <= _WEIGHT_BLOCK_BYTES:
        return n
    floor = min(n, _MIN_ROW_BYTES // itemsize)
    tn = max(floor, (_WEIGHT_BLOCK_BYTES // (k * itemsize)) // 128 * 128)
    while n % tn:
        tn -= 128
    return tn


def tile_visits(group_sizes, tm: int, xp=jnp):
    """(first tile, visits) of each group over rows sorted by group: a group
    visits every ``tm``-row tile it has a row in. ``xp`` is ``jnp`` in the
    program and ``numpy`` where the host counts what the kernel covered."""
    ends = xp.cumsum(group_sizes, axis=-1)
    first = (ends - group_sizes) // tm
    visits = xp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    return first, visits


def computed_rows(counts: np.ndarray, tm: int) -> int:
    """Rows the kernel's tiles covered for these ``[..., E]`` group sizes (one
    layer call a row): ``tm`` for every visit."""
    return int(tile_visits(np.asarray(counts, np.int64), tm, np)[1].sum()) * tm


def _gmm_kernel(offsets, group_of, tile_of, n_visits, layer, x_ref, w_ref, o_ref, *, tm):
    del layer  # the weight block's index map reads it
    v = pl.program_id(1)

    @pl.when(v < n_visits[0])
    def _():
        g = group_of[v]
        rows = tile_of[v] * tm + jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
        mine = (rows >= offsets[g]) & (rows < offsets[g + 1])
        acc = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)
        # rows of the tile that belong to an earlier group keep what that
        # group's visit stored (the block stays in VMEM between visits)
        o_ref[...] = jnp.where(mine, acc, o_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _gmm_pallas(lhs, rhs, group_sizes, layer, tm: int, interpret: bool, tn: Optional[int] = None):
    """``tn``: the weight block's columns; ``_col_tile``'s for every caller but
    a test or ``tools/moe_kernels.py``."""
    m, k = lhs.shape
    _, E, _, n = rhs.shape
    itemsize = rhs.dtype.itemsize
    tn = tn or _col_tile(k, n, itemsize)
    # once a traced call, into the set-up record: which block its visits read
    from deepspeed_tpu.observability.setup_record import get_setup_record

    record = get_setup_record()
    now = record.now()
    record.add("moe_gmm.block", now, now, k=k, n=n, tn=tn, run_bytes=(k * n if tn == n else tn) * itemsize)
    V = m // tm + E - 1  # every tile once, and once more for each boundary inside one
    first, visits = tile_visits(group_sizes.astype(jnp.int32), tm)
    vend = jnp.cumsum(visits)
    total = vend[-1]
    # visits past the last real one repeat it (no new block is fetched) and are skipped
    v = jnp.minimum(jnp.arange(V, dtype=jnp.int32), jnp.maximum(total - 1, 0))
    group_of = jnp.minimum(jnp.searchsorted(vend, v, side="right"), E - 1).astype(jnp.int32)
    tile_of = (first[group_of] + v - (vend[group_of] - visits[group_of])).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(group_sizes.astype(jnp.int32))])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n // tn, V),
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, v, off, g, t, nv, ly: (t[v], 0)),
            pl.BlockSpec((None, None, k, tn), lambda j, v, off, g, t, nv, ly: (ly[0], g[v], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda j, v, off, g, t, nv, ly: (t[v], j)),
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            # column tiles are independent; the visits of one row tile follow
            # each other and share its output block
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name=MOE_GMM,
    )(offsets, group_of, tile_of, total.reshape(1).astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), lhs, rhs)


def _gmm_ragged(lhs, rhs, group_sizes, layer):
    w = jax.lax.dynamic_index_in_dim(rhs, layer, 0, keepdims=False)
    return jax.lax.ragged_dot(lhs, w, group_sizes.astype(jnp.int32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _gmm_kernel_vjp(lhs, rhs, group_sizes, layer, tm, interpret):
    return _gmm_pallas(lhs, rhs, group_sizes, layer, tm, interpret)


def _gmm_fwd(lhs, rhs, group_sizes, layer, tm, interpret):
    return _gmm_pallas(lhs, rhs, group_sizes, layer, tm, interpret), (lhs, rhs, group_sizes, layer)


def _gmm_bwd(tm, interpret, res, g):
    lhs, rhs, group_sizes, layer = res
    live = jnp.arange(lhs.shape[0]) < jnp.sum(group_sizes)
    g = jnp.where(live[:, None], g, 0)  # rows of no group were never written
    _, vjp = jax.vjp(lambda a, b: _gmm_ragged(a, b, group_sizes, layer), lhs, rhs)
    d_lhs, d_rhs = vjp(g)
    return (d_lhs, d_rhs, np.zeros(group_sizes.shape, jax.dtypes.float0),
            np.zeros(jnp.shape(layer), jax.dtypes.float0))


_gmm_kernel_vjp.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs, rhs, group_sizes, tm: int, impl: Optional[str] = None, layer=None):
    """``out[r] = lhs[r] @ rhs[group of r]`` for rows sorted by group.

    lhs ``[m, k]`` with ``m`` a multiple of ``tm``; rhs ``[E, k, n]``, or with
    ``layer`` (an index, traced or not) the whole stack ``[L, E, k, n]`` of
    which the kernel reads layer ``layer``'s blocks in place: a slice in
    front of a custom call is a copy, 805 MB a layer at OLMoE's widths
    (PERF.md, PR 25). group_sizes ``[E]`` int32 whose sum may be under ``m``:
    the rows behind the last group belong to none, and what the result holds
    there is undefined (the caller masks them). ``impl``: ``"kernel"`` (the
    Pallas kernel; on a TPU), ``"interpret"`` (the same kernel interpreted, for
    tests on the CPU) or ``"ragged"`` (``jax.lax.ragged_dot``); None picks by
    the platform."""
    impl = impl or ("kernel" if on_tpu() else "ragged")
    if layer is None:
        rhs, layer = rhs[None], 0
    if impl == "ragged":
        return _gmm_ragged(lhs, rhs, group_sizes, layer)
    return _gmm_kernel_vjp(lhs, rhs, group_sizes, jnp.asarray(layer, jnp.int32), tm, impl == "interpret")


def kept_groups(config, choose, biased: bool):
    """The group step of a grouped router (``moe_n_group`` > 1): ``choose`` [t,
    E] are the scores a token's experts are chosen on, E experts in
    ``moe_n_group`` groups of consecutive numbers. A group's score is its
    LARGEST entry where the router has no selection bias (DeepSeek-V2's
    group_limited_greedy, vLLM's grouped_topk) and the sum of its two largest
    where it has one (DeepseekV3TopkRouter, on score + bias). Returns [t,
    moe_n_group] bool: the ``moe_topk_group`` best groups of each token."""
    t, E = choose.shape
    G = config.moe_n_group
    by_group = choose.reshape(t, G, E // G)
    score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1) if biased else jnp.max(by_group, axis=-1)
    best = jax.lax.top_k(score, config.moe_topk_group)[1]
    return jnp.sum(jax.nn.one_hot(best, G, dtype=jnp.int32), axis=1) > 0


def route(config, logits, live=None, bias=None):
    """Router as published. logits ``[t, E]`` float32 over every expert of the
    layer, held here or not. ``moe_score`` "softmax": the k most probable,
    renormalised where the model says so; with a ``bias`` (LongcatFlashTopkRouter)
    the k CHOSEN on ``probability + bias`` and weighted by the probability
    alone, and times ``moe_routed_scale`` where that is not 1. "sigmoid"
    (DeepseekV3TopkRouter):
    scores ``sigmoid(logits)``, the k CHOSEN on ``score + bias`` (``bias
    [E]``: the checkpoint's e_score_correction_bias; None for a router without
    one) and weighted by the score alone, renormalised, times
    ``moe_routed_scale``; with ``moe_n_group`` > 1 the k are taken inside the
    token's ``moe_topk_group`` best groups (``kept_groups``; an expert outside
    them is out of the choice whatever its score: -inf where the published
    code writes 0.0, which differs only if fewer than k kept scores are
    positive). Returns (gate values ``[t, k]`` float32, expert ids ``[t, k]``,
    aux loss, the kept groups ``[t, moe_n_group]`` bool: None for a router
    without groups)."""
    E, k = logits.shape[-1], config.moe_top_k
    kept = None
    if config.moe_score == "sigmoid":
        probs = jax.nn.sigmoid(logits.astype(jnp.float32))
        choose = probs if bias is None else probs + bias.astype(jnp.float32)
        if config.moe_n_group > 1:
            kept = kept_groups(config, choose, bias is not None)
            choose = jnp.where(jnp.repeat(kept, E // config.moe_n_group, axis=1), choose, -jnp.inf)
        top_e = jax.lax.top_k(choose, k)[1]
        top_p = jnp.take_along_axis(probs, top_e, axis=-1)
        if config.moe_norm_topk_prob:
            top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-20)
        top_p = top_p * config.moe_routed_scale
    else:
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        if bias is None:
            top_p, top_e = jax.lax.top_k(probs, k)
        else:
            top_e = jax.lax.top_k(probs + bias.astype(jnp.float32), k)[1]
            top_p = jnp.take_along_axis(probs, top_e, axis=-1)
        if config.moe_norm_topk_prob:
            top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)
        if config.moe_routed_scale != 1.0:
            top_p = top_p * config.moe_routed_scale
    # load-balancing loss of topkgating (sharded_moe.py): E/k * <probs_e> . <share_e>
    chosen = jnp.sum(jax.nn.one_hot(top_e, E, dtype=jnp.float32), axis=1)  # [t, E]
    w = jnp.ones(probs.shape[0], jnp.float32) if live is None else live.astype(jnp.float32)
    n = jnp.maximum(jnp.sum(w), 1.0)
    me = jnp.sum(probs * w[:, None], axis=0) / n
    ce = jnp.sum(chosen * w[:, None], axis=0) / n
    return top_p, top_e, jnp.sum(me * ce) * E / k, kept


def experts_grouped(config, lp, tokens, logits, live=None, layer=None
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The routed experts of one layer on ``tokens [t, h]`` under the router's
    ``logits [t, experts of the layer]``. ``live [t]`` bool marks the slots that
    hold a token. With ``layer``, the expert weights in ``lp`` are the whole
    stacks ``[L, E, ...]`` (see ``grouped_matmul``). ``E`` is the experts HELD
    here; the logits span more under an expert share. Returns (out ``[t, h]``:
    the held experts' part of the sum, aux loss, ``[E]`` int32 rows routed to
    each held expert; a grouped router appends one entry: the live tokens
    whose kept groups include one this share holds an expert of; a router with
    identity experts appends one: the live tokens' identity pairs)."""
    t, h = tokens.shape
    E, k = config.n_experts, config.moe_top_k
    top_p, top_e, aux, kept = route(config, logits, live, lp.get("router_bias"))

    tm = row_tile(t * k, tokens.dtype.itemsize)
    m = -(-t * k // tm) * tm
    # an expert's number among those held here; outside [0, E): held elsewhere
    pair_e = top_e.astype(jnp.int32) - config.moe_expert_shard * E
    here = (pair_e >= 0) & (pair_e < E)
    if live is not None:
        here = here & live[:, None]
    pair_e = jnp.where(here, pair_e, E)  # behind the last group
    pair_e = jnp.pad(pair_e.reshape(t * k), (0, m - t * k), constant_values=E)
    order = jnp.argsort(pair_e, stable=True)
    counts = jnp.sum(jax.nn.one_hot(pair_e, E + 1, dtype=jnp.int32), axis=0)[:E]
    rows = tokens[jnp.minimum(order // k, t - 1)]

    gmm = functools.partial(grouped_matmul, group_sizes=counts, tm=tm, layer=layer)
    up = gmm(rows, lp["w_up"])
    if config.activation in ("swiglu", "geglu"):
        gate = gmm(rows, lp["w_gate"])
        act = (jax.nn.gelu(gate) if config.activation == "geglu" else jax.nn.silu(gate)) * up
    else:
        act = jax.nn.gelu(up, approximate=config.activation != "gelu_exact")
    y = gmm(act, lp["w_down"])

    p_sorted = jnp.pad(top_p.reshape(t * k), (0, m - t * k))[order]
    routed = jnp.arange(m) < jnp.sum(counts)
    y = jnp.where(routed[:, None], y.astype(jnp.float32) * p_sorted[:, None], 0.0)
    back = jnp.argsort(order)[: t * k]  # where each (token, choice) pair went
    out = jnp.sum(y[back].reshape(t, k, h), axis=1)
    if config.moe_zero_experts:
        # the identity pairs: the sum of their gates times the input, on every chip
        zero = top_e >= config.routed_experts
        if live is not None:
            zero = zero & live[:, None]
        out = out + jnp.sum(jnp.where(zero, top_p, 0.0), axis=1, keepdims=True) * tokens.astype(jnp.float32)
        counts = jnp.concatenate([counts, jnp.sum(zero, dtype=jnp.int32)[None]])
    if kept is not None:
        # the groups this share's experts lie in (one, where a chip holds a group)
        per = config.router_width // config.moe_n_group
        first = config.moe_expert_shard * E
        hit = jnp.any(kept[:, first // per: (first + E - 1) // per + 1], axis=1)
        if live is not None:
            hit = hit & live
        counts = jnp.concatenate([counts, jnp.sum(hit, dtype=jnp.int32)[None]])
    return out.astype(tokens.dtype), aux, counts
