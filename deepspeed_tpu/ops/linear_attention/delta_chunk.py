"""A prompt chunk's delta rule as ONE Pallas kernel: what ``gdn_chunked`` and
``kda_chunked`` compute (their ``"jnp"`` bodies are the specification and the
kernel's second oracle), with a head's state in VMEM from a row's first
64-token chunk to its last.

A grid over (chunk row, block of value heads, chunk), the chunk axis last and
sequential. A program reads a chunk's q, k, v, decay and beta for its heads as
the adapters leave them (``[r, t, heads, d]``: a BlockSpec picks a head's 128
lanes, nothing is re-laid in HBM), and writes the chunk's output. The state
comes in at a row's first chunk, stays in a VMEM scratch TRANSPOSED
(``[dv, dk]``: a decay a key channel then scales it along its lanes) and goes
out after the last. Nothing else of a chunk goes back to HBM.

Inside a chunk, per head, on ``[64, 128]`` tiles in float32 with every product
at ``Precision.HIGHEST``:

  * ``G``, the in-chunk cumulative decay;
  * the pair matrices ``kk_ij = sum_c (beta k)_ic k_jc exp(G_ic - G_jc)`` and
    ``qk`` (``q`` in place of ``beta k``), ``j <= i``. The DECAY'S SHAPE selects
    how: one decay a head (Gated DeltaNet, ``g [r, t, H]``) is one product and a
    ``[64, 64]`` ``exp`` of differences; a decay a key channel (Kimi Delta
    Attention, ``g [r, t, H, dk]``) takes ``kda._pair_matrices``' sub-blocks:
    on the diagonal sub-blocks the difference BEFORE ``exp``, a column at a time
    (``exp`` shared by both matrices), off the diagonal a product of two factors
    both referred to the row in front of the sub-block. No exponential of a
    positive number is taken anywhere;
  * what the incoming state holds for the chunk's keys and queries, one product;
  * the triangular system, ``v_new = (I - A)^-1 (beta v - beta k exp(G) S)``
    with ``A = -tril(kk, -1)``: the 16-row diagonal blocks' inverses by doubling,
    ``prod_j (I + D^(2^j))``, then forward substitution down the block rows
    (``_solve``; the XLA bodies double the whole ``[64, 64]`` matrix and apply
    it to ``beta v`` and ``beta k exp(G)`` apart: the same sums in another
    order, fewer and smaller products);
  * the output and the state's update.

A token with ``g = 0`` and ``beta = 0`` leaves the state as it was. A
``pallas_call`` has no gradient: training goes through the ``"jnp"`` bodies.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.linear_attention.gated_delta import _HI, CHUNK

# the kernel's names in a device trace, beside ``GDN_DECODE`` / ``KDA_DECODE``
GDN_CHUNK = "dstpu_gdn_chunk"
KDA_CHUNK = "dstpu_kda_chunk"
SUB = 16          # tokens of a diagonal sub-block (KDA)
_GRANULE = 8      # rows of a float32 register: the diagonal's unit of work
_BLOCK = 16       # rows of a diagonal block of the triangular solve
# value heads a program, taken through every stage TOGETHER (``_each``), by the
# decay's shape. A decay a key channel: 2 / 4 / 8 heads read 725 / 590 / 535 us
# on two rows of 512 tokens, but a step program lowers every layer's kernel
# apart on the chip's host and the diagonal's columns are unrolled: 4 heads
# cost a warm set-up 18 s of lowering over nine layers in three programs (13% of
# the Kimi cell's `setup_s`, over its bound), 2 about half. A decay a head has
# no such columns: 4 (my chip runs, PR 57)
_HEADS = {True: 2, False: 4}


def _dot(a, b, dims=((1,), (0,))):
    """``a . b`` over ``dims`` (default: a's columns with b's rows), float32."""
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))   # a [m, k] . b [n, k]^T
_TN = ((0,), (0,))   # a [k, m]^T . b [k, n]


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _pairs_by_head(a, q, k, g):
    """One decay a head. a, q, k ``[C, dk]``, g ``[C, 1]``. Returns (kk, qk
    ``[C, C]``, diagonal kept, zero above it; G ``[C, 1]``)."""
    C = k.shape[0]
    row, col = _iota((C, C), 0), _iota((C, C), 1)
    g_row = jnp.sum(jnp.where(row == col, g, 0.0), axis=0, keepdims=True)     # g along the lanes
    G = jnp.sum(jnp.where(col <= row, g_row, 0.0), axis=1, keepdims=True)     # [C, 1]
    G_row = jnp.sum(jnp.where(row <= col, g, 0.0), axis=0, keepdims=True)     # [1, C]
    # exp only of what is kept: above the diagonal the difference is positive
    decay = jnp.exp(jnp.where(col <= row, G - G_row, -jnp.inf))
    pair = _dot(jnp.concatenate([a, q], axis=0), k, _NT)
    return pair[:C] * decay, pair[C:] * decay, G


def _pairs_by_channel(a, q, k, g):
    """A decay a key channel. a, q, k, g ``[C, dk]``. Returns as ``_pairs_by_head``
    (G ``[C, dk]``)."""
    C, dk = k.shape
    sub = SUB
    G = _dot((_iota((C, C), 1) <= _iota((C, C), 0)).astype(jnp.float32), g)
    lane, rows = _iota((_GRANULE, C), 1), _iota((_GRANULE, dk), 0)
    kk, qk = [], []
    for lo in range(0, C, sub):
        if lo:
            # the columns in front of the sub-block: both factors referred to the
            # row in front of it, so every exponent is <= 0
            ref = G[lo - 1 : lo]
            left = jnp.concatenate(
                [k[:lo] * jnp.exp(ref - G[:lo]), jnp.zeros((C - lo, dk), jnp.float32)], axis=0)
            e = jnp.exp(G[lo : lo + sub] - ref)
            off = _dot(jnp.concatenate([a[lo : lo + sub] * e, q[lo : lo + sub] * e], axis=0), left, _NT)
        for r0 in range(lo, lo + sub, _GRANULE):
            a_s, q_s, G_s = (x[r0 : r0 + _GRANULE] for x in (a, q, G))
            if lo:
                m_kk, m_qk = off[r0 - lo : r0 - lo + _GRANULE], off[sub + r0 - lo : sub + r0 - lo + _GRANULE]
            else:
                m_kk = m_qk = jnp.zeros((_GRANULE, C), jnp.float32)
            # the sub-block's own columns up to these rows' last: the difference
            # first, exp of what is kept alone, one exp for both matrices
            for j in range(lo, r0 + _GRANULE):
                diff = G_s - G[j : j + 1]
                if j >= r0:
                    diff = jnp.where(rows >= j - r0, diff, -jnp.inf)
                kd, here = k[j : j + 1] * jnp.exp(diff), lane == j
                m_kk = jnp.where(here, jnp.sum(a_s * kd, axis=1, keepdims=True), m_kk)
                m_qk = jnp.where(here, jnp.sum(q_s * kd, axis=1, keepdims=True), m_qk)
            kk.append(m_kk)
            qk.append(m_qk)
    return jnp.concatenate(kk, axis=0), jnp.concatenate(qk, axis=0), G


def _each(f, *lists):
    """``f`` on every head's operands in turn: the program's heads go through a
    stage together, so that the chip's scheduler finds one head's product beside
    another's and fills the wait for each (a head at a time, the chain of small
    dependent products ran at a third of this speed)."""
    return [f(*xs) for xs in zip(*lists)]


def _solve(As, Ws):
    """``(I - A)^-1 W`` for each head's strictly lower ``A [C, C]`` and ``W [C,
    n]``, by blocks of ``_BLOCK`` rows: the diagonal blocks' inverses by doubling,
    ``(I - D)^-1 = prod_j (I + D^(2^j))``, all of a head's at once PACKED side by
    side in one ``[_BLOCK, C]`` tile (a product with the block-diagonal spread of
    the other factor squares every block: 16 rows through the matrix unit where
    the whole ``A`` would push 64); then forward substitution down the block
    rows, ``X_i = T_i (W_i + sum_{j<i} A_ij X_j)``."""
    C, b = As[0].shape[0], _BLOCK
    n = C // b
    same = _iota((C, C), 0) // b == _iota((C, C), 1) // b            # the diagonal blocks
    eye = (_iota((b, C), 1) % b == _iota((b, C), 0)).astype(jnp.float32)
    spread = lambda Q: jnp.where(same, jnp.concatenate([Q] * n, axis=0), 0.0)  # noqa: E731
    Ds = _each(lambda A: jnp.where(same, A, 0.0), As)
    Ps = _each(lambda D: functools.reduce(jnp.add, [D[i : i + b] for i in range(0, C, b)]), Ds)
    Ts = _each(lambda P: P + eye, Ps)
    for _ in range((b - 1).bit_length() - 1):
        Ps = _each(lambda P: _dot(P, spread(P)), Ps)
        Ts = _each(lambda T, P: T + _dot(T, spread(P)), Ts, Ps)
    Ts = _each(spread, Ts)
    TWs, TOs = _each(_dot, Ts, Ws), _each(lambda T, A, D: _dot(T, A - D), Ts, As, Ds)
    Xs = _each(lambda TW: TW[:b], TWs)
    for lo in range(b, C, b):
        zeros = jnp.zeros((C - lo, Ws[0].shape[1]), jnp.float32)
        Xs = _each(lambda X, TW, TO: jnp.concatenate(
            [X, TW[lo : lo + b] + _dot(TO[lo : lo + b], jnp.concatenate([X, zeros], axis=0))], axis=0),
            Xs, TWs, TOs)
    return Xs


def _heads(qs, ks, vs, gs, betas, Sts, by_channel):
    """A program's heads, one chunk; per head q, k ``[C, dk]``, v ``[C, dv]``, g
    ``[C, dk]`` or ``[C, 1]``, beta ``[C, 1]``, St ``[dv, dk]`` (the state
    transposed). Returns (each head's o ``[C, dv]``, its state after, transposed)."""
    C = vs[0].shape[0]
    strict = _iota((C, C), 1) < _iota((C, C), 0)
    As = _each(jnp.multiply, ks, betas)                              # beta k
    pairs = _each(_pairs_by_channel if by_channel else _pairs_by_head, As, qs, ks, gs)
    kks, qks, Gs = zip(*pairs)
    eGs = _each(jnp.exp, Gs)
    # what the state holds for the chunk's keys and queries, in one product;
    # then v_new = (I - A)^-1 (beta v - beta k exp(G) S): the solve on 128 lanes
    boths = _each(lambda a, q, eG, St: _dot(jnp.concatenate([a * eG, q * eG], axis=0), St, _NT),
                  As, qs, eGs, Sts)                                  # [2 C, dv]
    v_news = _solve(_each(lambda kk: -jnp.where(strict, kk, 0.0), kks),
                    _each(lambda v, beta, both: v * beta - both[:C], vs, betas, boths))
    os = _each(lambda both, qk, v_new: both[C:] + _dot(qk, v_new), boths, qks, v_news)

    def update(k, G, St, v_new):
        last = G[C - 1 :]                                            # [1, dk] or [1, 1]
        k_out = k * jnp.exp(last - G)                                # what a token leaves at the chunk's end
        return St * jnp.exp(last) + _dot(v_new, k_out, _TN)

    return os, _each(update, ks, Gs, Sts, v_news)


def _chunk_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s0_ref, o_ref, s_ref, st_ref, *, hb, rep,
                  by_channel):
    c = pl.program_id(2)
    dk, dv = s0_ref.shape[1:]

    @pl.when(c == 0)
    def _():
        for h in range(hb):
            st_ref[h] = s0_ref[h].T

    key = lambda ref: [ref[:, h // rep * dk : (h // rep + 1) * dk] for h in range(hb)]  # noqa: E731
    wide = lambda ref, d: [ref[:, h * d : (h + 1) * d] for h in range(hb)]  # noqa: E731
    os, Sts = _heads(key(q_ref), key(k_ref), wide(v_ref, dv), wide(g_ref, dk if by_channel else 1),
                     wide(b_ref, 1), [st_ref[h] for h in range(hb)], by_channel)
    for h in range(hb):
        o_ref[:, h * dv : (h + 1) * dv] = os[h]
        st_ref[h] = Sts[h]

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        for h in range(hb):
            s_ref[h] = st_ref[h].T


def _heads_a_program(nv: int, rep: int, by_channel: bool) -> int:
    """Value heads a program: ``_HEADS``, or fewer, where that divides the heads
    and holds whole groups of a key head (or a part of one group)."""
    return next(hb for hb in (_HEADS[by_channel], 2, 1)
                if nv % hb == 0 and (hb % rep == 0 or rep % hb == 0))


# jitted so that a step program's layers share ONE trace and one lowering of the
# body a shape (thousands of small operations: unjitted, nine layers in three
# programs added 44 s of tracing and 18 s of lowering to a warm set-up)
@functools.partial(jax.jit, static_argnames=("interpret", "chunk"))
def delta_chunk(q, k, v, g, beta, state, interpret: bool, chunk: int = CHUNK):
    """q, k ``[r, t, nk, dk]`` (``qk_heads``), v ``[r, t, nv, dv]``, beta
    ``[r, t, nv]``, state ``[r, nv, dk, dv]`` float32; g ``[r, t, nv]`` (a decay
    a head, under the name ``dstpu_gdn_chunk``) or ``[r, t, nv, dk]`` (a decay
    a key channel, the same body under ``dstpu_kda_chunk``). A key head serves
    ``nv // nk`` consecutive value heads. Returns (o ``[r, t, nv, dv]`` float32,
    the states after). ``t`` short of whole chunks is padded with ``g = beta =
    0``."""
    f32 = jnp.float32
    r, t, nk, dk = q.shape
    nv, dv = v.shape[-2:]
    by_channel = g.ndim == 4
    rep = nv // nk
    hb = _heads_a_program(nv, rep, by_channel)
    J, kb = nv // hb, max(1, hb // rep)
    pad = -t % chunk
    T = t + pad

    def flat(a):  # [r, t, heads, d] -> [r, T, heads * d]: a head is 128 lanes of a token's row
        a = jnp.pad(a.astype(f32), ((0, 0), (0, pad), (0, 0), (0, 0)))
        return a.reshape(r, T, -1)

    def columns(a):  # [r, t, nv] -> [r, J, T, hb]: a number a token a head, down a column
        a = jnp.pad(a.astype(f32), ((0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(a.reshape(r, T, J, hb), 2, 1)

    keys = pl.BlockSpec((None, chunk, kb * dk), lambda i, j, c: (i, c, j * hb // rep // kb))
    wide = lambda d: pl.BlockSpec((None, chunk, hb * d), lambda i, j, c: (i, c, j))  # noqa: E731
    column = pl.BlockSpec((None, None, chunk, hb), lambda i, j, c: (i, j, c, 0))
    st = pl.BlockSpec((None, hb, dk, dv), lambda i, j, c: (i, j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, hb=hb, rep=rep, by_channel=by_channel),
        grid=(r, J, T // chunk),
        in_specs=[keys, keys, wide(dv), wide(dk) if by_channel else column, column, st],
        out_specs=[wide(dv), st],
        out_shape=[jax.ShapeDtypeStruct((r, T, nv * dv), f32),
                   jax.ShapeDtypeStruct((r, nv, dk, dv), f32)],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=KDA_CHUNK if by_channel else GDN_CHUNK,
    )(flat(q), flat(k), flat(v), flat(g) if by_channel else columns(g), columns(beta),
      state.astype(f32))
    return o.reshape(r, T, nv, dv)[:, :t], state
