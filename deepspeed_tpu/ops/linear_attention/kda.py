"""Kimi Delta Attention (Kimi Linear, arXiv:2510.26692): the gated delta rule
of ``gated_delta.py`` with a decay for every KEY CHANNEL in place of one a head.

Per head the cache is a state ``S [dk, dv]`` in float32 and a token does

  S     <- exp(g)[:, None] * S          g [dk] <= 0: a decay a key channel
  delta  = (v - S^T k) * beta
  S     <- S + k delta^T
  o      = S^T q

with q and k L2-normalised and q scaled (``gated_delta.qk_heads``). With all of
a head's channels given one decay this IS ``gdn_recurrent``. A token with
``g = 0`` and ``beta = 0`` leaves the state as it was.

  * ``kda_recurrent``: token by token (``lax.scan``): the oracle.
  * ``kda_chunked``: chunks of 64 tokens, the in-chunk part as matrix products,
    the state carried chunk to chunk: what a prompt chunk runs. On a TPU the
    Pallas kernel ``dstpu_kda_chunk`` (``delta_chunk.py``: a head's state stays
    in VMEM across a row's chunks); elsewhere, as the kernel's second oracle and
    where a gradient is taken, plain XLA. NO EXPONENTIAL OF A POSITIVE NUMBER is
    taken anywhere: with ``G`` the in-chunk cumulative sum of ``g`` a channel,
    the pair matrix ``M_ij = sum_c a_ic k_jc exp(G_ic - G_jc)`` (j <= i) cannot
    be split as ``(a exp(G)) (k exp(-G))^T``: ``A_log`` up to ln 16 under a
    softplus decays a channel by e^88 within ten tokens, and ``exp(-G)``
    overflows float32. So a chunk is cut in sub-blocks of 16 tokens: on the
    diagonal sub-blocks the differences are taken BEFORE ``exp`` (16 x 16 x dk
    a head), and an off-diagonal sub-block is a product of two factors both
    referred to the last row in front of its rows, ``(a_i exp(G_i - G_r))
    . (k_j exp(G_r - G_j))`` with ``j <= r < i``: every exponent <= 0, and a
    factor that underflows to zero stands for a product that is zero.
  * ``kda_decode``: one token a row over a POOL of states in place: on a TPU
    ``gated_delta``'s kernel body under the name ``dstpu_kda_decode``, its decay
    a ``[dk, heads]`` column as its key is; elsewhere gather / update / scatter.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.accelerator.device import on_tpu
from deepspeed_tpu.ops.linear_attention.gated_delta import _HI, CHUNK, _decode_pallas

SUB = 16  # tokens of a sub-block: the pair tensor kept is [SUB, SUB, dk] a head


def kda_recurrent(q, k, v, g, beta, state):
    """Token by token. q, k ``[r, t, H, dk]`` (``qk_heads``), v ``[r, t, H, dv]``,
    g ``[r, t, H, dk]`` (<= 0), beta ``[r, t, H]``, state ``[r, H, dk, dv]``
    float32. Returns (o ``[r, t, H, dv]`` float32, the state after)."""
    f32 = jnp.float32

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = S * jnp.exp(g_t)[..., None]
        mem = jnp.einsum("rhkv,rhk->rhv", S, k_t, precision=_HI)
        delta = (v_t - mem) * b_t[..., None]
        S = S + k_t[..., :, None] * delta[..., None, :]
        return S, jnp.einsum("rhkv,rhk->rhv", S, q_t, precision=_HI)

    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta))
    state, o = jax.lax.scan(token, state.astype(f32), xs)
    return jnp.moveaxis(o, 0, 1), state


def _pair_matrices(rows, k, G, sub):
    """``M[a]_ij = sum_c rows[a]_ic k_jc exp(G_ic - G_jc)`` for ``j <= i`` (zero
    above the diagonal), for each of the stacked ``rows [A, ..., C, dk]``; k, G
    ``[..., C, dk]``, G non-increasing along C. Returns ``[A, ..., C, C]``."""
    C, dk = k.shape[-2:]
    n = C // sub
    mm = functools.partial(jnp.matmul, precision=_HI)
    blocks = lambda a: a.reshape(a.shape[:-2] + (n, sub, dk))  # noqa: E731
    rb, kb, Gb = blocks(rows), blocks(k), blocks(G)
    # diagonal sub-blocks: the difference first, exp of what is kept alone
    low = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    diff = jnp.where(low, Gb[..., :, None, :] - Gb[..., None, :, :], -jnp.inf)
    diag = jnp.sum(rb[..., :, None, :] * (kb[..., None, :, :] * jnp.exp(diff)), axis=-1)
    out = []
    for i in range(n):
        parts = [diag[..., i, :, :]]
        if i:
            ref = Gb[..., i - 1, -1:, :]                              # the row in front: [.., 1, dk]
            left = k[..., : i * sub, :] * jnp.exp(ref - G[..., : i * sub, :])
            parts.insert(0, mm(rb[..., i, :, :] * jnp.exp(Gb[..., i, :, :] - ref),
                               jnp.swapaxes(left, -1, -2)))
        if i < n - 1:
            parts.append(jnp.zeros(diag.shape[:-3] + (sub, C - (i + 1) * sub), diag.dtype))
        out.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(out, axis=-2)


def kda_chunked(q, k, v, g, beta, state, chunk: int = CHUNK, sub: int = SUB,
                impl: Optional[str] = None):
    """``kda_recurrent``'s result in chunks of ``chunk`` tokens (sub-blocks of
    ``sub``): the delta rule's triangular system solved as ``gdn_chunked`` solves
    it, the state from chunk to chunk. ``t`` is padded to whole chunks with
    ``g = beta = 0``. ``impl`` as ``gdn_chunked``'s (the kernel sets its own
    sub-blocks)."""
    impl = impl or ("kernel" if on_tpu() else "jnp")
    if impl != "jnp":
        from deepspeed_tpu.ops.linear_attention.delta_chunk import delta_chunk

        return delta_chunk(q, k, v, g, beta, state, impl == "interpret", chunk)
    f32 = jnp.float32
    r, t, H, dv = v.shape
    pad = -t % chunk
    N = (t + pad) // chunk

    def chunks(a):  # [r, t, H, ...] -> [r, H, N, chunk, ...]
        a = jnp.pad(a.astype(f32), ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape((r, N, chunk) + a.shape[2:]), 3, 1)

    q, k, v, g, beta = (chunks(a) for a in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-2)                                        # [r, H, N, C, dk]
    k_beta, v_beta = k * beta[..., None], v * beta[..., None]
    mm = functools.partial(jnp.matmul, precision=_HI)
    kk, qk = _pair_matrices(jnp.stack([k_beta, q]), k, G, sub)       # qk: diagonal kept
    A = -jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool), -1), kk, 0.0)
    eye = jnp.eye(chunk, dtype=f32)
    T, P = eye + A, A
    for _ in range(max(0, (chunk - 1).bit_length() - 1)):
        P = mm(P, P)
        T = mm(T, eye + P)
    eG = jnp.exp(G)
    v_solved = mm(T, v_beta)
    k_cum = mm(T, k_beta * eG)
    last = G[..., -1:, :]                                             # [r, H, N, 1, dk]
    k_out = k * jnp.exp(last - G)                                     # what a token leaves at the chunk's end

    def one(S, xs):
        q_i, ko_i, vs_i, kc_i, qk_i, eG_i = xs
        v_new = vs_i - mm(kc_i, S)
        o_i = mm(q_i * eG_i, S) + mm(qk_i, v_new)
        S = S * jnp.swapaxes(eG_i[..., -1:, :], -1, -2) + mm(jnp.swapaxes(ko_i, -1, -2), v_new)
        return S, o_i

    xs = tuple(jnp.moveaxis(a, 2, 0) for a in (q, k_out, v_solved, k_cum, qk, eG))
    state, o = jax.lax.scan(one, state.astype(f32), xs)             # o [N, r, H, C, dv]
    o = jnp.moveaxis(o, 0, 2).reshape(r, H, N * chunk, dv)
    return jnp.moveaxis(o, 1, 2)[:, :t], state


def kda_decode(q, k, v, g, beta, pool, slots, impl: Optional[str] = None):
    """One token a row on the rows' states IN the pool. q, k ``[R, H, dk]``, v
    ``[R, H, dv]``, g ``[R, H, dk]``, beta ``[R, H]``, pool ``[slots, H, dk, dv]``
    float32, ``slots [R]``. Rows that share a slot (a grid's padding, on the
    spare slot) must carry ``g = beta = 0``. Returns (o ``[R, H, dv]`` float32,
    the pool). ``impl`` as ``gdn_decode``'s."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    impl = impl or ("kernel" if on_tpu() else "jnp")
    if impl != "jnp":
        return _decode_pallas(q, k, v, g, beta, pool, slots, impl == "interpret")
    o, S = kda_recurrent(q[:, None], k[:, None], v[:, None], g[:, None], beta[:, None], pool[slots])
    return o[:, 0], pool.at[slots].set(S)
