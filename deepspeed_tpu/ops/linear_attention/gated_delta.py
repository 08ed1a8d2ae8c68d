"""The gated delta rule, three ways, and the short causal convolution in
front of it (``transformers`` ``modeling_qwen3_next.py``:
``torch_recurrent_gated_delta_rule``, ``torch_chunk_gated_delta_rule``,
``torch_causal_conv1d_update``).

Per value head the cache is a state ``S [dk, dv]`` in float32 and a token does

  S     <- S * exp(g)
  delta  = (v - S^T k) * beta
  S     <- S + k delta^T
  o      = S^T q

with q and k L2-normalised over ``dk`` and q scaled by ``dk ** -0.5``
(``qk_heads``), ``g <= 0`` and ``0 < beta < 1`` from ``gdn_gates``. A token
with ``g = 0`` and ``beta = 0`` leaves the state as it was: that is how the
padding of a serving step's grid is kept out of it.

  * ``gdn_recurrent``: the recurrence token by token (``lax.scan``): the oracle.
  * ``gdn_chunked``: chunks of 64 tokens, the in-chunk part as matmuls (the
    WY form of the published chunked rule), the state carried from chunk to
    chunk: what a prompt chunk runs, from the slot's state to the slot's state.
    On a TPU the Pallas kernel ``dstpu_gdn_chunk`` (``delta_chunk.py``: a head's
    state stays in VMEM across a row's chunks); elsewhere, as the kernel's
    second oracle and where a gradient is taken, plain XLA.
  * ``gdn_decode``: one token a row over a POOL of states in place. On a TPU
    the Pallas kernel ``dstpu_gdn_decode`` (the rows' slot ids by scalar
    prefetch, the pool aliased to the output: one read and one write of a
    row's state); elsewhere, and as its oracle, gather / update / scatter.

q and k come at the KEY heads ``[..., nk, dk]``; each serves ``nv // nk``
consecutive value heads (``repeat_interleave``).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator.device import on_tpu

# the kernel's name in a device trace, beside the other ``dstpu_*`` names
GDN_DECODE = "dstpu_gdn_decode"
# the same kernel with a decay a key channel (ops/linear_attention/kda.py)
KDA_DECODE = "dstpu_kda_decode"
CHUNK = 64
_HI = jax.lax.Precision.HIGHEST


def l2norm(x, eps: float = 1e-6):
    """x / sqrt(sum(x^2) + eps) over the last axis (the FLA library's form)."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def qk_heads(q, k):
    """q, k ``[..., nk, dk]`` as the rule takes them: L2-normalised, q scaled."""
    return l2norm(q) * (q.shape[-1] ** -0.5), l2norm(k)


def gdn_gates(b, a, a_log, dt_bias):
    """(g, beta) float32, one a value head: ``beta = sigmoid(b)``,
    ``g = -exp(A_log) * softplus(a + dt_bias)``."""
    f32 = jnp.float32
    g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(a.astype(f32) + dt_bias.astype(f32))
    return g, jax.nn.sigmoid(b.astype(f32))


def gated_rms_norm(o, z, w, eps: float, gate=jax.nn.silu):
    """``w * rms(o) * gate(z)`` over the last axis, in float32 (the weight is
    plain ``w``, not ``1 + w``; ``gate``: SiLU, a KDA layer's the sigmoid)."""
    f32 = jnp.float32
    o = o.astype(f32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return w.astype(f32) * o * gate(z.astype(f32))


def causal_conv(x, w, state, n=None, bias=None):
    """Depthwise causal convolution (``bias [C]`` added where given), then
    SiLU, with carried inputs.

    x ``[r, t, C]``; w ``[K, C]`` (``w[j]`` multiplies the input ``K - 1 - j``
    tokens back); state ``[r, K - 1, C]``: the inputs before ``x[:, 0]``.
    ``n [r]``: how many of a row's ``t`` tokens are real (None: all). Returns
    (``[r, t, C]`` float32, the state after the row's ``n`` tokens in
    ``state``'s dtype: the last ``K - 1`` inputs, old ones where ``n < K - 1``;
    a row with ``n = 0`` keeps its state)."""
    K = w.shape[0]
    t = x.shape[1]
    ext = jnp.concatenate([state.astype(jnp.float32), x.astype(jnp.float32)], axis=1)
    wf = w.astype(jnp.float32)
    out = sum(ext[:, j : j + t] * wf[j] for j in range(K))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    if n is None:
        new = ext[:, t:]
    else:
        new = jax.vmap(lambda e, i: jax.lax.dynamic_slice_in_dim(e, i, K - 1, 0))(ext, n)
    return jax.nn.silu(out), new.astype(state.dtype)


def _to_value_heads(q, k, nv):
    rep = nv // q.shape[-2]
    return jnp.repeat(q, rep, axis=-2), jnp.repeat(k, rep, axis=-2)


def gdn_recurrent(q, k, v, g, beta, state):
    """Token by token. q, k ``[r, t, nk, dk]`` (``qk_heads``), v
    ``[r, t, nv, dv]``, g, beta ``[r, t, nv]``, state ``[r, nv, dk, dv]``
    float32. Returns (o ``[r, t, nv, dv]`` float32, the state after)."""
    f32 = jnp.float32
    q, k = _to_value_heads(q.astype(f32), k.astype(f32), v.shape[-2])

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = S * jnp.exp(g_t)[..., None, None]
        mem = jnp.einsum("rhkv,rhk->rhv", S, k_t, precision=_HI)
        delta = (v_t - mem) * b_t[..., None]
        S = S + k_t[..., :, None] * delta[..., None, :]
        return S, jnp.einsum("rhkv,rhk->rhv", S, q_t, precision=_HI)

    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta))
    state, o = jax.lax.scan(token, state.astype(f32), xs)
    return jnp.moveaxis(o, 0, 1), state


def gdn_chunked(q, k, v, g, beta, state, chunk: int = CHUNK, impl: Optional[str] = None):
    """``gdn_recurrent``'s result in chunks of ``chunk`` tokens: inside a chunk
    the delta rule's triangular system is solved as ``(I - A)^-1 = prod_j (I +
    A^(2^j))`` (A strictly lower, so nilpotent), the state goes from chunk to
    chunk. ``t`` is padded to a whole number of chunks with ``g = beta = 0``.
    ``impl``: ``"kernel"`` (on a TPU), ``"interpret"`` (the kernel interpreted,
    for tests on the CPU) or ``"jnp"`` (this body: the one a gradient goes
    through); None picks by the platform."""
    impl = impl or ("kernel" if on_tpu() else "jnp")
    if impl != "jnp":
        from deepspeed_tpu.ops.linear_attention.delta_chunk import delta_chunk

        return delta_chunk(q, k, v, g, beta, state, impl == "interpret", chunk)
    f32 = jnp.float32
    r, t, nv, dv = v.shape
    q, k = _to_value_heads(q.astype(f32), k.astype(f32), nv)
    pad = -t % chunk
    N = (t + pad) // chunk

    def chunks(a):  # [r, t, nv, ...] -> [r, nv, N, chunk, ...]
        a = jnp.pad(a.astype(f32), ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape((r, N, chunk) + a.shape[2:]), 3, 1)

    q, k, v, g, beta = (chunks(a) for a in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)                                     # [r, nv, N, C]
    low = jnp.tril(jnp.ones((chunk, chunk), bool))
    # exp only of what is kept: above the diagonal the difference is positive
    decay = jnp.exp(jnp.where(low, gc[..., :, None] - gc[..., None, :], -jnp.inf))
    k_beta, v_beta = k * beta[..., None], v * beta[..., None]
    mm = functools.partial(jnp.matmul, precision=_HI)
    A = -jnp.where(jnp.tril(low, -1), mm(k_beta, jnp.swapaxes(k, -1, -2)) * decay, 0.0)
    eye = jnp.eye(chunk, dtype=f32)
    T, P = eye + A, A
    for _ in range(max(0, (chunk - 1).bit_length() - 1)):
        P = mm(P, P)
        T = mm(T, eye + P)
    v_solved = mm(T, v_beta)
    k_cum = mm(T, k_beta * jnp.exp(gc)[..., None])
    qk = mm(q, jnp.swapaxes(k, -1, -2)) * decay                     # diagonal kept

    def one(S, xs):
        q_i, k_i, vs_i, kc_i, qk_i, gc_i = xs
        v_new = vs_i - mm(kc_i, S)
        o_i = mm(q_i * jnp.exp(gc_i)[..., None], S) + mm(qk_i, v_new)
        last = gc_i[..., -1:]
        S = S * jnp.exp(last)[..., None] + mm(
            jnp.swapaxes(k_i * jnp.exp(last - gc_i)[..., None], -1, -2), v_new)
        return S, o_i

    xs = tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v_solved, k_cum, qk, gc))
    state, o = jax.lax.scan(one, state.astype(f32), xs)           # o [N, r, nv, C, dv]
    o = jnp.moveaxis(o, 0, 2).reshape(r, nv, N * chunk, dv)
    return jnp.moveaxis(o, 1, 2)[:, :t], state


def _decode_kernel(slots, q_ref, k_ref, v_ref, d_ref, b_ref, s_ref, o_ref, s_out_ref, *, hb, rep,
                   by_channel):
    del slots  # the state's index maps read it
    for h in range(hb):
        kc = k_ref[:, h // rep : h // rep + 1]                       # [dk, 1]
        # the decay: a head's ONE number along a [1, dv] row (Gated DeltaNet), or
        # a number a key channel as a [dk, 1] column, laid out as k is (KDA)
        S = s_ref[h] * (d_ref[:, h : h + 1] if by_channel else d_ref[h : h + 1, :])
        mem = jnp.sum(S * kc, axis=0, keepdims=True)                 # [1, dv]
        delta = (v_ref[h : h + 1, :] - mem) * b_ref[h : h + 1, :]
        S = S + kc * delta
        s_out_ref[h] = S
        o_ref[h : h + 1, :] = jnp.sum(S * q_ref[:, h // rep : h // rep + 1], axis=0, keepdims=True)


def _head_block(nv: int, rep: int) -> int:
    """Value heads a program: 16 (1 MiB of state at 128 x 128) where that
    divides the heads and holds whole groups of a key head, else all."""
    return 16 if nv % 16 == 0 and 16 % rep == 0 else nv


def _decode_pallas(q, k, v, g, beta, pool, slots, interpret: bool):
    """The one-token kernel. ``g [R, nv]``: a decay a head, under the name
    ``dstpu_gdn_decode``; ``g [R, nv, dk]``: a decay a key channel (KDA), the
    same body under ``dstpu_kda_decode``."""
    R, nk, dk = q.shape
    nv, dv = v.shape[-2:]
    rep = nv // nk
    hb = _head_block(nv, rep)
    J, kb = nv // hb, hb // rep
    by_channel = g.ndim == 3

    def columns(a, n):  # [R, J * n, dk] -> [R, J, dk, n]: a head a lane
        return jnp.swapaxes(a.reshape(R, J, n, dk), -1, -2)

    rows = lambda a: jnp.broadcast_to(a[..., None], (R, nv, dv))  # noqa: E731
    head_rows = pl.BlockSpec((None, hb, dv), lambda r, j, s: (r, j, 0))
    key_cols = pl.BlockSpec((None, None, dk, kb), lambda r, j, s: (r, j, 0, 0))
    head_cols = pl.BlockSpec((None, None, dk, hb), lambda r, j, s: (r, j, 0, 0))
    state = pl.BlockSpec((None, hb, dk, dv), lambda r, j, s: (s[r], j, 0, 0))
    o, pool = pl.pallas_call(
        functools.partial(_decode_kernel, hb=hb, rep=rep, by_channel=by_channel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(R, J),
            in_specs=[key_cols, key_cols, head_rows, head_cols if by_channel else head_rows,
                      head_rows, state],
            out_specs=[head_rows, state],
        ),
        out_shape=[jax.ShapeDtypeStruct((R, nv, dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool is input 6 (the slot ids are input 0) and output 1
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=KDA_DECODE if by_channel else GDN_DECODE,
    )(slots.astype(jnp.int32), columns(q, kb), columns(k, kb), v,
      columns(jnp.exp(g), hb) if by_channel else rows(jnp.exp(g)), rows(beta), pool)
    return o, pool


def gdn_decode(q, k, v, g, beta, pool, slots, impl: Optional[str] = None):
    """One token a row on the rows' states IN the pool. q, k ``[R, nk, dk]``
    (``qk_heads``), v ``[R, nv, dv]``, g, beta ``[R, nv]``, pool
    ``[slots, nv, dk, dv]`` float32, ``slots [R]`` the row's slot. Rows that
    share a slot (the padding of a grid, all on the spare slot) must carry
    ``g = beta = 0``. Returns (o ``[R, nv, dv]`` float32, the pool). ``impl``:
    ``"kernel"`` (on a TPU), ``"interpret"`` (the kernel interpreted, for tests
    on the CPU) or ``"jnp"``; None picks by the platform."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    impl = impl or ("kernel" if on_tpu() else "jnp")
    if impl != "jnp":
        return _decode_pallas(q, k, v, g, beta, pool, slots, impl == "interpret")
    o, S = gdn_recurrent(q[:, None], k[:, None], v[:, None], g[:, None], beta[:, None], pool[slots])
    return o[:, 0], pool.at[slots].set(S)
