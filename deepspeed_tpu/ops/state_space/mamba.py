"""The selective scan of a Mamba-1 layer, three ways (``transformers``
``modeling_jamba.py``: ``JambaMambaMixer.slow_forward``, steps 3.b and 3.c).

Per channel ``c`` the cache is a state ``S[:, c]`` of ``N`` float32 numbers and
a token does

  S[n, c] <- exp(delta[c] * A[n, c]) * S[n, c] + delta[c] * u[c] * B[n]
  y[c]     = sum_n S[n, c] * C[n] + D[c] * u[c]
  y[c]    <- y[c] * silu(z[c])

with ``A = -exp(A_log) < 0`` and ``delta = softplus(..) > 0``. The decay
differs for every (state, channel) pair, so no chunk of the rule is a matrix
product: it is elementwise work on the vector unit, ``N`` exponentials and
``6 N`` multiplies and adds a channel a token. A token with ``delta = 0``
leaves the state as it was: that is how the padding of a serving step's grid
is kept out of it.

  * ``mamba_recurrent``: the recurrence token by token (``lax.scan``), carrying
    ``S`` and never a ``[tokens, channels, N]`` tensor: the oracle, and the path
    off the chip.
  * ``mamba_scan``: what a prompt chunk runs, from the slot's state to the
    slot's state. On a TPU the Pallas kernel ``dstpu_mamba_scan``: a grid over
    (row, block of tokens); the row's state stays in VMEM from its first
    block to its last, a program walks its tokens with one group of 1,024
    channels' state in registers, reads u, delta, z, B, C once and writes y once.
  * ``mamba_decode``: one token a row over a POOL of states in place. On a TPU
    the kernel ``dstpu_mamba_decode`` (the rows' slot ids by scalar prefetch,
    the pool aliased to the output: one read and one write of a row's state).

Layout. Channels sit on the lanes: a state is ``[N, d / 128, 128]``
(``state_shape``), so that eight rows of 128 channels are one register, and
B's and C's numbers, which every channel shares, are scalars read from SMEM.
The pool is allocated in that shape: a reshape of it between steps would be a
copy of the pool.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator.device import on_tpu

# the kernels' names in a device trace, beside the other ``dstpu_*`` names
MAMBA_SCAN = "dstpu_mamba_scan"
MAMBA_DECODE = "dstpu_mamba_decode"
LANES = 128
_GROUP = 8        # rows of 128 channels a register
_TOKENS = 32      # tokens a program of the scan kernel


def _lanes(d: int) -> int:
    return LANES if d % LANES == 0 else d


def state_shape(d: int, n: int) -> Tuple[int, int, int]:
    """A sequence's state for ``d`` channels of ``n`` numbers: ``[n, d / 128,
    128]`` (``[n, 1, d]`` where 128 does not divide the channels)."""
    return (n, d // _lanes(d), _lanes(d))


def _tiled(a, d: int):
    """``[..., d]`` as the kernels and the state are laid out: ``[..., d / 128, 128]``."""
    return a.reshape(a.shape[:-1] + state_shape(d, 1)[1:])


def mamba_recurrent(u, delta, B, C, z, A, D, state):
    """u, delta, z ``[r, t, d]``; B, C ``[r, t, N]``; A ``[N, d]`` float32
    (``-exp(A_log)`` transposed); D ``[d]``; state ``[r, N, d / 128, 128]``
    float32 (``state_shape``). Returns (y ``[r, t, d]`` float32, gated by
    ``silu(z)``; the state after the row's tokens)."""
    f32 = jnp.float32
    r, t, d = u.shape
    u, delta, B, C, z = (a.astype(f32) for a in (u, delta, B, C, z))
    A, D = A.astype(f32), D.astype(f32)

    def one(S, xs):
        u_t, dt_t, B_t, C_t = xs                                   # [r, d], [r, d], [r, N], [r, N]
        S = jnp.exp(dt_t[:, None, :] * A) * S + (dt_t * u_t)[:, None, :] * B_t[:, :, None]
        return S, jnp.sum(S * C_t[:, :, None], axis=1) + D * u_t

    S, y = jax.lax.scan(one, state.astype(f32).reshape(r, -1, d),
                        tuple(jnp.moveaxis(a, 1, 0) for a in (u, delta, B, C)))
    return jnp.moveaxis(y, 0, 1) * jax.nn.silu(z), S.reshape(state.shape)


def _token(S, A, u, dt, z, B_t, C_t, D):
    """One token on one group of channels. S, A: tuples of N ``[g, 128]``; u,
    dt, z, D ``[g, 128]``; B_t, C_t: N scalars each (every channel shares
    them). Returns (the new S, y)."""
    du = dt * u
    y = D * u
    new = []
    for S_n, A_n, B_n, C_n in zip(S, A, B_t, C_t):
        S_n = jnp.exp(dt * A_n) * S_n + du * B_n
        y = y + S_n * C_n
        new.append(S_n)
    return tuple(new), y * (z * jax.nn.sigmoid(z))


def _groups(rows: int):
    g = _GROUP if rows % _GROUP == 0 else rows
    return [(i, g) for i in range(0, rows, g)]


def _scan_kernel(u_ref, dt_ref, z_ref, b_ref, c_ref, a_ref, d_ref, s0_ref, y_ref, s_ref, *, tokens):
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    N = a_ref.shape[0]
    for g0, g in _groups(a_ref.shape[1]):
        rows = slice(g0, g0 + g)
        A = tuple(a_ref[n, rows, :] for n in range(N))
        D = d_ref[rows, :]

        def body(t, S):
            S, y = _token(S, A, u_ref[t, rows, :], dt_ref[t, rows, :], z_ref[t, rows, :],
                          [b_ref[t, n] for n in range(N)], [c_ref[t, n] for n in range(N)], D)
            y_ref[t, rows, :] = y
            return S

        S = jax.lax.fori_loop(0, tokens, body, tuple(s_ref[n, rows, :] for n in range(N)))
        for n in range(N):
            s_ref[n, rows, :] = S[n]


def _scan_pallas(u, delta, B, C, z, A, D, state, interpret: bool):
    r, t, d = u.shape
    N, rows, lanes = state.shape[1:]
    tb = _TOKENS if t % _TOKENS == 0 else t
    tok = pl.BlockSpec((None, tb, rows, lanes), lambda i, j: (i, j, 0, 0))
    # B's and C's numbers are scalars to every channel: in SMEM, a block of tokens at a time
    num = pl.BlockSpec((None, tb, N), lambda i, j: (i, j, 0), memory_space=pltpu.SMEM)
    st = pl.BlockSpec((None, N, rows, lanes), lambda i, j: (i, 0, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, tokens=tb),
        grid=(r, t // tb),
        in_specs=[tok, tok, tok, num, num,
                  pl.BlockSpec((N, rows, lanes), lambda i, j: (0, 0, 0)),
                  pl.BlockSpec((rows, lanes), lambda i, j: (0, 0)), st],
        out_specs=[tok, st],
        out_shape=[jax.ShapeDtypeStruct((r, t, rows, lanes), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=MAMBA_SCAN,
    )(_tiled(u, d), _tiled(delta, d), _tiled(z, d), B, C, _tiled(A, d), _tiled(D, d), state)
    return y.reshape(r, t, d), state


def mamba_scan(u, delta, B, C, z, A, D, state, impl: Optional[str] = None):
    """``mamba_recurrent``'s result for a prompt chunk a row. ``impl``:
    ``"kernel"`` (on a TPU), ``"interpret"`` (the kernel interpreted, for tests
    on the CPU) or ``"jnp"``; None picks by the platform."""
    f32 = jnp.float32
    impl = impl or ("kernel" if on_tpu() else "jnp")
    if impl == "jnp":
        return mamba_recurrent(u, delta, B, C, z, A, D, state)
    u, delta, B, C, z, A, D = (a.astype(f32) for a in (u, delta, B, C, z, A, D))
    return _scan_pallas(u, delta, B, C, z, A, D, state, impl == "interpret")


def _decode_kernel(slots, u_ref, dt_ref, z_ref, b_ref, c_ref, a_ref, d_ref, s_ref, y_ref, s_out_ref):
    del slots  # the state's index maps read it
    r = pl.program_id(0)
    N = a_ref.shape[0]
    B_t, C_t = [b_ref[r, n] for n in range(N)], [c_ref[r, n] for n in range(N)]
    for g0, g in _groups(a_ref.shape[1]):
        rows = slice(g0, g0 + g)
        S, y = _token(tuple(s_ref[n, rows, :] for n in range(N)),
                      tuple(a_ref[n, rows, :] for n in range(N)),
                      u_ref[r, rows, :], dt_ref[r, rows, :], z_ref[r, rows, :], B_t, C_t,
                      d_ref[rows, :])
        for n in range(N):
            s_out_ref[n, rows, :] = S[n]
        y_ref[r, rows, :] = y


def _decode_pallas(u, delta, B, C, z, A, D, pool, slots, interpret: bool):
    R, d = u.shape
    N, rows, lanes = pool.shape[1:]
    # every row's token is fetched once, for all programs, and y written once:
    # a program moves its own row's state and nothing else
    tok = pl.BlockSpec((R, rows, lanes), lambda r, s: (0, 0, 0))
    num = pl.BlockSpec(memory_space=pltpu.SMEM)
    st = pl.BlockSpec((None, N, rows, lanes), lambda r, s: (s[r], 0, 0, 0))
    y, pool = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(R,),
            in_specs=[tok, tok, tok, num, num,
                      pl.BlockSpec((N, rows, lanes), lambda r, s: (0, 0, 0)),
                      pl.BlockSpec((rows, lanes), lambda r, s: (0, 0)), st],
            out_specs=[tok, st],
        ),
        out_shape=[jax.ShapeDtypeStruct((R, rows, lanes), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool is input 8 (the slot ids are input 0) and output 1
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=MAMBA_DECODE,
    )(slots.astype(jnp.int32), _tiled(u, d), _tiled(delta, d), _tiled(z, d), B, C,
      _tiled(A, d), _tiled(D, d), pool)
    return y.reshape(R, d), pool


def mamba_decode(u, delta, B, C, z, A, D, pool, slots, impl: Optional[str] = None):
    """One token a row on the rows' states IN the pool. u, delta, z ``[R, d]``;
    B, C ``[R, N]``; pool ``[slots, N, d / 128, 128]`` float32; ``slots [R]`` the
    row's slot. Rows that share a slot (the padding of a grid, all on the spare
    slot) must carry ``delta = 0``. Returns (y ``[R, d]`` float32, the pool).
    ``impl`` as ``mamba_scan``'s."""
    f32 = jnp.float32
    u, delta, B, C, z, A, D = (a.astype(f32) for a in (u, delta, B, C, z, A, D))
    impl = impl or ("kernel" if on_tpu() else "jnp")
    if impl != "jnp":
        return _decode_pallas(u, delta, B, C, z, A, D, pool, slots, impl == "interpret")
    y, S = mamba_recurrent(u[:, None], delta[:, None], B[:, None], C[:, None], z[:, None], A, D,
                           pool[slots])
    return y[:, 0], pool.at[slots].set(S)
