"""The two state-space kernels (ops/state_space/mamba.py: ``dstpu_mamba_scan``,
``dstpu_mamba_decode``) interpreted on the CPU against their ``lax.scan``
oracle, and the oracle against a hand-written loop of the published rule."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.state_space import (
    mamba_decode, mamba_recurrent, mamba_scan, state_shape)

# float32 on both sides; the kernel and the scan differ in the order of the
# sum over a state's 16 numbers alone (measured 4e-6 on outputs of scale 3)
ATOL = 3e-5


def _inputs(seed, lead, d, n):
    k = jax.random.split(jax.random.key(seed), 8)
    u = jax.random.normal(k[0], lead + (d,), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(k[1], lead + (d,), jnp.float32) - 2.0)
    B = jax.random.normal(k[2], lead + (n,), jnp.float32)
    C = jax.random.normal(k[3], lead + (n,), jnp.float32)
    z = jax.random.normal(k[4], lead + (d,), jnp.float32)
    A = -jnp.exp(0.5 * jax.random.normal(k[5], (n, d), jnp.float32))
    D = 1.0 + 0.2 * jax.random.normal(k[6], (d,), jnp.float32)
    return (u, dt, B, C, z), (A, D), k[7]


def test_the_oracle_is_the_published_loop():
    """``mamba_recurrent`` against ``JambaMambaMixer.slow_forward``'s steps 3.b
    and 3.c written out in numpy: the [tokens, d, N] tensors and the loop."""
    (u, dt, B, C, z), (A, D), key = _inputs(0, (2, 9), 32, 4)
    S0 = jax.random.normal(key, (2,) + state_shape(32, 4), jnp.float32)
    y, S = mamba_recurrent(u, dt, B, C, z, A, D, S0)
    un, dtn, Bn, Cn, zn, An, Dn = (np.asarray(a, np.float64) for a in (u, dt, B, C, z, A, D))
    state = np.asarray(S0, np.float64).reshape(2, 4, 32).transpose(0, 2, 1)   # [b, d, N]
    dA = np.exp(An.T[None, None] * dtn[..., None])                             # [b, t, d, N]
    dBu = dtn[..., None] * Bn[:, :, None, :] * un[..., None]
    want = np.zeros_like(un)
    for t in range(9):
        state = dA[:, t] * state + dBu[:, t]
        want[:, t] = np.einsum("bdn,bn->bd", state, Cn[:, t])
    want = (want + un * Dn) * (zn / (1 + np.exp(-zn)))
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(S).reshape(2, 4, 32), state.transpose(0, 2, 1), atol=1e-5)


# (channels, tokens): one row of lanes; three; eight (one register) and twelve
# (a group that is not a register's worth); tokens in one block and in several
@pytest.mark.parametrize("d,t", [(128, 32), (384, 64), (1024, 96), (1536, 40)])
def test_scan_kernel_equals_the_scan(d, t):
    (x, (A, D), key) = _inputs(1, (2, t), d, 16)
    S0 = jax.random.normal(key, (2,) + state_shape(d, 16), jnp.float32)
    y, S = mamba_recurrent(*x, A, D, S0)
    yk, Sk = mamba_scan(*x, A, D, S0, impl="interpret")
    np.testing.assert_allclose(np.asarray(yk), np.asarray(y), atol=ATOL)
    np.testing.assert_allclose(np.asarray(Sk), np.asarray(S), atol=ATOL)


def test_a_chunk_continued_from_a_carried_state_equals_the_whole():
    """Two chunks, the second from the first's state, are the whole prompt's
    scan: what a prompt of several chunks does through its slot."""
    (x, (A, D), key) = _inputs(2, (2, 96), 256, 16)
    S0 = jnp.zeros((2,) + state_shape(256, 16), jnp.float32)
    y, S = mamba_recurrent(*x, A, D, S0)
    first, rest = [a[:, :64] for a in x], [a[:, 64:] for a in x]
    y1, S1 = mamba_scan(*first, A, D, S0, impl="interpret")
    y2, S2 = mamba_scan(*rest, A, D, S1, impl="interpret")
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)), np.asarray(y), atol=ATOL)
    np.testing.assert_allclose(np.asarray(S2), np.asarray(S), atol=ATOL)


def test_tokens_with_no_step_leave_the_state_as_it_was():
    """``delta = 0`` is how the padding of a step's grid is kept out of a
    state: a chunk whose tail has no step ends in the state after its head."""
    (x, (A, D), key) = _inputs(3, (1, 64), 256, 16)
    u, dt, B, C, z = x
    S0 = jax.random.normal(key, (1,) + state_shape(256, 16), jnp.float32)
    dt = dt.at[:, 40:].set(0.0)
    _, S_head = mamba_recurrent(u[:, :40], dt[:, :40], B[:, :40], C[:, :40], z[:, :40], A, D, S0)
    _, S = mamba_scan(u, dt, B, C, z, A, D, S0, impl="interpret")
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_head), atol=ATOL)


@pytest.mark.parametrize("d", [128, 1024, 1536])
def test_one_decode_step_equals_a_scan_of_one_token(d):
    """The decode kernel over a pool, rows on slots in no order, against the
    scan of one token from the same states; slots no row names stay as they
    were, bit for bit."""
    (x, (A, D), key) = _inputs(4, (3,), d, 16)
    pool = jax.random.normal(key, (7,) + state_shape(d, 16), jnp.float32)
    slots = jnp.asarray([5, 0, 3], jnp.int32)
    y, S = mamba_recurrent(*(a[:, None] for a in x), A, D, pool[slots])
    yk, pk = mamba_decode(*x, A, D, pool, slots, impl="interpret")
    yj, pj = mamba_decode(*x, A, D, pool, slots, impl="jnp")
    for got_y, got_p in ((yk, pk), (yj, pj)):
        np.testing.assert_allclose(np.asarray(got_y), np.asarray(y[:, 0]), atol=ATOL)
        np.testing.assert_allclose(np.asarray(got_p[slots]), np.asarray(S), atol=ATOL)
        for s in (1, 2, 4, 6):
            np.testing.assert_array_equal(np.asarray(got_p[s]), np.asarray(pool[s]))


def test_padding_rows_on_the_spare_slot_leave_it_as_it_was():
    """Rows that share a slot (the grid's padding, all on the spare) carry
    ``delta = 0``: the slot is read and written by each and keeps its value."""
    (x, (A, D), key) = _inputs(5, (4,), 256, 16)
    u, dt, B, C, z = x
    pool = jax.random.normal(key, (3,) + state_shape(256, 16), jnp.float32)
    dt = dt.at[1:].set(0.0)
    _, pk = mamba_decode(u, dt, B, C, z, A, D, pool, jnp.asarray([0, 2, 2, 2], jnp.int32),
                         impl="interpret")
    np.testing.assert_array_equal(np.asarray(pk[2]), np.asarray(pool[2]))
    assert float(jnp.abs(pk[0] - pool[0]).max()) > 1e-3


def test_widths_that_128_does_not_divide_keep_one_row():
    assert state_shape(5120, 16) == (16, 40, 128) and state_shape(96, 4) == (4, 1, 96)
    (x, (A, D), key) = _inputs(6, (1, 8), 96, 4)
    S0 = jnp.zeros((1,) + state_shape(96, 4), jnp.float32)
    y, S = mamba_recurrent(*x, A, D, S0)
    yk, Sk = mamba_scan(*x, A, D, S0, impl="interpret")
    np.testing.assert_allclose(np.asarray(yk), np.asarray(y), atol=ATOL)
