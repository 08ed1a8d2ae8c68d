"""The gated delta rule's three forms against each other (``ops/linear_attention``):
the chunked form and the one-token pool update (the Pallas kernel
``dstpu_gdn_decode``, interpreted) against the token-by-token recurrence, and
the causal conv with its carried inputs. float32 on the CPU: the forms differ by
the order of float32 sums only, so the tolerances are a few ulps of the values
(unit-scale q.k products, states of a few units)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.linear_attention import gated_delta as G

NK, NV, DK, DV = 2, 4, 16, 8


def _inputs(r, t, seed=0, state=True):
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (r, t, NK, DK))
    k = jax.random.normal(ks[1], (r, t, NK, DK))
    v = jax.random.normal(ks[2], (r, t, NV, DV))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (r, t, NV)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (r, t, NV)))
    S0 = jax.random.normal(ks[5], (r, NV, DK, DV)) if state else jnp.zeros((r, NV, DK, DV))
    q, k = G.qk_heads(q, k)
    return q, k, v, g, beta, S0


@pytest.mark.parametrize("t", [1, 63, 64, 65, 400])
def test_chunked_form_equals_the_recurrence(t):
    """Chunk lengths on both sides of the 64-token chunk and a prompt chunk of
    several, from a state that is not zero."""
    q, k, v, g, beta, S0 = _inputs(2, t, seed=t)
    o_ref, S_ref = G.gdn_recurrent(q, k, v, g, beta, S0)
    o, S = jax.jit(G.gdn_chunked)(q, k, v, g, beta, S0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_ref), atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("form", ["recurrent", "chunked"])
def test_a_tail_that_is_not_live_leaves_the_state(form):
    """Slots of a grid that hold no token carry g = beta = 0: the state after
    100 tokens of which 37 are live is the state after those 37, bit for bit
    in the recurrence, whatever the dead slots' q, k and v hold."""
    rule = G.gdn_recurrent if form == "recurrent" else jax.jit(G.gdn_chunked)
    q, k, v, g, beta, S0 = _inputs(1, 100, seed=3)
    live = (jnp.arange(100) < 37)[None, :, None]
    g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
    v = jnp.where(live[..., None], v, 1e3)  # garbage where nothing is live
    o, S = rule(q, k, v, g, beta, S0)
    o37, S37 = rule(q[:, :37], k[:, :37], v[:, :37], g[:, :37], beta[:, :37], S0)
    if form == "recurrent":
        np.testing.assert_array_equal(np.asarray(S), np.asarray(S37))
    np.testing.assert_allclose(np.asarray(S), np.asarray(S37), atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(o[:, :37]), np.asarray(o37), atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("impl", ["interpret", "jnp"])
def test_decode_updates_the_rows_slots_in_place(impl):
    """Six rows on a pool of nine slots: four live rows on slots of their own,
    two padding rows on the spare (g = beta = 0). The rows' slots take the
    recurrence's one-token update, every other slot and the spare are as
    they were, and the kernel's output is the recurrence's."""
    R, NS = 6, 9
    q, k, v, g, beta, _ = _inputs(R, 1, seed=5)
    q, k, v, g, beta = (a[:, 0] for a in (q, k, v, g, beta))
    live = jnp.arange(R) < 4
    g, beta = jnp.where(live[:, None], g, 0.0), jnp.where(live[:, None], beta, 0.0)
    pool = jax.random.normal(jax.random.key(6), (NS, NV, DK, DV))
    slots = jnp.asarray([7, 2, 5, 0, NS - 1, NS - 1], jnp.int32)
    o, new = jax.jit(lambda *a: G.gdn_decode(*a, impl=impl))(q, k, v, g, beta, pool, slots)
    o_ref, S_ref = G.gdn_recurrent(q[:, None], k[:, None], v[:, None], g[:, None], beta[:, None],
                                   pool[slots])
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref[:, 0]), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(new[slots[:4]]), np.asarray(S_ref[:4]), atol=1e-5, rtol=1e-5)
    untouched = np.asarray([1, 3, 4, 6, NS - 1])
    np.testing.assert_array_equal(np.asarray(new[untouched]), np.asarray(pool[untouched]))


def test_decode_kernel_token_after_token_equals_the_recurrence():
    """Twelve one-token updates through the interpreted kernel from a chunk's
    state: prefill by the chunked form, decode in the pool, as a request is
    served; against the recurrence over the whole sequence."""
    t0, t1 = 70, 12
    q, k, v, g, beta, S0 = _inputs(2, t0 + t1, seed=9, state=False)
    o_ref, S_ref = G.gdn_recurrent(q, k, v, g, beta, S0)
    _, S = G.gdn_chunked(q[:, :t0], k[:, :t0], v[:, :t0], g[:, :t0], beta[:, :t0], S0)
    pool = jnp.zeros((4, NV, DK, DV)).at[jnp.asarray([3, 1])].set(S)
    slots = jnp.asarray([3, 1], jnp.int32)
    step = jax.jit(lambda *a: G.gdn_decode(*a, impl="interpret"))
    for i in range(t0, t0 + t1):
        o, pool = step(q[:, i], k[:, i], v[:, i], g[:, i], beta[:, i], pool, slots)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref[:, i]), atol=5e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(pool[slots]), np.asarray(S_ref), atol=5e-5, rtol=1e-4)


def test_causal_conv_in_pieces_equals_the_whole():
    """A sequence cut into ragged pieces (0, 1, 2, 5 and 9 real tokens in grids
    of 9), each from the state the last left, equals the conv over the whole:
    the state is the last K - 1 inputs, old ones where a piece is shorter."""
    K, C, total = 4, 6, 17
    x = jax.random.normal(jax.random.key(1), (1, total, C))
    w = jax.random.normal(jax.random.key(2), (K, C))
    whole, last = G.causal_conv(x, w, jnp.zeros((1, K - 1, C)))
    np.testing.assert_array_equal(np.asarray(last), np.asarray(x[:, -(K - 1):]))
    state, at, outs = jnp.zeros((1, K - 1, C)), 0, []
    for n in (0, 1, 2, 5, 9):
        piece = jnp.zeros((1, 9, C)).at[:, :n].set(x[:, at: at + n])
        y, state = G.causal_conv(piece, w, state, n=jnp.asarray([n], jnp.int32))
        outs.append(y[:, :n])
        at += n
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)), np.asarray(whole), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(state), np.asarray(last))


def test_gated_norm_takes_the_plain_weight():
    o = jax.random.normal(jax.random.key(0), (3, NV, DV))
    z = jax.random.normal(jax.random.key(1), (3, NV, DV))
    w = jnp.full((DV,), 0.5)
    want = 0.5 * o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-6) * (z * jax.nn.sigmoid(z))
    np.testing.assert_allclose(np.asarray(G.gated_rms_norm(o, z, w, 1e-6)), np.asarray(want), atol=1e-6)
