"""Kimi Delta Attention's rule (ops/linear_attention/kda.py) on the CPU: the
chunked form and the interpreted decode kernel ``dstpu_kda_decode`` against the
``lax.scan`` oracle ``kda_recurrent``, the oracle against a hand-written loop,
and, with all of a head's channels given one decay, against ``gdn_recurrent``
(which ``tests/unit/ops/test_gated_delta.py`` holds to ``transformers``'
``modeling_qwen3_next.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.linear_attention import (
    gdn_chunked, gdn_decode, gdn_recurrent, kda_chunked, kda_decode, kda_recurrent)
from deepspeed_tpu.ops.linear_attention.gated_delta import qk_heads

# float32 on both sides: the chunked form and the scan differ in the order of
# float32 sums alone (measured 2e-7 on outputs of up to 0.6, 1e-6 on states)
ATOL = 1e-5


def _inputs(seed, r, t, H, dk, dv, rates=(20.0, 0.0, 0.05)):
    """Seeded inputs whose channels decay at ``rates`` in turn: by e^-20 a
    token (gone within a token), not at all, and slowly."""
    k = jax.random.split(jax.random.key(seed), 7)
    q, kk = qk_heads(jax.random.normal(k[0], (r, t, H, dk)), jax.random.normal(k[1], (r, t, H, dk)))
    v = jax.random.normal(k[2], (r, t, H, dv))
    rate = jnp.asarray(rates, jnp.float32)[jnp.arange(dk) % len(rates)]
    g = -rate * jax.random.uniform(k[3], (r, t, H, dk), minval=0.5, maxval=1.0)
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (r, t, H)))
    S0 = jax.random.normal(k[5], (r, H, dk, dv))
    return (q, kk, v, g, beta), S0


def test_the_oracle_is_the_rule_written_out():
    """``kda_recurrent`` against the rule as a numpy loop in float64: decay the
    state a key channel, then the delta step on the decayed state, then read."""
    (q, k, v, g, beta), S0 = _inputs(0, 2, 7, 3, 8, 4)
    o, S = kda_recurrent(q, k, v, g, beta, S0)
    qn, kn, vn, gn, bn, state = (np.asarray(a, np.float64) for a in (q, k, v, g, beta, S0))
    want = np.zeros(o.shape)
    for t in range(7):
        state = state * np.exp(gn[:, t])[..., None]
        delta = (vn[:, t] - np.einsum("rhkv,rhk->rhv", state, kn[:, t])) * bn[:, t][..., None]
        state = state + kn[:, t][..., :, None] * delta[..., None, :]
        want[:, t] = np.einsum("rhkv,rhk->rhv", state, qn[:, t])
    np.testing.assert_allclose(np.asarray(o), want, atol=ATOL)
    np.testing.assert_allclose(np.asarray(S), state, atol=ATOL)


# tokens: under a sub-block, a sub-block's edge, a chunk's edge, chunks and a tail
@pytest.mark.parametrize("t", [5, 16, 17, 64, 65, 150])
def test_chunked_equals_the_scan_with_a_carried_state(t):
    """From a carried state, with channels that decay by e^-20 a token beside
    channels that do not decay: no exponential overflows, nothing is lost."""
    x, S0 = _inputs(1, 2, t, 4, 32, 16)
    o, S = kda_recurrent(*x, S0)
    oc, Sc = jax.jit(kda_chunked)(*x, S0)
    assert bool(jnp.isfinite(oc).all()) and bool(jnp.isfinite(Sc).all())
    np.testing.assert_allclose(np.asarray(oc), np.asarray(o), atol=ATOL)
    np.testing.assert_allclose(np.asarray(Sc), np.asarray(S), atol=ATOL)


def test_chunked_with_a_ragged_live_mask_leaves_dead_tokens_out():
    """Tokens behind a row's length carry ``g = beta = 0`` (what the engine's
    ``live`` mask makes of them): the state after is the state after the live
    tokens, and a row with none keeps its state to the last bit."""
    (q, k, v, g, beta), S0 = _inputs(2, 3, 100, 2, 16, 16)
    n = jnp.asarray([100, 37, 0])
    live = jnp.arange(100)[None] < n[:, None]
    g, beta = jnp.where(live[..., None, None], g, 0.0), jnp.where(live[..., None], beta, 0.0)
    oc, Sc = kda_chunked(q, k, v, g, beta, S0)
    for row, m in enumerate([100, 37]):
        o, S = kda_recurrent(*(a[row: row + 1, :m] for a in (q, k, v, g, beta)), S0[row: row + 1])
        np.testing.assert_allclose(np.asarray(oc[row, :m]), np.asarray(o[0]), atol=ATOL)
        np.testing.assert_allclose(np.asarray(Sc[row]), np.asarray(S[0]), atol=ATOL)
    np.testing.assert_array_equal(np.asarray(Sc[2]), np.asarray(S0[2]))


def test_the_split_form_would_overflow_where_this_one_does_not():
    """The control: ``(k exp(G)) . (k exp(-G))`` at these decays is inf or nan
    within one chunk, which is why the sub-blocks take differences first."""
    (_, _, _, g, _), _ = _inputs(1, 1, 64, 1, 32, 16)
    assert not bool(jnp.isfinite(jnp.exp(-jnp.cumsum(g, axis=1))).all())


def test_one_decay_a_head_is_the_gated_delta_rule():
    """All of a head's channels given one decay: ``kda_recurrent`` IS
    ``gdn_recurrent``, and the chunked forms agree with both."""
    (q, k, v, g, beta), S0 = _inputs(3, 2, 70, 4, 16, 16, rates=(0.3,))
    gh = g[..., 0]
    wide = jnp.broadcast_to(gh[..., None], g.shape)
    o, S = gdn_recurrent(q, k, v, gh, beta, S0)
    ok, Sk = kda_recurrent(q, k, v, wide, beta, S0)
    np.testing.assert_allclose(np.asarray(ok), np.asarray(o), atol=1e-6)
    np.testing.assert_allclose(np.asarray(Sk), np.asarray(S), atol=1e-6)
    for oc, Sc in (kda_chunked(q, k, v, wide, beta, S0), gdn_chunked(q, k, v, gh, beta, S0)):
        np.testing.assert_allclose(np.asarray(oc), np.asarray(o), atol=ATOL)
        np.testing.assert_allclose(np.asarray(Sc), np.asarray(S), atol=ATOL)


# (heads, rows): one program a row; 32 heads are two programs of 16 a row
@pytest.mark.parametrize("H,R", [(4, 3), (32, 2)])
def test_decode_kernel_equals_the_scan_and_leaves_the_spare_slot(H, R):
    """``dstpu_kda_decode`` interpreted: the rows' slots updated in place, a
    padding row (``g = beta = 0``, on the spare slot) and every other slot of
    the pool untouched to the last bit."""
    dk = dv = 128
    (q, k, v, g, beta), _ = _inputs(4, R + 1, 1, H, dk, dv)
    q, k, v, g, beta = (a[:, 0] for a in (q, k, v, g, beta))
    g, beta = g.at[R].set(0.0), beta.at[R].set(0.0)           # the padding row
    pool = jax.random.normal(jax.random.key(9), (R + 3, H, dk, dv))
    slots = jnp.asarray(list(range(R, 0, -1)) + [R + 2])      # the spare: the last slot
    want_o, want_S = kda_recurrent(*(a[:, None] for a in (q, k, v, g, beta)), pool[slots])
    for impl in ("jnp", "interpret"):
        o, new = kda_decode(q, k, v, g, beta, pool, slots, impl=impl)
        np.testing.assert_allclose(np.asarray(o), np.asarray(want_o[:, 0]), atol=ATOL)
        np.testing.assert_allclose(np.asarray(new[slots]), np.asarray(want_S), atol=ATOL)
        for untouched in (0, R + 1, R + 2):
            np.testing.assert_array_equal(np.asarray(new[untouched]), np.asarray(pool[untouched]))


def test_the_shared_kernel_body_still_serves_gated_delta_net():
    """One body, two names: the same kernel with a decay a head (``g [R, nv]``)
    is ``dstpu_gdn_decode`` as it was, at grouped key heads."""
    R, nk, nv, d = 2, 2, 4, 128
    ks = jax.random.split(jax.random.key(5), 6)
    q, k = qk_heads(jax.random.normal(ks[0], (R, nk, d)), jax.random.normal(ks[1], (R, nk, d)))
    v = jax.random.normal(ks[2], (R, nv, d))
    g = -jax.random.uniform(ks[3], (R, nv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (R, nv)))
    pool = jax.random.normal(ks[5], (4, nv, d, d))
    slots = jnp.asarray([2, 0])
    o, new = gdn_decode(q, k, v, g, beta, pool, slots, impl="interpret")
    oj, newj = gdn_decode(q, k, v, g, beta, pool, slots, impl="jnp")
    np.testing.assert_allclose(np.asarray(o), np.asarray(oj), atol=ATOL)
    np.testing.assert_allclose(np.asarray(new), np.asarray(newj), atol=ATOL)
