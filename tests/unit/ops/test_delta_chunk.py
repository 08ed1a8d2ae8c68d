"""The chunk kernel of the two delta rules (ops/linear_attention/delta_chunk.py:
``dstpu_kda_chunk``, a decay a key channel, and ``dstpu_gdn_chunk``, a decay a
head: one body) interpreted on the CPU at a head of 128 x 128, against the
``lax.scan`` oracles ``kda_recurrent`` / ``gdn_recurrent`` and against the XLA
bodies (``impl="jnp"``) it replaces on a TPU. Few shapes, each compiled once:
the jitted kernels are the module's."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.linear_attention import (
    gdn_chunked, gdn_recurrent, kda_chunked, kda_recurrent)
from deepspeed_tpu.ops.linear_attention.gated_delta import qk_heads

# float32 on every side: the kernel, the XLA body and the scan differ in the
# order of float32 sums alone (measured 2e-7 on outputs, 2e-6 on states)
ATOL = 1e-5
D = 128
RULES = {"kda": (kda_chunked, kda_recurrent), "gdn": (gdn_chunked, gdn_recurrent)}
KERNEL = {rule: jax.jit(functools.partial(fns[0], impl="interpret")) for rule, fns in RULES.items()}
XLA = {rule: jax.jit(functools.partial(fns[0], impl="jnp")) for rule, fns in RULES.items()}
both_rules = pytest.mark.parametrize("rule", ["kda", "gdn"])


def _inputs(rule, seed, r, t, nk=2, nv=2, rates=(20.0, 0.0, 0.05)):
    """Seeded inputs whose channels (KDA) or heads (Gated DeltaNet) decay at
    ``rates`` in turn: by e^-20 a token, not at all, and slowly."""
    k = jax.random.split(jax.random.key(seed), 6)
    q, kk = qk_heads(jax.random.normal(k[0], (r, t, nk, D)), jax.random.normal(k[1], (r, t, nk, D)))
    v = jax.random.normal(k[2], (r, t, nv, D))
    shape = (r, t, nv, D) if rule == "kda" else (r, t, nv)
    rate = jnp.asarray(rates, jnp.float32)[jnp.arange(shape[-1]) % len(rates)]
    g = -rate * jax.random.uniform(k[3], shape, minval=0.5, maxval=1.0)
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (r, t, nv)))
    return (q, kk, v, g, beta), jax.random.normal(k[5], (r, nv, D, D))


def _close(got, want):
    for a, b in zip(got, want):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=ATOL)


@both_rules
@pytest.mark.parametrize("t", [128, 192], ids=["two_chunks", "three_chunks"])
def test_the_kernel_equals_the_scan_and_the_xla_body_from_a_carried_state(rule, t):
    """From a carried non-zero state, decays of e^-20 a token beside none at
    all: the kernel is the scan and is the body it replaces."""
    x, S0 = _inputs(rule, 1, 3, t)
    got = KERNEL[rule](*x, S0)
    _close(got, RULES[rule][1](*x, S0))
    _close(got, XLA[rule](*x, S0))


@both_rules
def test_a_ragged_live_mask_leaves_dead_tokens_out_and_a_dead_row_untouched(rule):
    """Tokens behind a row's length carry ``g = beta = 0`` (what the engine's
    ``live`` mask makes of them): the state after is the state after the live
    tokens, and a row with none keeps its state to the last bit."""
    (q, k, v, g, beta), S0 = _inputs(rule, 2, 3, 192)
    n = [192, 37, 0]
    live = jnp.arange(192)[None] < jnp.asarray(n)[:, None]
    g = jnp.where(live[..., None, None] if rule == "kda" else live[..., None], g, 0.0)
    beta = jnp.where(live[..., None], beta, 0.0)
    oc, Sc = KERNEL[rule](q, k, v, g, beta, S0)
    for row, m in enumerate(n[:2]):
        o, S = RULES[rule][1](*(a[row: row + 1, :m] for a in (q, k, v, g, beta)), S0[row: row + 1])
        _close((oc[row, :m], Sc[row]), (o[0], S[0]))
    np.testing.assert_array_equal(np.asarray(Sc[2]), np.asarray(S0[2]))


@both_rules
def test_a_fresh_row_beside_a_continued_one(rule):
    """A chunk row at position 0 comes in with a zero state (the engine passes
    it so) beside rows that continue from theirs."""
    x, S0 = _inputs(rule, 3, 3, 192)
    S0 = S0.at[1].set(0.0)
    _close(KERNEL[rule](*x, S0), RULES[rule][1](*x, S0))


@both_rules
def test_decays_of_e_88_within_ten_tokens_stay_finite(rule):
    """``A_log`` up to ln 16 under a softplus: a channel (a head) gone by e^-88
    within ten tokens, where ``exp(-G)`` overflows float32 inside one chunk
    (the control: ``test_kda.py::test_the_split_form_would_overflow...``). The
    kernel takes no exponential of a positive number: finite, and the scan's."""
    x, S0 = _inputs(rule, 4, 3, 192, rates=(8.8, 16.0, 0.0))
    assert not bool(jnp.isfinite(jnp.exp(-jnp.cumsum(x[3][:, :64], axis=1))).all())
    _close(KERNEL[rule](*x, S0), RULES[rule][1](*x, S0))


def test_one_decay_a_head_through_the_channel_path_is_the_head_path():
    """The two pair matrices are one: all of a head's channels given one decay,
    ``dstpu_kda_chunk``'s sub-blocks give what ``dstpu_gdn_chunk``'s one product
    gives, and both the scan."""
    (q, k, v, g, beta), S0 = _inputs("gdn", 5, 3, 192, rates=(0.3, 0.02))
    wide = jnp.broadcast_to(g[..., None], g.shape + (D,))
    want = gdn_recurrent(q, k, v, g, beta, S0)
    _close(KERNEL["gdn"](q, k, v, g, beta, S0), want)
    _close(KERNEL["kda"](q, k, v, wide, beta, S0), want)


# (Kimi Linear's toy in tests/unit/test_kimi_linear_serving.py runs four KDA heads a program)
@pytest.mark.parametrize("rule, nk, nv", [("kda", 1, 2), ("gdn", 2, 4)])
def test_a_key_head_serves_two_value_heads_by_index(rule, nk, nv):
    """Qwen3-Next's grouping (a program of four heads reads two key heads'
    lanes, ``h // 2``; of two heads, one), no ``jnp.repeat``; KDA's oracle
    takes q and k at the value heads."""
    (q, k, v, g, beta), S0 = _inputs(rule, 6, 2, 128, nk=nk, nv=nv)
    wide = (jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2)) if rule == "kda" else (q, k)
    _close(KERNEL[rule](q, k, v, g, beta, S0), RULES[rule][1](*wide, v, g, beta, S0))
