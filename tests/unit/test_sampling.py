"""Sampling in the serving engines (VERDICT r4 missing #2).

Reference semantics: v1 guard-railed generate (reference
inference/engine.py:585) + FastGen/MII sampled decoding on top of v2
logits. Covers the shared sampler's filters and distribution, v1/v2
agreement, per-sequence EOS with a step in flight, and logprobs."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.sampling import filter_logits, sample_tokens

pytestmark = pytest.mark.smoke


class TestFilters:
    def test_top_k_masks_exactly_k(self):
        logits = jnp.asarray([[5.0, 4.0, 3.0, 2.0, 1.0]])
        out = np.asarray(filter_logits(logits, top_k=2))
        assert np.isfinite(out[0, :2]).all()
        assert (out[0, 2:] < -1e29).all()

    def test_top_p_nucleus_keeps_crossing_token(self):
        # probs ~ [0.643, 0.236, 0.087, 0.032, ...]: top_p=0.8 keeps the
        # crossing token (cumulative 0.879) but not the next
        logits = jnp.asarray([[4.0, 3.0, 2.0, 1.0, 0.0]])
        out = np.asarray(filter_logits(logits, top_p=0.8))
        assert np.isfinite(out[0, :2]).all()
        assert (out[0, 2:] < -1e29).all()

    def test_top_p_one_keeps_all(self):
        logits = jnp.asarray([[4.0, 3.0, 2.0]])
        out = np.asarray(filter_logits(logits, top_p=1.0))
        assert np.isfinite(out).all()


class TestSampleTokens:
    def test_greedy_is_argmax(self):
        rng = jax.random.key(0)
        logits = jnp.asarray(np.random.default_rng(0).normal(size=(4, 16)), jnp.float32)
        toks = np.asarray(sample_tokens(logits, rng, greedy=True))
        np.testing.assert_array_equal(toks, np.argmax(np.asarray(logits), -1))

    def test_matches_v1_sampler_plain_temperature(self):
        """Same rng + temperature, no filters: identical draws to the v1
        engine's categorical (the two paths must not drift)."""
        from deepspeed_tpu.inference.engine import _sample

        rng = jax.random.key(7)
        logits = jnp.asarray(np.random.default_rng(1).normal(size=(8, 32)), jnp.float32)
        a = np.asarray(sample_tokens(logits, rng, temperature=0.7, greedy=False))
        b = np.asarray(_sample(logits, rng, jnp.float32(0.7), jnp.bool_(False)))
        np.testing.assert_array_equal(a, b)

    def test_distribution_tracks_softmax(self):
        """Empirical frequencies over many draws match the temperature
        softmax (loose tolerance, fixed seed: deterministic test)."""
        logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]], jnp.float32)
        temp = 0.9
        n = 4000
        keys = jax.random.split(jax.random.key(3), n)
        draws = np.asarray(
            jax.vmap(lambda k: sample_tokens(logits, k, temperature=temp, greedy=False))(keys)
        ).reshape(-1)
        freq = np.bincount(draws, minlength=4) / n
        want = np.asarray(jax.nn.softmax(logits[0] / temp))
        np.testing.assert_allclose(freq, want, atol=0.03)

    def test_logprobs_match_distribution(self):
        logits = jnp.asarray(np.random.default_rng(2).normal(size=(4, 16)), jnp.float32)
        toks, logp = sample_tokens(
            logits, jax.random.key(0), temperature=0.8, greedy=False,
            top_k=8, return_logprobs=True,
        )
        dist = filter_logits(logits, top_k=8) / 0.8
        want = np.asarray(jax.nn.log_softmax(dist, axis=-1))
        got = np.asarray(logp)
        for r in range(4):
            np.testing.assert_allclose(got[r], want[r, int(toks[r])], rtol=1e-5)


def _make_v2(greedy=True, temperature=1.0, top_k=0, top_p=0.0, seed=0):
    from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import TransformerConfig, init_params

    mc = TransformerConfig(
        vocab_size=128, hidden_size=64, n_layers=2, n_heads=4, n_kv_heads=2,
        max_seq_len=256, dtype="float32",
    )
    params = init_params(mc, jax.random.key(11))
    rc = RaggedInferenceEngineConfig.from_dict({
        "dtype": "float32",
        "greedy": greedy, "temperature": temperature, "top_k": top_k,
        "top_p": top_p, "seed": seed,
        "kv_cache": {"block_size": 16, "num_blocks": 64, "max_blocks_per_seq": 8},
    })
    return InferenceEngineV2(mc, params, rc)


class TestV2Sampling:
    def test_sampled_rounds_deterministic_per_seed(self):
        prompts = [np.arange(1, 9, dtype=np.int32), np.arange(20, 30, dtype=np.int32)]
        a = _make_v2(greedy=False, temperature=0.8, seed=5).generate(
            [p.copy() for p in prompts], max_new_tokens=8)
        b = _make_v2(greedy=False, temperature=0.8, seed=5).generate(
            [p.copy() for p in prompts], max_new_tokens=8)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
        c = _make_v2(greedy=False, temperature=0.8, seed=6).generate(
            [p.copy() for p in prompts], max_new_tokens=8)
        assert any(not np.array_equal(x, y) for x, y in zip(a, c))

    def test_greedy_config_matches_plain_argmax_flow(self):
        prompts = [np.arange(1, 9, dtype=np.int32)]
        a = _make_v2(greedy=True).generate([p.copy() for p in prompts], max_new_tokens=6)
        b = _make_v2(greedy=True, seed=9).generate([p.copy() for p in prompts], max_new_tokens=6)
        np.testing.assert_array_equal(a[0], b[0])  # greedy ignores the seed

    def test_verify_step_logprobs_exposed(self):
        """The verify step returns its emitted tokens' log-probabilities
        (``last_logprobs``); the streams it continues are ``generate()``'s."""
        prompts = [np.arange(1, 9, dtype=np.int32), np.arange(30, 38, dtype=np.int32)]
        want = _make_v2(greedy=False, temperature=0.9).generate(
            [p.copy() for p in prompts], max_new_tokens=8)
        eng = _make_v2(greedy=False, temperature=0.9)
        for uid, p in enumerate(prompts):
            eng.scheduler.submit(uid, p)
        at = {0: 8, 1: 8}  # the position of a row's next token
        while eng.scheduler.has_pending():
            for uid, tok in eng.step_tokens().items():
                assert tok == want[uid][at[uid]]
                at[uid] += 1
                eng.scheduler.feedback(uid, tok)
        # row 0 drafts its own next two tokens, row 1 nothing
        res = eng.spec_round(3, drafts={0: [int(t) for t in want[0][at[0]:at[0] + 2]]})
        assert [int(t) for t in res[0]] == [int(t) for t in want[0][at[0]:at[0] + 3]]
        assert [int(t) for t in res[1]] == [int(want[1][at[1]])]
        assert {u: lp.shape for u, lp in eng.last_logprobs.items()} == {0: (3,), 1: (1,)}
        assert all(np.isfinite(lp).all() and (lp <= 0).all() for lp in eng.last_logprobs.values())

    def test_mixed_eos_lengths(self):
        """Per-sequence EOS with a step in flight: rows stop at their own
        lengths. Probe the greedy streams first, then pick an eos id that
        one row emits early and the other never emits."""
        probe = _make_v2(greedy=True)
        prompts = [np.arange(1, 9, dtype=np.int32), np.arange(40, 48, dtype=np.int32)]
        outs = probe.generate([p.copy() for p in prompts], max_new_tokens=8)
        gen0 = list(outs[0][8:])
        gen1 = list(outs[1][8:])
        # an id generated early by row 0 and never by row 1
        eos = next((t for t in gen0[:3] if t not in gen1), None)
        if eos is None:
            pytest.skip("probe streams overlap; cannot construct a clean eos")
        eng = _make_v2(greedy=True)
        outs2 = eng.generate([p.copy() for p in prompts], max_new_tokens=8,
                             eos_token_id=int(eos))
        g0, g1 = list(outs2[0][8:]), list(outs2[1][8:])
        assert g0[-1] == eos and len(g0) <= 3  # stopped early at ITS eos
        assert len(g1) == 8 and g1 == gen1     # unaffected row runs out
