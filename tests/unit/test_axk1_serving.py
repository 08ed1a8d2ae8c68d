"""A.X-K1 (``model_type: axk1``) through the paged engine at a toy size with
every ratio kept, seeded weights, on the CPU: latent attention (two low-rank
query projections with a norm between, one latent vector + shared rotated key
dims a token in a pool of ONE plane), YaRN rotary in interleaved pairs, the
softmax scale times mscale squared, a dense lead layer and then a share of
sigmoid-routed experts chosen inside the best 4 of 8 groups, one group a chip.

The oracle is ``benchmarks/reference/axk1.py`` (plain float32 ``jax.numpy``,
the EXPANDED form, a full-sequence forward, no cache): chunked prefill and then
decode through the latent pool in the ABSORBED form, rows admitted and freed
mid-run, must give the reference's logits."""

import dataclasses
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2 import kv_pool
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.hf import config_from_hf
from deepspeed_tpu.parallel.moe.grouped import kept_groups, route

ref = importlib.import_module("benchmarks.reference.axk1")
HERE = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the published ratios at a toy size: 4 heads of (16 | 8) against values of 16,
# q rank 48, latent 32 + 8 rope dims = 40 a token a layer; a dense lead layer
# then 2 expert layers; 24 experts in 8 groups of 3, 4 groups kept, top 8; this
# chip is share 2 of 8: group 2, experts 6-8
HF = dict(
    model_type="axk1", vocab_size=256, hidden_size=128, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=4, intermediate_size=160,
    moe_intermediate_size=32, n_routed_experts=3, n_shared_experts=1, num_experts_per_tok=8,
    n_group=8, topk_group=4, topk_method="none", scoring_func="sigmoid", norm_topk_prob=True,
    routed_scaling_factor=2.5, first_k_dense_replace=1, moe_layer_freq=1,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rms_norm_eps=1e-6, rope_theta=10000, max_position_embeddings=4096,
    rope_scaling=dict(type="yarn", factor=32, original_max_position_embeddings=64,
                      beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1),
    tie_word_embeddings=False, attention_bias=False, seq_aux=True, ep_size=1,
    deployment_share={"n_routed_experts": 24, "chips_per_layer": 8, "share_index": 2},
)
BS = 16
# float32 engine against float32 reference: the same sums in another order and
# another FORM (absorbed against expanded); worst seen 3e-6 on logits of ~4
TOL = 3e-5


def _model(hf=HF, dtype="float32", seed=0):
    cfg = dataclasses.replace(config_from_hf(hf), dtype=dtype, remat=False)
    return cfg, T.init_params(cfg, jax.random.key(seed))


def _engine(cfg, params, dtype="float32", **extra):
    rc = {
        "dtype": dtype, "prompt_chunk": 32, "max_prompt_chunks": 2,
        "kv_cache": {"block_size": BS, "num_blocks": 40, "max_blocks_per_seq": 12,
                     "prefix_cache": False},
        "state_manager": {"max_tracked_sequences": 4, "max_ragged_batch_size": 96,
                          "max_ragged_sequence_count": 4, "max_context": 192},
    }
    for k, v in extra.items():
        rc[k] = {**rc.get(k, {}), **v} if isinstance(v, dict) else v
    return InferenceEngineV2(cfg, params, RaggedInferenceEngineConfig.from_dict(rc))


def _prompts(lens, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in lens]


def _serve_logits(eng, prompts, n_new, late=()):
    """Each prompt's logits at its last prompt token and at ``n_new[uid] - 1``
    greedy tokens after it, as the engine's steps return them. ``late``: uids
    submitted only once the first request has finished (a row admitted into
    blocks another has freed)."""
    n_new = dict(enumerate(n_new)) if not isinstance(n_new, dict) else n_new
    waiting = {u: prompts[u] for u in late}
    for uid, p in enumerate(prompts):
        if uid not in waiting:
            eng.scheduler.submit(uid, p)
    got = {uid: [] for uid in range(len(prompts))}
    toks = {uid: list(p) for uid, p in enumerate(prompts)}
    done = set()
    for _ in range(600):
        for uid, lg in eng.step().items():
            got[uid].append(np.asarray(lg, np.float32))
            if len(got[uid]) < n_new[uid]:
                toks[uid].append(int(np.argmax(lg)))
                eng.scheduler.feedback(uid, toks[uid][-1])
            else:
                eng.scheduler.finish(uid)
                done.add(uid)
        if done and waiting:
            for uid, p in waiting.items():
                eng.scheduler.submit(uid, p)
            waiting = {}
        if not eng.scheduler.has_work() and not waiting:
            break
    assert not eng.scheduler.has_work()
    return {u: np.stack(g) for u, g in got.items()}, toks


def _gap(params, hf, prompts, got, toks):
    """Worst |engine - reference| over every served logit row."""
    worst = 0.0
    for u, p in enumerate(prompts):
        want = np.asarray(ref.logits(params, np.asarray(toks[u]), hf))
        rows = want[len(p) - 1: len(p) - 1 + len(got[u])]
        worst = max(worst, float(np.abs(got[u] - rows).max()))
    return worst


# -- served logits against the reference --------------------------------------
@pytest.mark.parametrize("lens,late", [((100,), ()), ((5, 70, 100, 33), ()), ((20, 90, 60, 47), (2, 3))],
                         ids=["alone", "ragged_batch", "admitted_and_freed_mid_run"])
def test_engine_equals_the_reference_on_logits_float32(lens, late):
    """Prompts in chunks of 32 (a chunk attends to the latent blocks earlier
    chunks wrote and to its own vectors), then decode across block edges, every
    row through the one-plane pool; rows of unequal length finish at different
    steps, and in the third case two requests are admitted only after the
    first has freed its blocks."""
    cfg, params = _model()
    eng = _engine(cfg, params)
    assert eng._k_cache.shape == (3, 41, 40, BS) and eng._v_cache is None
    prompts = _prompts(lens)
    n_new = [40, 12, 25, 33][: len(lens)]
    got, toks = _serve_logits(eng, prompts, n_new, late)
    assert all(len(got[u]) == n for u, n in enumerate(n_new))
    assert _gap(params, HF, prompts, got, toks) < TOL
    acct = eng.state_manager.kv_block_accounting()
    assert acct["free"] == acct["total"] == 40


def test_engine_equals_the_reference_through_the_interpreted_kernels():
    """The three latent kernels interpreted (``dstpu_mla_decode``,
    ``dstpu_mla_chunk``, ``dstpu_mla_write``): chunks of two blocks that start
    inside a block, a decode that crosses into a new one, a pool written by
    visits staged on the host."""
    cfg, params = _model()
    eng = _engine(cfg, params, paged_attention_impl="kernel")
    prompts = _prompts((70, 9))
    got, toks = _serve_logits(eng, prompts, [30, 20])
    assert _gap(params, HF, prompts, got, toks) < TOL


def _long_prompt_engine(cfg, params, impl):
    """``prompt_chunk`` 512 as the cells have it, at the toy widths: a prompt's
    whole chunks attend EXPANDED, a lone tail of at most 128 tokens absorbed."""
    return _engine(cfg, params, prompt_chunk=512, max_prompt_chunks=1, paged_attention_impl=impl,
                   kv_cache={"num_blocks": 48, "max_blocks_per_seq": 44},
                   state_manager={"max_ragged_batch_size": 520, "max_context": 704})


@pytest.mark.parametrize("impl", ["dense", "kernel"], ids=["dense_forms", "interpreted_kernels"])
def test_a_long_prompts_chunk_attends_expanded_and_its_tail_absorbed(impl):
    """A prompt of 600 tokens is a 512-slot chunk row and then a tail of 88 in
    the 128-slot bucket: ``StepStats`` counts one expanded row and one absorbed,
    the tail attends to what the expanded chunk's step cached, and the logits
    are the one-pass reference's; then decode rows alone count no chunk row."""
    cfg, params = _model()
    eng = _long_prompt_engine(cfg, params, impl)
    prompts = _prompts((600,))
    rows = []
    step = eng.step

    def counted():
        out = step()
        rows.append((eng.last_step.prefill_tokens, eng.last_step.latent_chunk_rows,
                     eng.last_step.latent_chunk_expanded_rows))
        return out

    eng.step = counted
    got, toks = _serve_logits(eng, prompts, [6])
    assert rows[:2] == [(512, 1, 1), (88, 1, 0)] and set(rows[2:]) == {(0, 0, 0)}
    assert _gap(params, HF, prompts, got, toks) < TOL


def test_the_serving_core_folds_the_chunk_rows_by_form():
    """``latent_chunk_rows_total`` / ``latent_chunk_expanded_rows_total`` beside
    the decode rows' counters, tracing off: two prompts of 600 and 1,100 tokens,
    one chunk row a step, are three whole chunks and two tails."""
    from deepspeed_tpu.serving import SamplingParams, ServingDriver

    cfg, params = _model()
    eng = _engine(cfg, params, prompt_chunk=512, max_prompt_chunks=1,
                  kv_cache={"num_blocks": 120, "max_blocks_per_seq": 72},
                  state_manager={"max_ragged_batch_size": 520, "max_context": 1152})
    with ServingDriver(eng) as driver:
        reqs = [driver.submit(p, params=SamplingParams(max_new_tokens=3, ignore_eos=True))
                for p in _prompts((600, 1100))]
        assert all(r.wait(300) for r in reqs)
        c = dict(driver.metrics.counters)
    assert (c["latent_chunk_rows_total"], c["latent_chunk_expanded_rows_total"]) == (5, 3)
    assert c["latent_decode_rows_total"] > 0


@pytest.mark.parametrize("sampling", [{}, {"greedy": False, "temperature": 0.9, "seed": 7}],
                         ids=["greedy", "sampled"])
def test_generate_equals_the_driven_core_on_the_latent_plane(sampling):
    """``generate()`` is the served step: the same prompts through the serving
    driver give the same tokens, greedy and sampled."""
    from tests.unit.simple_model import served_tokens

    cfg, params = _model()
    prompts = _prompts((5, 70, 100, 33))
    outs = _engine(cfg, params, **sampling).generate(prompts, max_new_tokens=20)
    driven = served_tokens(_engine(cfg, params, **sampling), prompts, 20)
    for p, out, got in zip(prompts, outs, driven):
        assert [int(t) for t in out[len(p):]] == got


def test_a_prefix_hit_shares_latent_blocks():
    """The prefix cache stays on for a latent pool: a hit shares blocks by
    table, whatever a block holds, and the second request's logits are the
    reference's."""
    cfg, params = _model()
    eng = _engine(cfg, params, kv_cache={"prefix_cache": True})
    assert eng.prefix_cache is not None
    base = _prompts((64,))[0]
    prompts = [base, np.concatenate([base[:48], _prompts((20,), seed=5)[0]])]
    got, toks = _serve_logits(eng, prompts, [4, 6], late=(1,))
    assert eng.prefix_cache.hit_blocks == 3   # 48 shared tokens: three blocks of 16
    assert _gap(params, HF, prompts, got, toks) < TOL


def test_engine_in_bf16_equals_the_no_cache_forward_in_bf16():
    """bf16 weights AND compute, every expert chosen (no decision for a
    rounding to turn: top 3 of the 3 held, one group, no share), against the
    no-cache EXPANDED ``forward()`` in bf16 on the same weights: the absorbed
    form, chunks and the pool round at other places than one dense pass, and
    nothing else may differ. Measured 0.04 on logits of scale 1, limit 0.1."""
    hf = {**HF, "num_experts_per_tok": 3, "n_group": 1, "topk_group": 1, "deployment_share": None}
    cfg, params = _model(hf, dtype="bfloat16")
    eng = _engine(cfg, params, dtype="bfloat16")
    assert eng._k_cache.dtype == jnp.bfloat16
    prompts = _prompts((70, 33))
    got, toks = _serve_logits(eng, prompts, [30, 30])
    for u, p in enumerate(prompts):
        want = np.asarray(T.forward(params, jnp.asarray(toks[u])[None], cfg)[0][0], np.float32)
        np.testing.assert_allclose(got[u], want[len(p) - 1:], atol=0.1, rtol=0)


def test_absorbed_equals_expanded_on_the_same_weights():
    """One layer's attention both ways in float32: every head's keys and values
    made of the latent (the model's forward) against ``W_UK`` on the query and
    ``W_UV`` behind the output over the cached vectors (the served form)."""
    cfg, params = _model()
    lp = T.take_layer(params["layers"], cfg, 1, lambda a, i: a[i])
    s = 37
    a = jax.random.normal(jax.random.key(3), (s, cfg.hidden_size))
    pos = jnp.arange(s)
    with jax.default_matmul_precision("highest"):
        expanded, _ = T._latent_attention_block(cfg, lp, a[None], pos, None)
        q_nope, q_rope, ckv = T.latent_qkv(cfg, lp, a, pos)
        w_uk, w_uv = T.latent_up(cfg, lp)
        q = jnp.concatenate([jnp.einsum("thd,chd->thc", q_nope, w_uk), q_rope], -1)
        scores = jnp.einsum("thd,jd->htj", q, ckv) * cfg.attn_scale
        scores = jnp.where(pos[:, None] >= pos[None, :], scores, -jnp.inf)
        o_lat = jnp.einsum("htj,jc->thc", jax.nn.softmax(scores, -1), ckv[:, : cfg.kv_lora_rank])
        absorbed = jnp.einsum("thc,chd->thd", o_lat, w_uv).reshape(s, -1) @ lp["wo"]
    assert ckv.shape == (s, cfg.latent_dim)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded[0]), atol=2e-6)


# -- controls: each must FAIL the comparison ----------------------------------
def _control_gap(monkeypatch, patch, lens=(100,)):
    cfg, params = _model()
    patch(monkeypatch)
    ref.layer.clear_cache()
    try:
        prompts = _prompts(lens)
        got, toks = _serve_logits(_engine(cfg, params), prompts, [30] * len(lens))
        return _gap(params, HF, prompts, got, toks)
    finally:
        monkeypatch.undo()
        ref.layer.clear_cache()


def _patched_latent_qkv(change):
    plain = T.latent_qkv

    def patched(c, lp, a, positions, seq_len=None, **kw):
        return change(c, lp, a, positions, *plain(c, lp, a, positions, seq_len, **kw))
    return patched


CONTROLS = {
    # the rope dims of the cache left unrotated
    "cache_rope_unrotated": lambda mp: mp.setattr(T, "latent_qkv", _patched_latent_qkv(
        lambda c, lp, a, pos, qn, qr, ckv: (qn, qr, jnp.concatenate(
            [ckv[:, : c.kv_lora_rank], (a @ lp["wkv_a"])[:, c.kv_lora_rank:]], -1)))),
    # the latent stored before its norm
    "latent_before_its_norm": lambda mp: mp.setattr(T, "latent_qkv", _patched_latent_qkv(
        lambda c, lp, a, pos, qn, qr, ckv: (qn, qr, jnp.concatenate(
            [(a @ lp["wkv_a"])[:, : c.kv_lora_rank], ckv[:, c.kv_lora_rank:]], -1)))),
    # W_UK of a neighbouring head
    "w_uk_of_a_neighbouring_head": lambda mp: mp.setattr(T, "latent_up", (
        lambda plain: lambda c, lp: (jnp.roll(plain(c, lp)[0], 1, axis=1), plain(c, lp)[1]))(
            T.latent_up)),
    # the softmax scale without m^2
    "scale_without_mscale": lambda mp: mp.setattr(
        ref, "softmax_scale",
        lambda hf: (int(hf["qk_nope_head_dim"]) + int(hf["qk_rope_head_dim"])) ** -0.5),
    # groups ignored: plain top 8 of 24
    "groups_ignored": lambda mp: mp.setattr(ref, "routing_weights", (
        lambda plain: lambda x, router, bias, **kw: plain(
            x, router, bias, **{**kw, "n_group": 1, "topk_group": 1}))(ref.routing_weights)),
    # rotary in halves where the pairs are interleaved
    "rope_pairs_in_halves": lambda mp: mp.setattr(ref, "rope_interleaved", (
        lambda x, pos, inv, f: jnp.concatenate([
            x[..., : x.shape[-1] // 2] * (jnp.cos(pos[:, None] * inv[None]) * f)[:, None]
            - x[..., x.shape[-1] // 2:] * (jnp.sin(pos[:, None] * inv[None]) * f)[:, None],
            x[..., x.shape[-1] // 2:] * (jnp.cos(pos[:, None] * inv[None]) * f)[:, None]
            + x[..., : x.shape[-1] // 2] * (jnp.sin(pos[:, None] * inv[None]) * f)[:, None]], -1))),
}


@pytest.mark.parametrize("name", sorted(CONTROLS))
def test_control_fails_the_comparison(monkeypatch, name):
    """The program or the reference changed on purpose: each reads hundreds of
    times the tolerance through the same comparison."""
    assert _control_gap(monkeypatch, CONTROLS[name]) > 300 * TOL


# -- the grouped router ---------------------------------------------------------
def _grouped_cfg(bias):
    return dataclasses.replace(
        T.TransformerConfig(n_experts=24, moe_top_k=8, moe_score="sigmoid", moe_n_group=8,
                            moe_topk_group=4, moe_routed_scale=2.5, moe_router_bias=bias))


@pytest.mark.parametrize("biased", [False, True], ids=["max_rule", "top2_sum_rule"])
def test_route_with_groups_equals_the_reference_on_drawn_scores(biased):
    """Both group rules against the reference's routing on drawn logits: the
    dense [t, E] weights the reference builds equal the (value, id) pairs
    ``route()`` returns, scattered."""
    cfg = _grouped_cfg(biased)
    logits = jax.random.normal(jax.random.key(11), (64, 24)) * 2.0
    bias = jax.random.normal(jax.random.key(12), (24,)) * 0.3 if biased else None
    top_p, top_e, _, kept = route(cfg, logits, bias=bias)
    got = jnp.sum(jax.nn.one_hot(top_e, 24) * top_p[..., None], axis=1)
    # (the reference routes on ``x @ router``: the identity hands it the logits)
    want = ref.routing_weights(logits, jnp.eye(24), bias, top_k=8, scale=2.5, n_group=8,
                               topk_group=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(top_p.sum(-1)), 2.5, rtol=1e-6)
    # every chosen expert lies in a kept group, and 4 groups are kept
    assert bool(jnp.all(jnp.take_along_axis(kept, top_e // 3, axis=1)))
    assert bool(jnp.all(kept.sum(-1) == 4))


def test_a_group_that_wins_on_one_expert_and_ties():
    """Scores by hand. Group 0 holds ONE high expert and two at the floor;
    group 1 three middling ones. Under the maximum rule group 0 is kept before
    group 1; under the top-two-sum rule (a router with a bias, here zero) group
    1's two middling ones beat group 0's one. Equal scores break to the lower
    expert number, as ``jax.lax.top_k`` and ``torch.topk`` on sorted ties do."""
    cfg = dataclasses.replace(_grouped_cfg(False), moe_topk_group=1, moe_top_k=2)
    cfg_b = dataclasses.replace(cfg, moe_router_bias=True)
    s = np.full((1, 24), 0.05, np.float32)
    s[0, 0] = 0.9                      # group 0 wins on one expert
    s[0, 3:6] = 0.6                    # group 1: three tied
    logits = jnp.log(s / (1 - s))      # sigmoid^-1
    kept = kept_groups(cfg, jax.nn.sigmoid(logits), biased=False)
    assert kept[0].tolist() == [True] + [False] * 7
    _, top_e, *_ = route(cfg, logits)
    assert top_e[0].tolist() == [0, 1]          # inside group 0: 0.9, then the tie's lowest
    kept_b = kept_groups(cfg_b, jax.nn.sigmoid(logits), biased=True)
    assert kept_b[0].tolist() == [False, True] + [False] * 6   # 1.2 > 0.95
    _, top_e, *_ = route(cfg_b, logits, bias=jnp.zeros(24))
    assert top_e[0].tolist() == [3, 4]          # three tied: the two lowest numbers


def test_the_eight_shares_parts_add_up_to_the_uncut_layer():
    """The SHARE test: each of the 8 chips that share a layer routes over all 24
    experts in their 8 groups and computes its own group's 3; the eight partial
    results, the ungated shared expert counted once, add up to the reference's
    uncut layer over all 24; and the grouped counter's last entry says how many
    tokens kept a share's group."""
    from deepspeed_tpu.parallel.moe import moe_mlp
    from deepspeed_tpu.parallel.moe.sharded_moe import _moe_tail

    uncut_hf = {**HF, "n_routed_experts": 24, "deployment_share": None}
    cfg_all, params = _model(uncut_hf)
    lp_all = T.take_layer(params["layers"], cfg_all, 1, lambda a, i: a[i])  # the first expert layer
    x = jax.random.normal(jax.random.key(7), (1, 40, 128))
    with jax.default_matmul_precision("highest"):
        parts = []
        for share in range(8):
            cfg = dataclasses.replace(cfg_all, n_experts=3, moe_experts_total=24, moe_expert_shard=share)
            lp = {k: (v[3 * share: 3 * share + 3] if k in ("w_up", "w_gate", "w_down") else v)
                  for k, v in lp_all.items()}
            out, _, counts = moe_mlp(cfg, lp, x)
            parts.append((out[0], counts))
        shared = _moe_tail(cfg_all, lp_all, x[0], jnp.zeros_like(x[0]))
        total = sum(o - shared for o, _ in parts) + shared
        want = ref.sparse_mlp(x[0], params["layers"]["sparse"], 0, first=0, top_k=8, scale=2.5,
                              n_group=8, topk_group=4)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=1e-5)
    assert all(c.shape == (4,) for _, c in parts)
    assert sum(int(c[:3].sum()) for _, c in parts) == 40 * 8   # every pair is some share's
    assert sum(int(c[3]) for _, c in parts) == 40 * 4          # a token keeps 4 of the 8 groups


def test_exaone_moe_with_groups_takes_the_bias_rule():
    """``exaone_moe`` no longer refuses ``n_group`` / ``topk_group``: its router
    has a selection bias, so a group scores the sum of its two largest ``score +
    bias`` (DeepseekV3TopkRouter); with ``n_group 1`` the configuration is the
    one it was."""
    from tests.unit.test_k_exaone_serving import HF as EXAONE

    one = config_from_hf(EXAONE)
    assert (one.moe_n_group, one.moe_topk_group, one.moe_router_bias) == (1, 1, True)
    grouped = config_from_hf({**EXAONE, "n_group": 4, "topk_group": 2})
    assert (grouped.moe_n_group, grouped.moe_topk_group, grouped.router_width) == (4, 2, 16)
    logits = jax.random.normal(jax.random.key(5), (32, 16))
    bias = jax.random.normal(jax.random.key(6), (16,)) * 0.2
    top_p, top_e, *_ = route(grouped, logits, bias=bias)
    got = jnp.sum(jax.nn.one_hot(top_e, 16) * top_p[..., None], axis=1)
    want = ref.routing_weights(logits, jnp.eye(16), bias, top_k=3, scale=2.5, n_group=4, topk_group=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-7)
    with pytest.raises(ValueError, match="n_group"):
        config_from_hf({**EXAONE, "n_group": 3, "topk_group": 1})   # 16 experts in 3 groups


# -- config_from_hf, the loader, the refusals -----------------------------------
def test_config_from_hf_on_the_published_keys():
    row = json.load(open(os.path.join(HERE, "benchmarks", "configs", "a.x-k1.json")))
    assert sorted(row["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    cfg = config_from_hf({**row, **row["published"], "deployment_share": None})
    assert (cfg.n_layers, cfg.hidden_size, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (61, 7168, 64, 64, 192)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim) == (
        1536, 512, 128, 64, 128)
    assert cfg.latent and cfg.latent_dim == 576 and cfg.rope_interleave
    m = 0.1 * np.log(32) + 1
    assert abs(cfg.attn_scale - 192 ** -0.5 * m * m) < 1e-12 and abs(m - 1.3466) < 1e-4
    assert dict(cfg.rope_scaling)["rope_type"] == "yarn" and T.rope_params(cfg, 64)[1] == 1.0
    assert (cfg.moe_dense_lead, cfg.ffn_dim, cfg.expert_dim, cfg.moe_shared_expert_dim) == (1, 18432, 2048, 2048)
    assert (cfg.n_experts, cfg.router_width, cfg.moe_top_k, cfg.moe_n_group, cfg.moe_topk_group) == (
        192, 192, 8, 8, 4)
    assert (cfg.moe_score, cfg.moe_router_bias, cfg.moe_routed_scale, cfg.moe_shared_gated) == (
        "sigmoid", False, 2.5, False)
    assert (cfg.vocab_size, cfg.tie_embeddings, cfg.norm_eps) == (163840, False, 1e-6)
    cut = config_from_hf(row)  # the benchmark's cut: a stage's layers, one group of the experts
    assert (cut.n_layers, cut.n_experts, cut.router_width, cut.moe_expert_shard, cut.vocab_size) == (
        5, 24, 192, 0, 20480)
    shapes = jax.eval_shape(lambda k: T.init_params(cut, k), jax.random.key(0))
    assert "router_bias" not in shapes["layers"]["sparse"]
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert n == 5_605_186_560   # 11.21 GB in bf16, as the configuration file counts it


def test_pool_accounting_at_the_cells_sizes():
    """One plane of 576 a token a layer: 147,456 bytes a block a layer, and the
    cell's 2 GB hold 2,712 blocks, one of them the pool's trash."""
    row = json.load(open(os.path.join(HERE, "benchmarks", "configs", "a.x-k1.json")))
    cell = json.load(open(os.path.join(
        HERE, "benchmarks", "cells", "a.x-k1.serve-doc-long-closed64.json")))["serve_args"]
    cfg = config_from_hf(row)
    heads, dim, planes = kv_pool.pool_geometry(cfg)
    assert (heads, dim, planes) == (1, 576, 1)
    per = kv_pool.bytes_per_block(128, heads, dim, cfg.kv_layers, planes=planes)
    assert per == 5 * 147_456 and per // 128 == 5_760
    n = kv_pool.blocks_for_budget(cell["--kv-pool-bytes"], 128, heads, dim, cfg.kv_layers, planes=planes)
    assert n + 1 == 2_712 and n * 128 > 347_000
    assert kv_pool.pool_bytes(n, 128, heads, dim, cfg.kv_layers, planes=planes) <= 2_000_000_000
    # per-head keys and values of the same model: 40 KiB a token a layer
    assert kv_pool.bytes_per_block(128, 64, 192 + 128, 5, planes=1) // 128 == 5 * 40 * 1024
    # ... and the CLI sizes the engine's pool by the same arithmetic
    from deepspeed_tpu.inference.cli import engine_config_from_args, serve_parse_args

    argv = ["--model", "", "--port", "0"]
    for flag, value in cell.items():
        argv += [flag, str(value)]
    rc = engine_config_from_args(serve_parse_args(argv), cfg)
    assert rc.kv_cache.num_blocks == n
    # a model with K and V planes is counted as it was
    assert kv_pool.bytes_per_block(128, 8, 128, 2) == 2 * 2 * 128 * 8 * 128 * 2


def test_health_reports_the_one_plane():
    cfg, params = _model()
    info = _engine(cfg, params).kv_pool_info()
    assert info["kv_bytes_per_block"] == 3 * BS * 40 * 2
    assert info["kv_pool_bytes"] == 41 * info["kv_bytes_per_block"]


@pytest.mark.parametrize("change,match", [
    ({"deployment_share": {"n_routed_experts": 24, "chips_per_layer": 5}}, "not one chip's share"),
    ({"n_group": 8, "topk_group": 9}, "n_group"),
    ({"n_group": 5}, "moe_n_group"),
    ({"topk_group": 2}, "moe_n_group"),          # 2 groups of 3 hold fewer than top 8
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"first_k_dense_replace": 0}, "first_k_dense_replace"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"q_lora_rank": None}, "q_lora_rank"),
    ({"attention_bias": True}, "attention_bias"),
    ({"rope_scaling": {"type": "ntk", "factor": 2}}, "rope_scaling"),
], ids=["share", "more_groups_kept_than_there_are", "groups_do_not_divide", "too_few_kept",
        "softmax", "topk_method", "no_lead", "layer_freq", "no_q_rank", "bias", "rope_scaling"])
def test_config_from_hf_refuses_what_it_cannot_compute(change, match):
    with pytest.raises(ValueError, match=match):
        config_from_hf({**HF, **change})


@pytest.mark.parametrize("extra,match", [
    ({"kv_cache": {"kv_cache_dtype": "int8"}}, "int8 pool's scale planes"),
    ({"kv_cache": {"host_tier_bytes": 1 << 20, "prefix_cache": True}}, "host block tier"),
    ({"spec_k": 2}, "speculative"),
], ids=["int8_pool", "host_tier", "speculative"])
def test_what_cannot_carry_one_plane_refuses_at_build(extra, match):
    cfg, params = _model()
    with pytest.raises(NotImplementedError, match=match):
        _engine(cfg, params, **extra)


@pytest.mark.parametrize("mover", ["export_kv_blocks", "import_kv_blocks", "export_kv_blocks_device"])
def test_a_mover_of_kv_planes_refuses_the_latent_plane(mover):
    """Handoff, recovery and peer pulls move K and V planes: a latent pool is
    refused with its reason, never misread."""
    cfg, params = _model()
    eng = _engine(cfg, params)
    args = ([0], {}) if mover == "import_kv_blocks" else ([0],)
    with pytest.raises(NotImplementedError, match="one latent plane"):
        getattr(eng, mover)(*args)


def test_v1_decode_step_refuses_the_architecture():
    cfg, params = _model()
    caches = [(None, None, 0)] * cfg.n_layers
    with pytest.raises(NotImplementedError):
        T.decode_step(params, jnp.zeros((1, 1), jnp.int32), cfg, caches, jnp.zeros((1, 1), jnp.int32))


def test_load_hf_model_reads_a_checkpoint_with_deepseek_v3s_names(tmp_path):
    """A checkpoint written under the names ``_axk1_layer`` reads (all 24
    experts, no selection bias) comes back as the seeded tree: this chip's
    group of the experts, the router whole."""
    import torch
    from safetensors.torch import save_file

    from deepspeed_tpu.models.hf import load_hf_model

    cfg, params = _model()
    _, whole = _model({**HF, "n_routed_experts": 24, "deployment_share": None})
    L, lw = params["layers"], whole["layers"]
    state = {"model.embed_tokens.weight": params["embed"], "model.norm.weight": params["final_norm"],
             "lm_head.weight": params["lm_head"].T}
    attn = (("wq_a", "q_a_proj"), ("wq_b", "q_b_proj"), ("wkv_a", "kv_a_proj_with_mqa"),
            ("wkv_b", "kv_b_proj"), ("wo", "o_proj"))
    mlp = (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj"))
    for i in range(3):
        p = f"model.layers.{i}"
        state[f"{p}.input_layernorm.weight"] = L["attn_norm"][i]
        state[f"{p}.post_attention_layernorm.weight"] = L["mlp_norm"][i]
        for name, hf in attn:
            state[f"{p}.self_attn.{hf}.weight"] = L[name][i].T
        state[f"{p}.self_attn.q_a_layernorm.weight"] = L["q_a_norm"][i]
        state[f"{p}.self_attn.kv_a_layernorm.weight"] = L["kv_a_norm"][i]
        if i == 0:
            for name, hf in mlp:
                state[f"{p}.mlp.{hf}.weight"] = L["lead"][name][0].T
            continue
        state[f"{p}.mlp.gate.weight"] = L["sparse"]["router"][i - 1].T
        for name, hf in mlp:
            state[f"{p}.mlp.shared_experts.{hf}.weight"] = L["sparse"][f"shared_{name[2:]}"][i - 1].T
            for e in range(24):
                # this chip's group (experts 6-8) holds the seeded tree's; the rest another's
                w = L["sparse"][name][i - 1][e - 6] if 6 <= e < 9 else lw["sparse"][name][i - 1][e]
                state[f"{p}.mlp.experts.{e}.{hf}.weight"] = w.T
    save_file({k: torch.tensor(np.ascontiguousarray(np.asarray(v, np.float32)))
               for k, v in state.items()}, str(tmp_path / "model.safetensors"))
    json.dump(HF, open(tmp_path / "config.json", "w"))
    got_cfg, got = load_hf_model(str(tmp_path), dtype="float32")
    assert dataclasses.replace(got_cfg, remat=False) == cfg
    flat_want = jax.tree_util.tree_leaves_with_path(params)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got))
    assert set(flat_got) == {k for k, _ in flat_want}
    for k, v in flat_want:
        np.testing.assert_array_equal(np.asarray(flat_got[k]), np.asarray(v), err_msg=str(k))
