"""LongCat-Flash (``model_type: longcat_flash``) through the paged engine at a
toy size with every ratio kept, seeded weights, on the CPU: a layer of TWO
sub-blocks (latent attention with its two LoRA scales, a dense MLP) and ONE
expert block on a shortcut across them, a softmax router that chooses on
probability + bias over real and identity experts, a cache of two planes a
layer.

The oracle is ``benchmarks/reference/longcat_flash.py`` (plain float32
``jax.numpy``, the EXPANDED form, a full-sequence forward, no cache): chunked
prefill and then decode through the latent pool in the ABSORBED form, rows
admitted and freed mid-run, must give the reference's logits."""

import dataclasses
import functools
import hashlib
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
from deepspeed_tpu.parallel.moe import grouped, moe_mlp
from deepspeed_tpu.parallel.moe.grouped import route

ref = importlib.import_module("benchmarks.reference.longcat_flash")
HERE = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the published ratios at a toy size: 2 layers (4 sub-blocks, 4 cache planes) of
# hidden 64; 4 heads of (16 | 8) against values of 16, q rank 24, latent 16 + 8
# rope dims = 24 a token a plane; 24 experts + 12 identity ids, top 4, x 6; this
# chip is share 2 of 8: experts 6-8
HF = dict(
    model_type="longcat_flash", vocab_size=256, hidden_size=64, num_layers=2,
    num_attention_heads=4, ffn_hidden_size=96, expert_ffn_hidden_size=32, n_routed_experts=3,
    zero_expert_num=12, zero_expert_type="identity", moe_topk=4, routed_scaling_factor=6,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    mla_scale_q_lora=True, mla_scale_kv_lora=True, attention_method="MLA", attention_bias=False,
    rms_norm_eps=1e-5, rope_theta=10000000, max_position_embeddings=4096,
    deployment_share={"n_routed_experts": 24, "chips_per_layer": 8, "share_index": 2},
)
UNCUT = {**HF, "n_routed_experts": 24, "deployment_share": None}
BS = 16
# float32 engine against float32 reference: the same sums in another order and
# another FORM (absorbed against expanded); worst seen 2e-6 on logits of ~4
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
    submitted only once the first request has finished."""
    n_new = dict(enumerate(n_new))
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


# -- (1) the full pass, (2) the served steps, against the reference -------------------
@pytest.mark.parametrize("hf", [HF, UNCUT], ids=["share_2_of_8", "uncut"])
def test_forward_equals_the_reference_on_logits_float32(hf):
    """``forward()`` runs the six published lines in the expanded form, under a
    scan over layers whose sub-block stacks are viewed [layers, 2, ...]. float32
    against float32: rounding in another order, worst seen 2e-6."""
    cfg, params = _model(hf)
    toks = _prompts((70,))[0]
    got = T.forward(params, toks[None], cfg)[0][0]
    want = ref.logits(params, toks, hf)
    assert float(jnp.abs(got - want).max()) < TOL
    assert float(jnp.abs(want).max()) > 1.0     # logits of unit scale: the tolerance means something


@pytest.mark.parametrize("lens,late", [((100,), ()), ((5, 70, 100, 33), ()), ((20, 90, 60, 47), (2, 3))],
                         ids=["alone", "ragged_batch", "admitted_and_freed_mid_run"])
def test_engine_equals_the_reference_on_logits_float32(lens, late):
    """Prompts in chunks of 32 (a chunk attends, in each of a layer's two
    sub-blocks, to that sub-block's plane and to its own vectors), then decode
    across block edges; rows of unequal length finish at different steps, and in
    the third case two requests are admitted only after the first has freed its
    blocks. The pool is 2 x layers planes deep."""
    cfg, params = _model()
    eng = _engine(cfg, params)
    assert cfg.kv_layers == 4 and eng._k_cache.shape == (4, 41, 24, BS) and eng._v_cache is None
    prompts = _prompts(lens)
    n_new = [40, 12, 25, 33][: len(lens)]
    got, toks = _serve_logits(eng, prompts, n_new, late)
    assert all(len(got[u]) == n for u, n in enumerate(n_new))
    assert _gap(params, HF, prompts, got, toks) < TOL
    acct = eng.state_manager.kv_block_accounting()
    assert acct["free"] == acct["total"] == 40


def test_engine_equals_the_reference_through_the_interpreted_kernels():
    """The three latent kernels interpreted, each called once a PLANE."""
    cfg, params = _model()
    eng = _engine(cfg, params, paged_attention_impl="kernel")
    prompts = _prompts((70, 9))
    got, toks = _serve_logits(eng, prompts, [30, 20])
    assert _gap(params, HF, prompts, got, toks) < TOL


def test_bf16_where_float32_is_stated_fails_the_tolerance():
    """The same comparison with the engine in bfloat16 misses the float32
    tolerance by orders of magnitude: it is tight enough to tell a precision
    step."""
    cfg, params = _model()
    prompts = _prompts((70,))
    got, toks = _serve_logits(_engine(cfg, params, dtype="bfloat16"), prompts, [20])
    assert _gap(params, HF, prompts, got, toks) > 100 * TOL


def test_engine_in_bf16_equals_the_no_cache_forward_in_bf16():
    """bf16 weights AND compute, every id chosen (no decision for a rounding to
    turn: top 15 of 3 held + 12 identity, no share), against the no-cache
    EXPANDED ``forward()`` in bf16 on the same weights: the absorbed form,
    chunks and the pool round at other places than one dense pass, and nothing
    else may differ. Measured 0.05 on logits of scale 1, limit 0.12."""
    hf = {**HF, "moe_topk": 15, "deployment_share": None}
    cfg, params = _model(hf, dtype="bfloat16")
    eng = _engine(cfg, params, dtype="bfloat16")
    assert eng._k_cache.dtype == jnp.bfloat16
    prompts = _prompts((70, 33))
    got, toks = _serve_logits(eng, prompts, [30, 30])
    for u, p in enumerate(prompts):
        want = np.asarray(T.forward(params, jnp.asarray(toks[u])[None], cfg)[0][0], np.float32)
        np.testing.assert_allclose(got[u], want[len(p) - 1:], atol=0.12, rtol=0)


# -- (3) the share test ----------------------------------------------------------------
def test_the_eight_shares_parts_add_up_to_the_uncut_layer():
    """Each of the 8 chips that share a layer routes over all 36 ids and computes
    its own 3 experts' part; every chip also adds the identity part for its own
    tokens. The eight held parts, the identity part counted ONCE, add up to the
    reference's uncut expert block; with the two dense sub-blocks (every chip's,
    counted once) that is the reference's uncut layer."""
    cfg_all, params = _model(UNCUT)
    layers = params["layers"]
    lp_all = {k: v[1] for k, v in layers.items() if k != "sub"}
    x = jax.random.normal(jax.random.key(7), (1, 40, 64))
    with jax.default_matmul_precision("highest"):
        parts, zero_pairs = [], []
        for share in range(8):
            cfg = dataclasses.replace(cfg_all, n_experts=3, moe_experts_total=24, moe_expert_shard=share)
            lp = {k: (v[3 * share: 3 * share + 3] if k in ("w_up", "w_gate", "w_down") else v)
                  for k, v in lp_all.items()}
            out, _, counts = moe_mlp(cfg, lp, x)
            parts.append(out[0])
            assert counts.shape == (4,)
            zero_pairs.append(int(counts[3]))
        # the identity part, which every chip's output holds: counted once
        top_p, top_e, *_ = route(cfg_all, x[0] @ lp_all["router"], bias=lp_all["router_bias"])
        identity = jnp.sum(jnp.where(top_e >= 24, top_p, 0.0), axis=1, keepdims=True) * x[0]
        total = sum(p - identity for p in parts) + identity
        want = ref.expert_block(x[0], layers, 1, first=0, total=24, top_k=4, scale=6.0)
        whole, _, counts_all = moe_mlp(cfg_all, lp_all, x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(whole[0]), np.asarray(want), atol=1e-5)
    assert len(set(zero_pairs)) == 1 and zero_pairs[0] == int(counts_all[24])   # every chip's alike
    assert 0 < zero_pairs[0] < 40 * 4 and int(counts_all[:24].sum()) + zero_pairs[0] == 40 * 4
    # ... and the layer: shares summed inside the six lines equal the uncut reference's layer
    xs = x[0]
    kw = dict(nh=4, dn=16, dr=8, dv=16, rank=16, eps=1e-5, q_scale=(64 / 24) ** 0.5,
              kv_scale=2.0, top_k=4, route_scale=6.0, first=0, total=24)
    inv = jnp.asarray((1.0 / 1e7 ** (np.arange(0, 8, 2) / 8)).astype(np.float32))
    want_layer = ref.layer(xs, layers, inv, 1, **kw)
    got_layer = T._layer(cfg_all, {**lp_all, "sub": jax.tree.map(lambda a: a[2:4], layers["sub"])},
                         x, jnp.arange(40), None)[0][0]
    np.testing.assert_allclose(np.asarray(got_layer), np.asarray(want_layer), atol=2e-5)


# -- (4) the router ------------------------------------------------------------------------
def _router_cfg(**kw):
    return T.TransformerConfig(n_experts=8, moe_zero_experts=4, moe_top_k=3, moe_score="softmax",
                               moe_norm_topk_prob=False, moe_routed_scale=6.0, moe_drop_tokens=False,
                               **kw)


ROUTER_CASES = {
    # (probabilities over 12 ids, bias, the ids chosen, in top_k's order of p + b)
    "chosen_on_p_plus_b_weighed_by_p": (
        [.30, .20, .10, .08, .07, .06, .05, .04, .04, .03, .02, .01],
        [0, 0, 0, 0, 0, 0, 0, 0, 0, 0, .5, 0], [10, 0, 1]),
    "all_identity": (
        [.02, .02, .02, .02, .02, .02, .02, .02, .30, .25, .20, .09],
        [0] * 12, [8, 9, 10]),
    "all_real": (
        [.40, .30, .20, .02, .02, .01, .01, .01, .01, .01, .005, .005],
        [0] * 12, [0, 1, 2]),
    "a_bias_that_keeps_an_id_out": (
        [.30, .20, .10, .08, .07, .06, .05, .04, .04, .03, .02, .01],
        [-1, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0], [1, 2, 3]),
}


@pytest.mark.parametrize("name", sorted(ROUTER_CASES))
def test_router_cases_by_hand(name):
    """The softmax law with a selection bias: the k ids CHOSEN on ``p + b``, each
    WEIGHED by ``p`` alone, not renormalised, times 6; and what the expert block
    makes of them: an identity pair adds ``gate x m`` and reaches no kernel row."""
    p, b, want_ids = ROUTER_CASES[name]
    cfg = _router_cfg()
    logits = jnp.log(jnp.asarray([p], jnp.float32))
    p = np.asarray(p) / np.sum(p)
    top_p, top_e, _, kept = route(cfg, logits, bias=jnp.asarray(b, jnp.float32))
    assert kept is None and top_e[0].tolist() == want_ids
    np.testing.assert_allclose(np.asarray(top_p[0]), 6.0 * p[want_ids], rtol=1e-5)
    assert abs(float(top_p.sum()) - 6.0 * p[want_ids].sum()) < 1e-5       # no renormalisation
    # the block on one token m: held experts through the grouped matmul, identity pairs as m
    h, ed = 16, 8
    ks = jax.random.split(jax.random.key(3), 4)
    lp = {"router_bias": jnp.asarray(b, jnp.float32),
          "w_up": jax.random.normal(ks[0], (8, h, ed)), "w_gate": jax.random.normal(ks[1], (8, h, ed)),
          "w_down": jax.random.normal(ks[2], (8, ed, h))}
    m = jax.random.normal(ks[3], (1, h))
    cfg = dataclasses.replace(cfg, hidden_size=h, dtype="float32")
    with jax.default_matmul_precision("highest"):
        out, _, counts = grouped.experts_grouped(cfg, lp, m, logits)
        want = sum(6.0 * p[j] * (m[0] if j >= 8 else ref.swiglu(
            m[0], lp["w_gate"][j], lp["w_up"][j], lp["w_down"][j])) for j in want_ids)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want), rtol=2e-5, atol=2e-6)
    n_zero = sum(j >= 8 for j in want_ids)
    assert counts.shape == (9,) and int(counts[8]) == n_zero and int(counts[:8].sum()) == 3 - n_zero
    if name == "all_identity":   # no row reaches the kernel, and the output is 6 sum(p) m
        np.testing.assert_allclose(np.asarray(out[0]), 6.0 * p[want_ids].sum() * np.asarray(m[0]), rtol=1e-5)


def test_route_equals_the_reference_on_drawn_scores():
    """Drawn logits and bias: the dense [t, ids] gates the reference builds equal
    the (value, id) pairs ``route()`` returns, scattered."""
    cfg = dataclasses.replace(config_from_hf(UNCUT))
    logits = jax.random.normal(jax.random.key(11), (64, 36)) * 2.0
    bias = jax.random.normal(jax.random.key(12), (36,)) * 0.02
    top_p, top_e, *_ = route(cfg, logits, bias=bias)
    got = jnp.sum(jax.nn.one_hot(top_e, 36) * top_p[..., None], axis=1)
    want = ref.routing_weights(logits, jnp.eye(36), bias, top_k=4, scale=6.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-7)
    plain = route(cfg, logits, bias=jnp.zeros(36))[1]
    assert not np.array_equal(np.asarray(plain), np.asarray(top_e))   # the bias turns choices


def test_padding_slots_choose_nothing_and_count_nothing():
    """A slot of the grid that holds no token has no pair of any kind."""
    cfg, params = _model(UNCUT)
    lp = {k: v[0] for k, v in params["layers"].items() if k != "sub"}
    x = jax.random.normal(jax.random.key(2), (1, 10, 64))
    live = jnp.arange(10) < 6
    out, _, counts = moe_mlp(cfg, lp, x, live=live[None])
    assert float(jnp.abs(out[0, 6:]).max()) == 0.0
    assert int(counts[:24].sum()) + int(counts[24]) == 6 * 4


# -- (5) the planes, and controls that must FAIL the comparison --------------------------------
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


def _shortcut_one_block_early(self, lp, x, li, meta, carry):
    """The served layer with the expert block's output added behind D_0."""
    c = self._mc
    moe = None
    for i, sp in enumerate(lp["sub"]):
        plane = 2 * li + i
        attn_out, ckv = self._latent_attention(sp, x, plane, meta)
        carry = dict(carry, k=jax.lax.dynamic_update_index_in_dim(carry["k"], ckv, plane, 0))
        x = x + attn_out
        m = T._norm(x, sp["mlp_norm"], None, c.norm, c.norm_eps)
        x = x + T._mlp_block(c, sp, m)[0]
        if i == 0:
            shortcut, _, moe = moe_mlp(c, lp, m, live=meta["slot_live"][None], layer=li)
            x = x + shortcut
    return x, self._record_moe(carry, li, moe)


def _without(name):
    def patched(get):
        return lambda k, d=None: False if k == name else get(k, d)
    return patched


CONTROLS = {
    # sub-block 1 reads sub-block 0's plane (it still writes its own)
    "planes_swapped": lambda mp: mp.setattr(InferenceEngineV2, "_kv_source", (
        lambda plain: lambda self, meta, li, tables: plain(self, meta, li - li % 2, tables))(
            InferenceEngineV2._kv_source)),
    # the expert block joins one block early, behind D_0
    "shortcut_one_block_early": lambda mp: mp.setattr(
        InferenceEngineV2, "_shortcut_layer", _shortcut_one_block_early),
    # identity pairs dropped from the sum
    "identity_pairs_dropped": lambda mp: mp.setattr(ref, "expert_block", (
        lambda plain: lambda x, layers, i, **kw: plain(x, layers, i, **kw) - jnp.sum(
            ref.routing_weights(x, layers["router"][i].astype(jnp.float32),
                                layers["router_bias"][i].astype(jnp.float32), top_k=kw["top_k"],
                                scale=kw["scale"])[:, kw["total"]:], axis=-1, keepdims=True) * x)(
            ref.expert_block)),
    # the latent's scale left out of the reference
    "kv_scale_left_out": lambda mp: mp.setattr(ref, "attention", (
        lambda plain: lambda x, lp, **kw: plain(x, lp, **{**kw, "kv_scale": 1.0}))(ref.attention)),
    # ... and the queries'
    "q_scale_left_out": lambda mp: mp.setattr(ref, "attention", (
        lambda plain: lambda x, lp, **kw: plain(x, lp, **{**kw, "q_scale": 1.0}))(ref.attention)),
    # the rotary key dims scaled with the latent
    "k_rope_scaled_too": lambda mp: mp.setattr(T, "latent_qkv", (
        lambda plain: lambda c, lp, a, pos, n=None, **kw: (lambda q, r, ckv: (q, r, jnp.concatenate(
            [ckv[:, : c.kv_lora_rank], ckv[:, c.kv_lora_rank:] * c.latent_kv_scale], -1)))(
                *plain(c, lp, a, pos, n, **kw)))(T.latent_qkv)),
    # the selection bias used as weight
    "bias_used_as_weight": lambda mp: mp.setattr(ref, "routing_weights", (
        lambda x, router, bias, *, top_k, scale: (lambda p: (lambda top: jnp.sum(
            jax.nn.one_hot(top, p.shape[-1]) * (jnp.take_along_axis(p + bias, top, -1) * scale)[..., None],
            axis=1))(jax.lax.top_k(p + bias, top_k)[1]))(jax.nn.softmax(x @ router, -1)))),
    # the top-k renormalised
    "top_k_renormalised": lambda mp: mp.setattr(ref, "routing_weights", (
        lambda plain: lambda x, router, bias, **kw: (lambda g: g / jnp.sum(g, -1, keepdims=True)
                                                     * kw["scale"])(plain(x, router, bias, **kw)))(
            ref.routing_weights)),
}


@pytest.mark.parametrize("name", sorted(CONTROLS))
def test_control_fails_the_comparison(monkeypatch, name):
    """The program or the reference changed on purpose: each reads tens of times
    the tolerance and more through the same comparison. ``planes_swapped`` is
    case (5): plane 2 l + i holds sub-block i's vector, and no other."""
    assert _control_gap(monkeypatch, CONTROLS[name]) > 30 * TOL


def test_each_sub_block_writes_its_own_plane():
    """After one prompt the four planes hold four different vectors a token, and
    plane 2 l + i is what sub-block i of layer l computes from the stream it saw:
    plane 0 is layer 0's first sub-block's, a function of the embedding alone."""
    cfg, params = _model()
    eng = _engine(cfg, params)
    prompt = _prompts((BS,))[0]
    eng.scheduler.submit(0, prompt)
    eng.step()
    block = int(eng.state_manager.get_sequence(0).block_table[0])
    planes = np.asarray(eng._k_cache[:, block])                  # [4, latent_dim, BS]
    for a in range(4):
        for b in range(a + 1, 4):
            assert np.abs(planes[a] - planes[b]).max() > 1e-2
    sp = jax.tree.map(lambda w: w[0], params["layers"]["sub"])
    x = params["embed"][jnp.asarray(prompt)]
    a = T._norm(x[None], sp["attn_norm"], None, cfg.norm, cfg.norm_eps)[0]
    want = T.latent_qkv(cfg, sp, a, jnp.arange(BS))[2]            # [BS, latent_dim]
    np.testing.assert_allclose(planes[0].T, np.asarray(want), atol=1e-5)


# -- (6) the models that share the code trace what they traced ----------------------------------
# sha256 of str(jaxpr), made on the parent commit (0ba692c) and again on this one
# by the code below: byte-identical. A.X-K1's split steps hold ``_latent_layer``
# (now ``_latent_attention`` + its tail), ``route()``'s sigmoid branch and the
# grouped dispatch; OLMoE's hold ``route()``'s softmax branch with no bias and no
# scale. A change that moves one of these on purpose moves its hash with it and
# says here what changed.
PARENT_JAXPRS = {
    # PR 62, on purpose: ``_latent_attention`` absorbs the DECODE rows' queries alone and
    # applies ``W_UV`` to their outputs alone; the chunk rows go to ``latent_chunk`` with the
    # query projection as written (``latent_q``) and ``wkv_b``, which picks their form by
    # their slots (32 here: absorbed, the same products on the chunk rows' share of the grid);
    # an unrolled layer's ``wkv_b`` rides unsliced (``Stacked``) and is sliced where it is used
    "axk1_step_decode_only": "69ce9f57577aff81",
    "axk1_step_two_chunk_rows": "8174959756bea42a",
    "olmoe_step_decode_only": "04622bed5d4837d2",
    "olmoe_step_two_chunk_rows": "ec2f0070e4739179",
    "olmoe_route": "eff34a66bbfca8bb",
    "axk1_route": "052705b9bca383cd",
    # (forward()'s activation constraints are the suite's: tests/conftest.py's eight devices)
    "axk1_forward": "e597939813b15ed9",
    # PR 59: forward()'s softmax attention (``_attention_block``) keeps q, k, v where the
    # projections wrote them (``heads_view``, rank-3 ``attention_op``); the served steps above
    # did not move. Latent attention (axk1_forward) makes its own transposes and did not either.
    "olmoe_forward": "5e8c02ae07e46f57",
}
OLMOE = dict(model_type="olmoe", vocab_size=256, hidden_size=64, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=4, intermediate_size=32, num_experts=8,
             num_experts_per_tok=2, norm_topk_prob=False, rms_norm_eps=1e-5, rope_theta=10000,
             max_position_embeddings=512)


def _step_jaxpr(cfg, params, shape):
    eng = _engine(cfg, params)
    _, inputs = eng._stage_split(0, [], [])
    R = eng.config.state_manager.max_ragged_sequence_count
    Rc, tq = shape
    if tq:
        T_, B = R + Rc * tq, eng.config.kv_cache.max_blocks_per_seq
        grid = {"tokens": T_, "positions": T_, "blk": T_, "row": T_, "chk_tables": (Rc, B),
                "chk_pos": (Rc, tq), "chk_start": Rc, "chk_last": Rc, "chk_uids": Rc}
        inputs = {**inputs, **{k: np.zeros(v, np.int32) for k, v in grid.items()}}
    return jax.make_jaxpr(eng._build_split_step(shape))(
        params, inputs, jax.random.key(0), jnp.float32(1.0), eng._pools())


@pytest.mark.parametrize("name", sorted(PARENT_JAXPRS))
def test_the_models_that_share_the_code_trace_what_they_traced(name):
    from tests.unit.test_axk1_serving import HF as AXK1

    model, what = name.split("_", 1)
    cfg = dataclasses.replace(config_from_hf(AXK1 if model == "axk1" else OLMOE), dtype="float32",
                              remat=False)
    params = T.init_params(cfg, jax.random.key(0))
    if what.startswith("step"):
        jaxpr = _step_jaxpr(cfg, params, (2, 32) if "chunk" in what else (0, 0))
    elif what == "route":
        lg, live = jnp.zeros((24, cfg.router_width), jnp.float32), jnp.ones(24, bool)
        jaxpr = jax.make_jaxpr(lambda l, v: route(cfg, l, v)[: 3 if model == "olmoe" else 4])(lg, live)
    else:
        jaxpr = jax.make_jaxpr(lambda p, t: T.forward(p, t, cfg))(params, jnp.zeros((1, 16), jnp.int32))
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16] == PARENT_JAXPRS[name]


# -- config_from_hf, the pool's arithmetic, the loader, the refusals ---------------------------
def test_config_from_hf_on_the_published_keys():
    row = json.load(open(os.path.join(HERE, "benchmarks", "configs", "longcat-flash-chat.json")))
    assert sorted(row["reduced"]) == ["n_routed_experts", "num_layers", "vocab_size"]
    cfg = config_from_hf({**row, **row["published"], "deployment_share": None})
    assert (cfg.n_layers, cfg.sub_blocks, cfg.kv_layers, cfg.hidden_size, cfg.n_heads, cfg.head_dim) == (
        28, 2, 56, 6144, 64, 192)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim) == (
        1536, 512, 128, 64, 128)
    assert cfg.latent and cfg.latent_dim == 576 and cfg.rope_interleave and cfg.rope_scaling is None
    assert cfg.latent_q_scale == 2.0 and abs(cfg.latent_kv_scale - 12 ** 0.5) < 1e-12
    assert cfg.attn_scale is None and cfg.rope_theta == 1e7 and cfg.norm_eps == 1e-5
    assert (cfg.moe_shortcut, cfg.moe_dense_lead, cfg.ffn_dim, cfg.expert_dim) == (True, 0, 12288, 2048)
    assert (cfg.n_experts, cfg.routed_experts, cfg.moe_zero_experts, cfg.router_width, cfg.moe_top_k) == (
        512, 512, 256, 768, 12)
    assert (cfg.moe_score, cfg.router_has_bias, cfg.moe_routed_scale, cfg.moe_norm_topk_prob,
            cfg.moe_shared_expert_dim, cfg.moe_drop_tokens) == ("softmax", True, 6.0, False, 0, False)
    assert (cfg.vocab_size, cfg.tie_embeddings) == (131072, False)
    cut = config_from_hf(row)  # the benchmark's cut: a stage's layers, a 32nd of the experts
    assert (cut.n_layers, cut.kv_layers, cut.n_experts, cut.router_width, cut.moe_expert_shard,
            cut.vocab_size) == (4, 8, 16, 768, 0, 16384)
    shapes = jax.eval_shape(lambda k: T.init_params(cut, k), jax.random.key(0))
    assert shapes["layers"]["router"].shape == (4, 6144, 768)
    assert shapes["layers"]["router_bias"].shape == (4, 768)
    assert shapes["layers"]["sub"]["wkv_a"].shape == (8, 6144, 576)
    assert shapes["layers"]["sub"]["w_up"].shape == (8, 6144, 12288)
    assert shapes["layers"]["w_up"].shape == (4, 16, 6144, 2048)
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert n == 5_172_749_312   # 10.35 GB in bf16, as the configuration file counts it


def test_pool_accounting_at_the_cells_sizes():
    """One vector of 576 a token a PLANE, two planes a layer: 9,216 bytes a token
    over four layers, and the cell's 3 GB hold 2,543 blocks, one of them the trash."""
    row = json.load(open(os.path.join(HERE, "benchmarks", "configs", "longcat-flash-chat.json")))
    cell = json.load(open(os.path.join(
        HERE, "benchmarks", "cells", "longcat-flash-chat.serve-tool-agent-closed64.json")))["serve_args"]
    cfg = config_from_hf(row)
    heads, dim, planes = kv_pool.pool_geometry(cfg)
    assert (heads, dim, planes) == (1, 576, 1)
    per = kv_pool.bytes_per_block(128, heads, dim, cfg.kv_layers, planes=planes)
    assert per == 8 * 147_456 and per // 128 == 9_216
    from deepspeed_tpu.inference.cli import engine_config_from_args, serve_parse_args

    argv = ["--model", "", "--port", "0"]
    for flag, value in cell.items():
        argv += [flag, str(value)]
    rc = engine_config_from_args(serve_parse_args(argv), cfg)
    assert rc.kv_cache.num_blocks + 1 == 2_543 and rc.kv_cache.num_blocks * 128 > 325_000


def test_health_reports_two_planes_a_layer():
    cfg, params = _model()
    info = _engine(cfg, params).kv_pool_info()
    assert info["kv_bytes_per_block"] == 4 * BS * 24 * 2      # four planes of 24 a token, priced in bf16
    assert info["kv_pool_bytes"] == 41 * info["kv_bytes_per_block"]


def test_the_steps_count_pairs_by_kind():
    """``StepStats.moe`` of a served step: every (token, choice) pair, those of a
    held expert, those of an identity expert; one expert call a LAYER."""
    cfg, params = _model()
    eng = _engine(cfg, params)
    eng.scheduler.submit(0, _prompts((20,))[0])
    eng.step_tokens()
    moe = eng.last_step.moe
    assert moe["calls"] == 2 and moe["pairs"] == 20 * 4 * 2
    assert 0 < moe["zero_pairs"] < moe["pairs"] and moe["routed"] + moe["zero_pairs"] <= moe["pairs"]


@pytest.mark.parametrize("change,match", [
    ({"deployment_share": {"n_routed_experts": 24, "chips_per_layer": 5}}, "not one chip's share"),
    ({"zero_expert_type": "copy"}, "zero_expert_type"),
    ({"attention_method": "GQA"}, "attention_method"),
    ({"q_lora_rank": None}, "q_lora_rank"),
    ({"attention_bias": True}, "bias"),
    ({"rope_scaling": {"type": "ntk", "factor": 2}}, "rope_scaling"),
], ids=["share", "zero_expert_type", "attention_method", "no_q_rank", "bias", "rope_scaling"])
def test_config_from_hf_refuses_what_it_cannot_compute(change, match):
    with pytest.raises(ValueError, match=match):
        config_from_hf({**HF, **change})


@pytest.mark.parametrize("extra,match", [
    ({"kv_cache": {"kv_cache_dtype": "int8"}}, "int8 pool's scale planes"),
    ({"kv_cache": {"host_tier_bytes": 1 << 20, "prefix_cache": True}}, "host block tier"),
    ({"spec_k": 2}, "speculative"),
    ({"quant": {"enabled": True, "bits": 8}}, "quantized weights"),
    ({"tp_size": 2}, "tp_size=2"),
], ids=["int8_pool", "host_tier", "speculative", "quantized_weights", "tp"])
def test_what_cannot_carry_the_planes_refuses_at_build(extra, match):
    cfg, params = _model()
    with pytest.raises(NotImplementedError, match=match):
        _engine(cfg, params, **extra)


@pytest.mark.parametrize("mover", ["export_kv_blocks", "import_kv_blocks", "export_kv_blocks_device"])
def test_a_mover_of_kv_planes_refuses_the_latent_planes(mover):
    cfg, params = _model()
    eng = _engine(cfg, params)
    args = ([0], {}) if mover == "import_kv_blocks" else ([0],)
    with pytest.raises(NotImplementedError, match="one latent plane"):
        getattr(eng, mover)(*args)


def test_the_capacity_dispatch_refuses_identity_experts():
    with pytest.raises(ValueError, match="moe_zero_experts"):
        T.TransformerConfig(n_experts=8, moe_zero_experts=4, moe_drop_tokens=True)
    with pytest.raises(ValueError, match="moe_shortcut"):
        T.TransformerConfig(n_experts=8, moe_shortcut=True, moe_drop_tokens=False)   # no latent attention


def test_load_hf_model_reads_a_checkpoint_with_the_published_names(tmp_path):
    """A checkpoint written under the names ``_longcat_flash_layer`` reads (all 24
    experts, the 36-wide classifier and its bias) comes back as the seeded tree:
    this chip's experts, the router whole, the sub-blocks in order."""
    import torch
    from safetensors.torch import save_file

    from deepspeed_tpu.models.hf import load_hf_model

    cfg, params = _model()
    _, whole = _model(UNCUT)
    L, lw = params["layers"], whole["layers"]
    state = {"model.embed_tokens.weight": params["embed"], "model.norm.weight": params["final_norm"],
             "lm_head.weight": params["lm_head"].T}
    attn = (("wq_a", "q_a_proj"), ("wq_b", "q_b_proj"), ("wkv_a", "kv_a_proj_with_mqa"),
            ("wkv_b", "kv_b_proj"), ("wo", "o_proj"))
    mlp = (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj"))
    for l in range(2):
        p = f"model.layers.{l}"
        for i in range(2):
            j = 2 * l + i
            state[f"{p}.input_layernorm.{i}.weight"] = L["sub"]["attn_norm"][j]
            state[f"{p}.post_attention_layernorm.{i}.weight"] = L["sub"]["mlp_norm"][j]
            for name, hf in attn:
                state[f"{p}.self_attn.{i}.{hf}.weight"] = L["sub"][name][j].T
            state[f"{p}.self_attn.{i}.q_a_layernorm.weight"] = L["sub"]["q_a_norm"][j]
            state[f"{p}.self_attn.{i}.kv_a_layernorm.weight"] = L["sub"]["kv_a_norm"][j]
            for name, hf in mlp:
                state[f"{p}.mlps.{i}.{hf}.weight"] = L["sub"][name][j].T
        state[f"{p}.mlp.router.classifier.weight"] = L["router"][l].T
        state[f"{p}.mlp.router.e_score_correction_bias"] = L["router_bias"][l]
        for name, hf in mlp:
            for e in range(24):
                w = L[name][l][e - 6] if 6 <= e < 9 else lw[name][l][e]
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


def test_forward_equals_the_published_model_on_its_own_weights():
    """``forward()`` against transformers' ``LongcatFlashForCausalLM`` itself, on
    that model's seeded weights read through the names ``_longcat_flash_layer``
    reads: float32 both sides, logits of unit scale, worst seen 3e-6."""
    pytest.importorskip("transformers")
    import torch

    from benchmarks.tests.test_reference_longcat_flash import _hf, _published

    model, params = _published()
    cfg = dataclasses.replace(config_from_hf(_hf()), dtype="float32", remat=False)
    toks = np.random.default_rng(1).integers(0, 128, size=48)
    with torch.no_grad():
        want = model(torch.tensor(toks[None])).logits[0].numpy()
    got = T.forward(jax.tree.map(jnp.asarray, params), jnp.asarray(toks)[None], cfg)[0][0]
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4, rtol=0)


def test_v1_decode_step_refuses_the_architecture():
    cfg, params = _model()
    caches = [(None, None, 0)] * cfg.n_layers
    with pytest.raises(NotImplementedError, match="two sub-blocks"):
        T.decode_step(params, jnp.zeros((1, 1), jnp.int32), cfg, caches, jnp.zeros((1, 1), jnp.int32))


@pytest.mark.parametrize("program", ["split_step", "decode_only_step", "one_row_step"])
def test_the_step_programs_alias_the_planes_and_copy_no_pool(program):
    """``dstpu lint --verify``'s questions of the two-planes-a-layer model: every
    shape of the split step donates the one pool [2 x layers, ...] whole, aliases
    it to its output and traces once (the sub-blocks record their vectors in the
    carry; the pool is written once, after the loop). Off the chip XLA's scatter
    of a column transposes a latent pool, so the question of pool-sized copies is
    asked of the program compiled for a described v5e: tests/unit/ops/
    test_latent_attention.py, the ``longcat_*`` cases."""
    from deepspeed_tpu.analysis import verify as dv

    eng, programs = _verify_programs()
    fn, args = programs[program]
    (pool,) = eng._pools()
    assert pool.shape[0] == 4
    res = dv.check_donation(program, fn, args)
    assert res.ok and len(res.buffers) == 1 and all(b.aliased for b in res.buffers), res.detail
    if program == "split_step":   # the captured, live jit
        res = dv.check_recompile(program, fn)
        assert res.ok, res.detail


@functools.lru_cache(maxsize=None)
def _verify_programs():
    from deepspeed_tpu.analysis import verify as dv

    return dv._engine_v2_programs("bf16", model="planes")
