"""MiMo-V2-Flash (``model_type: mimo_v2_flash``) through the paged engine at a
tiny size, seeded weights, on the CPU: window layers of 4 KV heads with a
learned sink a head beside full layers of 2, keys of 24 against values of 16,
a rotary base by layer kind on the leading third of a head, values scaled
before they are attended, a dense lead layer and then a share of
sigmoid-routed experts with no shared one; the window layers' keys and values
in a window pool of ITS geometry beside the block pool of the full layers'.

The oracle is ``benchmarks/reference/mimo_v2_flash.py`` (plain float32
``jax.numpy``, a full-sequence forward, no cache): prefill in chunks LONGER
than the window and then decode through both pools, past the point where the
rings wrap, must give the reference's logits; so must ``forward()``."""

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

ref = importlib.import_module("benchmarks.reference.mimo_v2_flash")
HERE = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# F S S S S | F S S S S S (the published pattern's head), layer 0 dense; 16
# experts of which share 1 of 4 (experts 4-7) is held; window 16 over blocks
# of 8: rings of 3 blocks; rotary on int(24 x 0.334) = 8 of a head's 24 dims
PATTERN = [0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 2
HF = dict(
    model_type="mimo_v2_flash", vocab_size=128, hidden_size=64, num_hidden_layers=7,
    num_attention_heads=8, num_key_value_heads=2, swa_num_key_value_heads=4,
    swa_num_attention_heads=8, head_dim=24, swa_head_dim=24, v_head_dim=16, swa_v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, n_routed_experts=4, num_experts_per_tok=3,
    n_shared_experts=None, norm_topk_prob=True, scoring_func="sigmoid", topk_method="noaux_tc",
    routed_scaling_factor=None, n_group=1, topk_group=1, hybrid_layer_pattern=PATTERN,
    moe_layer_freq=[0] + [1] * 16, sliding_window=16, sliding_window_size=16,
    layernorm_epsilon=1e-5, rope_theta=5000000, swa_rope_theta=10000, partial_rotary_factor=0.334,
    attention_value_scale=0.707, add_swa_attention_sink_bias=True,
    add_full_attention_sink_bias=False, attention_bias=False, tie_word_embeddings=False,
    max_position_embeddings=512, hidden_act="silu",
    deployment_share={"n_routed_experts": 16, "chips_per_layer": 4, "share_index": 1},
)
WINDOW, BS = 16, 8
# float32 engine against float32 reference: the same sums in another order
TOL = 2e-5


def _model(hf=HF, dtype="float32", seed=0):
    cfg = dataclasses.replace(config_from_hf(hf), dtype=dtype, remat=False)
    return cfg, T.init_params(cfg, jax.random.key(seed))


def _engine(cfg, params, dtype="float32", **extra):
    rc = {
        "dtype": dtype, "prompt_chunk": 40, "max_prompt_chunks": 2,
        "kv_cache": {"block_size": BS, "num_blocks": 64, "max_blocks_per_seq": 32},
        "state_manager": {"max_tracked_sequences": 6, "max_ragged_batch_size": 128,
                          "max_ragged_sequence_count": 4, "max_context": 256},
    }
    for k, v in extra.items():
        rc[k] = {**rc.get(k, {}), **v} if isinstance(v, dict) else v
    return InferenceEngineV2(cfg, params, RaggedInferenceEngineConfig.from_dict(rc))


def _prompts(lens, vocab=128, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in lens]


def _serve_logits(eng, prompts, n_new):
    """Each prompt's logits at its last prompt token and at ``n_new - 1``
    greedy tokens after it, as the engine's steps return them, and the
    sequences as served."""
    for uid, p in enumerate(prompts):
        eng.scheduler.submit(uid, p)
    got = {uid: [] for uid in range(len(prompts))}
    toks = {uid: list(p) for uid, p in enumerate(prompts)}
    for _ in range(400):
        for uid, lg in eng.step().items():
            got[uid].append(np.asarray(lg, np.float32))
            if len(got[uid]) < n_new:
                toks[uid].append(int(np.argmax(lg)))
                eng.scheduler.feedback(uid, toks[uid][-1])
            else:
                eng.scheduler.finish(uid)
        if not eng.scheduler.has_work():
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


@pytest.mark.parametrize("lens", [(100,), (5, 70, 100, 33)], ids=["alone", "ragged_batch"])
def test_engine_equals_the_reference_on_logits_float32(lens):
    """Chunks of 40 tokens against a window of 16 and rings of 24 tokens, then
    60 decode steps, past 2 x window + a block, so every ring wraps: the
    window pool (4 KV heads) and the block pool (2) each at its geometry."""
    cfg, params = _model()
    eng = _engine(cfg, params)
    assert (cfg.window_layers, cfg.kv_layers, eng._win_blocks) == (5, 2, 3)
    assert eng._geom == {"block": (2, 24, 16), "window": (4, 24, 16)}
    prompts = _prompts(lens)
    got, toks = _serve_logits(eng, prompts, 60)
    assert all(len(g) == 60 for g in got.values()) and 60 > 2 * WINDOW + BS
    assert _gap(params, HF, prompts, got, toks) < TOL
    acct = eng.state_manager.kv_block_accounting()
    assert acct["free"] == acct["total"] and acct["window_free"] == acct["window_total"] == 18


def test_forward_equals_the_reference():
    """The model's own dense path: attention stacked by kind, the sinks, the
    rotary bases, the value scale and the value width, unrolled."""
    cfg, params = _model()
    toks = _prompts((90,))[0]
    got = np.asarray(T.forward(params, jnp.asarray(toks)[None], cfg)[0][0], np.float32)
    want = np.asarray(ref.logits(params, toks, HF))
    assert float(np.abs(got - want).max()) < TOL
    # packed sequences attend within their own segment
    seg = jnp.asarray([0] * 40 + [1] * 50)[None]
    pos = jnp.concatenate([jnp.arange(40), jnp.arange(50)])
    packed = np.asarray(T.forward(params, jnp.asarray(toks)[None], cfg, positions=pos,
                                  segment_ids=seg)[0][0], np.float32)
    want2 = np.asarray(ref.logits(params, toks[40:], HF))
    assert float(np.abs(packed[40:] - want2).max()) < TOL


def test_engine_equals_the_reference_through_the_interpreted_kernels():
    """The Pallas paged kernels (decode and chunk, interpreted) at the
    published head: keys of 192 stored a token a row (``keys_flat``) against
    values of 128, 2 and 4 KV heads, the sinks in the finish of a window
    layer's rows; blocks of 128, window 128: rings of 2, a chunk of two blocks
    and a decode that crosses into a third."""
    hf = {**HF, "head_dim": 192, "swa_head_dim": 192, "v_head_dim": 128, "swa_v_head_dim": 128,
          "sliding_window": 128, "num_hidden_layers": 3}
    cfg, params = _model(hf)
    eng = _engine(
        cfg, params, prompt_chunk=256, max_prompt_chunks=1, paged_attention_impl="kernel",
        kv_cache={"block_size": 128, "num_blocks": 16, "max_blocks_per_seq": 8},
        state_manager={"max_tracked_sequences": 3, "max_ragged_batch_size": 512,
                       "max_ragged_sequence_count": 2, "max_context": 1024})
    assert eng._win_blocks == 2
    assert eng._k_cache.shape == (1, 17, 128, 2 * 192) and eng._v_cache.shape == (1, 17, 128, 2, 128)
    assert eng._wk_cache.shape == (2, 8, 128, 4 * 192) and eng._wv_cache.shape == (2, 8, 128, 4, 128)
    prompts = _prompts((300,))
    got, toks = _serve_logits(eng, prompts, 100)  # positions 300..399: block 3 over ring slot 1
    assert _gap(params, hf, prompts, got, toks) < 5 * TOL  # longer sums


def test_generate_equals_the_driven_core_through_both_pools():
    """``generate()`` is the served step: the same prompts through the serving
    driver give the same tokens, and over 50 tokens (a ring wraps, each pool's
    side buffers at its geometry) each is the reference's best."""
    from tests.unit.simple_model import served_tokens

    cfg, params = _model()
    prompts = _prompts((5, 70, 100))
    outs = _engine(cfg, params).generate(prompts, max_new_tokens=50)
    driven = served_tokens(_engine(cfg, params), prompts, 50)
    for p, out, got in zip(prompts, outs, driven):
        assert [int(t) for t in out[len(p):]] == got
        want = np.asarray(ref.logits(params, out, HF))
        served = out[len(p):]
        best = want[len(p) - 1: -1]
        chosen = np.take_along_axis(best, served[:, None], axis=-1)[:, 0]
        assert float((best.max(-1) - chosen).max()) < TOL


def test_engine_in_bf16_equals_the_no_cache_forward_in_bf16():
    """bf16 weights AND compute, every held expert chosen (no decision to
    turn): the engine is held to the no-cache ``forward()`` in bf16 on the same
    weights: chunks, both pools and paged attention round at other places than
    one dense pass does, and nothing else may differ."""
    hf = {**HF, "num_experts_per_tok": 4, "deployment_share": None}
    cfg, params = _model(hf, dtype="bfloat16")
    eng = _engine(cfg, params, dtype="bfloat16")
    assert eng._wk_cache.dtype == jnp.bfloat16
    prompts = _prompts((70, 33))
    got, toks = _serve_logits(eng, prompts, 40)
    for u, p in enumerate(prompts):
        want = np.asarray(T.forward(params, jnp.asarray(toks[u])[None], cfg)[0][0], np.float32)
        np.testing.assert_allclose(got[u], want[len(p) - 1:], atol=0.1, rtol=0)


# -- controls: each must FAIL the comparison ---------------------------------
CONTROLS = {
    # the sink dropped: a window row's softmax over its keys alone
    "no_sink": lambda mp: mp.setattr(
        ref, "attention", (lambda plain: lambda a, ap, j, **kw: plain(
            a, {**ap, "sink": jnp.full_like(ap["sink"], -1e30)} if "sink" in ap else ap, j, **kw))(
                ref.attention)),
    # the value scale left out
    "no_value_scale": lambda mp: mp.setattr(
        ref, "attention", (lambda plain: lambda a, ap, j, **kw: plain(
            a, ap, j, **{**kw, "value_scale": 1.0}))(ref.attention)),
    # the two rotary bases swapped
    "bases_swapped": lambda mp: mp.setattr(
        ref, "attention", (lambda plain: lambda a, ap, j, **kw: plain(
            a, ap, j, **{**kw, "theta": 10000.0 if kw["theta"] > 1e5 else 5e6}))(ref.attention)),
    # a window layer seeing the whole context (its sinks kept)
    "window_sees_all": lambda mp: mp.setattr(
        ref, "attention", (lambda plain: lambda a, ap, j, **kw: plain(
            a, ap, j, **{**kw, "window": 4096 if kw["window"] else 0}))(ref.attention)),
    # the last third of a key dropped
    "key_tail_dropped": lambda mp: mp.setattr(
        ref, "rope", (lambda plain: lambda x, pos, theta, rot: (
            lambda y: y.at[..., 16:].set(0.0) if y.shape[1] in (2, 4) else y)(
                plain(x, pos, theta, rot)))(ref.rope)),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_control_fails_the_comparison(monkeypatch, control):
    """Each fault, put into the REFERENCE's side of the comparison, moves the
    served logits off it by far more than the sums' order does: the comparison
    tells the sink, the value scale, the rotary base by kind, the window and
    the key's full width."""
    cfg, params = _model()
    CONTROLS[control](monkeypatch)
    ref.layer.clear_cache()
    try:
        prompts = _prompts((100,))
        got, toks = _serve_logits(_engine(cfg, params), prompts, 60)
        gap = _gap(params, HF, prompts, got, toks)
    finally:
        monkeypatch.undo()
        ref.layer.clear_cache()
    assert gap > 1000 * TOL, gap


def test_the_engine_without_the_sink_fails_the_comparison():
    """The control on the PROGRAM's side: an engine whose window layers lose
    their sinks (the parameter dropped from the tree) serves other logits."""
    cfg, params = _model()
    prompts = _prompts((100,))
    lost = jax.tree.map(lambda a: a, params)
    lost["layers"] = dict(lost["layers"], window=dict(
        lost["layers"]["window"], sink=jnp.full_like(params["layers"]["window"]["sink"], -1e30)))
    got, toks = _serve_logits(_engine(cfg, lost), prompts, 60)
    assert _gap(params, HF, prompts, got, toks) > 1000 * TOL


# -- the expert block ---------------------------------------------------------
def test_the_shares_parts_add_up_to_the_uncut_layer():
    """The SHARE test: each of the 4 chips that share a layer routes over all
    16 experts and computes its own 4; the four partial results (there is no
    shared expert to count once) add up to the reference's uncut layer."""
    from deepspeed_tpu.parallel.moe import moe_mlp

    uncut_hf = {**HF, "n_routed_experts": 16, "deployment_share": None, "num_hidden_layers": 3}
    cfg_all, params = _model(uncut_hf)
    lp_all = T.take_layer(params["layers"], cfg_all, 1, lambda a, i: a[i])  # the first expert layer
    assert "sink" in lp_all and lp_all["wk"].shape == (64, 4 * 24)  # ... a window layer's attention
    x = jax.random.normal(jax.random.key(7), (1, 24, 64))
    with jax.default_matmul_precision("highest"):
        parts = []
        for share in range(4):
            cfg = dataclasses.replace(cfg_all, n_experts=4, moe_experts_total=16, moe_expert_shard=share)
            lp = {k: (v[4 * share: 4 * share + 4] if k in ("w_up", "w_gate", "w_down") else v)
                  for k, v in lp_all.items()}
            out, _, counts = moe_mlp(cfg, lp, x)
            parts.append((out[0], counts))
        want = ref.sparse_mlp(x[0], params["layers"]["sparse"], 0, top_k=3, scale=1.0, first=0)
    np.testing.assert_allclose(np.asarray(sum(o for o, _ in parts)), np.asarray(want), atol=1e-5)
    assert sum(int(c.sum()) for _, c in parts) == 24 * 3  # every pair is some share's


# -- config_from_hf -----------------------------------------------------------
def test_config_from_hf_on_the_published_keys():
    row = json.load(open(os.path.join(HERE, "benchmarks", "configs", "mimo-v2-flash.json")))
    cfg = config_from_hf({**row, **row["published"], "deployment_share": None})
    assert (cfg.n_layers, cfg.hidden_size, cfg.n_heads, cfg.kv_heads, cfg.window_kv_heads) == (
        48, 4096, 64, 4, 8)
    assert (cfg.head_dim, cfg.value_dim, cfg.rope_frac, cfg.attn_value_scale) == (192, 128, 0.334, 0.707)
    assert cfg.attn_layer_pattern == (0, 1, 1, 1, 1) + (0, 1, 1, 1, 1, 1) * 7 + (0,)
    assert (cfg.window_layers, cfg.kv_layers, cfg.sliding_window) == (39, 9, 128)
    assert (cfg.rope_theta, cfg.window_rope_theta) == (5e6, 1e4) and cfg.attn_sink_window
    assert cfg.attn_by_kind and cfg.norm_scheme == "pre" and not cfg.qk_norm
    assert (cfg.moe_dense_lead, cfg.ffn_dim, cfg.expert_dim, cfg.moe_shared_expert_dim) == (1, 16384, 2048, 0)
    assert (cfg.n_experts, cfg.router_width, cfg.moe_top_k) == (256, 256, 8)
    assert (cfg.moe_score, cfg.moe_routed_scale, cfg.moe_router_bias, cfg.moe_drop_tokens) == (
        "sigmoid", 1.0, True, False)
    assert (cfg.vocab_size, cfg.tie_embeddings, cfg.norm_eps) == (152576, False, 1e-5)
    cut = config_from_hf(row)  # the benchmark's cut: a stage's layers, a share of the experts
    assert (cut.n_layers, cut.attn_layer_pattern) == (11, (0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1))
    assert (cut.n_experts, cut.router_width, cut.moe_expert_shard, cut.vocab_size) == (16, 256, 0, 19072)
    assert T.cache_kinds(cut) == ("full",) + ("window",) * 4 + ("full",) + ("window",) * 5
    assert T.layer_stacks(cut, 0) == (("full", 0), ("lead", 0))
    assert T.layer_stacks(cut, 5) == (("full", 1), ("sparse", 4))
    assert T.layer_stacks(cut, 10) == (("window", 8), ("sparse", 9))
    # every number of the catalog's row is in the file under its key but the three cuts
    assert row["first_k_dense_replace"] == 1 and "first_k_dense_replace" in row["derived"]
    assert row["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]


@pytest.mark.parametrize("change,match", [
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"moe_layer_freq": [1] * 17}, "moe_layer_freq"),
    ({"moe_layer_freq": [0, 1, 0] + [1] * 14}, "moe_layer_freq"),
    ({"hybrid_layer_pattern": [0, 1, 1]}, "hybrid_layer_pattern"),
    ({"hybrid_layer_pattern": [0] * 17}, "some of each"),
    ({"sliding_window": 0, "sliding_window_size": 0}, "window"),
    ({"add_full_attention_sink_bias": True}, "sink on the full layers"),
    ({"n_shared_experts": 1}, "shared experts"),
    ({"swa_head_dim": 128}, "head widths differ"),
    ({"deployment_share": {"n_routed_experts": 16, "chips_per_layer": 3}}, "not one chip's share"),
], ids=["softmax", "topk_method", "no_lead", "dense_behind", "short_list", "no_window_layer",
        "no_window", "full_sink", "shared", "swa_head", "share"])
def test_config_from_hf_refuses_what_it_cannot_compute(change, match):
    with pytest.raises(ValueError, match=match):
        config_from_hf({**HF, **change})


def test_v1_decode_step_and_tensor_parallel_specs_refuse_the_architecture():
    cfg, params = _model()
    with pytest.raises(NotImplementedError, match="v2 paged engine"):
        T.decode_step(params, jnp.zeros((1, 1), jnp.int32), cfg, None, jnp.zeros((1, 1), jnp.int32))
    with pytest.raises(NotImplementedError, match="stacked by kind"):
        T.param_partition_specs(cfg)
    with pytest.raises(ValueError, match="window_kv_heads"):
        dataclasses.replace(cfg, attn_layer_pattern=None)
    with pytest.raises(ValueError, match="attn_sink_window"):
        dataclasses.replace(cfg, window_kv_heads=0)


def test_load_hf_model_reads_a_checkpoint_with_the_assumed_names(tmp_path):
    """A checkpoint written under the names ``_mimo_v2_flash_layer`` reads (the
    llama layer's, ``attention_sink_bias`` on window layers, DeepseekV3's expert
    block, all 16 experts and an ``mtp.`` layer that the load drops) comes back
    as the seeded tree: attention by kind, this chip's share of the experts."""
    import torch

    from deepspeed_tpu.models.hf import load_hf_model

    cfg, params = _model()
    _, whole = _model({**HF, "n_routed_experts": 16, "deployment_share": None})
    L, lw = params["layers"], whole["layers"]
    state = {"model.embed_tokens.weight": params["embed"], "model.norm.weight": params["final_norm"],
             "lm_head.weight": params["lm_head"].T, "mtp.layers.0.input_proj.weight": np.zeros((2, 2))}
    mlp = (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj"))
    seen = {"full": 0, "window": 0}
    for i in range(7):
        p = f"model.layers.{i}"
        kind = "window" if PATTERN[i] else "full"
        j = seen[kind]
        seen[kind] += 1
        state[f"{p}.input_layernorm.weight"] = L["attn_norm"][i]
        state[f"{p}.post_attention_layernorm.weight"] = L["mlp_norm"][i]
        for n in "qkvo":
            state[f"{p}.self_attn.{n}_proj.weight"] = L[kind][f"w{n}"][j].T
        if kind == "window":
            state[f"{p}.self_attn.attention_sink_bias"] = L["window"]["sink"][j]
        if i == 0:
            for ours, theirs in mlp:
                state[f"{p}.mlp.{theirs}.weight"] = L["lead"][ours][0].T
            continue
        S = L["sparse"]
        state[f"{p}.mlp.gate.weight"] = S["router"][i - 1].T
        state[f"{p}.mlp.gate.e_score_correction_bias"] = S["router_bias"][i - 1]
        for ours, theirs in mlp:
            for e in range(16):  # this chip's experts are 4-7; the others from the uncut draw
                w = S[ours][i - 1, e - 4] if 4 <= e < 8 else lw["sparse"][ours][i - 1, e]
                state[f"{p}.mlp.experts.{e}.{theirs}.weight"] = w.T
    torch.save({k: torch.tensor(np.asarray(v, np.float32)) for k, v in state.items()},
               tmp_path / "pytorch_model.bin")
    json.dump(HF, open(tmp_path / "config.json", "w"))
    got_cfg, got = load_hf_model(str(tmp_path), dtype="float32")
    assert got_cfg == dataclasses.replace(cfg, remat=True)
    flat_want = jax.tree_util.tree_leaves_with_path(params)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got))
    assert set(flat_got) == {k for k, _ in flat_want}
    for k, v in flat_want:
        np.testing.assert_array_equal(np.asarray(flat_got[k]), np.asarray(v), err_msg=str(k))


# -- the cache, by pool and by plane --------------------------------------------
def test_pool_bytes_are_counted_by_pool_and_plane_as_the_engine_allocates_them():
    """``kv_pool``'s byte functions, priced from each pool's geometry, equal
    the bytes of the arrays the engine allocates: the block pool at the full
    layers' 2 KV heads, the window pool at the window layers' 4, a K plane of
    24 a head beside a V plane of 16; the budget pays the rings first."""
    cfg, params = _model(dtype="bfloat16")
    eng = _engine(cfg, params, dtype="bfloat16")
    assert kv_pool.pool_geometry(cfg) == (2, (24, 16), 2)
    assert kv_pool.pool_geometry(cfg, "window") == (4, (24, 16), 2)
    assert kv_pool.plane_widths((24, 16)) == (24, 16) and kv_pool.plane_widths(128) == (128, 128)
    heads, dim, planes = kv_pool.pool_geometry(cfg)
    per_block = kv_pool.bytes_per_block(BS, heads, dim, cfg.kv_layers, planes=planes)
    assert per_block == 2 * BS * 2 * (24 + 16) * 2
    assert eng._k_cache.nbytes + eng._v_cache.nbytes == (64 + 1) * per_block
    assert eng._k_cache.nbytes == (64 + 1) * 2 * BS * 2 * 24 * 2    # by plane: K ...
    assert eng._v_cache.nbytes == (64 + 1) * 2 * BS * 2 * 16 * 2    # ... and V
    per_slot = kv_pool.window_slot_bytes(cfg, BS)
    assert per_slot == 3 * (5 * BS * 4 * (24 + 16) * 2)
    assert eng._wk_cache.nbytes + eng._wv_cache.nbytes == 7 * per_slot
    info = eng.kv_pool_info()
    assert info["kv_pool_geometry"] == {"layers": 2, "kv_heads": 2, "plane_widths": [24, 16]}
    assert info["window_pool_geometry"] == {"layers": 5, "kv_heads": 4, "plane_widths": [24, 16]}
    assert info["kv_bytes_per_block"] == per_block and info["window_bytes_per_block"] == per_slot // 3
    assert info["kv_pool_bytes"] == eng._k_cache.nbytes + eng._v_cache.nbytes
    assert info["window_pool_bytes"] == eng._wk_cache.nbytes + eng._wv_cache.nbytes
    # one budget: the rings of the tracked sequences and a spare first, then blocks
    n = kv_pool.blocks_for_budget(2_000_000, BS, heads, dim, cfg.kv_layers,
                                  state_bytes=7 * kv_pool.slot_bytes(cfg, BS), planes=planes)
    assert n == (2_000_000 - 7 * per_slot) // per_block - 1
    # at the published widths, through the CLI's sizing: the cell's numbers
    row = json.load(open(os.path.join(HERE, "benchmarks", "configs", "mimo-v2-flash.json")))
    cut = config_from_hf(row)
    assert kv_pool.bytes_per_block(128, *kv_pool.pool_geometry(cut)[:2], cut.kv_layers) == 655_360
    assert kv_pool.window_slot_bytes(cut, 128) == 2 * 9 * 655_360 == 11_796_480


def test_the_served_counters_count_the_cache_by_kind():
    """A tracked sequence holds ``wb`` ring blocks a window layer whatever its
    context and global blocks by its length; the step's counters say so."""
    cfg, params = _model()
    eng = _engine(cfg, params)
    mgr = eng.state_manager
    for uid, p in enumerate(_prompts((100, 9))):
        eng.scheduler.submit(uid, p)
    for _ in range(8):
        for uid, lg in eng.step().items():
            eng.scheduler.feedback(uid, int(np.argmax(lg)))
        acct = mgr.kv_block_accounting()
        st = eng.last_step
        assert st.kv_window_blocks == acct["window_live"] == 2 * 3
        assert st.kv_global_blocks == acct["live"] == sum(
            len(mgr.get_sequence(u).block_table) for u in (0, 1))
        assert st.kv_context_tokens == sum(mgr.get_sequence(u).seen_tokens for u in (0, 1))
    st = eng.last_step
    pos = [mgr.get_sequence(u).seen_tokens - 1 for u in (0, 1)]
    want = sum((p - 1) // BS - max(p - WINDOW + 1, 0) // BS + 1 for p in pos)
    assert st.paged_window_live_blocks == want and st.paged_live_blocks == sum(-(-p // BS) for p in pos)


@pytest.mark.parametrize("what,kw", [
    ("kv_cache_dtype", {"kv_cache": {"kv_cache_dtype": "int8"}}),
    ("host_tier_bytes", {"kv_cache": {"host_tier_bytes": 1 << 20, "prefix_cache": True}}),
    ("spec_k", {"spec_k": 2}),
])
def test_what_cannot_carry_the_window_pool_is_refused_at_build(what, kw):
    """The refusals a window pool already has stay for this model."""
    cfg, params = _model()
    with pytest.raises(NotImplementedError, match="window layers keep their K/V"):
        _engine(cfg, params, **kw)


def test_one_predicate_sends_a_geometry_to_the_kernels_or_to_the_dense_form(monkeypatch):
    """``kernels_take`` is the engine's ``auto`` gate and the chunk kernel's:
    6 KV heads, a head of 64 resolve to ``dense`` in both places; this
    model's two pools (192 | 128 at 4 and 8 heads) to the kernels, and since
    PR 53 ONE head too (read as the row it is: ``paged_pallas.one_head``)."""
    import deepspeed_tpu.inference.v2.engine_v2 as E
    from deepspeed_tpu.ops.attention import paged_pallas as pp

    assert pp.kernels_take(4, 192, 128) and pp.kernels_take(8, 192, 128)
    assert pp.kernels_take(8, 128) and pp.kernels_take(2, 256) and pp.kernels_take(16, 128)
    assert pp.kernels_take(1, 128)
    assert not (pp.kernels_take(6, 128) or pp.kernels_take(1, 64) or pp.kernels_take(8, 64)
                or pp.kernels_take(4, 192, 96))
    assert pp.keys_flat(192) and not (pp.keys_flat(128) or pp.keys_flat(256) or pp.keys_flat(64))
    pool = (9, 128, 6, 128)
    assert not pp.chunk_kernel_takes((2, 512, 18, 128), pool, jnp.bfloat16, True, False)
    assert pp.chunk_kernel_takes((2, 512, 64, 192), (9, 128, 4, 128), jnp.bfloat16, True, False, 128)
    monkeypatch.setattr(E, "on_tpu", lambda: True)
    six = T.get_config("tiny", n_heads=6, n_kv_heads=6, hidden_size=768, dtype="float32")
    assert six.head_dim == 128
    assert _engine(six, T.init_params(six, jax.random.key(0)))._attn_impl == "dense"
    eight = dataclasses.replace(six, n_heads=8, n_kv_heads=8, hidden_size=1024)
    assert _engine(eight, T.init_params(eight, jax.random.key(0)))._attn_impl == "kernel"
    row = json.load(open(os.path.join(HERE, "benchmarks", "configs", "mimo-v2-flash.json")))
    cut = config_from_hf(row)
    assert all(pp.kernels_take(kv_pool.pool_geometry(cut, p)[0], *kv_pool.pool_geometry(cut, p)[1])
               for p in ("block", "window"))
