"""The drop-free expert layer (parallel/moe/grouped.py): rows sorted by expert
and grouped matmuls, against the obvious oracle (every expert on every token,
masked by the top-k) in float32; its gradients; the Pallas kernel interpreted
against ``jax.lax.ragged_dot``, at a wide hidden size in the rule's column tiles
and in one block too; the rule of the weight block over the shapes of the
benchmark's seven expert configurations; and the counters a serving step feeds from the
[L, E] routed rows, against a count by hand with the grid's padding excluded."""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import init_params
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.parallel.moe import grouped, moe_mlp

# float32 on the CPU, the same sums in another order: 1e-5 of outputs of unit
# scale. A dropped token, a routed padding slot or a wrong gate is whole units.
TOL = dict(atol=2e-5, rtol=2e-5)


def _config(**kw):
    base = dict(vocab_size=64, hidden_size=32, n_layers=1, n_heads=4, ffn_hidden_size=24,
                n_experts=64, moe_top_k=8, moe_drop_tokens=False, moe_norm_topk_prob=False,
                dtype="float32")
    base.update(kw)
    return TransformerConfig(**base)


def _layer(cfg, seed=0):
    params = init_params(cfg, jax.random.key(seed))
    return {k: v[0] for k, v in params["layers"].items()}


def _oracle(cfg, lp, x, live=None):
    """Every expert on every token, weighted by the top-k softmax mass."""
    t = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(t @ lp["router"], axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.moe_top_k)
    if cfg.moe_norm_topk_prob:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    w = jnp.sum(jax.nn.one_hot(top_e, cfg.n_experts) * top_p[..., None], axis=1)   # [t, E]
    if live is not None:
        w = w * live.reshape(-1, 1)
    y = jnp.einsum("tef,efh->teh",
                   jax.nn.silu(jnp.einsum("th,ehf->tef", t, lp["w_gate"]))
                   * jnp.einsum("th,ehf->tef", t, lp["w_up"]), lp["w_down"])
    out = jnp.einsum("te,teh->th", w, y)
    if cfg.moe_shared_expert_dim:
        gate = jax.nn.sigmoid(t @ lp["shared_gate_proj"])
        out = out + gate * ((jax.nn.silu(t @ lp["shared_gate"]) * (t @ lp["shared_up"])) @ lp["shared_down"])
    return out.reshape(x.shape), (w > 0).sum(0)


def _x(cfg, b=2, s=9, seed=1):
    return jax.random.normal(jax.random.key(seed), (b, s, cfg.hidden_size), jnp.float32)


CASES = {
    "top8_of_64": {},
    "renormalised": {"moe_norm_topk_prob": True},
    "shared_expert": {"moe_shared_expert_dim": 40, "n_experts": 8, "moe_top_k": 2},
    "top1": {"n_experts": 4, "moe_top_k": 1},
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_grouped_equals_every_expert_oracle(case):
    cfg = _config(**CASES[case])
    lp, x = _layer(cfg), _x(cfg)
    with jax.default_matmul_precision("highest"):
        out, aux, counts = jax.jit(lambda lp, x: moe_mlp(cfg, lp, x))(lp, x)
        want, want_counts = _oracle(cfg, lp, x)
    np.testing.assert_allclose(out, want, **TOL)
    np.testing.assert_array_equal(counts, want_counts)
    assert int(counts.sum()) == x.shape[0] * x.shape[1] * cfg.moe_top_k   # nothing dropped
    assert np.isfinite(float(aux))


def test_all_tokens_on_one_expert_and_experts_with_no_token():
    cfg = _config(n_experts=8, moe_top_k=2)
    lp, x = _layer(cfg), _x(cfg, b=1, s=40)
    # a router that sends every token to experts 5 and 2, whatever else it holds
    x = x.at[..., 0].set(1.0)
    lp["router"] = jnp.zeros_like(lp["router"]).at[0, 5].set(9.0).at[0, 2].set(6.0)
    with jax.default_matmul_precision("highest"):
        out, _, counts = moe_mlp(cfg, lp, x)
        want, _ = _oracle(cfg, lp, x)
    np.testing.assert_array_equal(counts, [0, 0, 40, 0, 0, 40, 0, 0])
    np.testing.assert_allclose(out, want, **TOL)


def test_slots_that_are_not_live_go_to_no_expert():
    cfg = _config(n_experts=8, moe_top_k=2)
    lp, x = _layer(cfg), _x(cfg, b=1, s=21)
    live = jnp.asarray(np.random.default_rng(3).random((1, 21)) < 0.6)
    with jax.default_matmul_precision("highest"):
        out, _, counts = jax.jit(lambda lp, x, live: moe_mlp(cfg, lp, x, live=live))(lp, x, live)
        want, want_counts = _oracle(cfg, lp, x, live)
    np.testing.assert_allclose(out, want, **TOL)
    assert not np.asarray(out)[~np.asarray(live)].any()         # padding gets exactly nothing
    np.testing.assert_array_equal(counts, want_counts)
    assert int(counts.sum()) == int(live.sum()) * 2
    # and no live slot at all is a legal step (an engine's warm-up)
    out, _, counts = moe_mlp(cfg, lp, x, live=jnp.zeros((1, 21), bool))
    assert not np.asarray(out).any() and not np.asarray(counts).any()


def test_gradients_equal_the_oracles():
    cfg = _config(n_experts=8, moe_top_k=2, moe_norm_topk_prob=True)
    lp, x = _layer(cfg), _x(cfg)
    tgt = jax.random.normal(jax.random.key(5), x.shape)

    def loss(fn):
        return lambda lp, x: jnp.sum((fn(lp, x) - tgt) ** 2)

    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(lambda lp, x: moe_mlp(cfg, lp, x)[0]), argnums=(0, 1))(lp, x)
        want = jax.grad(loss(lambda lp, x: _oracle(cfg, lp, x)[0]), argnums=(0, 1))(lp, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, atol=2e-4, rtol=2e-4)


def test_a_dropping_configuration_keeps_the_capacity_dispatch():
    """moe_drop_tokens=True (the default) is the einsum path: with a capacity
    too small for the load some rows are dropped, which the grouped path never
    does; both count what they routed."""
    cfg = _config(n_experts=4, moe_top_k=1, moe_drop_tokens=True, moe_capacity_factor=0.25)
    lp, x = _layer(cfg), _x(cfg, b=1, s=64)
    out, _, counts = moe_mlp(cfg, lp, x)
    free, _, free_counts = moe_mlp(dataclasses.replace(cfg, moe_drop_tokens=False), lp, x)
    assert int(free_counts.sum()) == 64 and int(counts.sum()) == 64
    dropped = ~np.asarray(out).any(-1)[0]
    assert dropped.sum() >= 64 - 4 * 4 and not (~np.asarray(free).any(-1)).any()


@pytest.mark.parametrize("rows,tm,dtype", [(64, 8, jnp.float32), (96, 16, jnp.bfloat16),
                                           (128, 32, jnp.float32)])
def test_the_kernel_interpreted_equals_ragged_dot(rows, tm, dtype):
    rng = np.random.default_rng(rows)
    E, k, n = 6, 32, 256
    sizes = rng.multinomial(rows - 11, [0.05, 0.4, 0.0, 0.3, 0.25, 0.0]).astype(np.int32)
    lhs = jnp.asarray(rng.standard_normal((rows, k)), dtype)
    rhs = jnp.asarray(rng.standard_normal((E, k, n)) * 0.2, dtype)
    got = grouped.grouped_matmul(lhs, rhs, jnp.asarray(sizes), tm, "interpret")
    want = grouped.grouped_matmul(lhs, rhs, jnp.asarray(sizes), tm, "ragged")
    total = int(sizes.sum())   # the 11 rows behind the last group belong to none
    np.testing.assert_allclose(np.asarray(got[:total], np.float32), np.asarray(want[:total], np.float32),
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)
    # the kernel's backward is ragged_dot's
    gk, gr = (jax.grad(lambda a, b: jnp.sum(grouped.grouped_matmul(a, b, jnp.asarray(sizes), tm, impl)
                                            [:total].astype(jnp.float32) ** 2), argnums=(0, 1))(lhs, rhs)
              for impl in ("interpret", "ragged"))
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   atol=0.5 if dtype == jnp.bfloat16 else 1e-3, rtol=2e-2)


# group sizes over four experts and 48 rows in tiles of 16 (bf16) or 8: groups
# that straddle a row tile beside an empty one, with rows of no group and
# skipped visits behind them; one group that holds every row; a full grid; a
# traced layer of the stack. ``tn``: None for the rule's block, which at ``k`` =
# 4,224 is the floor's (a kilobyte a row: two column tiles of 512 in bf16, four
# of 256 in float32, as at K-EXAONE's 6,144 and A.X-K1's 7,168); 1,024 for the
# whole [4224, 1024] matrix as one block.
WIDE_CASES = {
    "straddling_and_empty": ([5, 0, 19, 11], False, None),
    "straddling_and_empty_in_one_block": ([5, 0, 19, 11], False, 1024),
    "one_group_holds_every_row": ([0, 0, 48, 0], False, None),
    "every_row_routed_in_one_block": ([9, 23, 1, 15], False, 1024),
    "a_traced_layer_of_the_stack": ([14, 3, 0, 20], True, None),
    "a_traced_layer_in_one_block": ([14, 3, 0, 20], True, 1024),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(WIDE_CASES))
def test_the_kernel_interpreted_at_a_wide_hidden_size(case, dtype):
    sizes, stacked, tn = WIDE_CASES[case]
    E, k, n, rows = 4, 4224, 1024, 48
    tm = grouped.row_tile(rows, jnp.dtype(dtype).itemsize)
    assert grouped._col_tile(k, n, jnp.dtype(dtype).itemsize) * jnp.dtype(dtype).itemsize == grouped._MIN_ROW_BYTES
    rng = np.random.default_rng(len(case))
    lhs = jnp.asarray(rng.standard_normal((rows, k)), dtype)
    rhs = jnp.asarray(rng.standard_normal((2 if stacked else 1, E, k, n)) * k ** -0.5, dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    layer = len(rhs) - 1
    got = jax.jit(lambda li: grouped._gmm_pallas(lhs, rhs, sizes, li, tm, True, tn=tn))(jnp.int32(layer))
    want = grouped.grouped_matmul(lhs, rhs[layer], sizes, tm, "ragged")
    total = int(sizes.sum())
    # sums of 4,224 terms in two orders, rounded to the dtype: one unit in the
    # last place of bf16 (2 ** -7 of the value) where the two straddle a rounding
    tol = dict(atol=1e-2, rtol=2 ** -7) if dtype == jnp.bfloat16 else dict(atol=1e-5)
    np.testing.assert_allclose(np.asarray(got[:total], np.float32), np.asarray(want[:total], np.float32), **tol)


def test_a_traced_call_leaves_its_block_in_the_setup_record():
    from deepspeed_tpu.observability import SetupRecord, get_setup_record, set_setup_record

    old = get_setup_record()
    fresh = set_setup_record(SetupRecord())
    try:
        lhs, rhs = jnp.ones((16, 256), jnp.bfloat16), jnp.ones((3, 256, 384), jnp.bfloat16)
        grouped.grouped_matmul(lhs, rhs, jnp.asarray([4, 0, 9], jnp.int32), 16, "interpret")
        said = [sp.args for sp in fresh.spans() if sp.name == "moe_gmm.block"]
    finally:
        set_setup_record(old)
    # the whole [256, 384] matrix is one block and one run of HBM
    assert said == [{"k": 256, "n": 384, "tn": 384, "run_bytes": 256 * 384 * 2}]


@pytest.mark.parametrize("impl", ["interpret", "ragged"])
def test_a_layer_of_the_whole_stack_is_read_in_place(impl):
    """``layer=``: the weights are the stack [L, E, k, n] and the kernel's index
    map picks the layer, traced or not, as a serving step's layer loop does."""
    rng = np.random.default_rng(0)
    L, E, k, n, rows, tm = 3, 4, 16, 128, 32, 8
    sizes = jnp.asarray([9, 0, 16, 7], jnp.int32)
    lhs = jnp.asarray(rng.standard_normal((rows, k)), jnp.float32)
    stack = jnp.asarray(rng.standard_normal((L, E, k, n)), jnp.float32)
    got = jax.jit(lambda li: grouped.grouped_matmul(lhs, stack, sizes, tm, impl, layer=li))(jnp.int32(2))
    want = grouped.grouped_matmul(lhs, stack[2], sizes, tm, "ragged")
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert np.abs(np.asarray(want) - np.asarray(
        grouped.grouped_matmul(lhs, stack[1], sizes, tm, "ragged"))).max() > 0.1


def test_the_tiling_rule_and_the_rows_it_covers():
    # a decode step (32 rows x 8) and a step with two 512-token chunks alike
    assert grouped.row_tile(256, 2) == 128 and grouped.row_tile(8448, 2) == 128
    # a problem under one tile: its rows, rounded up to the dtype's sublane tile
    assert grouped.row_tile(42, 4) == 48 and grouped.row_tile(42, 2) == 48 and grouped.row_tile(5, 2) == 16
    # groups 5, 0, 17, 3, 0, 9 under tiles of 8: tiles visited 1, 0, 3, 2, 0, 2
    first, visits = grouped.tile_visits(np.array([5, 0, 17, 3, 0, 9]), 8, np)
    np.testing.assert_array_equal(visits, [1, 0, 3, 2, 0, 2])
    np.testing.assert_array_equal(first[[0, 2, 3, 5]], [0, 0, 2, 3])
    assert grouped.computed_rows(np.array([[5, 0, 17, 3, 0, 9], [0, 0, 0, 0, 0, 0]]), 8) == 64
    assert grouped._col_tile(2048, 1024, 2) == 1024


# The benchmark's seven expert configurations, and the columns of the block a
# visit read of each one's bf16 ``w_up`` / ``w_gate`` and ``w_down`` under the 4
# MiB budget alone, as it stood until PR 63. The floor on a block's rows moves
# the three wide hidden sizes' up / gate blocks and no other (PERF.md section 6,
# PR 63: the kernel-alone sweep of tools/moe_kernels.py, and what a wider block
# cost MiMo-V2-Flash in its cell).
COLUMNS_BEFORE = {
    "olmoe-1b-7b": (1024, 2048),
    "qwen3-next-80b-a3b": (512, 2048),
    "mimo-v2-flash": (512, 1024),
    "k-exaone-236b-a23b": (256, 1024),
    "a.x-k1": (256, 1024),
    "longcat-flash-chat": (256, 1024),
    "kimi-linear-48b-a3b": (512, 1152),
}


@pytest.mark.parametrize("product", ["up", "down"])
@pytest.mark.parametrize("name", sorted(COLUMNS_BEFORE))
def test_the_weight_block_of_the_benchmarks_expert_shapes(name, product):
    from deepspeed_tpu.models.hf import config_from_hf

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmarks", "configs", name + ".json")) as f:
        cfg = config_from_hf(json.load(f))
    k, n = (cfg.hidden_size, cfg.expert_dim) if product == "up" else (cfg.expert_dim, cfg.hidden_size)
    tn = grouped._col_tile(k, n, 2)
    before = COLUMNS_BEFORE[name][product == "down"]
    assert n % tn == 0 and tn % 128 == 0
    if before * 2 < grouped._MIN_ROW_BYTES:
        # a wide hidden size's up / gate block: rows of a kilobyte, half the passes
        assert cfg.hidden_size >= 6144 and product == "up" and tn * 2 == grouped._MIN_ROW_BYTES
    else:
        assert tn == before
    # both buffers of the block, of a 128-row tile and of the output block, and
    # the product's float32 result, inside the stated limit
    vmem = 2 * (k * tn + 128 * k + 128 * tn) * 2 + 128 * tn * 4
    assert vmem <= grouped._VMEM_LIMIT_BYTES


def test_a_matrix_over_the_budget_is_read_in_column_tiles():
    assert grouped._col_tile(8192, 4096, 2) == 512       # the floor: 8 MiB a block
    assert grouped._col_tile(1024, 4096, 2) == 2048      # the budget: 4 MiB a block
    assert grouped._col_tile(6144, 2048, 4) == 256       # float32: a kilobyte a row is 256 columns
    assert grouped._col_tile(1024, 2304, 2) == 1152      # the widest multiple of 128 that divides n
    assert grouped._col_tile(64, 100, 2) == 100          # an n that is no multiple of 128 is not cut


def test_serving_counters_equal_a_count_by_hand():
    """One prompt of 13 tokens through an engine with chunks of 8 and a grid
    of 4 decode slots + 8 chunk slots (ONE chunk row of the two the scheduler
    may cut; the 4 decode slots alone for the step with no chunk): the step
    programs return what every layer routed,
    padding excluded, and the core folds it into the counters."""
    from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.serving.cluster.core import EngineCore
    from deepspeed_tpu.serving.metrics import ServingMetrics

    cfg = _config(n_experts=8, moe_top_k=2, n_layers=2, max_seq_len=64)
    params = init_params(cfg, jax.random.key(0))
    rc = RaggedInferenceEngineConfig.from_dict({
        "dtype": "float32", "prompt_chunk": 8, "max_prompt_chunks": 2,
        "kv_cache": {"block_size": 4, "num_blocks": 32, "max_blocks_per_seq": 8},
        "state_manager": {"max_tracked_sequences": 4, "max_ragged_batch_size": 64,
                          "max_ragged_sequence_count": 4, "max_context": 32}})
    eng = InferenceEngineV2(cfg, params, rc)
    core = EngineCore(eng, name="d0", metrics=ServingMetrics())
    eng.scheduler.submit(0, np.arange(1, 14, dtype=np.int32))
    L, k = 2, 2
    want = []
    for real in (8, 5, 1):                       # chunk, chunk, one decode row
        toks = eng.step_tokens()
        core._count_step()
        # a step with a chunk has the chunk's row, the decode-only step its 4 slots
        assert eng.last_step.scheduled_tokens == real
        assert eng.last_step.grid_slots == (4 + 8 if real > 1 else 4)
        assert eng.last_step.moe["routed"] == real * k * L and eng.last_step.moe["calls"] == L
        # the fullest expert holds at least the mean and at most every token
        assert real * k * L / 8 <= eng.last_step.moe["hot"] <= real * L
        assert eng.last_step.moe["computed"] % 8 == 0 and eng.last_step.moe["computed"] >= eng.last_step.moe["routed"]
        want.append(dict(eng.last_step.moe))
        for uid, tok in toks.items():
            eng.scheduler.feedback(uid, tok)
    c = core.metrics.counters
    assert c["moe_routed_rows_total"] == (8 + 5 + 1) * k * L
    assert c["moe_layer_calls_total"] == 3 * L
    assert c["moe_hot_expert_rows_total"] == sum(w["hot"] for w in want)
    assert c["moe_computed_rows_total"] == sum(w["computed"] for w in want)
    assert "moe_routed_rows_total 56" in core.metrics.prometheus_text()
    # a dense model counts nothing
    dense = dataclasses.replace(cfg, n_experts=0)
    eng = InferenceEngineV2(dense, init_params(dense, jax.random.key(0)), rc)
    eng.scheduler.submit(0, np.arange(1, 6, dtype=np.int32))
    eng.step_tokens()
    assert eng.last_step.moe is None


@pytest.mark.parametrize("case", ["moe_tp2", "moe_int8_weights", "full_norm_tp2"])
def test_the_engine_refuses_at_build_what_it_has_no_test_for(case):
    from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    moe = dict(n_experts=8, moe_top_k=2)
    cfg, extra, match = {
        "moe_tp2": (_config(**moe), {"tp_size": 2}, "tp_size=2"),
        "moe_int8_weights": (_config(**moe), {"quant": {"enabled": True}}, "quantized weights"),
        "full_norm_tp2": (_config(n_experts=0, qk_norm=True, qk_norm_kind="rmsnorm_full"),
                          {"tp_size": 2}, "rmsnorm_full"),
    }[case]
    rc = RaggedInferenceEngineConfig.from_dict({
        "dtype": "float32", **extra,
        "kv_cache": {"block_size": 4, "num_blocks": 8, "max_blocks_per_seq": 4},
        "state_manager": {"max_ragged_batch_size": 16, "max_ragged_sequence_count": 2}})
    with pytest.raises(NotImplementedError, match=match):
        InferenceEngineV2(cfg, init_params(cfg, jax.random.key(0)), rc)
