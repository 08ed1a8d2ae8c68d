"""The five readers PR 31 adds (cache bytes a token and the block pool's fill by kind, a window
layer's share of a global layer's decode walk, the paged kernel's roofline share with bytes
summed by kind, and the grouped matmul's over the layers that have experts), on hand-made
counts and a hand-made trace. Run by hand on the CPU with the other tests of this directory:
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``."""

import importlib

import pytest

from benchmarks.harness import peaks
from benchmarks.harness.common import Catalog
from benchmarks.metrics import sat_kv_bytes_per_token as per_token
from benchmarks.metrics import sat_kv_global_fill_pct as fill
from benchmarks.metrics import sat_moe_sparse_gmm_roofline_pct as sparse_roofline

CELL = "k-exaone-236b-a23b.serve-reason-long-closed64"
HF = Catalog().config("k-exaone-236b-a23b")
V5E = "TPU v5 lite"
NEW = {"sat_kv_bytes_per_token": "cache", "sat_kv_global_fill_pct": "cache",
       "sat_paged_window_live_block_pct": "kernels", "sat_paged_kinds_roofline_pct": "kernels",
       "sat_moe_sparse_gmm_roofline_pct": "expert layer"}
BLOCK = 128 * 8 * 128 * 2 * 2  # K and V of 128 tokens at 8 heads of 128 in bf16: 512 KiB a layer


def reader(name):
    return importlib.import_module(f"benchmarks.metrics.{name}").read


def record(counters=True, trace=True, paged_s=0.5, gmm_s=1.2, hf=HF):
    """A 10 s window of 100 steps with 32 tracked sequences of 4,000 tokens: 1,000 global blocks
    and 64 ring blocks held a step, 128,000 tokens of context; a step's 32 decode rows walk 1,000
    blocks in a global layer and 62 in a window layer; 700 expert-layer calls (7 sparse layers)
    route 1,792 pairs each and hit 14 of the 16 held experts; the last 3 s traced, 30 launches."""
    before = {"engine_steps_total": 10, "paged_live_blocks_total": 9000,
              "moe_layer_calls_total": 70, "moe_routed_rows_total": 125440, "moe_experts_hit_total": 980}
    after = {"engine_steps_total": 110, "paged_live_blocks_total": 109000,
             "moe_layer_calls_total": 770, "moe_routed_rows_total": 1379840, "moe_experts_hit_total": 10780}
    if counters:
        before.update(kv_global_blocks_used_total=7000, kv_window_blocks_used_total=600,
                      kv_context_tokens_total=900000, paged_window_live_blocks_total=500)
        after.update(kv_global_blocks_used_total=107000, kv_window_blocks_used_total=7000,
                     kv_context_tokens_total=13700000, paged_window_live_blocks_total=6700)
    spans = [("engine.launch", 107.0 + 0.1 * i, 107.004 + 0.1 * i) for i in range(30)]
    spans += [("engine.launch", 106.95, 106.96), ("engine.launch", 109.99, None)]
    ops = [["fusion:kOutput", 0.4]]
    if gmm_s is not None:
        ops.insert(0, ["dstpu_moe_gmm custom-call:tpu_custom_call", gmm_s])
    if paged_s is not None:
        ops.insert(0, ["dstpu_paged_decode custom-call:tpu_custom_call", paged_s])
    return {"cell": CELL, "hf": hf, "device_kind": V5E, "t_window0": 100.0, "t_window1": 110.0,
            "spans": spans, "snapshots": {0: {"counters": before}, 1: {"counters": after}},
            "trace": {"window_s": 3.0, "device_ops": ops, "busy_s_by_device": {0: 2.5}}
            if trace else None}


def test_layers_by_kind_and_a_blocks_bytes():
    assert per_token.layer_counts(HF) == (2, 6)      # the stage's 8 of the published 48
    assert sparse_roofline.sparse_layers(HF) == 7    # layer 0 is dense
    assert per_token.bytes(1, 1, HF, 128) == BLOCK == 524288
    assert per_token.bytes(3, 6, HF, 128) == 18 * BLOCK


def test_bytes_per_token_by_kind():
    # a step: 1,000 global blocks over 2 layers + 64 ring blocks over 6 layers, 128,000 tokens
    want = (1000 * 2 + 64 * 6) * BLOCK / 128000
    assert reader("sat_kv_bytes_per_token")(record()) == pytest.approx(want)
    assert 8 * 1024 < want < 12 * 1024               # a uniform pool would read 32 KiB
    assert reader("sat_kv_bytes_per_token")(record(trace=False)) == pytest.approx(want)  # counters
    assert reader("sat_kv_bytes_per_token")(record(counters=False)) is None              # the parent


def test_global_fill_against_the_pool_the_program_sizes():
    args = Catalog().cell(CELL)["serve_args"]
    assert fill.ring_blocks(128, 128) == 2 and fill.ring_blocks(16, 8) == 3
    # 2e9 less 33 rings of 2 blocks over 6 layers, over 1 MiB a block over 2 layers, less the trash
    assert fill.pool_blocks(HF, args) == (2_000_000_000 - 33 * 2 * 6 * BLOCK) // (2 * BLOCK) - 1 == 1708
    assert reader("sat_kv_global_fill_pct")(record()) == pytest.approx(100.0 * 1000 / 1708)
    assert reader("sat_kv_global_fill_pct")(record(counters=False)) is None


def test_window_walk_over_global_walk():
    assert reader("sat_paged_window_live_block_pct")(record()) == pytest.approx(6.2)
    assert reader("sat_paged_window_live_block_pct")(record(counters=False)) is None


def test_paged_roofline_sums_bytes_by_kind():
    a_step = (1000 * 2 + 62 * 6) * BLOCK
    want = 100.0 * 30 * a_step / peaks.device_peaks(V5E).hbm_bytes_s / 0.5
    assert reader("sat_paged_kinds_roofline_pct")(record()) == pytest.approx(want)
    assert 0 < want < 100
    # counting every layer as a global one (num_hidden_layers x the global walk) reads 3.4x that
    uniform = 100.0 * 30 * 8 * 1000 * BLOCK / peaks.device_peaks(V5E).hbm_bytes_s / 0.5
    assert uniform / want == pytest.approx(8000 / 2372)


def test_sparse_gmm_roofline_counts_the_layers_that_have_experts():
    h, f = 6144, 2048
    per_call = 2 * (3 * 14 * h * f + 3 * 1792 * (h + f))
    assert sparse_roofline.bytes(1792, 14, HF) == per_call
    want = 100.0 * (30 * 7 * per_call) / peaks.device_peaks(V5E).hbm_bytes_s / 1.2
    assert reader("sat_moe_sparse_gmm_roofline_pct")(record()) == pytest.approx(want)
    assert 0 < want < 100
    hit = reader("sat_moe_hit_gmm_roofline_pct")(record())   # num_hidden_layers: 8/7 of it
    assert hit == pytest.approx(want * 8 / 7)


@pytest.mark.parametrize("name", ["sat_paged_kinds_roofline_pct", "sat_moe_sparse_gmm_roofline_pct"])
def test_roofline_shares_read_nothing_where_a_part_is_missing(name):
    read = reader(name)
    assert read(record(trace=False)) is None                 # an untraced run
    assert read(record(paged_s=None, gmm_s=None)) is None    # the kernel's name not listed
    assert read(record(paged_s=0.0, gmm_s=0.0)) is None      # zero seconds under it
    rec = record()
    rec["spans"] = []
    assert read(rec) is None                                 # tracing off in the program
    assert read(record(hf=Catalog().config("qwen3-1.7b"))) is None  # no layer kinds to count by
    if "paged" in name:
        assert read(record(counters=False)) is None          # the parent's program


def test_benchmark_json_declares_the_five():
    index = {m["name"]: m for m in Catalog().index["per_layer"]}
    for name, layer in NEW.items():
        m = index[name]
        assert (m["moves"], m["workloads"], m["layer"]) == ("gen_tok_s", [CELL], layer)
        assert m["unit"] == ("bytes/token" if name == "sat_kv_bytes_per_token" else "%")
    # the readers that count num_hidden_layers of one kind are not asked of the cell
    for name in ("sat_moe_gmm_roofline_pct", "sat_moe_hit_gmm_roofline_pct", "sat_paged_roofline_pct"):
        assert CELL not in index[name]["workloads"]
    cells = {w["name"]: w for w in Catalog().index["workloads"]}
    assert cells[CELL]["chips"] == 1 and sum(w["chips"] == 4 for w in cells.values()) == 1
