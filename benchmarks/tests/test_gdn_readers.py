"""The four readers PR 29 adds (the one-token state update's share of the device's time and of
its roofline, the share of the held experts a layer call reads, and the grouped matmul's
roofline share over the experts HIT), on hand-made counts and a hand-made trace. Run by hand
on the CPU with the other tests of this directory:
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``."""

import importlib

import pytest

from benchmarks.harness import peaks
from benchmarks.harness.common import Catalog
from benchmarks.metrics import sat_gdn_decode_roofline_pct as gdn_roofline
from benchmarks.metrics import sat_moe_hit_gmm_roofline_pct as hit_roofline

CELL = "qwen3-next-80b-a3b.serve-decode-closed64"
HF = Catalog().config("qwen3-next-80b-a3b")
V5E = "TPU v5 lite"
NEW = ("sat_gdn_decode_time_pct", "sat_gdn_decode_roofline_pct", "sat_moe_experts_hit_pct",
       "sat_moe_hit_gmm_roofline_pct")


def reader(name):
    return importlib.import_module(f"benchmarks.metrics.{name}").read


def record(counters=True, trace=True, gdn_s=0.2, gmm_s=0.8, hf=HF):
    """A 10 s window of 100 steps (12 layers: 1,200 layer calls) whose decode rows' states took
    3,100 one-token updates a DeltaNet layer, whose expert layers routed 120,000 pairs to the 128
    held experts and hit 72,000 of them; its last 3 s traced, 30 launches begun in them."""
    before = {"engine_steps_total": 10, "moe_layer_calls_total": 120, "moe_routed_rows_total": 9000}
    after = {"engine_steps_total": 110, "moe_layer_calls_total": 1320, "moe_routed_rows_total": 129000}
    if counters:
        before.update(gdn_decode_rows_total=300, moe_experts_hit_total=7000)
        after.update(gdn_decode_rows_total=3400, moe_experts_hit_total=79000)
    spans = [("engine.launch", 107.0 + 0.1 * i, 107.004 + 0.1 * i) for i in range(30)]
    spans += [("engine.launch", 106.95, 106.96), ("engine.launch", 109.99, None)]
    ops = [["fusion:kOutput", 0.4]]
    if gmm_s is not None:
        ops.insert(0, ["dstpu_moe_gmm custom-call:tpu_custom_call", gmm_s])
    if gdn_s is not None:
        ops.insert(0, ["dstpu_gdn_decode custom-call:tpu_custom_call", gdn_s])
    return {"cell": CELL, "hf": hf, "device_kind": V5E, "t_window0": 100.0, "t_window1": 110.0,
            "spans": spans, "snapshots": {0: {"counters": before}, 1: {"counters": after}},
            "trace": {"window_s": 3.0, "device_ops": ops, "busy_s_by_device": {0: 2.5}}
            if trace else None}


def test_time_share_is_the_kernels_seconds_over_busy():
    read = reader("sat_gdn_decode_time_pct")
    assert read(record()) == pytest.approx(100.0 * 0.2 / 2.5)
    assert read(record(gdn_s=None)) is None  # the parent: no such kernel in the trace
    assert read(record(trace=False)) is None


def test_state_bytes_against_a_hand_count():
    # a row: [32, 128, 128] float32 in and out, q and k at 16 key heads of 128, v and o at 32 of 128
    row = 4 * (2 * 32 * 128 * 128 + 2 * 16 * 128 + 2 * 32 * 128)
    assert row == 4_194_304 + 16_384 + 32_768
    assert gdn_roofline.bytes(1, HF) == row and gdn_roofline.bytes(31, HF) == 31 * row
    assert gdn_roofline.layers(HF) == 9
    # 31 rows a step, 30 traced steps, nine DeltaNet layers: over 819 GB/s, against 0.2 s
    want = 100.0 * (30 * 9 * 31 * row) / peaks.device_peaks(V5E).hbm_bytes_s / 0.2
    assert reader("sat_gdn_decode_roofline_pct")(record()) == pytest.approx(want)
    assert 0 < want < 100


def test_experts_hit_is_the_windows_difference():
    read = reader("sat_moe_experts_hit_pct")
    assert read(record()) == pytest.approx(100.0 * 72000 / (1200 * 128))
    assert read(record(trace=False)) == pytest.approx(46.875)  # a counter: no trace needed
    assert read(record(counters=False)) is None                # the parent: no such counter


def test_hit_roofline_counts_the_experts_with_a_row():
    h, f = 2048, 512
    # a layer call: 60 experts hit, 100 rows; three matmuls of [2048, 512] an expert in bf16
    per_call = 2 * (3 * 60 * h * f + 3 * 100 * (h + f))
    assert hit_roofline.bytes(100, 60, HF) == per_call
    # bound by the bytes, and far: a call's operations take a hundredth of its bytes' time
    pk = peaks.device_peaks(V5E)
    assert hit_roofline.ops(100, HF) / pk.bf16_flops < 0.05 * per_call / pk.hbm_bytes_s
    want = 100.0 * (30 * 12 * per_call) / pk.hbm_bytes_s / 0.8
    assert reader("sat_moe_hit_gmm_roofline_pct")(record()) == pytest.approx(want)
    assert 0 < want < 100


@pytest.mark.parametrize("name", ["sat_gdn_decode_roofline_pct", "sat_moe_hit_gmm_roofline_pct"])
def test_roofline_shares_read_nothing_where_a_part_is_missing(name):
    read = reader(name)
    assert read(record(counters=False)) is None              # the parent's program
    assert read(record(trace=False)) is None                 # an untraced run
    assert read(record(gdn_s=None, gmm_s=None)) is None      # the kernel's name not listed
    assert read(record(gdn_s=0.0, gmm_s=0.0)) is None        # zero seconds under it
    rec = record()
    rec["spans"] = []
    assert read(rec) is None                                 # tracing off in the program
    assert read(record(hf=Catalog().config("qwen3-1.7b"))) is None  # no such widths


def test_benchmark_json_declares_the_four():
    index = {m["name"]: m for m in Catalog().index["per_layer"]}
    for name in NEW:
        m = index[name]
        assert (m["moves"], m["workloads"], m["unit"]) == ("gen_tok_s", [CELL], "%")
        assert m["layer"] == ("linear attention" if "gdn" in name else "expert layer")
    # the two readers whose arithmetic assumes other widths are not asked of the cell
    for name in ("sat_moe_gmm_roofline_pct", "sat_paged_roofline_pct"):
        assert CELL not in index[name]["workloads"]
