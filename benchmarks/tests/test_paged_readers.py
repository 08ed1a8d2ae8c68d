"""The readers of the paged decode kernel's counters and its roofline share,
on hand-made counts and a hand-made trace. Run by hand on the CPU with the
other tests of this directory: ``JAX_PLATFORMS=cpu python -m pytest
benchmarks/tests -q``."""

import importlib

import pytest

from benchmarks.harness import peaks
from benchmarks.harness.common import Catalog
from benchmarks.metrics import serve_paged_roofline_pct as roofline

QWEN3 = Catalog().config("qwen3-1.7b")
OLMOE = Catalog().config("olmoe-1b-7b")
V5E = "TPU v5 lite"


def reader(name):
    return importlib.import_module(f"benchmarks.metrics.{name}").read


def record(cell="qwen3-1.7b.serve-decode-closed64", hf=QWEN3, counters=True, trace=True,
           kernel_s=0.5):
    """A 10 s window of 100 steps that held 18,500 live blocks in 102,400
    table slots; its last 3 s traced, with 30 launches begun in them (one
    before, one still open) and ``kernel_s`` seconds under the kernel."""
    before = {"engine_steps_total": 10, "decode_tokens_total": 50}
    after = {"engine_steps_total": 110, "decode_tokens_total": 3250}
    if counters:
        before.update(paged_live_blocks_total=1000, paged_table_slots_total=10240)
        after.update(paged_live_blocks_total=19500, paged_table_slots_total=112640)
    spans = [("engine.launch", 107.0 + 0.1 * i, 107.004 + 0.1 * i) for i in range(30)]
    spans += [("engine.launch", 106.95, 106.96), ("engine.launch", 109.99, None),
              ("engine.stage", 107.5, 107.501)]
    ops = [["fusion:kOutput", 0.4], ["copy", 0.03]]
    if kernel_s is not None:
        ops.insert(0, ["dstpu_paged_decode.1", kernel_s])
    return {"cell": cell, "hf": hf, "device_kind": V5E, "t_window0": 100.0, "t_window1": 110.0,
            "spans": spans, "snapshots": {0: {"counters": before}, 1: {"counters": after}},
            "trace": {"window_s": 3.0, "device_ops": ops, "busy_s_by_device": {0: 2.5}}
            if trace else None}


@pytest.mark.parametrize("form", ["serve_", "sat_"])
def test_live_block_share_is_the_windows_difference(form):
    read = reader(form + "paged_live_block_pct")
    assert read(record()) == pytest.approx(100.0 * 18500 / 102400)
    assert read(record(trace=False)) == pytest.approx(100.0 * 18500 / 102400)  # a counter: no trace needed
    assert read(record(counters=False)) is None  # the parent: no such counter


@pytest.mark.parametrize("hf,cell,block_bytes", [
    # K and V of one block: 128 tokens x 8 KV heads x 128 x 2 bytes, twice
    (QWEN3, "qwen3-1.7b.serve-decode-closed64", 2 * 128 * 8 * 128 * 2),
    # 16 KV heads, and no head_dim key: 2048 / 16
    (OLMOE, "olmoe-1b-7b.serve-decode-closed64", 2 * 128 * 16 * 128 * 2),
])
def test_bytes_against_a_hand_count(hf, cell, block_bytes):
    assert block_bytes in (524288, 1048576)
    assert roofline.bytes(1, hf, 128) == block_bytes
    assert roofline.bytes(185, hf, 128) == 185 * block_bytes
    # 185 live blocks a step, 30 traced steps, every layer: over 819 GB/s, against 0.5 s
    need = 30 * int(hf["num_hidden_layers"]) * 185 * block_bytes
    want = 100.0 * need / peaks.device_peaks(V5E).hbm_bytes_s / 0.5
    for form in ("serve_", "sat_"):
        assert reader(form + "paged_roofline_pct")(record(cell=cell, hf=hf)) == pytest.approx(want)
    assert 0 < want < 100


@pytest.mark.parametrize("form", ["serve_", "sat_"])
def test_roofline_share_reads_nothing_where_a_part_is_missing(form):
    read = reader(form + "paged_roofline_pct")
    assert read(record(counters=False)) is None   # the parent's program
    assert read(record(trace=False)) is None      # an untraced run
    assert read(record(kernel_s=None)) is None    # the kernel's name not among the listed operations
    assert read(record(kernel_s=0.0)) is None     # zero seconds under it
    rec = record()
    rec["spans"] = [s for s in rec["spans"] if s[0] != "engine.launch"]
    assert read(rec) is None                      # tracing off in the program: no launch spans


def test_benchmark_json_declares_the_four():
    index = {m["name"]: m for m in Catalog().index["per_layer"]}
    closed = ["qwen3-1.7b.serve-decode-closed64", "olmoe-1b-7b.serve-decode-closed64"]
    for name, moves, cells in (
            ("serve_paged_live_block_pct", "tpot_p50_ms", ["qwen3-1.7b.serve-prefill-open"]),
            ("sat_paged_live_block_pct", "gen_tok_s", closed),
            ("serve_paged_roofline_pct", "tpot_p50_ms", ["qwen3-1.7b.serve-prefill-open"]),
            ("sat_paged_roofline_pct", "gen_tok_s", closed)):
        m = index[name]
        assert (m["layer"], m["moves"], m["workloads"], m["unit"]) == ("kernels", moves, cells, "%")
