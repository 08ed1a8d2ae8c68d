"""The five readers PR 53 adds (the two state-space kernels' shares of the device's time and of
the memory's roofline, and a state slot's size), on hand-made counts and a hand-made trace. Run
by hand on the CPU with the other tests of this directory:
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``."""

import importlib

import pytest

from benchmarks.harness import peaks
from benchmarks.harness.common import Catalog
from benchmarks.metrics import sat_mamba_decode_roofline_pct as decode_roofline
from benchmarks.metrics import sat_mamba_scan_roofline_pct as scan_roofline

CELL = "jamba2-3b.serve-doc-reason-closed64"
HF = Catalog().config("jamba2-3b")
V5E = "TPU v5 lite"
NEW = ("sat_mamba_scan_time_pct", "sat_mamba_decode_time_pct", "sat_mamba_decode_roofline_pct",
       "sat_mamba_scan_roofline_pct", "sat_state_slot_mb")


def reader(name):
    return importlib.import_module(f"benchmarks.metrics.{name}").read


def record(counters=True, trace=True, scan_s=0.9, decode_s=0.3, hf=HF):
    """A 10 s window of 100 steps, 40 of them with a prompt chunk, whose decode rows' states
    took 3,000 one-token updates a Mamba layer and whose scans walked 30,000 prompt tokens a
    layer; its last 3 s traced, 30 launches begun in them."""
    before = {"engine_steps_total": 10, "steps_with_prefill_total": 5}
    after = {"engine_steps_total": 110, "steps_with_prefill_total": 45}
    if counters:
        before.update(mamba_decode_rows_total=300, mamba_chunk_tokens_total=2000,
                      state_slot_bytes=9_318_400)
        after.update(mamba_decode_rows_total=3300, mamba_chunk_tokens_total=32000,
                     state_slot_bytes=9_318_400)
    spans = [("engine.launch", 107.0 + 0.1 * i, 107.004 + 0.1 * i) for i in range(30)]
    spans += [("engine.launch", 106.95, 106.96), ("engine.launch", 109.99, None)]
    ops = [["fusion:kOutput", 0.4]]
    if scan_s is not None:
        ops.insert(0, ["dstpu_mamba_scan custom-call:tpu_custom_call", scan_s])
    if decode_s is not None:
        ops.insert(0, ["dstpu_mamba_decode custom-call:tpu_custom_call", decode_s])
    return {"cell": CELL, "hf": hf, "device_kind": V5E, "t_window0": 100.0, "t_window1": 110.0,
            "spans": spans, "snapshots": {0: {"counters": before}, 1: {"counters": after}},
            "trace": {"window_s": 3.0, "device_ops": ops, "busy_s_by_device": {0: 2.5}}
            if trace else None}


def test_time_shares_are_the_kernels_seconds_over_busy():
    assert reader("sat_mamba_scan_time_pct")(record()) == pytest.approx(100.0 * 0.9 / 2.5)
    assert reader("sat_mamba_decode_time_pct")(record()) == pytest.approx(100.0 * 0.3 / 2.5)
    for name in ("sat_mamba_scan_time_pct", "sat_mamba_decode_time_pct"):
        assert reader(name)(record(scan_s=None, decode_s=None)) is None  # the parent: no such kernel
        assert reader(name)(record(trace=False)) is None


def test_decode_bytes_against_a_hand_count():
    # a row: [5120, 16] float32 in and out; u, delta, z in and y out at 5,120; B and C at 16
    row = 4 * (2 * 5120 * 16 + 4 * 5120 + 2 * 16)
    assert row == 655_360 + 81_920 + 128
    assert decode_roofline.bytes(1, HF) == row and decode_roofline.bytes(30, HF) == 30 * row
    assert decode_roofline.layers(HF) == 26
    # 30 rows a step, 30 traced steps, 26 Mamba layers: over 819 GB/s, against 0.3 s
    want = 100.0 * (30 * 26 * 30 * row) / peaks.device_peaks(V5E).hbm_bytes_s / 0.3
    assert reader("sat_mamba_decode_roofline_pct")(record()) == pytest.approx(want)
    assert 0 < want < 100


def test_scan_bytes_against_a_hand_count():
    token, state = 4 * (4 * 5120 + 2 * 16), 4 * 2 * 5120 * 16
    assert scan_roofline.bytes(512, 1, HF) == 512 * token + state
    # 300 tokens and 0.4 chunk rows a step, 30 traced steps, 26 layers, against 0.9 s
    want = 100.0 * 30 * 26 * (300 * token + 0.4 * state) / peaks.device_peaks(V5E).hbm_bytes_s / 0.9
    assert reader("sat_mamba_scan_roofline_pct")(record()) == pytest.approx(want)
    assert 0 < want < 100


def test_a_slots_size_is_the_counters():
    read = reader("sat_state_slot_mb")
    assert read(record()) == pytest.approx(9.3184)
    assert read(record(trace=False)) == pytest.approx(9.3184)   # a counter: no trace needed
    assert read(record(counters=False)) is None                 # the parent: no such counter


@pytest.mark.parametrize("name", NEW[:4])
def test_nothing_to_read_is_none_and_never_raises(name):
    read = reader(name)
    assert read(record(trace=False)) is None
    assert read(record(counters=False, scan_s=None, decode_s=None)) is None  # the parent's line
    if "roofline" in name:
        assert read(record(counters=False)) is None          # no such counters
        other = Catalog().config("qwen3-1.7b")               # a configuration without Mamba layers
        assert read(record(hf=other)) is None


def test_the_index_lists_the_readers_for_the_cell():
    by_name = {m["name"]: m for m in Catalog().index["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["moves"] == "gen_tok_s"
