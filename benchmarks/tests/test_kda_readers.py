"""The three readers PR 56 adds (the KDA one-token update's share of the device's time and of
its roofline, and the latent planes' bytes a token over the latent layers alone), on hand-made
counts and a hand-made trace. Run by hand on the CPU with the other tests of this directory:
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``."""

import importlib
import json
import os

import pytest

from benchmarks.harness import peaks
from benchmarks.harness.common import REPO, Catalog
from benchmarks.metrics import sat_hybrid_latent_bytes_per_token as latent_bytes
from benchmarks.metrics import sat_kda_decode_roofline_pct as kda_roofline

CELL = "kimi-linear-48b-a3b.serve-doc-xlong-closed64"
HF = Catalog().config("kimi-linear-48b-a3b")
V5E = "TPU v5 lite"
NEW = ("sat_kda_decode_time_pct", "sat_kda_decode_roofline_pct", "sat_hybrid_latent_bytes_per_token")


def reader(name):
    return importlib.import_module(f"benchmarks.metrics.{name}").read


def record(counters=True, trace=True, kda_s=0.6, hf=HF):
    """A 10 s window of 100 steps whose decode rows' states took 3,100 one-token updates a KDA
    layer and whose tables held 40,000 latent blocks, summed a step; its last 3 s traced, 30
    launches begun in them."""
    before, after = {"engine_steps_total": 10}, {"engine_steps_total": 110}
    if counters:
        before.update(kda_decode_rows_total=300, latent_live_blocks_total=5000, state_slot_bytes=19_537_920)
        after.update(kda_decode_rows_total=3400, latent_live_blocks_total=45000, state_slot_bytes=19_537_920)
    spans = [("engine.launch", 107.0 + 0.1 * i, 107.004 + 0.1 * i) for i in range(30)]
    spans += [("engine.launch", 106.95, 106.96), ("engine.launch", 109.99, None)]
    ops = [["fusion:kOutput", 0.4], ["dstpu_gdn_decode custom-call:tpu_custom_call", 0.3]]
    if kda_s is not None:
        ops.insert(0, ["dstpu_kda_decode custom-call:tpu_custom_call", kda_s])
    return {"cell": CELL, "hf": hf, "device_kind": V5E, "t_window0": 100.0, "t_window1": 110.0,
            "spans": spans, "snapshots": {0: {"counters": before}, 1: {"counters": after}},
            "trace": {"window_s": 3.0, "device_ops": ops, "busy_s_by_device": {0: 2.5}}
            if trace else None}


def test_time_share_is_the_kernels_seconds_over_busy_and_not_gdns():
    read = reader("sat_kda_decode_time_pct")
    assert read(record()) == pytest.approx(100.0 * 0.6 / 2.5)
    assert read(record(kda_s=None)) is None  # the parent: no such kernel (GDN's name does not count)
    assert read(record(trace=False)) is None


def test_state_bytes_against_a_hand_count():
    # a row: [32, 128, 128] float32 in and out; q, k, v, the decays and the output at 32 x 128; beta 32
    row = 4 * (2 * 32 * 128 * 128 + 5 * 32 * 128 + 32)
    assert row == 4_194_304 + 81_920 + 128
    assert kda_roofline.bytes(1, HF) == row and kda_roofline.bytes(31, HF) == 31 * row
    assert kda_roofline.layers(HF) == 9          # the KDA layers the share has, not 12 and not 20
    want = 100.0 * (30 * 9 * 31 * row) / peaks.device_peaks(V5E).hbm_bytes_s / 0.6
    assert reader("sat_kda_decode_roofline_pct")(record()) == pytest.approx(want)
    assert 0 < want < 100
    assert reader("sat_kda_decode_roofline_pct")(record(counters=False)) is None   # the parent
    assert reader("sat_kda_decode_roofline_pct")(record(trace=False)) is None
    assert reader("sat_kda_decode_roofline_pct")(record(hf={"num_hidden_layers": 12})) is None


def test_latent_bytes_a_token_count_the_latent_layers_alone():
    assert latent_bytes.layers(HF) == 3
    assert latent_bytes.bytes(1, HF, 128) == 2 * 3 * 128 * 576
    assert reader("sat_hybrid_latent_bytes_per_token")(record()) == pytest.approx(3456.0)
    assert reader("sat_hybrid_latent_bytes_per_token")(record(trace=False)) == pytest.approx(3456.0)
    assert reader("sat_hybrid_latent_bytes_per_token")(record(counters=False)) is None
    # the accepted reader multiplies by num_hidden_layers: four times too high here
    assert importlib.import_module("benchmarks.metrics.sat_latent_bytes_per_token").read(record()) == 4 * 3456.0


def test_the_index_lists_the_new_readers_for_the_new_cell_alone():
    index = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    by_name = {m["name"]: m for m in index["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["moves"] == "gen_tok_s"
    for name in ("sat_latent_bytes_per_token", "sat_mla_decode_roofline_pct", "sat_moe_lead_gmm_roofline_pct"):
        assert CELL not in by_name[name]["workloads"]      # their layer counts would misread this cell
    for name in ("sat_state_slot_mb", "sat_mla_decode_time_pct", "sat_mla_chunk_time_pct", "rows_per_step"):
        assert CELL in by_name[name]["workloads"]
