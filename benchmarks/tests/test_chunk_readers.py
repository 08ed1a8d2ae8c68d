"""The readers of the chunk attention kernel's counters, on hand-made counts.
Run by hand on the CPU with the other tests of this directory:
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``."""

import importlib

import pytest

from benchmarks.harness.common import Catalog

CLOSED = ["qwen3-1.7b.serve-decode-closed64", "olmoe-1b-7b.serve-decode-closed64",
          "qwen3-next-80b-a3b.serve-decode-closed64"]


def reader(name):
    return importlib.import_module(f"benchmarks.metrics.{name}").read


def record(counters=True):
    """A window of 100 steps of which 22 carried chunks: 2 chunk rows x (32
    table slots + 4 chunk blocks) = 72 slots each, 130 live blocks in all."""
    before = {"engine_steps_total": 10}
    after = {"engine_steps_total": 110}
    if counters:
        before.update(chunk_live_blocks_total=40, chunk_table_slots_total=720)
        after.update(chunk_live_blocks_total=170, chunk_table_slots_total=720 + 22 * 72)
    return {"snapshots": {0: {"counters": before}, 1: {"counters": after}}, "trace": None}


@pytest.mark.parametrize("form", ["serve_", "sat_"])
def test_chunk_live_block_share_is_the_windows_difference(form):
    read = reader(form + "chunk_live_block_pct")
    assert read(record()) == pytest.approx(100.0 * 130 / (22 * 72))
    assert read(record(counters=False)) is None  # the parent: no such counter
    idle = record()
    idle["snapshots"][1]["counters"]["chunk_table_slots_total"] = 720
    assert read(idle) is None  # a window with no chunk step


def test_benchmark_json_declares_the_two():
    index = {m["name"]: m for m in Catalog().index["per_layer"]}
    for name, moves, cells in (
            ("sat_chunk_live_block_pct", "gen_tok_s", CLOSED),
            ("serve_chunk_live_block_pct", "tpot_p50_ms", ["qwen3-1.7b.serve-prefill-open"])):
        m = index[name]
        assert (m["layer"], m["moves"], m["workloads"], m["unit"], m["source"]) == (
            "kernels", moves, cells, "%", "program_counter")
