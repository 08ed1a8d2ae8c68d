"""The readers of the serving loop's run-ahead counter, on hand-made counts.
Run by hand on the CPU with the other tests of this directory:
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``."""

import importlib

import pytest

from benchmarks.harness.common import Catalog

CLOSED = ["qwen3-1.7b.serve-decode-closed64", "olmoe-1b-7b.serve-decode-closed64",
          "qwen3-next-80b-a3b.serve-decode-closed64",
          "k-exaone-236b-a23b.serve-reason-long-closed64"]
OPEN = ["qwen3-1.7b.serve-prefill-open"]


def reader(name):
    return importlib.import_module(f"benchmarks.metrics.{name}").read


def record(ahead=(7, 1997), steps=(10, 2010)):
    """A window of 2,000 steps of which 1,990 were launched with their
    predecessor in flight (10 followed an idle loop)."""
    before = {"engine_steps_total": steps[0]}
    after = {"engine_steps_total": steps[1]}
    if ahead is not None:
        before["steps_ahead_total"], after["steps_ahead_total"] = ahead
    return {"snapshots": {0: {"counters": before}, 1: {"counters": after}}, "trace": None}


@pytest.mark.parametrize("name", ["steps_ahead_pct", "sat_steps_ahead_pct"])
def test_steps_ahead_share_is_the_windows_difference(name):
    read = reader(name)
    assert read(record()) == pytest.approx(100.0 * 1990 / 2000)
    assert read(record(ahead=None)) is None  # the parent: no such counter
    assert read(record(steps=(10, 10))) is None  # a window with no step
    # a core that collects where it launches counts steps and none ahead
    assert read(record(ahead=(0, 0))) == 0.0


def test_benchmark_json_declares_the_two():
    index = {m["name"]: m for m in Catalog().index["per_layer"]}
    for name, moves, cells in (("steps_ahead_pct", "tpot_p50_ms", OPEN),
                               ("sat_steps_ahead_pct", "gen_tok_s", CLOSED)):
        m = index[name]
        assert (m["layer"], m["moves"], m["unit"], m["source"], m["better"]) == (
            "serving loop", moves, "%", "program_counter", "higher")
        # a later PR may append its cells: these are the ones this reader came with
        assert m["workloads"][: len(cells)] == cells
