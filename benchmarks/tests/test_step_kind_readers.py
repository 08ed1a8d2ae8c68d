"""The readers of the device's step timed by kind (decode steps and chunk steps apart, launches
that found the chip dry, the program's account of busy time against the profiler's), on hand-made
counts and stamps. Run by hand on the CPU with the other tests of this directory:
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``."""

import importlib

import pytest

from benchmarks.harness.common import Catalog

CLOSED = ["qwen3-1.7b.serve-decode-closed64", "olmoe-1b-7b.serve-decode-closed64",
          "qwen3-next-80b-a3b.serve-decode-closed64",
          "k-exaone-236b-a23b.serve-reason-long-closed64"]
OPEN = ["qwen3-1.7b.serve-prefill-open"]
BOTH_FORMS = ("decode_step_ms", "chunk_step_ms", "chunk_step_time_pct", "steps_starved_pct",
              "step_clock_error_ms")
NEW = {"decode_step_seconds_total": (1.0, 25.0), "decode_steps_timed_total": (100, 1700),
       "chunk_step_seconds_total": (0.5, 16.5), "chunk_steps_timed_total": (20, 420),
       "steps_starved_total": (3, 43)}


def reader(name):
    return importlib.import_module(f"benchmarks.metrics.{name}").read


def record(new=NEW, steps=(130, 2130), spans=(), trace=None):
    """A window of 2,000 steps: 1,600 decode steps of 15 ms, 400 chunk steps of 40 ms, 40 of them
    enqueued behind a step that had already finished."""
    before = {"engine_steps_total": steps[0]}
    after = {"engine_steps_total": steps[1]}
    for name, (c0, c1) in (new or {}).items():
        before[name], after[name] = c0, c1
    return {"snapshots": {0: {"counters": before}, 1: {"counters": after}},
            "t_window0": 100.0, "t_window1": 150.0, "spans": list(spans), "trace": trace}


@pytest.mark.parametrize("form", ["", "sat_"])
def test_counter_readers_are_the_windows_differences(form):
    rec = record()
    assert reader(form + "decode_step_ms")(rec) == pytest.approx(15.0)
    assert reader(form + "chunk_step_ms")(rec) == pytest.approx(40.0)
    assert reader(form + "chunk_step_time_pct")(rec) == pytest.approx(100.0 * 16 / (16 + 24))
    assert reader(form + "steps_starved_pct")(rec) == pytest.approx(2.0)
    # the parent: no such counters, and the line leaves the metrics out
    for name in ("decode_step_ms", "chunk_step_ms", "chunk_step_time_pct", "steps_starved_pct"):
        assert reader(form + name)(record(new=None)) is None, name
    # a window with no step of a kind has no mean for it; one with no timed step no share
    still = {**NEW, "chunk_step_seconds_total": (0.5, 0.5), "chunk_steps_timed_total": (20, 20)}
    assert reader(form + "chunk_step_ms")(record(new=still)) is None
    assert reader(form + "chunk_step_time_pct")(record(new=still)) == 0.0
    assert reader(form + "decode_step_ms")(record(new=still)) == pytest.approx(15.0)
    nothing = {name: (c0, c0) for name, (c0, _) in NEW.items()}
    assert reader(form + "chunk_step_time_pct")(record(new=nothing)) is None
    assert reader(form + "steps_starved_pct")(record(new=NEW, steps=(130, 130))) is None
    # a compute-free engine counts steps and no second of them
    assert reader(form + "steps_starved_pct")(record(new=nothing)) == 0.0


def steps(t, lengths):
    """Steps back to back from ``t``: a chunk step wherever the length is 40 ms or more."""
    out = []
    for ms in lengths:
        out.append(("step.chunk" if ms >= 40 else "step.decode", t, t + ms * 1e-3))
        t += ms * 1e-3
    return out, t


@pytest.mark.parametrize("form", ["", "sat_"])
def test_clock_error_is_the_spans_clipped_to_the_sub_window_against_busy_time(form):
    read = reader(form + "step_clock_error_ms")
    # the traced sub-window is the window's last 0.2 s: 149.8 to 150.0. Steps back to back from
    # 149.79: the first is cut by the sub-window's start (10 of its 15 ms lie before it), the
    # last by its end (it runs 5 ms past it)
    spans, end = steps(149.79, [15, 15, 40, 15, 15, 40, 15, 15, 15, 15, 15])
    assert end == pytest.approx(150.005)
    spans += [("step.decode", 120.0, 120.015),        # before the sub-window: not counted
              ("step.decode", 150.2, 150.215),        # after it
              ("step.chunk", 149.9, None),            # still open
              ("engine.device_wait", 149.8, 149.9), ("step.deliver", 149.85, 149.86),
              ("step.split", 149.8, 150.0)]           # an old program's: not a step
    # the eleven clipped spans cover the whole 0.2 s; the device was busy for 0.1978 of them
    rec = record(spans=spans, trace={"window_s": 0.2, "busy_s": 0.1978})
    assert read(rec) == pytest.approx(1e3 * 0.0022 / 11)
    # the program's account may fall short of the profiler's too: the error has no sign
    rec["trace"]["busy_s"] = 0.2022
    assert read(rec) == pytest.approx(1e3 * 0.0022 / 11)
    assert read(record(spans=spans, trace=None)) is None            # an untraced run
    assert read(record(spans=[s for s in spans if not s[0].startswith(("step.decode",
                                                                        "step.chunk"))],
                       trace={"window_s": 0.2, "busy_s": 0.19})) is None  # the parent: no spans
    assert read(record(spans=spans[-6:-5], trace={"window_s": 0.2, "busy_s": 0.19})) is None


def test_step_p99_is_over_the_spans_that_ended_in_the_window():
    read = reader("step_p99_ms")
    spans, _ = steps(100.0, [15] * 98 + [40, 60])
    # one that ended before the window opened and one after it closed: neither counts
    spans += [("step.chunk", 99.9, 99.99), ("step.chunk", 149.99, 150.2),
              ("step.decode", 140.0, None), ("engine.device_wait", 100.0, 130.0)]
    # 100 lengths: rank 98.01 between the two largest
    assert read(record(spans=spans)) == pytest.approx(40 + 0.01 * 20)
    assert read(record(spans=[("step.split", 100.0, 100.5)])) is None
    assert read(record()) is None


def test_benchmark_json_declares_the_eleven():
    catalog = Catalog()
    index = {m["name"]: m for m in catalog.index["per_layer"]}
    end_to_end = {m["name"]: m for m in catalog.index["end_to_end"]}
    want = {"decode_step_ms": ("serving programs", "tpot_p50_ms", "ms", "program_counter"),
            "chunk_step_ms": ("serving programs", "ttft_p90_ms", "ms", "program_counter"),
            "chunk_step_time_pct": ("serving programs", "tpot_p90_ms", "%", "program_counter"),
            "steps_starved_pct": ("serving loop", "tpot_p50_ms", "%", "program_counter"),
            "step_clock_error_ms": ("serving programs", "tpot_p50_ms", "ms", "program_span"),
            "step_p99_ms": ("serving programs", "tpot_p90_ms", "ms", "program_span")}
    declared = []
    for name, (layer, moves, unit, source) in want.items():
        forms = [(name, moves, OPEN)]
        if name in BOTH_FORMS:
            forms.append(("sat_" + name, "gen_tok_s", CLOSED))
        for full, moved, cells in forms:
            m = index[full]
            declared.append(full)
            assert (m["layer"], m["moves"], m["unit"], m["source"], m["better"]) == (
                layer, moved, unit, source, "lower"), full
            # a later PR may append its cells: these are the ones this reader came with
            assert m["workloads"][: len(cells)] == cells
            # every cell it names reports the end-to-end metric it moves
            judged = end_to_end[moved].get("workloads")
            assert judged is None or set(m["workloads"]) <= set(judged), full
            assert callable(reader(full))
    assert len(declared) == 11 and "sat_step_p99_ms" not in index
    # appended in one block (a later PR appends behind it)
    names = [m["name"] for m in catalog.index["per_layer"]]
    at = names.index("decode_step_ms")
    assert at >= 66 and names[at: at + 11] == [
        "decode_step_ms", "sat_decode_step_ms", "chunk_step_ms", "sat_chunk_step_ms",
        "chunk_step_time_pct", "sat_chunk_step_time_pct", "steps_starved_pct",
        "sat_steps_starved_pct", "step_clock_error_ms", "sat_step_clock_error_ms", "step_p99_ms"]
