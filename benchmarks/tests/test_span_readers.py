"""The readers of the program's own spans, counters and kernel names, on
hand-made stamps. Run by hand on the CPU with the other tests of this
directory: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``."""

import importlib

import pytest

SERVING = ("host_gap_ms_per_step", "schedule_ms_per_step", "stage_ms_per_step",
           "launch_ms_per_step", "deliver_ms_per_step", "loop_ms_per_step",
           "grid_fill_pct", "steps_with_prefill_pct")


def reader(name):
    return importlib.import_module(f"benchmarks.metrics.{name}").read


def step(t, wait=0.100, admit=0.0002):
    """One synchronous step that starts at ``t``: 1 ms of scheduling, 2 ms of
    staging, 3 ms of launch, the wait, 1 ms to the host, 2 ms of delivery,
    then admission and 0.5 ms of bookkeeping under the loop's lock."""
    w = t + 0.0065 + wait
    return [
        ("loop.admit", t - 0.0002, t),
        ("engine.dispatch", t, t + 0.0065),
        ("engine.schedule", t, t + 0.001),
        ("engine.stage", t + 0.001, t + 0.003),
        ("engine.launch", t + 0.003, t + 0.006),
        ("engine.device_wait", t + 0.0065, w),
        ("engine.materialize", w, w + 0.001),
        ("step.split", t, w + 0.001),
        ("step.deliver", w + 0.001, w + 0.003),
        ("loop.admit", w + 0.003, w + 0.003 + admit),
        ("loop.bookkeeping", w + 0.003 + admit, w + 0.0035 + admit),
    ]


def record():
    """Three steps back to back, then 40 ms with no work, then a fourth; one
    step before the window and one after it."""
    period = 0.0065 + 0.100 + 0.0035 + 0.0002 + 0.0002   # one step to the next
    starts = [100.0 + i * period for i in range(3)]
    idle0 = starts[2] + period - 0.0002                   # where the third step's bookkeeping ends
    starts.append(idle0 + 0.040 + 0.0002)
    spans = [s for t in [99.0] + starts + [111.0] for s in step(t)]
    spans.append(("loop.wait", idle0, idle0 + 0.040))
    spans.append(("loop.wait", 98.0, 98.5))               # before the window
    spans.append(("engine.launch", 105.0, None))          # still open: not counted
    counters = {"engine_steps_total": 10, "decode_tokens_total": 50, "grid_slots_total": 5000,
                "scheduled_tokens_total": 800, "steps_with_prefill_total": 4}
    after = {"engine_steps_total": 60, "decode_tokens_total": 250, "grid_slots_total": 37000,
             "scheduled_tokens_total": 5600, "steps_with_prefill_total": 14}
    return {"t_window0": 99.5, "t_window1": 110.0, "spans": spans,
            "snapshots": {0: {"counters": counters}, 1: {"counters": after}}}


def test_span_readers_on_hand_made_stamps():
    rec = record()
    for form in ("", "sat_"):
        assert reader(form + "schedule_ms_per_step")(rec) == pytest.approx(1.0)
        assert reader(form + "stage_ms_per_step")(rec) == pytest.approx(2.0)
        assert reader(form + "launch_ms_per_step")(rec) == pytest.approx(3.0)
        assert reader(form + "deliver_ms_per_step")(rec) == pytest.approx(1.0 + 2.0)
        # admit 0.2 ms after and 0.2 ms before each step, bookkeeping 0.5 ms
        assert reader(form + "loop_ms_per_step")(rec) == pytest.approx(0.9)
        # wait's end to the next launch's end: materialize 1 + deliver 2 + loop 0.9 +
        # schedule 1 + stage 2 + launch 3 = 9.9 ms; the fourth step has 40 ms of no work
        # in it; the first step in the window follows the wait of the step before the window
        gaps = [100.0 + 0.006 - (99.0 + 0.1065), 9.9e-3, 9.9e-3, 49.9e-3]
        assert reader(form + "host_gap_ms_per_step")(rec) == pytest.approx(1e3 * sum(gaps) / 4)
    assert reader("no_work_ms_per_step")(rec) == pytest.approx(40.0 / 4)
    # the named parts account for the gap of a step that follows a step
    parts = sum(reader(n)(rec) for n in ("schedule_ms_per_step", "stage_ms_per_step",
                                         "launch_ms_per_step", "deliver_ms_per_step",
                                         "loop_ms_per_step"))
    assert parts == pytest.approx(9.9)


def test_counter_readers_on_hand_made_counts():
    rec = record()
    for form in ("", "sat_"):
        assert reader(form + "grid_fill_pct")(rec) == pytest.approx(100.0 * 4800 / 32000)
        assert reader(form + "steps_with_prefill_pct")(rec) == pytest.approx(100.0 * 10 / 50)
    still = dict(rec, snapshots={0: rec["snapshots"][1], 1: rec["snapshots"][1]})
    assert reader("grid_fill_pct")(still) is None   # no step in the window


def test_a_program_without_the_spans_or_counters_reads_as_nothing():
    """The parent of the PR that added them: brackets only, the old counters only. Every reader
    returns None and raises nothing, so the metric is left out of the line."""
    old = {"t_window0": 100.0, "t_window1": 110.0,
           "spans": [("engine.dispatch", 101.0, 101.005), ("engine.device_wait", 101.005, 101.1),
                     ("step.split", 101.0, 101.1)],
           "snapshots": {0: {"counters": {"engine_steps_total": 10, "decode_tokens_total": 50}},
                         1: {"counters": {"engine_steps_total": 60, "decode_tokens_total": 250}}}}
    for name in SERVING + ("no_work_ms_per_step",):
        assert reader(name)(old) is None, name
    for name in SERVING:
        assert reader("sat_" + name)(old) is None, name
    untraced = dict(old, spans=[])
    for name in SERVING[:6] + ("no_work_ms_per_step",):
        assert reader(name)(untraced) is None, name
    # a traced program that never slept has no loop.wait span: that reads 0, not nothing
    busy = dict(record(), spans=[s for s in record()["spans"] if s[0] != "loop.wait"])
    assert reader("no_work_ms_per_step")(busy) == 0.0


def trace(ops, busy=2.0):
    return {"trace": {"busy_s_by_device": {0: busy, 1: 9.0}, "device_ops": ops}}


def test_kernel_share_readers_by_name():
    serve = trace([("dstpu_paged_decode custom-call:tpu_custom_call", 0.5), ("copy copy", 0.4),
                   ("fusion fusion:kOutput", 0.3)])
    for name in ("serve_paged_kernel_time_pct", "sat_paged_kernel_time_pct"):
        assert reader(name)(serve) == pytest.approx(25.0)
    train = trace([("dstpu_flash_fwd custom-call:tpu_custom_call", 0.3),
                   ("dstpu_flash_bwd_dkv custom-call:tpu_custom_call", 0.4),
                   ("dstpu_flash_bwd_dq custom-call:tpu_custom_call", 0.2),
                   ("dstpu_flash_fwd_chunk custom-call:tpu_custom_call", 0.1),
                   ("fusion fusion:kOutput", 0.5)])
    assert reader("train_flash_fwd_time_pct")(train) == pytest.approx(100 * 0.4 / 2.0)
    assert reader("train_flash_bwd_time_pct")(train) == pytest.approx(100 * 0.6 / 2.0)
    # the names a program without them gives, an untraced run, an empty trace
    old = trace([("closed_call custom-call:tpu_custom_call", 1.2), ("checkpoint custom-call:tpu_custom_call", 0.5)])
    for name in ("serve_paged_kernel_time_pct", "sat_paged_kernel_time_pct",
                 "train_flash_fwd_time_pct", "train_flash_bwd_time_pct"):
        assert reader(name)(old) is None
        assert reader(name)({}) is None and reader(name)({"trace": None}) is None
        assert reader(name)({"trace": {"busy_s_by_device": {}, "device_ops": []}}) is None


def test_gap_report_on_a_hand_made_trace():
    from benchmarks.tools import gap_report

    # device 0: busy 0-1 and 1.01-2 (a 10 ms gap), idle from 2 to the window's end at 2.004
    ops = [("%fusion.1 = fusion(...), kind=kLoop", 0.0, 1.0), ("%while.2 = while(...)", 1.01, 2.0),
           ("%dstpu_paged_decode.3 = custom-call(...)", 1.5, 1.6)]
    host = [[("dstpu.engine.device_wait", 0.5, 1.001),          # the wake-up: 1 ms of the gap
             ("dstpu.engine.materialize", 1.001, 1.002),
             ("dstpu.step.deliver", 1.002, 1.004),
             ("dstpu.engine.dispatch", 1.005, 1.0095),          # outer: its own 0.5 ms at the end
             ("dstpu.engine.stage", 1.005, 1.007),
             ("dstpu.engine.launch", 1.007, 1.009),
             ("dstpu.engine.device_wait", 1.0095, 2.001)],      # launch latency 0.5 ms, wake-up 1 ms
            [("dstpu.loop.wait", 5.0, 6.0)]]                     # another thread, outside the window
    rep = gap_report.report({"ops": ops, "host": host, "window": (0.0, 2.004)})
    assert rep["gaps"] == 2 and rep["idle_s"] == pytest.approx(0.014)
    assert rep["longest_gap_ms"] == pytest.approx(10.0)
    rows = {r["annotation"]: r for r in rep["rows"]}
    assert rows["dstpu.engine.device_wait"]["split_s"] == pytest.approx(0.001 + 0.0005 + 0.001)
    assert rows["dstpu.engine.stage"]["split_s"] == pytest.approx(0.002)
    assert rows["dstpu.engine.launch"]["split_s"] == pytest.approx(0.002)
    assert rows["dstpu.engine.dispatch"]["split_s"] == pytest.approx(0.0005)
    assert rows["dstpu.step.deliver"]["split_s"] == pytest.approx(0.002)
    assert rows["unattributed"]["split_s"] == pytest.approx(0.001 + 0.003)   # 1.004-1.005; 2.001-2.004
    assert sum(r["split_s"] for r in rep["rows"]) == pytest.approx(rep["idle_s"])
    assert all(set(r) == {"annotation", "split_s", "host_s"} for r in rep["rows"])
    # an annotation's own time in the window: the wait's 0.501 + 0.9915 s were mostly busy time
    assert rows["dstpu.engine.device_wait"]["host_s"] == pytest.approx(0.501 + 0.9915)
    assert rows["dstpu.engine.launch"]["host_s"] == pytest.approx(0.002)
    assert rows["unattributed"]["host_s"] is None and "dstpu.loop.wait" not in rows
    assert "dstpu.engine.stage" in gap_report.render(rep)
