"""Layer: serving loop (serving/driver.py, serving/cluster/core.py). The host's time between two
device programs: from the end of one ``engine.device_wait`` span (the step's result arrays are
ready) to the end of the next ``engine.launch`` span (the jitted call has returned), as a mean
over the launches that began inside the window. SpanTracer ring spans, host clock, traced run
only; None where the program records no such spans. Should move tpot_p50_ms.

This is NOT the device's idle time. It is an upper bound on the idle the HOST causes: the
program is enqueued inside the jitted call and starts while ``engine.launch`` still runs, so the
span's tail lies beside device work and the figure overstates that idle (PR 23 on the v5e: 3.3-4.4
ms of device idle a step under a launch span of 4.4-8 ms). It is also blind to idle the host
cannot see: bubbles between ops inside a program, the launch's latency, the wake-up after the last
op (1-2 ms a step). Which of the two wins is the host's speed: as a share of the step it read up
to 2.8 points over the trace's idle share on a slow host and 0.4-1.5 under it on a fast one. For
the device's own figure read ``serve_idle_pct`` / ``sat_idle_pct``, and for whose time
each idle interval is, ``benchmarks/tools/gap_report.py`` on a ``--keep-trace`` directory. What
this metric is for: the parts beside it (schedule, stage, launch, deliver, loop, no_work) sum to
it, so it says which host phase a change moved.

Also the arithmetic the other readers of this PR's spans share (every file here is a metric of
the index, so a helper lives in a reader)."""
from benchmarks.harness import stats


def spans(rec, name):
    """``(t0, t1)`` of the closed ring spans of that name that began inside the window."""
    w0, w1 = rec["t_window0"], rec["t_window1"]
    return [(t0, t1) for n, t0, t1 in rec.get("spans", ())
            if n == name and t1 is not None and stats.in_window(t0, w0, w1)]


def mean_ms(rec, name):
    """Mean length of the spans of that name, in ms; None without one."""
    m = stats.mean(t1 - t0 for t0, t1 in spans(rec, name))
    return None if m is None else m * 1e3


def sum_ms_per_step(rec, *names):
    """Total length of the spans of those names over the window, per device program launched
    in it (``engine.launch``), in ms: for spans that do not come once a step. None where the
    program records neither a launch nor any of the names."""
    steps = len(spans(rec, "engine.launch"))
    found = [spans(rec, name) for name in names]
    if not steps or not any(found):
        return None
    return 1e3 * sum(t1 - t0 for xs in found for t0, t1 in xs) / steps


def read(rec):
    waits = sorted(t1 for n, _, t1 in rec.get("spans", ())
                   if n == "engine.device_wait" and t1 is not None)
    gaps, i = [], -1
    for t0, t1 in sorted(spans(rec, "engine.launch")):
        while i + 1 < len(waits) and waits[i + 1] <= t0:
            i += 1
        if i >= 0:  # the run's first launch follows no wait
            gaps.append(t1 - waits[i])
    m = stats.mean(gaps)
    return None if m is None else m * 1e3
