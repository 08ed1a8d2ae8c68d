"""Whose time are the device's gaps? For a human, from a kept trace:

    python3 -m benchmarks.run --workload <cell> --trace 1 --keep-trace DIR ...
    JAX_PLATFORMS=cpu python3 -m benchmarks.tools.gap_report DIR

Loads the ``.xplane.pb`` under DIR, takes the idle intervals of device 0 inside
the traced window (with ``harness/xplane.py``'s own ``union`` / ``subtract`` /
``self_pieces``), and charges them to the program's host annotations: with
tracing on, every ring span of ``SpanTracer.span()`` is on the profiler's host
lines as ``dstpu.<span>``. One account of the gaps, and one column beside it:

  split_s   every instant of a gap goes to the innermost annotation that
            covers it (a gap between two steps crosses materialize, deliver,
            the loop's bookkeeping, schedule, stage and launch: each gets its
            part), else to "unattributed"
  host_s    for comparison, the annotation's own time inside the window (what
            no annotation nested in it covers): where it is well above
            split_s the device was busy under that phase, as it is under the
            tail of ``dstpu.engine.launch`` once the program is enqueued

``dstpu.engine.device_wait`` in the table is what the host cannot see: the
device idle while the host already (launch latency) or still (the wake-up
after the device finished) blocks on the step's result. The ledger's
``idle_gaps`` stays "unattributed" until ``harness/xplane.py HOST_PREFIX``
admits ``dstpu.`` beside ``bench.`` (a ``benchmark`` PR); this tool is the
check, once a cell, that the host-clock account of
``benchmarks/metrics/host_gap_ms_per_step.py`` and the device's own gaps agree.
"""

import argparse
import json
import os
import sys
from typing import Dict, List, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import xplane  # noqa: E402

PROGRAM_PREFIX = "dstpu."
Event = Tuple[str, float, float]


def load(path: str) -> Dict:
    """{"ops": device 0's operation events, "host": per host line the
    ``dstpu.*`` events, "window": the harness's traced window or None}."""
    from jax.profiler import ProfileData

    ops: Dict[int, List[Event]] = {}
    host: List[List[Event]] = []
    window = None
    for plane in ProfileData.from_file(path).planes:
        m = xplane.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in xplane.OP_LINES:
                ops.setdefault(int(m.group(1)), []).extend(
                    (ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                    for ev in line.events)
            elif plane.name.startswith("/host:"):
                evs = []
                for ev in line.events:
                    span = (ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                    if ev.name.startswith(PROGRAM_PREFIX):
                        evs.append(span)
                    elif ev.name == xplane.WINDOW_EVENT:
                        window = span[1:]
                if evs:
                    host.append(evs)
    if not ops:
        raise SystemExit(f"{path}: no device plane with an operations line {xplane.OP_LINES}")
    return {"ops": ops[min(ops)], "host": host, "window": window}


def report(trace: Dict) -> Dict:
    ops = trace["ops"]
    lo, hi = trace["window"] or (min(e[1] for e in ops), max(e[2] for e in ops))
    busy = xplane.union((max(a, lo), min(b, hi)) for _, a, b in xplane.self_pieces(ops))
    gaps = xplane.subtract([(lo, hi)], busy)
    # innermost annotation at every instant, per host line (a line's events
    # overlap only by nesting)
    pieces = [p for line in trace["host"] for p in xplane.self_pieces(line)]
    split: Dict[str, float] = {}
    own: Dict[str, float] = {}
    for name, s, e in pieces:
        if min(e, hi) > max(s, lo):
            own[name] = own.get(name, 0.0) + min(e, hi) - max(s, lo)
    for a, b in gaps:
        rest = b - a
        for name, s, e in pieces:
            c = min(b, e) - max(a, s)
            if c > 0:
                split[name] = split.get(name, 0.0) + c
                rest -= c
        if rest > 1e-12:
            split["unattributed"] = split.get("unattributed", 0.0) + rest
    idle = xplane.total(gaps)
    names = sorted(split, key=lambda n: -split[n])
    return {
        "window_s": hi - lo, "idle_s": idle, "idle_pct": 100.0 * idle / (hi - lo),
        "gaps": len(gaps), "longest_gap_ms": 1e3 * max((b - a for a, b in gaps), default=0.0),
        "rows": [{"annotation": n, "split_s": split[n], "host_s": own.get(n)} for n in names],
    }


def render(rep: Dict) -> str:
    out = [f"window {rep['window_s']:.3f} s, device 0 idle {rep['idle_s']:.4f} s "
           f"({rep['idle_pct']:.2f}%) in {rep['gaps']} gap(s), longest {rep['longest_gap_ms']:.2f} ms",
           f"{'annotation':34s} {'split_s':>9s} {'of idle':>8s} {'host_s':>9s}"]
    for r in rep["rows"]:
        share = 100.0 * r["split_s"] / rep["idle_s"] if rep["idle_s"] else 0.0
        host_s = "" if r["host_s"] is None else f"{r['host_s']:9.4f}"
        out.append(f"{r['annotation']:34s} {r['split_s']:9.4f} {share:7.1f}% {host_s:>9s}")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir", help="the DIR given to --keep-trace")
    ap.add_argument("--json", default=None, metavar="FILE", help="also write the report as JSON")
    args = ap.parse_args(argv)
    rep = report(load(xplane.find_xplane(args.trace_dir)))
    print(render(rep))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rep, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
