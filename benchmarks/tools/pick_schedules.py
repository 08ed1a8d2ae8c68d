"""Choose an open-loop mix's ``schedules``: a few draws of arrival times and
length order that are alike in difficulty, so that runs which play different
ones can be held to one bound. No chip; run once when the mix is defined:

    python3 -m benchmarks.tools.pick_schedules --traffic prefill-open --base 1 --seconds 50

Why: with ~110 requests in a window, free draws differ by 10-17% in the 90th
percentile of time to first token (PERF.md), more than any bound may allow,
while one draw alone can be overfitted. So the mix lists a base draw and
siblings picked from many candidates for reading like the base on a crude model
of the engine: steps of ``a + b * prompt_tokens / 1024 (+ c with any prompt
chunk)`` seconds, at most two prompt chunks of 512 tokens and 32 decode rows a
step, first come first served. The model was fitted to the base draw's readings
on the v5e (PERF.md); a candidate has to agree with the base under that fit and
under three others (a faster engine, a slower one, another split between fixed
and per-token cost), so that it does not hang on the fit. The chip then says
whether the siblings agree (the spread of the cell's runs): the model only
proposes. The last line is one JSON object with the ranking.
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import loadgen, stats  # noqa: E402
from benchmarks.harness.common import Catalog  # noqa: E402
from benchmarks.harness.serve import DRAIN_S  # noqa: E402

# (a, b, c) in seconds: the fit to the base draw on the v5e, then the variants
MODELS = ((0.10, 0.03, 0.03), (0.08, 0.024, 0.024), (0.115, 0.03, 0.03), (0.09, 0.05, 0.01))
# how far a sibling may sit from the base, relative, under every model
TOLERANCE = {"ttft_p90_ms": 0.03, "ttft_p50_ms": 0.06, "tpot_p50_ms": 0.015, "tpot_p90_ms": 0.025}
CHUNK, CHUNKS, ROWS, BUDGET = 512, 2, 32, 1024


def replay(schedule, w0, w1, a, b, c):
    """The metrics the model engine gives on ``[(due_s, prompt_len, max_new)]``."""
    t, i, pending, running, first, finish = 0.0, 0, [], {}, {}, {}
    while i < len(schedule) or pending or running:
        while i < len(schedule) and schedule[i][0] <= t:
            pending.append([i, schedule[i][1]])
            i += 1
        if not pending and not running:
            t = schedule[i][0]
            continue
        rows = list(running)[:ROWS]
        budget, chunks = BUDGET - len(rows), []
        for p in pending[:CHUNKS]:
            take = min(CHUNK, p[1], budget)
            if take > 0:
                chunks.append((p, take))
                budget -= take
        t += a + b * sum(n for _, n in chunks) / BUDGET + (c if chunks else 0.0)
        for r in rows:
            running[r] -= 1
            if running[r] == 0:
                finish[r] = t
                del running[r]
        for p, take in chunks:
            p[1] -= take
            if p[1] == 0:
                first[p[0]] = t
                running[p[0]] = schedule[p[0]][2] - 1
        pending = [p for p in pending if p[1] > 0]
    ttft = [first[k] - s[0] for k, s in enumerate(schedule) if w0 <= s[0] < w1]
    tpot = [(finish[k] - first[k]) / (s[2] - 1) for k, s in enumerate(schedule)
            if w0 <= finish[k] < w1]
    return {"ttft_p50_ms": 1e3 * stats.percentile(ttft, 50), "ttft_p90_ms": 1e3 * stats.percentile(ttft, 90),
            "tpot_p50_ms": 1e3 * stats.percentile(tpot, 50), "tpot_p90_ms": 1e3 * stats.percentile(tpot, 90)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--base", type=int, required=True, help="the draw the siblings have to read like")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--candidates", type=int, default=1500)
    ap.add_argument("--keep", type=int, default=8)
    args = ap.parse_args(argv)

    catalog = Catalog()
    mix = catalog.traffic(args.traffic)
    mix.pop("schedules", None)   # a candidate's seed is its plan
    seconds = args.seconds or catalog.run_seconds
    ramp = float(mix["ramp_s"])

    def metrics(seed):
        schedule = [(due, len(spec.prompt), spec.max_new)
                    for due, spec in loadgen.open_schedule(seed, mix, (ramp, seconds, DRAIN_S), 2)]
        return [replay(schedule, ramp, ramp + seconds, *m) for m in MODELS]

    base = metrics(args.base)
    ranking = []
    for seed in range(args.candidates):
        if seed == args.base:
            continue
        off = {k: max(abs(got[k] / want[k] - 1) for got, want in zip(metrics(seed), base))
               for k in TOLERANCE}
        ranking.append((max(off[k] / TOLERANCE[k] for k in TOLERANCE), seed, off))
    ranking.sort()
    for score, seed, off in ranking[: args.keep]:
        print(f"draw {seed}: worst distance from draw {args.base} over the models "
              + ", ".join(f"{k} {100 * v:.2f}%" for k, v in off.items())
              + (" (inside the tolerance)" if score <= 1 else ""), file=sys.stderr)
    print(json.dumps({"traffic": args.traffic, "base": args.base, "base_model_reading": base[0],
                      "ranking": [{"draw": seed, "score": round(score, 3), "distance": off}
                                  for score, seed, off in ranking[: args.keep]]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
