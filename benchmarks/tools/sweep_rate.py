"""Find the knee of an open-loop cell: the highest arrival rate the system
sustains without a growing backlog. Run once when the cell is defined (and by
a later ``benchmark`` PR when an optimisation has moved the knee), on the chip:

    python3 -m benchmarks.tools.sweep_rate --workload <cell> --rates 4,5,6,7,8 --seconds 20

One process and one serving stack; each rate gets the cell's own ramp and a
window of ``--seconds``, then the stack drains before the next. For each rate
it prints the offered and the completed load, the tails of time to first
token in the two halves of the window, and the backlog as the window closed.
A rate is sustained when the second half's median time to first token is not
far above the first half's (the queue is not growing) and the backlog stays a
few requests. The cell's file then gets 0.8 of the highest such rate, as a
number. The last line is one JSON object with the table.
"""

import argparse
import json
import os
import sys
import time
from types import SimpleNamespace

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks.harness import serve, stats
    from benchmarks.harness.common import Catalog, SubWindowTrace, log, start_jax

    catalog = Catalog(rehearse=args.rehearse)
    cell = catalog.cell(args.workload)
    mix = catalog.traffic(cell["traffic"])
    if "arrivals" not in mix:
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    hf = catalog.config(cell["config"])

    devices = start_jax(args.rehearse, cell["chips"])

    ctx = SimpleNamespace(cell=cell, traffic=mix, hf=hf, seed=args.seed, seconds=args.seconds,
                          trace=False, devices=devices, keep_trace=None)
    vocab = int(hf["vocab_size"])
    driver, _ = serve.build(ctx)
    table = []
    try:
        load = serve.Load(driver)
        serve.warm_up(load, mix, ctx, vocab)
        for rate in [float(r) for r in args.rates.split(",")]:
            load.entries.clear()
            m = dict(mix, arrivals=dict(mix["arrivals"], rate=rate))
            backlog = {}

            def snapshot(i):
                now = time.monotonic()
                backlog[i] = sum(1 for e in load.entries if e["req"] is not None
                                 and e["req"].t_first_token is None and not e["req"].is_terminal)
                backlog[f"gen{i}"] = load.generated()
                return now

            w0, w1 = serve.drive(load, m, ctx, vocab, snapshot, SubWindowTrace(False, 0))
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline and any(
                    e["req"] is not None and not e["req"].is_terminal for e in load.entries):
                time.sleep(0.1)
            reqs = [q for q in serve.tabulate(load.entries) if w0 <= q["due"] < w1]
            mid = (w0 + w1) / 2

            def ttft(lo, hi, q):
                xs = [r["first"] - r["due"] for r in reqs if lo <= r["due"] < hi and r["first"]]
                p = stats.percentile(xs, q)
                return None if p is None else round(p * 1e3, 1)

            row = {
                "rate": rate, "due": len(reqs),
                "failed": sum(1 for r in reqs if r["state"] != "finished"),
                "prompt_tok_s": round(sum(r["prompt_len"] for r in reqs) / (w1 - w0), 1),
                "gen_tok_s": round((backlog["gen1"] - backlog["gen0"]) / (w1 - w0), 1),
                "ttft_p50_ms_1st_half": ttft(w0, mid, 50), "ttft_p50_ms_2nd_half": ttft(mid, w1, 50),
                "ttft_p90_ms": ttft(w0, w1, 90),
                "backlog_open": backlog[0], "backlog_close": backlog[1],
            }
            table.append(row)
            log(json.dumps(row))
    finally:
        driver.shutdown(drain=False, timeout=60)
    d = devices[0]
    print(json.dumps({"workload": args.workload, "seconds": args.seconds, "table": table,
                      "device": {"platform": d.platform, "kind": d.device_kind}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
