"""Play FREE deals of a closed-loop cell from a cold start with fine marks, so
that a mix's ``ramp_s``, ``block_tokens`` and ``schedules`` can be read off the
marks afterwards (how PRs 31, 37, 45 and 53 set theirs).

    python tools/free_deals.py --workload <cell> --deals 5300000101,5300000102 \\
        [--seconds 130] [--every 500] [--weights-seed 1] [--check 2]

One engine, built once as the benchmark builds it; each deal is driven by the
harness's own ``serve.drive`` with ``ramp_s`` 0 and a mark every ``--every``
delivered tokens, then left to drain so that the next starts cold. Writes a
line a deal (the marks as ``[seconds from the deal's start, tokens]``) to
``chiprun_out/free_deals.jsonl`` and prints the rates of a few candidate
(ramp, block) pairs. ``--check n``: the harness's comparison with the float32
reference over ``n`` finished requests of the last deal. On a TPU.
"""
import argparse
import gc
import json
import os
import sys
import time
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def rates(marks, ramp, block, seconds):
    """The rates of the whole blocks of ``block`` tokens a window of
    ``seconds`` from ``ramp`` holds, as ``stats.BlockMarks`` would cut them."""
    inside = [(t, n) for t, n in marks if ramp <= t <= ramp + seconds]
    out, last = [], None
    for t, n in inside:
        if last is None:
            last = (t, n)
        elif n - last[1] >= block:
            out.append((n - last[1]) / (t - last[0]))
            last = (t, n)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--deals", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=130.0)
    ap.add_argument("--every", type=int, default=500)
    ap.add_argument("--weights-seed", type=int, default=1)
    ap.add_argument("--check", type=int, default=0)
    args = ap.parse_args(argv)

    from benchmarks.harness import serve
    from benchmarks.harness.common import Catalog, SubWindowTrace, start_jax

    cat = Catalog()
    cell = cat.cell(args.workload)
    mix = {k: v for k, v in cat.traffic(cell["traffic"]).items() if k != "schedules"}
    mix.update(ramp_s=0.0, block_tokens=args.every)
    hf = cat.config(cell["config"])
    vocab = int(hf["vocab_size"])
    devices = start_jax(False, cell["chips"])
    import jax
    import numpy as np

    ctx = SimpleNamespace(cell=cell, traffic=mix, hf=hf, seed=args.weights_seed, seconds=args.seconds,
                          trace=False, devices=devices, record={}, keep_trace=None)
    driver, params = serve.build(ctx)
    load = serve.Load(driver)
    serve.warm_up(load, mix, ctx, vocab)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    entries = []
    for deal in (int(s) for s in args.deals.split(",")):
        load = serve.Load(driver)
        ctx.seed = deal
        t0 = time.monotonic()
        w0, w1, marks = serve.drive(load, mix, ctx, vocab, lambda i: time.monotonic(),
                                    SubWindowTrace(False, 3.0, None))
        marks = [(t - w0, n) for t, n in marks]
        for e in load.entries:   # drain: the next deal starts from an idle engine
            if e["req"] is not None:
                e["req"].wait(timeout=300)
        line = {"workload": args.workload, "deal": deal, "seconds": args.seconds, "marks": marks,
                "drain_s": time.monotonic() - w1,
                "lens": [(len(e["spec"].prompt), e["spec"].max_new) for e in load.entries[:64]]}
        with open(os.path.join(ROOT, "chiprun_out", "free_deals.jsonl"), "a") as f:
            f.write(json.dumps(line) + "\n")
        summary = {f"{ramp}/{block}": [round(r, 1) for r in rates(marks, ramp, block, 50.0)]
                   for ramp in (45, 55, 65, 75) for block in (8000, 16000, 24000)}
        print("DEAL", deal, json.dumps(summary), f"({time.monotonic() - t0:.0f}s)", flush=True)
        entries = load.entries
    driver.shutdown(drain=False, timeout=60)
    if args.check:
        done = [e for e in entries if e["req"] is not None and e["req"].state == "finished"]
        pick = np.random.default_rng(5).permutation(len(done))[: args.check]
        load.driver = None
        del driver
        gc.collect()
        with jax.default_device(devices[0]):
            worst = serve.reference_shortfall(hf, mix, params, [done[i] for i in pick])
        print("CHECK", json.dumps({"shortfall": worst, "limit": serve.NEAR_ARGMAX}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
