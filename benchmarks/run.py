"""One cell, one run: ``python3 -m benchmarks.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout.

Finds the cell, its configuration, its traffic and its metrics by name in
``BENCHMARK.json`` and the files beside this one; holds no cell's name itself.
The last line of standard output is the result as one JSON object; everything
else goes to standard error, but for the loss trajectory of a train cell.
Exits non-zero, with no result, without a TPU holding the chips the cell asks
for. ``--rehearse`` (never passed by the driver) runs the same code path on the
CPU with the toy sizes of ``benchmarks/tests/rehearsal`` laid over the cell's
files, and says ``platform: cpu``.
"""

import time

T_PROC0 = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness.common import (  # noqa: E402
    Catalog, CompileCounter, device_info, log, start_jax)


def fail(msg, code=3):
    log(f"benchmarks.run: {msg}")
    return code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="with --trace 1: also copy the .xplane.pb into DIR (for a human)")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU run of the same code path at toy sizes; never a result")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(REPO, "deepspeed_tpu")):
        return fail(f"no system under test: {REPO} holds no deepspeed_tpu package")
    catalog = Catalog(rehearse=args.rehearse)
    cell = catalog.cell(args.workload)
    traffic = catalog.traffic(cell["traffic"])
    hf = catalog.config(cell["config"])
    seconds = args.seconds if args.seconds is not None else catalog.run_seconds

    devices = start_jax(args.rehearse, cell["chips"])

    compiles = CompileCounter()
    record = {"cell": cell["name"], "chips": cell["chips"], "t_proc0": T_PROC0, "hf": hf,
              "device_kind": devices[0].device_kind}
    ctx = SimpleNamespace(cell=cell, traffic=traffic, hf=hf, seed=args.seed, seconds=seconds,
                          trace=bool(args.trace), devices=devices, record=record,
                          keep_trace=args.keep_trace)
    importlib.import_module(traffic["runner"]).run(ctx)

    record["compiles_in_window"] = compiles.between(record["t_window0"], record["t_window1"])
    used = [d for d in devices if d.id in record["devices_used"]]
    device = device_info(used)
    record.setdefault("peak_bytes", device["memory_peak_bytes"])
    device["memory_peak_bytes"] = max(device["memory_peak_bytes"], record["peak_bytes"])

    checks = dict(record["checks"])
    checks["no_compile_in_window"] = record["compiles_in_window"] == 0
    # the weights (train: the sharded parameters) sit on exactly the cell's chips
    checks["on_the_cells_chips"] = (
        device["platform"] == ("cpu" if args.rehearse else "tpu") and len(used) == cell["chips"])
    log(f"checks: {checks}; reference: {record.get('reference')}")

    def read(group, sources=None):
        out = {}
        for m in catalog.metrics(group, cell["name"]):
            if sources and m["source"] not in sources:
                continue
            reader = importlib.import_module(f"benchmarks.metrics.{m['name']}")
            try:
                value = reader.read(record)
            except KeyError as e:
                if not args.rehearse:
                    raise
                log(f"rehearsal: {m['name']} not computed on the CPU ({e})")
                value = None
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out

    metrics = read("per_layer" if args.trace else "end_to_end")
    # for a human: what the other kind of run reports, as far as this one can read it
    # (a traced run's end-to-end readings are taken with the profiler on)
    also = read("end_to_end") if args.trace else read(
        "per_layer", ("host_clock", "program_span", "program_counter"))
    log(f"also (not in the result): { {k: v['value'] for k, v in also.items()} }")

    result = {"correct": all(checks.values()), "attempted": int(record["attempted"]),
              "failed": int(record["failed"]), "metrics": metrics, "device": device}
    if args.trace:
        tr = record.get("trace")
        if tr:
            device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
            result["breakdown"] = {
                "device_ops": [[n, s] for n, s in tr["device_ops"]],
                "idle_gaps": [[n, s] for n, s in tr["idle_gaps"]],
            }
        elif not args.rehearse:  # the CPU's trace has no device plane
            return fail("the traced run found no device operation in its trace", code=4)
    if args.rehearse:
        result["rehearsal"] = "platform: cpu; not a measurement"
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
