"""Run one benchmark cell in this process and write the program's set-up
record beside its result line: the per-program rows (trace, lower, compile,
first run of every program key) that the line's nine ``setup_*`` metrics add
up, and every span.

    python3 tools/setup_report.py --out chiprun_out/cell.setup.json -- \\
        --workload <cell> --seed <n> --seconds 50 --trace 1

Everything after ``--`` goes to ``benchmarks.run`` as the driver passes it;
the result line is the run's own, on standard output. ``--ring`` switches the
span ring on for the whole run without the profiler (``--trace 0`` then
measures what the ring costs a served step: docs/OBSERVABILITY.md,
"Overhead"). On the chip through the chip tool; ``--rehearse`` after ``--``
tries it on the CPU.
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True, help="where the report goes (JSON)")
    ap.add_argument("--ring", action="store_true",
                    help="configure_tracing(enabled=True) before the run")
    ap.add_argument("bench", nargs=argparse.REMAINDER, help="-- then benchmarks.run's arguments")
    args = ap.parse_args(argv)

    from benchmarks import run  # stamps T_PROC0 as the module's first line

    if args.ring:
        from deepspeed_tpu.observability import configure_tracing

        configure_tracing(enabled=True)
    rc = run.main([a for a in args.bench if a != "--"])

    from deepspeed_tpu.observability import get_setup_record

    record = get_setup_record()
    report = record.report(run.T_PROC0)
    report["t_proc0"] = run.T_PROC0
    report["counters"] = record.counters()
    report["all_spans"] = [s.to_dict() for s in record.spans()]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1, default=str)
    return rc


if __name__ == "__main__":
    sys.exit(main())
