"""Layer: entry points. Source: the program's set-up record
(``deepspeed_tpu.observability.setup_report``, clipped to the run's set-up:
setup_outside_s.report). How many ``compile.backend`` spans lie under the
program's spans: the programs a set-up pays for, step programs and the eager
operations of the build alike (a new bucket adds one). Should move setup_s."""
from benchmarks.metrics.setup_outside_s import report


def read(rec):
    rep = report(rec)
    return None if rep is None else float(rep["compile"]["programs"])
