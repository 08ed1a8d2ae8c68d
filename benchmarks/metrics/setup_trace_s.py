"""Layer: entry points. Source: the program's set-up record
(``deepspeed_tpu.observability.setup_report``, clipped to the run's set-up:
setup_outside_s.report). Self seconds of ``compile.trace`` under the
program's spans: Python to jaxpr, every step program's and every eager
operation's of the build. Should move setup_s."""
from benchmarks.metrics.setup_outside_s import phase


def read(rec):
    return phase(rec, "trace")
