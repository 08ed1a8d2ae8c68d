"""Layer: entry points. Source: the program's set-up record
(``deepspeed_tpu.observability.setup_report``, clipped to the run's set-up:
setup_outside_s.report). Self seconds of ``program.first_call``:
what is left of a first call once its trace, lower and compile are taken out:
the builder, transfers, the enqueue, the wait for the first result. Should move setup_s."""
from benchmarks.metrics.setup_outside_s import phase


def read(rec):
    return phase(rec, "first_run")
