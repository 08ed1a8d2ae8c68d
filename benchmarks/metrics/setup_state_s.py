"""Layer: entry points. Source: the program's set-up record
(``deepspeed_tpu.observability.setup_report``, clipped to the run's set-up:
setup_outside_s.report). Self seconds of ``setup.initialize`` /
``setup.build_stack`` and their ``setup.*`` children: weights, optimizer state,
pools and re-laid stacks, less the compiles inside them. Should move setup_s."""
from benchmarks.metrics.setup_outside_s import phase


def read(rec):
    return phase(rec, "state")
