"""Layer: entry points. Source: the program's set-up record and the benchmark's
clock. What of ``t_window0 - t_proc0`` no span of the program covers: the
benchmark's own weights from the seed, its float32 reference, the warm-up
requests' run, the mix's ramp, JAX's import and the backend's start. With the
six phases of ``report`` it adds up to setup_s, the metric it should move.

Also the home of what the nine ``setup_*`` readers share, as a ``sat_`` reader
imports another reader's ``read``."""


def report(rec):
    """``deepspeed_tpu.observability.setup_report()`` of THIS process, clipped
    to the run's set-up ``[t_proc0, t_window0]``: seconds by phase (every
    instant charged once, to the innermost span that covers it), one row a
    program, and the backend compiles under the program's spans with what the
    persistent cache answered. None where the program keeps no such record (a
    commit from before it had one) or the record is empty.

    A reader that reads the program's record and not ``rec`` is new. It is so
    because the record (``harness/``) is a ``benchmark`` PR's to extend and
    the train runner's holds nothing of the engine; the next such PR puts
    ``setup`` into the record (PERF.md section 7) and this reads it there."""
    try:
        from deepspeed_tpu.observability import setup_report
    except ImportError:
        return None
    rep = setup_report(rec["t_proc0"], rec["t_window0"])
    return rep if rep["spans"] else None


def phase(rec, name):
    rep = report(rec)
    return None if rep is None else rep["phases"][name]


def read(rec):
    rep = report(rec)
    if rep is None:
        return None
    return rec["t_window0"] - rec["t_proc0"] - sum(rep["phases"].values())
