"""Layer: entry points. Source: the program's set-up record
(``deepspeed_tpu.observability.setup_report``, clipped to the run's set-up:
setup_outside_s.report). Of the ``compile.backend`` spans under the program's
spans that asked the persistent cache, the share it answered: 100 on a warm
set-up, under 100 on one that compiled, which tells the two apart on the line
itself. None where none asked (a rehearsal switches the cache off). Should
move setup_s."""
from benchmarks.metrics.setup_outside_s import report


def read(rec):
    rep = report(rec)
    if rep is None or not rep["compile"]["asked_cache"]:
        return None
    return 100.0 * rep["compile"]["cache_hits"] / rep["compile"]["asked_cache"]
