"""Layer: serving programs (v2/engine_v2.py). Mean length of the SpanTracer span ``engine.launch``
(the host-to-device transfers of the staged arrays and the jitted call, to its asynchronous return) over the steps that began inside the window, on the host's clock. Traced run only;
None where the program records no such span. Should move tpot_p50_ms."""
from benchmarks.metrics.host_gap_ms_per_step import mean_ms


def read(rec):
    return mean_ms(rec, "engine.launch")
