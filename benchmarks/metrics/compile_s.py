"""Layer: entry points. Source: the benchmark's clock. Process start until the
first train step or the first served token completes: weights, compilation or
the compile cache, and the first execution. Should move setup_s."""


def read(rec):
    return rec['t_first_done'] - rec['t_proc0']
