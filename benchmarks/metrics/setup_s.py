"""End to end, all cells: process start to the window's start, on the host's clock.
Loading, weights from the seed, compilation or the compile cache, warm-up, the
reference check where it runs first, and the ramp the traffic needs."""


def read(rec):
    return rec['t_window0'] - rec['t_proc0']
