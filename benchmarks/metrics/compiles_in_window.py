"""Layer: entry points. Source: jax.monitoring compile events inside the window.
Should read 0: a compile in the window is set-up that leaked out of setup_s,
and the run is then not a measurement (``correct`` is false)."""


def read(rec):
    return float(rec['compiles_in_window'])
