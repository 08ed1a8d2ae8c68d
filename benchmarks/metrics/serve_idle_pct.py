"""Layer: device, serve cells. Source: device trace. 1 less the union of the
intervals in which an operation ran, over the traced window, averaged over
the chips used. Should move tpot_p50_ms."""


def read(rec):
    tr = rec.get("trace")
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
