"""Layer: kernels (ops/attention/paged_pallas.py), serve cells. Source: device trace. Share of
device 0's busy time under the paged decode kernel's own name (``pallas_call(name=...)`` names
the Mosaic custom call; harness/xplane.py short_name keeps it), read from the operations the
trace lists (its ten largest). None where the name is not among them: a program whose kernels
are not named, or a kernel too small to be listed. Should move tpot_p50_ms."""

PAGED_DECODE = "dstpu_paged_decode"


def named_share_pct(rec, prefix):
    """100 x seconds in the listed operations whose instruction name starts with ``prefix``
    over device 0's busy seconds."""
    tr = rec.get("trace")
    if not tr or not tr["busy_s_by_device"]:
        return None
    busy0 = tr["busy_s_by_device"][min(tr["busy_s_by_device"])]
    hits = [s for name, s in tr["device_ops"] if name.startswith(prefix)]
    return 100.0 * sum(hits) / busy0 if hits and busy0 > 0 else None


def read(rec):
    return named_share_pct(rec, PAGED_DECODE)
