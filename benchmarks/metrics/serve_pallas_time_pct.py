"""Layer: kernels (ops/attention/*_pallas.py, fused_norm.py), serve cells. Source:
device trace. Share of device 0's busy time inside Mosaic custom calls, by the
names the trace shows today. Should move tpot_p50_ms."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["busy_s_by_device"]:
        return None
    busy0 = tr["busy_s_by_device"][min(tr["busy_s_by_device"])]
    return 100.0 * tr["pallas_s"] / busy0 if busy0 > 0 else None
