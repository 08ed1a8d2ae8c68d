"""Layer: expert layer (parallel/moe/grouped.py, kernel ``dstpu_moe_gmm``), for a configuration
whose experts have a width of their own (``moe_intermediate_size``) and whose chip holds more
experts than a step has rows. Source: device trace + program counters. The least time the chip
could take to move what the grouped expert matmuls of the traced steps had to, over the seconds
the trace shows under the kernel's name, in percent.

What the kernel has to move is computed here, by ``bytes()`` below: one layer call is three
grouped matmuls (gate and up ``[rows, h] x [h, f]``, down ``[rows, f] x [f, h]``); each reads
the weights of the experts that HAVE a row once (the window's ``moe_experts_hit_total /
moe_layer_calls_total``: the kernel visits no other) and its rows in and out (``rows`` = the
window's ``moe_routed_rows_total / moe_layer_calls_total``). At these widths (an expert's three
matrices 6.3 MB for a row or a few) the bytes bound a decode step and a chunk step alike
(``ops()`` says by how much), so the least time is linear in the counters and the share cannot
pass 100: a group that straddles a tile's edge fetches its weights twice, which only adds to the
kernel's side. The layer calls the trace held are the ``engine.launch`` spans that began in the
traced sub-window (the window's last ``trace.window_s`` seconds) x the layers. None without a
trace, the kernel's name, the counters or the spans."""
from benchmarks.harness import peaks
from benchmarks.metrics.sat_gdn_decode_roofline_pct import traced_launches
from benchmarks.metrics.sat_moe_gmm_time_pct import MOE_GMM

ITEMSIZE = 2  # bf16 weights and activations


def ops(rows, hf):
    """Operations of one layer call: every row against one expert's three matrices."""
    return 3 * 2.0 * rows * int(hf["hidden_size"]) * int(hf["moe_intermediate_size"])


def bytes(rows, hit, hf):  # noqa: A001 (the name the benchmark's contract gives)
    """Bytes one layer call has to move: gate, up and down of the ``hit`` experts that have a
    row, once each, and the rows in and out of each of the three matmuls."""
    h, f = int(hf["hidden_size"]), int(hf["moe_intermediate_size"])
    return ITEMSIZE * (3 * hit * h * f + 3 * rows * (h + f))


def read(rec):
    tr = rec.get("trace")
    c0, c1 = rec["snapshots"][0]["counters"], rec["snapshots"][1]["counters"]
    if not tr or "moe_experts_hit_total" not in c1 or "moe_intermediate_size" not in rec["hf"]:
        return None
    seconds = sum(s for name, s in tr["device_ops"] if name.startswith(MOE_GMM))
    calls = c1["moe_layer_calls_total"] - c0.get("moe_layer_calls_total", 0)
    launches = traced_launches(rec, tr)
    if seconds <= 0 or calls <= 0 or not launches:
        return None
    rows = (c1["moe_routed_rows_total"] - c0.get("moe_routed_rows_total", 0)) / calls
    hit = (c1["moe_experts_hit_total"] - c0.get("moe_experts_hit_total", 0)) / calls
    need = launches * int(rec["hf"]["num_hidden_layers"]) * bytes(rows, hit, rec["hf"])
    return 100.0 * need / peaks.device_peaks(rec["device_kind"]).hbm_bytes_s / seconds
