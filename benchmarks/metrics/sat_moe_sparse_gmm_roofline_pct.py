"""Layer: expert layer (parallel/moe/grouped.py, kernel ``dstpu_moe_gmm``), for a configuration
in which not every layer has experts (``mlp_layer_types``: dense lead layers, then sparse ones)
and whose chip holds a share of each expert layer's experts. Source: device trace + program
counters. The least time the chip could take to move what the grouped expert matmuls of the
traced steps had to, over the seconds the trace shows under the kernel's name, in percent.

As ``sat_moe_hit_gmm_roofline_pct`` (its ``ops()`` and ``bytes()``: three grouped matmuls a
layer call, the weights of the experts that HAVE a row read once, the rows in and out; bytes-
bound at these widths, so linear in the counters), but over the layers that HAVE experts:
``sparse_layers()`` below counts them from ``mlp_layer_types``, where that reader multiplies by
``num_hidden_layers`` and would read 8/7 of the truth here. The program counts a layer call for
an expert layer alone (``moe_layer_calls_total``), so ``rows`` and ``hit`` a call are means over
those. The layer calls the trace held are the ``engine.launch`` spans that began in the traced
sub-window x the sparse layers. None without a trace, the kernel's name, the counters, the
spans or ``mlp_layer_types`` in the configuration."""
from benchmarks.harness import peaks
from benchmarks.metrics.sat_gdn_decode_roofline_pct import traced_launches
from benchmarks.metrics.sat_moe_gmm_time_pct import MOE_GMM
from benchmarks.metrics.sat_moe_hit_gmm_roofline_pct import bytes, ops  # noqa: A004, F401


def sparse_layers(hf):
    """Layers held that have experts: the head of ``mlp_layer_types``."""
    return list(hf["mlp_layer_types"])[: int(hf["num_hidden_layers"])].count("sparse")


def read(rec):
    tr = rec.get("trace")
    c0, c1 = rec["snapshots"][0]["counters"], rec["snapshots"][1]["counters"]
    if not tr or "moe_experts_hit_total" not in c1 or "mlp_layer_types" not in rec["hf"]:
        return None
    seconds = sum(s for name, s in tr["device_ops"] if name.startswith(MOE_GMM))
    calls = c1["moe_layer_calls_total"] - c0.get("moe_layer_calls_total", 0)
    launches = traced_launches(rec, tr)
    if seconds <= 0 or calls <= 0 or not launches:
        return None
    rows = (c1["moe_routed_rows_total"] - c0.get("moe_routed_rows_total", 0)) / calls
    hit = (c1["moe_experts_hit_total"] - c0.get("moe_experts_hit_total", 0)) / calls
    need = launches * sparse_layers(rec["hf"]) * bytes(rows, hit, rec["hf"])
    return 100.0 * need / peaks.device_peaks(rec["device_kind"]).hbm_bytes_s / seconds
