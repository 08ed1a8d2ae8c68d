"""Layer: expert layer (parallel/moe/grouped.py, kernel ``dstpu_moe_gmm``), for a configuration
whose first ``first_k_dense_replace`` layers are dense and whose chip holds a share of each
expert layer's experts. Source: device trace + program counters. The least time the chip could
take to move what the grouped expert matmuls of the traced steps had to, over the seconds the
trace shows under the kernel's name, in percent.

As ``sat_moe_hit_gmm_roofline_pct`` (its ``ops()`` and ``bytes()``: three grouped matmuls a layer
call, the weights of the experts that HAVE a row read once, the rows in and out; bytes-bound at
these widths), but over the layers that HAVE experts: ``expert_layers()`` below,
``num_hidden_layers - first_k_dense_replace``, where that reader multiplies by
``num_hidden_layers`` and would read 5/4 of the truth here (``sat_moe_sparse_gmm_roofline_pct``
counts them from ``mlp_layer_types``, which this configuration does not have). The program
counts a layer call for an expert layer alone (``moe_layer_calls_total``), so ``rows`` and
``hit`` a call are means over those. None without a trace, the kernel's name, the counters, the
spans or ``first_k_dense_replace`` in the configuration."""
from benchmarks.harness import peaks
from benchmarks.metrics.sat_gdn_decode_roofline_pct import traced_launches
from benchmarks.metrics.sat_moe_gmm_time_pct import MOE_GMM
from benchmarks.metrics.sat_moe_hit_gmm_roofline_pct import bytes, ops  # noqa: A004, F401


def expert_layers(hf):
    """Layers held that have experts: all behind the dense lead ones."""
    return int(hf["num_hidden_layers"]) - int(hf["first_k_dense_replace"])


def read(rec):
    tr = rec.get("trace")
    c0, c1 = rec["snapshots"][0]["counters"], rec["snapshots"][1]["counters"]
    hf = rec["hf"]
    if (not tr or "moe_experts_hit_total" not in c1 or "first_k_dense_replace" not in hf
            or "mlp_layer_types" in hf):
        return None
    seconds = sum(s for name, s in tr["device_ops"] if name.startswith(MOE_GMM))
    calls = c1["moe_layer_calls_total"] - c0.get("moe_layer_calls_total", 0)
    launches = traced_launches(rec, tr)
    if seconds <= 0 or calls <= 0 or not launches:
        return None
    rows = (c1["moe_routed_rows_total"] - c0.get("moe_routed_rows_total", 0)) / calls
    hit = (c1["moe_experts_hit_total"] - c0.get("moe_experts_hit_total", 0)) / calls
    need = launches * expert_layers(hf) * bytes(rows, hit, hf)
    return 100.0 * need / peaks.device_peaks(rec["device_kind"]).hbm_bytes_s / seconds
