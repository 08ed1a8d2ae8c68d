"""Layer: expert layer (parallel/moe/grouped.py, kernel ``dstpu_moe_gmm``), for a configuration
whose expert block sits on a shortcut across a layer's two sub-blocks (longcat_flash:
``num_layers`` expert calls a step, experts of ``expert_ffn_hidden_size``) and whose router's
last ids are identity experts that never reach the kernel. Source: device trace + program
counters. The least time the chip could take to move what the grouped expert matmuls of the
traced steps had to, over the seconds the trace shows under the kernel's name, in percent.

One layer call is three grouped matmuls; each reads the weights of the experts that HAVE a row
once (the window's ``moe_experts_hit_total / moe_layer_calls_total``) and its rows in and out
(``moe_routed_rows_total / moe_layer_calls_total``: the pairs of HELD experts alone; an identity
pair is a multiply-add outside the kernel and a pair held elsewhere is nothing). The arithmetic is
``sat_moe_hit_gmm_roofline_pct``'s ``bytes()`` / ``ops()`` at this configuration's width: at
6,144 x 2,048 an expert's three matrices are 75.5 MB, so the bytes bound a decode step (~8 rows
over ~6 experts) and a chunk step (~260 rows over 16) alike, the least time is linear in the
counters and the share cannot pass 100. The layer calls the trace held are the ``engine.launch``
spans that began in the traced sub-window x ``num_layers``. None without a trace, the kernel's
name, the counters, the spans or the configuration's ``expert_ffn_hidden_size``."""
from benchmarks.harness import peaks
from benchmarks.metrics import sat_moe_hit_gmm_roofline_pct as hit_gmm
from benchmarks.metrics.sat_gdn_decode_roofline_pct import traced_launches
from benchmarks.metrics.sat_kv_bytes_per_token import window_delta
from benchmarks.metrics.sat_moe_gmm_time_pct import MOE_GMM


def widths(hf):
    """This configuration's widths under the names the shared arithmetic reads."""
    return {"hidden_size": hf["hidden_size"], "moe_intermediate_size": hf["expert_ffn_hidden_size"]}


def ops(rows, hf):
    """Operations of one layer call: every row against one expert's three matrices."""
    return hit_gmm.ops(rows, widths(hf))


def bytes(rows, hit, hf):  # noqa: A001 (the name the benchmark's contract gives)
    """Bytes one layer call has to move: the ``hit`` experts' matrices once, the rows in and out."""
    return hit_gmm.bytes(rows, hit, widths(hf))


def read(rec):
    tr, hf = rec.get("trace"), rec["hf"]
    c1 = rec["snapshots"][1]["counters"]
    if not tr or "moe_experts_hit_total" not in c1 or "expert_ffn_hidden_size" not in hf:
        return None
    seconds = sum(s for name, s in tr["device_ops"] if name.startswith(MOE_GMM))
    calls = window_delta(rec, "moe_layer_calls_total")
    launches = traced_launches(rec, tr)
    if seconds <= 0 or calls <= 0 or not launches:
        return None
    rows = window_delta(rec, "moe_routed_rows_total") / calls
    hit = window_delta(rec, "moe_experts_hit_total") / calls
    peak = peaks.device_peaks(rec["device_kind"])
    least = max(bytes(rows, hit, hf) / peak.hbm_bytes_s, ops(rows, hf) / peak.bf16_flops)
    return 100.0 * launches * int(hf["num_layers"]) * least / seconds
