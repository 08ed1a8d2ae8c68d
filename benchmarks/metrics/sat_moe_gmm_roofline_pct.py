"""Layer: expert layer (parallel/moe/grouped.py, kernel ``dstpu_moe_gmm``). Source: device
trace + program counters. The least time the chip could take for the grouped expert matmuls
the trace held, over the seconds the trace shows under the kernel's name, in percent.

What the kernel has to do is computed here, from the configuration's widths and the counters,
by ``ops()`` and ``bytes()`` below: one layer call is three grouped matmuls (gate and up
``[rows, h] x [h, f]``, down ``[rows, f] x [f, h]``) over ``rows`` = the window's
``moe_routed_rows_total / moe_layer_calls_total``; each reads the weights of the experts that
have a row once (at most ``num_experts``, at most one an expert a row) and its rows in and
out. The least time of a matmul is the larger of operations over the bf16 peak and bytes over
the HBM peak (harness/peaks.py); which of the two bounds it is the widths' doing: at 4 rows an
expert it is the bytes. The layer calls the trace held are the ``engine.launch`` spans that
began in the traced sub-window (the window's last ``trace.window_s`` seconds) x the layers: a
launch is one step program and runs every layer once. A launch cut by the sub-window's edge
is counted whole: one in some forty. None without a trace, the kernel's name, the counters or
the spans."""
from benchmarks.harness import peaks
from benchmarks.metrics.sat_moe_gmm_time_pct import MOE_GMM

ITEMSIZE = 2  # bf16 weights and activations


def ops(rows, k, n):
    """Operations of one grouped matmul: every row against one expert's [k, n]."""
    return 2.0 * rows * k * n


def bytes(rows, k, n, experts):  # noqa: A001 (the name the benchmark's contract gives)
    """Bytes one grouped matmul has to move: the weights of the experts that have a row,
    once each, and the rows in and out."""
    return ITEMSIZE * (min(experts, rows) * k * n + rows * (k + n))


def least_seconds(rows, hf, peak):
    """One layer call: gate, up and down."""
    h, f, experts = int(hf["hidden_size"]), int(hf["intermediate_size"]), int(hf["num_experts"])
    return sum(max(ops(rows, k, n) / peak.bf16_flops, bytes(rows, k, n, experts) / peak.hbm_bytes_s)
               for k, n in ((h, f), (h, f), (f, h)))


def read(rec):
    tr = rec.get("trace")
    c0, c1 = rec["snapshots"][0]["counters"], rec["snapshots"][1]["counters"]
    if not tr or "moe_layer_calls_total" not in c1:
        return None
    seconds = sum(s for name, s in tr["device_ops"] if name.startswith(MOE_GMM))
    calls = c1["moe_layer_calls_total"] - c0.get("moe_layer_calls_total", 0)
    t0 = rec["t_window1"] - tr["window_s"]
    launches = sum(1 for n, a, b in rec.get("spans", ())
                   if n == "engine.launch" and b is not None and t0 <= a < rec["t_window1"])
    if seconds <= 0 or calls <= 0 or not launches:
        return None
    rows = (c1["moe_routed_rows_total"] - c0.get("moe_routed_rows_total", 0)) / calls
    need = launches * int(rec["hf"]["num_hidden_layers"]) * least_seconds(
        rows, rec["hf"], peaks.device_peaks(rec["device_kind"]))
    return 100.0 * need / seconds
