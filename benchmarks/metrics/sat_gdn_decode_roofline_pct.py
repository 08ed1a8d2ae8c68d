"""Layer: linear attention (ops/linear_attention/gated_delta.py, kernel ``dstpu_gdn_decode``).
Source: device trace + program counters. The least time the chip could take to read and write
the recurrent states the decode rows of the traced steps hold, over the seconds the trace shows
under the kernel's name, in percent.

What the kernel has to move is computed here, by ``bytes()`` below, from the configuration's
widths: a row's state is ``linear_num_value_heads`` x ``linear_key_head_dim`` x
``linear_value_head_dim`` float32, read once and written once, beside its q and k (at the key
heads), v and output in float32; the update is bound by those bytes (a state element takes five
operations for its eight bytes). The conv's carried inputs are gathered and scattered by XLA
around the kernel and are no part of it. The rows of one layer's call of a step are the window's
``gdn_decode_rows_total / engine_steps_total`` (live rows: the grid's padding points at a spare
slot and is not counted, so a step of few rows reads low); the steps the trace held are the
``engine.launch`` spans that began in the traced sub-window (the window's last
``trace.window_s`` seconds), and a step runs every DeltaNet layer once (``layers()``). A launch
cut by the sub-window's edge is counted whole: one in some forty. None without a trace, the
kernel's name, the counters or the spans."""
from benchmarks.harness import peaks
from benchmarks.metrics.sat_gdn_decode_time_pct import GDN_DECODE

F32 = 4


def layers(hf):
    """Gated DeltaNet layers of the configuration: all but every ``full_attention_interval``-th."""
    n = int(hf["num_hidden_layers"])
    return n - n // int(hf["full_attention_interval"])


def bytes(rows, hf):  # noqa: A001 (the name the benchmark's contract gives)
    """Bytes one layer's call has to move for ``rows`` rows: each row's state in and out, its
    q, k and v in and its output out."""
    nk, nv = int(hf["linear_num_key_heads"]), int(hf["linear_num_value_heads"])
    dk, dv = int(hf["linear_key_head_dim"]), int(hf["linear_value_head_dim"])
    return F32 * rows * (2 * nv * dk * dv + 2 * nk * dk + 2 * nv * dv)


def traced_launches(rec, tr):
    """``engine.launch`` spans that began in the traced sub-window and have ended."""
    t0 = rec["t_window1"] - tr["window_s"]
    return sum(1 for n, a, b in rec.get("spans", ())
               if n == "engine.launch" and b is not None and t0 <= a < rec["t_window1"])


def read(rec):
    tr = rec.get("trace")
    c0, c1 = rec["snapshots"][0]["counters"], rec["snapshots"][1]["counters"]
    if not tr or "gdn_decode_rows_total" not in c1 or "linear_num_value_heads" not in rec["hf"]:
        return None
    seconds = sum(s for name, s in tr["device_ops"] if name.startswith(GDN_DECODE))
    steps = c1["engine_steps_total"] - c0.get("engine_steps_total", 0)
    launches = traced_launches(rec, tr)
    if seconds <= 0 or steps <= 0 or not launches:
        return None
    rows = (c1["gdn_decode_rows_total"] - c0.get("gdn_decode_rows_total", 0)) / steps
    need = launches * layers(rec["hf"]) * bytes(rows, rec["hf"])
    return 100.0 * need / peaks.device_peaks(rec["device_kind"]).hbm_bytes_s / seconds
