"""Layer: linear attention (ops/linear_attention/kda.py, kernel ``dstpu_kda_decode``). Source:
device trace + program counters. The least time the chip could take to read and write the
recurrent states the decode rows of the traced steps hold, over the seconds the trace shows
under the kernel's name, in percent. Counted as ``sat_gdn_decode_roofline_pct`` counts.

What the kernel has to move is computed here, by ``bytes()`` below, from the configuration's
widths: a row's state is ``linear_attn_config.num_heads`` x ``head_dim`` x ``head_dim`` float32,
read once and written once, beside its q, k and v, its beta (a number a head) and its DECAYS (a
number a key channel: ``[heads, head_dim]``, what tells this kernel from Gated DeltaNet's) in
and its output out, in float32; the update is bound by those bytes. The conv's carried inputs
are gathered and scattered by XLA around the kernel and are no part of it. The rows of one
layer's call of a step are the window's ``kda_decode_rows_total / engine_steps_total`` (live
rows: the grid's padding points at a spare slot and is not counted, so a step of few rows reads
low); the steps the trace held are the ``engine.launch`` spans that began in the traced
sub-window, and a step runs every KDA layer once (``layers()``: the length of
``linear_attn_config.kda_layers``, so a configuration cut in depth counts the layers it has). A
launch cut by the sub-window's edge is counted whole. None without a trace, the kernel's name,
the counters (the parent has none) or the spans."""
from benchmarks.harness import peaks
from benchmarks.metrics.sat_gdn_decode_roofline_pct import traced_launches
from benchmarks.metrics.sat_kda_decode_time_pct import KDA_DECODE

F32 = 4


def layers(hf):
    """Kimi Delta Attention layers of the configuration."""
    return len(hf["linear_attn_config"]["kda_layers"])


def bytes(rows, hf):  # noqa: A001 (the name the benchmark's contract gives)
    """Bytes one layer's call has to move for ``rows`` rows: each row's state in and out, its
    q, k, v, beta and decays in and its output out."""
    lin = hf["linear_attn_config"]
    H, d = int(lin["num_heads"]), int(lin["head_dim"])
    return F32 * rows * (2 * H * d * d + 5 * H * d + H)


def read(rec):
    tr = rec.get("trace")
    c0, c1 = rec["snapshots"][0]["counters"], rec["snapshots"][1]["counters"]
    if not tr or "kda_decode_rows_total" not in c1 or "linear_attn_config" not in rec["hf"]:
        return None
    seconds = sum(s for name, s in tr["device_ops"] if name.startswith(KDA_DECODE))
    steps = c1["engine_steps_total"] - c0.get("engine_steps_total", 0)
    launches = traced_launches(rec, tr)
    if seconds <= 0 or steps <= 0 or not launches:
        return None
    rows = (c1["kda_decode_rows_total"] - c0.get("kda_decode_rows_total", 0)) / steps
    need = launches * layers(rec["hf"]) * bytes(rows, rec["hf"])
    return 100.0 * need / peaks.device_peaks(rec["device_kind"]).hbm_bytes_s / seconds
