"""Layer: state-space layers (ops/state_space/mamba.py, kernel ``dstpu_mamba_decode``).
Source: device trace + program counters. The least time the chip could take to read and write
the states the decode rows of the traced steps hold, over the seconds the trace shows under the
kernel's name, in percent.

What the kernel has to move is computed here, by ``bytes()`` below, from the configuration's
widths: a row's state is ``mamba_expand x hidden_size`` channels of ``mamba_d_state`` float32
numbers, read once and written once, beside the row's u, delta and z in and y out (a channel
each, float32) and its B and C (``mamba_d_state`` numbers each). Against the bytes the update is
eight bytes a state element for one exponential and six multiplies and adds, all on the vector
unit: whether the bytes or the vector unit bound it is what this share says (PERF.md section 5
has the reading). The kernel as written reads B and C already spread over the lanes (512 bytes a
number): what the rule NEEDS is counted, not what the kernel moves. The conv's carried inputs
are gathered and scattered by XLA around the kernel and are no part of it. The rows of one
layer's call of a step are the window's ``mamba_decode_rows_total / engine_steps_total`` (live
rows: the grid's padding points at a spare slot and is not counted, so a step of few rows reads
low); the steps the trace held are the ``engine.launch`` spans that began in the traced
sub-window, and a step runs every Mamba layer once (``layers()``). None without a trace, the
kernel's name, the counters or the spans."""
from benchmarks.harness import peaks
from benchmarks.metrics.sat_gdn_decode_roofline_pct import traced_launches
from benchmarks.metrics.sat_mamba_decode_time_pct import MAMBA_DECODE

F32 = 4


def layers(hf):
    """Mamba layers of the configuration: all but those at ``attn_layer_offset`` in every
    ``attn_layer_period``."""
    period, offset = int(hf["attn_layer_period"]), int(hf["attn_layer_offset"])
    return sum(1 for i in range(int(hf["num_hidden_layers"])) if i % period != offset)


def widths(hf):
    """(channels, numbers of state a channel)."""
    return int(hf.get("mamba_expand", 2)) * int(hf["hidden_size"]), int(hf["mamba_d_state"])


def token_bytes(hf):
    """What one token moves in one layer beside a state: u, delta, z in, y out, B and C in."""
    d, n = widths(hf)
    return F32 * (4 * d + 2 * n)


def state_bytes(hf):
    """A row's state in one layer, read and written."""
    d, n = widths(hf)
    return F32 * 2 * d * n


def bytes(rows, hf):  # noqa: A001 (the name the benchmark's contract gives)
    """Bytes one layer's call has to move for ``rows`` rows of one token each."""
    return rows * (state_bytes(hf) + token_bytes(hf))


def read(rec):
    tr = rec.get("trace")
    c0, c1 = rec["snapshots"][0]["counters"], rec["snapshots"][1]["counters"]
    if not tr or "mamba_decode_rows_total" not in c1 or "mamba_d_state" not in rec["hf"]:
        return None
    seconds = sum(s for name, s in tr["device_ops"] if name.startswith(MAMBA_DECODE))
    steps = c1["engine_steps_total"] - c0.get("engine_steps_total", 0)
    launches = traced_launches(rec, tr)
    if seconds <= 0 or steps <= 0 or not launches:
        return None
    rows = (c1["mamba_decode_rows_total"] - c0.get("mamba_decode_rows_total", 0)) / steps
    need = launches * layers(rec["hf"]) * bytes(rows, rec["hf"])
    return 100.0 * need / peaks.device_peaks(rec["device_kind"]).hbm_bytes_s / seconds
