"""Layer: kernels (ops/attention/paged_pallas.py, kernel ``dstpu_paged_decode``), serve cells.
Source: device trace + program counters. The least time the chip could take to read the keys
and values the decode rows of the traced steps hold, over the seconds the trace shows under the
kernel's name, in percent.

What the kernel has to read is computed here, by ``bytes()`` below: a live block is
``--block-size`` tokens of K and of V at the configuration's ``num_key_value_heads`` x
``head_dim`` in bf16, and decode attention is bound by those bytes (a row's one query does two
operations a byte). The live blocks of one layer's calls of a step are the window's
``paged_live_blocks_total / engine_steps_total``; the steps the trace held are the
``engine.launch`` spans that began in the traced sub-window (the window's last
``trace.window_s`` seconds), and a step runs every layer once. A launch cut by the sub-window's
edge is counted whole: one in some forty. The queries, the step's own K/V and the output are a
thousandth of the blocks and are left out. None without a trace, the kernel's name, the
counters or the spans."""
from benchmarks.harness import peaks
from benchmarks.harness.common import Catalog
from benchmarks.metrics.serve_paged_kernel_time_pct import PAGED_DECODE

ITEMSIZE = 2  # a bf16 pool


def bytes(blocks, hf, block_size):  # noqa: A001 (the name the benchmark's contract gives)
    """Bytes of K and V in ``blocks`` pool blocks of one layer."""
    head_dim = hf.get("head_dim") or int(hf["hidden_size"]) // int(hf["num_attention_heads"])
    return 2 * ITEMSIZE * blocks * block_size * int(hf["num_key_value_heads"]) * int(head_dim)


def read(rec):
    tr = rec.get("trace")
    c0, c1 = rec["snapshots"][0]["counters"], rec["snapshots"][1]["counters"]
    if not tr or "paged_live_blocks_total" not in c1:
        return None
    seconds = sum(s for name, s in tr["device_ops"] if name.startswith(PAGED_DECODE))
    steps = c1["engine_steps_total"] - c0.get("engine_steps_total", 0)
    t0 = rec["t_window1"] - tr["window_s"]
    launches = sum(1 for n, a, b in rec.get("spans", ())
                   if n == "engine.launch" and b is not None and t0 <= a < rec["t_window1"])
    if seconds <= 0 or steps <= 0 or not launches:
        return None
    live = (c1["paged_live_blocks_total"] - c0.get("paged_live_blocks_total", 0)) / steps
    block_size = int(Catalog().cell(rec["cell"])["serve_args"]["--block-size"])
    need = launches * int(rec["hf"]["num_hidden_layers"]) * bytes(live, rec["hf"], block_size)
    return 100.0 * need / peaks.device_peaks(rec["device_kind"]).hbm_bytes_s / seconds
