"""Layer: kernels (ops/attention/paged_pallas.py, kernel ``dstpu_paged_decode``), a
configuration whose stack mixes window and global layers. Source: device trace + program
counters. The least time the chip could take to read the keys and values the decode rows of
the traced steps' walks visit, summed BY KIND of layer, over the seconds the trace shows under
the kernel's name, in percent.

What the kernel has to read is computed here, by ``bytes()`` (``sat_kv_bytes_per_token``'s: a
block is ``--block-size`` tokens of K and of V at ``num_key_value_heads`` x ``head_dim`` in
bf16): a global layer's call reads the blocks the rows' contexts cover (the window's
``paged_live_blocks_total / engine_steps_total``), a window layer's the blocks its window
covers (``paged_window_live_blocks_total / engine_steps_total``); a step runs every layer once,
and the steps the trace held are the ``engine.launch`` spans that began in the traced
sub-window. Decode attention is bound by those bytes (a row's one query does two operations a
byte). ``serve_paged_roofline_pct`` multiplies one count by ``num_hidden_layers`` and would
count a window layer as a global one. None without a trace, the kernel's name, the counters
(the parent has none) or the spans."""
from benchmarks.harness import peaks
from benchmarks.metrics.sat_gdn_decode_roofline_pct import traced_launches
from benchmarks.metrics.sat_kv_bytes_per_token import block_size_of, bytes, layer_counts, window_delta  # noqa: A004
from benchmarks.metrics.serve_paged_kernel_time_pct import PAGED_DECODE


def read(rec):
    tr = rec.get("trace")
    c1 = rec["snapshots"][1]["counters"]
    if not tr or "paged_window_live_blocks_total" not in c1 or "layer_types" not in rec["hf"]:
        return None
    seconds = sum(s for name, s in tr["device_ops"] if name.startswith(PAGED_DECODE))
    steps = window_delta(rec, "engine_steps_total")
    launches = traced_launches(rec, tr)
    if seconds <= 0 or steps <= 0 or not launches:
        return None
    n_global, n_window = layer_counts(rec["hf"])
    bs = block_size_of(rec)
    a_step = (bytes(window_delta(rec, "paged_live_blocks_total") / steps, n_global, rec["hf"], bs)
              + bytes(window_delta(rec, "paged_window_live_blocks_total") / steps, n_window,
                      rec["hf"], bs))
    return 100.0 * launches * a_step / peaks.device_peaks(rec["device_kind"]).hbm_bytes_s / seconds
