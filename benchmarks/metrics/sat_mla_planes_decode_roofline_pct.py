"""Layer: kernels (ops/attention/latent_pallas.py, kernel ``dstpu_mla_decode``), a configuration
whose layer holds TWO latent attentions (``num_layers`` layers, ``2 x num_layers`` cache planes:
longcat_flash). Source: device trace + program counters. The least time the chip could take for
the latent blocks the decode rows of the traced steps walked, over the seconds the trace shows
under the kernel's name, in percent.

The arithmetic of one call is ``sat_mla_decode_roofline_pct``'s ``ops()`` / ``bytes()`` (a pool
block read once for scores and values alike; every head's score over the 576-wide vector and its
weighted sum over the 512-wide latent): the bytes bound it on the v5e. What differs is the count
of calls: ``latent_decode_blocks_total / engine_steps_total`` are the blocks ONE PLANE's call
walks (a row at position p holds ``ceil(p / block_size)`` blocks below it, whichever plane), and
a step makes a call a PLANE, ``2 x num_layers`` of them, not a layer. The steps the trace held
are the ``engine.launch`` spans that began in the traced sub-window. It cannot pass 100 unless
the counters or the name are wrong. None without a trace, the kernel's name, the counters, the
spans, or the configuration's ``num_layers``."""
from benchmarks.harness import peaks
from benchmarks.metrics.sat_gdn_decode_roofline_pct import traced_launches
from benchmarks.metrics.sat_kv_bytes_per_token import block_size_of, window_delta
from benchmarks.metrics.sat_mla_decode_roofline_pct import bytes, ops  # noqa: A004 (one call's arithmetic)
from benchmarks.metrics.sat_mla_decode_time_pct import MLA_DECODE


def planes(hf):
    """Cache planes of the layers held: a layer's two latent attentions each write one."""
    return 2 * int(hf["num_layers"])


def read(rec):
    tr, hf = rec.get("trace"), rec["hf"]
    c1 = rec["snapshots"][1]["counters"]
    if not tr or "latent_decode_blocks_total" not in c1 or "num_layers" not in hf or "kv_lora_rank" not in hf:
        return None
    seconds = sum(s for name, s in tr["device_ops"] if name.startswith(MLA_DECODE))
    steps = window_delta(rec, "engine_steps_total")
    launches = traced_launches(rec, tr)
    if seconds <= 0 or steps <= 0 or not launches:
        return None
    bs = block_size_of(rec)
    blocks = window_delta(rec, "latent_decode_blocks_total") / steps
    peak = peaks.device_peaks(rec["device_kind"])
    least = max(bytes(blocks, hf, bs) / peak.hbm_bytes_s, ops(blocks, hf, bs) / peak.bf16_flops)
    return 100.0 * launches * planes(hf) * least / seconds
