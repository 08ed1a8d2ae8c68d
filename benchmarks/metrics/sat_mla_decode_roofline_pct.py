"""Layer: kernels (ops/attention/latent_pallas.py, kernel ``dstpu_mla_decode``). Source: device
trace + program counters. The least time the chip could take for the latent blocks the decode
rows of the traced steps walked, over the seconds the trace shows under the kernel's name, in
percent.

What the kernel has to do is computed here from the configuration's widths: a pool block of a
layer is ``block_size`` tokens of ``kv_lora_rank + qk_rope_head_dim`` bf16 (147,456 bytes at 128
x 576), read ONCE for scores and values alike (``bytes()``); a cached token costs every head a
score over the whole vector and a weighted sum over the latent, ``heads x (576 + 512) x 2``
operations (``ops()``). The least time is the larger of bytes over the chip's HBM rate and
operations over its bf16 peak: ~121 operations a byte, so the bytes bound it on the v5e (ridge
240) with the MXU's share at half of them. The blocks one layer's call walks are the window's
``latent_decode_blocks_total / engine_steps_total`` (a row at position p holds ``ceil(p /
block_size)`` blocks below it; its own new vector rides as a column and is not counted; a
block is counted whole, as the kernel reads it and multiplies it); the steps the trace held are
the ``engine.launch`` spans that began in the traced sub-window, and a step runs every layer
once. It cannot pass 100 unless the counters or the name are wrong. None without a trace, the
kernel's name, the counters or the spans."""
from benchmarks.harness import peaks
from benchmarks.harness.common import Catalog
from benchmarks.metrics.sat_gdn_decode_roofline_pct import traced_launches
from benchmarks.metrics.sat_mla_decode_time_pct import MLA_DECODE

ITEMSIZE = 2  # a bf16 pool


def latent_dim(hf):
    return int(hf["kv_lora_rank"]) + int(hf["qk_rope_head_dim"])


def ops(blocks, hf, block_size):
    """Operations one layer's call spends on ``blocks`` pool blocks: every head's score over the
    cached vector and its weighted sum over the latent, for each of the blocks' tokens."""
    per_token = int(hf["num_attention_heads"]) * (latent_dim(hf) + int(hf["kv_lora_rank"])) * 2.0
    return blocks * block_size * per_token


def bytes(blocks, hf, block_size):  # noqa: A001 (the name the benchmark's contract gives)
    """Bytes one layer's call has to read for ``blocks`` pool blocks: each once."""
    return ITEMSIZE * blocks * block_size * latent_dim(hf)


def read(rec):
    tr = rec.get("trace")
    c0, c1 = rec["snapshots"][0]["counters"], rec["snapshots"][1]["counters"]
    if not tr or "latent_decode_blocks_total" not in c1 or "kv_lora_rank" not in rec["hf"]:
        return None
    seconds = sum(s for name, s in tr["device_ops"] if name.startswith(MLA_DECODE))
    steps = c1["engine_steps_total"] - c0.get("engine_steps_total", 0)
    launches = traced_launches(rec, tr)
    if seconds <= 0 or steps <= 0 or not launches:
        return None
    hf = rec["hf"]
    bs = int(Catalog().cell(rec["cell"])["serve_args"]["--block-size"])
    blocks = (c1["latent_decode_blocks_total"] - c0.get("latent_decode_blocks_total", 0)) / steps
    peak = peaks.device_peaks(rec["device_kind"])
    least = max(bytes(blocks, hf, bs) / peak.hbm_bytes_s, ops(blocks, hf, bs) / peak.bf16_flops)
    return 100.0 * launches * int(hf["num_hidden_layers"]) * least / seconds
