"""Layer: state-space layers (ops/state_space/mamba.py, kernel ``dstpu_mamba_scan``).
Source: device trace + program counters. The least time the chip's MEMORY could take for the
prompt tokens the traced steps' chunked scans walked, over the seconds the trace shows under the
kernel's name, in percent.

``bytes()`` below counts what the rule has to move: a token's u, delta and z in and y out (a
channel each, float32) and its B and C, and a chunk row's state in and out once a chunk. The
tokens of one layer's call of a step are the window's ``mamba_chunk_tokens_total /
engine_steps_total``; chunk rows are counted one a step that carried a chunk
(``steps_with_prefill_total``: at least one, at most ``max_prompt_chunks``; a state is 4 tokens'
worth of bytes, so the undercount is under 1%); the steps the trace held are the
``engine.launch`` spans that began in the traced sub-window, and a step runs every Mamba layer
once.

THIS SHARE IS AGAINST BYTES, AND THE KERNEL IS NOT BOUND BY BYTES: ``harness/peaks.py`` has no
peak of the vector unit and this PR may not edit it. A token moves 80 KiB a layer at the
published widths (100 ns at 819 GB/s) and needs, a state element (81,920 of them a token a
layer), one exponential and six multiplies and adds on the vector unit: by the builder's count 7
operations an element on registers of 1,024 elements, 560 register operations a token a layer,
600 ns at ONE register operation a cycle (940 MHz). The chip issues several a cycle (how many of
which kind is not published), so the share can reach 100 x 100 / 600 x that number: 17 at one,
50 at three, 67 at four. It reads 43-57 in the cell (my chip runs, PR 53: 175-230 ns a token a
layer, 2.6-3.4 register operations a cycle by that count, an exponential counted as one): the
vector unit, not the memory, is what the kernel is near. A ``benchmark`` PR may bring the vector
unit's peak, after which the share should be taken against the larger of the two bounds. It
must read under 100 whatever bounds it. None without a trace, the
kernel's name (a traced sub-window with no chunk step), the counters or the spans."""
from benchmarks.harness import peaks
from benchmarks.metrics.sat_gdn_decode_roofline_pct import traced_launches
from benchmarks.metrics.sat_mamba_decode_roofline_pct import layers, state_bytes, token_bytes
from benchmarks.metrics.sat_mamba_scan_time_pct import MAMBA_SCAN


def bytes(tokens, rows, hf):  # noqa: A001 (the name the benchmark's contract gives)
    """Bytes one layer's call has to move for ``tokens`` prompt tokens in ``rows`` chunk rows."""
    return tokens * token_bytes(hf) + rows * state_bytes(hf)


def read(rec):
    tr = rec.get("trace")
    c0, c1 = rec["snapshots"][0]["counters"], rec["snapshots"][1]["counters"]
    if not tr or "mamba_chunk_tokens_total" not in c1 or "mamba_d_state" not in rec["hf"]:
        return None
    seconds = sum(s for name, s in tr["device_ops"] if name.startswith(MAMBA_SCAN))
    steps = c1["engine_steps_total"] - c0.get("engine_steps_total", 0)
    launches = traced_launches(rec, tr)
    if seconds <= 0 or steps <= 0 or not launches:
        return None
    delta = lambda k: c1.get(k, 0) - c0.get(k, 0)  # noqa: E731
    need = launches * layers(rec["hf"]) * bytes(
        delta("mamba_chunk_tokens_total") / steps, delta("steps_with_prefill_total") / steps,
        rec["hf"])
    return 100.0 * need / peaks.device_peaks(rec["device_kind"]).hbm_bytes_s / seconds
