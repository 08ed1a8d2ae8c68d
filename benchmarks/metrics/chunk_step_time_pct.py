"""Layer: serving programs (v2/engine_v2.py). Share of the chip's step time spent in steps that
carried a prompt chunk: driver.metrics.counters ``chunk_step_seconds_total`` over it +
``decode_step_seconds_total``, as differences over the window, in percent. Beside it
steps_with_prefill_pct says what share of the STEPS they were: a chunk step is 2-4 decode steps
long, so the share of time is the larger. A decoding row sits through each of them between two of
its tokens: it should move tpot_p90_ms. None where the program has no such counters."""


def read(rec):
    c0, c1 = rec["snapshots"][0]["counters"], rec["snapshots"][1]["counters"]
    names = ("chunk_step_seconds_total", "decode_step_seconds_total")
    if any(name not in c1 for name in names):
        return None
    chunk, decode = (c1[name] - c0.get(name, 0) for name in names)
    return 100.0 * chunk / (chunk + decode) if chunk + decode > 0 else None
