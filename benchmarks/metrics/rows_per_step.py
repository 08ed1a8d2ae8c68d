"""Layer: serving loop. driver.metrics.counters: decode_tokens_total over
engine_steps_total, both as differences over the window: the sequences that
yielded a token in an average step. Should move gen_tok_s."""


def read(rec):
    c0, c1 = rec["snapshots"][0]["counters"], rec["snapshots"][1]["counters"]
    steps = c1["engine_steps_total"] - c0["engine_steps_total"]
    if steps <= 0:
        return None
    return (c1["decode_tokens_total"] - c0["decode_tokens_total"]) / steps
