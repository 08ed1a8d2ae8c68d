"""Layer: expert layer (parallel/moe/grouped.py ``route()``), a configuration whose router keeps
``topk_group`` of ``n_group`` groups and whose chip holds one group of each expert layer's
experts. Source: program counters. Of the (token, expert-layer call) pairs the steps routed, the
share whose kept groups include the one held here: driver.metrics.counters
``moe_group_hit_tokens_total`` over ``moe_group_tokens_total``, as differences over the window, in
percent. ``topk_group / n_group`` (50 for A.X-K1's 4 of 8) under an even router: the share of a
step's tokens that reach this chip at all, which is what a deployment's dispatch would send it;
far from it, the seeded router favours or starves the held group and the expert matmuls' rows
(``sat_moe_tile_fill_pct``) follow. Counted with tracing off or on; None where the program has no
such counters (the parent, or a router without groups). Should move gen_tok_s."""


def read(rec):
    c0, c1 = rec["snapshots"][0]["counters"], rec["snapshots"][1]["counters"]
    if "moe_group_tokens_total" not in c1 or int(rec["hf"].get("n_group", 1) or 1) <= 1:
        return None
    tokens = c1["moe_group_tokens_total"] - c0.get("moe_group_tokens_total", 0)
    hit = c1["moe_group_hit_tokens_total"] - c0.get("moe_group_hit_tokens_total", 0)
    return 100.0 * hit / tokens if tokens > 0 else None
