"""Layer: expert layer (the router's balance). The fullest expert's rows over the mean
expert's, averaged over the window's layer calls: driver.metrics.counters
``moe_hot_expert_rows_total`` (sum over layer calls of the fullest expert's rows) x the
configuration's ``num_experts`` over ``moe_routed_rows_total``. 1 is a perfectly even router;
the kernel's longest group, and under expert parallelism the slowest chip, grow with it. None
where the program has no such counters. Should move gen_tok_s."""


def read(rec):
    c0, c1 = rec["snapshots"][0]["counters"], rec["snapshots"][1]["counters"]
    if "moe_routed_rows_total" not in c1 or "num_experts" not in rec["hf"]:
        return None
    routed = c1["moe_routed_rows_total"] - c0.get("moe_routed_rows_total", 0)
    hot = c1["moe_hot_expert_rows_total"] - c0.get("moe_hot_expert_rows_total", 0)
    return hot * int(rec["hf"]["num_experts"]) / routed if routed > 0 else None
