"""Layer: expert layer (parallel/moe/grouped.py), a chip that holds a share of each layer's
experts. How much of the held expert weights a layer call reads: driver.metrics.counters
``moe_experts_hit_total`` (experts with at least one row, summed over layer calls) over
``moe_layer_calls_total`` x the configuration's ``num_experts`` (the experts held here), as
differences over the window, in percent. The grouped kernel visits no expert without a row, so
at under a row an expert the bytes a step moves FOLLOW this number; at several rows an expert it
reads 100 whatever the router does. Counted with tracing off or on; None where the program has
no such counter (the parent). Should move gen_tok_s."""


def read(rec):
    c0, c1 = rec["snapshots"][0]["counters"], rec["snapshots"][1]["counters"]
    if "moe_experts_hit_total" not in c1 or "num_experts" not in rec["hf"]:
        return None
    calls = c1["moe_layer_calls_total"] - c0.get("moe_layer_calls_total", 0)
    hit = c1["moe_experts_hit_total"] - c0.get("moe_experts_hit_total", 0)
    return 100.0 * hit / (calls * int(rec["hf"]["num_experts"])) if calls > 0 else None
