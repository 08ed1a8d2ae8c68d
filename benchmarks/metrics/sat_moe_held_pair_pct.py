"""Layer: expert layer (parallel/moe/grouped.py ``experts_grouped()``), a configuration that holds
a SHARE of each layer's experts beside identity experts no chip holds (longcat_flash). Source:
program counters. Of the (token, choice) pairs the expert layers' calls routed, the share whose
expert this chip holds, which are the rows of its grouped matmuls: driver.metrics.counters
``moe_held_pairs_total`` over ``moe_pairs_total``, as differences over the window, in percent.
``held / router width`` under an even router (16 of 768: 2.08); far from it the seeded router
starves or floods the share and ``sat_moe_tile_fill_pct`` and the kernel's time follow. The rest
are identity pairs (``sat_moe_zero_pair_pct``) and pairs of experts held elsewhere, which are
neither multiplied nor summed. Counted with tracing off or on; None where the program has no such
counter (the parent) or the configuration no ``zero_expert_num``. Should move gen_tok_s."""
from benchmarks.metrics.sat_kv_bytes_per_token import window_delta


def read(rec):
    c1 = rec["snapshots"][1]["counters"]
    if "moe_held_pairs_total" not in c1 or not int(rec["hf"].get("zero_expert_num", 0) or 0):
        return None
    pairs = window_delta(rec, "moe_pairs_total")
    return 100.0 * window_delta(rec, "moe_held_pairs_total") / pairs if pairs > 0 else None
