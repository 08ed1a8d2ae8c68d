"""Layer: expert layer (parallel/moe/grouped.py ``experts_grouped()``), a configuration whose
router's last ``zero_expert_num`` ids are identity experts (longcat_flash). Source: program
counters. Of the (token, choice) pairs the expert layers' calls routed, the share that chose an
identity expert: driver.metrics.counters ``moe_zero_pairs_total`` over ``moe_pairs_total``, as
differences over the window, in percent. ``zero_expert_num / (n_routed_experts +
zero_expert_num)`` under an even router (33.3 for 256 of 768): such a pair is one multiply-add
with the sum of a token's identity gates and never a row of the grouped matmul, so it is what
makes a token's rows vary (0 to ``moe_topk``); far from it the seeded router (or its selection
bias) favours or starves the identity ids. Counted with tracing off or on; None where the program
has no such counter (the parent) or the configuration no ``zero_expert_num``. Should move
gen_tok_s."""
from benchmarks.metrics.sat_kv_bytes_per_token import window_delta


def read(rec):
    c1 = rec["snapshots"][1]["counters"]
    if "moe_zero_pairs_total" not in c1 or not int(rec["hf"].get("zero_expert_num", 0) or 0):
        return None
    pairs = window_delta(rec, "moe_pairs_total")
    return 100.0 * window_delta(rec, "moe_zero_pairs_total") / pairs if pairs > 0 else None
