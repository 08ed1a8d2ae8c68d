"""Layer: train step (runtime/engine.py, models/transformer.py). A step of the
median block of the window. Should move train_tok_s."""
from benchmarks.harness import stats


def read(rec):
    rate = stats.median_rate(rec["marks"])
    return None if rate is None else rec["tokens_per_step"] / rate * 1e3
