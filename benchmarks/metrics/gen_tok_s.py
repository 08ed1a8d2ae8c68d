"""End to end, serve cells at saturation (a closed loop): output tokens delivered
per second in the median block of the window, counted on the client's side (sum
of len(request.generated) at a block's end less the same at its start; a block
is block_tokens tokens and ends where a step's delivery ends; harness/stats.py
says why the median and not the total). A run that took no marks has nothing
to read."""
from benchmarks.harness import stats


def read(rec):
    return stats.median_rate(rec["marks"]) if rec.get("marks") else None
