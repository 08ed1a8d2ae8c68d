"""The one general traffic generator. A mix is a data file of parameters; this
module turns it and ``--seed`` into requests and, for an open loop, into the
absolute times at which they are due. The program under test sees only the
generated token arrays.

Length laws a mix may name (``prompt_len`` / ``output_len``):
  {"law": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
  {"law": "uniform", "min": a, "max": b}          (both ends included)
Arrival law (``arrivals``, open loop): {"law": "poisson", "rate": r}

Every run of a mix offers the same work: a span of an open loop's timeline
holds exactly ``round(rate * span)`` arrivals whose lengths are the quantile
midpoints of the laws, and the k-th requests of a closed loop's clients hold
each quantile midpoint once. ``--seed`` decides the rest: the tokens, when the
arrivals are due and in which order the lengths come (open loop), who is dealt
which length (closed loop). A mix may list ``schedules``: run ``seed`` then
plays ``schedules[seed % len(schedules)]``, one of a few fixed draws of times
and order chosen alike in difficulty (``tools/pick_schedules.py``), because a
tail over ~100 requests differs more between free draws than a bound may allow.
"""

from statistics import NormalDist
from typing import List, NamedTuple

import numpy as np


class Spec(NamedTuple):
    prompt: np.ndarray   # int32 token ids
    max_new: int


def client_request(seed: int, client: int, k: int, n_clients: int, mix: dict,
                   vocab_size: int) -> Spec:
    """Closed loop: the ``k``-th request of client ``client``, a function of
    the mix, the seed, the client and k alone, never of how fast the system
    answered. The k-th requests of all the clients together hold each quantile
    midpoint of the length laws once; the seed deals them (who gets which
    length) and makes the tokens."""
    deal = np.random.default_rng([plan_seed(seed, mix), 1, int(k)])
    up = (deal.permutation(n_clients)[client] + 0.5) / n_clients
    uo = (deal.permutation(n_clients)[client] + 0.5) / n_clients
    rng = np.random.default_rng([int(seed), 3, int(client), int(k)])
    prompt = rng.integers(0, vocab_size, size=quantile_len(mix["prompt_len"], up), dtype=np.int32)
    return Spec(prompt, quantile_len(mix["output_len"], uo))


def quantile_len(law: dict, u: float) -> int:
    """The length at quantile ``u`` (0..1) of a length law."""
    kind = law["law"]
    if kind == "lognormal":
        x = float(law["median"]) * float(np.exp(float(law["sigma"]) * NormalDist().inv_cdf(u)))
        return int(min(max(round(x), law["min"]), law["max"]))
    if kind == "uniform":
        lo, hi = int(law["min"]), int(law["max"])
        return min(hi, lo + int(u * (hi - lo + 1)))
    raise ValueError(f"unknown length law {kind!r}")


def plan_seed(seed: int, mix: dict) -> int:
    """What seeds the plan of run ``seed``: the seed itself, or the one of the
    mix's ``schedules`` that falls to it."""
    return int(mix["schedules"][seed % len(mix["schedules"])] if "schedules" in mix else seed)


def _arrival_times(rng, law: dict, n: int, span_s: float) -> np.ndarray:
    """``n`` arrivals inside ``[0, span_s)``: n + 1 exponential gaps, scaled so
    that they fill the span, which is exactly a Poisson process given its
    count."""
    if law["law"] != "poisson":
        raise ValueError(f"unknown arrival law {law['law']!r}")
    gaps = rng.gamma(1.0, 1.0, size=n + 1)  # shape 1: exponential
    return span_s * np.cumsum(gaps)[:n] / gaps.sum()


def _stratified_requests(plan, rng, mix: dict, n: int, vocab_size: int) -> List[Spec]:
    """``n`` requests whose prompt and output lengths are the n quantile
    midpoints of the mix's laws, each list in the order ``plan`` draws; ``rng``
    makes the tokens."""
    us = (np.arange(n) + 0.5) / max(n, 1)
    prompts = [quantile_len(mix["prompt_len"], u) for u in plan.permutation(us)]
    outputs = [quantile_len(mix["output_len"], u) for u in plan.permutation(us)]
    return [Spec(rng.integers(0, vocab_size, size=p, dtype=np.int32), o)
            for p, o in zip(prompts, outputs)]


def open_schedule(seed: int, mix: dict, spans, vocab_size: int):
    """Open loop: ``[(due_s, Spec), ...]`` over consecutive spans of seconds
    (ramp, window, drain), due times relative to the start of the first. Each
    span holds exactly ``round(rate * span)`` arrivals. The plan (when they are
    due, in which order the lengths come) is drawn from ``plan_seed``, the
    tokens from ``seed``."""
    plan = np.random.default_rng([plan_seed(seed, mix), 2])
    rng = np.random.default_rng([int(seed), 2])
    rate = float(mix["arrivals"]["rate"])
    out: List = []
    t0 = 0.0
    for span in spans:
        n = int(round(rate * float(span)))
        times = _arrival_times(plan, mix["arrivals"], n, float(span))
        out += [(t0 + float(t), spec)
                for t, spec in zip(times, _stratified_requests(plan, rng, mix, n, vocab_size))]
        t0 += float(span)
    return out


def zipf_cdf(vocab_size: int, exponent: float) -> np.ndarray:
    p = 1.0 / np.arange(1, vocab_size + 1, dtype=np.float64) ** float(exponent)
    return np.cumsum(p / p.sum())


def token_batch(rng: np.random.Generator, tokens: dict, cdf, shape, vocab_size: int):
    """A [rows, seq + 1] int32 batch of training tokens under the mix's law.
    Zipf ranks are token ids: id 0 is the most frequent."""
    if tokens["law"] == "zipf":
        ids = np.searchsorted(cdf, rng.random(size=shape), side="left")
        return np.minimum(ids, vocab_size - 1).astype(np.int32)
    if tokens["law"] == "uniform":
        return rng.integers(0, vocab_size, size=shape, dtype=np.int32)
    raise ValueError(f"unknown token law {tokens['law']!r}")
