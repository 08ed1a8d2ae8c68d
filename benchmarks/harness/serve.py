"""Runner for serving mixes, an open loop (the mix has ``arrivals``) or a
closed one (it has ``clients``): requests through ``ServingDriver.submit`` on
the stack ``dstpu serve`` builds (``serve_parse_args`` ->
``build_serving_stack`` -> ``driver.start``). The load comes from this one
thread; the program's driver thread does the rest.

One timeline: ``T0`` the load starts, ``T0 + ramp_s`` the window opens,
``seconds`` later it closes. An open loop goes on after the window until every
request that was due in it has its first token, so that the requests at the
window's end are served under the same load as those at its start. A closed
loop with more clients than the system holds keeps some of them queued by
design, so it stops as the window closes. A closed loop runs at saturation and
is judged by its throughput: its poll also counts the tokens delivered to its
clients and takes a mark every ``block_tokens`` of them inside the window, and
the judged rate is the median block's (``harness/stats.py``).
"""

import dataclasses
import gc
import time

import numpy as np

from benchmarks.harness import loadgen, stats
from benchmarks.harness.common import SubWindowTrace, log, log_blocks, reference_module

# A served token may sit this far below the best logit the float32 reference
# gives over the request's own history. Logits here have unit scale (a
# unit-RMS hidden state against a tied head of 0.02-scale rows); bf16 through
# 28 layers moves them by hundredths (worst 0.039 over 8 requests of ~200
# tokens on the v5e, my chip run, PR 22; PR 21 saw 0.028 on ten layers); four
# times that is the limit. A token from a corrupted cache or a wrong position
# lands whole units below.
NEAR_ARGMAX = 0.15
SAMPLE = 8
# a request due in the window must have its first token this long after it
DRAIN_S = 20.0
# how often the closed loop looks for clients whose request has finished
POLL_S = 0.001
# What a cell may pin under ``serve_args``: the sizes a deployment states.
# Policies stay at the program's defaults (benchmarks/README.md).
SIZE_FLAGS = {"--kv-pool-bytes", "--num-blocks", "--block-size", "--max-context",
              "--max-blocks-per-seq", "--max-concurrent", "--max-queue"}


class Load:
    """Requests submitted so far, in order, each with the time it was due."""

    def __init__(self, driver):
        from deepspeed_tpu.serving.driver import RequestRejected
        from deepspeed_tpu.serving.request import SamplingParams

        self.driver, self.Params, self.Rejected = driver, SamplingParams, RequestRejected
        self.entries = []  # {"due", "submit", "req" | None, "spec"}

    def submit(self, spec, due):
        params = self.Params(max_new_tokens=spec.max_new, ignore_eos=True)
        entry = {"due": due, "spec": spec, "req": None, "submit": time.monotonic()}
        try:
            entry["req"] = self.driver.submit(spec.prompt, params)
        except self.Rejected as e:
            entry["error"] = e.reason
        self.entries.append(entry)
        return entry["req"]

    def generated(self):
        return sum(len(e["req"].generated) for e in self.entries if e["req"] is not None)

    def waiting_for_first_token(self, w0, w1):
        return any(
            e["req"] is not None and w0 <= e["due"] < w1
            and e["req"].t_first_token is None and not e["req"].is_terminal
            for e in self.entries)


def drive(load, mix, ctx, vocab, snapshot, tracer):
    """Offer the mix's load along the timeline; ``snapshot(i)`` is called as
    the window opens (0) and closes (1). Returns (w0, w1) as they happened and
    the marks a closed loop took between them (an open loop takes none)."""
    ramp, seconds = float(mix["ramp_s"]), float(ctx.seconds)
    T0 = time.monotonic()
    plan0, plan1 = T0 + ramp, T0 + ramp + seconds
    marks = []
    closed = "clients" in mix
    if closed:
        n = int(mix["clients"])
        sent = [0] * n
        starts = [T0 + float(mix["stagger_s"]) * i / n for i in range(n)]
        current = [None] * n
        blocks = stats.BlockMarks(mix["block_tokens"])
        banked = 0  # tokens of the requests whose client has gone on to its next
    else:
        schedule = loadgen.open_schedule(ctx.seed, mix, (ramp, seconds, DRAIN_S), vocab)
        nxt = 0
    while True:
        now = time.monotonic()
        if len(marks) == 0 and now >= plan0:
            marks.append(snapshot(0))
        if len(marks) == 1:
            tracer.maybe_start(now, plan1)
            if now >= plan1:
                marks.append(snapshot(1))
                tracer.stop()
        if len(marks) == 2 and (
            closed  # its clients wait in the queue by design: nothing to drain
            or not load.waiting_for_first_token(marks[0], marks[1])
            or now >= marks[1] + DRAIN_S
        ):
            return marks[0], marks[1], (blocks.marks if closed else [])
        if closed:
            delivered = banked
            for i in range(n):
                r = current[i]
                if (r is None and now >= starts[i]) or (r is not None and r.is_terminal):
                    # closed loop: due when the client is free, which is now
                    spec = loadgen.client_request(ctx.seed, i, sent[i], n, mix, vocab)
                    sent[i] += 1
                    new = load.submit(spec, time.monotonic())
                    if new is not None:
                        banked += len(r.generated) if r is not None else 0
                        current[i] = new
                if r is not None:
                    delivered += len(r.generated)
            if len(marks) == 1:
                blocks.see(now, delivered)
            time.sleep(POLL_S)
        else:
            if nxt < len(schedule) and now >= T0 + schedule[nxt][0]:
                due, spec = schedule[nxt]
                load.submit(spec, T0 + due)
                nxt += 1
                continue
            wake = [plan0, plan1, now + 0.05]
            if nxt < len(schedule):
                wake.append(T0 + schedule[nxt][0])
            time.sleep(max(0.0, min(w for w in wake if w > now) - now))


def reference_shortfall(hf, mix, params, sample):
    """For each sampled request, how far below the reference's best logit each
    served token sits, given the request's own history. Every request is padded
    to the mix's longest prompt and answer, so the reference compiles once for
    the mix and never again."""
    import jax
    import jax.numpy as jnp

    ref = reference_module(hf)
    max_out = loadgen.quantile_len(mix["output_len"], 1.0 - 1e-9)
    longest = loadgen.quantile_len(mix["prompt_len"], 1.0 - 1e-9) + max_out
    width = -(-longest // 128) * 128

    @jax.jit
    def worst_gap(lg, served, n):
        chosen = jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]
        return jnp.max(jnp.where(jnp.arange(max_out) < n, lg.max(-1) - chosen, -jnp.inf))

    worst = []
    for e in sample:
        p, g = e["spec"].prompt, np.asarray(e["req"].generated, np.int32)
        toks = np.zeros(width, np.int32)
        toks[: len(p) + len(g)] = np.concatenate([p, g])
        rows = np.minimum(len(p) - 1 + np.arange(max_out), width - 1)  # past len(g): masked
        served = np.zeros(max_out, np.int32)
        served[: len(g)] = g
        lg = ref.logits(params, toks, hf, rows=rows)
        worst.append(float(worst_gap(lg, jnp.asarray(served), len(g))))
    return worst


def build(ctx):
    """The stack as ``dstpu serve`` builds it, weights from the seed, started.
    Returns (driver, params)."""
    policies = set(ctx.cell["serve_args"]) - SIZE_FLAGS
    if policies:
        raise SystemExit(f"a cell pins sizes, not policies: {sorted(policies)} in serve_args")
    import jax

    from deepspeed_tpu.inference.cli import build_serving_stack, serve_parse_args
    from deepspeed_tpu.models import init_params
    from deepspeed_tpu.models.hf import config_from_hf
    from deepspeed_tpu.parallel.topology import Topology, reset_topology, set_topology

    devices = ctx.devices[:1]
    reset_topology()
    set_topology(Topology(devices=devices))
    # as ``load_hf_model`` builds it for ``dstpu serve``
    cfg = dataclasses.replace(config_from_hf(ctx.hf), dtype="bfloat16")
    with jax.default_device(devices[0]):
        params = jax.jit(lambda k: init_params(cfg, k))(jax.random.key(ctx.seed))
    argv = ["--model", "", "--port", "0"]
    for flag, value in ctx.cell["serve_args"].items():
        argv += [flag, str(value)]
    args = serve_parse_args(argv)
    if ctx.trace:
        from deepspeed_tpu.observability import configure_tracing

        configure_tracing(enabled=True)
    driver, _ = build_serving_stack(args, cfg=cfg, params=params)
    driver.start()
    return driver, params


def warm_up(load, mix, ctx, vocab):
    """Every program shape the mix can reach: its shortest and its longest
    prompt, a few tokens each. Returns when the first token came."""
    rng = np.random.default_rng([ctx.seed, 0])
    first = None
    for n in sorted({int(mix["prompt_len"]["min"]), int(mix["prompt_len"]["max"])}):
        req = load.submit(loadgen.Spec(rng.integers(0, vocab, size=n, dtype=np.int32), 4),
                          time.monotonic())
        if req is None or not req.wait(timeout=1100) or req.state != "finished":
            raise RuntimeError(f"warm-up request of {n} tokens did not finish: "
                               f"{getattr(req, 'state', 'rejected')} {getattr(req, 'error', '')}")
        first = first or req.t_first_token
    return first


def tabulate(entries):
    """Plain stamps of each request, for the metric readers."""
    out = []
    for e in entries:
        r = e["req"]
        out.append({
            "due": e["due"], "submit": e["submit"], "prompt_len": len(e["spec"].prompt),
            "max_new": e["spec"].max_new,
            "admitted": getattr(r, "t_admitted", None), "first": getattr(r, "t_first_token", None),
            "finish": getattr(r, "t_finish", None), "n_out": len(r.generated) if r else 0,
            "state": r.state if r else "rejected",
        })
    return out


def run(ctx) -> dict:
    import jax

    mix, hf, rec = ctx.traffic, ctx.hf, ctx.record
    devices = ctx.devices[:1]
    vocab = int(hf["vocab_size"])
    driver, params = build(ctx)
    rec["devices_used"] = {d.id for x in jax.tree.leaves(params) for d in x.devices()}
    load = Load(driver)
    tracer = SubWindowTrace(ctx.trace, mix.get("trace_s", 3.0), ctx.keep_trace)
    snaps = {}
    thread = driver._thread
    try:
        rec["t_first_done"] = warm_up(load, mix, ctx, vocab)
        n_warm = len(load.entries)

        def snapshot(i):
            now = time.monotonic()
            snaps[i] = {"t": now, "generated": load.generated(),
                        "counters": dict(driver.metrics.counters)}
            return now

        w0, w1, marks = drive(load, mix, ctx, vocab, snapshot, tracer)
        rec["peak_bytes"] = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)
    finally:
        tracer.stop()
        driver.shutdown(drain=False, timeout=60)
    if thread.is_alive():
        raise RuntimeError("the serving driver's thread did not stop")

    from deepspeed_tpu.observability.tracing import get_tracer

    spans = [(s.name, s.t0, s.t1) for s in get_tracer().ring_spans()] if ctx.trace else []
    entries = load.entries[n_warm:]
    requests = tabulate(entries)
    in_window = [q for q in requests if w0 <= q["due"] < w1]
    closed = "clients" in mix
    bad = [q for q in in_window if q["state"] in ("rejected", "failed", "timed_out")
           or (q["first"] is None and not closed)   # open loop: no first token in DRAIN_S
           or (q["state"] == "finished" and q["n_out"] != q["max_new"])]
    rec.update(
        t_window0=w0, t_window1=w1, requests=requests, snapshots=snaps, spans=spans, marks=marks,
        attempted=len(in_window), failed=len(bad),
        trace=tracer.reduce(),
    )

    if marks:
        log_blocks(marks)

    # the reference reads the whole weights; the engine and its pool go first
    done = [e for e in entries if e["req"] is not None and e["req"].state == "finished"
            and w0 <= e["req"].t_finish < w1]
    load.driver = None
    del driver
    gc.collect()
    pick = np.random.default_rng([ctx.seed, 5]).permutation(len(done))[:SAMPLE]
    t = time.monotonic()
    with jax.default_device(devices[0]):
        worst = reference_shortfall(hf, mix, params, [done[i] for i in pick]) if len(pick) else []
    log(f"reference over {len(worst)} request(s): worst shortfall below the best logit "
        f"{max(worst, default=float('nan')):.4f} (limit {NEAR_ARGMAX}; {time.monotonic() - t:.1f}s)")
    rec["reference"] = {"shortfall": worst, "limit": NEAR_ARGMAX}
    rec["checks"] = {
        "reference_agrees": len(worst) == min(SAMPLE, len(done)) > 0 and max(worst) <= NEAR_ARGMAX,
        "served_something": snaps[1]["generated"] > snaps[0]["generated"],
    }
    return rec
