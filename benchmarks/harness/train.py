"""Runner for training jobs: what a user of ``deepspeed_tpu.initialize`` runs.
A fresh seeded batch every step, made on the host while the previous step runs
on the device; the clock stops on ``block_until_ready`` of the last step's
loss. The host time at which each step's loss was seen ready is kept, and one
mark every ``block_steps`` steps: the judged rate is the median block's
(``harness/stats.py``)."""

import dataclasses
import time

import numpy as np

from benchmarks.harness import loadgen
from benchmarks.harness.common import SubWindowTrace, log, log_blocks, reference_module

# The engine's bf16 loss against the float32 reference on the same rows. Both
# read the same bf16 weights; the engine rounds activations to bf16 (8 bits of
# mantissa) through 28 layers and a 152k-wide head, which moves a mean
# log-likelihood of ~12 nats in its fourth digit: 2e-4 to 2e-3 over eight seeds
# on the v5e, one chip and four (my chip runs, PR 22). Five times the worst is
# the limit. A wrong mask, a missing norm or a head computed below bf16 moves
# the loss by hundredths to tenths.
LOSS_TOLERANCE = 1e-2
# Zipf(1.1) tokens have a unigram entropy far below ln(vocab): AdamW at 1e-4
# pulls the loss from 12.1 to 7.4 in forty steps (my chip run, PR 22). Less
# than a nat after a window of steps means the optimizer is not learning.
LOSS_MUST_FALL_BY = 1.0
# What a cell may pin under ``model``: memory sizing, never arithmetic or policy.
SIZE_KEYS = {"loss_tiles"}


def partitioned(engine, chips: int) -> bool:
    """ZeRO-3 over ``chips`` devices: the largest leaf of the parameters and of
    the fp32 masters is split over exactly that many distinct devices."""
    import jax

    for tree in (engine.params, engine.opt_state.master):
        big = max(jax.tree.leaves(tree), key=lambda x: x.size)
        shards = big.addressable_shards
        if len({s.device for s in shards}) != chips or shards[0].data.size * chips != big.size:
            return False
    return True


def run(ctx) -> dict:
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import init_params, make_loss_fn
    from deepspeed_tpu.models.hf import config_from_hf
    from deepspeed_tpu.parallel.topology import Topology, reset_topology

    cell, mix, hf, rec = ctx.cell, ctx.traffic, ctx.hf, ctx.record
    devices = ctx.devices[: cell["chips"]]
    seq = int(mix["seq_len"])
    vocab = int(hf["vocab_size"])
    policies = set(cell.get("model", {})) - SIZE_KEYS
    if policies:
        raise SystemExit(f"a cell pins sizes, not policies: {sorted(policies)} under model")
    cfg = dataclasses.replace(config_from_hf(hf), **cell.get("model", {}))

    reset_topology()
    topo = Topology(devices=devices, **cell.get("layout", {}))
    dp = topo.sizes["data"]
    rows = int(cell["micro_batch_per_chip"]) * dp
    gas = int(mix.get("gradient_accumulation_steps", 1))

    with jax.default_device(devices[0]):
        params = jax.jit(lambda k: init_params(cfg, k))(jax.random.key(ctx.seed))
    cdf = loadgen.zipf_cdf(vocab, mix["tokens"]["exponent"]) if mix["tokens"]["law"] == "zipf" else None

    def make(rng, n_rows):
        return loadgen.token_batch(rng, mix["tokens"], cdf, (n_rows, seq + 1), vocab)

    # the plain reference first, on the whole weights, before ZeRO shards them.
    # One seeded row, given to every data-parallel rank: the engine's mean over
    # the sub-batch is then that row's loss, and the reference pays for it once.
    row = make(np.random.default_rng([ctx.seed, 3]), 1)
    sub = np.repeat(row, dp, axis=0)
    ref = reference_module(hf)
    t = time.monotonic()
    with jax.default_device(devices[0]):
        ref_loss = float(ref.loss(params, row[0], hf))
    log(f"reference loss on one row: {ref_loss:.5f} ({time.monotonic() - t:.1f}s)")

    ds_config = {
        "train_batch_size": rows * gas,
        "gradient_accumulation_steps": gas,
        "bf16": {"enabled": mix["precision"] == "bf16"},
        "optimizer": mix["optimizer"],
        "zero_optimization": {"stage": int(mix["zero_stage"])},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=make_loss_fn(cfg), model_parameters=params, config=ds_config, mpu=topo)
    del params
    eng_loss = float(engine.eval_batch({"input_ids": sub}))
    rec["reference"] = {"engine_loss": eng_loss, "reference_loss": ref_loss,
                        "gap": abs(eng_loss - ref_loss), "tolerance": LOSS_TOLERANCE}
    log(f"engine eval loss {eng_loss:.5f}, gap {abs(eng_loss - ref_loss):.2e}")
    rec["partitioned"] = partitioned(engine, len(devices)) if len(devices) > 1 else True
    rec["devices_used"] = {d.id for x in jax.tree.leaves(engine.params) for d in x.devices()}

    rng = np.random.default_rng([ctx.seed, 4])
    warm = []
    for i in range(int(mix["warmup_steps"])):
        warm.append(float(engine.train_batch(batch={"input_ids": make(rng, rows * gas)})))
        if i == 0:
            rec["t_first_done"] = time.monotonic()

    tracer = SubWindowTrace(ctx.trace, mix.get("trace_s", 3.0), ctx.keep_trace)
    annotate = jax.profiler.TraceAnnotation
    batch = make(rng, rows * gas)
    losses, ready, prev = [], [], None
    t0 = time.monotonic()
    while True:
        with annotate("bench.train_batch"):
            loss = engine.train_batch(batch={"input_ids": batch})
        losses.append(loss)
        with annotate("bench.make_batch"):
            batch = make(rng, rows * gas)
        if prev is not None:
            with annotate("bench.wait_previous_step"):
                prev.block_until_ready()
            ready.append(time.monotonic())
        prev = loss
        now = time.monotonic()
        if now - t0 >= ctx.seconds:
            break
        tracer.maybe_start(now, t0 + ctx.seconds)
    loss.block_until_ready()
    t1 = time.monotonic()
    ready.append(t1)
    tracer.stop()

    losses = [float(x) for x in losses]
    tokens_per_step = rows * gas * seq
    rec.update(
        t_window0=t0, t_window1=t1, steps=len(losses), tokens_per_step=tokens_per_step,
        marks=[(ready[i], (i + 1) * tokens_per_step)
               for i in range(0, len(ready), int(mix["block_steps"]))],
        losses_warmup=warm, losses=losses, seq_len=seq,
        attempted=len(losses), failed=sum(1 for x in losses if not np.isfinite(x)),
        trace=tracer.reduce(),
    )
    log_blocks(rec["marks"])
    print("loss trajectory: warm-up " + " ".join(f"{x:.4f}" for x in warm)
          + " | window " + " ".join(f"{x:.4f}" for x in losses), flush=True)
    tail = float(np.mean(losses[-5:]))
    rec["checks"] = {
        "reference_agrees": rec["reference"]["gap"] <= LOSS_TOLERANCE,
        "losses_finite": bool(np.all(np.isfinite(warm + losses))),
        "loss_fell": tail < warm[0] - LOSS_MUST_FALL_BY,
        "state_partitioned": rec["partitioned"],
    }
    return rec
