"""Autotuning experiment subprocess (reference ``autotuning/scheduler.py``
experiment jobs: every candidate config runs as its OWN process via the
launcher, so an OOM/compile crash kills the experiment, not the tuner).

Usage (spawned by :mod:`deepspeed_tpu.autotuning.scheduler`):

    python -m deepspeed_tpu.autotuning.exp_runner '<json>'

The JSON carries {"shape": {TransformerConfig kwargs}, "zero_stage",
"micro_batch", "remat_policy", "flash_block", "seq", "steps", "warmup",
"platform"}. Prints ONE JSON result line to stdout:
{"ok": true, "tok_s": ..., "mfu_pct": ..., "loss": ...} — everything else
goes to stderr. Exit code 0 even on handled failure (the line carries
ok=false + the reason); hard crashes (OOM kill) surface as a nonzero exit
the scheduler maps to None.
"""

import json
import os
import sys
import time


def _best_gen_tok_s(eng, n_seq=32, max_new=64, repeats=2, prompt_min=64,
                    prompt_max=512, seed=0):
    """The serving experiments' workload (FastGen-analogue: n_seq concurrent
    sequences, mixed prompt lengths, max_new generated tokens). Returns best
    generated tok/s over ``repeats`` measured passes (first pass warms every
    compiled program)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    vocab = eng._mc.vocab_size

    def batch():
        return [
            rng.integers(0, vocab, size=(int(l),)).astype(np.int32)
            for l in rng.integers(prompt_min, prompt_max, size=n_seq)
        ]

    eng.generate(batch(), max_new_tokens=max_new)  # warm
    best = 0.0
    for _ in range(repeats):
        prompts = batch()
        t0 = time.perf_counter()
        outs = eng.generate(prompts, max_new_tokens=max_new)
        dt = time.perf_counter() - t0
        gen = sum(len(o) - len(p) for o, p in zip(outs, prompts))
        best = max(best, gen / dt)
    return best


def run_serving(exp: dict) -> dict:
    """Serving-throughput experiment (reference ``autotuning_metric``
    throughput mode, autotuning/autotuner.py:42, pointed at the v2 engine):
    measure generated tok/s of the FastGen-analogue workload (32 concurrent
    sequences, mixed prompt lengths, 64 new tokens) under the given
    scheduler/engine knobs."""
    import jax
    import numpy as np

    if exp.get("platform") == "cpu":
        # ``python -m deepspeed_tpu...`` imported the package, and jax with
        # it, before this ran: the variable alone would come too late
        os.environ["JAX_PLATFORMS"] = "cpu"
        jax.config.update("jax_platforms", "cpu")

    from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import TransformerConfig, init_params

    shape = dict(exp.get("shape") or {})
    if not shape:
        shape = dict(  # the ``bench-767m`` preset
            vocab_size=32000, hidden_size=2304, n_layers=10, n_heads=18,
            n_kv_heads=6, ffn_hidden_size=6912, max_seq_len=2048,
            dtype="bfloat16",
        )
    cfg = TransformerConfig(**shape)
    params = init_params(cfg, jax.random.key(0))
    rc = RaggedInferenceEngineConfig.from_dict({
        "dtype": cfg.dtype,
        "prompt_chunk": int(exp.get("prompt_chunk", 0)),
        "max_prompt_chunks": int(exp.get("max_prompt_chunks", 0)),
        "kv_cache": {
            "block_size": int(exp.get("block_size", 128)),
            "num_blocks": int(exp.get("num_blocks", 512)),
            "max_blocks_per_seq": int(exp.get("max_blocks_per_seq", 8)),
        },
        "state_manager": {
            "max_tracked_sequences": 64,
            "max_ragged_batch_size": int(exp.get("token_budget", 1024)),
            "max_ragged_sequence_count": int(exp.get("concurrency", 32)),
            "max_context": int(exp.get("max_context", 1024)),
        },
    })
    eng = InferenceEngineV2(cfg, params, rc)
    best = _best_gen_tok_s(
        eng,
        n_seq=int(exp.get("concurrency", 32)),
        max_new=int(exp.get("max_new", 64)),
        repeats=int(exp.get("repeats", 2)),
        prompt_min=int(exp.get("prompt_min", 64)),
        prompt_max=int(exp.get("prompt_max", 512)),
    )
    return {"ok": True, "gen_tok_s": round(best, 1)}


def run(exp: dict) -> dict:
    if exp.get("mode") == "serving":
        return run_serving(exp)
    # flash block must be in the env BEFORE the ops import chain
    if exp.get("flash_block"):
        os.environ["DSTPU_FLASH_BLOCK"] = str(exp["flash_block"])
    import jax
    import numpy as np

    if exp.get("platform") == "cpu":
        # ``python -m deepspeed_tpu...`` imported the package, and jax with
        # it, before this ran: the variable alone would come too late
        os.environ["JAX_PLATFORMS"] = "cpu"
        jax.config.update("jax_platforms", "cpu")

    import deepspeed_tpu
    from deepspeed_tpu.accelerator.device import device_peaks, on_tpu
    from deepspeed_tpu.models import (
        TransformerConfig,
        flops_per_token,
        init_params,
        make_loss_fn,
    )

    shape = dict(exp.get("shape") or {})  # no-shape searches benchmark the default config
    shape["remat_policy"] = exp.get("remat_policy") or shape.get("remat_policy", "flash")
    if exp.get("matmul_precision"):
        shape["matmul_precision"] = exp["matmul_precision"]
    cfg = TransformerConfig(**shape)
    micro = int(exp.get("micro_batch", 1))
    seq = int(exp.get("seq", min(cfg.max_seq_len, 2048)))
    steps = int(exp.get("steps", 6))
    warmup = int(exp.get("warmup", 2))

    params = init_params(cfg, jax.random.key(0))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=make_loss_fn(cfg),
        model_parameters=params,
        config={
            "train_batch_size": micro,
            "bf16": {"enabled": on_tpu()},
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": int(exp.get("zero_stage", 0))},
            "steps_per_print": 10**9,
        },
    )
    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(micro, seq + 1)
    ).astype(np.int32)
    batch = {"input_ids": toks}
    for _ in range(warmup):
        float(engine.train_batch(batch=batch))
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch=batch)
    loss = float(loss)  # device sync
    dt = (time.perf_counter() - t0) / steps
    tok_s = micro * seq / dt
    out = {
        "ok": True,
        "tok_s": round(tok_s, 1),
        "s_per_step": round(dt, 4),
        "loss": round(loss, 4),
    }
    if on_tpu():  # a CPU run has no device peak and reports no MFU
        peak = device_peaks().bf16_flops
        out["mfu_pct"] = round(tok_s * flops_per_token(cfg, seq) / peak * 100, 2)
    return out


def main():
    exp = json.loads(sys.argv[1])
    try:
        out = run(exp)
    except Exception as e:  # handled failure: report, don't crash the tuner
        out = {"ok": False, "error": f"{type(e).__name__}: {e}"[:300]}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
