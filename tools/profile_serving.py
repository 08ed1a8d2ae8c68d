"""Serving-path profile: where does a generate() second go?

Phase timing for the v2 engine on the bench shape (PERF.md serving roofline
evidence): dispatch round trip, per-prefill-step device time, fused
decode-round device time, and host scheduler/staging overhead.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    import dataclasses

    from deepspeed_tpu.accelerator.device import on_tpu
    from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
    from deepspeed_tpu.models import TransformerConfig, init_params
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    if on_tpu():
        cfg = TransformerConfig(
            vocab_size=32000, hidden_size=2304, n_layers=10, n_heads=18,
            n_kv_heads=6, ffn_hidden_size=6912, max_seq_len=2048,
            dtype="bfloat16",
        )
    else:
        cfg = TransformerConfig(
            vocab_size=512, hidden_size=128, n_layers=2, n_heads=4,
            max_seq_len=256, dtype="float32",
        )

    # host<->device round trip: trivial program, dispatch + sync
    one = jnp.ones((8, 8), jnp.float32)
    f = jax.jit(lambda x: x + 1)
    float(f(one).sum())
    t0 = time.perf_counter()
    for _ in range(10):
        float(f(one).sum())
    disp = (time.perf_counter() - t0) / 10
    print(f"dispatch+sync latency: {disp * 1e3:.1f} ms")

    params = init_params(cfg, jax.random.key(0))
    rc = RaggedInferenceEngineConfig.from_dict({
        "dtype": cfg.dtype, "decode_steps": 16,
        "kv_cache": {"block_size": 128, "num_blocks": 512, "max_blocks_per_seq": 8},
        "state_manager": {"max_tracked_sequences": 64, "max_ragged_batch_size": 1024,
                          "max_ragged_sequence_count": 32, "max_context": 1024},
    })
    eng = InferenceEngineV2(cfg, params, rc)
    rng = np.random.default_rng(0)

    def run_once(tag, max_new=64, time_phases=True):
        prompts = [rng.integers(0, cfg.vocab_size, size=(int(l),)).astype(np.int32)
                   for l in rng.integers(64, 512, size=32)]
        uids = list(range(len(prompts)))
        for uid, p in zip(uids, prompts):
            eng.scheduler.submit(uid, p)
        remaining = {uid: max_new for uid in uids}
        prefill_steps = decode_rounds = 0
        t_prefill = t_decode = t_host = 0.0
        t_all0 = time.perf_counter()
        while eng.scheduler.has_work():
            if not eng.scheduler.has_pending() and eng.scheduler.running_uids():
                t0 = time.perf_counter()
                res = eng.decode_round(16)
                t_decode += time.perf_counter() - t0
                decode_rounds += 1
                if res:
                    t0 = time.perf_counter()
                    for uid, gen in res.items():
                        take = [int(t) for t in gen][: remaining[uid]]
                        remaining[uid] -= len(take)
                        if remaining[uid] <= 0:
                            eng.scheduler.finish(uid)
                    t_host += time.perf_counter() - t0
                    continue
            t0 = time.perf_counter()
            results = eng.step()
            t_prefill += time.perf_counter() - t0
            prefill_steps += 1
            t0 = time.perf_counter()
            for uid, logits in results.items():
                nxt = int(np.argmax(logits))
                remaining[uid] -= 1
                if remaining[uid] <= 0:
                    eng.scheduler.finish(uid)
                else:
                    eng.scheduler.feedback(uid, nxt)
            t_host += time.perf_counter() - t0
        dt = time.perf_counter() - t_all0
        gen = sum(max_new - r for r in remaining.values())
        print(
            f"{tag}: {gen} tok in {dt:.2f}s = {gen / dt:.0f} tok/s | "
            f"prefill {prefill_steps} steps {t_prefill:.2f}s | "
            f"decode {decode_rounds} rounds {t_decode:.2f}s | host {t_host:.2f}s"
        )
        return dt

    run_once("warmup")
    run_once("measured")

    # isolate: one decode_round's DEVICE time (jit call only, state pre-staged)
    prompts = [rng.integers(0, cfg.vocab_size, size=(256,)).astype(np.int32) for _ in range(32)]
    for uid, p in enumerate(prompts):
        eng.scheduler.submit(uid, p)
    while eng.scheduler.has_pending():
        res = eng.step()
        for uid, lg in res.items():
            eng.scheduler.feedback(uid, int(np.argmax(lg)))
    t0 = time.perf_counter()
    eng.decode_round(16)
    jax.block_until_ready(eng._k_cache)
    d1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng.decode_round(16)
    jax.block_until_ready(eng._k_cache)
    d2 = time.perf_counter() - t0
    print(f"decode_round(16) total: {d1 * 1e3:.0f} ms / {d2 * 1e3:.0f} ms "
          f"({d2 / 16 * 1e3:.1f} ms/token-step, 32 seqs -> {32 * 16 / d2:.0f} tok/s in-round)")
    for uid in eng.scheduler.running_uids():
        eng.scheduler.finish(uid)

    # one batched prefill step at the full bucket
    prompts = [rng.integers(0, cfg.vocab_size, size=(512,)).astype(np.int32) for _ in range(2)]
    for uid, p in enumerate(prompts):
        eng.scheduler.submit(uid, p)
    t0 = time.perf_counter()
    eng.step()
    p1 = time.perf_counter() - t0
    print(f"prefill step (1024 tok bucket): {p1 * 1e3:.0f} ms "
          f"-> {1024 / p1:.0f} prompt tok/s")


if __name__ == "__main__":
    main()
