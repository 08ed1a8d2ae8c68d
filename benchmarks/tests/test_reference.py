"""The plain Qwen3 reference against the system, on the toy configuration in
float32: ``models.forward`` (the training forward pass) and the serving
engine's prefill-then-decode through the paged cache must give the logits the
reference gives from a full forward pass. CPU, by hand:
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``."""

import dataclasses
import os

import jax
import numpy as np
import pytest

from benchmarks.harness.common import BENCH, read_json
from benchmarks.reference import qwen3

# float32 on both sides, different summation orders through two layers
TOL = 2e-4


@pytest.fixture(scope="module")
def model():
    from deepspeed_tpu.models import init_params
    from deepspeed_tpu.models.hf import config_from_hf

    hf = read_json(os.path.join(BENCH, "tests", "data", "qwen3-tiny.json"))
    cfg = dataclasses.replace(config_from_hf(hf), dtype="float32")
    params = init_params(cfg, jax.random.key(3))
    # norms at 1 would hide a norm that is skipped or misplaced
    keys = iter(jax.random.split(jax.random.key(4), 8))

    def jitter(a):
        return a + 0.2 * jax.random.normal(next(keys), a.shape, a.dtype)

    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        params["layers"][name] = jitter(params["layers"][name])
    params["final_norm"] = jitter(params["final_norm"])
    return hf, cfg, params


def test_against_the_training_forward_pass(model):
    from deepspeed_tpu.models import forward, make_loss_fn

    hf, cfg, params = model
    toks = np.random.default_rng(0).integers(0, hf["vocab_size"], size=(2, 49)).astype(np.int32)
    want = np.stack([np.asarray(qwen3.logits(params, row, hf)) for row in toks])
    got = np.asarray(forward(params, toks, cfg)[0])
    assert np.max(np.abs(got - want)) < TOL * max(1.0, np.max(np.abs(want)))
    loss = float(make_loss_fn(cfg)(params, {"input_ids": toks}))
    ref = float(np.mean([float(qwen3.loss(params, row, hf)) for row in toks]))
    assert abs(loss - ref) < TOL


def test_against_prefill_then_decode_through_the_paged_cache(model):
    from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    hf, cfg, params = model
    rc = RaggedInferenceEngineConfig.from_dict({
        "dtype": "float32",
        "kv_cache": {"block_size": 16, "num_blocks": 32, "max_blocks_per_seq": 8},
        "state_manager": {"max_tracked_sequences": 4, "max_ragged_batch_size": 64,
                          "max_ragged_sequence_count": 4, "max_context": 128},
    })
    eng = InferenceEngineV2(cfg, params, rc)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, hf["vocab_size"], size=37).astype(np.int32)
    follow = rng.integers(0, hf["vocab_size"], size=6).astype(np.int32)  # forced, not sampled
    history = np.concatenate([prompt, follow])
    want = np.asarray(qwen3.logits(params, history, hf))          # [43, vocab]

    out = eng.put([0], [prompt])
    while 0 not in out:                                           # a chunked prompt
        out = eng.step()
    rows = [np.asarray(out[0])]                                   # after the prompt
    for tok in follow:
        eng.scheduler.feedback(0, int(tok))
        rows.append(np.asarray(eng.step()[0]))
    got = np.stack(rows)                                          # positions 36 .. 42
    ref = want[len(prompt) - 1:]
    assert got.shape == ref.shape
    assert np.max(np.abs(got - ref)) < TOL * max(1.0, np.max(np.abs(ref)))
