"""The plain OLMoE reference against the published code: ``transformers``'
``OlmoeForCausalLM`` on a tiny seeded checkpoint in float32, loaded through the
system's importer (``models/hf.py load_hf_model``, which only renames and
transposes), so that the yardstick itself is held to ``modeling_olmoe.py``:
the q/k RMSNorm over the whole projection width, the float32 router softmax,
top-k without renormalisation (and with it), the untied head. CPU, by hand:
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``."""

import json
import os

import numpy as np
import pytest

from benchmarks.reference import olmoe

transformers = pytest.importorskip("transformers")
import torch  # noqa: E402

# float32 on both sides (torch on the CPU, jax.numpy at "highest"), different
# summation orders through two layers, logits of unit scale
TOL = 2e-4


@pytest.mark.parametrize("renormalise", [False, True])
def test_against_transformers(tmp_path, renormalise):
    from deepspeed_tpu.models import load_hf_model

    torch.manual_seed(0)
    cfg = transformers.OlmoeConfig(
        vocab_size=256, hidden_size=64, intermediate_size=48, num_experts=8, num_experts_per_tok=2,
        norm_topk_prob=renormalise, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=4, max_position_embeddings=128, tie_word_embeddings=False,
        output_router_logits=False)
    model = transformers.OlmoeForCausalLM(cfg).eval()
    with torch.no_grad():   # norms at 1 would hide a norm over the wrong width
        for name, p in model.named_parameters():
            if "norm" in name:
                p.add_(0.2 * torch.randn_like(p))
    model.save_pretrained(tmp_path)
    hf = json.load(open(os.path.join(tmp_path, "config.json")))
    _, params = load_hf_model(str(tmp_path), dtype="float32")
    toks = np.random.default_rng(0).integers(0, 256, size=(2, 33)).astype(np.int32)
    with torch.no_grad():
        want = model(torch.tensor(toks, dtype=torch.long)).logits.numpy()
    got = np.stack([np.asarray(olmoe.logits(params, row, hf)) for row in toks])
    assert np.max(np.abs(want)) > 0.1
    assert np.max(np.abs(got - want)) < TOL * max(1.0, np.max(np.abs(want)))
    # rows= picks positions; the loss is the mean next-token NLL
    np.testing.assert_allclose(olmoe.logits(params, toks[0], hf, rows=[3, 32]), got[0][[3, 32]], atol=1e-6)
    with torch.no_grad():
        ref_loss = float(model(torch.tensor(toks[:1], dtype=torch.long),
                               labels=torch.tensor(toks[:1], dtype=torch.long)).loss)
    assert abs(float(olmoe.loss(params, toks[0], hf)) - ref_loss) < TOL
