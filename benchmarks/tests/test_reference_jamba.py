"""The plain Jamba reference against the published code: ``transformers``'
``JambaForCausalLM`` (4.57, its slow path: ``use_mamba_kernels=False``) on a tiny seeded
checkpoint in float32, loaded through the system's importer (``models/hf.py load_hf_model``,
which renames and transposes), so that the yardstick itself is held to ``modeling_jamba.py``:
plain-w norms, the conv's tap order and bias, the norms on dt, B and C, softplus on the step,
``-exp(A_log)``, the D term, the silu gate, attention without positions on one key head, the
dense MLP, the tied head. CPU, by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``
(tests/unit/test_hf_archs.py holds one case of it in tier 1)."""

import json
import os

import numpy as np
import pytest

from benchmarks.reference import jamba

transformers = pytest.importorskip("transformers")
import torch  # noqa: E402

# float32 on both sides (torch on the CPU, jax.numpy at "highest"): measured 6e-7 on logits of
# scale 0.8
TOL = 2e-5


@pytest.mark.parametrize("layers,period,offset,kv", [(8, 4, 2, 1), (6, 3, 0, 2)])
def test_against_transformers(tmp_path, layers, period, offset, kv):
    from deepspeed_tpu.models import load_hf_model

    torch.manual_seed(0)
    cfg = transformers.JambaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=96, num_hidden_layers=layers,
        num_attention_heads=4, num_key_value_heads=kv, attn_layer_period=period,
        attn_layer_offset=offset, num_experts=1, num_experts_per_tok=1, mamba_d_state=16,
        mamba_dt_rank=8, mamba_expand=2, mamba_d_conv=4, use_mamba_kernels=False,
        tie_word_embeddings=True, rms_norm_eps=1e-6, max_position_embeddings=128)
    model = transformers.JambaForCausalLM(cfg).eval()
    with torch.no_grad():   # norms at one and a bias at zero would hide a term left out
        for name, p in model.named_parameters():
            if "layernorm" in name or name.endswith(("conv1d.bias", "A_log", "mamba.D")):
                p.add_(0.2 * torch.randn_like(p))
    model.save_pretrained(tmp_path)
    hf = json.load(open(os.path.join(tmp_path, "config.json")))
    _, params = load_hf_model(str(tmp_path), dtype="float32")
    toks = np.random.default_rng(0).integers(0, 256, size=(2, 70)).astype(np.int32)
    with torch.no_grad():
        want = model(torch.tensor(toks, dtype=torch.long)).logits.numpy()
    got = np.stack([np.asarray(jamba.logits(params, row, hf)) for row in toks])
    assert np.max(np.abs(want)) > 0.1
    assert np.max(np.abs(got - want)) < TOL * max(1.0, np.max(np.abs(want)))


def test_layer_kinds_follow_offset_and_period():
    hf = {"num_hidden_layers": 28, "attn_layer_period": 14, "attn_layer_offset": 7}
    kinds = jamba.layer_kinds(hf)
    assert [i for i, k in enumerate(kinds) if k == "full"] == [7, 21] and kinds.count("mamba") == 26


def test_another_architecture_is_refused():
    with pytest.raises(ValueError, match="Jamba"):
        jamba.hidden({}, [1], {"model_type": "qwen3"})
    with pytest.raises(ValueError, match="dense"):
        jamba.hidden({}, [1], {"model_type": "jamba", "num_experts": 16})
