"""The plain Qwen3-Next reference against the published code: ``transformers``'
``Qwen3NextForCausalLM`` (4.57, its torch path: ``torch_chunk_gated_delta_rule`` for the
prompt, no flash-linear-attention here) on a tiny seeded checkpoint in float32 with ALL
experts held, loaded through the system's importer (``models/hf.py load_hf_model``, which
renames, transposes and splits the fused projections by use), so that the yardstick itself is
held to ``modeling_qwen3_next.py``: the (1 + w) norms and the plain-w gated one, the conv's
tap order, L2-normalised q / k and the q scale, the gates, value heads 3 a key head, the
gated attention output, partial rotary, top-k renormalised, the shared expert's sigmoid gate,
the untied head. CPU, by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``."""

import json
import os

import numpy as np
import pytest

from benchmarks.reference import qwen3_next

transformers = pytest.importorskip("transformers")
import torch  # noqa: E402

# float32 on both sides (torch on the CPU, jax.numpy at "highest"); the published prefill is
# the chunked rule and the reference the recurrence, through five layers, logits of unit scale
TOL = 5e-4


@pytest.mark.parametrize("layers,interval", [(5, 4), (4, 2)])
def test_against_transformers(tmp_path, layers, interval):
    from deepspeed_tpu.models import load_hf_model

    torch.manual_seed(0)
    cfg = transformers.Qwen3NextConfig(
        vocab_size=256, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
        shared_expert_intermediate_size=48, num_experts=8, num_experts_per_tok=3,
        norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[],
        num_hidden_layers=layers, full_attention_interval=interval, num_attention_heads=4,
        num_key_value_heads=2, head_dim=32, partial_rotary_factor=0.25,
        linear_conv_kernel_dim=4, linear_key_head_dim=16, linear_value_head_dim=24,
        linear_num_key_heads=2, linear_num_value_heads=6, max_position_embeddings=128,
        tie_word_embeddings=False, output_router_logits=False)
    model = transformers.Qwen3NextForCausalLM(cfg).eval()
    with torch.no_grad():   # norms at their identity would hide (1 + w) read as w
        for name, p in model.named_parameters():
            if "norm" in name or name.endswith(("dt_bias", "A_log")):
                p.add_(0.2 * torch.randn_like(p))
    model.save_pretrained(tmp_path)
    hf = json.load(open(os.path.join(tmp_path, "config.json")))
    _, params = load_hf_model(str(tmp_path), dtype="float32")
    # 70 tokens: more than one chunk of the published prefill
    toks = np.random.default_rng(0).integers(0, 256, size=(2, 70)).astype(np.int32)
    with torch.no_grad():
        want = model(torch.tensor(toks, dtype=torch.long)).logits.numpy()
    got = np.stack([np.asarray(qwen3_next.logits(params, row, hf)) for row in toks])
    assert np.max(np.abs(want)) > 0.1
    assert np.max(np.abs(got - want)) < TOL * max(1.0, np.max(np.abs(want)))
    np.testing.assert_allclose(
        qwen3_next.logits(params, toks[0], hf, rows=[3, 69]), got[0][[3, 69]], atol=1e-6)


def test_layer_types_follow_the_interval():
    hf = {"num_hidden_layers": 12, "full_attention_interval": 4}
    kinds = qwen3_next.layer_types(hf)
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] == [3, 7, 11]
    assert kinds.count("linear_attention") == 9


def test_the_reference_refuses_what_it_is_not():
    for bad in ({"model_type": "olmoe"}, {"model_type": "qwen3_next", "tie_word_embeddings": True},
                {"model_type": "qwen3_next", "mlp_only_layers": [0]}):
        with pytest.raises(ValueError):
            qwen3_next.hidden({}, [0], bad)
