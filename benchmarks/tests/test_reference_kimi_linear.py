"""The plain Kimi Linear reference. ``transformers`` 4.57 has no ``kimi_linear`` and the
flash-linear-attention library is not installed, so there is no published code here to hold
it to: its parts are held to the equations written out in numpy (float64), to the Gated
DeltaNet reference where one decay a head makes the two rules the same (that one IS held to
``transformers``' ``modeling_qwen3_next.py``), and the whole to the configuration file. Run by
hand on the CPU with the other tests of this directory:
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.common import Catalog
from benchmarks.reference import kimi_linear as ref


def test_the_delta_rule_is_the_equations_written_out():
    """Decay a key channel, the delta step on the decayed state, read the updated state."""
    rng = np.random.default_rng(0)
    s, H, d = 9, 2, 4
    q, k, v = (rng.normal(size=(s, H, d)) for _ in range(3))
    g, beta = -rng.uniform(0, 3, size=(s, H, d)), rng.uniform(size=(s, H))
    S, want = np.zeros((H, d, d)), np.zeros((s, H, d))
    for t in range(s):
        S = S * np.exp(g[t])[:, :, None]
        S = S + k[t][:, :, None] * (beta[t][:, None] * (v[t] - np.einsum("hkv,hk->hv", S, k[t])))[:, None, :]
        want[t] = np.einsum("hkv,hk->hv", S, q[t])
    with jax.default_matmul_precision("highest"):
        got = ref.delta_rule(*(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta)))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


def test_one_decay_a_head_is_the_gated_delta_reference():
    """With every channel of a head at one decay the rule is Qwen3-Next's, whose reference
    (``benchmarks/reference/qwen3_next.py``) is held to the published code."""
    from benchmarks.reference.qwen3_next import delta_rule as rule

    k = jax.random.split(jax.random.key(1), 5)
    s, H, d = 20, 2, 8
    q, kk, v = (jax.random.normal(k[i], (s, H, d)) for i in range(3))
    g = -jax.random.uniform(k[3], (s, H))
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (s, H)))
    with jax.default_matmul_precision("highest"):
        got = ref.delta_rule(q, kk, v, jnp.broadcast_to(g[..., None], (s, H, d)), beta)
        want = rule(q, kk, v, g, beta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_the_conv_is_causal_depthwise_and_starts_from_zero():
    rng = np.random.default_rng(2)
    x, w = rng.normal(size=(6, 3)), rng.normal(size=(4, 3))
    want = np.zeros((6, 3))
    for t in range(6):
        for j in range(4):   # w[j] multiplies the input 3 - j tokens back
            if t - (3 - j) >= 0:
                want[t] += w[j] * x[t - (3 - j)]
    want = want / (1 + np.exp(-want))
    got = ref.causal_conv_silu(jnp.asarray(x, jnp.float32), jnp.asarray(w, jnp.float32))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-6)


def test_routing_chooses_on_score_plus_bias_and_weighs_by_the_score():
    rng = np.random.default_rng(3)
    x, router, bias = rng.normal(size=(5, 8)), rng.normal(size=(8, 16)), 0.5 * rng.normal(size=16)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.routing_weights(*(jnp.asarray(a, jnp.float32) for a in (x, router, bias)),
                                             top_k=3, scale=2.446))
    s = 1 / (1 + np.exp(-(x @ router)))
    for t in range(5):
        top = np.argsort(-(s[t] + bias))[:3]
        want = np.zeros(16)
        want[top] = s[t][top] / s[t][top].sum() * 2.446
        np.testing.assert_allclose(got[t], want, atol=1e-5)


def test_the_layer_plan_is_the_head_of_the_published_lists():
    hf = Catalog().config("kimi-linear-48b-a3b")
    assert ref.layer_kinds(hf) == ("kda", "kda", "kda", "full") * 3
    pub = hf["published"]["linear_attn_config"]
    assert hf["linear_attn_config"]["kda_layers"] == [i for i in pub["kda_layers"] if i <= 12]
    assert hf["linear_attn_config"]["full_attn_layers"] == [i for i in pub["full_attn_layers"] if i <= 12]
    with pytest.raises(ValueError, match="do not partition"):
        ref.layer_kinds({**hf, "num_hidden_layers": 13})
    with pytest.raises(ValueError, match="Kimi Linear's"):
        ref.hidden({}, [0], {**hf, "model_type": "axk1"})
    with pytest.raises(ValueError, match="published Kimi Linear"):
        ref.hidden({}, [0], {**hf, "mla_use_nope": False})


def test_the_configuration_file_holds_every_key_of_the_published_row():
    hf = Catalog().config("kimi-linear-48b-a3b")
    assert hf["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size", "linear_attn_config"]
    assert (hf["num_hidden_layers"], hf["num_experts"], hf["vocab_size"]) == (12, 32, 20480)
    assert hf["published"]["num_experts"] == hf["deployment_share"]["num_experts"] == 256
    assert hf["deployment_share"]["chips_per_layer"] * hf["num_experts"] == 256
    lin = hf["linear_attn_config"]
    assert (lin["head_dim"], lin["num_heads"], lin["short_conv_kernel_size"]) == (128, 32, 4)
    assert (hf["hidden_size"], hf["intermediate_size"], hf["moe_intermediate_size"]) == (2304, 9216, 1024)
    assert (hf["kv_lora_rank"], hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]) == (512, 128, 64, 128)
    assert hf["num_experts_per_token"] == 8 and hf["q_lora_rank"] is None and hf["mla_use_nope"]
