"""Qwen3 (dense) in plain ``jax.numpy``: the reference the benchmark holds the
system to. float32 throughout, ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no batching tricks; one function per equation of the
published description (Qwen3 technical report, arXiv:2505.09388, and the
``Qwen3ForCausalLM`` modelling code it ships with):

  x      = E[tokens]
  layer:   a = RMSNorm(x) ; q, k, v = a Wq, a Wk, a Wv  (no bias)
           q, k = RMSNorm_head(q), RMSNorm_head(k)     (per head, over head_dim,
                                                        one weight shared by heads)
           q, k = RoPE(q), RoPE(k)                     (rotate-half, theta)
           x += softmax(causal(q k^T / sqrt(d))) v Wo  (GQA: query head i reads
                                                        kv head i // group)
           m = RMSNorm(x) ; x += (silu(m Wg) * (m Wu)) Wd
  logits = RMSNorm(x) E^T                              (tied head)

It reads the system's parameter tree (``deepspeed_tpu.models.init_params``
layout: layer weights stacked on a leading [n_layers] axis, projections stored
[in, out]) and the configuration file's Hugging Face keys, and nothing else of
the program. Departures from the published code: none in the mathematics; the
layers are applied one jitted call at a time so that one layer's float32
weights are resident at once.
"""

import functools

import jax
import jax.numpy as jnp

PRECISION = "highest"


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, positions, theta):
    """x: [s, heads, d]; rotate-half form: pair (i, i + d/2)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]   # [s, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnames=("nh", "nkv", "d", "eps", "theta"))
def layer(x, lp, *, nh, nkv, d, eps, theta):
    """One decoder layer on one sequence. x: [s, h] float32."""
    with jax.default_matmul_precision(PRECISION):
        lp = _f32(lp)
        s = x.shape[0]
        pos = jnp.arange(s)
        a = rms_norm(x, lp["attn_norm"], eps)
        q = (a @ lp["wq"]).reshape(s, nh, d)
        k = (a @ lp["wk"]).reshape(s, nkv, d)
        v = (a @ lp["wv"]).reshape(s, nkv, d)
        q = rope(rms_norm(q, lp["q_norm"], eps), pos, theta)
        k = rope(rms_norm(k, lp["k_norm"], eps), pos, theta)
        group = nh // nkv
        k = jnp.repeat(k, group, axis=1)     # query head i reads kv head i // group
        v = jnp.repeat(v, group, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
        causal = pos[:, None] >= pos[None, :]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, nh * d)
        x = x + attn @ lp["wo"]
        m = rms_norm(x, lp["mlp_norm"], eps)
        return x + (jax.nn.silu(m @ lp["w_gate"]) * (m @ lp["w_up"])) @ lp["w_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, embed, *, eps):
    """Logits of the rows of x against the tied head. x: [n, h]."""
    with jax.default_matmul_precision(PRECISION):
        x = rms_norm(x, final_norm.astype(jnp.float32), eps)
        return x @ embed.astype(jnp.float32).T


@jax.jit
def _take_layer(layers, i):
    return jax.tree.map(lambda a: a[i], layers)


def hidden(params, tokens, hf):
    """Last-layer residual stream of one sequence, [s, h] float32."""
    if hf.get("model_type") != "qwen3":
        raise ValueError(f"this reference is Qwen3's, not {hf.get('model_type')!r}'s")
    if not hf.get("tie_word_embeddings"):
        raise ValueError("this reference assumes the tied head of the small Qwen3 models")
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    kw = dict(
        nh=int(hf["num_attention_heads"]), nkv=int(hf["num_key_value_heads"]),
        d=int(hf["head_dim"]), eps=float(hf["rms_norm_eps"]), theta=float(hf["rope_theta"]),
    )
    for i in range(int(hf["num_hidden_layers"])):
        x = layer(x, _take_layer(params["layers"], i), **kw)
    return x


def logits(params, tokens, hf, rows=None):
    """[len(rows) or s, vocab] float32 logits of one sequence; ``rows`` picks
    the positions whose logits are wanted, so that a 152k-wide head is not
    spent on positions nobody reads."""
    x = hidden(params, tokens, hf)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return head(x, params["final_norm"], params["embed"], eps=float(hf["rms_norm_eps"]))


def loss(params, tokens, hf):
    """Mean next-token negative log-likelihood of one sequence [s + 1]:
    positions 0..s-1 predict tokens 1..s."""
    tokens = jnp.asarray(tokens)
    lg = logits(params, tokens[:-1], hf)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)
