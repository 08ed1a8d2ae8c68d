"""OLMoE in plain ``jax.numpy``: the reference the benchmark holds the system
to. float32 throughout, ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no sorting, no batching tricks; one function per equation
of the published code (``transformers`` ``modeling_olmoe.py``:
``OlmoeAttention``, ``OlmoeSparseMoeBlock``, ``OlmoeForCausalLM``; OLMoE,
arXiv:2409.02060):

  x      = E[tokens]
  layer:   a = RMSNorm(x) ; q, k, v = a Wq, a Wk, a Wv  (no bias; clip_qkv null)
           q, k = RMSNorm(q), RMSNorm(k)               (over the WHOLE projection
                                                        width nh*d, before the
                                                        heads are split)
           q, k = RoPE(q), RoPE(k)                     (rotate-half, theta)
           x += softmax(causal(q k^T / sqrt(d))) v Wo  (query head i reads kv
                                                        head i // group; 16:16 here)
           m = RMSNorm(x)
           p = softmax(m Wr)                           (float32, over all experts)
           top = the k largest p of the token          (not renormalised unless
                                                        norm_topk_prob)
           x += sum_{e in top} p_e (silu(m Wg_e) * (m Wu_e)) Wd_e
  logits = RMSNorm(x) W_head                           (untied head)

It reads the system's parameter tree (``deepspeed_tpu.models.init_params``
layout: layer weights stacked on a leading [n_layers] axis, projections stored
[in, out], experts on a second [n_experts] axis) and the configuration file's
Hugging Face keys, and nothing else of the program. Departures from the
published code: none in the mathematics. Every expert is applied to every
token and the result masked by the top-k (the published code gathers each
expert's tokens: the same sum); the layers are applied one jitted call at a
time so that one layer's float32 weights (1.7 GB at the published widths) are
resident at once, and the experts of a layer one at a time inside it.
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


def attention(x, lp, *, nh, nkv, eps, theta):
    """The attention half of a layer, residual included. x: [s, h]."""
    s = x.shape[0]
    d = lp["wq"].shape[-1] // nh
    pos = jnp.arange(s)
    a = rms_norm(x, lp["attn_norm"], eps)
    q = rms_norm(a @ lp["wq"], lp["q_norm"], eps).reshape(s, nh, d)   # whole width
    k = rms_norm(a @ lp["wk"], lp["k_norm"], eps).reshape(s, nkv, d)
    v = (a @ lp["wv"]).reshape(s, nkv, d)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    group = nh // nkv
    k = jnp.repeat(k, group, axis=1)     # query head i reads kv head i // group
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, nh * d)
    return x + attn @ lp["wo"]


def routing_weights(m, router, top_k, renormalise):
    """[s, E]: the token's softmax probability on its top-k experts, 0 elsewhere."""
    probs = jax.nn.softmax(m @ router, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    if renormalise:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(top_e, probs.shape[-1]) * top_p[..., None], axis=1)


def experts(x, lp, *, top_k, renormalise, eps):
    """The sparse block of a layer, residual included: every expert on every
    token, weighted by the routing weights (0 outside the top-k)."""
    m = rms_norm(x, lp["mlp_norm"], eps)
    weights = routing_weights(m, lp["router"], top_k, renormalise)   # [s, E]

    def one(acc, ew):
        wg, wu, wd, w_e = ew
        y = (jax.nn.silu(m @ wg.astype(jnp.float32)) * (m @ wu.astype(jnp.float32))
             ) @ wd.astype(jnp.float32)
        return acc + w_e[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], weights.T))
    return x + out


@functools.partial(jax.jit, static_argnames=("nh", "nkv", "eps", "theta", "top_k", "renormalise"))
def layer(x, lp, *, nh, nkv, eps, theta, top_k, renormalise):
    """One decoder layer on one sequence. x: [s, h] float32."""
    with jax.default_matmul_precision(PRECISION):
        big = ("w_gate", "w_up", "w_down")   # cast an expert at a time, inside the scan
        lp = {k: v if k in big else v.astype(jnp.float32) for k, v in lp.items()}
        x = attention(x, lp, nh=nh, nkv=nkv, eps=eps, theta=theta)
        return experts(x, lp, top_k=top_k, renormalise=renormalise, eps=eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, lm_head, *, eps):
    """Logits of the rows of x against the untied head. x: [n, h]."""
    with jax.default_matmul_precision(PRECISION):
        x = rms_norm(x, final_norm.astype(jnp.float32), eps)
        return x @ lm_head.astype(jnp.float32)


@jax.jit
def _take_layer(layers, i):
    return jax.tree.map(lambda a: a[i], layers)


def hidden(params, tokens, hf):
    """Last-layer residual stream of one sequence, [s, h] float32."""
    if hf.get("model_type") != "olmoe":
        raise ValueError(f"this reference is OLMoE's, not {hf.get('model_type')!r}'s")
    if hf.get("tie_word_embeddings"):
        raise ValueError("this reference assumes OLMoE's untied head")
    if hf.get("clip_qkv") is not None or hf.get("attention_bias"):
        raise ValueError("this reference is of the published OLMoE: clip_qkv null, no attention bias")
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    kw = dict(
        nh=int(hf["num_attention_heads"]), nkv=int(hf["num_key_value_heads"]),
        eps=float(hf["rms_norm_eps"]), theta=float(hf["rope_theta"]),
        top_k=int(hf["num_experts_per_tok"]), renormalise=bool(hf["norm_topk_prob"]),
    )
    for i in range(int(hf["num_hidden_layers"])):
        x = layer(x, _take_layer(params["layers"], i), **kw)
    return x


def logits(params, tokens, hf, rows=None):
    """[len(rows) or s, vocab] float32 logits of one sequence; ``rows`` picks
    the positions whose logits are wanted."""
    x = hidden(params, tokens, hf)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return head(x, params["final_norm"], params["lm_head"], eps=float(hf["rms_norm_eps"]))


def loss(params, tokens, hf):
    """Mean next-token negative log-likelihood of one sequence [s + 1]:
    positions 0..s-1 predict tokens 1..s."""
    tokens = jnp.asarray(tokens)
    lg = logits(params, tokens[:-1], hf)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)
