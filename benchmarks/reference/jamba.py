"""Jamba (the dense form: ``num_experts`` 1) in plain ``jax.numpy``: the
reference the benchmark holds the system to. float32 throughout,
``jax.default_matmul_precision("highest")``, no kernels, no cache, no chunking,
no batching; one function per equation of the published code (``transformers``
4.57 ``modeling_jamba.py``: ``JambaMambaMixer.slow_forward``,
``JambaAttention`` / ``eager_attention_forward``, ``JambaMLP``,
``JambaForCausalLM``). Every norm is ``w * x / rms(x)``.

  x      = E[tokens]
  layer i, where i % attn_layer_period != attn_layer_offset: Mamba
           a = norm(x) ; (u, z) = a W_in                      (no bias)
           u = silu(causal depthwise conv_4(u) + b_conv)
           (dt, B, C) = u W_x ; dt, B, C = norm(dt), norm(B), norm(C)
           delta = softplus(dt W_dt + b_dt) ; A = -exp(A_log)
           per channel c, token by token, S [N] from 0:
               S <- exp(delta_c A_c) S + delta_c u_c B ; y_c = S . C + D_c u_c
           x += (y * silu(z)) W_out
  layer i otherwise: grouped softmax attention, NO position term
           a = norm(x) ; q, k, v = a Wq, a Wk, a Wv
           x += softmax(causal(q k^T / sqrt(head_dim))) v Wo
  every layer:
           m = norm(x) ; x += (silu(m Wg) * (m Wu)) Wd
  logits = norm(x) E^T                                        (tied head)

It reads the system's parameter tree (``deepspeed_tpu.models.init_params``
layout: what every layer has stacked on [n_layers], attention under
``layers["full"]`` on the attention layers, Mamba under ``layers["mamba"]``,
projections stored [in, out]) and the configuration file's Hugging Face keys,
and nothing else of the program.

Departures from the published code, none in the mathematics:
  * the tree stores ``A_log`` as [N, d] (a checkpoint's is [d, N]) and the
    conv's weight as [K, d] (a checkpoint's is [d, 1, K]); ``models/hf.py
    _jamba_layer`` does both;
  * the recurrence is a ``lax.scan`` over tokens that carries ``S [N, d]`` and
    forms each token's ``exp(delta A)`` and ``delta u B`` inside it; the
    published loop first writes both for every token, a ``[tokens, d, N]``
    tensor (5.9 GB a layer at 17,920 tokens) that nothing needs whole;
  * attention runs a block of queries at a time (the scores of 20 heads over
    17,920 tokens squared would be 25 GB), each block over every key;
  * a layer is one jitted call that reads its weights out of the whole stacked
    tree in place and upcasts them there, and the next layer waits for it.
"""

import functools

import jax
import jax.numpy as jnp

PRECISION = "highest"
QUERY_BLOCK = 512


def rms_norm(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def causal_conv(x, w, b):
    """x: [s, C]; w: [K, C], ``w[j]`` on the input ``K - 1 - j`` tokens back;
    zeros before the sequence; bias; then SiLU."""
    K, s = w.shape[0], x.shape[0]
    ext = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x], axis=0)
    return jax.nn.silu(sum(ext[j: j + s] * w[j] for j in range(K)) + b)


def selective_scan(u, delta, A, B, C, D):
    """The recurrence, one token at a time. u, delta: [s, d]; A: [N, d]; B, C:
    [s, N]; D: [d]. Returns y [s, d]."""

    def token(S, xs):
        u_t, dt_t, B_t, C_t = xs
        S = jnp.exp(dt_t[None, :] * A) * S + (dt_t * u_t)[None, :] * B_t[:, None]
        return S, jnp.sum(S * C_t[:, None], axis=0) + D * u_t

    return jax.lax.scan(token, jnp.zeros(A.shape, jnp.float32), (u, delta, B, C))[1]


def mamba(x, lp, *, eps):
    """The Mamba half of a layer, residual included. x: [s, h]."""
    N, d = lp["mamba_a_log"].shape
    R = lp["mamba_dt"].shape[0]
    uz = rms_norm(x, lp["attn_norm"], eps) @ lp["mamba_in"]
    u, z = uz[:, :d], uz[:, d:]
    u = causal_conv(u, lp["mamba_conv"], lp["mamba_conv_b"])
    sel = u @ lp["mamba_x"]
    dt = rms_norm(sel[:, :R], lp["mamba_dt_norm"], eps)
    B = rms_norm(sel[:, R: R + N], lp["mamba_b_norm"], eps)
    C = rms_norm(sel[:, R + N:], lp["mamba_c_norm"], eps)
    delta = jax.nn.softplus(dt @ lp["mamba_dt"] + lp["mamba_dt_b"])
    y = selective_scan(u, delta, -jnp.exp(lp["mamba_a_log"]), B, C, lp["mamba_d"])
    return x + (y * jax.nn.silu(z)) @ lp["mamba_out"]


def attention(x, lp, *, nh, nkv, eps):
    """The attention half of a layer, residual included: no rotary, no bias,
    no q/k norm. x: [s, h]. A block of queries at a time."""
    s = x.shape[0]
    d = lp["wq"].shape[-1] // nh
    pos = jnp.arange(s)
    a = rms_norm(x, lp["attn_norm"], eps)
    q = (a @ lp["wq"]).reshape(s, nh, d)
    k = jnp.repeat((a @ lp["wk"]).reshape(s, nkv, d), nh // nkv, axis=1)
    v = jnp.repeat((a @ lp["wv"]).reshape(s, nkv, d), nh // nkv, axis=1)
    qb = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    def block(args):
        q_b, pos_b = args
        scores = jnp.einsum("qhd,khd->hqk", q_b, k) / jnp.sqrt(jnp.float32(d))
        scores = jnp.where((pos_b[:, None] >= pos[None, :])[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    o = jax.lax.map(block, (q.reshape(s // qb, qb, nh, d), pos.reshape(s // qb, qb)))
    return x + o.reshape(s, nh * d) @ lp["wo"]


def mlp(x, lp, *, eps):
    """The gated MLP every layer has, residual included."""
    m = rms_norm(x, lp["mlp_norm"], eps)
    return x + (jax.nn.silu(m @ lp["w_gate"]) * (m @ lp["w_up"])) @ lp["w_down"]


@functools.partial(jax.jit, static_argnames=("kind", "nh", "nkv", "eps"))
def layer(x, layers, i, j, *, kind, nh, nkv, eps):
    """Decoder layer ``i`` on one sequence, the ``j``-th of its kind. x: [s, h]
    float32; ``layers``: the whole stacked tree."""
    with jax.default_matmul_precision(PRECISION):
        lp = {k: v[i].astype(jnp.float32) for k, v in layers.items() if not isinstance(v, dict)}
        lp |= {k: v[j].astype(jnp.float32) for k, v in layers[kind].items()}
        x = mamba(x, lp, eps=eps) if kind == "mamba" else attention(x, lp, nh=nh, nkv=nkv, eps=eps)
        return mlp(x, lp, eps=eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, embed, *, eps):
    """Logits of the rows of x against the tied head. x: [n, h]."""
    with jax.default_matmul_precision(PRECISION):
        return rms_norm(x, final_norm.astype(jnp.float32), eps) @ embed.astype(jnp.float32).T


def layer_kinds(hf):
    """``JambaConfig.layers_block_type`` under this tree's names."""
    period, offset = int(hf["attn_layer_period"]), int(hf["attn_layer_offset"])
    return ["full" if i % period == offset else "mamba" for i in range(int(hf["num_hidden_layers"]))]


def hidden(params, tokens, hf):
    """Last-layer residual stream of one sequence, [s, h] float32."""
    if hf.get("model_type") != "jamba":
        raise ValueError(f"this reference is Jamba's, not {hf.get('model_type')!r}'s")
    if int(hf.get("num_experts", 1)) != 1 or not hf.get("tie_word_embeddings", True):
        raise ValueError("this reference is of the dense Jamba: num_experts 1, a tied head")
    if not hf.get("mamba_conv_bias", True) or hf.get("mamba_proj_bias", False):
        raise ValueError("this reference assumes a conv with bias and projections without")
    kw = dict(nh=int(hf["num_attention_heads"]), nkv=int(hf["num_key_value_heads"]),
              eps=float(hf["rms_norm_eps"]))
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    seen = {"full": 0, "mamba": 0}
    for i, kind in enumerate(layer_kinds(hf)):
        x = layer(x, params["layers"], i, seen[kind], kind=kind, **kw)
        x.block_until_ready()   # a layer's temporaries go before the next one's come
        seen[kind] += 1
    return x


def logits(params, tokens, hf, rows=None):
    """[len(rows) or s, vocab] float32 logits of one sequence; ``rows`` picks
    the positions whose logits are wanted."""
    x = hidden(params, tokens, hf)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return head(x, params["final_norm"], params["embed"], eps=float(hf["rms_norm_eps"]))
