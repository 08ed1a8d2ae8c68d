"""Qwen3-Next in plain ``jax.numpy``: the reference the benchmark holds the
system to. float32 throughout, ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no chunking, no sorting, no batching; one function per
equation of the published code (``transformers`` 4.57 ``modeling_qwen3_next.py``:
``Qwen3NextGatedDeltaNet`` with ``torch_recurrent_gated_delta_rule``,
``Qwen3NextAttention``, ``Qwen3NextSparseMoeBlock``, ``Qwen3NextForCausalLM``).
Every norm but the gated one is ``rms(x) * (1 + w)``.

  x      = E[tokens]
  layer i, where (i + 1) % full_attention_interval != 0: Gated DeltaNet
           a = norm(x) ; qkv = a Wqkv ; z = a Wz ; b, a' = a Wba
           qkv = silu(causal depthwise conv_4(qkv))          (no bias)
           q, k = l2norm(q), l2norm(k) over the head's 128 ; q *= 128^-0.5
           beta = sigmoid(b) ; g = -exp(A_log) * softplus(a' + dt_bias)
           per value head (key head h // 2), token by token, S [dk, dv] from 0:
               S <- S exp(g) ; d = (v - S^T k) beta ; S <- S + k d^T ; o = S^T q
           x += (w * rms(o) * silu(z)) Wo                    (this norm: plain w)
  layer i otherwise: gated softmax attention
           a = norm(x) ; q, gate = a Wq, a Wq_gate ; k, v = a Wk, a Wv
           q, k = norm(q), norm(k) over the head's 256
           q, k = RoPE on the first partial_rotary_factor * 256 dims (rotate-half)
           x += (softmax(causal(q k^T / sqrt(256))) v * sigmoid(gate)) Wo
  every layer:
           m = norm(x) ; p = softmax(m Wr) (float32, over ALL experts)
           top = the k largest p of the token, renormalised over the k
           x += sum_{e in top, e held here} p_e (silu(m Wg_e) * (m Wu_e)) Wd_e
              + sigmoid(m w_sg) (silu(m Wg_s) * (m Wu_s)) Wd_s
  logits = norm(x) W_head                                    (untied head)

It reads the system's parameter tree (``deepspeed_tpu.models.init_params``
layout: what every layer has stacked on [n_layers], attention under
``layers["full"]`` on the full-attention layers, DeltaNet under
``layers["gdn"]``, projections stored [in, out], experts on a second axis) and
the configuration file's Hugging Face keys, and nothing else of the program.

Departures from the published code, none in the mathematics:
  * the checkpoint's fused ``in_proj_qkvz`` / ``in_proj_ba`` (laid out by key
    head) and ``q_proj`` (a query and a gate a head) are read already split by
    what they feed, which is how the system's tree stores them
    (``models/hf.py _qwen3_next_layer`` does the split);
  * prefill runs the same token-by-token recurrence as decode (the published
    prefill uses the chunked form of the same rule);
  * the chip's SHARE: where the configuration holds fewer experts than the
    router is wide (``deployment_share``), the pairs of experts held elsewhere
    are dropped from the sum, as the program drops them; the router, its top-k
    and the renormalisation are over all of them;
  * the ``mtp.*`` weights are no part of ``Qwen3NextForCausalLM`` and none here;
  * every held expert is applied to every token and masked by the top-k; a
    layer is one jitted call that reads its weights out of the whole stacked
    tree in place (no copy of a layer is made) and upcasts what is small at
    once and its experts one at a time, and the next layer waits for it: beside
    10.85 GB of bf16 weights the reference holds under half a GB.
"""

import functools

import jax
import jax.numpy as jnp

PRECISION = "highest"


def rms_norm_1p(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def rope_partial(x, positions, theta, rot):
    """x: [s, heads, d]; rotate-half over the first ``rot`` dims, the rest pass."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., : rot // 2], x[..., rot // 2: rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def causal_conv(x, w):
    """x: [s, C]; w: [K, C], ``w[j]`` on the input ``K - 1 - j`` tokens back;
    zeros before the sequence; then SiLU."""
    K, s = w.shape[0], x.shape[0]
    ext = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x], axis=0)
    return jax.nn.silu(sum(ext[j: j + s] * w[j] for j in range(K)))


def delta_rule(q, k, v, g, beta):
    """The recurrence, one token at a time. q, k: [s, nv, dk]; v: [s, nv, dv];
    g, beta: [s, nv]. Returns o [s, nv, dv]."""

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = S * jnp.exp(g_t)[:, None, None]
        delta = (v_t - jnp.einsum("hkv,hk->hv", S, k_t)) * b_t[:, None]
        S = S + k_t[:, :, None] * delta[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(token, S0, (q, k, v, g, beta))[1]


def delta_net(x, lp, *, nk, nv, dk, dv, eps):
    """The Gated DeltaNet half of a layer, residual included. x: [s, h]."""
    s = x.shape[0]
    a = rms_norm_1p(x, lp["attn_norm"], eps)
    qkv = causal_conv(a @ lp["gdn_qkv"], lp["gdn_conv"])
    q, k, v = jnp.split(qkv, [nk * dk, 2 * nk * dk], axis=-1)
    q = l2norm(q.reshape(s, nk, dk)) * dk ** -0.5
    k = l2norm(k.reshape(s, nk, dk))
    q, k = jnp.repeat(q, nv // nk, axis=1), jnp.repeat(k, nv // nk, axis=1)
    ba = a @ lp["gdn_ba"]
    beta = jax.nn.sigmoid(ba[:, :nv])
    g = -jnp.exp(lp["gdn_a_log"]) * jax.nn.softplus(ba[:, nv:] + lp["gdn_dt_bias"])
    o = delta_rule(q, k, v.reshape(s, nv, dv), g, beta)
    z = (a @ lp["gdn_z"]).reshape(s, nv, dv)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + eps)
    return x + (lp["gdn_norm"] * o * jax.nn.silu(z)).reshape(s, nv * dv) @ lp["gdn_out"]


def attention(x, lp, *, nh, nkv, eps, theta, rot):
    """The gated-attention half of a layer, residual included. x: [s, h]."""
    s = x.shape[0]
    d = lp["wq"].shape[-1] // nh
    pos = jnp.arange(s)
    a = rms_norm_1p(x, lp["attn_norm"], eps)
    q = rms_norm_1p((a @ lp["wq"]).reshape(s, nh, d), lp["q_norm"], eps)
    k = rms_norm_1p((a @ lp["wk"]).reshape(s, nkv, d), lp["k_norm"], eps)
    v = (a @ lp["wv"]).reshape(s, nkv, d)
    q, k = rope_partial(q, pos, theta, rot), rope_partial(k, pos, theta, rot)
    k = jnp.repeat(k, nh // nkv, axis=1)     # query head i reads kv head i // group
    v = jnp.repeat(v, nh // nkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
    scores = jnp.where((pos[:, None] >= pos[None, :])[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v).reshape(s, nh * d)
    return x + (attn * jax.nn.sigmoid(a @ lp["wq_gate"])) @ lp["wo"]


def routing_weights(m, router, top_k):
    """[s, E_all]: the token's renormalised probability on its top-k experts."""
    probs = jax.nn.softmax(m @ router, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(top_e, probs.shape[-1]) * top_p[..., None], axis=1)


def swiglu(m, wg, wu, wd):
    f32 = jnp.float32
    return (jax.nn.silu(m @ wg.astype(f32)) * (m @ wu.astype(f32))) @ wd.astype(f32)


def experts(x, lp, *, top_k, first, eps, layer=None):
    """The sparse block of a layer, residual included: the held experts
    (numbers ``first`` on, as many as the stacks hold) on every token under the
    routing weights, and the shared expert under its sigmoid gate. The expert
    stacks in ``lp`` are the layer's ``[E, ...]``, or with ``layer`` the whole
    ``[L, E, ...]``, read an expert at a time where they lie."""
    m = rms_norm_1p(x, lp["mlp_norm"], eps)
    held = lp["w_gate"].shape[-3]
    weights = routing_weights(m, lp["router"], top_k)[:, first: first + held]

    def one(acc, ew):
        e, w_e = ew
        wg, wu, wd = (lp[k][e] if layer is None else lp[k][layer, e]
                      for k in ("w_gate", "w_up", "w_down"))
        return acc + w_e[:, None] * swiglu(m, wg, wu, wd), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (jnp.arange(held), weights.T))
    shared = swiglu(m, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    return x + out + jax.nn.sigmoid(m @ lp["shared_gate_proj"]) * shared


_STATIC = ("kind", "nh", "nkv", "nk", "nv", "dk", "dv", "eps", "theta", "rot", "top_k", "first")


@functools.partial(jax.jit, static_argnames=_STATIC)
def layer(x, layers, i, j, *, kind, nh, nkv, nk, nv, dk, dv, eps, theta, rot, top_k, first):
    """Decoder layer ``i`` on one sequence, the ``j``-th of its kind. x: [s, h]
    float32; ``layers``: the whole stacked tree."""
    with jax.default_matmul_precision(PRECISION):
        big = ("w_gate", "w_up", "w_down")   # left where they are: an expert at a time
        own = layers["gdn" if kind == "linear_attention" else "full"]
        lp = {k: v[i].astype(jnp.float32) for k, v in layers.items()
              if k not in big + ("full", "gdn")}
        lp |= {k: v[j].astype(jnp.float32) for k, v in own.items()}
        lp |= {k: layers[k] for k in big}
        if kind == "linear_attention":
            x = delta_net(x, lp, nk=nk, nv=nv, dk=dk, dv=dv, eps=eps)
        else:
            x = attention(x, lp, nh=nh, nkv=nkv, eps=eps, theta=theta, rot=rot)
        return experts(x, lp, top_k=top_k, first=first, eps=eps, layer=i)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, lm_head, *, eps):
    """Logits of the rows of x against the untied head. x: [n, h]."""
    with jax.default_matmul_precision(PRECISION):
        return rms_norm_1p(x, final_norm.astype(jnp.float32), eps) @ lm_head.astype(jnp.float32)


def layer_types(hf):
    """``layer_types`` as ``Qwen3NextConfig`` derives it from the interval."""
    every = int(hf.get("full_attention_interval", 4))
    return hf.get("layer_types") or [
        "full_attention" if (i + 1) % every == 0 else "linear_attention"
        for i in range(int(hf["num_hidden_layers"]))]


def hidden(params, tokens, hf):
    """Last-layer residual stream of one sequence, [s, h] float32."""
    if hf.get("model_type") != "qwen3_next":
        raise ValueError(f"this reference is Qwen3-Next's, not {hf.get('model_type')!r}'s")
    if hf.get("tie_word_embeddings") or not hf.get("norm_topk_prob", True):
        raise ValueError("this reference is of the published Qwen3-Next: untied head, "
                         "top-k renormalised")
    if hf.get("mlp_only_layers") or int(hf.get("decoder_sparse_step", 1)) != 1:
        raise ValueError("this reference assumes the expert block in every layer")
    share = hf.get("deployment_share") or {}
    kw = dict(
        nh=int(hf["num_attention_heads"]), nkv=int(hf["num_key_value_heads"]),
        nk=int(hf["linear_num_key_heads"]), nv=int(hf["linear_num_value_heads"]),
        dk=int(hf["linear_key_head_dim"]), dv=int(hf["linear_value_head_dim"]),
        eps=float(hf["rms_norm_eps"]), theta=float(hf["rope_theta"]),
        rot=int(int(hf["head_dim"]) * float(hf.get("partial_rotary_factor", 1.0))),
        top_k=int(hf["num_experts_per_tok"]),
        first=int(share.get("share_index", 0)) * int(hf["num_experts"]),
    )
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    seen = {"full": 0, "gdn": 0}
    for i, kind in enumerate(layer_types(hf)):
        stack = "full" if kind == "full_attention" else "gdn"
        x = layer(x, params["layers"], i, seen[stack], kind=kind, **kw)
        x.block_until_ready()   # a layer's temporaries go before the next one's come
        seen[stack] += 1
    return x


def logits(params, tokens, hf, rows=None):
    """[len(rows) or s, vocab] float32 logits of one sequence; ``rows`` picks
    the positions whose logits are wanted."""
    x = hidden(params, tokens, hf)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return head(x, params["final_norm"], params["lm_head"], eps=float(hf["rms_norm_eps"]))
