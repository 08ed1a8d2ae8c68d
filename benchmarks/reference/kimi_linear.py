"""Kimi Linear (``model_type: kimi_linear``) in plain ``jax.numpy``: the
reference the benchmark holds the system to. float32 throughout,
``jax.default_matmul_precision("highest")``, no kernels, no cache, no chunks, no
batching: a full-sequence forward of one sequence, the Kimi Delta Attention rule
as a ``lax.scan`` over tokens that carries the state, latent attention in the
EXPANDED form (every head's keys and values made of the latent; the served
program attends in the absorbed form). The equations are the published
``modeling_kimi.py``'s (``KimiDeltaAttention``, ``KimiMLAAttention``,
``KimiMoEGate``) and the Kimi Linear report's (arXiv:2510.26692) as remembered:
the configuration file lists under ``assumed`` each point that could not be read
here. Layers are numbered from 1 in ``linear_attn_config``, from 0 below.

  x = E[tokens]
  a KDA layer:  a = rms(x) w_in
      q, k, v = silu(conv4(a Wq)), silu(conv4(a Wk)), silu(conv4(a Wv))   [T, H, d]
                                   (depthwise, causal, no bias, from zero inputs)
      q, k = l2norm(q) d^-0.5, l2norm(k)                  over a head's d
      g    = -exp(A_log[head]) softplus((a Wf_a) Wf_b + dt_bias)   [T, H, d] <= 0
      beta = sigmoid(a Wb)                                [T, H]
      S_t  = exp(g_t)[:, None] S_{t-1}                    S [d (key), d (value)] a head, from 0
      S_t += k_t (beta_t (v_t - S_t^T k_t))^T ;  o_t = S_t^T q_t
      x   += (rms_head(o) w_o * sigmoid((a Wg_a) Wg_b)) Wo
  a latent layer:  a = rms(x) w_in
      q = a Wq -> heads of (qk_nope | qk_rope) ; [c | k_p] = a W_kva ; c = rms(c) w_kva
      [k_n | v] = c W_kvb -> heads of (qk_nope | v_head)
      scores (q_n . k_n + q_p . k_p) (qk_nope + qk_rope)^-0.5, causal softmax;
      k_p shared by the heads; NO rotary anywhere (mla_use_nope)
      x += (softmax v) Wo
  every layer:  m = rms(x) w_ff
      layer < first_k_dense_replace: x += (silu(m Wg) * (m Wu)) Wd
      else: s = sigmoid(m Wr) (over ALL experts) ; top = the k largest s + bias
            w = s[top] / (sum s[top] + 1e-20) * routed_scaling_factor
            x += sum_{e in top, e held here} w_e E_e(m) + S(m)
  logits = rms(x) w_f W_head                              (untied head)

It reads the system's parameter tree (``deepspeed_tpu.models.init_params``
layout: norms on [n_layers], the latent layers' attention under
``layers["full"]`` and the KDA layers' under ``layers["kda"]`` at the layer's
ordinal among its kind, the lead layers' MLP under ``layers["lead"]``, the expert
block under ``layers["sparse"]``, projections stored [in, out], a KDA layer's q |
k | v projections and conv weights side by side) and the configuration file's
Hugging Face keys, and nothing else of the program.

Departures from the published code, none in the mathematics:
  * the chip's SHARE: where the configuration holds fewer experts than the
    router is wide (``deployment_share``), the pairs of experts held elsewhere
    are neither multiplied nor summed, as the program drops them; the router,
    its top-k and the renormalisation are over all of them.
    ``num_hidden_layers`` is a pipeline stage's: the stack's head, the two layer
    lists cut to it;
  * ``num_shared_experts`` shared experts are one MLP of their summed width;
  * every held expert is applied to every token and masked by the top-k;
  * the work is done in blocks so that 34,816 positions fit beside the weights:
    a layer is one jitted call that reads its weights out of the whole stacked
    tree in place, attends EIGHT HEADS at a time and 128 queries at a time
    against every key, upcasts an expert or a 2,304-column block of the dense MLP
    at a time, and the next layer waits for it.
"""

import functools

import jax
import jax.numpy as jnp

PRECISION = "highest"
QUERY_BLOCK = 128
HEAD_BLOCK = 8
COLUMN_BLOCK = 2304


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def causal_conv_silu(x, w):
    """Depthwise causal conv from zero inputs, then SiLU. x [s, C]; w [K, C]:
    ``w[j]`` multiplies the input ``K - 1 - j`` tokens back."""
    K, s = w.shape[0], x.shape[0]
    ext = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x], axis=0)
    return jax.nn.silu(sum(ext[j: j + s] * w[j] for j in range(K)))


def delta_rule(q, k, v, g, beta):
    """Token by token from a zero state. q, k, v, g [s, H, d]; beta [s, H].
    Returns o [s, H, d]."""
    H, d = q.shape[1:]

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = S * jnp.exp(g_t)[:, :, None]                       # a decay a KEY channel
        delta = (v_t - jnp.einsum("hkv,hk->hv", S, k_t)) * b_t[:, None]
        S = S + k_t[:, :, None] * delta[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    return jax.lax.scan(token, jnp.zeros((H, d, d), jnp.float32), (q, k, v, g, beta))[1]


def kda(x, lp, *, H, d, eps):
    """Kimi Delta Attention of one layer, no residual. x: [s, h] normed."""
    s = x.shape[0]
    q, k, v = (a.reshape(s, H, d) for a in jnp.split(
        causal_conv_silu(x @ lp["kda_qkv"], lp["kda_conv"]), 3, axis=-1))
    q, k = l2norm(q) * d ** -0.5, l2norm(k)
    f = ((x @ lp["kda_f_a"]) @ lp["kda_f_b"] + lp["kda_dt_bias"]).reshape(s, H, d)
    g = -jnp.exp(lp["kda_a_log"])[None, :, None] * jax.nn.softplus(f)
    beta = jax.nn.sigmoid(x @ lp["kda_b"])
    o = delta_rule(q, k, v, g, beta)
    gate = jax.nn.sigmoid((x @ lp["kda_g_a"]) @ lp["kda_g_b"]).reshape(s, H, d)
    return (rms_norm(o, lp["kda_norm"], eps) * gate).reshape(s, H * d) @ lp["kda_out"]


def attention(x, lp, *, nh, dn, dr, dv, rank, eps):
    """Latent attention of one layer in the expanded form, no rotary, no
    residual. x: [s, h] normed. A block of heads at a time, a block of queries
    at a time against every key."""
    s = x.shape[0]
    pos = jnp.arange(s)
    scale = (dn + dr) ** -0.5
    kv = x @ lp["wkv_a"]
    c, k_p = rms_norm(kv[:, :rank], lp["kv_a_norm"], eps), kv[:, rank:]
    hb = HEAD_BLOCK if nh % HEAD_BLOCK == 0 else nh
    qb = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    wq = lp["wq"].reshape(-1, nh // hb, hb, dn + dr).transpose(1, 0, 2, 3)
    wkv_b = lp["wkv_b"].reshape(rank, nh // hb, hb, dn + dv).transpose(1, 0, 2, 3)
    wo = lp["wo"].reshape(nh // hb, hb * dv, -1)

    def heads(out, w):
        wq_g, wkv, wo_g = w
        q = jnp.einsum("sh,hnd->snd", x, wq_g)                # [s, hb, dn + dr]
        kvh = jnp.einsum("sc,chd->shd", c, wkv)               # [s, hb, dn + dv]
        k_n, v = kvh[..., :dn], kvh[..., dn:]

        def block(args):
            q_b, pos_b = args
            scores = (jnp.einsum("qhd,jhd->hqj", q_b[..., :dn], k_n)
                      + jnp.einsum("qhd,jd->hqj", q_b[..., dn:], k_p)) * scale
            scores = jnp.where((pos_b[:, None] >= pos[None, :])[None], scores, -jnp.inf)
            return jnp.einsum("hqj,jhd->qhd", jax.nn.softmax(scores, axis=-1), v)

        o = jax.lax.map(block, (q.reshape(s // qb, qb, hb, dn + dr), pos.reshape(s // qb, qb)))
        return out + o.reshape(s, hb * dv) @ wo_g, None

    return jax.lax.scan(heads, jnp.zeros((s, wo.shape[-1]), jnp.float32), (wq, wkv_b, wo))[0]


def routing_weights(x, router, bias, *, top_k, scale):
    """[s, E_all]: the token's weight on each of its top-k experts: sigmoid
    scores, the k CHOSEN on ``score + bias`` and weighted by the score alone,
    renormalised, scaled."""
    scores = jax.nn.sigmoid(x @ router)
    top_e = jax.lax.top_k(scores + bias, top_k)[1]
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20) * scale
    return jnp.sum(jax.nn.one_hot(top_e, scores.shape[-1]) * top_s[..., None], axis=1)


def swiglu(x, wg, wu, wd):
    f32 = jnp.float32
    return (jax.nn.silu(x @ wg.astype(f32)) * (x @ wu.astype(f32))) @ wd.astype(f32)


def dense_mlp(x, lead, i):
    """The lead layer's SwiGLU, a block of its columns at a time, the weights
    read where they lie in the ``[lead layers, ...]`` stacks."""
    h, ffn = lead["w_gate"].shape[-2:]
    cb = COLUMN_BLOCK if ffn % COLUMN_BLOCK == 0 else ffn

    def one(acc, j):
        wg, wu = (jax.lax.dynamic_slice(lead[k], (i, 0, j * cb), (1, h, cb))[0]
                  for k in ("w_gate", "w_up"))
        wd = jax.lax.dynamic_slice(lead["w_down"], (i, j * cb, 0), (1, cb, h))[0]
        return acc + swiglu(x, wg, wu, wd), None

    return jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(ffn // cb))[0]


def sparse_mlp(x, moe, i, *, first, top_k, scale):
    """The expert block of sparse layer ``i`` (its index in the ``sparse``
    stacks): the held experts (numbers ``first`` on) on every token under the
    routing weights, and the shared expert as it is."""
    f32 = jnp.float32
    held = moe["w_gate"].shape[1]
    weights = routing_weights(x, moe["router"][i].astype(f32), moe["router_bias"][i].astype(f32),
                              top_k=top_k, scale=scale)[:, first: first + held]

    def one(acc, ew):
        e, w_e = ew
        wg, wu, wd = (moe[k][i, e] for k in ("w_gate", "w_up", "w_down"))
        return acc + w_e[:, None] * swiglu(x, wg, wu, wd), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (jnp.arange(held), weights.T))
    return out + swiglu(x, moe["shared_gate"][i], moe["shared_up"][i], moe["shared_down"][i])


_STATIC = ("kind", "sparse", "H", "d", "nh", "dn", "dr", "dv", "rank", "eps", "top_k",
           "route_scale", "first")


@functools.partial(jax.jit, static_argnames=_STATIC)
def layer(x, layers, i, ki, j, *, kind, sparse, H, d, nh, dn, dr, dv, rank, eps, top_k,
          route_scale, first):
    """Decoder layer ``i`` on one sequence, the ``ki``-th of its ``kind`` and
    the ``j``-th of its MLP kind. x: [s, h] float32; ``layers``: the whole
    stacked tree."""
    f32 = jnp.float32
    with jax.default_matmul_precision(PRECISION):
        a = rms_norm(x, layers["attn_norm"][i].astype(f32), eps)
        lp = {k: w[ki].astype(f32) for k, w in layers[kind].items()}
        if kind == "kda":
            x = x + kda(a, lp, H=H, d=d, eps=eps)
        else:
            x = x + attention(a, lp, nh=nh, dn=dn, dr=dr, dv=dv, rank=rank, eps=eps)
        m = rms_norm(x, layers["mlp_norm"][i].astype(f32), eps)
        if sparse:
            return x + sparse_mlp(m, layers["sparse"], j, first=first, top_k=top_k,
                                  scale=route_scale)
        return x + dense_mlp(m, layers["lead"], j)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, lm_head, *, eps):
    """Logits of the rows of x against the untied head. x: [n, h]."""
    with jax.default_matmul_precision(PRECISION):
        return rms_norm(x, final_norm.astype(jnp.float32), eps) @ lm_head.astype(jnp.float32)


def layer_kinds(hf):
    """("kda" | "full") a layer, from the two lists numbered from 1."""
    n, lin = int(hf["num_hidden_layers"]), hf["linear_attn_config"]
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    if kda | full != set(range(1, n + 1)) or kda & full:
        raise ValueError("linear_attn_config's two lists do not partition the layers")
    return tuple("kda" if i + 1 in kda else "full" for i in range(n))


def hidden(params, tokens, hf):
    """Last-layer residual stream of one sequence, [s, h] float32."""
    if hf.get("model_type") != "kimi_linear":
        raise ValueError(f"this reference is Kimi Linear's, not {hf.get('model_type')!r}'s")
    if (hf.get("tie_word_embeddings") or not hf.get("moe_renormalize", True)
            or hf.get("moe_router_activation_func") != "sigmoid" or hf.get("q_lora_rank")
            or not hf.get("mla_use_nope") or int(hf.get("num_expert_group", 1)) != 1
            or int(hf.get("moe_layer_freq", 1)) != 1):
        raise ValueError("this reference is of the published Kimi Linear: untied head, one query "
                         "projection and no rotary in the latent layers, a sigmoid router in one "
                         "group with top-k renormalised, experts in every layer behind the lead")
    share = hf.get("deployment_share") or {}
    lin = hf["linear_attn_config"]
    kw = dict(
        H=int(lin["num_heads"]), d=int(lin["head_dim"]),
        nh=int(hf["num_attention_heads"]), dn=int(hf["qk_nope_head_dim"]),
        dr=int(hf["qk_rope_head_dim"]), dv=int(hf["v_head_dim"]), rank=int(hf["kv_lora_rank"]),
        eps=float(hf["rms_norm_eps"]), top_k=int(hf["num_experts_per_token"]),
        route_scale=float(hf["routed_scaling_factor"]),
        first=int(share.get("share_index", 0)) * int(hf["num_experts"]),
    )
    lead = int(hf["first_k_dense_replace"])
    kinds = layer_kinds(hf)
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for i, kind in enumerate(kinds):
        x = layer(x, params["layers"], i, kinds[:i].count(kind), i - lead if i >= lead else i,
                  kind=kind, sparse=i >= lead, **kw)
        x.block_until_ready()   # a layer's temporaries go before the next one's come
    return x


def logits(params, tokens, hf, rows=None):
    """[len(rows) or s, vocab] float32 logits of one sequence; ``rows`` picks
    the positions whose logits are wanted."""
    x = hidden(params, tokens, hf)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return head(x, params["final_norm"], params["lm_head"], eps=float(hf["rms_norm_eps"]))
