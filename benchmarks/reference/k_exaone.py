"""K-EXAONE (``model_type: exaone_moe``) in plain ``jax.numpy``: the reference
the benchmark holds the system to. float32 throughout,
``jax.default_matmul_precision("highest")``, no kernels, no cache, no sorting,
no batching: a full-sequence forward of one sequence. The expert block is the
published ``DeepseekV3MoE`` / ``DeepseekV3TopkRouter`` (transformers 4.57
``modeling_deepseek_v3.py``, whose key names ``config.json`` carries) with one
group; what ``config.json`` has no key for is the family's hybrid model's
(``modeling_exaone4.py``: ``Exaone4DecoderLayer``, ``Exaone4Attention``).

  x      = E[tokens]
  layer l (type_l from layer_types, mlp_l from mlp_layer_types):
           q, k, v = x Wq, x Wk, x Wv                 (no bias, no norm in front)
           q, k = rms(q) w_q, rms(k) w_k              over the head's 128
           sliding_attention: q, k = RoPE(q), RoPE(k) (all dims, rotate-half)
           full_attention:    no position term
           scores q k^T / sqrt(d), causal; sliding: key j to query i iff
           0 <= i - j < sliding_window
           x += rms(softmax(scores) v Wo) w_attn      (the norm on the OUTPUT)
           dense:  m = (silu(x Wg) * (x Wu)) Wd       (it reads the stream itself)
           sparse: s = sigmoid(x Wr) (float32, over ALL experts)
                   top = the k largest of s + b       (b chooses, and only chooses)
                   w = s[top] / (sum s[top] + 1e-20) * routed_scaling_factor
                   m = sum_{e in top, e held here} w_e E_e(x) + S(x)
           x += rms(m) w_mlp
  logits = rms(x) w_f W_head                          (untied head)

It reads the system's parameter tree (``deepspeed_tpu.models.init_params``
layout: norms and attention stacked on [n_layers], the lead layers' MLP under
``layers["lead"]``, the expert block under ``layers["sparse"]`` on the layers
behind them, projections stored [in, out]) and the configuration file's Hugging
Face keys, and nothing else of the program.

Departures from the published code, none in the mathematics:
  * the chip's SHARE: where the configuration holds fewer experts than the
    router is wide (``deployment_share``), the pairs of experts held elsewhere
    are dropped from the sum, as the program drops them; the router, its top-k
    and the renormalisation are over all of them. ``num_hidden_layers`` below
    the lists' length is a pipeline stage: the lists' head;
  * ``num_shared_experts`` shared experts are one MLP of their summed width
    (as the published block builds them);
  * the multi-token-prediction layer is no part of the model's own logits and
    none here;
  * every held expert is applied to every token and masked by the top-k;
  * the work is done in blocks so that a sequence of ~10k tokens fits beside
    12 GB of bf16 weights: a layer is one jitted call that reads its weights
    out of the whole stacked tree in place (no copy of a layer is made),
    upcasts an expert, a 2,048-column block of the dense MLP or an attention
    matrix at a time, scores 128 queries at a time against every key, and the
    next layer waits for it.
"""

import functools

import jax
import jax.numpy as jnp

PRECISION = "highest"
QUERY_BLOCK = 128
COLUMN_BLOCK = 2048


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, positions, theta):
    """x: [s, heads, d]; rotate-half over all d dims."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x, lp, *, nh, nkv, eps, theta, window, rotary):
    """softmax(q k^T / sqrt(d)) v Wo of one layer, no residual, no output
    norm. x: [s, h]. ``window`` 0: every earlier key; else the last
    ``window``. Scores a block of queries at a time."""
    s = x.shape[0]
    d = lp["wq"].shape[-1] // nh
    g = nh // nkv
    pos = jnp.arange(s)
    q = rms_norm((x @ lp["wq"]).reshape(s, nh, d), lp["q_norm"], eps)
    k = rms_norm((x @ lp["wk"]).reshape(s, nkv, d), lp["k_norm"], eps)
    v = (x @ lp["wv"]).reshape(s, nkv, d)
    if rotary:
        q, k = rope(q, pos, theta), rope(k, pos, theta)
    qb = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    def block(args):
        q_b, pos_b = args                                    # [qb, nkv, g, d], [qb]
        scores = jnp.einsum("qkgd,jkd->kgqj", q_b, k) / jnp.sqrt(jnp.float32(d))
        dist = pos_b[:, None] - pos[None, :]                 # query i - key j
        seen = (dist >= 0) & ((dist < window) if window else True)
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("kgqj,jkd->qkgd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(block, (q.reshape(s // qb, qb, nkv, g, d), pos.reshape(s // qb, qb)))
    return out.reshape(s, nh * d) @ lp["wo"]


def routing_weights(x, router, bias, top_k, scale):
    """[s, E_all]: the token's weight on each of its top-k experts: chosen on
    ``sigmoid + bias``, weighted by the sigmoid alone, renormalised, scaled."""
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


def sparse_mlp(x, moe, i, *, top_k, scale, first):
    """The expert block of sparse layer ``i`` (its index in the ``sparse``
    stacks): the held experts (numbers ``first`` on) on every token under the
    routing weights, and the shared expert as it is."""
    f32 = jnp.float32
    held = moe["w_gate"].shape[1]
    weights = routing_weights(
        x, moe["router"][i].astype(f32), moe["router_bias"][i].astype(f32), top_k, scale
    )[:, first: first + held]

    def one(acc, ew):
        e, w_e = ew
        wg, wu, wd = (moe[k][i, e] for k in ("w_gate", "w_up", "w_down"))
        return acc + w_e[:, None] * swiglu(x, wg, wu, wd), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (jnp.arange(held), weights.T))
    return out + swiglu(x, moe["shared_gate"][i], moe["shared_up"][i], moe["shared_down"][i])


_STATIC = ("sparse", "nh", "nkv", "eps", "theta", "window", "rotary", "top_k", "scale", "first")


@functools.partial(jax.jit, static_argnames=_STATIC)
def layer(x, layers, i, j, *, sparse, nh, nkv, eps, theta, window, rotary, top_k, scale, first):
    """Decoder layer ``i`` on one sequence, the ``j``-th of its MLP kind.
    x: [s, h] float32; ``layers``: the whole stacked tree."""
    f32 = jnp.float32
    with jax.default_matmul_precision(PRECISION):
        lp = {k: layers[k][i].astype(f32)
              for k in ("wq", "wk", "wv", "wo", "q_norm", "k_norm", "attn_norm", "mlp_norm")}
        attn = attention(x, lp, nh=nh, nkv=nkv, eps=eps, theta=theta, window=window, rotary=rotary)
        x = x + rms_norm(attn, lp["attn_norm"], eps)
        if sparse:
            m = sparse_mlp(x, layers["sparse"], j, top_k=top_k, scale=scale, first=first)
        else:
            m = dense_mlp(x, layers["lead"], j)
        return x + rms_norm(m, lp["mlp_norm"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, lm_head, *, eps):
    """Logits of the rows of x against the untied head. x: [n, h]."""
    with jax.default_matmul_precision(PRECISION):
        return rms_norm(x, final_norm.astype(jnp.float32), eps) @ lm_head.astype(jnp.float32)


def layer_plan(hf):
    """[(attention type, MLP type)] of the layers held: the head of the
    published lists (a pipeline stage's layers)."""
    n = int(hf["num_hidden_layers"])
    kinds, mlps = list(hf["layer_types"])[:n], list(hf["mlp_layer_types"])[:n]
    if len(kinds) != n or len(mlps) != n:
        raise ValueError(f"layer_types / mlp_layer_types are shorter than {n} layers")
    return list(zip(kinds, mlps))


def hidden(params, tokens, hf):
    """Last-layer residual stream of one sequence, [s, h] float32."""
    if hf.get("model_type") != "exaone_moe":
        raise ValueError(f"this reference is K-EXAONE's, not {hf.get('model_type')!r}'s")
    if (hf.get("tie_word_embeddings") or not hf.get("norm_topk_prob", True)
            or hf.get("scoring_func") != "sigmoid"
            or int(hf.get("n_group", 1)) != 1 or int(hf.get("topk_group", 1)) != 1):
        raise ValueError("this reference is of the published K-EXAONE: untied head, a sigmoid "
                         "router with one group, top-k renormalised")
    share = hf.get("deployment_share") or {}
    kw = dict(
        nh=int(hf["num_attention_heads"]), nkv=int(hf["num_key_value_heads"]),
        eps=float(hf["rms_norm_eps"]), theta=float(hf["rope_parameters"]["rope_theta"]),
        top_k=int(hf["num_experts_per_tok"]), scale=float(hf["routed_scaling_factor"]),
        first=int(share.get("share_index", 0)) * int(hf["num_experts"]),
    )
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    seen = {"dense": 0, "sparse": 0}
    for i, (kind, mlp) in enumerate(layer_plan(hf)):
        sliding = kind == "sliding_attention"
        x = layer(x, params["layers"], i, seen[mlp], sparse=mlp == "sparse",
                  window=int(hf["sliding_window"]) if sliding else 0, rotary=sliding, **kw)
        x.block_until_ready()   # a layer's temporaries go before the next one's come
        seen[mlp] += 1
    return x


def logits(params, tokens, hf, rows=None):
    """[len(rows) or s, vocab] float32 logits of one sequence; ``rows`` picks
    the positions whose logits are wanted."""
    x = hidden(params, tokens, hf)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return head(x, params["final_norm"], params["lm_head"], eps=float(hf["rms_norm_eps"]))
