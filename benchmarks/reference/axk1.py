"""A.X-K1 (``model_type: axk1``) in plain ``jax.numpy``: the reference the
benchmark holds the system to. float32 throughout,
``jax.default_matmul_precision("highest")``, no kernels, no cache, no sorting, no
batching: a full-sequence forward of one sequence, latent attention in the
EXPANDED form only (every head's keys and values made of the latent; the served
program attends in the absorbed form, the same numbers with ``W_UK`` / ``W_UV``
moved to the query and the output side). The block is DeepseekV3's (transformers
``modeling_deepseek_v3.py``: ``DeepseekV3Attention``, ``DeepseekV3TopkRouter``,
``DeepseekV3MoE``), whose key names ``config.json`` carries.

  x      = E[tokens]
  layer l: a = rms(x) w_attn
           c_q = rms(a W_qa) w_qa                       (q_lora_rank)
           q = c_q W_qb -> heads of (qk_nope | qk_rope)
           [c | k_r] = a W_kva ; c = rms(c) w_kva       (kv_lora_rank | qk_rope)
           q_rope, k_r = RoPE(q_rope), RoPE(k_r)        (YaRN frequencies, pairs
                                                         (2i, 2i+1) together, k_r
                                                         shared by every head)
           [k_nope | v] = c W_kvb -> heads of (qk_nope | v_head)
           scores (q_nope . k_nope + q_rope . k_r) x (qk_nope + qk_rope)^-0.5 x m^2,
           m = 0.1 mscale_all_dim ln(factor) + 1; causal softmax
           x += (softmax v) W_o
           m = rms(x) w_mlp
           layer < first_k_dense_replace: x += (silu(m Wg) * (m Wu)) Wd
           else: s = sigmoid(m Wr) (float32, over ALL experts)
                 a group's score: the LARGEST s among its experts (no selection
                 bias: topk_method "none"; with a bias, the sum of the two
                 largest s + b); keep the topk_group best groups
                 top = the k largest s (+ b) inside them
                 w = s[top] / (sum s[top] + 1e-20) * routed_scaling_factor
                 x += sum_{e in top, e held here} w_e E_e(m) + S(m)
  logits = rms(x) w_f W_head                            (untied head)

It reads the system's parameter tree (``deepspeed_tpu.models.init_params``
layout: norms and attention stacked on [n_layers], the lead layers' MLP under
``layers["lead"]``, the expert block under ``layers["sparse"]``, projections
stored [in, out]) and the configuration file's Hugging Face keys, and nothing
else of the program.

Departures from the published code, none in the mathematics:
  * the chip's SHARE: where the configuration holds fewer experts than the
    router is wide (``deployment_share``), the pairs of experts held elsewhere
    are dropped from the sum, as the program drops them; the router, its groups,
    its top-k and the renormalisation are over all of them. ``num_hidden_layers``
    is a pipeline stage's: the stack's head;
  * an expert outside the kept groups is out of the choice (-inf) where the
    published code writes a score of 0.0: the same unless fewer than k kept
    scores are positive, which a sigmoid never gives;
  * ``n_shared_experts`` shared experts are one MLP of their summed width;
  * ``seq_aux`` is a training loss and none here;
  * every held expert is applied to every token and masked by the top-k;
  * the work is done in blocks so that 14,336 positions fit beside 11 GB of bf16
    weights: a layer is one jitted call that reads its weights out of the whole
    stacked tree in place, attends EIGHT HEADS at a time (their expanded keys and
    values are 0.5 GB in float32; all 64 would be 3.8 GB) and 128 queries at a
    time against every key, upcasts an expert or a 2,048-column block of the
    dense MLP at a time, and the next layer waits for it.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = "highest"
QUERY_BLOCK = 128
HEAD_BLOCK = 8
COLUMN_BLOCK = 2048


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def yarn_inv_freq(dim, theta, scaling):
    """Rotary frequencies [dim / 2] under ``rope_scaling`` of type "yarn"
    (transformers ``_compute_yarn_parameters``): each frequency between its own
    and its ``1 / factor``, by how many turns it makes over the original context;
    the cos / sin factor ``mscale / mscale_all_dim`` comes back beside them."""
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return base.astype(np.float32), 1.0
    if scaling.get("rope_type", scaling.get("type")) != "yarn":
        raise ValueError(f"this reference knows YaRN rotary only, not {scaling!r}")
    factor, orig = float(scaling["factor"]), float(scaling["original_max_position_embeddings"])

    def turns_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(turns_dim(float(scaling.get("beta_fast", 32)))), 0)
    high = min(math.ceil(turns_dim(float(scaling.get("beta_slow", 1)))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001), 0, 1)
    inv = base / factor * ramp + base * (1 - ramp)

    def mscale(m):
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0

    m, m_all = scaling.get("mscale"), scaling.get("mscale_all_dim")
    return inv.astype(np.float32), (mscale(m) / mscale(m_all) if m and m_all else mscale(1.0))


def rope_interleaved(x, positions, inv_freq, factor):
    """x: [s, heads, d]; dims (2i, 2i + 1) turn together by ``positions x
    inv_freq[i]`` (DeepseekV3's ``apply_rotary_pos_emb_interleave``)."""
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :] * factor, jnp.sin(ang)[:, None, :] * factor
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def softmax_scale(hf):
    """(qk_nope + qk_rope)^-0.5, times mscale^2 under YaRN with ``mscale_all_dim``."""
    scale = (int(hf["qk_nope_head_dim"]) + int(hf["qk_rope_head_dim"])) ** -0.5
    scaling = hf.get("rope_scaling") or {}
    if scaling.get("mscale_all_dim") and float(scaling.get("factor", 1.0)) > 1:
        m = 0.1 * float(scaling["mscale_all_dim"]) * math.log(float(scaling["factor"])) + 1.0
        scale *= m * m
    return scale


def attention(x, lp, *, nh, dn, dr, dv, rank, eps, inv_freq, rope_factor, scale):
    """Latent attention of one layer in the expanded form, no residual. x: [s,
    h] normed. A block of heads at a time, a block of queries at a time."""
    s = x.shape[0]
    pos = jnp.arange(s)
    cq = rms_norm(x @ lp["wq_a"], lp["q_a_norm"], eps)
    kv = x @ lp["wkv_a"]
    c = rms_norm(kv[:, :rank], lp["kv_a_norm"], eps)
    k_r = rope_interleaved(kv[:, None, rank:], pos, inv_freq, rope_factor)  # [s, 1, dr]
    hb = HEAD_BLOCK if nh % HEAD_BLOCK == 0 else nh
    qb = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    wq_b = lp["wq_b"].reshape(-1, nh // hb, hb, dn + dr).transpose(1, 0, 2, 3)
    wkv_b = lp["wkv_b"].reshape(rank, nh // hb, hb, dn + dv).transpose(1, 0, 2, 3)
    wo = lp["wo"].reshape(nh // hb, hb * dv, -1)

    def heads(out, w):
        wq, wkv, wo_g = w
        q = jnp.einsum("sr,rhd->shd", cq, wq)                 # [s, hb, dn + dr]
        q_rope = rope_interleaved(q[..., dn:], pos, inv_freq, rope_factor)
        kvh = jnp.einsum("sc,chd->shd", c, wkv)               # [s, hb, dn + dv]
        k_nope, v = kvh[..., :dn], kvh[..., dn:]

        def block(args):
            qn_b, qr_b, pos_b = args
            scores = (jnp.einsum("qhd,jhd->hqj", qn_b, k_nope)
                      + jnp.einsum("qhd,jd->hqj", qr_b, k_r[:, 0])) * scale
            scores = jnp.where((pos_b[:, None] >= pos[None, :])[None], scores, -jnp.inf)
            return jnp.einsum("hqj,jhd->qhd", jax.nn.softmax(scores, axis=-1), v)

        o = jax.lax.map(block, (q[..., :dn].reshape(s // qb, qb, hb, dn),
                                q_rope.reshape(s // qb, qb, hb, dr), pos.reshape(s // qb, qb)))
        return out + o.reshape(s, hb * dv) @ wo_g, None

    return jax.lax.scan(heads, jnp.zeros((s, wo.shape[-1]), jnp.float32), (wq_b, wkv_b, wo))[0]


def routing_weights(x, router, bias, *, top_k, scale, n_group, topk_group):
    """[s, E_all]: the token's weight on each of its top-k experts: sigmoid
    scores, the best ``topk_group`` of ``n_group`` groups kept (a group scores
    its largest member; with a selection ``bias`` the sum of its two largest
    ``score + bias``), the k chosen inside them on ``score (+ bias)`` and
    weighted by the score alone, renormalised, scaled."""
    scores = jax.nn.sigmoid(x @ router)
    choose = scores if bias is None else scores + bias
    t, E = scores.shape
    by_group = choose.reshape(t, n_group, E // n_group)
    if bias is None:
        group_score = by_group.max(axis=-1)
    else:
        group_score = jax.lax.top_k(by_group, 2)[0].sum(axis=-1)
    best = jax.lax.top_k(group_score, topk_group)[1]
    kept = jnp.sum(jax.nn.one_hot(best, n_group), axis=1) > 0
    choose = jnp.where(jnp.repeat(kept, E // n_group, axis=1), choose, -jnp.inf)
    top_e = jax.lax.top_k(choose, top_k)[1]
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20) * scale
    return jnp.sum(jax.nn.one_hot(top_e, E) * top_s[..., None], axis=1)


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


def sparse_mlp(x, moe, i, *, first, **route):
    """The expert block of sparse layer ``i`` (its index in the ``sparse``
    stacks): the held experts (numbers ``first`` on) on every token under the
    routing weights, and the shared expert as it is."""
    f32 = jnp.float32
    held = moe["w_gate"].shape[1]
    bias = moe["router_bias"][i].astype(f32) if "router_bias" in moe else None
    weights = routing_weights(x, moe["router"][i].astype(f32), bias, **route
                              )[:, first: first + held]

    def one(acc, ew):
        e, w_e = ew
        wg, wu, wd = (moe[k][i, e] for k in ("w_gate", "w_up", "w_down"))
        return acc + w_e[:, None] * swiglu(x, wg, wu, wd), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (jnp.arange(held), weights.T))
    return out + swiglu(x, moe["shared_gate"][i], moe["shared_up"][i], moe["shared_down"][i])


_ATTN = ("nh", "dn", "dr", "dv", "rank", "eps", "rope_factor", "scale")
_ROUTE = ("top_k", "route_scale", "n_group", "topk_group", "first")


@functools.partial(jax.jit, static_argnames=("sparse",) + _ATTN + _ROUTE)
def layer(x, layers, inv_freq, i, j, *, sparse, top_k, route_scale, n_group, topk_group, first,
          **attn):
    """Decoder layer ``i`` on one sequence, the ``j``-th of its MLP kind.
    x: [s, h] float32; ``layers``: the whole stacked tree."""
    f32 = jnp.float32
    eps = attn["eps"]
    with jax.default_matmul_precision(PRECISION):
        lp = {k: layers[k][i].astype(f32) for k in (
            "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b", "wo", "attn_norm",
            "mlp_norm")}
        x = x + attention(rms_norm(x, lp["attn_norm"], eps), lp, inv_freq=inv_freq, **attn)
        m = rms_norm(x, lp["mlp_norm"], eps)
        if sparse:
            return x + sparse_mlp(m, layers["sparse"], j, first=first, top_k=top_k,
                                  scale=route_scale, n_group=n_group, topk_group=topk_group)
        return x + dense_mlp(m, layers["lead"], j)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, lm_head, *, eps):
    """Logits of the rows of x against the untied head. x: [n, h]."""
    with jax.default_matmul_precision(PRECISION):
        return rms_norm(x, final_norm.astype(jnp.float32), eps) @ lm_head.astype(jnp.float32)


def hidden(params, tokens, hf):
    """Last-layer residual stream of one sequence, [s, h] float32."""
    if hf.get("model_type") != "axk1":
        raise ValueError(f"this reference is A.X-K1's, not {hf.get('model_type')!r}'s")
    if (hf.get("tie_word_embeddings") or not hf.get("norm_topk_prob", True)
            or hf.get("scoring_func") != "sigmoid" or hf.get("attention_bias")
            or int(hf.get("moe_layer_freq", 1)) != 1):
        raise ValueError("this reference is of the published A.X-K1: untied head, no attention "
                         "bias, a sigmoid router with top-k renormalised, experts in every "
                         "layer behind the lead ones")
    share = hf.get("deployment_share") or {}
    dr = int(hf["qk_rope_head_dim"])
    inv_freq, rope_factor = yarn_inv_freq(dr, float(hf["rope_theta"]), hf.get("rope_scaling"))
    kw = dict(
        nh=int(hf["num_attention_heads"]), dn=int(hf["qk_nope_head_dim"]), dr=dr,
        dv=int(hf["v_head_dim"]), rank=int(hf["kv_lora_rank"]), eps=float(hf["rms_norm_eps"]),
        rope_factor=float(rope_factor), scale=float(softmax_scale(hf)),
        top_k=int(hf["num_experts_per_tok"]), route_scale=float(hf["routed_scaling_factor"]),
        n_group=int(hf.get("n_group", 1)), topk_group=int(hf.get("topk_group", 1)),
        first=int(share.get("share_index", 0)) * int(hf["n_routed_experts"]),
    )
    lead = int(hf["first_k_dense_replace"])
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(int(hf["num_hidden_layers"])):
        x = layer(x, params["layers"], jnp.asarray(inv_freq), i, i - lead if i >= lead else i,
                  sparse=i >= lead, **kw)
        x.block_until_ready()   # a layer's temporaries go before the next one's come
    return x


def logits(params, tokens, hf, rows=None):
    """[len(rows) or s, vocab] float32 logits of one sequence; ``rows`` picks
    the positions whose logits are wanted."""
    x = hidden(params, tokens, hf)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return head(x, params["final_norm"], params["lm_head"], eps=float(hf["rms_norm_eps"]))
