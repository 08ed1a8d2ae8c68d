"""LongCat-Flash (``model_type: longcat_flash``) in plain ``jax.numpy``: the
reference the benchmark holds the system to. float32 throughout,
``jax.default_matmul_precision("highest")``, no kernels, no cache, no sorting, no
batching: a full-sequence forward of one sequence, latent attention in the
EXPANDED form only (every head's keys and values made of the latent; the served
program attends in the absorbed form). Written from the published equations
(transformers ``LongcatFlashDecoderLayer``, ``LongcatFlashMLA``,
``LongcatFlashTopkRouter``, ``LongcatFlashMoE``), whose key names ``config.json``
carries:

  x = E[tokens]
  layer l, with N_i^a, N_i^m RMSNorms, A_i latent attentions, D_i SwiGLU MLPs
  of ffn_hidden_size (i = 0, 1) and E the expert block:
      h  = x  + A_0(N_0^a(x))
      m  = N_0^m(h)
      s  = E(m)                      the shortcut: not added here
      h  = h  + D_0(m)
      h' = h  + A_1(N_1^a(h))
      x' = h' + D_1(N_1^m(h')) + s   the expert block joins behind the SECOND MLP
  A_i(a): (these two inner norms at eps 1e-6, LongcatFlashRMSNorm's default: the
          published MLA does not hand them rms_norm_eps)
          c_q = rms(a W_qa) w_qa ; q = c_q W_qb x sqrt(hidden / q_lora_rank)
          -> heads of (qk_nope | qk_rope)
          [c | k_r] = a W_kva ; c = rms(c) w_kva x sqrt(hidden / kv_lora_rank)
          (k_r is NOT scaled) ; [k_nope | v] = c W_kvb -> heads of (qk_nope | v)
          rotary (theta, no scaling, pairs (2i, 2i+1) together) on q_rope and
          the one shared k_r ; scores x (qk_nope + qk_rope)^-0.5, causal softmax
          out = (softmax v) W_o
  E(m):   p = softmax(m W_r) in float32 over n_routed_experts + zero_expert_num
          top = the moe_topk ids with the largest p + b (b: e_score_correction_bias)
          g_j = routed_scaling_factor x p_j   (no renormalisation)
          E(m) = sum_{j in top} g_j f_j(m), f_j the SwiGLU expert j of
          expert_ffn_hidden_size for j < n_routed_experts, and f_j(m) = m behind
  logits = rms(x) w_f W_head         (untied head)

It reads the system's parameter tree (``deepspeed_tpu.models.init_params``
layout: the expert block stacked on [num_layers], what a sub-block has under
``layers["sub"]`` on [2 num_layers] with sub-block i of layer l at 2 l + i,
projections stored [in, out]) and the configuration file's Hugging Face keys,
and nothing else of the program.

Departures from the published code, none in the mathematics:
  * the chip's SHARE: where the configuration holds fewer experts than the
    router has (``deployment_share``), the pairs of experts held elsewhere are
    dropped from the sum, as the program drops them; the router, its top-k and
    its weights are over all ids. The identity pairs are every chip's and stay.
    ``num_layers`` is a pipeline stage's: the stack's head;
  * every held expert is applied to every token and masked by the top-k;
  * the work is done in blocks so that 14,336 positions fit beside 10 GB of bf16
    weights: a layer is one jitted call that reads its weights out of the whole
    stacked tree in place, attends EIGHT HEADS at a time and 128 queries at a
    time against every key, upcasts an expert or a 2,048-column block of a dense
    MLP at a time, and the next layer waits for it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = "highest"
INNER_NORM_EPS = 1e-6  # q_a_layernorm / kv_a_layernorm: LongcatFlashRMSNorm(rank), eps left at its default
QUERY_BLOCK = 128
HEAD_BLOCK = 8
COLUMN_BLOCK = 2048
SUB_KEYS = ("wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b", "wo", "attn_norm",
            "mlp_norm")


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope_interleaved(x, positions, inv_freq):
    """x: [s, heads, d]; dims (2i, 2i + 1) turn together by ``positions x
    inv_freq[i]`` (``apply_rotary_pos_emb_interleave``)."""
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def attention(x, lp, *, nh, dn, dr, dv, rank, eps, inv_freq, q_scale, kv_scale):
    """Latent attention of one sub-block in the expanded form, no residual.
    x: [s, h] normed. A block of heads at a time, a block of queries at a time."""
    s = x.shape[0]
    pos = jnp.arange(s)
    del eps  # the layer norms'; the two norms in here have their own
    cq = rms_norm(x @ lp["wq_a"], lp["q_a_norm"], INNER_NORM_EPS)
    kv = x @ lp["wkv_a"]
    c = rms_norm(kv[:, :rank], lp["kv_a_norm"], INNER_NORM_EPS) * kv_scale
    k_r = rope_interleaved(kv[:, None, rank:], pos, inv_freq)[:, 0]           # [s, dr]
    hb = HEAD_BLOCK if nh % HEAD_BLOCK == 0 else nh
    qb = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    wq_b = lp["wq_b"].reshape(-1, nh // hb, hb, dn + dr).transpose(1, 0, 2, 3)
    wkv_b = lp["wkv_b"].reshape(rank, nh // hb, hb, dn + dv).transpose(1, 0, 2, 3)
    wo = lp["wo"].reshape(nh // hb, hb * dv, -1)
    scale = (dn + dr) ** -0.5

    def heads(out, w):
        wq, wkv, wo_g = w
        q = jnp.einsum("sr,rhd->shd", cq, wq) * q_scale          # [s, hb, dn + dr]
        q_rope = rope_interleaved(q[..., dn:], pos, inv_freq)
        kvh = jnp.einsum("sc,chd->shd", c, wkv)                  # [s, hb, dn + dv]
        k_nope, v = kvh[..., :dn], kvh[..., dn:]

        def block(args):
            qn_b, qr_b, pos_b = args
            scores = (jnp.einsum("qhd,jhd->hqj", qn_b, k_nope)
                      + jnp.einsum("qhd,jd->hqj", qr_b, k_r)) * scale
            scores = jnp.where((pos_b[:, None] >= pos[None, :])[None], scores, -jnp.inf)
            return jnp.einsum("hqj,jhd->qhd", jax.nn.softmax(scores, axis=-1), v)

        o = jax.lax.map(block, (q[..., :dn].reshape(s // qb, qb, hb, dn),
                                q_rope.reshape(s // qb, qb, hb, dr), pos.reshape(s // qb, qb)))
        return out + o.reshape(s, hb * dv) @ wo_g, None

    return jax.lax.scan(heads, jnp.zeros((s, wo.shape[-1]), jnp.float32), (wq_b, wkv_b, wo))[0]


def routing_weights(x, router, bias, *, top_k, scale):
    """[s, ids]: the token's gate on each of its top-k ids: softmax over every
    id, the k chosen on ``probability + bias``, weighted by the probability
    alone, not renormalised, scaled."""
    p = jax.nn.softmax(x @ router, axis=-1)
    top = jax.lax.top_k(p + bias, top_k)[1]
    gates = jnp.take_along_axis(p, top, axis=-1) * scale
    return jnp.sum(jax.nn.one_hot(top, p.shape[-1]) * gates[..., None], axis=1)


def swiglu(x, wg, wu, wd):
    f32 = jnp.float32
    return (jax.nn.silu(x @ wg.astype(f32)) * (x @ wu.astype(f32))) @ wd.astype(f32)


def dense_mlp(x, sub, j):
    """Sub-block ``j``'s SwiGLU, a block of its columns at a time, the weights
    read where they lie in the ``[sub-blocks, ...]`` stacks."""
    h, ffn = sub["w_gate"].shape[-2:]
    cb = COLUMN_BLOCK if ffn % COLUMN_BLOCK == 0 else ffn

    def one(acc, b):
        wg, wu = (jax.lax.dynamic_slice(sub[k], (j, 0, b * cb), (1, h, cb))[0]
                  for k in ("w_gate", "w_up"))
        wd = jax.lax.dynamic_slice(sub["w_down"], (j, b * cb, 0), (1, cb, h))[0]
        return acc + swiglu(x, wg, wu, wd), None

    return jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(ffn // cb))[0]


def expert_block(x, layers, i, *, first, total, top_k, scale):
    """The expert block of layer ``i`` on ``x`` [s, h]: the held experts
    (numbers ``first`` on) on every token under their gates, and the identity
    ids (``total`` on) as the sum of their gates times ``x``."""
    f32 = jnp.float32
    held = layers["w_gate"].shape[1]
    gates = routing_weights(x, layers["router"][i].astype(f32),
                            layers["router_bias"][i].astype(f32), top_k=top_k, scale=scale)

    def one(acc, ew):
        e, g_e = ew
        wg, wu, wd = (layers[k][i, e] for k in ("w_gate", "w_up", "w_down"))
        return acc + g_e[:, None] * swiglu(x, wg, wu, wd), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (jnp.arange(held), gates[:, first: first + held].T))
    return out + jnp.sum(gates[:, total:], axis=-1, keepdims=True) * x


_ATTN = ("nh", "dn", "dr", "dv", "rank", "eps", "q_scale", "kv_scale")
_ROUTE = ("top_k", "route_scale", "first", "total")


@functools.partial(jax.jit, static_argnames=_ATTN + _ROUTE)
def layer(x, layers, inv_freq, i, *, top_k, route_scale, first, total, **attn):
    """Decoder layer ``i`` on one sequence. x: [s, h] float32; ``layers``: the
    whole stacked tree."""
    f32 = jnp.float32
    eps = attn["eps"]
    with jax.default_matmul_precision(PRECISION):
        shortcut = None
        for b in range(2):
            j = 2 * i + b
            sp = {k: layers["sub"][k][j].astype(f32) for k in SUB_KEYS}
            x = x + attention(rms_norm(x, sp["attn_norm"], eps), sp, inv_freq=inv_freq, **attn)
            m = rms_norm(x, sp["mlp_norm"], eps)
            if b == 0:
                shortcut = expert_block(m, layers, i, first=first, total=total, top_k=top_k,
                                        scale=route_scale)
            x = x + dense_mlp(m, layers["sub"], j)
        return x + shortcut


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, lm_head, *, eps):
    """Logits of the rows of x against the untied head. x: [n, h]."""
    with jax.default_matmul_precision(PRECISION):
        return rms_norm(x, final_norm.astype(jnp.float32), eps) @ lm_head.astype(jnp.float32)


def hidden(params, tokens, hf):
    """Last-layer residual stream of one sequence, [s, h] float32."""
    if hf.get("model_type") != "longcat_flash":
        raise ValueError(f"this reference is LongCat-Flash's, not {hf.get('model_type')!r}'s")
    if (hf.get("tie_word_embeddings") or hf.get("norm_topk_prob") or hf.get("attention_bias")
            or hf.get("rope_scaling") or hf.get("zero_expert_type", "identity") != "identity"):
        raise ValueError("this reference is of the published LongCat-Flash: untied head, no "
                         "attention bias, plain rotary, a softmax router whose top-k is not "
                         "renormalised, identity zero experts")
    share = hf.get("deployment_share") or {}
    h, dr = int(hf["hidden_size"]), int(hf["qk_rope_head_dim"])
    held = int(hf["n_routed_experts"])
    inv_freq = (1.0 / float(hf["rope_theta"]) ** (np.arange(0, dr, 2, dtype=np.float64) / dr)
                ).astype(np.float32)
    kw = dict(
        nh=int(hf["num_attention_heads"]), dn=int(hf["qk_nope_head_dim"]), dr=dr,
        dv=int(hf["v_head_dim"]), rank=int(hf["kv_lora_rank"]), eps=float(hf["rms_norm_eps"]),
        q_scale=float((h / int(hf["q_lora_rank"])) ** 0.5) if hf.get("mla_scale_q_lora") else 1.0,
        kv_scale=float((h / int(hf["kv_lora_rank"])) ** 0.5) if hf.get("mla_scale_kv_lora") else 1.0,
        top_k=int(hf["moe_topk"]), route_scale=float(hf.get("routed_scaling_factor", 1.0)),
        first=int(share.get("share_index", 0)) * held,
        total=int(share.get("n_routed_experts", held)),
    )
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(int(hf["num_layers"])):
        x = layer(x, params["layers"], jnp.asarray(inv_freq), i, **kw)
        x.block_until_ready()   # a layer's temporaries go before the next one's come
    return x


def logits(params, tokens, hf, rows=None):
    """[len(rows) or s, vocab] float32 logits of one sequence; ``rows`` picks
    the positions whose logits are wanted."""
    x = hidden(params, tokens, hf)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return head(x, params["final_norm"], params["lm_head"], eps=float(hf["rms_norm_eps"]))
