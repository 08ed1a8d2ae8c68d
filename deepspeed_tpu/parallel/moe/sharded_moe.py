"""Top-k gated MoE with capacity-padded einsum dispatch.

Semantics match the reference gate functions (moe/sharded_moe.py):
  * capacity = ceil(tokens_per_expert * capacity_factor) (:120 _capacity)
  * top1gating (:183): optional jitter noise, load-balancing aux loss
    l_aux = E * mean(gate_prob_per_expert) . mean(token_fraction_per_expert)
  * top2gating (:290): second expert with normalized weights
  * topkgating (:374): general k, capacity-aware token dropping
  * tokens over capacity are dropped (their combine weights zero out)

Dispatch uses the GShard einsum form the reference itself adopted
(sharded_moe.py:589): dispatch_mask [s, e, c] one-hot scatters tokens into
[e, c, m] buffers; expert compute runs with e sharded over the ``expert``
mesh axis (GSPMD inserts the all-to-all); combine_weights gather back.
"""

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.parallel.topology import EXPERT_AXIS, MODEL_AXIS, constrain, get_topology


# the per-expert weights of a layer: what ``moe_mlp(layer=...)`` takes whole
EXPERT_STACKS = ("w_up", "w_gate", "w_down")


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float, min_capacity: int = 4) -> int:
    """Reference _capacity (sharded_moe.py:167): ceil(tokens * cf / experts)."""
    cap = math.ceil(num_tokens * capacity_factor / num_experts)
    return max(cap, min_capacity)


def _one_hot(x, n):
    return jax.nn.one_hot(x, n, dtype=jnp.float32)


def _position_in_expert(expert_mask: jax.Array) -> jax.Array:
    """Cumulative position of each token within its chosen expert.
    expert_mask: [s, e] one-hot. Returns [s, e] positions (0-based)."""
    return jnp.cumsum(expert_mask, axis=0) - expert_mask


def top1gating(
    logits: jax.Array,
    capacity_factor: float = 1.0,
    min_capacity: int = 4,
    noisy_gate_policy: Optional[str] = None,
    rng: Optional[jax.Array] = None,
    drop_tokens: bool = True,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Reference top1gating (sharded_moe.py:183).

    logits: [s, e]. Returns (l_aux, combine_weights [s,e,c], dispatch_mask
    [s,e,c], exp_counts [e]).
    """
    s, e = logits.shape
    # drop_tokens=False must keep every token: capacity becomes the static
    # worst case (all tokens to one expert). The reference grows capacity to
    # max(exp_counts) at runtime (sharded_moe.py:215); under jit shapes are
    # static, so the worst-case bound is the shape-safe equivalent.
    c = s if not drop_tokens else _capacity(s, e, capacity_factor, min_capacity)
    if noisy_gate_policy == "RSample" and rng is not None:
        logits_w_noise = logits + jax.random.gumbel(rng, logits.shape, logits.dtype)
    else:
        logits_w_noise = logits
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    indices1 = jnp.argmax(logits_w_noise, axis=-1)  # [s]
    mask1 = _one_hot(indices1, e)  # [s, e]

    exp_counts = jnp.sum(mask1, axis=0)
    # load-balancing loss (sharded_moe.py:249): E * <gates_e> . <frac_e>
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * e

    locations1 = _position_in_expert(mask1)  # [s, e]
    if drop_tokens:
        mask1 = mask1 * (locations1 < c).astype(mask1.dtype)
    pos = jnp.sum(locations1 * mask1, axis=-1).astype(jnp.int32)  # [s]

    gates1 = jnp.sum(gates * mask1, axis=-1)  # [s] gate value of kept tokens
    combine = gates1[:, None, None] * mask1[:, :, None] * _one_hot(pos, c)[:, None, :]
    dispatch = combine > 0
    return l_aux, combine, dispatch, exp_counts


def top2gating(
    logits: jax.Array,
    capacity_factor: float = 1.0,
    min_capacity: int = 4,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Reference top2gating (sharded_moe.py:290): capacity 2·cf·s/e, which
    topkgating's k-token scaling (_capacity(s·k, e, cf)) already yields.

    Aux loss follows the reference top2 convention — mean(me·ce1)·e² over the
    FIRST-choice mask only, no /k — which is ~2× topkgating's k=2 value."""
    # reference top2 drops by position with 1st choices outranking 2nd
    # (locations2 offset by sum(mask1)), not by gate value
    l_aux_k, combine, dispatch, exp_counts = topkgating(
        logits, k=2, capacity_factor=capacity_factor, min_capacity=min_capacity,
        drop_policy="choice_priority",
    )
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    e = logits.shape[1]
    mask1 = _one_hot(jnp.argmax(logits, axis=-1), e)
    me = jnp.mean(gates, axis=0)
    ce1 = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce1) * e
    return l_aux, combine, dispatch, exp_counts


def topkgating(
    logits: jax.Array,
    k: int,
    capacity_factor: float = 1.0,
    min_capacity: int = 4,
    drop_tokens: bool = True,
    drop_policy: str = "probs",
    normalize: bool = True,
    live: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Reference topkgating (sharded_moe.py:374): general top-k with
    normalized combine weights and per-expert capacity dropping. ``live``
    ([s] bool): slots that hold a token; the others (a serving step's padding)
    take no capacity slot, weigh nothing and are not counted.

    drop_policy (reference default "probs"): which tokens lose when an
    expert's capacity overflows —
      * "probs": each expert keeps its top-capacity tokens by gate value;
      * "position": capacity slots are filled in token order over the union
        top-k mask (reference topkgating cumsum-over-tokens semantics);
      * "choice_priority": all 1st choices outrank all 2nd choices, etc.,
        then token order within a choice (reference top2gating's
        locations2 += sum(mask1) offset semantics).
    """
    s, e = logits.shape
    # drop_tokens=False: a token takes at most one slot of an expert, so the
    # token count is the capacity that drops nothing
    c = s if not drop_tokens else _capacity(s * k, e, capacity_factor, min_capacity)
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [s, e]

    topk_vals, topk_idx = jax.lax.top_k(gates, k)  # [s, k]

    # aux loss over the top-k mask (reference: uses full mask counts)
    mask = jnp.sum(_one_hot(topk_idx, e), axis=1)  # [s, e] (0/1, k ones)
    if live is not None:
        mask = mask * live[:, None].astype(mask.dtype)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask, axis=0)
    l_aux = jnp.sum(me * ce) * e / k
    exp_counts = jnp.sum(mask, axis=0)

    # Per-(token, expert) capacity slot + survival, by policy. Both produce
    # pos_full [s, e] (slot index within the expert) and keep [s, e] (0/1).
    if drop_policy == "probs":
        # rank tokens within each expert column by gate value, descending
        # (double argsort = inverse permutation = rank); keep ranks < c.
        masked_gates = jnp.where(mask > 0, gates, -jnp.inf)
        order = jnp.argsort(-masked_gates, axis=0)
        pos_full = jnp.argsort(order, axis=0).astype(jnp.float32)
    elif drop_policy == "position":
        pos_full = jnp.cumsum(mask, axis=0) - 1.0
    elif drop_policy == "choice_priority":
        # choice-major slot order: expert e's slots go to 1st-choice tokens
        # first (in token order), then 2nd-choice, ... — each choice's
        # locations are offset by the cumulative count of earlier choices.
        pos_full = jnp.zeros((s, e), jnp.float32)
        base_counts = jnp.zeros((e,), jnp.float32)
        for j in range(k):
            oh_j = _one_hot(topk_idx[:, j], e)
            loc_j = (jnp.cumsum(oh_j, axis=0) - 1.0 + base_counts[None, :]) * oh_j
            pos_full = pos_full + loc_j
            base_counts = base_counts + jnp.sum(oh_j, axis=0)
    else:
        raise ValueError(f"unknown drop_policy {drop_policy!r}")
    keep = mask * (pos_full < c).astype(mask.dtype) if drop_tokens else mask

    # Combine weights are renormalized over SURVIVING experts only (reference
    # top2 denom over post-drop gates, sharded_moe.py:356) — accumulate raw
    # gate values first, normalize at the end.
    combine = jnp.zeros((s, e, c), jnp.float32)
    kept_total = jnp.zeros((s,), jnp.float32)
    for j in range(k):
        oh_j = _one_hot(topk_idx[:, j], e)  # [s, e]
        mask_j = oh_j * keep
        pos_j = jnp.sum(pos_full * oh_j, axis=-1).astype(jnp.int32)
        kept_j = jnp.sum(mask_j, axis=-1)  # [s] 1 if this choice survived
        w_j = topk_vals[:, j] * kept_j
        kept_total = kept_total + w_j
        combine = combine + w_j[:, None, None] * mask_j[:, :, None] * _one_hot(pos_j, c)[:, None, :]
    if normalize:
        combine = combine / jnp.maximum(kept_total, 1e-9)[:, None, None]
    dispatch = combine > 0
    return l_aux, combine, dispatch, exp_counts


class TopKGate:
    """Object wrapper mirroring reference TopKGate (sharded_moe.py:452)."""

    def __init__(
        self,
        k: int = 1,
        capacity_factor: float = 1.0,
        eval_capacity_factor: float = 1.0,
        min_capacity: int = 4,
        noisy_gate_policy: Optional[str] = None,
        drop_tokens: bool = True,
        drop_policy: str = "probs",
    ):
        self.k = k
        self.capacity_factor = capacity_factor
        self.eval_capacity_factor = eval_capacity_factor
        self.min_capacity = min_capacity
        self.noisy_gate_policy = noisy_gate_policy
        self.drop_tokens = drop_tokens
        self.drop_policy = drop_policy

    def __call__(self, logits, train: bool = True, rng=None):
        cf = self.capacity_factor if train else self.eval_capacity_factor
        if self.k == 1:
            return top1gating(
                logits, cf, self.min_capacity,
                self.noisy_gate_policy if train else None, rng, self.drop_tokens,
            )
        return topkgating(
            logits, self.k, cf, self.min_capacity, self.drop_tokens, self.drop_policy
        )


def _expert_sharded(x, spec):
    return constrain(x, *spec)


def _moe_exchange_quant(config, lp, tokens, dispatch, combine, dtype):
    """Expert dispatch/combine with the EP exchange quantized INSIDE the
    collective (comm/quantized.py, EQuARX-style int8 + fp32 block scales).

    GSPMD's implicit all-to-all behind the ``ech`` resharding cannot be
    rewritten from the outside, so the exchange runs in an explicit
    shard_map island manual over EXPERT_AXIS only (data/zero/model stay
    auto — the f-dim TP psum under w_down is still GSPMD's):

      local partial dispatch einsum  [e, c, h]   (zeros in peer-owned slots)
      quantized_all_to_all(reduce=True)  → this shard's experts [e/E, c, h]
          (the reference all_to_all_quant_reduce / qgZ reduce-scatter)
      local expert FFN
      quantized_all_gather over e        → full [e, c, h]
      local combine einsum               → this shard's tokens [t/E, h]

    Gating stays global (capacity slots are a cumsum over the GLOBAL token
    dim), so two shards never claim the same (e, c) slot and the
    reduce-sum merge is exact.
    """
    from deepspeed_tpu.comm.quantized import quantized_all_gather, quantized_all_to_all

    topo = get_topology()
    E = topo.axis_size(EXPERT_AXIS)
    t = tokens.shape[0]
    e = dispatch.shape[1]
    if e % E or t % E:
        raise ValueError(
            f"comm_quant='int8' MoE exchange: n_experts={e} and tokens={t} "
            f"must both be divisible by the expert-parallel degree {E}"
        )
    weights = {"w_up": lp["w_up"], "w_down": lp["w_down"]}
    if config.activation in ("swiglu", "geglu"):
        weights["w_gate"] = lp["w_gate"]

    def island(tokens_l, dispatch_l, combine_l, w):
        partial = jnp.einsum("tec,th->ech", dispatch_l.astype(dtype), tokens_l)
        expert_in = quantized_all_to_all(
            partial, EXPERT_AXIS, split_dim=0, reduce=True, tag="moe_dispatch"
        )
        up = jnp.einsum("ech,ehf->ecf", expert_in, w["w_up"])
        if config.activation in ("swiglu", "geglu"):
            gate = jnp.einsum("ech,ehf->ecf", expert_in, w["w_gate"])
            g = jax.nn.gelu(gate) if config.activation == "geglu" else jax.nn.silu(gate)
            act = g * up
        else:
            act = jax.nn.gelu(up, approximate=config.activation != "gelu_exact")
        act = constrain(act, None, None, MODEL_AXIS)
        expert_out = jnp.einsum("ecf,efh->ech", act, w["w_down"])
        full = quantized_all_gather(expert_out, EXPERT_AXIS, dim=0, tag="moe_combine")
        return jnp.einsum("tec,ech->th", combine_l.astype(dtype), full)

    fn = jax.shard_map(
        island,
        mesh=topo.mesh,
        in_specs=(
            P(EXPERT_AXIS, None),
            P(EXPERT_AXIS, None, None),
            P(EXPERT_AXIS, None, None),
            jax.tree.map(lambda _: P(EXPERT_AXIS, None, None), weights),
        ),
        out_specs=P(EXPERT_AXIS, None),
        axis_names={EXPERT_AXIS},
        check_vma=False,
    )
    return fn(tokens, dispatch, combine, weights)


def moe_mlp(config, lp, x: jax.Array, live: Optional[jax.Array] = None, layer=None
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """MoE MLP block used by models/transformer.py and the serving steps.

    lp: layer params with router [h,E], w_up [E,h,f], w_down [E,f,h]
    (+ w_gate [E,h,f] for swiglu); under an expert share the router spans the
    published count and E experts are held (grouped.py). x: [b, s, h]. ``live`` ([b, s] bool, or
    None for all): the slots that hold a token; the padding of a serving
    step's grid goes to no expert. ``layer``: where given, ``lp``'s expert
    weights (``EXPERT_STACKS``) are the whole stacks [L, E, ...] and this is
    the layer's index: the grouped kernel reads its layer's blocks in place,
    where a slice taken by the caller would be copied in front of it.
    Returns (out [b, s, h], aux_loss scalar, [E] int32 rows routed to each
    expert).

    Two dispatches, chosen from the configuration and the mesh:
      * ``moe_drop_tokens=False`` on one device's experts (``expert`` and
        ``model`` axes of 1): rows sorted by expert and grouped matmuls
        (grouped.py): no capacity, no dropped token.
      * otherwise the einsum pipeline (reference MOELayer.forward,
        sharded_moe.py:589): gate → dispatch [s,e,c] → expert buffers [e,c,h]
        (GSPMD all-to-all as e is expert-sharded) → per-expert MLP → combine
        back; with ``moe_drop_tokens=False`` at the capacity that drops
        nothing (every token's k slots), which is what expert parallelism
        keeps until a ragged all-to-all exists.
    """
    b, s, h = x.shape
    tokens = x.reshape(b * s, h)
    live = None if live is None else live.reshape(b * s)
    # the router as published: logits and softmax in float32 (a bf16 logit
    # moves a near-tie between the k-th and the next expert)
    logits = jnp.dot(tokens.astype(jnp.float32), lp["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    topo = get_topology()
    if not config.moe_drop_tokens and topo.axis_size(EXPERT_AXIS) == 1 and topo.axis_size(MODEL_AXIS) == 1:
        from deepspeed_tpu.parallel.moe.grouped import experts_grouped

        out, l_aux, counts = experts_grouped(config, lp, tokens, logits, live, layer)
        return _moe_tail(config, lp, tokens, out).reshape(b, s, h), l_aux, counts
    if config.moe_score != "softmax":
        raise NotImplementedError(
            "a sigmoid-scored router with a selection bias runs the grouped dispatch "
            "(moe_drop_tokens=False, expert and model axes of 1); topkgating is softmax only")
    if config.router_width != config.n_experts:
        raise NotImplementedError(
            "an expert share (moe_experts_total > n_experts) runs the grouped "
            "dispatch on one device's experts (moe_drop_tokens=False, expert and "
            "model axes of 1); the capacity dispatch has no held-expert form")
    if layer is not None:  # an einsum reads a slice in place
        lp = {k: jax.lax.dynamic_index_in_dim(v, layer, 0, keepdims=False) if k in EXPERT_STACKS
              else v for k, v in lp.items()}
    l_aux, combine, dispatch, counts = topkgating(
        logits,
        k=config.moe_top_k,
        capacity_factor=config.moe_capacity_factor,
        drop_tokens=config.moe_drop_tokens,
        normalize=config.moe_norm_topk_prob,
        live=live,
    )
    from deepspeed_tpu.parallel.moe.mappings import quantized_ep_active

    if quantized_ep_active(config):
        # int8-inside-the-collective EP exchange (explicit island; the
        # implicit GSPMD form below cannot quantize its own all-to-all)
        out = _moe_exchange_quant(config, lp, tokens, dispatch, combine, x.dtype)
    else:
        # dispatch: [t, e, c] bool; tokens: [t, h] → expert buffers [e, c, h]
        expert_in = jnp.einsum("tec,th->ech", dispatch.astype(x.dtype), tokens)
        expert_in = _expert_sharded(expert_in, P(EXPERT_AXIS, None, None))

        # per-expert FFN, e sharded over the expert axis, f over model axis
        up = jnp.einsum("ech,ehf->ecf", expert_in, lp["w_up"])
        if config.activation in ("swiglu", "geglu"):
            gate = jnp.einsum("ech,ehf->ecf", expert_in, lp["w_gate"])
            g = jax.nn.gelu(gate) if config.activation == "geglu" else jax.nn.silu(gate)
            act = g * up
        else:
            act = jax.nn.gelu(up, approximate=config.activation != "gelu_exact")
        act = _expert_sharded(act, P(EXPERT_AXIS, None, MODEL_AXIS))
        expert_out = jnp.einsum("ecf,efh->ech", act, lp["w_down"])
        expert_out = _expert_sharded(expert_out, P(EXPERT_AXIS, None, None))

        # combine back to tokens (reverse all-to-all via resharding)
        out = jnp.einsum("tec,ech->th", combine.astype(x.dtype), expert_out)
    out = _moe_tail(config, lp, tokens, out)
    return out.reshape(b, s, h), l_aux, counts.astype(jnp.int32)


def _moe_tail(config, lp, tokens, out):
    """What every token gets beside its routed experts, on either dispatch:
    the residual expert and the shared expert. tokens, out: [t, h]."""

    def _dense_mlp(prefix):
        up = tokens @ lp[f"{prefix}_up"]
        if config.activation in ("swiglu", "geglu"):
            gate = tokens @ lp[f"{prefix}_gate"]
            act = (jax.nn.gelu(gate) if config.activation == "geglu" else jax.nn.silu(gate)) * up
        else:
            act = jax.nn.gelu(up, approximate=config.activation != "gelu_exact")
        return act @ lp[f"{prefix}_down"]

    if config.moe_residual and "res_coef" in lp:
        # Residual-MoE (reference moe/layer.py:29,47 — arXiv 2201.05596): a
        # dense MLP runs on every token; a learned 2-way softmax coefficient
        # mixes it with the (possibly dropped) expert output
        coef = jax.nn.softmax((tokens @ lp["res_coef"]).astype(jnp.float32), axis=-1)
        out = out * coef[:, 0:1].astype(out.dtype) + _dense_mlp("res") * coef[:, 1:2].astype(out.dtype)
    if config.moe_shared_expert_dim > 0 and "shared_up" in lp:
        # qwen2-moe shared expert: always-on dense expert scaled by a
        # sigmoid gate (HF Qwen2MoeSparseMoeBlock.shared_expert_gate)
        shared = _dense_mlp("shared")
        if config.moe_shared_gated:
            gate = jax.nn.sigmoid((tokens @ lp["shared_gate_proj"]).astype(jnp.float32))
            shared = gate.astype(out.dtype) * shared
        out = out + shared
    return out


class MoE:
    """API-parity layer object (reference deepspeed/moe/layer.py:17): wraps an
    expert MLP param set and exposes forward(x) -> (out, l_aux, exp_counts).

    For the functional training path prefer building the model with
    ``TransformerConfig(n_experts=...)`` which routes through ``moe_mlp``.
    """

    def __init__(self, config, layer_params):
        self.config = config
        self.lp = layer_params

    def __call__(self, x):
        return moe_mlp(self.config, self.lp, x)
