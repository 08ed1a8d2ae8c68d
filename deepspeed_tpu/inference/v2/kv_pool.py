"""Cache-pool byte accounting: pool dtypes, bytes-per-block, bytes a state
slot, budget sizing. Two kinds of cache are paid from one byte budget.

One shared source of truth for "how big is a KV block" so the engine
(allocating the pools), the serve CLI (sizing ``num_blocks`` from a byte
budget), the driver (health/metrics) and the capacity tests cannot drift.

Layout recap (engine_v2): each of K and V is [L, num_blocks+1, block_size,
kv_heads, head_dim] in the payload dtype, L the layers that HAVE keys and
values (``TransformerConfig.kv_layers``: all of them, or with ``layer_kinds``
the full-attention ones); ``int8`` mode adds a per-token-row
per-kv-head fp32 scale plane [L, num_blocks+1, block_size, kv_heads] per
pool (quantize_kv's per-vector granularity — see ops/quantizer/block_quant).
A "block" here is one (block_size, kv_heads, head_dim) slab counted across
all L layers and BOTH pools, i.e. the unit ``free_blocks`` admission counts.

At head_dim=128 the int8 ratio is 2*128/(128+4) ≈ 1.94x — the ≥1.9x
capacity bar the acceptance tests pin.

A model with recurrent layers (Gated DeltaNet, Mamba) also keeps, for every
tracked sequence and whatever its length, one STATE SLOT: a layer's recurrent
state in float32 (DeltaNet [value heads, key dim, value dim], Mamba [state
size, channels]) and its conv's last K - 1 inputs, over the such layers
(``state_slot_bytes``). ``blocks_for_budget`` takes the slots, one a
tracked sequence and the spare, from the budget first and sizes the K/V
blocks from what is left.

A stack that mixes window layers with global ones (``window_layers`` > 0)
has a WINDOW POOL beside the block pool, [Lw, slots * wb, block_size, kv_heads,
head_dim] per K and V: a tracked sequence's slot is a ring of ``wb =
window_blocks(window, block_size)`` blocks a window layer, the blocks a query
at its position can still see and the one being written, whatever the
context: logical block ``b`` of slot ``s`` is ring block ``s * wb + b % wb``.
It is paid like the state slots, one ring a tracked sequence and a spare
(``window_slot_bytes``), from the budget first; the block pool then holds the
global layers alone (``kv_layers``).

A latent-attention model (``TransformerConfig.latent``) has a pool of ONE
plane, [L, num_blocks+1, latent_dim, block_size]: a token's keys and values
are one vector every head shares (the normed latent and the rotated key dims:
512 + 64 for A.X-K1), stored a block as ``latent_dim`` rows of ``block_size``
tokens (ops/attention/latent_pallas.py says why). ``pool_geometry`` gives what
the functions below take for it: one "KV head" of ``latent_dim``, one plane.

GEOMETRY IS BY POOL AND BY PLANE (``pool_geometry(config, pool)``): the block
pool is shaped by the global layers' KV heads and the window pool by the window
layers' (mimo_v2_flash: 4 and 8), and a key may be wider than a value (192
against 128), which ``head_dim`` then says as one width a plane, ``(192,
128)``. Every byte function below takes ``head_dim`` in either form. Where a
head's keys are wider than the 128 lanes and do not fill them whole
(``paged_pallas.keys_flat``: 192) the K plane stores a token's keys as ONE ROW,
[.., block_size, kv_heads * head_dim]: the same bytes, the bytes the chip holds (a row a head pads to 256 lanes there,
or is laid out tokens-minor and copied whole in front of every kernel call).
"""

import math
from typing import Any, Dict, Tuple

import numpy as np

# payload bytes per element + scale bytes per head vector
KV_DTYPES = ("bf16", "int8")

# plane name -> ((n_layers, *per_block_tail), dtype) — the exact-contract
# spec ``check_kv_payload`` validates against (engine_v2._kv_payload_spec
# builds it from the live pools)
KVPayloadSpec = Dict[str, Tuple[tuple, Any]]


def check_kv_payload(spec: KVPayloadSpec, n: int, payload: Dict,
                     context: str = "import_kv_blocks") -> None:
    """ONE strict payload contract for every path that moves KV blocks
    between pools — handoff import (all transports), host-tier readmit,
    and router peer pulls validate here instead of keeping drifting
    copies. Raises loudly on any mismatch BEFORE a scatter: a malformed
    payload (wrong dtype, wrong trailing dims, missing or stray scale
    planes) must never silently cast-and-scatter garbage into live KV.

    ``spec`` maps each required plane to ``((n_layers, *per_block_tail),
    dtype)``; ``payload[name]`` must be ``[n_layers, n, *per_block_tail]``
    in exactly that dtype."""
    missing = sorted(set(spec) - set(payload))
    extra = sorted(set(payload) - set(spec))
    if missing or extra:
        raise ValueError(
            f"{context}: payload planes {sorted(payload)} do not "
            f"match the pool's {sorted(spec)}"
            + (f"; missing {missing}" if missing else "")
            + (f"; unexpected {extra}" if extra else "")
        )
    for name, (block_shape, dtype) in spec.items():
        plane = payload[name]
        expect = (block_shape[0], n) + tuple(block_shape[1:])
        if tuple(plane.shape) != expect:
            raise ValueError(
                f"{context}: payload[{name!r}] shape "
                f"{tuple(plane.shape)} != {expect} expected for {n} "
                f"target blocks"
            )
        if np.dtype(plane.dtype) != np.dtype(dtype):
            raise ValueError(
                f"{context}: payload[{name!r}] dtype "
                f"{np.dtype(plane.dtype)} != pool dtype "
                f"{np.dtype(dtype)} (a silent cast would corrupt "
                "quantized codes/scales)"
            )


def _check_dtype(kv_dtype: str) -> str:
    if kv_dtype not in KV_DTYPES:
        raise ValueError(
            f"kv_cache_dtype={kv_dtype!r}: expected one of {KV_DTYPES} "
            "(bf16 = pool in the engine compute dtype, int8 = quantized "
            "payload + fp32 per-vector scale plane)"
        )
    return kv_dtype


def pool_geometry(config, pool: str = "block"):
    """(kv_heads, head_dim, planes) of one of a model's pools, as the byte
    accounting below takes them (``config``: the TransformerConfig; ``pool``:
    "block", the pool of the layers that keep the whole context, or "window",
    a mixed stack's window pool): K and V planes of ``kv_heads x head_dim`` a
    token, ``head_dim`` a pair ``(key width, value width)`` where the two
    differ, or a latent model's one plane of one ``latent_dim``-wide vector."""
    if config.latent:
        return 1, config.latent_dim, 1
    d, dv = config.head_dim, config.value_dim
    return (config.kv_heads_of("window" if pool == "window" else "full"),
            d if dv == d else (d, dv), 2)


def plane_widths(head_dim, planes: int = 2) -> Tuple[int, ...]:
    """A head vector's width in each plane: ``head_dim`` as ``pool_geometry``
    gives it, one width for every plane or one a plane."""
    return tuple(head_dim) if isinstance(head_dim, (tuple, list)) else (int(head_dim),) * planes


def bytes_per_block(block_size: int, kv_heads: int, head_dim,
                    n_layers: int, kv_dtype: str = "bf16", planes: int = 2) -> int:
    """HBM bytes one logical KV block costs across all layers and its
    ``planes`` pools (K and V; one for a latent pool) — payload plus, for
    int8, the fp32 scale plane. ``head_dim``: one width, or one a plane."""
    _check_dtype(kv_dtype)
    vectors = block_size * kv_heads  # head vectors per block per pool
    per_block = 0
    for width in plane_widths(head_dim, planes):
        if kv_dtype == "int8":
            per_block += vectors * width * 1 + vectors * 4  # int8 payload + fp32 scale
        else:
            per_block += vectors * width * 2  # bf16 payload
    return n_layers * per_block


def state_slot_bytes(config, conv_itemsize: int = 2) -> int:
    """HBM bytes of one sequence's state slot over a model's recurrent layers
    (``config``: the TransformerConfig; ``models.transformer.RECURRENT``
    describes the kind's slot): float32 states and the conv inputs in the
    compute dtype. 0 for a model without such layers."""
    from deepspeed_tpu.models.transformer import RECURRENT

    if config.recurrent_kind is None:
        return 0
    kind = RECURRENT[config.recurrent_kind]
    state = math.prod(kind.state_shape(config)) * 4
    conv = (kind.kernel(config) - 1) * kind.channels(config) * conv_itemsize
    return config.kind_count(config.recurrent_kind) * (state + conv)


def window_blocks(window: int, block_size: int) -> int:
    """Blocks of a window layer's ring: those a query can still see
    (``window - 1`` keys behind it span at most ``ceil((window - 1) /
    block_size)`` blocks beside its own) and the one being written."""
    return -(-(int(window) - 1) // int(block_size)) + 1


def window_slot_bytes(config, block_size: int) -> int:
    """HBM bytes of one sequence's rings over a model's window layers
    (``config``: the TransformerConfig; bf16 pools only). 0 for a stack that
    does not mix window and global layers."""
    if not config.window_layers:
        return 0
    kv_heads, head_dim, planes = pool_geometry(config, "window")
    return window_blocks(config.sliding_window, block_size) * bytes_per_block(
        block_size, kv_heads, head_dim, config.window_layers, planes=planes)


def slot_bytes(config, block_size: int, conv_itemsize: int = 2) -> int:
    """What one tracked sequence holds beside its K/V blocks, whatever its
    length: a recurrent-state slot, or its window layers' rings."""
    return state_slot_bytes(config, conv_itemsize) + window_slot_bytes(config, block_size)


def blocks_for_budget(budget_bytes: int, block_size: int, kv_heads: int,
                      head_dim, n_layers: int,
                      kv_dtype: str = "bf16", state_bytes: int = 0, planes: int = 2) -> int:
    """How many pool blocks fit a fixed byte budget (the +1 trash block is
    charged too, so the returned count is directly ``num_blocks``).
    ``n_layers``: the layers that keep the whole context's K/V. ``state_bytes``:
    what the slots of the second kind of cache (recurrent states, or the window
    layers' rings: ``slot_bytes`` a tracked sequence and a spare) take from the
    budget first."""
    per = bytes_per_block(block_size, kv_heads, head_dim, n_layers, kv_dtype, planes)
    n = (budget_bytes - state_bytes) // per - 1  # -1: the engine allocates num_blocks + 1
    if n < 1:
        raise ValueError(
            f"kv pool budget {budget_bytes} bytes ({state_bytes} of them state or window slots) holds no blocks at "
            f"{per} bytes/block (block_size={block_size}, kv_heads={kv_heads}, "
            f"head_dim={head_dim}, n_layers={n_layers}, dtype={kv_dtype})"
        )
    return int(n)


def capacity_multiplier(block_size: int, kv_heads: int, head_dim,
                        kv_dtype: str = "bf16") -> float:  # (the planes cancel)
    """Effective pool-capacity multiplier of ``kv_dtype`` vs the bf16
    baseline at a fixed byte budget (layer count cancels)."""
    base = bytes_per_block(block_size, kv_heads, head_dim, 1, "bf16")
    cur = bytes_per_block(block_size, kv_heads, head_dim, 1, kv_dtype)
    return base / cur


def pool_bytes(num_blocks: int, block_size: int, kv_heads: int,
               head_dim, n_layers: int, kv_dtype: str = "bf16", planes: int = 2) -> int:
    """Total HBM bytes of the allocated pools (num_blocks + 1 trash)."""
    return (num_blocks + 1) * bytes_per_block(
        block_size, kv_heads, head_dim, n_layers, kv_dtype, planes
    )


def describe(num_blocks: int, block_size: int, kv_heads: int, head_dim,
             n_layers: int, kv_dtype: str = "bf16", planes: int = 2) -> Dict:
    """The health()/metrics snapshot: the block pool's geometry, bytes, dtype,
    capacity multiplier."""
    return {
        "kv_cache_dtype": _check_dtype(kv_dtype),
        "kv_pool_geometry": {"layers": n_layers, "kv_heads": kv_heads,
                             "plane_widths": list(plane_widths(head_dim, planes))},
        "kv_pool_bytes": pool_bytes(
            num_blocks, block_size, kv_heads, head_dim, n_layers, kv_dtype, planes),
        "kv_bytes_per_block": bytes_per_block(
            block_size, kv_heads, head_dim, n_layers, kv_dtype, planes),
        "kv_capacity_multiplier": capacity_multiplier(
            block_size, kv_heads, head_dim, kv_dtype),
    }
