"""Symmetric block quantization + quantized gradient reduction.

Semantics (matching reference csrc/quantization/pt_binding.cpp ds_quantize
symmetric path): values are grouped into fixed-size blocks; each block stores
int8 values (int4 packed two-per-byte) and one fp32 scale = absmax/qmax.
Dequant is ``q * scale``.

ZeRO++ qgZ (quantized-gradient all-to-all, reference
runtime/comm/coalesced_collectives.py all_to_all_quant_reduce +
csrc/quantization/quant_reduce.cu): ``quantized_reduce_scatter`` runs inside
a ``shard_map`` collective context — the int8 payload and fp32 scales cross
the wire via ``lax.all_to_all`` (2× fewer bytes than fp16 grads at int8, 4×
at packed int4), each rank dequantizes the received shards and reduces
locally, exactly the reference pipeline.
"""

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

_QMAX = {8: 127.0, 4: 7.0}
# names of the Mosaic custom calls in a device trace (metadata only)
BLOCK_QUANT = "dstpu_block_quant"


class QuantizedTensor(NamedTuple):
    values: jax.Array  # int8 payload; for bits=4, two biased nibbles per byte
    scales: jax.Array  # fp32 per block
    shape: tuple  # original shape
    bits: int
    block_size: int


def _pad_to(x, multiple):
    n = x.shape[0]
    pad = (-n) % multiple
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    return x, pad


def _pack_int4(q: jax.Array) -> jax.Array:
    """[-7, 7] int values → two biased nibbles per uint8 byte ([nb, block/2])."""
    biased = (q + 7).astype(jnp.uint8)  # 0..14
    lo, hi = biased[:, ::2], biased[:, 1::2]
    return (lo | (hi << 4)).astype(jnp.int8)


def _unpack_int4(packed: jax.Array) -> jax.Array:
    u = packed.astype(jnp.uint8)
    lo = (u & 0xF).astype(jnp.float32) - 7.0
    hi = (u >> 4).astype(jnp.float32) - 7.0
    nb, half = u.shape
    return jnp.stack([lo, hi], axis=-1).reshape(nb, half * 2)


def quantize_blockwise(
    x: jax.Array,
    bits: int = 8,
    block_size: int = 2048,
    stochastic: bool = False,
    rng: Optional[jax.Array] = None,
) -> QuantizedTensor:
    """Symmetric per-block quantization. Flattens, pads to block_size."""
    qmax = _QMAX[bits]
    flat = x.reshape(-1).astype(jnp.float32)
    flat, _pad = _pad_to(flat, block_size)
    blocks = flat.reshape(-1, block_size)
    absmax = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True)
    scales = absmax / qmax
    inv = jnp.where(scales > 0, 1.0 / jnp.maximum(scales, 1e-30), 0.0)
    scaled = blocks * inv
    if stochastic:
        if rng is None:
            raise ValueError("stochastic=True requires an rng key (silent deterministic fallback would bias gradients)")
        noise = jax.random.uniform(rng, scaled.shape) - 0.5
        q = jnp.clip(jnp.round(scaled + noise), -qmax, qmax)
    else:
        q = jnp.clip(jnp.round(scaled), -qmax, qmax)
    values = _pack_int4(q) if bits == 4 else q.astype(jnp.int8)
    return QuantizedTensor(
        values=values,
        scales=scales[:, 0],
        shape=tuple(x.shape),
        bits=bits,
        block_size=block_size,
    )


def dequantize_blockwise(qt: QuantizedTensor, dtype=jnp.float32) -> jax.Array:
    vals = _unpack_int4(qt.values) if qt.bits == 4 else qt.values.astype(jnp.float32)
    flat = (vals * qt.scales[:, None]).reshape(-1)
    n = 1
    for d in qt.shape:
        n *= d
    return flat[:n].reshape(qt.shape).astype(dtype)


def quantized_reduce_scatter(
    x: jax.Array,
    axis_name: str,
    bits: int = 8,
    block_size: int = 256,
    mean: bool = True,
) -> jax.Array:
    """qgZ gradient exchange, to be called INSIDE shard_map over ``axis_name``.

    x: this rank's local (replica) gradient, flat or any shape; logically the
    same array exists on every rank of the axis. Each rank quantizes W chunks
    of its local grads, the int8 payload + scales move via ``lax.all_to_all``,
    and each rank dequantizes + reduces the W received copies of its own
    chunk. Returns this rank's reduced chunk [ceil(n/W) elements], matching
    reference all_to_all_quant_reduce (reduce-scatter semantics). Bytes on
    the wire: n/2 (int8 vs bf16) or n/4 (int4) + scales.
    """
    W = jax.lax.axis_size(axis_name)
    flat = x.reshape(-1).astype(jnp.float32)
    flat, _ = _pad_to(flat, W * block_size)
    chunk = flat.shape[0] // W
    rows = flat.reshape(W, chunk)

    payload, scales = _quantize_rows(rows, bits, block_size)
    # the int8 payload and fp32 block scales are what crosses ICI
    payload_rx = jax.lax.all_to_all(payload, axis_name, split_axis=0, concat_axis=0, tiled=True)
    scales_rx = jax.lax.all_to_all(scales, axis_name, split_axis=0, concat_axis=0, tiled=True)
    deq = _dequantize_rows(payload_rx, scales_rx, bits, block_size)  # [W, chunk]
    total = jnp.sum(deq, axis=0)
    if mean:
        total = total / W
    return total.astype(x.dtype)


def _quantize_rows(rows: jax.Array, bits: int, block_size: int):
    """Per-row blockwise quantization helper: rows [R, m] (m % block == 0) →
    (payload int8 [R, nb, bs or bs/2], scales fp32 [R, nb, 1])."""
    qmax = _QMAX[bits]
    R, m = rows.shape
    blocks = rows.reshape(R, m // block_size, block_size)
    absmax = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True)
    scales = absmax / qmax
    inv = jnp.where(scales > 0, 1.0 / jnp.maximum(scales, 1e-30), 0.0)
    q = jnp.clip(jnp.round(blocks * inv), -qmax, qmax)
    if bits == 4:
        payload = _pack_int4(q.reshape(-1, block_size)).reshape(R, m // block_size, block_size // 2)
    else:
        payload = q.astype(jnp.int8)
    return payload, scales


def _dequantize_rows(payload: jax.Array, scales: jax.Array, bits: int, block_size: int):
    R, nb = payload.shape[0], payload.shape[1]
    if bits == 4:
        vals = _unpack_int4(payload.reshape(-1, block_size // 2)).reshape(R, nb, block_size)
    else:
        vals = payload.astype(jnp.float32)
    return (vals * scales).reshape(R, nb * block_size)


def quantize_kv(x: jax.Array):
    """Symmetric per-head-vector int8 quantization for paged KV-cache
    payloads: ``x`` [..., d] → (int8 payload [..., d], fp32 scales [...]),
    scale = absmax/127 over each head vector's d components, dequant
    ``q * scale`` (the ds_quantize symmetric convention above).

    Per-VECTOR (not per-block) granularity is what makes quantize-on-write
    compatible with the engine's one write-back scatter a step: a new token's
    row never changes an already-written row's scale, so incremental
    appends need no read-modify-write of neighbouring pool slots."""
    qmax = _QMAX[8]
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scales = absmax / qmax
    inv = jnp.where(scales > 0, 1.0 / jnp.maximum(scales, 1e-30), 0.0)
    q = jnp.clip(jnp.round(xf * inv[..., None]), -qmax, qmax).astype(jnp.int8)
    return q, scales


def dequantize_kv(values: jax.Array, scales: jax.Array, dtype=jnp.float32):
    """Inverse of :func:`quantize_kv`: int8 payload [..., d] + fp32 scales
    [...] → dense [..., d] in ``dtype``."""
    return (values.astype(jnp.float32) * scales[..., None].astype(jnp.float32)).astype(dtype)


def quantized_reduce_scatter_along(
    x: jax.Array,
    axis_name: str,
    dim: int,
    bits: int = 8,
    block_size: int = 256,
    mean: bool = True,
) -> jax.Array:
    """qgZ exchange producing a *dimension* shard: reduce-scatter ``x`` along
    logical dim ``dim`` of the tensor (which must divide by the axis size),
    int8/int4 payload on the wire. Call INSIDE shard_map over ``axis_name``
    with the full local gradient; returns this rank's dim-``dim`` slice —
    i.e. the ZeRO stage-2/3 gradient layout (``grad_specs`` data placement).
    """
    W = jax.lax.axis_size(axis_name)
    D = x.shape[dim]
    if D % W != 0:
        raise ValueError(f"dim {dim} of size {D} not divisible by axis {axis_name}={W}")
    moved = jnp.moveaxis(x, dim, 0)
    rest_shape = moved.shape[1:]
    rows = moved.reshape(W, -1).astype(jnp.float32)  # [W, m] — row w goes to rank w
    m = rows.shape[1]
    pad = (-m) % block_size
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad)))

    payload, scales = _quantize_rows(rows, bits, block_size)
    payload_rx = jax.lax.all_to_all(payload, axis_name, split_axis=0, concat_axis=0, tiled=True)
    scales_rx = jax.lax.all_to_all(scales, axis_name, split_axis=0, concat_axis=0, tiled=True)
    deq = _dequantize_rows(payload_rx, scales_rx, bits, block_size)  # [W, m+pad]
    total = jnp.sum(deq, axis=0)[:m]
    if mean:
        total = total / W
    out = total.reshape((D // W,) + rest_shape)
    return jnp.moveaxis(out, 0, dim).astype(x.dtype)


def quantized_allreduce(
    x: jax.Array,
    axis_name: str,
    bits: int = 8,
    block_size: int = 256,
    mean: bool = True,
) -> jax.Array:
    """Quantized mean-allreduce for replicated-gradient layouts (ZeRO ≤ 1
    under ``zero_quantized_gradients``): quantized reduce-scatter followed by
    a *re-quantized* all-gather (the reference qgZ two-hop pipeline,
    quant_reduce.cu — both hops move int payloads, never full-width floats).
    Call INSIDE shard_map over ``axis_name``. Returns the full averaged
    tensor in ``x``'s shape/dtype."""
    W = jax.lax.axis_size(axis_name)
    n = x.size
    chunk = quantized_reduce_scatter(x, axis_name, bits=bits, block_size=block_size, mean=mean)
    rows = chunk.reshape(1, -1).astype(jnp.float32)
    pad = (-rows.shape[1]) % block_size
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad)))
    payload, scales = _quantize_rows(rows, bits, block_size)
    payload_all = jax.lax.all_gather(payload, axis_name, axis=0, tiled=True)  # [W, nb, bs]
    scales_all = jax.lax.all_gather(scales, axis_name, axis=0, tiled=True)
    deq = _dequantize_rows(payload_all, scales_all, bits, block_size)  # [W, chunk+pad]
    flat = deq[:, : chunk.shape[0]].reshape(-1)[:n]
    return flat.reshape(x.shape).astype(x.dtype)


def loco_quantized_reduce_scatter_along(
    x: jax.Array,
    err: jax.Array,
    axis_name: str,
    dim: int,
    bits: int = 8,
    block_size: int = 256,
    err_beta: float = 0.8,
    mean: bool = True,
):
    """LoCo error-feedback qgZ exchange (reference ZeRO++ LoCo:
    ``coalesced_collectives.all_to_all_loco_quant_reduce`` +
    ``loco_swizzled_quant_kernel``, csrc/quantization/swizzled_quantize.cu:200).

    The compensated gradient ``x + err`` is what gets block-quantized onto
    the wire, and the error buffer EMA-absorbs this step's quantization
    residual: ``err' = err_beta·err + (1-err_beta)·(compensated - dequant)``
    — computed LOCALLY from this rank's own quantization, before the
    all-to-all. The reference runs two hops (intra/inter node) with two
    buffers; the ICI mesh is one hop, so one buffer suffices. ``err``
    persists across steps in the caller (engine loco state), stored bf16
    (reference stores it int8-requantized; bf16 is strictly more faithful).

    Call INSIDE shard_map over ``axis_name``. Returns (this rank's reduced
    dim-``dim`` slice, new local error buffer in ``err``'s dtype).
    """
    W = jax.lax.axis_size(axis_name)
    D = x.shape[dim]
    if D % W != 0:
        raise ValueError(f"dim {dim} of size {D} not divisible by axis {axis_name}={W}")
    comp = x.astype(jnp.float32) + err.astype(jnp.float32)
    moved = jnp.moveaxis(comp, dim, 0)
    rest_shape = moved.shape[1:]
    rows = moved.reshape(W, -1)
    m = rows.shape[1]
    pad = (-m) % block_size
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad)))

    payload, scales = _quantize_rows(rows, bits, block_size)
    # local residual BEFORE the exchange: what this rank failed to send
    deq_local = _dequantize_rows(payload, scales, bits, block_size)
    resid = (rows - deq_local)[:, :m].reshape((D,) + rest_shape)
    resid = jnp.moveaxis(resid, 0, dim)
    new_err = err_beta * err.astype(jnp.float32) + (1.0 - err_beta) * resid

    payload_rx = jax.lax.all_to_all(payload, axis_name, split_axis=0, concat_axis=0, tiled=True)
    scales_rx = jax.lax.all_to_all(scales, axis_name, split_axis=0, concat_axis=0, tiled=True)
    deq = _dequantize_rows(payload_rx, scales_rx, bits, block_size)
    total = jnp.sum(deq, axis=0)[:m]
    if mean:
        total = total / W
    out = total.reshape((D // W,) + rest_shape)
    return jnp.moveaxis(out, 0, dim).astype(x.dtype), new_err.astype(err.dtype)


def loco_quantized_allreduce(
    x: jax.Array,
    err: jax.Array,
    axis_name: str,
    bits: int = 8,
    block_size: int = 256,
    err_beta: float = 0.8,
    mean: bool = True,
):
    """LoCo error-feedback variant of :func:`quantized_allreduce` for
    replicated-gradient layouts: error feedback compensates the reduce hop
    (where the W-way quantization noise accumulates); the re-quantized
    gather hop stays plain — a deliberate single-buffer simplification of
    the reference's two-buffer intra/inter scheme (one ICI hop here).
    Returns (full averaged tensor, new local error buffer)."""
    W = jax.lax.axis_size(axis_name)
    n = x.size
    flat = x.reshape(-1).astype(jnp.float32) + err.reshape(-1).astype(jnp.float32)
    flat_p, _ = _pad_to(flat, W * block_size)
    chunk = flat_p.shape[0] // W
    rows = flat_p.reshape(W, chunk)

    payload, scales = _quantize_rows(rows, bits, block_size)
    deq_local = _dequantize_rows(payload, scales, bits, block_size)
    resid = (rows - deq_local).reshape(-1)[:n].reshape(x.shape)
    new_err = err_beta * err.astype(jnp.float32) + (1.0 - err_beta) * resid

    payload_rx = jax.lax.all_to_all(payload, axis_name, split_axis=0, concat_axis=0, tiled=True)
    scales_rx = jax.lax.all_to_all(scales, axis_name, split_axis=0, concat_axis=0, tiled=True)
    red = jnp.sum(_dequantize_rows(payload_rx, scales_rx, bits, block_size), axis=0)
    if mean:
        red = red / W
    # second hop: re-quantized all-gather of the reduced chunk (unchanged)
    rows2 = red.reshape(1, -1)
    pad2 = (-rows2.shape[1]) % block_size
    if pad2:
        rows2 = jnp.pad(rows2, ((0, 0), (0, pad2)))
    p2, s2 = _quantize_rows(rows2, bits, block_size)
    p_all = jax.lax.all_gather(p2, axis_name, axis=0, tiled=True)
    s_all = jax.lax.all_gather(s2, axis_name, axis=0, tiled=True)
    deq = _dequantize_rows(p_all, s_all, bits, block_size)
    full = deq[:, : red.shape[0]].reshape(-1)[:n]
    return full.reshape(x.shape).astype(x.dtype), new_err.astype(err.dtype)


def quantized_all_gather_along(
    x: jax.Array,
    axis_name: str,
    dim: int,
    bits: int = 8,
    block_size: int = 256,
) -> jax.Array:
    """qwZ: quantized parameter all-gather (reference zero_quantized_weights,
    stage3.py:1610 + csrc/quantization swizzled gather). Each rank quantizes
    its dim-``dim`` slice, int8 payload + fp32 block scales cross the wire,
    receivers dequantize — halving gather bytes vs bf16 weights. Call INSIDE
    shard_map over ``axis_name`` with the local slice; returns the full
    tensor along ``dim`` in ``x``'s dtype."""
    moved = jnp.moveaxis(x, dim, 0)
    rest_shape = moved.shape[1:]
    rows = moved.reshape(1, -1).astype(jnp.float32)
    m = rows.shape[1]
    pad = (-m) % block_size
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad)))
    payload, scales = _quantize_rows(rows, bits, block_size)
    payload_all = jax.lax.all_gather(payload, axis_name, axis=0, tiled=True)
    scales_all = jax.lax.all_gather(scales, axis_name, axis=0, tiled=True)
    deq = _dequantize_rows(payload_all, scales_all, bits, block_size)  # [W, m+pad]
    W = deq.shape[0]
    full = deq[:, :m].reshape((W * moved.shape[0],) + rest_shape)
    return jnp.moveaxis(full, 0, dim).astype(x.dtype)


# ---------------------------------------------------------------------------
# fp8 scaled casts (reference csrc/fp_quantizer/ FP6/FP8 paths)
# ---------------------------------------------------------------------------
def fp8_cast(x: jax.Array, dtype=jnp.float8_e4m3fn):
    """Tensor-scaled fp8 cast: returns (fp8 values, fp32 scale)."""
    finfo_max = jnp.finfo(dtype).max.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    scale = jnp.where(absmax > 0, absmax / finfo_max, 1.0)
    return (x.astype(jnp.float32) / scale).astype(dtype), scale


def fp8_uncast(values: jax.Array, scale: jax.Array, dtype=jnp.float32):
    return (values.astype(jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# low-bit float quantization: fp6 (e3m2, FP6-LLM) and fp12 (e4m7)
# Reference: csrc/fp_quantizer/ (quantize.cu templated on q_bits 6/8/12,
# wrapped by ops/fp_quantizer/quantize.py FP_Quantize with group_size scaling)
# ---------------------------------------------------------------------------
_FP_FORMATS = {6: (3, 2), 8: (4, 3), 12: (4, 7)}  # bits -> (exp_bits, man_bits)


def _round_to_fp(x, exp_bits, man_bits):
    """Round |x| to the nearest representable e{exp_bits}m{man_bits} value
    (RNE via float round-half-even of the mantissa grid), flushing
    sub-subnormals to zero and saturating at the format max."""
    bias = (1 << (exp_bits - 1)) - 1
    emin = 1 - bias  # smallest normal exponent
    emax = bias  # reserve nothing for inf/nan (reference formats are finite)
    ax = jnp.abs(x)
    e = jnp.floor(jnp.log2(jnp.maximum(ax, 1e-38)))
    e = jnp.clip(e, emin, emax)
    step = jnp.exp2(e - man_bits)
    q = jnp.round(ax / step) * step
    max_val = jnp.exp2(float(emax)) * (2.0 - jnp.exp2(-float(man_bits)))
    q = jnp.minimum(q, max_val)
    # below half the smallest subnormal -> 0
    min_sub = jnp.exp2(float(emin - man_bits))
    q = jnp.where(ax < min_sub / 2, 0.0, q)
    return jnp.sign(x) * q


def fp_quantize(x: jax.Array, q_bits: int = 6, group_size: int = 128):
    """Group-scaled low-bit float quantization (reference FP_Quantize.quantize):
    per-group absmax scaling into the format's range, then e/m rounding.
    Returns (values fp32 [*, groups, group_size] SIMULATED in the format,
    scales fp32) — the memory-format pack/unpack lives in ``fp_pack``."""
    if q_bits not in _FP_FORMATS:
        raise ValueError(f"q_bits must be one of {sorted(_FP_FORMATS)}, got {q_bits}")
    exp_bits, man_bits = _FP_FORMATS[q_bits]
    orig_shape = x.shape
    flat, _ = _pad_to(x.astype(jnp.float32).reshape(-1), group_size)
    groups = flat.reshape(-1, group_size)
    bias = (1 << (exp_bits - 1)) - 1
    fmt_max = 2.0 ** bias * (2.0 - 2.0 ** (-man_bits))
    absmax = jnp.max(jnp.abs(groups), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / fmt_max, 1.0)
    q = _round_to_fp(groups / scale, exp_bits, man_bits)
    return q, scale, orig_shape


def fp_dequantize(q, scale, orig_shape, dtype=jnp.float32):
    n = 1
    for s in orig_shape:
        n *= s
    return (q * scale).reshape(-1)[:n].reshape(orig_shape).astype(dtype)


def fp_pack(q: jax.Array, q_bits: int, exp_bits: int = None, man_bits: int = None):
    """Encode format-rounded values into integer codes and pack to uint8:
    fp6 packs 4 codes into 3 bytes, fp12 packs 2 codes into 3 bytes
    (reference swizzled packing, csrc/fp_quantizer/quantize.cu)."""
    if exp_bits is None:
        exp_bits, man_bits = _FP_FORMATS[q_bits]
    bias = (1 << (exp_bits - 1)) - 1
    sign = (q < 0).astype(jnp.uint32)
    ax = jnp.abs(q)
    e = jnp.clip(jnp.floor(jnp.log2(jnp.maximum(ax, 1e-38))), 1 - bias, bias)
    # subnormal handling: values below 2^emin encode with biased exp 0
    is_sub = ax < jnp.exp2(1.0 - bias)
    man_scale = jnp.where(is_sub, jnp.exp2(float(1 - bias - man_bits)),
                          jnp.exp2(e - man_bits))
    man = jnp.round(jnp.where(is_sub, ax, ax / jnp.exp2(e) - 1.0) *
                    jnp.where(is_sub, 1.0 / man_scale, 2.0 ** man_bits))
    man = jnp.clip(man, 0, (1 << man_bits) - 1).astype(jnp.uint32)
    biased = jnp.where(is_sub, 0, (e + bias).astype(jnp.uint32))
    code = (sign << (exp_bits + man_bits)) | (biased << man_bits) | man
    flat = code.reshape(-1)
    if q_bits == 6:
        flat, _ = _pad_to(flat, 4)
        flat = flat.reshape(-1, 4).astype(jnp.uint32)
        b0 = (flat[:, 0] | ((flat[:, 1] & 0x3) << 6)).astype(jnp.uint8)
        b1 = ((flat[:, 1] >> 2) | ((flat[:, 2] & 0xF) << 4)).astype(jnp.uint8)
        b2 = ((flat[:, 2] >> 4) | (flat[:, 3] << 2)).astype(jnp.uint8)
        return jnp.stack([b0, b1, b2], -1).reshape(-1)
    if q_bits == 12:
        flat, _ = _pad_to(flat, 2)
        flat = flat.reshape(-1, 2).astype(jnp.uint32)
        b0 = (flat[:, 0] & 0xFF).astype(jnp.uint8)
        b1 = ((flat[:, 0] >> 8) | ((flat[:, 1] & 0xF) << 4)).astype(jnp.uint8)
        b2 = (flat[:, 1] >> 4).astype(jnp.uint8)
        return jnp.stack([b0, b1, b2], -1).reshape(-1)
    return flat.astype(jnp.uint8)  # q_bits == 8: one code per byte


def fp_unpack(packed: jax.Array, n: int, q_bits: int):
    """Inverse of fp_pack -> fp32 values (pre-scale)."""
    exp_bits, man_bits = _FP_FORMATS[q_bits]
    bias = (1 << (exp_bits - 1)) - 1
    if q_bits == 6:
        trip = packed.reshape(-1, 3).astype(jnp.uint32)
        c0 = trip[:, 0] & 0x3F
        c1 = ((trip[:, 0] >> 6) | (trip[:, 1] << 2)) & 0x3F
        c2 = ((trip[:, 1] >> 4) | (trip[:, 2] << 4)) & 0x3F
        c3 = (trip[:, 2] >> 2) & 0x3F
        codes = jnp.stack([c0, c1, c2, c3], -1).reshape(-1)[:n]
    elif q_bits == 12:
        trip = packed.reshape(-1, 3).astype(jnp.uint32)
        c0 = trip[:, 0] | ((trip[:, 1] & 0xF) << 8)
        c1 = (trip[:, 1] >> 4) | (trip[:, 2] << 4)
        codes = jnp.stack([c0, c1], -1).reshape(-1)[:n]
    else:
        codes = packed.astype(jnp.uint32)[:n]
    sign = jnp.where((codes >> (exp_bits + man_bits)) & 1, -1.0, 1.0)
    biased = (codes >> man_bits) & ((1 << exp_bits) - 1)
    man = (codes & ((1 << man_bits) - 1)).astype(jnp.float32)
    is_sub = biased == 0
    mag = jnp.where(
        is_sub,
        man * jnp.exp2(float(1 - bias - man_bits)),
        (1.0 + man * 2.0 ** (-man_bits)) * jnp.exp2(biased.astype(jnp.float32) - bias),
    )
    return sign * mag


# ---------------------------------------------------------------------------
# Pallas kernel path (TPU): fused absmax + scale + round in VMEM, optional
# in-kernel stochastic rounding via the TPU PRNG
# ---------------------------------------------------------------------------
def _quant_kernel(seed_ref, x_ref, v_ref, s_ref, *, qmax, stochastic):
    from jax.experimental.pallas import tpu as pltpu

    blk = x_ref[:].astype(jnp.float32)  # [rows, block]
    absmax = jnp.max(jnp.abs(blk), axis=-1, keepdims=True)
    scale = absmax / qmax
    inv = jnp.where(scale > 0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    scaled = blk * inv
    if stochastic:
        import jax.experimental.pallas as pl

        pltpu.prng_seed(seed_ref[0, 0] + pl.program_id(0))
        bits = pltpu.prng_random_bits(scaled.shape)
        # top 24 bits → uniform [0, 1) → centered noise [-0.5, 0.5)
        u = (bits >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
        scaled = scaled + (u - 0.5)
    v_ref[:] = jnp.clip(jnp.round(scaled), -qmax, qmax).astype(jnp.int8)
    s_ref[:] = jnp.broadcast_to(scale, s_ref.shape)


def quantize_blockwise_pallas(
    x: jax.Array,
    bits: int = 8,
    block_size: int = 2048,
    stochastic: bool = False,
    seed: int = 0,
    interpret: bool = False,
) -> QuantizedTensor:
    """Pallas path: one VMEM pass per row-block (int8 layout; int4 packing is
    a host-side post-pass)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    qmax = _QMAX[bits]
    flat = x.reshape(-1)
    flat, _ = _pad_to(flat, block_size * 8)
    rows = flat.shape[0] // block_size
    blocks = flat.reshape(rows, block_size)
    row_tile = 8
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1, 1)

    values, scales = pl.pallas_call(
        functools.partial(_quant_kernel, qmax=qmax, stochastic=stochastic),
        grid=(rows // row_tile,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((row_tile, block_size), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((row_tile, block_size), lambda i: (i, 0)),
            pl.BlockSpec((row_tile, 128), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, block_size), jnp.int8),
            jax.ShapeDtypeStruct((rows, 128), jnp.float32),
        ],
        interpret=interpret,
        name=BLOCK_QUANT,
    )(seed_arr, blocks)
    if bits == 4:
        values = _pack_int4(values.astype(jnp.float32))
    return QuantizedTensor(
        values=values,
        scales=scales[:, 0],
        shape=tuple(x.shape),
        bits=bits,
        block_size=block_size,
    )
