"""FPDT — fully-pipelined distributed transformer (Ulysses-Offload).

Analogue of the reference ``sequence/fpdt_layer.py:79`` (``FPDT_InputConstruct``
+ the offloaded chunked-attention autograd functions): attention over very
long sequences processes q in CHUNKS with online-softmax merging, and the
K/V for already-processed chunks rests in HOST memory instead of HBM —
per-chunk peak device memory is O(chunk × s_chunk) instead of O(s²)/O(s).

TPU-native form:
  * the chunk loop is a ``lax.scan`` (online merge identical to flash);
  * KV host placement uses the same ``pinned_host`` memory-kind machinery as
    the ZeRO-Offload tier — ``jax.device_put`` inside jit becomes an async
    D2H/H2D the XLA scheduler overlaps with the neighbor chunk's compute
    (the reference's hand-rolled double buffering);
  * composes with Ulysses: run this as the local attention under the
    head-scattered layout for sequence lengths past the dense ceiling.

Host offload is TPU-only (the CPU test backend rejects memory-kind
annotations inside SPMD programs — same gate as the offload tier); elsewhere
the math is identical with KV device-resident.
"""

from typing import Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.accelerator.device import on_tpu
from deepspeed_tpu.parallel.sequence.ring import (
    NEG_INF,
    _local_attention_stats,
    block_causal_bias,
    make_block_biases,
)


def _chunk(x, n_chunks, axis):
    s = x.shape[axis]
    if s % n_chunks != 0:
        raise ValueError(f"seq {s} not divisible by {n_chunks} chunks")
    moved = jnp.moveaxis(x, axis, 0)
    return moved.reshape((n_chunks, s // n_chunks) + moved.shape[1:])


def fpdt_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    n_chunks: int = 4,
    causal: bool = True,
    scale: Optional[float] = None,
    offload_kv: Optional[bool] = None,
) -> jax.Array:
    """Chunked attention with online merging. q/k/v: [b, h|hk, s, d] (full
    or Ulysses-local). Peak score memory is (s/n_chunks)² per chunk pair.

    offload_kv: place the chunked K/V in pinned_host between uses (default:
    on for the TPU backend). Gradients flow through the placement ops.
    """
    b, h, s, d = q.shape
    if offload_kv is None:
        offload_kv = on_tpu()

    sc = s // n_chunks
    qc = _chunk(q, n_chunks, 2).reshape(n_chunks, sc, -1)  # scan xs stay 3-D
    q_rest = (sc, b, h, d)
    # K/V chunks become SEPARATE per-chunk arrays and the inner loop unrolls:
    # dynamic-slicing a host-resident (or high-rank bf16) buffer inside scan
    # trips XLA TPU layout RET_CHECKs, and separate buffers also let each
    # chunk's H2D start as soon as the schedule allows
    kc_list = [k[:, :, j * sc : (j + 1) * sc] for j in range(n_chunks)]
    vc_list = [v[:, :, j * sc : (j + 1) * sc] for j in range(n_chunks)]

    to_device = lambda x: x  # noqa: E731
    if offload_kv:
        try:
            from jax.sharding import NamedSharding, PartitionSpec

            from deepspeed_tpu.parallel.topology import get_topology

            mesh = get_topology().mesh
            host = NamedSharding(mesh, PartitionSpec(), memory_kind="pinned_host")
            dev = NamedSharding(mesh, PartitionSpec())
            kc_list = [jax.device_put(x, host) for x in kc_list]
            vc_list = [jax.device_put(x, host) for x in vc_list]
            # each chunk stages back into HBM just before use (the async H2D
            # XLA overlaps with the previous chunk's attention)
            to_device = lambda x: jax.device_put(x, dev)  # noqa: E731
        except Exception:
            pass  # placement unsupported: keep device-resident, math unchanged

    diag_bias, zero_bias, full_mask = make_block_biases(sc)

    def q_chunk_body(_, qi_and_idx):
        q_i, i = qi_and_idx
        q_i = jnp.moveaxis(q_i.reshape(q_rest), 0, 2).astype(jnp.float32)  # [b, h, sc, d]

        acc = jnp.zeros(q_i.shape, jnp.float32)
        m_run = jnp.full(q_i.shape[:3], NEG_INF, jnp.float32)
        l_run = jnp.zeros(q_i.shape[:3], jnp.float32)
        for j in range(n_chunks):  # unrolled: j static, i traced
            k_j = to_device(kc_list[j])
            v_j = to_device(vc_list[j])
            if causal:
                bias = block_causal_bias(sc, jnp.int32(j), i, diag_bias, zero_bias, full_mask)
            else:
                bias = zero_bias
            out_b, m_b, l_b = _local_attention_stats(q_i, k_j, v_j, bias, scale)
            m_new = jnp.maximum(m_run, m_b)
            alpha = jnp.exp(m_run - m_new)
            beta = jnp.exp(m_b - m_new)
            acc = acc * alpha[..., None] + out_b * beta[..., None]
            l_run = l_run * alpha + l_b * beta
            m_run = m_new
        out = acc / jnp.maximum(l_run[..., None], 1e-30)
        return None, jnp.moveaxis(out, 2, 0).reshape(sc, -1)  # [sc, F]

    _, out_chunks = jax.lax.scan(q_chunk_body, None, (qc, jnp.arange(n_chunks)))
    out = out_chunks.reshape((s,) + q_rest[1:])  # [s, b, h, d]
    return jnp.moveaxis(out, 0, 2).astype(q.dtype)
