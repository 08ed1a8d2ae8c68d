"""Fused RMSNorm / LayerNorm Pallas kernels with custom VJP.

Reference: csrc/transformer/inference/csrc/rms_norm.cu, layer_norm.cu
(fused_rms_norm / fused_ln bindings, pt_binding.cpp). Forward computes the
row statistics and normalized output in one VMEM pass; backward recomputes
statistics (cheaper than storing them for long rows) and reduces the weight
grads across the row grid.
"""

import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.accelerator.device import on_tpu

# names of the Mosaic custom calls in a device trace (metadata only)
RMSNORM_FWD = "dstpu_rmsnorm_fwd"
RMSNORM_BWD = "dstpu_rmsnorm_bwd"


def rms_norm_reference(x, w, eps=1e-5):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def layer_norm_reference(x, w, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def _rms_fwd_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)  # [rows, h]
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    o_ref[:] = (x * inv * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _rms_bwd_kernel(x_ref, w_ref, g_ref, dx_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    inv = jax.lax.rsqrt(ms)
    xhat = x * inv
    gw = g * w
    # dx = inv * (gw - xhat * mean(gw * xhat))
    dot = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx_ref[:] = (inv * (gw - xhat * dot)).astype(dx_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_rms_norm(x, w, eps=1e-5, interpret=False):
    """x: [..., h]; w: [h]. Pallas on TPU, jnp elsewhere unless interpret."""
    out, _ = _rms_fwd(x, w, eps, interpret)
    return out


def _use_pallas(interpret):
    # single-shard gate only: multi-device dispatch happens in rms_norm(),
    # which runs this kernel per-shard under shard_map
    return interpret or on_tpu()


def _rows_view(x):
    h = x.shape[-1]
    return x.reshape(-1, h), x.shape


def _pick_rows(n_rows, h=0):
    # cap rows*h so the kernel's fp32 scratch stays under the ~16 MB scoped
    # VMEM limit: 256 rows at h=4096 is 16.1 MB of stack and fails to compile
    max_rows = 256
    while h and max_rows > 1 and max_rows * h > (1 << 19):
        max_rows //= 2
    for r in (256, 128, 64, 32, 16, 8, 4, 2, 1):
        if r <= max_rows and n_rows % r == 0:
            return r
    return 1


def _rms_fwd(x, w, eps, interpret):
    if not _use_pallas(interpret):
        return rms_norm_reference(x, w, eps), (x, w)
    from jax.experimental import pallas as pl

    x2, shape = _rows_view(x)
    n, h = x2.shape
    rows = _pick_rows(n, h)
    out = pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps),
        grid=(n // rows,),
        in_specs=[
            pl.BlockSpec((rows, h), lambda i: (i, 0)),
            pl.BlockSpec((h,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((rows, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h), x.dtype),
        interpret=interpret,
        name=RMSNORM_FWD,
    )(x2, w)
    return out.reshape(shape), (x, w)


def _rms_bwd(eps, interpret, res, g):
    x, w = res
    if not _use_pallas(interpret):
        def f(x, w):
            return rms_norm_reference(x, w, eps)

        _, vjp = jax.vjp(f, x, w)
        return vjp(g)
    from jax.experimental import pallas as pl

    x2, shape = _rows_view(x)
    g2, _ = _rows_view(g)
    n, h = x2.shape
    rows = _pick_rows(n, h)
    dx = pl.pallas_call(
        functools.partial(_rms_bwd_kernel, eps=eps),
        grid=(n // rows,),
        in_specs=[
            pl.BlockSpec((rows, h), lambda i: (i, 0)),
            pl.BlockSpec((h,), lambda i: (0,)),
            pl.BlockSpec((rows, h), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h), x.dtype),
        interpret=interpret,
        name=RMSNORM_BWD,
    )(x2, w, g2)
    # dw reduction is one fused elementwise+sum in XLA; keeping it out of the
    # kernel avoids the (8,128) output-tile constraint on the [1, h] partial
    xf = x2.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    dw = jnp.sum(g2.astype(jnp.float32) * xf * inv, axis=0).astype(w.dtype)
    return dx.reshape(shape), dw


fused_rms_norm.defvjp(_rms_fwd, _rms_bwd)


def rms_norm(x, w, eps=1e-5, interpret=False):
    """Mesh-aware RMSNorm entry point (the one model code should call).

    Single device: the Pallas kernel directly. Multi-device mesh: pallas_call
    is opaque to GSPMD, so the activation is pinned to the canonical layout
    (batch over data/expert, seq over sequence, h replicated) and the kernel
    runs per-shard under a fully-manual shard_map — same pattern as
    ops/attention/core._flash_sharded. shard_map is differentiable: w enters
    replicated (P()), so its cotangent is psum'd across shards by the
    transpose, and dx stays in the activation layout. Falls back to the jnp
    reference whenever the layout preconditions don't hold.
    """
    if not _use_pallas(interpret):
        return rms_norm_reference(x, w, eps)

    from deepspeed_tpu.parallel.topology import get_topology

    topo = get_topology()
    if topo.world_size == 1:
        return fused_rms_norm(x, w, eps, interpret)
    if x.ndim != 3:
        return rms_norm_reference(x, w, eps)
    b, s, _h = x.shape
    batch_div = topo.data_parallel_size * topo.expert_parallel_size
    seq_div = topo.sequence_parallel_size
    if b % batch_div or s % seq_div:
        return rms_norm_reference(x, w, eps)

    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.parallel.topology import (
        BATCH_AXES,
        SEQUENCE_AXIS,
        manual_over,
    )

    spec = P(BATCH_AXES, SEQUENCE_AXIS, None)
    x = jax.lax.with_sharding_constraint(x, NamedSharding(topo.mesh, spec))
    fn = manual_over(
        lambda x_, w_: fused_rms_norm(x_, w_, eps, interpret),
        topo.mesh, (spec, P()), spec,
    )
    return fn(x, w)


def fused_layer_norm(x, w, b, eps=1e-5):
    """LayerNorm: jnp semantics (XLA fuses this well already); kept as the
    single entry point so a Pallas variant can swap in transparently."""
    return layer_norm_reference(x, w, b, eps)
