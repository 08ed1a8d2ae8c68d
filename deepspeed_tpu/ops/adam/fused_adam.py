"""Fused Adam: single-pass m/v/param update as a Pallas kernel.

Reference: ``multi_tensor_adam.cu`` (csrc/adam/fused_adam_frontend.cpp:22) —
one fused CUDA kernel updating many tensors; and ``cpu_adam_impl.cpp`` for
the offloaded variant. On TPU the fused update is one VMEM pass; XLA already
fuses the optax elementwise chain into comparable code, so the Pallas kernel
exists for the op_builder parity surface and as the building block for the
offload tier's host-batched updates; numerics are bit-compatible with the
jnp path (tests/unit/ops/test_fused_adam.py).
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.accelerator.device import on_tpu

# names of the Mosaic custom calls in a device trace (metadata only)
FUSED_ADAM = "dstpu_fused_adam"


class AdamParams(NamedTuple):
    lr: float = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    adam_w_mode: bool = True
    bias_correction: bool = True


def _adam_math(p, g, m, v, step, hp: AdamParams, lr, c1=None, c2=None):
    """The update shared by every path (matches reference Adam semantics:
    adam_w_mode=True → AdamW decoupled decay, else L2-into-grad).

    ``c1``/``c2`` optionally carry precomputed bias corrections — the Pallas
    kernel passes them in because Mosaic cannot lower a traced-exponent
    ``pow`` inside the kernel body."""
    g = g.astype(jnp.float32)
    p32 = p.astype(jnp.float32)
    if not hp.adam_w_mode and hp.weight_decay:
        g = g + hp.weight_decay * p32
    m_new = hp.beta1 * m + (1 - hp.beta1) * g
    v_new = hp.beta2 * v + (1 - hp.beta2) * jnp.square(g)
    if hp.bias_correction:
        if c1 is None:
            c1 = 1 - hp.beta1 ** step
        if c2 is None:
            c2 = 1 - hp.beta2 ** step
        update = (m_new / c1) / (jnp.sqrt(v_new / c2) + hp.eps)
    else:
        update = m_new / (jnp.sqrt(v_new) + hp.eps)
    if hp.adam_w_mode and hp.weight_decay:
        update = update + hp.weight_decay * p32
    return (p32 - lr * update).astype(p.dtype), m_new, v_new


def _fused_kernel(lr_ref, c1_ref, c2_ref, p_ref, g_ref, m_ref, v_ref, po_ref, mo_ref, vo_ref, *, hp):
    lr = lr_ref[0, 0]
    p_new, m_new, v_new = _adam_math(
        p_ref[:], g_ref[:], m_ref[:], v_ref[:], None, hp, lr,
        c1=c1_ref[0, 0], c2=c2_ref[0, 0],
    )
    po_ref[:] = p_new
    mo_ref[:] = m_new
    vo_ref[:] = v_new


def fused_adam_step(
    params: jax.Array,
    grads: jax.Array,
    m: jax.Array,
    v: jax.Array,
    step,
    hp: AdamParams = AdamParams(),
    lr=None,
    block: int = 2048,
    interpret: bool = False,
):
    """Pallas fused update over ONE flat shard. Returns (params, m, v)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lr = jnp.asarray(hp.lr if lr is None else lr, jnp.float32).reshape((1, 1))
    stepf = jnp.asarray(step, jnp.float32).reshape((1, 1))
    c1 = 1.0 - hp.beta1 ** stepf  # bias corrections computed outside the
    c2 = 1.0 - hp.beta2 ** stepf  # kernel (Mosaic can't lower traced pow)
    orig_shape = params.shape
    n = params.size
    flat = lambda a, dt: a.reshape(-1).astype(dt)
    p, g = flat(params, params.dtype), flat(grads, jnp.float32)
    mm, vv = flat(m, jnp.float32), flat(v, jnp.float32)
    pad = (-n) % (block * 8)
    if pad:
        zpad = lambda a: jnp.concatenate([a, jnp.zeros((pad,), a.dtype)])
        p, g, mm, vv = zpad(p), zpad(g), zpad(mm), zpad(vv)
    rows = p.shape[0] // block
    shape2 = (rows, block)
    p, g, mm, vv = (a.reshape(shape2) for a in (p, g, mm, vv))

    p_new, m_new, v_new = pl.pallas_call(
        functools.partial(_fused_kernel, hp=hp),
        grid=(rows // 8,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((8, block), lambda i: (i, 0)),
            pl.BlockSpec((8, block), lambda i: (i, 0)),
            pl.BlockSpec((8, block), lambda i: (i, 0)),
            pl.BlockSpec((8, block), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((8, block), lambda i: (i, 0)),
            pl.BlockSpec((8, block), lambda i: (i, 0)),
            pl.BlockSpec((8, block), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(shape2, params.dtype),
            jax.ShapeDtypeStruct(shape2, jnp.float32),
            jax.ShapeDtypeStruct(shape2, jnp.float32),
        ],
        interpret=interpret,
        name=FUSED_ADAM,
    )(lr, c1, c2, p, g, mm, vv)
    unflat = lambda a: a.reshape(-1)[:n].reshape(orig_shape)
    return unflat(p_new), unflat(m_new), unflat(v_new)


class FusedAdamState(NamedTuple):
    m: any
    v: any
    count: jnp.ndarray


def _spec_axes(spec):
    """Flat tuple of mesh axis names appearing in a PartitionSpec."""
    axes = []
    for entry in tuple(spec or ()):
        if entry is None:
            continue
        axes.extend(entry if isinstance(entry, (tuple, list)) else (entry,))
    return tuple(axes)


def _shardable(shape, spec, mesh) -> bool:
    """Every sharded dim must divide evenly for shard_map."""
    for i, entry in enumerate(tuple(spec)):
        if entry is None:
            continue
        k = 1
        for a in entry if isinstance(entry, (tuple, list)) else (entry,):
            k *= mesh.shape[a]
        if i >= len(shape) or shape[i] % k:
            return False
    return True


def _sharded_adam_step(p, g, m, v, count, hp, lr, spec, mesh, interpret):
    """Per-shard Pallas update under partial-manual shard_map: each device
    runs the fused kernel on its local slice of the ZeRO-partitioned
    p/g/m/v (the TPU form of the reference's per-partition multi_tensor
    update, stage_1_and_2.py step). Axes not in ``spec`` stay automatic,
    so this composes with the surrounding GSPMD program."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, spec)
    p, g, m, v = (jax.lax.with_sharding_constraint(x, sharding) for x in (p, g, m, v))
    fn = jax.shard_map(
        lambda p_, g_, m_, v_, c_, lr_: fused_adam_step(
            p_, g_, m_, v_, c_, hp, lr_, interpret=interpret
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec, spec, P(), P()),
        out_specs=(spec, spec, spec),
        axis_names=set(_spec_axes(spec)),
        check_vma=False,
    )
    return fn(p, g, m, v, count, jnp.asarray(lr, jnp.float32))


def fused_adam_transform(
    hp: AdamParams = AdamParams(),
    use_pallas: bool = None,
    master_specs=None,
    mesh=None,
    interpret: bool = False,
):
    """optax-contract transformation: ``update(grads, state, params, lr) ->
    (updates, new_state)`` where ``params + updates`` is the fused-Adam
    result — pluggable into DeepSpeedOptimizer.step's ``apply_updates`` flow.

    Single device: the Pallas kernel runs on whole leaves. Multi-device mesh
    with ``master_specs``/``mesh`` provided (the engine plumbs its ZeRO
    plan): the kernel runs per-shard under shard_map on each leaf's own
    partition layout — no gather, optimizer state stays ZeRO-partitioned.
    The jnp path (XLA-fused) defines the semantics everywhere else."""
    import optax

    if use_pallas is None:
        use_pallas = interpret or on_tpu()
    single_device = True
    if mesh is not None:
        single_device = mesh.size == 1
    else:
        from deepspeed_tpu.parallel.topology import get_topology

        single_device = get_topology().world_size == 1
    sharded = use_pallas and not single_device and master_specs is not None and mesh is not None

    flat_specs = None
    if sharded:
        from jax.sharding import PartitionSpec

        is_spec = lambda x: x is None or isinstance(x, PartitionSpec)
        flat_specs = jax.tree_util.tree_leaves(master_specs, is_leaf=is_spec)

    def init(params):
        z = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return FusedAdamState(m=z, v=jax.tree.map(jnp.copy, z), count=jnp.zeros((), jnp.int32))

    def update(grads, state, params=None, *, lr):
        if params is None:
            raise ValueError("fused adam needs params")
        count = state.count + 1
        stepf = count.astype(jnp.float32)

        def leaf(p, g, m, v, spec=None):
            if use_pallas and p.size >= 1 << 16:
                if (
                    sharded
                    and spec is not None
                    and _spec_axes(spec)
                    and _shardable(p.shape, spec, mesh)
                ):
                    p_new, m_new, v_new = _sharded_adam_step(
                        p, g, m, v, count, hp, lr, spec, mesh, interpret
                    )
                elif single_device:
                    p_new, m_new, v_new = fused_adam_step(
                        p, g, m, v, count, hp, lr, interpret=interpret
                    )
                else:  # multi-device but this leaf has no usable spec
                    p_new, m_new, v_new = _adam_math(p, g.astype(jnp.float32), m, v, stepf, hp, lr)
            else:
                p_new, m_new, v_new = _adam_math(p, g.astype(jnp.float32), m, v, stepf, hp, lr)
            return (p_new - p).astype(p.dtype), m_new, v_new

        treedef = jax.tree_util.tree_structure(params)
        flat_p = treedef.flatten_up_to(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state.m)
        flat_v = treedef.flatten_up_to(state.v)
        specs = flat_specs if flat_specs is not None else [None] * len(flat_p)
        flat = [
            leaf(p, g, m, v, s)
            for p, g, m, v, s in zip(flat_p, flat_g, flat_m, flat_v, specs)
        ]
        updates = treedef.unflatten([o[0] for o in flat])
        new_m = treedef.unflatten([o[1] for o in flat])
        new_v = treedef.unflatten([o[2] for o in flat])
        return updates, FusedAdamState(m=new_m, v=new_v, count=count)

    return optax.GradientTransformation(init, update)


class FusedAdam:
    """API-parity wrapper (reference ops/adam/FusedAdam): hyperparams + the
    optax-contract transform, consumed by runtime/optimizers.build_optimizer
    for config ``{"optimizer": {"type": "FusedAdam"}}``."""

    def __init__(self, lr=1e-3, betas=(0.9, 0.999), eps=1e-8, weight_decay=0.0,
                 adam_w_mode=True, bias_correction=True, master_specs=None,
                 mesh=None, interpret=False):
        self.hp = AdamParams(
            lr=lr, beta1=betas[0], beta2=betas[1], eps=eps,
            weight_decay=weight_decay, adam_w_mode=adam_w_mode,
            bias_correction=bias_correction,
        )
        tx = fused_adam_transform(
            self.hp, master_specs=master_specs, mesh=mesh, interpret=interpret
        )
        self.init, self.update = tx.init, tx.update
