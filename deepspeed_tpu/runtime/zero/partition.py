"""ZeRO as a GSPMD sharding plan.

The TPU-native re-design of the reference ZeRO implementations:
  * stage 1/2 (``DeepSpeedZeroOptimizer`` runtime/zero/stage_1_and_2.py:125 —
    flattened partitions, IPG bucketing, allgather of updated partitions)
  * stage 3 (``DeepSpeedZeroOptimizer_Stage3`` runtime/zero/stage3.py:129 +
    ``partition_parameters.py`` + ``partitioned_param_coordinator.py`` —
    gather-on-demand hooks, trace-based prefetch)

On TPU none of that machinery is hand-built: ZeRO *is a sharding assignment*.

  stage 0: params/grads/opt-state replicated over ``data`` (grads psum'd)
  stage 1: optimizer state (fp32 master + moments) sharded over ``data``
  stage 2: + gradients constrained to the sharded layout → XLA emits
           reduce-scatter instead of all-reduce (the ``average_tensor``
           hot loop, stage_1_and_2.py:1159)
  stage 3: + parameters sharded over ``data``; XLA inserts all-gathers at
           each use and its latency-hiding scheduler overlaps them with
           compute (replacing fetch/release hooks + prefetching,
           partitioned_param_coordinator.py:285)

Persistence threshold (`param_persistence_threshold`, stage3.py): leaves with
fewer elements stay replicated — same memory/latency trade the reference
makes for small params.

Sharding rule per leaf: place ``data`` on the largest dimension divisible by
the data-axis size that is not already taken by a model/expert/sequence axis
from tensor-parallel sharding rules (``base_specs``).
"""

from dataclasses import dataclass
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.parallel.topology import DATA_AXIS, ZERO_AXES, Topology


def host_memory_kind() -> Optional[str]:
    """``"pinned_host"`` when the default device exposes that memory kind,
    else ``None`` (NamedSharding reads ``memory_kind=None`` as the default
    memory, so offload placements degrade to numerics-only)."""
    kinds = {m.kind for m in jax.devices()[0].addressable_memories()}
    return "pinned_host" if "pinned_host" in kinds else None


def _spec_axes(spec: Optional[PartitionSpec]):
    """Set of mesh-axis names already used by a PartitionSpec."""
    used = set()
    if spec is None:
        return used
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            used.update(entry)
        else:
            used.add(entry)
    return used


def choose_zero_spec(
    shape,
    axis_size: int,
    base_spec: Optional[PartitionSpec] = None,
    axes=(DATA_AXIS,),
) -> PartitionSpec:
    """Add the ZeRO axes (``data``/``zero`` or a MiCS subset) to a leaf's
    PartitionSpec on the best free dim. ``axis_size`` is the product of the
    participating axis sizes; trivial (size-1) axes are dropped from the
    placement so specs stay readable."""
    axes = tuple(a for a in axes)
    if axis_size <= 1:
        return base_spec if base_spec is not None else PartitionSpec()
    placement = axes[0] if len(axes) == 1 else tuple(axes)
    base = tuple(base_spec) if base_spec is not None else ()
    base = base + (None,) * (len(shape) - len(base))
    used = _spec_axes(base_spec)
    if any(a in used for a in axes):
        return PartitionSpec(*base)
    # candidate dims: unsharded by base spec and divisible by axis_size
    best_dim, best_size = None, 0
    for i, d in enumerate(shape):
        taken = i < len(base) and base[i] is not None
        if taken:
            # dim already sharded by e.g. model axis; data can nest with it
            # only if the residual size divides. Handled below via tuple merge.
            continue
        if d % axis_size == 0 and d > best_size:
            best_dim, best_size = i, d
    if best_dim is None:
        # try nesting the zero axes inside an already-sharded dim
        for i, d in enumerate(shape):
            if i < len(base) and base[i] is not None:
                prev = base[i] if isinstance(base[i], tuple) else (base[i],)
                if not any(a in prev for a in axes) and d % (axis_size * _axes_product(prev)) == 0:
                    new = list(base)
                    new[i] = tuple(prev) + axes
                    return PartitionSpec(*new)
        return PartitionSpec(*base)  # replicated over data (e.g. odd-shaped scalars)
    new = list(base)
    new[best_dim] = placement
    return PartitionSpec(*new)


def _axes_product(axes):
    from deepspeed_tpu.parallel.topology import get_topology

    topo = get_topology()
    out = 1
    for a in axes:
        out *= topo.axis_size(a)
    return out


@dataclass
class ZeroShardingPlan:
    """Per-pytree NamedShardings implementing a ZeRO stage."""

    stage: int
    topology: Topology
    param_shardings: Any  # how model (half) params live
    grad_shardings: Any  # constraint applied to grads before the optimizer
    master_shardings: Any  # fp32 master + optimizer moments
    param_specs: Any
    grad_specs: Any
    master_specs: Any
    persistence_threshold: int = 0
    # ZeRO-Offload tiers (reference offload_config.py): state/params live in
    # host memory ("pinned_host" memory kind) instead of HBM
    offload_optimizer: bool = False
    offload_param: bool = False
    # Twin-Flow partial offload (reference engine.py:921): fraction of
    # optimizer-state BYTES placed host-side; largest leaves offload first so
    # the fewest leaves pay the transfer. 1.0 = everything offloads.
    offload_ratio: float = 1.0
    # MiCS/hpZ: which mesh axes params vs optimizer state shard over
    # (ZERO_AXES = full dp; ("zero",) = within the shard group only)
    param_zero_axes: tuple = ZERO_AXES
    state_zero_axes: tuple = ZERO_AXES

    @property
    def state_memory_kind(self):
        return host_memory_kind() if self.offload_optimizer else None

    @property
    def param_memory_kind(self):
        return host_memory_kind() if self.offload_param else None

    def device_shardings(self, shardings):
        """The HBM-resident twin of a (possibly host-kind) sharding tree —
        used to stage offloaded state onto the chip around the update. No
        explicit memory kind: the default is device memory, and kind-less
        shardings avoid placement annotations that the CPU backend's SPMD
        partitioner rejects on scalars."""
        return jax.tree.map(
            lambda s: NamedSharding(s.mesh, s.spec),
            shardings,
            is_leaf=lambda x: isinstance(x, NamedSharding),
        )

    def state_shardings(self, state_shape_tree):
        """Shardings for an optimizer-state pytree (from ``jax.eval_shape`` of
        ``opt.init``). Optimizer moments mirror param shapes, so each array
        leaf gets the stage's master sharding rule applied to its own shape;
        scalars (step counts) are replicated. This is how the reference's
        per-partition optimizer state (stage_1_and_2.py ``single_partition_of_
        fp32_groups``) falls out of the sharding rule for free."""
        axes = tuple(a for a in self.state_zero_axes if self.topology.axis_size(a) > 1)
        axis_size = 1
        for a in axes:
            axis_size *= self.topology.axis_size(a)
        mesh = self.topology.mesh
        stage = self.stage

        kind = self.state_memory_kind
        # Twin-Flow: offload only `offload_ratio` of the state bytes —
        # largest leaves first — leaving the rest in HBM
        host_leaf = self._partial_offload_mask(state_shape_tree) if kind else None

        def leaf_sharding(leaf, offloaded=True):
            shape = tuple(getattr(leaf, "shape", ()))
            if stage >= 1 and shape:
                spec = choose_zero_spec(shape, axis_size, None, axes=axes or (DATA_AXIS,))
            else:
                spec = PartitionSpec()
            # scalars (step counts) stay in device memory: XLA's SPMD
            # partitioner rejects host-placement annotations on scalar
            # side-effect custom-calls, and 4 bytes buys nothing offloaded
            if kind is not None and shape and offloaded:
                return NamedSharding(mesh, spec, memory_kind=kind)
            return NamedSharding(mesh, spec)

        if host_leaf is None:
            return jax.tree.map(leaf_sharding, state_shape_tree)
        return jax.tree.map(leaf_sharding, state_shape_tree, host_leaf)

    def _partial_offload_mask(self, state_shape_tree):
        """Boolean-per-leaf tree: True = leaf lives host-side. Greedy by
        descending size until ``offload_ratio`` of total bytes is host-bound."""
        flat, treedef = jax.tree_util.tree_flatten(state_shape_tree)
        sizes = [
            int(np.prod(getattr(l, "shape", ()) or (1,)))
            * np.dtype(getattr(l, "dtype", np.float32)).itemsize
            for l in flat
        ]
        if self.offload_ratio >= 1.0:
            return jax.tree_util.tree_unflatten(treedef, [True] * len(flat))
        budget = self.offload_ratio * sum(sizes)
        mask = [False] * len(flat)
        cum = 0
        for i in sorted(range(len(flat)), key=lambda j: -sizes[j]):
            if cum >= budget:
                break
            mask[i] = True
            cum += sizes[i]
        return jax.tree_util.tree_unflatten(treedef, mask)


def build_zero_plan(
    stage: int,
    topology: Topology,
    params: Any,
    persistence_threshold: int = 0,
    base_specs: Any = None,
    zero_axes=ZERO_AXES,
    param_zero_axes=None,
    offload_optimizer: bool = False,
    offload_param: bool = False,
    offload_ratio: float = 1.0,
) -> ZeroShardingPlan:
    """Construct the stage's sharding plan over a params pytree.

    ``base_specs`` optionally carries tensor/expert-parallel PartitionSpecs
    per leaf (the AutoTP analogue); ZeRO composes with them by choosing a
    free dimension. ``zero_axes`` shard optimizer state + gradients;
    ``param_zero_axes`` (default = same) shard the parameters — MiCS/hpZ
    restrict it to the ``zero`` shard-group axis so param gathers stay
    intra-group while grads still reduce over the whole dp world.
    """
    if param_zero_axes is None:
        param_zero_axes = zero_axes

    def live(axes):
        return tuple(a for a in axes if topology.axis_size(a) > 1)

    def size_of(axes):
        out = 1
        for a in axes:
            out *= topology.axis_size(a)
        return out

    state_axes = live(zero_axes)
    param_axes = live(param_zero_axes)
    state_size = size_of(state_axes)
    param_size = size_of(param_axes)
    mesh = topology.mesh

    flat_params, treedef = jax.tree_util.tree_flatten(params)
    if base_specs is None:
        flat_base = [None] * len(flat_params)
    else:
        # base_specs mirrors the params structure with PartitionSpec/None leaves
        flat_base = treedef.flatten_up_to(base_specs)

    def leaf_shape(p):
        return tuple(p.shape) if hasattr(p, "shape") else ()

    def strip_trivial(base):
        """Drop size-1 mesh axes from a base spec so the dims they nominally
        occupy stay candidates for the zero axes. Without this an embed table
        with base P('model', None) under model=1 pushes the zero shard onto
        the hidden dim — and the backward scatter-add then reshards the
        batch-sharded cotangent to hidden-sharded via an involuntary full
        rematerialization (whole-tensor replication per step)."""
        if base is None:
            return None
        out = []
        for e in tuple(base):
            if isinstance(e, tuple):
                kept = tuple(a for a in e if topology.axis_size(a) > 1)
                out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
            elif e is None or topology.axis_size(e) > 1:
                out.append(e)
            else:
                out.append(None)
        return PartitionSpec(*out)

    def sharded_spec(axes, axis_size):
        def fn(p, base, threshold=0):
            shape = leaf_shape(p)
            n = int(np.prod(shape)) if shape else 1
            if n < threshold or not shape:
                return PartitionSpec(*base) if base is not None else PartitionSpec()
            return choose_zero_spec(
                shape, axis_size, strip_trivial(base), axes=axes or (DATA_AXIS,)
            )

        return fn

    def base_or_replicated(p, base, threshold=0):
        return PartitionSpec(*base) if base is not None else PartitionSpec()

    def build(spec_fn, threshold=0):
        return jax.tree_util.tree_unflatten(
            treedef, [spec_fn(p, b, threshold) for p, b in zip(flat_params, flat_base)]
        )

    # persistence threshold applies to *params* only (reference
    # param_persistence_threshold); optimizer state and gradients always
    # partition at their stage.
    param_specs = build(
        sharded_spec(param_axes, param_size) if stage >= 3 else base_or_replicated,
        persistence_threshold,
    )
    grad_specs = build(sharded_spec(state_axes, state_size) if stage >= 2 else base_or_replicated)
    master_specs = build(sharded_spec(state_axes, state_size) if stage >= 1 else base_or_replicated)

    def to_sharding(kind):
        if kind is None:
            return lambda spec: NamedSharding(mesh, spec)
        return lambda spec: NamedSharding(mesh, spec, memory_kind=kind)

    is_spec = lambda x: isinstance(x, PartitionSpec)
    param_kind = host_memory_kind() if offload_param else None
    master_kind = host_memory_kind() if offload_optimizer else None
    return ZeroShardingPlan(
        stage=stage,
        topology=topology,
        param_shardings=jax.tree.map(to_sharding(param_kind), param_specs, is_leaf=is_spec),
        grad_shardings=jax.tree.map(to_sharding(None), grad_specs, is_leaf=is_spec),
        master_shardings=jax.tree.map(to_sharding(master_kind), master_specs, is_leaf=is_spec),
        param_specs=param_specs,
        grad_specs=grad_specs,
        master_specs=master_specs,
        persistence_threshold=persistence_threshold,
        offload_optimizer=offload_optimizer,
        offload_param=offload_param,
        offload_ratio=offload_ratio,
        param_zero_axes=tuple(param_zero_axes),
        state_zero_axes=tuple(zero_axes),
    )


def constrain_tree(tree, specs, mesh):
    """with_sharding_constraint over a pytree (the stage-2 reduce-scatter
    trigger and stage-3 repartition point)."""
    from jax.lax import with_sharding_constraint

    is_spec = lambda x: isinstance(x, PartitionSpec)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs, is_leaf=is_spec)
    return jax.tree.map(
        lambda x, s: with_sharding_constraint(x, s),
        tree,
        shardings,
        is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, NamedSharding),
    )
