"""Device-mesh topology: the TPU-native parallelism grid.

Analogue of the reference's process-group algebra
(``deepspeed/utils/groups.py`` — data/model/sequence/expert groups,
``runtime/pipe/topology.py`` — ``ProcessTopology``/``PipelineParallelGrid``).
Instead of materializing torch process groups per parallel dimension, a single
``jax.sharding.Mesh`` with named axes carries the whole grid; XLA compiles
collectives over whichever axis subset an op names, so every reference
"group" becomes an axis name (or tuple of names).

Axis order (outermost→innermost) is chosen for ICI locality: the ``model``
(tensor-parallel) axis is innermost so its per-layer collectives ride the
fastest ICI links; ``data`` is outermost so it can span DCN on multi-slice.
This mirrors the sharding recipe of the public scaling literature rather than
the reference's rank-arithmetic (groups.py:315 ``_get_expert_parallel_ranks``).

Batch (DP) arithmetic: the global batch is sharded over ``data``×``expert``;
the ``sequence`` axis shards the *sequence* dimension of each example
(Ulysses), and ``pipe``/``model`` hold replicas of the batch.
"""

from typing import Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# Canonical axis names, outermost first. The ``zero`` axis factorizes data
# parallelism for MiCS/hpZ hierarchical partitioning (reference
# runtime/zero/mics.py, groups.py:702 _create_zero_param_parallel_group):
# it sits INSIDE ``data`` so shard groups are ICI-contiguous — ZeRO can
# partition over only ``zero`` (shard group) while gradients still average
# over the full data x zero x expert batch.
PIPE_AXIS = "pipe"
DATA_AXIS = "data"
ZERO_AXIS = "zero"
EXPERT_AXIS = "expert"
# ``context`` shards the SEQUENCE dimension itself for ring attention
# (context parallelism, O(s/N) activations); distinct from ``sequence``,
# which is Ulysses-style (all-to-all head scatter, per-device memory O(s)).
# Both can be >1 at once: Ulysses within a context shard.
CONTEXT_AXIS = "context"
SEQUENCE_AXIS = "sequence"
MODEL_AXIS = "model"
MESH_AXES = (
    PIPE_AXIS, DATA_AXIS, ZERO_AXIS, EXPERT_AXIS, CONTEXT_AXIS, SEQUENCE_AXIS,
    MODEL_AXIS,
)

# Axis set that jointly shards the batch dimension (DP world).
BATCH_AXES = (DATA_AXIS, ZERO_AXIS, EXPERT_AXIS)
# Axes that ZeRO partitions parameters/optimizer state over (full dp).
ZERO_AXES = (DATA_AXIS, ZERO_AXIS)


class Topology:
    """A named-axis device mesh with DeepSpeed-style size queries."""

    def __init__(
        self,
        data: int = 0,
        model: int = 1,
        pipe: int = 1,
        sequence: int = 1,
        expert: int = 1,
        zero: int = 1,
        context: int = 1,
        devices: Optional[Sequence] = None,
    ):
        if devices is None:
            devices = jax.devices()
        n = len(devices)
        fixed = model * pipe * sequence * expert * zero * context
        if n % fixed != 0:
            raise ValueError(
                f"device count {n} not divisible by "
                f"model*pipe*context*sequence*expert*zero={fixed}"
            )
        if data in (0, None):
            data = n // fixed
        if data * fixed != n:
            raise ValueError(
                f"mesh sizes pipe={pipe} data={data} zero={zero} expert={expert} "
                f"context={context} sequence={sequence} model={model} do not "
                f"multiply to device count {n}"
            )
        self.sizes = {
            PIPE_AXIS: pipe,
            DATA_AXIS: data,
            ZERO_AXIS: zero,
            EXPERT_AXIS: expert,
            CONTEXT_AXIS: context,
            SEQUENCE_AXIS: sequence,
            MODEL_AXIS: model,
        }
        shape = tuple(self.sizes[a] for a in MESH_AXES)
        device_array = np.asarray(devices).reshape(shape)
        self.mesh = Mesh(device_array, MESH_AXES)

    # ---- reference groups.py-style queries ----
    @property
    def world_size(self) -> int:
        return int(np.prod([self.sizes[a] for a in MESH_AXES]))

    def axis_size(self, axis: str) -> int:
        return self.sizes[axis]

    @property
    def dp_world_size(self) -> int:
        """Data-parallel world (batch shards): data × zero × expert axes."""
        return self.sizes[DATA_AXIS] * self.sizes[ZERO_AXIS] * self.sizes[EXPERT_AXIS]

    @property
    def data_parallel_size(self) -> int:
        """Non-expert data parallelism (data × its zero factorization)."""
        return self.sizes[DATA_AXIS] * self.sizes[ZERO_AXIS]

    @property
    def zero_shard_size(self) -> int:
        """MiCS/hpZ shard-group size (1 = flat ZeRO over the full dp world)."""
        return self.sizes[ZERO_AXIS]

    @property
    def model_parallel_size(self) -> int:
        return self.sizes[MODEL_AXIS]

    tensor_parallel_size = model_parallel_size

    @property
    def pipe_parallel_size(self) -> int:
        return self.sizes[PIPE_AXIS]

    @property
    def sequence_parallel_size(self) -> int:
        return self.sizes[SEQUENCE_AXIS]

    @property
    def context_parallel_size(self) -> int:
        """Ring (context-parallel) degree: shards the sequence dim itself."""
        return self.sizes[CONTEXT_AXIS]

    @property
    def expert_parallel_size(self) -> int:
        return self.sizes[EXPERT_AXIS]

    # ---- sharding constructors ----
    def sharding(self, *spec) -> NamedSharding:
        """NamedSharding over this mesh; spec entries are axis names/None/tuples."""
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec())

    def batch_sharding(self, extra_leading: Tuple = ()) -> NamedSharding:
        """Sharding for a [batch, ...] array: batch over data×expert."""
        return NamedSharding(self.mesh, PartitionSpec(*extra_leading, BATCH_AXES))

    def __repr__(self):
        live = {a: s for a, s in self.sizes.items() if s > 1}
        return f"Topology(world={self.world_size}, {live or 'single-device'})"


def filter_spec_entry(entry, predicate):
    """Normalize one PartitionSpec entry keeping only axis names that satisfy
    ``predicate`` (None passthrough, tuple/scalar handling, 0/1/n collapse).
    Shared by constrain()'s manual-axis strip and the engine's pure-DP spec
    sanitizer."""
    if entry is None:
        return None
    axes = entry if isinstance(entry, (tuple, list)) else (entry,)
    kept = tuple(a for a in axes if predicate(a))
    return kept if len(kept) > 1 else (kept[0] if kept else None)


def _manual_axis_names():
    """Axis names of the enclosing ``shard_map`` manual region (empty when
    tracing outside one). Inside a manual region those axes are already
    per-device; a with_sharding_constraint naming them is invalid (the qgZ
    exchange wraps the model forward in shard_map over ``data``)."""
    try:
        from jax._src import core

        return set(core.get_axis_env().axis_sizes)
    except Exception:  # private API moved — degrade to no stripping
        return set()


def constrain(x, *spec):
    """``with_sharding_constraint`` over the ambient topology's mesh, degrading
    to identity when the mesh cannot shard that way (e.g. axis missing under a
    test mesh). Axes that are manual in an enclosing shard_map are stripped
    from the spec. Shared helper for model/MoE/sequence activation
    constraints."""
    topo = get_topology()
    manual = _manual_axis_names()
    if manual:
        spec = tuple(filter_spec_entry(e, lambda a: a not in manual) for e in spec)
        if all(e is None for e in spec):
            # nothing left to constrain — emitting an empty-sharding
            # custom-call inside a manual region has tripped XLA CPU
            # partitioner bugs; identity is exactly equivalent
            return x
    try:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(topo.mesh, PartitionSpec(*spec))
        )
    except ValueError:
        return x


def manual_over(body, mesh, in_specs, out_specs):
    """``jax.shard_map`` for wrapping GSPMD-opaque Pallas calls: manual over
    EVERY mesh axis that is not already manual in an enclosing region, with
    the specs naming only the axes the operands are split over. Mosaic
    refuses a kernel under a map that leaves any axis to GSPMD, even one of
    size 1 ("Mosaic kernels cannot be automatically partitioned"), and jax
    0.9.0 rejects such a partial map outright when called eagerly. Jitted,
    because an eager shard_map runs its body op by op; under an enclosing
    jit that is an inlined call."""
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=set(mesh.axis_names) - _manual_axis_names(), check_vma=False,
    ))


_TOPOLOGY: Optional[Topology] = None


def set_topology(topo: Topology):
    global _TOPOLOGY
    _TOPOLOGY = topo


def get_topology() -> Topology:
    global _TOPOLOGY
    if _TOPOLOGY is None:
        _TOPOLOGY = Topology()
    return _TOPOLOGY


def reset_topology():
    global _TOPOLOGY
    _TOPOLOGY = None
