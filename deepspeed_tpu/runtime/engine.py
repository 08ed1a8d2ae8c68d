"""DeepSpeed training engine, TPU-native.

Analogue of the reference ``DeepSpeedEngine`` (runtime/engine.py:202): the
central training wrapper exposing ``forward``/``backward``/``step`` (and the
fused ``train_batch``), config plumbing, optimizer construction
(``_configure_optimizer`` :1467), ZeRO integration
(``_configure_zero_optimizer`` :1768), checkpoint save/load, and monitoring.

TPU-first architecture:
  * The model is a pure loss function ``loss_fn(params, batch[, rng]) -> loss``
    (or ``(loss, aux)``); params are a pytree of jax arrays.
  * ZeRO stages are sharding assignments (see runtime/zero/partition.py);
    one jitted train step carries forward+backward+reduce+update, and XLA
    inserts/overlaps every collective (the reference's IPG bucketing, overlap
    streams and param coordinators have no hand-written counterpart here).
  * Mixed precision: params in bf16/fp16, fp32 master inside the optimizer
    state (reference bf16_optimizer.py:35); fp16 adds a dynamic loss-scale
    state threaded through the step (fp16/loss_scaler.py).
  * The imperative ``engine(batch)`` / ``engine.backward(loss)`` /
    ``engine.step()`` API is preserved: forward computes loss AND caches
    grads (one pass — no double compute), backward accumulates, step applies
    at gradient-accumulation boundaries (reference ``engine.step`` :2606).
    ``train_batch`` fuses all micro-steps into one compiled scan and is the
    recommended hot path.
"""

import contextlib
import inspect
import os
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.accelerator.device import on_tpu
from deepspeed_tpu.comm.logging import get_comms_logger
from deepspeed_tpu.parallel.topology import (
    BATCH_AXES,
    Topology,
    get_topology,
    set_topology,
)
from deepspeed_tpu.observability.setup_record import (
    get_setup_record,
    setup_line,
    setup_report,
)
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.fp16 import loss_scaler as ls
from deepspeed_tpu.runtime.lr_schedules import get_lr_scheduler
from deepspeed_tpu.runtime.optimizers import (
    DeepSpeedOptimizer,
    build_optimizer,
    clip_by_global_norm,
    global_grad_norm,
)
from deepspeed_tpu.runtime.zero.partition import ZeroShardingPlan, build_zero_plan, constrain_tree
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import (
    BACKWARD_GLOBAL_TIMER,
    FORWARD_GLOBAL_TIMER,
    STEP_GLOBAL_TIMER,
    NoopTimer,
    SynchronizedWallClockTimer,
    ThroughputTimer,
)

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}


def _tree_cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype) if hasattr(x, "astype") else x, tree)


def _snapshot_cast(tree, dtype):
    """Cast params to the compute dtype, *copying* any leaf that is already a
    jax Array: the engine's jitted steps donate their param buffers, and
    ``device_put`` may alias the caller's buffer — without the copy, donation
    would delete the user's original pytree out from under them."""

    def leaf(x):
        if isinstance(x, jax.Array):
            return jnp.array(x, dtype=dtype, copy=True)
        if hasattr(x, "astype"):  # host numpy: device_put copies to device anyway
            return np.asarray(x).astype(dtype)
        return x

    return jax.tree.map(leaf, tree)


def _tree_select(pred, on_true, on_false):
    """Elementwise pytree select for the overflow skip-step branch."""
    return jax.tree.map(lambda a, b: jnp.where(pred, a, b), on_true, on_false)


class DeepSpeedEngine:
    # qgZ: gradient leaves below this many elements reduce in full precision —
    # quantizing a [h]-sized norm/bias grad saves no bandwidth but injects
    # noise and costs two collective launches (the reference likewise only
    # quantizes the bucketed bulk)
    QGZ_MIN_SIZE = 65536

    def __init__(
        self,
        loss_fn: Callable,
        params: Any,
        config: DeepSpeedConfig,
        topology: Optional[Topology] = None,
        optimizer: Optional[Any] = None,
        lr_scheduler: Optional[Any] = None,
        training_data=None,
        collate_fn=None,
        param_specs: Any = None,
        dont_change_device: bool = False,
    ):
        self.config = config
        self.topo = topology or get_topology()
        set_topology(self.topo)
        self.loss_fn = loss_fn
        self._loss_fn_takes_rng = self._detect_rng_arg(loss_fn)
        self.client_optimizer = optimizer
        self.client_lr_scheduler = lr_scheduler
        self.collate_fn = collate_fn
        self.training_dataloader = None

        # precision
        self.compute_dtype = DTYPES[config.precision_dtype]
        self.fp16_enabled = config.fp16.enabled
        self.bf16_enabled = config.bf16.enabled
        grad_accum = config.data_types.grad_accum_dtype
        self.grad_accum_dtype = DTYPES[
            {"fp32": "float32", "fp16": "float16", "bf16": "bfloat16", None: "float32"}[grad_accum]
        ]

        # ZeRO plan (+ offload tiers: reference offload_config.py — optimizer
        # state / params in host memory; nvme maps to the host tier until a
        # DeepNVMe analogue exists)
        zcfg = config.zero_optimization
        self.zero_stage = zcfg.stage
        # Host-optimizer tiers: the jitted step ends at gradients and the
        # update runs outside jit through the native C++ CPU-Adam.
        #   nvme          — ZeRO-Infinity (reference swap_tensor/): state in
        #                   NVMe files, pipelined per-leaf swap
        #   super_offload — reference superoffload_stage3.py: state resident
        #                   in host RAM, no swap traffic
        self._super_offload = (
            zcfg.offload_optimizer.device == "cpu"
            and getattr(zcfg.offload_optimizer, "super_offload", False)
        )
        self._host_opt_requested = (
            zcfg.offload_optimizer.device == "nvme" or self._super_offload
        )
        # The host tiers run CPU-Adam single-process; anything else falls
        # back to the pinned-host in-jit tier (the pre-NVMe behavior) with a
        # warning instead of refusing to train.
        if self._host_opt_requested:
            opt_name = (config.optimizer.type or "adamw").lower() if optimizer is None else None
            adam_family = opt_name in ("adam", "adamw", "deepspeedcpuadam")
            reason = None
            if config.zenflow:
                reason = "zenflow runs its own selective/offload schedule"
            elif optimizer is not None or not adam_family:
                reason = f"optimizer {opt_name or type(optimizer).__name__} is not CPU-Adam-compatible"
            elif jax.process_count() > 1:
                reason = "multi-process runs are not supported by the host tier yet"
            elif zcfg.offload_optimizer.device == "nvme" and not zcfg.offload_optimizer.nvme_path:
                reason = "offload_optimizer.nvme_path is not set"
            if reason is not None:
                log_dist(
                    f"offload_optimizer.device={zcfg.offload_optimizer.device}: "
                    f"{reason}; falling back to the pinned-host tier", ranks=[0],
                )
                self._host_opt_requested = False
                self._super_offload = False
        offload_opt = (
            zcfg.offload_optimizer.device in ("cpu", "nvme") and not self._host_opt_requested
        )
        if config.zenflow and optimizer is not None:
            # client optimizers bypass build_optimizer, where zenflow wraps in
            logger.warning(
                "zenflow config section is ignored when a client optimizer is "
                "passed to initialize(); remove one of the two"
            )
        if config.zenflow and optimizer is None and offload_opt:
            # ZenFlow owns the offload economics: its lax.cond schedule only
            # touches master/moments on boundary steps, so state stays
            # device-resident (XLA's host-compute path cannot compile the
            # selective gathers/scatters in a pinned_host region today)
            log_dist(
                "zenflow active: optimizer state stays device-resident; the "
                "boundary-interval schedule replaces pinned-host placement",
                ranks=[0],
            )
            offload_opt = False
        offload_par = zcfg.offload_param.device != "none"
        if zcfg.offload_param.device == "nvme":
            log_dist(
                "offload_param device 'nvme' maps to the host-memory tier on "
                "TPU (param NVMe swap not implemented)", ranks=[0],
            )
        # zero.Init deferred construction (reference partition_parameters.py:878):
        # a callable/zero.Init marker materializes UNDER jit with the plan's
        # out_shardings — each device computes only its shard and the full
        # pytree never exists on a single host
        from deepspeed_tpu.runtime.zero import as_deferred_init

        deferred_init = as_deferred_init(params)
        plan_shapes = jax.eval_shape(deferred_init) if deferred_init is not None else params
        if deferred_init is None:
            params = _snapshot_cast(params, self.compute_dtype)
            plan_shapes = params
        # MiCS / hpZ (reference runtime/zero/mics.py, zero++ hpZ): when the
        # topology carries a `zero` shard-group axis, MiCS shards params AND
        # optimizer state only within the group (replicated across groups —
        # intra-group gathers, reference hierarchical partitioning); hpZ keeps
        # optimizer state sharded over the full dp world but gathers params
        # intra-group (the secondary partition)
        from deepspeed_tpu.parallel.topology import ZERO_AXES, ZERO_AXIS

        zero_axes, param_zero_axes = ZERO_AXES, None
        wants_shard_group = (zcfg.mics_shard_size or -1) > 0 or (zcfg.zero_hpz_partition_size or 1) > 1
        if wants_shard_group and self.topo.zero_shard_size <= 1:
            raise ValueError(
                "mics_shard_size/zero_hpz_partition_size configured but the topology has "
                "no `zero` shard-group axis — build it with Topology(zero=N) (initialize() "
                "does this automatically unless an mpu/topology was passed in)"
            )
        if self.topo.zero_shard_size > 1:
            mics = zcfg.mics_shard_size and zcfg.mics_shard_size > 0
            param_zero_axes = (ZERO_AXIS,)
            if mics:
                zero_axes = (ZERO_AXIS,)
            log_dist(
                f"{'MiCS' if mics else 'hpZ'}: shard group size "
                f"{self.topo.zero_shard_size} over {self.topo.dp_world_size} dp",
                ranks=[0],
            )
        self.plan: ZeroShardingPlan = build_zero_plan(
            stage=self.zero_stage,
            topology=self.topo,
            params=plan_shapes,
            persistence_threshold=zcfg.param_persistence_threshold if self.zero_stage >= 3 else 0,
            base_specs=param_specs,
            zero_axes=zero_axes,
            param_zero_axes=param_zero_axes,
            offload_optimizer=offload_opt,
            offload_param=offload_par,
            offload_ratio=zcfg.offload_optimizer.ratio,
        )
        # offload execution mode: the true host-offload path (host-kind
        # out_shardings + compute_on) is TPU-only; the CPU test mesh hits an
        # XLA SPMD-partitioner RET_CHECK on memory-kind annotations, so it
        # stages state through device memory inside the step and parks it
        # back to pinned_host eagerly between steps (same semantics).
        self._offload_native = on_tpu()
        # ZeRO-Infinity weight streaming (models/transformer.py weight_stream):
        # the MODEL stages one layer of host-resident weights per scan step
        # and its grads stream back to host — the engine must NOT whole-tree
        # stage params, and the grad epilogue + optimizer run as host compute
        # so full-model grads never materialize in HBM.
        _mc = getattr(loss_fn, "model_config", None)
        self._weight_stream = (
            bool(getattr(_mc, "weight_stream", False))
            and self._offload_native
            and self.plan.offload_param
        )
        if self._weight_stream:
            if config.gradient_accumulation_steps != 1:
                raise NotImplementedError(
                    "weight_stream requires gradient_accumulation_steps == 1: "
                    "accumulating full-model grads needs a host-side buffer pass "
                    "that would stage HBM temps (grow the micro batch instead)"
                )
            if config.gradient_clipping:
                raise NotImplementedError(
                    "gradient_clipping is unsupported with weight_stream: the "
                    "global-norm pass over host-resident grads would stage "
                    "full-model fp32 temps in HBM"
                )
            if self.fp16_enabled:
                raise NotImplementedError(
                    "fp16 dynamic loss scaling is unsupported with weight_stream "
                    "(no overflow scan over host grads) — use bf16"
                )
        if self._weight_stream and not self.plan.offload_optimizer:
            logger.warning(
                "weight_stream without offload_optimizer: host-resident grads "
                "would be pulled back to HBM for the device optimizer — enable "
                "zero_optimization.offload_optimizer (device 'cpu') for models "
                "larger than HBM"
            )
        if self._weight_stream:
            # keep SMALL leaves (norm vectors, biases) device-resident: their
            # [1, h] scan slices violate libtpu's >=8-sublane host-DUS bound,
            # and they cost ~nothing in HBM. Streamed = stacked >=3-D leaves
            # + large 2-D matrices (embed / lm_head).
            import dataclasses as _dc

            from jax.sharding import NamedSharding as _NS

            def _destream_small(sh, p):
                shape = tuple(getattr(p, "shape", ()))
                nbytes = int(np.prod(shape or (1,))) * np.dtype(p.dtype).itemsize
                big = len(shape) >= 3 or (len(shape) == 2 and nbytes >= (8 << 20))
                return sh if big else _NS(sh.mesh, sh.spec)

            self.plan = _dc.replace(
                self.plan,
                param_shardings=jax.tree.map(
                    _destream_small,
                    self.plan.param_shardings,
                    plan_shapes,  # shape tree works for eager AND deferred init
                    is_leaf=lambda x: isinstance(x, _NS),
                ),
            )
        # the placement and sharding of the parameters and the optimizer
        # state's creation: what a set-up pays for the weights, less the
        # compiles inside it
        with get_setup_record().span("setup.state"):
            init_shardings = (
                self.plan.param_shardings
                if self._offload_native
                else self.plan.device_shardings(self.plan.param_shardings)
            )
            if deferred_init is not None:
                dtype = self.compute_dtype
                params = jax.jit(
                    lambda: _tree_cast(deferred_init(), dtype), out_shardings=init_shardings
                )()
            elif not dont_change_device:
                params = jax.device_put(params, init_shardings)
            self.params = params

            # optimizer (+ fp32 master, sharded per plan)
            self.optimizer = self._configure_optimizer(optimizer, config)
            if self._weight_stream:
                if self.optimizer.name not in ("adam", "adamw"):
                    raise NotImplementedError(
                        f"weight_stream supports Adam/AdamW only (got {self.optimizer.name}): "
                        "the chunk-streamed host-state update is AdamW-specific "
                        "(runtime/streamed_adam.py)"
                    )
                from deepspeed_tpu.runtime.streamed_adam import StreamedAdamW

                d = self.optimizer.defaults
                if self.optimizer.name == "adam" and d.get("weight_decay", 0.0):
                    raise NotImplementedError(
                        "weight_stream implements decoupled (AdamW) weight decay "
                        "only; use AdamW or weight_decay=0"
                    )
                self.optimizer = StreamedAdamW(
                    lr=d.get("lr", 1e-3),
                    betas=tuple(d.get("betas", (0.9, 0.999))),
                    eps=d.get("eps", 1e-8),
                    weight_decay=d.get("weight_decay", 0.0),
                    # int8 moment streaming: the tier is PCIe-wire-limited and
                    # bytes are the lever (PERF.md streamed-7B roofline)
                    quant_bits=int(getattr(
                        config.zero_optimization.offload_optimizer,
                        "stream_quant_bits", 0,
                    ) or 0),
                    # double-buffered state-window streaming rides the same
                    # escape hatch as the collective overlap scheduler
                    overlap=config.zero_optimization.overlap_enabled,
                )
            self._host_opt = None
            self._host_step_jit = None
            if self._host_opt_requested:
                # state never materializes in device/host jax memory at all —
                # it is seeded straight to NVMe files (ZeRO-Infinity semantics)
                self._init_host_optimizer(zcfg)
                self._state_shardings = {}
                self.opt_state = {}
            else:
                state_shapes = jax.eval_shape(self.optimizer.init, self.params)
                if getattr(self.optimizer, "state_partition_specs", None) is not None:
                    # collective optimizers (1-bit Adam) own their state layout:
                    # per-worker error buffers shard over data, moments replicate
                    from jax.sharding import NamedSharding, PartitionSpec

                    specs = self.optimizer.state_partition_specs(state_shapes)
                    self._state_shardings = jax.tree.map(
                        lambda s: NamedSharding(self.topo.mesh, s),
                        specs,
                        is_leaf=lambda x: isinstance(x, PartitionSpec),
                    )
                else:
                    self._state_shardings = self.plan.state_shardings(state_shapes)
                if self._weight_stream:
                    self.opt_state = self._streamed_opt_init(state_shapes)
                else:
                    self.opt_state = jax.jit(
                        self.optimizer.init,
                        out_shardings=self.plan.device_shardings(self._state_shardings),
                    )(self.params)
                    if self.plan.offload_optimizer:
                        self.opt_state = jax.device_put(self.opt_state, self._state_shardings)
            self.params = self._park_params(self.params)

        # Bucketed comm/compute overlap (runtime/zero/overlap.py): resolve
        # the overlap_comm knob once and size the transformer scan-chunk for
        # parameter prefetch from the model's per-layer footprint. The
        # chunked and unchunked/unbucketed paths are loss-identical — the
        # escape hatch (overlap_comm: false) only changes the schedule.
        zcfg_o = config.zero_optimization
        self._overlap = zcfg_o.overlap_enabled
        self._reduce_bucket_bytes = int(zcfg_o.reduce_bucket_size)
        self._prefetch_bucket_bytes = int(zcfg_o.effective_prefetch_bucket_size)
        # tile-granular overlap seam (comm/overlap_tiled.py): "tiled" splits
        # each prefetch bucket's fused all-gather into tp_overlap_tiles
        # independent per-tile collectives — bitwise-identical transport the
        # latency-hiding scheduler can stream behind the scan's GEMMs
        self._comm_overlap = config.comm_overlap
        self._overlap_tiles = int(config.tp_overlap_tiles)
        self._gather_tiles = (
            self._overlap_tiles if self._comm_overlap == "tiled" else 1
        )
        self._overlap_scan_chunk = 1
        if (
            self._overlap
            and _mc is not None
            and (zcfg_o.stage == 3 or self._weight_stream)
        ):
            try:
                from deepspeed_tpu.runtime.zero.overlap import overlap_chunk

                stacked = self.params.get("layers") if isinstance(self.params, dict) else None
                if stacked is not None:
                    leaves = jax.tree_util.tree_leaves(stacked)
                    n_layer = int(leaves[0].shape[0])
                    layer_bytes = sum(
                        int(np.prod(l.shape[1:] or (1,))) * np.dtype(l.dtype).itemsize
                        for l in leaves
                    )
                    self._overlap_scan_chunk = overlap_chunk(
                        n_layer, layer_bytes, self._prefetch_bucket_bytes
                    )
            except Exception:  # non-transformer param trees: no scan to chunk
                self._overlap_scan_chunk = 1

        # loss scaling
        self.scaler_cfg = ls.make_config(config.fp16) if self.fp16_enabled else ls.LossScalerConfig(
            False, 1.0, 2.0, 1000, 1.0, 1, False
        )
        self.scaler_state = jax.device_put(ls.init_state(self.scaler_cfg), self.topo.replicated())

        # lr scheduler
        self.lr_scheduler = self._configure_lr_scheduler(lr_scheduler, config)

        # counters (reference engine.py micro_steps/global_steps/global_samples)
        self.micro_steps = 0
        self.global_steps = 0
        self.global_samples = 0
        self.skipped_steps = 0
        self._in_no_sync = False
        self._boundary_override = None
        self.seed = config.seed
        self._rng_key = jax.random.key(config.seed)

        # cached step metrics
        self._last_loss = None
        self._last_grad_norm = None
        self._last_overflow = None

        # grad accumulation buffer for the imperative path
        self._acc_grads = None

        # ZeRO++ LoCo error-feedback buffers (threaded through every step
        # jit); size-0 placeholders when LoCo is off so signatures stay
        # uniform. _loco_enabled() also VALIDATES the knob: zeropp_loco_param
        # without qgZ raises instead of being silently ignored.
        if self._quantized_exchange_enabled() and self._loco_enabled():
            self._loco_state = self._loco_init_state()
        else:
            if config.zero_optimization.zeropp_loco_param is not None:
                self._loco_enabled()  # raises with the real reason
            # placed on the mesh like the step's outputs: jax 0.9.0 types
            # carry the mesh, and an unplaced placeholder made step 2
            # retrace and recompile the whole train step
            # (one buffer per leaf: the step donates them)
            self._loco_state = jax.tree.map(
                lambda _: jax.device_put(
                    jnp.zeros((0,), jnp.bfloat16), self.topo.replicated()),
                self.params,
            )

        # timers / throughput
        self.wall_clock_breakdown = config.wall_clock_breakdown
        self.timers = SynchronizedWallClockTimer() if self.wall_clock_breakdown else NoopTimer()
        self.tput_timer = ThroughputTimer(
            config=type("C", (), {"enabled": True})(),
            batch_size=config.train_batch_size,
            steps_per_output=config.steps_per_print,
        )

        # monitor
        self.monitor = self._configure_monitor(config)

        # curriculum learning (reference engine.py:2112 legacy hooks +
        # data_efficiency.data_sampling.curriculum_learning)
        self.curriculum_scheduler = None
        self._curriculum_metric = "seqlen"
        self._curriculum_post = None
        ccfg = dict(config.curriculum_learning or {})
        if not ccfg.get("enabled") and config.data_efficiency:
            ccfg = (
                (config.data_efficiency.get("data_sampling", {}) or {}).get(
                    "curriculum_learning", {}
                )
                or {}
            )
        if ccfg.get("enabled"):
            from deepspeed_tpu.runtime.data_pipeline import CurriculumScheduler

            self._curriculum_metric = ccfg.get("curriculum_type", "seqlen")
            self.curriculum_scheduler = CurriculumScheduler(ccfg)

        # comms logger
        get_comms_logger().configure(config.comms_logger)

        # compiled fns (built lazily per batch-structure)
        self._train_step_jit = None
        self._fwd_bwd_jit = None
        self._apply_jit = None
        self._eval_jit = None
        self._acc_add_jit = None

        # data
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data)

        if jax.process_index() == 0:
            n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(self.params))
            log_dist(
                f"DeepSpeedEngine: {n_params / 1e6:.2f}M params | zero_stage={self.zero_stage} "
                f"| dtype={config.precision_dtype} | topology={self.topo} "
                f"| micro_bsz={config.train_micro_batch_size_per_gpu} gas={config.gradient_accumulation_steps}",
                ranks=[0],
            )

    # ------------------------------------------------------------------
    # configuration
    # ------------------------------------------------------------------
    @staticmethod
    def _detect_rng_arg(loss_fn):
        try:
            sig = inspect.signature(loss_fn)
            return len(sig.parameters) >= 3 or "rng" in sig.parameters
        except (TypeError, ValueError):
            return False

    def _configure_optimizer(self, client_optimizer, config) -> DeepSpeedOptimizer:
        """Reference _configure_optimizer (engine.py:1467): client optimizer
        wins; else build from the config's ``optimizer`` section."""
        if client_optimizer is not None:
            if isinstance(client_optimizer, DeepSpeedOptimizer):
                return client_optimizer
            if hasattr(client_optimizer, "init") and hasattr(client_optimizer, "update"):
                # raw optax transformation — wrap with master-weight handling
                import optax

                def update_with_lr(grads, state, params=None, *, lr):
                    return client_optimizer.update(grads, state, params)

                import optax as _o

                tx = _o.GradientTransformation(client_optimizer.init, update_with_lr)
                return DeepSpeedOptimizer(tx, "client", {"lr": 0.0})
            if callable(client_optimizer):
                return self._configure_optimizer(client_optimizer(self.params), config)
            raise TypeError(f"Unsupported client optimizer {type(client_optimizer)}")
        if config.optimizer.type is None:
            raise ValueError(
                "No optimizer: pass `optimizer=` to initialize() or set the config 'optimizer' section"
            )
        if config.zenflow:
            # ZenFlow selective-offload schedule (reference engine.py:351-356
            # + runtime/zenflow/): adam-family only, like the reference
            from deepspeed_tpu.runtime.zenflow import build_zenflow_optimizer

            name = (config.optimizer.type or "").lower()
            if name not in ("adam", "adamw", "zenflowselectiveadam"):
                raise ValueError(f"zenflow requires an Adam-family optimizer, got {name}")
            return build_zenflow_optimizer(config.zenflow, config.optimizer)
        return build_optimizer(
            config.optimizer,
            config.precision_dtype,
            master_specs=self.plan.master_specs,
            mesh=self.plan.topology.mesh,
        )

    def _configure_lr_scheduler(self, client_scheduler, config):
        if client_scheduler is not None:
            if callable(client_scheduler) and not hasattr(client_scheduler, "step"):
                return client_scheduler(self.optimizer)
            return client_scheduler
        if config.scheduler.type:
            sched = get_lr_scheduler(config.scheduler.type, optimizer=self.optimizer, **config.scheduler.params)
            if hasattr(sched, "set_base_lr"):
                sched.set_base_lr(self.optimizer.get_lr())
            return sched
        return None

    def _configure_monitor(self, config):
        try:
            from deepspeed_tpu.monitor.monitor import MonitorMaster

            return MonitorMaster(config)
        except Exception as e:  # monitor must never break training
            logger.warning(f"Monitor disabled: {e}")
            return None

    # ------------------------------------------------------------------
    # reference-parity property accessors (engine.py:588-1146)
    # ------------------------------------------------------------------
    def train_batch_size(self):
        return self.config.train_batch_size

    def train_micro_batch_size_per_gpu(self):
        return self.config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self):
        return self.config.gradient_accumulation_steps

    def zero_optimization_stage(self):
        return self.zero_stage

    def zero_optimization(self):
        return self.zero_stage > 0

    def get_lr(self):
        return [self._current_lr()]

    def get_global_grad_norm(self):
        """Last step's global grad norm, or None when the norm reduction was
        skipped (monitor_grad_norm auto-off) — a numeric consumer must see an
        explicit None, not a NaN that silently fails every comparison. Set
        config monitor_grad_norm=True to always compute it."""
        n = self._last_grad_norm
        if n is None:
            return None
        f = float(n)
        return None if f != f else f

    @property
    def loss_scale(self):
        return float(self.scaler_state.scale)

    def gradient_clipping(self):
        return self.config.gradient_clipping

    @property
    def module(self):
        return self.loss_fn

    def is_gradient_accumulation_boundary(self):
        """Reference engine.py:2499."""
        if self._boundary_override is not None:
            return self._boundary_override
        return (self.micro_steps + 1) % self.config.gradient_accumulation_steps == 0

    def set_gradient_accumulation_boundary(self, is_boundary):
        self._boundary_override = is_boundary

    @contextlib.contextmanager
    def no_sync(self):
        """Reference engine.no_sync (engine.py:2364): skip grad sync — on TPU
        grads are accumulated locally anyway until a boundary step; this
        context just forces boundary off."""
        prev = self._boundary_override
        self._boundary_override = False
        try:
            yield
        finally:
            self._boundary_override = prev

    def train(self, mode=True):
        self._train_mode = mode
        return self

    def eval(self):
        self._train_mode = False
        return self

    # ------------------------------------------------------------------
    # jitted step construction
    # ------------------------------------------------------------------
    def _current_lr(self):
        if self.lr_scheduler is not None:
            try:
                return float(self.lr_scheduler.get_last_lr()[0])
            except (AssertionError, AttributeError):
                lr = self.lr_scheduler.get_lr()
                return float(lr[0] if isinstance(lr, (list, tuple)) else lr)
        return float(self.optimizer.get_lr())

    def _next_rng(self, step):
        return jax.random.fold_in(self._rng_key, step)

    def _call_loss(self, params, batch, rng):
        ctx = contextlib.nullcontext()
        if getattr(self, "_overlap_scan_chunk", 1) > 1:
            # trace-scoped: the model's layer scan runs chunked (bucketed
            # parameter prefetch — models/transformer.py overlap_scan)
            from deepspeed_tpu.models.transformer import overlap_scan

            ctx = overlap_scan(self._overlap_scan_chunk)
        with ctx:
            if self._loss_fn_takes_rng:
                out = self.loss_fn(params, batch, rng)
            else:
                out = self.loss_fn(params, batch)
        if isinstance(out, tuple):
            return out[0], out[1] if len(out) > 1 else None
        return out, None

    def _batch_shardings(self, batch, leading_gas_dim=False):
        from jax.sharding import NamedSharding, PartitionSpec

        mesh = self.topo.mesh
        dp = self.topo.dp_world_size

        def spec(x):
            nd = getattr(x, "ndim", 0)
            batch_dim = 1 if leading_gas_dim else 0
            if nd <= batch_dim or x.shape[batch_dim] % dp != 0:
                # batch smaller than / not divisible by the DP world: replicate
                return NamedSharding(mesh, PartitionSpec())
            if leading_gas_dim:
                return NamedSharding(mesh, PartitionSpec(None, BATCH_AXES))
            return NamedSharding(mesh, PartitionSpec(BATCH_AXES))

        return jax.tree.map(spec, batch)

    def _streamed_opt_init(self, state_shapes):
        """Leaf-wise optimizer-state construction for weight streaming.

        The whole-tree ``jit(init)`` would materialize every fp32 master +
        moment in HBM before the host copy (~80 GB for a 7B model). Masters
        cast per leaf (peak HBM = one leaf); inner-state leaves are created
        per leaf and moved straight to their host shardings. Contract: the
        streamed optimizers' inner states are zero-init (true for the optax
        adam/lamb/lion/sgd family this path supports)."""
        from deepspeed_tpu.runtime.optimizers import OptState

        if not isinstance(state_shapes, OptState):
            raise NotImplementedError(
                "weight_stream requires an OptState-shaped optimizer (fp32 master form)"
            )
        master = jax.tree.map(
            lambda p, sh: jax.jit(lambda x: x.astype(jnp.float32), out_shardings=sh)(p),
            self.params,
            self._state_shardings.master,
        )
        inner = jax.tree.map(
            lambda s, sh: jax.jit(
                lambda: jnp.zeros(s.shape, s.dtype), out_shardings=sh
            )(),
            state_shapes.inner,
            self._state_shardings.inner,
        )
        return OptState(master=master, inner=inner)

    def _stage_params(self, params):
        """offload_param tier (native/TPU): params rest in pinned_host between
        steps; the compiled step stages them into HBM before any compute
        (XLA overlaps the per-leaf H2D chain with the first layers' compute).
        On the eager path the un-park happens outside jit instead."""
        if not (self.plan.offload_param and self._offload_native):
            return params
        if self._weight_stream:
            return params  # the model stages layer-by-layer itself
        return jax.device_put(params, self.plan.device_shardings(self.plan.param_shardings))

    def _unpark_for_step(self):
        """Eager offload mode only: move host-parked state/params into device
        memory before a compiled step (outside jit — the CPU backend rejects
        memory-kind annotations inside SPMD programs)."""
        if self._offload_native:
            return
        if self.plan.offload_optimizer:
            self.opt_state = jax.device_put(
                self.opt_state, self.plan.device_shardings(self._state_shardings)
            )
        self._unpark_params()

    def _unpark_params(self):
        if self._offload_native:
            return
        if self.plan.offload_param:
            self.params = jax.device_put(
                self.params, self.plan.device_shardings(self.plan.param_shardings)
            )

    def _opt_apply(self, safe_grads, opt_state, params, lr, overflow):
        """Optimizer update + overflow skip-step, honoring the offload tier.

        ZeRO-Offload (reference stage_1_and_2.py:1307 cpu-offload path +
        cpu_adam): with ``offload_optimizer`` the fp32 master and moments
        live in ``pinned_host`` memory; on TPU the update itself runs on the
        host CPU (``compute_on("device_host")`` — the XLA-native CPU-Adam),
        so only grads cross PCIe down and the half-precision params cross
        back up; optimizer state never touches HBM. XLA schedules the
        per-leaf D2H/compute/H2D chains concurrently, which is the
        double-buffering the reference implements by hand. Muon's
        Newton–Schulz matmuls belong on the MXU, so it stages state through
        HBM instead. On non-TPU backends (CPU test meshes) the state is
        staged through device memory inside the step and parked back to host
        eagerly after it — same semantics, exercised by the CPU suite.
        """
        if self._weight_stream:
            raise AssertionError(
                "streamed optimizer must run eagerly (train_batch streamed "
                "path), never inside the fused step jit"
            )
        offload = self.plan.offload_optimizer
        # Pallas-backed optimizers (fused_adam) and MXU-bound ones (muon)
        # cannot lower inside a host-compute region; they stage through HBM.
        host_compute = (
            offload
            and self._offload_native
            # Twin-Flow partial offload keeps a fraction of state in HBM:
            # the update must run on-device so those leaves never cross PCIe
            and self.plan.offload_ratio >= 1.0
            and self.optimizer.name not in ("muon", "fused_adam", "zenflow")
        )
        if host_compute:
            from jax.experimental.compute_on import compute_on
            from jax.sharding import NamedSharding, PartitionSpec

            host_grads = jax.device_put(safe_grads, self.plan.master_shardings)
            # params must live host-side inside the host-compute region too:
            # elementwise ops tolerate mixed memory spaces, but gathers
            # (zenflow's column selection) refuse them
            host_params = jax.device_put(
                params,
                jax.tree.map(
                    lambda s: NamedSharding(s.mesh, s.spec, memory_kind="pinned_host"),
                    self.plan.device_shardings(self.plan.param_shardings),
                    is_leaf=lambda x: isinstance(x, NamedSharding),
                ),
            )
            ov_host = jax.device_put(
                overflow,
                NamedSharding(self.topo.mesh, PartitionSpec(), memory_kind="pinned_host"),
            )
            with compute_on("device_host"):
                new_params, new_opt_state = self.optimizer.step(
                    host_grads, opt_state, host_params, lr
                )
                new_opt_state = _tree_select(ov_host, opt_state, new_opt_state)
            new_params = jax.device_put(
                new_params, self.plan.device_shardings(self.plan.param_shardings)
            )
            new_params = _tree_select(overflow, self._stage_params(params), new_params)
            return new_params, new_opt_state
        if offload and self._offload_native:  # muon: stage through HBM
            opt_state = jax.device_put(
                opt_state, self.plan.device_shardings(self._state_shardings)
            )
        new_params, new_opt_state = self.optimizer.step(safe_grads, opt_state, params, lr)
        new_params = _tree_select(overflow, self._stage_params(params), new_params)
        new_opt_state = _tree_select(overflow, opt_state, new_opt_state)
        return new_params, new_opt_state

    def _init_host_optimizer(self, zcfg):
        """Host-optimizer tiers (NVMe swap / SuperOffload resident): fp32
        master + moments live outside jax entirely; each step runs the native
        CPU-Adam against them (reference partitioned_optimizer_swapper.py,
        superoffload_stage3.py)."""
        ocfg = zcfg.offload_optimizer
        # capability checks already ran (with graceful fallback) in __init__;
        # these are defensive
        if self.optimizer.name not in ("adam", "adamw"):
            raise RuntimeError(f"superoffload requires adam/adamw, got {self.optimizer.name}")
        if jax.process_count() != 1:
            raise RuntimeError("superoffload is single-process only")
        d = self.optimizer.defaults
        kw = dict(
            lr=d.get("lr", 1e-3),
            betas=tuple(d.get("betas", (0.9, 0.999))),
            eps=d.get("eps", 1e-8),
            weight_decay=d.get("weight_decay", 0.0),
            adamw_mode=self.optimizer.name == "adamw",
        )
        if self._super_offload:
            from deepspeed_tpu.runtime.superoffload import SuperOffloadHostOptimizer

            self._host_opt = SuperOffloadHostOptimizer(
                cpuadam_cores_perc=getattr(ocfg, "cpuadam_cores_perc", 0.8), **kw
            )
        else:
            from deepspeed_tpu.runtime.swap_tensor import NVMeOptimizerSwapper

            if not ocfg.nvme_path:
                raise ValueError("offload_optimizer.device=nvme requires nvme_path")
            self._host_opt = NVMeOptimizerSwapper(
                nvme_path=ocfg.nvme_path, buffer_count=ocfg.buffer_count, **kw
            )
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.params)
        self._host_leaf_names = [jax.tree_util.keystr(path) for path, _ in flat]
        self._host_treedef = treedef
        self._host_opt.init_from_params(
            (name, np.asarray(leaf))
            for name, (_, leaf) in zip(self._host_leaf_names, flat)
        )

    def _train_batch_hostopt(self, stacked):
        """train_batch for the NVMe tier: grads-only compiled step on the
        chip, then the pipelined NVMe/CPU-Adam update on the host (reference
        stage3 step with _optimizer_states_and_gradient_swap_in/out,
        stage3.py:1985/2035)."""
        if self._host_step_jit is None:
            self._host_step_jit = self._build_train_step(grads_only=True)
        lr = self._lr_for_step()
        self.tput_timer.start()
        self.timers(STEP_GLOBAL_TIMER).start()
        self._unpark_params()  # eager offload_param mode parks params host-side
        shardings = self._batch_shardings(stacked, leading_gas_dim=True)
        stacked = jax.device_put(stacked, shardings)
        safe_grads, self.scaler_state, loss, grad_norm, overflow, self._loco_state = self._host_step_jit(
            self.params,
            self.scaler_state,
            jnp.int32(self.global_steps),
            stacked,
            self._loco_state,
        )
        if not bool(overflow):  # functional skip-step, decided on host here
            flat_grads = jax.tree_util.tree_leaves(safe_grads)
            # leaves stay jax arrays: the host optimizers pull D2H per leaf,
            # overlapping the pull with the previous leaf's Adam compute
            named = list(zip(self._host_leaf_names, flat_grads))
            new_leaves = self._host_opt.step(named, lr=lr)
            # device_put straight from numpy: one H2D per leaf (jnp.asarray
            # first would stage through the default device and transfer twice)
            params = jax.tree_util.tree_unflatten(
                self._host_treedef, [new_leaves[n] for n in self._host_leaf_names]
            )
            self.params = jax.device_put(params, self.plan.param_shardings)
        self.timers(STEP_GLOBAL_TIMER).stop()
        self._after_step(loss, grad_norm, overflow)
        self.tput_timer.stop(global_step=True)
        return loss

    def _train_batch_streamed(self, stacked):
        """train_batch for the weight-streaming tier (ZeRO-Infinity on one
        chip): grads-only compiled step (grads of streamed leaves land
        pinned_host via the staging vjp), then the chunk-streamed AdamW runs
        EAGERLY — one donated jit call per leaf — so host temp memory is
        bounded by one leaf's buffers (streamed_adam.StreamedAdamW)."""
        if getattr(self, "_stream_grads_jit", None) is None:
            self._stream_grads_jit = self._build_train_step(grads_only=True)
        lr = self._lr_for_step()
        self.tput_timer.start()
        self.timers(STEP_GLOBAL_TIMER).start()
        shardings = self._batch_shardings(stacked, leading_gas_dim=True)
        stacked = jax.device_put(stacked, shardings)
        safe_grads, self.scaler_state, loss, grad_norm, overflow, self._loco_state = self._stream_grads_jit(
            self.params,
            self.scaler_state,
            jnp.int32(self.global_steps),
            stacked,
            self._loco_state,
        )
        self.params, self.opt_state = self.optimizer.step(
            safe_grads, self.opt_state, self.params, jnp.float32(lr)
        )
        del safe_grads
        # join ALL per-leaf updates: dispatching the next step's fused grads
        # program against ~100 in-flight host-update executions serializes
        # pathologically (measured 179 s/step vs 25 s/step joined at 7B) —
        # this tier is PCIe-bound, so the lost overlap is noise
        jax.block_until_ready(self.params)
        self.timers(STEP_GLOBAL_TIMER).stop()
        self._after_step(loss, grad_norm, overflow)
        self.tput_timer.stop(global_step=True)
        return loss

    def _jit_param_shardings(self):
        if self.plan.offload_param and not self._offload_native:
            return self.plan.device_shardings(self.plan.param_shardings)
        return self.plan.param_shardings

    def _jit_state_shardings(self):
        if self.plan.offload_optimizer and not self._offload_native:
            return self.plan.device_shardings(self._state_shardings)
        return self._state_shardings

    def _park_state(self, opt_state):
        """Eager-mode offload: move optimizer state back to pinned_host
        between steps (no-op on the native path, where out_shardings keep it
        there)."""
        if self.plan.offload_optimizer and not self._offload_native:
            return jax.device_put(opt_state, self._state_shardings)
        return opt_state

    def _park_params(self, params):
        if self.plan.offload_param and not self._offload_native:
            return jax.device_put(params, self.plan.param_shardings)
        return params

    def _pure_dp(self) -> bool:
        """True when the data axis is the only non-trivial mesh axis — the
        supported topology for the explicit-collective paths (1-bit, qgZ)."""
        from deepspeed_tpu.parallel.topology import DATA_AXIS, MESH_AXES

        return all(self.topo.axis_size(a) == 1 for a in MESH_AXES if a != DATA_AXIS)

    @staticmethod
    def _data_dim(spec):
        """Index of the dim a PartitionSpec places the data axis on, or None."""
        from deepspeed_tpu.parallel.topology import DATA_AXIS

        if spec is None:
            return None
        for i, entry in enumerate(tuple(spec)):
            if entry is None:
                continue
            axes = entry if isinstance(entry, (tuple, list)) else (entry,)
            if DATA_AXIS in axes:
                return i
        return None

    def _quantized_exchange_enabled(self) -> bool:
        zcfg = self.config.zero_optimization
        return (zcfg.zero_quantized_gradients or zcfg.zero_quantized_weights) and self.topo.dp_world_size > 1

    def _loco_enabled(self) -> bool:
        """ZeRO++ LoCo (zeropp_loco_param): error-feedback on the qgZ
        quantized gradient exchange (reference stage3.py:2084
        _loco_err_buf_update + coalesced_collectives
        all_to_all_loco_quant_reduce)."""
        zcfg = self.config.zero_optimization
        if zcfg.zeropp_loco_param is None:
            return False
        if not (zcfg.zero_quantized_gradients and self.topo.dp_world_size > 1):
            raise ValueError(
                "zeropp_loco_param requires zero_quantized_gradients with a "
                "data-parallel world > 1: LoCo is error feedback ON the qgZ "
                "exchange — without qgZ there is no quantization error to feed back"
            )
        return True

    def _loco_init_state(self):
        """Per-rank error buffers as a [W, ...]-leading pytree sharded over
        the data axis (rank w owns err[w] — shard_map slices it to the local
        buffer). Ineligible leaves (below QGZ_MIN_SIZE) carry size-0
        placeholders. bf16 storage (reference requantizes to int8; bf16 is
        more faithful at comparable footprint)."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu.parallel.topology import DATA_AXIS

        W = self.topo.dp_world_size
        mesh = self.topo.mesh

        def shard_of(p):
            if p.size >= self.QGZ_MIN_SIZE:
                return NamedSharding(mesh, P(DATA_AXIS, *([None] * p.ndim)))
            return NamedSharding(mesh, P())

        shardings = jax.tree.map(shard_of, self.params)
        # ONE compile for the whole zero pytree (per-leaf jits would pay one
        # XLA compilation per parameter leaf)
        return jax.jit(
            lambda: jax.tree.map(
                lambda p: jnp.zeros(
                    (W,) + p.shape if p.size >= self.QGZ_MIN_SIZE else (0,),
                    jnp.bfloat16,
                ),
                self.params,
            ),
            out_shardings=shardings,
        )()

    def _make_quantized_micro_grads(self, grad_specs, mesh):
        """ZeRO++ qgZ/qwZ gradient/weight exchange (reference engine.py:1088
        zero_quantized_gradients + stage3.py:1610 quantize_nontrainable_params,
        runtime/comm/coalesced_collectives.py all_to_all_quant_reduce).

        The implicit GSPMD reduction is replaced by a shard_map manual region
        over the data axis: parameters arrive as their ZeRO-3 slices and are
        (optionally int8-quantized) all-gathered; local grads leave through a
        quantized reduce-scatter — int payloads on the wire in both
        directions."""
        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu.ops.quantizer.block_quant import (
            loco_quantized_allreduce,
            loco_quantized_reduce_scatter_along,
            quantized_all_gather_along,
            quantized_allreduce,
            quantized_reduce_scatter_along,
        )
        from deepspeed_tpu.parallel.topology import DATA_AXIS

        if not self._pure_dp():
            raise NotImplementedError(
                "zero_quantized_gradients/weights currently require a pure "
                "data-parallel topology — no tensor/pipe/sequence/expert axes, and "
                "no MiCS/hpZ `zero` shard group (the explicit quantized exchange is "
                "manual over the data axis only)"
            )
        zcfg = self.config.zero_optimization
        qgz, qwz = zcfg.zero_quantized_gradients, zcfg.zero_quantized_weights
        loco = self._loco_enabled()
        loco_cfg = zcfg.zeropp_loco_param or {}
        err_beta = float(loco_cfg.get("err_beta", 0.8))
        W = self.topo.dp_world_size

        def _data_only(spec):
            """shard_map in_specs may only name MANUAL axes; _pure_dp()
            guarantees every non-data axis is size 1, so stripping their
            names (e.g. the transformer's 'model' TP entries) is layout-
            preserving."""
            from deepspeed_tpu.parallel.topology import filter_spec_entry

            if spec is None or not isinstance(spec, P):
                return spec
            return P(*(filter_spec_entry(e, lambda a: a == DATA_AXIS) for e in tuple(spec)))

        param_specs = jax.tree.map(
            _data_only, self.plan.param_specs, is_leaf=lambda x: isinstance(x, P)
        )
        grad_specs = jax.tree.map(
            _data_only, grad_specs, is_leaf=lambda x: isinstance(x, P)
        )

        overlap = getattr(self, "_overlap", True)
        if overlap:
            from deepspeed_tpu.runtime.zero.overlap import (
                assign_buckets,
                bucketed_all_gather,
                bucketed_loco_quantized_reduce_scatter,
                bucketed_psum_scatter,
                bucketed_quantized_all_gather,
                bucketed_quantized_reduce_scatter,
            )

        def gather_leaf(x, spec):
            k = self._data_dim(spec)
            if k is None:
                return x
            if qwz:
                return quantized_all_gather_along(x, DATA_AXIS, k)
            return jax.lax.all_gather(x, DATA_AXIS, axis=k, tiled=True)

        def reduce_leaf(g, spec, err):
            """Returns (reduced grad, new local err). err is this rank's
            local buffer ([*g.shape] bf16) or a size-0 placeholder."""
            k = self._data_dim(spec)
            if qgz and g.size >= self.QGZ_MIN_SIZE:
                if loco:
                    if k is None:
                        return loco_quantized_allreduce(g, err, DATA_AXIS, err_beta=err_beta)
                    return loco_quantized_reduce_scatter_along(
                        g, err, DATA_AXIS, k, err_beta=err_beta
                    )
                if k is None:
                    return quantized_allreduce(g, DATA_AXIS), err
                return quantized_reduce_scatter_along(g, DATA_AXIS, k), err
            if k is None:
                return jax.lax.pmean(g, DATA_AXIS), err
            return (
                jax.lax.psum_scatter(g, DATA_AXIS, scatter_dimension=k, tiled=True) / W
            ).astype(g.dtype), err

        def _nbytes(x):
            return int(np.prod(x.shape or (1,))) * np.dtype(x.dtype).itemsize

        def gather_all(flat_p, flat_ps):
            """All-gather the ZeRO-3 param slices. Overlap ON: sharded
            leaves group into prefetch-bucket-sized fused collectives
            (one wire launch per bucket — independent ops the scheduler
            pipelines); OFF: the original per-leaf chain. Both orders
            produce bitwise-identical gathered leaves."""
            if not overlap:
                return [gather_leaf(x, s) for x, s in zip(flat_p, flat_ps)]
            ks = [self._data_dim(s) for s in flat_ps]
            out = list(flat_p)
            if qwz:
                idxs = [i for i, k in enumerate(ks) if k is not None]
                groups = [idxs] if idxs else []
            else:
                # plain gathers concatenate raw payloads: same-dtype only
                by_dt = {}
                for i, k in enumerate(ks):
                    if k is not None:
                        by_dt.setdefault(flat_p[i].dtype, []).append(i)
                groups = list(by_dt.values())
            fuse = (
                bucketed_quantized_all_gather if qwz else bucketed_all_gather
            )
            for idxs in groups:
                buckets = assign_buckets(
                    [_nbytes(flat_p[i]) for i in idxs], self._prefetch_bucket_bytes
                )
                for b in buckets:
                    sel = [idxs[j] for j in b]
                    res = fuse(
                        [flat_p[i] for i in sel], [ks[i] for i in sel], DATA_AXIS,
                        tiles=getattr(self, "_gather_tiles", 1),
                    )
                    for i, r in zip(sel, res):
                        out[i] = r
            return out

        def reduce_all(flat_g, flat_gs, flat_e):
            """Reduce-scatter the grads. Overlap ON: dim-sharded leaves
            group into reduce-bucket-sized fused collectives launched as
            each bucket's grads exist — independent of later buckets, so
            the scheduler overlaps them with remaining backward compute.
            Replicated (k=None) leaves and the unbucketed path keep the
            per-leaf collectives. Returns (reduced list, new-err list)."""
            ks = [self._data_dim(s) for s in flat_gs]
            if not overlap:
                pairs = [
                    reduce_leaf(g, s, e)
                    for g, s, e in zip(flat_g, flat_gs, flat_e)
                ]
                return [p[0] for p in pairs], [p[1] for p in pairs]
            out_g = list(flat_g)
            out_e = list(flat_e)
            q_idx, plain_by_dt = [], {}
            for i, (g, k) in enumerate(zip(flat_g, ks)):
                if k is None or not (qgz and g.size >= self.QGZ_MIN_SIZE):
                    if k is None:
                        out_g[i], out_e[i] = reduce_leaf(g, None, flat_e[i])
                    else:
                        plain_by_dt.setdefault(g.dtype, []).append(i)
                else:
                    q_idx.append(i)
            for idxs in plain_by_dt.values():
                buckets = assign_buckets(
                    [_nbytes(flat_g[i]) for i in idxs], self._reduce_bucket_bytes
                )
                for b in buckets:
                    sel = [idxs[j] for j in b]
                    res = bucketed_psum_scatter(
                        [flat_g[i] for i in sel], [ks[i] for i in sel], DATA_AXIS
                    )
                    for i, r in zip(sel, res):
                        out_g[i] = r
            buckets = assign_buckets(
                [_nbytes(flat_g[i]) for i in q_idx], self._reduce_bucket_bytes
            )
            for b in buckets:
                sel = [q_idx[j] for j in b]
                gs = [flat_g[i] for i in sel]
                ds = [ks[i] for i in sel]
                if loco:
                    res, errs = bucketed_loco_quantized_reduce_scatter(
                        gs, [flat_e[i] for i in sel], ds, DATA_AXIS,
                        err_beta=err_beta,
                    )
                    for i, r, e2 in zip(sel, res, errs):
                        out_g[i], out_e[i] = r, e2
                else:
                    res = bucketed_quantized_reduce_scatter(gs, ds, DATA_AXIS)
                    for i, r in zip(sel, res):
                        out_g[i] = r
            return out_g, out_e

        def inner(params, mb, rng, scale, loco_state):
            flat_p, treedef = jax.tree_util.tree_flatten(params)
            flat_ps = treedef.flatten_up_to(param_specs)
            full = jax.tree_util.tree_unflatten(
                treedef, gather_all(flat_p, flat_ps)
            )

            def scaled_loss(p):
                loss, _aux = self._call_loss(p, mb, rng)
                return (loss * scale.astype(loss.dtype)).astype(jnp.float32)

            loss_scaled, g_full = jax.value_and_grad(scaled_loss)(full)
            flat_g = treedef.flatten_up_to(g_full)
            flat_gs = treedef.flatten_up_to(grad_specs)
            # local err slices arrive [1, ...] (P(DATA_AXIS) on dim 0)
            flat_e = [
                e[0] if e.size else e
                for e in treedef.flatten_up_to(loco_state)
            ]
            red_g, red_e = reduce_all(flat_g, flat_gs, flat_e)
            grads = jax.tree_util.tree_unflatten(treedef, red_g)
            new_loco = jax.tree_util.tree_unflatten(
                treedef, [e2[None] if e2.size else e2 for e2 in red_e]
            )
            return jax.lax.pmean(loss_scaled, DATA_AXIS) / scale, grads, new_loco

        loco_specs = jax.tree.map(
            lambda p: P(DATA_AXIS) if loco and p.size >= self.QGZ_MIN_SIZE else P(),
            self.params,
        )

        def micro_grads(params, mb, rng, scale, loco_state):
            bspecs = jax.tree.map(
                lambda x: P(DATA_AXIS)
                if getattr(x, "ndim", 0) >= 1 and x.shape[0] % W == 0
                else P(),
                mb,
            )
            fn = jax.shard_map(
                inner,
                mesh=mesh,
                in_specs=(param_specs, bspecs, P(), P(), loco_specs),
                out_specs=(P(), grad_specs, loco_specs),
                axis_names={DATA_AXIS},
                check_vma=False,
            )
            return fn(params, mb, rng, scale, loco_state)

        return micro_grads

    def _grad_epilogue_flags(self):
        """Resolve check_grad_overflow / monitor_grad_norm (None = auto):
        both cost a full fp32-grad pass per step — auto runs the overflow
        scan for fp16 only (reference bf16 engines skip it) and the norm
        reduction only when a monitor consumes it. Shared by the fused and
        imperative step builders; the 1-bit path keeps its own overflow
        handling (load-bearing for the compressed-state skip-step)."""
        cfg = self.config
        check_overflow = (
            cfg.check_grad_overflow
            if cfg.check_grad_overflow is not None
            else self.fp16_enabled
        )
        monitor_norm = (
            cfg.monitor_grad_norm
            if cfg.monitor_grad_norm is not None
            else bool(getattr(self.monitor, "enabled", False)) or cfg.wall_clock_breakdown
        )
        if (
            not check_overflow
            and not cfg.gradient_clipping
            and cfg.check_grad_overflow is None
            and not getattr(self, "_warned_no_sanitize", False)
        ):
            # one-time notice (round-3 advisor): with auto-off overflow checks
            # and no clipping, a non-finite grad leaf poisons params silently
            self._warned_no_sanitize = True
            log_dist(
                "bf16 mode skips the per-step grad overflow scan and NaN "
                "sanitization (matching reference bf16 engines); set "
                '"check_grad_overflow": true to re-enable it',
                ranks=[0],
            )
        return check_overflow, monitor_norm

    def _build_train_step(self, grads_only=False):
        if getattr(self.optimizer, "collective_grad_exchange", False):
            if getattr(self.loss_fn, "custom_value_and_grad", None) is not None:
                raise NotImplementedError(
                    "1-bit optimizers are incompatible with custom-gradient loss "
                    "functions (1F1B pipeline): the compressed exchange needs local "
                    "grads from autodiff, which they bypass"
                )
            return self._build_onebit_train_step()
        gas = self.config.gradient_accumulation_steps
        clip = self.config.gradient_clipping
        scaler_cfg = self.scaler_cfg
        grad_specs = self.plan.grad_specs
        mesh = self.topo.mesh
        accum_dtype = self.grad_accum_dtype
        stream = self._weight_stream
        check_overflow, monitor_norm = self._grad_epilogue_flags()

        custom_vg = getattr(self.loss_fn, "custom_value_and_grad", None)
        if stream and (custom_vg is not None or self._quantized_exchange_enabled()):
            raise NotImplementedError(
                "weight_stream is incompatible with custom-gradient loss functions "
                "(1F1B pipeline) and quantized grad exchange: their micro_grads "
                "constrain the full grad tree with kind-less specs, which would "
                "drag host-resident streamed grads into HBM"
            )
        if custom_vg is not None and self.fp16_enabled:
            raise NotImplementedError(
                "fp16 dynamic loss scaling is incompatible with custom-gradient loss "
                "functions (1F1B pipeline): scaling wraps autodiff, which they bypass — use bf16"
            )
        if custom_vg is not None and self._quantized_exchange_enabled():
            raise NotImplementedError(
                "zero_quantized_gradients/weights are incompatible with custom-gradient "
                "loss functions (1F1B pipeline): the quantized exchange wraps autodiff, "
                "which they bypass"
            )
        if custom_vg is not None:
            # loss fn drives its own backward (1F1B pipeline executor)
            def micro_grads(params, mb, rng, scale, loco):
                loss, grads = custom_vg(params, mb)
                grads = constrain_tree(grads, grad_specs, mesh)
                return loss.astype(jnp.float32), grads, loco

        elif self._quantized_exchange_enabled():
            micro_grads = self._make_quantized_micro_grads(grad_specs, mesh)
        else:

            def micro_grads(params, mb, rng, scale, loco):
                def scaled_loss(p):
                    loss, _aux = self._call_loss(p, mb, rng)
                    return (loss * scale.astype(loss.dtype)).astype(jnp.float32)

                loss_scaled, grads = jax.value_and_grad(scaled_loss)(params)
                if not stream:
                    # stage>=2: reduce-scatter layout. Streamed grads are
                    # host-kind; a kind-less constraint would drag them to HBM
                    grads = constrain_tree(grads, grad_specs, mesh)
                return loss_scaled / scale, grads, loco

        loco_on = self._quantized_exchange_enabled() and self._loco_enabled()
        loco_reset_T = (
            int((self.config.zero_optimization.zeropp_loco_param or {}).get("reset_T", 0))
            if loco_on
            else 0
        )

        def train_step(params, opt_state, scaler_state, step, lr, batch, loco):
            params = self._stage_params(params)
            scale = scaler_state.scale if scaler_cfg.dynamic or scaler_cfg.init_scale != 1.0 else jnp.float32(1.0)
            base_rng = jax.random.fold_in(self._rng_key, step)
            if loco_reset_T:
                # reference loco_idx > reset_T periodic error-buffer reset
                reset = (step % loco_reset_T) == 0
                loco = jax.tree.map(lambda e: jnp.where(reset, jnp.zeros_like(e), e), loco)

            def body(carry, xs):
                acc, lc = carry
                i, mb = xs
                rng = jax.random.fold_in(base_rng, i)
                loss, grads, lc = micro_grads(params, mb, rng, scale, lc)
                acc = jax.tree.map(lambda a, g: a + g.astype(accum_dtype), acc, grads)
                acc = constrain_tree(acc, grad_specs, mesh)
                return (acc, lc), loss

            if stream:
                # weight streaming (gas == 1 by construction): grads pass
                # straight from autodiff (pinned_host for streamed leaves) to
                # the host optimizer — any jnp pass over the full grad tree
                # would stage fp32 HBM temps for the HostExecute operands
                mb = jax.tree.map(lambda x: x[0] if x.ndim >= 1 else x, batch)
                loss0, grads, loco = micro_grads(
                    params, mb, jax.random.fold_in(base_rng, jnp.int32(0)), scale, loco
                )
                losses = loss0[None]
            else:
                zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype), params)
                zeros = constrain_tree(zeros, grad_specs, mesh)
                if gas == 1:
                    mb = jax.tree.map(lambda x: x[0] if x.ndim >= 1 else x, batch)
                    (grads, loco), losses = body((zeros, loco), (jnp.int32(0), mb))
                    losses = losses[None]
                else:
                    idx = jnp.arange(gas, dtype=jnp.int32)
                    (grads, loco), losses = jax.lax.scan(body, (zeros, loco), (idx, batch))

            def grad_epilogue(grads):
                inv = 1.0 / (gas * scale)
                grads = jax.tree.map(lambda g: (g.astype(jnp.float32) * inv), grads)
                # the overflow scan + NaN-zeroing cost a full fp32-grad pass:
                # auto mode runs them for fp16 only (reference bf16 engines
                # skip them too; config.check_grad_overflow forces either way)
                overflow = ls.has_overflow(grads) if check_overflow else jnp.zeros((), jnp.bool_)
                if check_overflow or clip > 0:
                    # clipping must see sanitized grads even in bf16 mode: one
                    # non-finite leaf would NaN the global norm and the clip
                    # scale would poison EVERY parameter in a single step
                    safe_grads = jax.tree.map(
                        lambda g: jnp.where(jnp.isfinite(g), g, jnp.zeros_like(g)), grads
                    )
                else:
                    safe_grads = grads
                if clip > 0:
                    safe_grads, grad_norm = clip_by_global_norm(safe_grads, clip)
                elif monitor_norm:
                    grad_norm = global_grad_norm(safe_grads)
                else:
                    # norm reduction skipped (another full grad read): report
                    # NaN so a consumer can tell "not computed" from 0
                    grad_norm = jnp.full((), jnp.nan, jnp.float32)
                return safe_grads, overflow, grad_norm

            if stream:
                # no full-tree epilogue: overflow protection is the optimizer
                # skip-step (disabled here — bf16-only mode), clipping and the
                # grad-norm readout are unsupported under streaming (any jnp
                # pass over full-model grads stages fp32 HBM temps)
                safe_grads = grads
                overflow = jnp.zeros((), jnp.bool_)
                grad_norm = jnp.full((), jnp.nan, jnp.float32)
            else:
                safe_grads, overflow, grad_norm = grad_epilogue(grads)
            if loco_on:
                # reference _loco_err_buf_update: error buffers absorbed the
                # non-finite residual of an overflow-skipped step — drop them
                # (gated on loco itself, NOT reset_T: reset_T=0 means no
                # periodic reset but overflow recovery must still happen)
                loco = jax.tree.map(
                    lambda e: jnp.where(overflow, jnp.zeros_like(e), e), loco
                )
            new_scaler = ls.update_state(scaler_cfg, scaler_state, overflow)
            mean_loss = jnp.mean(losses)
            if grads_only:
                # NVMe tier: the update happens on the host afterwards
                return safe_grads, new_scaler, mean_loss, grad_norm, overflow, loco
            # offload-aware update + functional skip-step on overflow
            # (reference step skipping, fp16)
            new_params, new_opt_state = self._opt_apply(safe_grads, opt_state, params, lr, overflow)
            return new_params, new_opt_state, new_scaler, mean_loss, grad_norm, overflow, loco

        if grads_only:
            def grads_step(params, scaler_state, step, batch, loco):
                return train_step(params, {}, scaler_state, step, None, batch, loco)

            return jax.jit(grads_step, donate_argnums=(1, 4))

        self._train_step_raw = train_step  # unjitted: profiler jaxpr walk
        return jax.jit(
            train_step,
            donate_argnums=(0, 1, 2, 6),
            out_shardings=(
                self._jit_param_shardings(),
                self._jit_state_shardings(),
                None,
                None,
                None,
                None,
                None,
            ),
        )

    def _build_onebit_train_step(self):
        """Train step for the 1-bit (compressed-exchange) optimizers.

        Reference analogue: engines set ``enable_backward_allreduce=False``
        for OnebitAdam — gradients are NOT reduced; the optimizer updates
        momentum with the local gradient and the compressed allreduce happens
        inside the optimizer (runtime/fp16/onebit/adam.py:14 + the
        NcclBackend pipeline). Here the whole step runs inside one shard_map
        manual region over the data axis so the optimizer sees local grads
        and the packed sign bits are the only full-size payload on the wire.
        """
        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu.parallel.topology import DATA_AXIS

        if not self._pure_dp():
            raise NotImplementedError("1-bit optimizers require a pure data-parallel topology")
        if self.zero_stage != 0:
            raise NotImplementedError(
                "1-bit optimizers support ZeRO stage 0 only: the compressed "
                "exchange needs replicated momentum (reference onebit/adam.py warmup=ZeRO semantics)"
            )
        if self.config.gradient_clipping:
            raise NotImplementedError(
                "gradient_clipping is incompatible with 1-bit optimizers: clipping needs the "
                "full-precision global gradient the compressed exchange never materializes"
            )
        if self.plan.offload_optimizer or self.plan.offload_param:
            raise NotImplementedError("offload tiers are not supported with 1-bit optimizers")

        mesh = self.topo.mesh
        W = self.topo.dp_world_size
        gas = self.config.gradient_accumulation_steps
        scaler_cfg = self.scaler_cfg
        accum_dtype = self.grad_accum_dtype
        state_specs = self.optimizer.state_partition_specs(
            jax.eval_shape(self.optimizer.init, self.params)
        )
        param_specs_rep = jax.tree.map(lambda _: P(), self.params)
        scaler_specs = jax.tree.map(lambda _: P(), self.scaler_state)

        def inner(params, opt_state, scaler_state, step, lr, batch):
            scale = (
                scaler_state.scale
                if scaler_cfg.dynamic or scaler_cfg.init_scale != 1.0
                else jnp.float32(1.0)
            )
            base_rng = jax.random.fold_in(self._rng_key, step)

            def body(carry, xs):
                (acc,) = carry
                i, mb = xs
                rng = jax.random.fold_in(base_rng, i)

                def scaled_loss(p):
                    loss, _aux = self._call_loss(p, mb, rng)
                    return (loss * scale.astype(loss.dtype)).astype(jnp.float32)

                loss_scaled, grads = jax.value_and_grad(scaled_loss)(params)
                acc = jax.tree.map(lambda a, g: a + g.astype(accum_dtype), acc, grads)
                return (acc,), loss_scaled

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype), params)
            if gas == 1:
                mb = jax.tree.map(lambda x: x[0] if x.ndim >= 1 else x, batch)
                (acc,), losses = body((zeros,), (jnp.int32(0), mb))
                losses = losses[None]
            else:
                idx = jnp.arange(gas, dtype=jnp.int32)
                (acc,), losses = jax.lax.scan(body, (zeros,), (idx, batch))

            inv = 1.0 / (gas * scale)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32) * inv, acc)  # LOCAL mean grads
            overflow = jax.lax.pmax(ls.has_overflow(grads).astype(jnp.int32), DATA_AXIS) > 0
            safe_grads = jax.tree.map(
                lambda g: jnp.where(jnp.isfinite(g), g, jnp.zeros_like(g)), grads
            )
            # norm of the local grads averaged over workers: a monitoring
            # proxy — the exact global-gradient norm would need the very
            # full-precision allreduce this optimizer exists to avoid
            grad_norm = jax.lax.pmean(global_grad_norm(safe_grads), DATA_AXIS)
            new_params, new_opt_state = self.optimizer.step(safe_grads, opt_state, params, lr)
            new_params = _tree_select(overflow, params, new_params)
            new_opt_state = _tree_select(overflow, opt_state, new_opt_state)
            new_scaler = ls.update_state(scaler_cfg, scaler_state, overflow)
            mean_loss = jax.lax.pmean(jnp.mean(losses), DATA_AXIS) / scale
            return new_params, new_opt_state, new_scaler, mean_loss, grad_norm, overflow

        def train_step(params, opt_state, scaler_state, step, lr, batch, loco):
            bspecs = jax.tree.map(
                lambda x: P(None, DATA_AXIS)
                if getattr(x, "ndim", 0) >= 2 and x.shape[1] % W == 0
                else P(),
                batch,
            )
            fn = jax.shard_map(
                inner,
                mesh=mesh,
                in_specs=(param_specs_rep, state_specs, scaler_specs, P(), P(), bspecs),
                out_specs=(param_specs_rep, state_specs, scaler_specs, P(), P(), P()),
                axis_names={DATA_AXIS},
                check_vma=False,
            )
            # loco is a uniform-signature pass-through: the 1-bit exchange has
            # its own error-feedback state inside the optimizer
            return fn(params, opt_state, scaler_state, step, lr, batch) + (loco,)

        self._train_step_raw = train_step
        return jax.jit(train_step, donate_argnums=(0, 1, 2))

    def _build_fwd_bwd(self):
        if getattr(self.loss_fn, "custom_value_and_grad", None) is not None:
            raise NotImplementedError(
                "custom-gradient loss functions (1F1B pipeline) require the fused "
                "train_batch() path: the imperative forward/backward API would autodiff "
                "through the GPipe-shaped forward, losing the 1F1B memory bound"
            )
        grad_specs = self.plan.grad_specs
        mesh = self.topo.mesh
        quantized = (
            self._make_quantized_micro_grads(grad_specs, mesh)
            if self._quantized_exchange_enabled()
            else None
        )

        def fwd_bwd(params, scaler_state, step, batch, loco):
            params = self._stage_params(params)
            scale = scaler_state.scale
            rng = jax.random.fold_in(self._rng_key, step)
            if quantized is not None:
                # imperative path honors qgZ/qwZ/LoCo too — same shard_map exchange
                return quantized(params, batch, rng, scale, loco)

            def scaled_loss(p):
                loss, _ = self._call_loss(p, batch, rng)
                return (loss * scale.astype(loss.dtype)).astype(jnp.float32)

            loss_scaled, grads = jax.value_and_grad(scaled_loss)(params)
            grads = constrain_tree(grads, grad_specs, mesh)
            return loss_scaled / scale, grads, loco

        return jax.jit(fwd_bwd, donate_argnums=(4,))

    def _build_apply(self):
        if getattr(self.optimizer, "collective_grad_exchange", False):
            raise RuntimeError(
                "1-bit optimizers require the fused train_batch() path: the imperative "
                "forward/backward/step API reduces gradients before the optimizer runs, "
                "which would bypass the compressed exchange"
            )
        clip = self.config.gradient_clipping
        scaler_cfg = self.scaler_cfg
        gas = self.config.gradient_accumulation_steps
        check_overflow, monitor_norm = self._grad_epilogue_flags()

        def apply_step(params, opt_state, scaler_state, acc_grads, lr):
            params = self._stage_params(params)
            scale = scaler_state.scale
            inv = 1.0 / (gas * scale)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32) * inv, acc_grads)
            overflow = ls.has_overflow(grads) if check_overflow else jnp.zeros((), jnp.bool_)
            if check_overflow or clip > 0:
                # see grad_epilogue: clip needs sanitized grads in bf16 too
                safe_grads = jax.tree.map(
                    lambda g: jnp.where(jnp.isfinite(g), g, jnp.zeros_like(g)), grads
                )
            else:
                safe_grads = grads
            if clip > 0:
                safe_grads, grad_norm = clip_by_global_norm(safe_grads, clip)
            elif monitor_norm:
                grad_norm = global_grad_norm(safe_grads)
            else:
                grad_norm = jnp.full((), jnp.nan, jnp.float32)
            new_params, new_opt_state = self._opt_apply(safe_grads, opt_state, params, lr, overflow)
            new_scaler = ls.update_state(scaler_cfg, scaler_state, overflow)
            return new_params, new_opt_state, new_scaler, grad_norm, overflow

        return jax.jit(
            apply_step,
            donate_argnums=(0, 1, 2, 3),
            out_shardings=(self._jit_param_shardings(), self._jit_state_shardings(), None, None, None),
        )

    # ------------------------------------------------------------------
    # public training API
    # ------------------------------------------------------------------
    def _stack_batch(self, batch_or_iter):
        """Normalize input to a pytree with leading [gas, global_micro, ...]."""
        gas = self.config.gradient_accumulation_steps
        if hasattr(batch_or_iter, "__next__"):
            micro_batches = [next(batch_or_iter) for _ in range(gas)]
            batch = jax.tree.map(lambda *xs: np.stack(xs), *micro_batches)
        else:
            batch = jax.tree.map(
                lambda x: np.asarray(x).reshape((gas, -1) + np.asarray(x).shape[1:]), batch_or_iter
            )
        return batch

    def set_custom_curriculum_truncation(self, fn):
        """Override how a batch adapts to the curriculum difficulty:
        ``fn(stacked_batch, difficulty) -> stacked_batch`` (the analogue of
        the reference's data post-process hook)."""
        self._curriculum_post = fn

    _CURRICULUM_SHAPE_BUDGET = 16

    def _apply_curriculum(self, stacked):
        if self.curriculum_scheduler is None:
            return stacked
        difficulty = self.curriculum_scheduler.update_difficulty(self.global_steps + 1)
        # enforcement for the compile-thrash hazard: every distinct difficulty
        # is a distinct compiled train step. Track them and flag the schedule
        # the moment it exceeds a sane budget, with the actionable fix.
        seen = getattr(self, "_curriculum_difficulties", None)
        if seen is None:
            seen = self._curriculum_difficulties = set()
        if difficulty not in seen:
            seen.add(difficulty)
            if len(seen) == self._CURRICULUM_SHAPE_BUDGET + 1:
                logger.warning(
                    f"curriculum produced {len(seen)} distinct difficulty values — "
                    "each is a separate XLA compilation of the train step. Raise "
                    "schedule.difficulty_step (coarser bins) to bound compile time; "
                    "compiled programs are cached, but a fine-grained schedule can "
                    "spend minutes per new shape."
                )
        if self._curriculum_post is not None:
            return self._curriculum_post(stacked, difficulty)
        if self._curriculum_metric == "seqlen":
            # token-stream convention: leaves carry s+1 tokens for s targets,
            # so difficulty d trains on sequences of length d. Each distinct
            # difficulty is a compiled shape — use coarse difficulty_step.
            # Only SEQUENCE leaves truncate (by batch key name): slicing the
            # last axis of arbitrary leaves would cut hidden dims / per-sample
            # vectors. Custom batches use set_custom_curriculum_truncation.
            seq_keys = {
                "input_ids", "labels", "tokens", "loss_mask", "attention_mask",
                "segment_ids", "positions",
            }

            def trunc(path, x):
                name = str(path[-1].key) if path and hasattr(path[-1], "key") else ""
                if name in seq_keys and getattr(x, "ndim", 0) >= 2 and x.shape[-1] > difficulty + 1:
                    return x[..., : difficulty + 1]
                return x

            return jax.tree_util.tree_map_with_path(trunc, stacked)
        return stacked

    def train_batch(self, data_iter=None, batch=None):
        """Fused full step: gas micro-batches → grads → update. The hot path
        (reference PipelineEngine.train_batch :337 is the analogous fused API)."""
        if (data_iter is None) == (batch is None):
            raise ValueError("pass exactly one of data_iter/batch")
        stacked = self._stack_batch(data_iter if data_iter is not None else batch)
        stacked = self._apply_curriculum(stacked)
        if self._host_opt is not None:
            step, program = self._train_batch_hostopt, self._host_step_jit
        elif self._weight_stream:
            step, program = self._train_batch_streamed, getattr(self, "_stream_grads_jit", None)
        else:
            step, program = self._train_batch_fused, self._train_step_jit
        if program is not None:
            return step(stacked)
        # the step program's first call, builder to the step's return: its
        # trace, lower and compile (or the cache's answer) nest in it
        with get_setup_record().span("program.first_call", key="train_step"):
            loss = step(stacked)
        log_dist(setup_line(self.setup_report()), ranks=[0])
        return loss

    @staticmethod
    def setup_report():
        """What this process spent before its first step, by phase and by
        program (observability/setup_record.py, docs/OBSERVABILITY.md)."""
        return setup_report()

    def _train_batch_fused(self, stacked):
        """train_batch where the whole step is one program on the chip."""
        if self._train_step_jit is None:
            self._train_step_jit = self._build_train_step()
        lr = self._lr_for_step()
        self.tput_timer.start()
        self.timers(STEP_GLOBAL_TIMER).start()
        self._unpark_for_step()
        shardings = self._batch_shardings(stacked, leading_gas_dim=True)
        stacked = jax.device_put(stacked, shardings)
        fp = self.config.flops_profiler
        profiling = fp.enabled and self.global_steps + 1 == fp.profile_step
        t_prof = time.perf_counter() if profiling else 0.0
        (
            self.params,
            self.opt_state,
            self.scaler_state,
            loss,
            grad_norm,
            overflow,
            self._loco_state,
        ) = self._train_step_jit(
            self.params,
            self.opt_state,
            self.scaler_state,
            jnp.int32(self.global_steps),
            jnp.float32(lr),
            stacked,
            self._loco_state,
        )
        if profiling:
            jax.block_until_ready(loss)
            self._run_flops_profile(stacked, time.perf_counter() - t_prof)
        self.timers(STEP_GLOBAL_TIMER).stop()
        self.params = self._park_params(self.params)
        self.opt_state = self._park_state(self.opt_state)
        self._after_step(loss, grad_norm, overflow)
        self.tput_timer.stop(global_step=True)
        return loss

    def _run_flops_profile(self, stacked, duration):
        """flops_profiler.profile_step hook (reference engine.py:2690): cost
        analysis of the train step + the measured wall time. Runs once; the
        extra lower/compile pass is the price of the XLA cost model (logged)."""
        from deepspeed_tpu.profiling.flops_profiler import (
            FlopsProfiler,
            jaxpr_flops_by_primitive,
        )

        fp = self.config.flops_profiler
        if fp.profile_step <= 1:
            logger.warning(
                "flops_profiler.profile_step=1 measures the FIRST step, whose wall "
                "time includes tracing + XLA compilation — the reported achieved "
                "FLOPS/s will be far below hardware rate; set profile_step >= 2"
            )
        args = (
            self.params, self.opt_state, self.scaler_state,
            jnp.int32(self.global_steps), jnp.float32(self._current_lr()), stacked,
            self._loco_state,
        )
        try:
            log_dist("flops profile: lowering step for cost analysis (one-time)", ranks=[0])
            cost = self._train_step_jit.lower(*args).compile().cost_analysis() or {}
        except Exception as e:  # profiling must never break training
            logger.warning(f"flops profile failed: {e}")
            return
        by_prim = {}
        if fp.detailed and getattr(self, "_train_step_raw", None) is not None:
            try:
                jaxpr = jax.make_jaxpr(self._train_step_raw)(*args)
                by_prim = jaxpr_flops_by_primitive(jaxpr.jaxpr)
            except Exception as e:
                logger.warning(f"per-primitive breakdown failed: {e}")
        prof = FlopsProfiler(ds_engine=self)
        prof._analysis = {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "by_primitive": by_prim,
        }
        prof._duration = duration
        prof.set_total_params(self.params)
        prof.print_model_profile(
            profile_step=self.global_steps + 1,
            module_depth=fp.module_depth,
            top_modules=fp.top_modules,
            detailed=fp.detailed,
            output_file=fp.output_file,
        )
        if self.config.memory_breakdown:
            from deepspeed_tpu.utils.memory import see_memory_usage

            see_memory_usage("after profiled step", force=True)

    def forward(self, batch):
        """Compute loss for one micro-batch; grads are computed in the same
        pass and cached for backward() (no double forward)."""
        if self._fwd_bwd_jit is None:
            self._fwd_bwd_jit = self._build_fwd_bwd()
        self.timers(FORWARD_GLOBAL_TIMER).start()
        self._unpark_params()
        batch = self._apply_curriculum(batch)  # name-keyed: works un-stacked too
        batch = jax.device_put(batch, self._batch_shardings(batch))
        loss, grads, self._loco_state = self._fwd_bwd_jit(
            self.params, self.scaler_state, jnp.int32(self.micro_steps), batch, self._loco_state
        )
        self.timers(FORWARD_GLOBAL_TIMER).stop()
        self._pending_grads = grads
        self._last_loss = loss
        return loss

    __call__ = forward

    def backward(self, loss=None, retain_graph=False, scale_wrt_gas=True):
        """Accumulate the cached grads (reference engine.backward :2436)."""
        if getattr(self, "_pending_grads", None) is None:
            raise RuntimeError("call forward() before backward()")
        self.timers(BACKWARD_GLOBAL_TIMER).start()
        grads = self._pending_grads
        self._pending_grads = None
        if self._acc_grads is None:
            self._acc_grads = jax.tree.map(lambda g: g.astype(self.grad_accum_dtype), grads)
        else:
            if self._acc_add_jit is None:
                self._acc_add_jit = jax.jit(
                    lambda acc, g: jax.tree.map(lambda a, b: a + b.astype(a.dtype), acc, g),
                    donate_argnums=(0,),
                )
            self._acc_grads = self._acc_add_jit(self._acc_grads, grads)
        self.timers(BACKWARD_GLOBAL_TIMER).stop()
        return loss

    def step(self, lr_kwargs=None):
        """Optimizer step at gradient-accumulation boundaries
        (reference engine.step :2606 → _take_model_step :2533)."""
        boundary = self.is_gradient_accumulation_boundary()
        self.micro_steps += 1
        self.global_samples += self.config.train_micro_batch_size_per_gpu * self.topo.dp_world_size
        if not boundary:
            return
        if self._acc_grads is None:
            raise RuntimeError("step() with no accumulated gradients")
        if self._host_opt is not None:
            raise NotImplementedError(
                "the NVMe optimizer tier supports the fused train_batch() API "
                "only (the imperative forward/backward/step path would leave "
                "accumulated grads on-device across the host update)"
            )
        if self._apply_jit is None:
            self._apply_jit = self._build_apply()
        lr = self._lr_for_step()
        self._unpark_for_step()
        self.timers(STEP_GLOBAL_TIMER).start()
        (
            self.params,
            self.opt_state,
            self.scaler_state,
            grad_norm,
            overflow,
        ) = self._apply_jit(self.params, self.opt_state, self.scaler_state, self._acc_grads, jnp.float32(lr))
        self.params = self._park_params(self.params)
        self.opt_state = self._park_state(self.opt_state)
        self.timers(STEP_GLOBAL_TIMER).stop()
        self._acc_grads = None
        if bool(overflow) and any(
            e.size for e in jax.tree_util.tree_leaves(self._loco_state)
        ):
            # mirror the fused step's overflow recovery: LoCo error buffers
            # absorbed the non-finite residual during forward() and must be
            # dropped, or every later compensated gradient stays non-finite
            self._loco_state = jax.tree.map(jnp.zeros_like, self._loco_state)
        self._after_step(self._last_loss, grad_norm, overflow)

    def _lr_for_step(self):
        if self.lr_scheduler is not None:
            lrs = self.lr_scheduler.step()
            return float(lrs[0] if isinstance(lrs, (list, tuple)) else lrs)
        return float(self.optimizer.get_lr())

    def _after_step(self, loss, grad_norm, overflow):
        self.global_steps += 1
        self._last_loss = loss
        self._last_grad_norm = grad_norm
        self._last_overflow = overflow
        if self.config.steps_per_print and self.global_steps % self.config.steps_per_print == 0:
            overflow_f = bool(overflow) if overflow is not None else False
            if overflow_f:
                self.skipped_steps += 1
            loss_f = float(loss) if loss is not None else float("nan")
            gn = float(grad_norm) if grad_norm is not None else float("nan")
            # NaN is the "not computed" sentinel (monitor_grad_norm auto-off)
            gn_s = f"{gn:.3f}" if gn == gn else "n/a (set monitor_grad_norm)"
            log_dist(
                f"step={self.global_steps} loss={loss_f:.4f} lr={self._current_lr():.3e} "
                f"grad_norm={gn_s} scale={float(self.scaler_state.scale):.1f}"
                + (" OVERFLOW-SKIPPED" if overflow_f else ""),
                ranks=[0],
            )
            if self.monitor is not None and self.monitor.enabled:
                self.monitor.write_events(
                    [
                        ("Train/Samples/train_loss", loss_f, self.global_samples),
                        ("Train/Samples/lr", self._current_lr(), self.global_samples),
                        ("Train/Samples/grad_norm", float(grad_norm), self.global_samples),
                        ("Train/Samples/loss_scale", float(self.scaler_state.scale), self.global_samples),
                    ]
                )
        if self.wall_clock_breakdown and self.global_steps % self.config.steps_per_print == 0:
            self.timers.log([FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER, STEP_GLOBAL_TIMER])

    def eval_batch(self, batch):
        first = self._eval_jit is None
        # (no frame of its own around the first call: every frame above a
        # program's trace is paid again in its lowering, PERF.md section 6, PR 51)
        with get_setup_record().span("program.first_call", key="eval") if first else contextlib.nullcontext():
            if first:

                def eval_fn(params, batch):
                    params = self._stage_params(params)
                    loss, aux = self._call_loss(params, batch, None if not self._loss_fn_takes_rng else self._rng_key)
                    return loss

                self._eval_jit = jax.jit(eval_fn)
            self._unpark_params()
            batch = jax.device_put(batch, self._batch_shardings(batch))
            return self._eval_jit(self.params, batch)

    # ------------------------------------------------------------------
    # dataloader (reference deepspeed_io, engine.py:2005)
    # ------------------------------------------------------------------
    def deepspeed_io(self, dataset, batch_size=None, route="train", data_sampler=None, collate_fn=None, num_local_io_workers=None):
        from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader

        return DeepSpeedDataLoader(
            dataset,
            batch_size=batch_size or self.config.train_batch_size,
            collate_fn=collate_fn or self.collate_fn,
            seed=self.config.seed,
        )

    # ------------------------------------------------------------------
    # checkpointing (reference save_checkpoint :3560 / load_checkpoint :3212)
    # ------------------------------------------------------------------
    def _client_state(self):
        return {
            "micro_steps": self.micro_steps,
            "global_steps": self.global_steps,
            "global_samples": self.global_samples,
            "skipped_steps": self.skipped_steps,
            "lr_scheduler": self.lr_scheduler.state_dict() if hasattr(self.lr_scheduler, "state_dict") else None,
        }

    def _checkpoint_writer(self):
        if getattr(self, "_ckpt_writer", None) is None:
            from deepspeed_tpu.runtime.checkpoint_engine import create_checkpoint_engine

            self._ckpt_writer = create_checkpoint_engine(self.config.checkpoint.writer)
            self._ckpt_pending = None
        return self._ckpt_writer

    def checkpoint_commit(self):
        """Join outstanding async checkpoint writes and publish their tag
        (the reference two-phase commit, engine.py:3655). No-op for the
        synchronous orbax path. A failed commit DROPS the pending tag —
        'latest' must never name a checkpoint that did not land."""
        if getattr(self, "_ckpt_pending", None) is None:
            return
        save_dir, tag, save_latest = self._ckpt_pending
        self._ckpt_pending = None  # even on failure: never re-publish a failed tag
        self._ckpt_writer.commit(tag)  # raises if any write failed
        if jax.process_count() > 1:
            # every process's writes must be durable before the marker exists
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices(f"ckpt_commit_{tag}")
        if save_latest and jax.process_index() == 0:
            with open(os.path.join(save_dir, "latest"), "w") as f:
                f.write(tag)

    def save_checkpoint(self, save_dir, tag=None, client_state=None, save_latest=True, exclude_frozen_parameters=False):
        tag = tag or f"global_step{self.global_steps}"
        state = self._client_state()
        state.update(client_state or {})
        # NVMe tier: materialize the swapped state (leaf at a time) for the
        # writer; self.opt_state itself is an empty placeholder
        opt_payload = (
            self._host_opt.as_state_tree() if self._host_opt is not None else self.opt_state
        )
        params_payload = self.params
        canon = getattr(self.optimizer, "canonicalize_checkpoint_state", None)
        if canon is not None and self._host_opt is None:
            # 0/1 Adam phase-2: strip worker-0 drift so the checkpoint holds
            # the last-sync canonical params (load re-localizes per worker).
            # The stamp lets load tell canonicalized checkpoints from older
            # drifted ones — re-localizing the latter would ADD drift twice
            # (round-3 advisor finding)
            params_payload, opt_payload = canon(params_payload, opt_payload)
            state["canonicalized_onebit_state"] = True
        writer = self.config.checkpoint.writer
        if writer:
            # pluggable engine path (reference checkpoint_engine/): async
            # writers return after the device→host snapshot; the PREVIOUS
            # save publishes here (decoupled two-phase commit) and a final
            # checkpoint_commit() publishes the last one
            eng = self._checkpoint_writer()
            self.checkpoint_commit()
            eng.create(tag)
            if jax.process_index() == 0:
                # the writer branch must ship the recovery script too
                # (the reference copies it on EVERY save, engine.py:3991);
                # the writers only create directories later, off-thread
                os.makedirs(save_dir, exist_ok=True)
                from deepspeed_tpu.checkpoint.engine import copy_recovery_script

                copy_recovery_script(save_dir)
            eng.save(
                {
                    "params": params_payload,
                    "opt_state": opt_payload,
                    "scaler_state": self.scaler_state,
                    "__meta__": state,
                },
                os.path.join(save_dir, tag, "state"),
            )
            self._ckpt_pending = (save_dir, tag, save_latest)
            if writer in ("sync", "torch"):
                self.checkpoint_commit()
            return True
        from deepspeed_tpu.checkpoint.engine import save_checkpoint as _save

        _save(
            save_dir,
            tag,
            params=params_payload,
            opt_state=opt_payload,
            scaler_state=self.scaler_state,
            client_state=state,
            save_latest=save_latest,
        )
        return True

    def _restore_tree(self, template, loaded):
        """Order-based restore: the writer serialized leaves in tree-flatten
        order, so zip them back into the template's structure/shardings."""
        t_leaves, treedef = jax.tree_util.tree_flatten(template)
        l_leaves = jax.tree_util.tree_leaves(loaded)
        if len(t_leaves) != len(l_leaves):
            raise ValueError(f"checkpoint leaf count {len(l_leaves)} != "
                             f"template leaf count {len(t_leaves)}")
        out = []
        for t, l in zip(t_leaves, l_leaves):
            if tuple(t.shape) != tuple(l.shape):
                raise ValueError(f"checkpoint leaf shape {tuple(l.shape)} != "
                                 f"template shape {tuple(t.shape)}")
            out.append(jax.device_put(jnp.asarray(l, dtype=t.dtype), t.sharding))
        return jax.tree_util.tree_unflatten(treedef, out)

    def load_checkpoint(
        self,
        load_dir,
        tag=None,
        load_module_strict=True,
        load_optimizer_states=True,
        load_lr_scheduler_states=True,
        load_module_only=False,
        custom_load_fn=None,
    ):
        writer = self.config.checkpoint.writer
        if writer:
            self.checkpoint_commit()  # a just-written tag must be readable
            if tag is None:
                latest = os.path.join(load_dir, "latest")
                if not os.path.isfile(latest):
                    return None, {}
                tag = open(latest).read().strip()
            eng = self._checkpoint_writer()
            data = eng.load(os.path.join(load_dir, tag, "state"))
            self.params = self._restore_tree(self.params, data["params"])
            if load_optimizer_states and not load_module_only and "opt_state" in data:
                if self._host_opt is not None:
                    # pluggable writers may hand back a FLAT leaf list; rebuild
                    # the name-keyed dict from the template's structure
                    tmpl = self._host_opt.state_tree_template()
                    loaded = data["opt_state"]
                    if not isinstance(loaded, dict):
                        loaded = jax.tree_util.tree_unflatten(
                            jax.tree_util.tree_structure(tmpl),
                            jax.tree_util.tree_leaves(loaded),
                        )
                    self._host_opt.load_state_tree(jax.tree.map(np.asarray, loaded))
                else:
                    self.opt_state = self._restore_tree(self.opt_state, data["opt_state"])
            if "scaler_state" in data:
                self.scaler_state = self._restore_tree(self.scaler_state, data["scaler_state"])
            client_state = data.get("__meta__", {})
            # Missing stamp defaults True: every canonicalizing release saved
            # canonical state before the stamp existed — skipping would break
            # their resume. Only an explicit False (a future drifted-state
            # writer) disables re-localization.
            if (
                load_optimizer_states
                and not load_module_only
                and client_state.get("canonicalized_onebit_state", True)
            ):
                self._maybe_relocalize_params()
            self._restore_client_state(client_state, load_module_only, load_lr_scheduler_states)
            return os.path.join(load_dir, tag), client_state
        from deepspeed_tpu.checkpoint.engine import load_checkpoint as _load

        want_opt = load_optimizer_states and not load_module_only
        if self._host_opt is not None:
            # template mirrors the current swapped tree's structure/dtypes
            # structure-only template: no need to read the live state back
            opt_template = self._host_opt.state_tree_template() if want_opt else None
        else:
            opt_template = self.opt_state if want_opt else None
        out = _load(
            load_dir,
            tag,
            params_template=self.params,
            opt_state_template=opt_template,
            scaler_template=self.scaler_state,
        )
        if out is None:
            return None, {}
        self.params = out["params"]
        if out.get("opt_state") is not None:
            if self._host_opt is not None:
                self._host_opt.load_state_tree(jax.tree.map(np.asarray, out["opt_state"]))
            else:
                self.opt_state = out["opt_state"]
        if out.get("scaler_state") is not None:
            self.scaler_state = out["scaler_state"]
        client_state = out.get("client_state", {})
        # missing stamp defaults True — see the writer-branch comment above
        if (
            want_opt
            and out.get("opt_state") is not None
            and client_state.get("canonicalized_onebit_state", True)
        ):
            self._maybe_relocalize_params()
        self._restore_client_state(client_state, load_module_only, load_lr_scheduler_states)
        return out.get("load_path", load_dir), client_state

    def _maybe_relocalize_params(self):
        """Inverse of checkpoint canonicalization for 0/1 Adam: worker w's
        params/master = canonical + u[w], rebuilt with one shard_map over the
        data axis (out specs replicated + check_vma=False — the same
        physically-divergent convention as the 1-bit train step)."""
        canon = getattr(self.optimizer, "canonicalize_checkpoint_state", None)
        if canon is None or self._host_opt is not None or not hasattr(self.opt_state, "inner"):
            return
        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu.parallel.topology import DATA_AXIS

        mesh = self.topo.mesh
        pspec = jax.tree.map(lambda _: P(), self.params)
        mspec = jax.tree.map(lambda _: P(), self.opt_state.master)
        u_specs = jax.tree.map(lambda _: P(DATA_AXIS), self.opt_state.inner.u)

        def inner(params, master, u):
            new_master = jax.tree.map(lambda m, uu: m + uu[0], master, u)
            new_params = jax.tree.map(lambda p, m: m.astype(p.dtype), params, new_master)
            return new_params, new_master

        fn = jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(pspec, mspec, u_specs),
            out_specs=(pspec, mspec),
            axis_names={DATA_AXIS},
            check_vma=False,
        )
        new_params, new_master = jax.jit(fn)(
            self.params, self.opt_state.master, self.opt_state.inner.u
        )
        self.params = new_params
        self.opt_state = self.opt_state._replace(master=new_master)

    def _restore_client_state(self, client_state, load_module_only, load_lr_scheduler_states):
        """Counter + LR-schedule restore shared by the orbax and writer-engine
        load paths (one exit path: a counter added to _client_state restores
        everywhere)."""
        if load_module_only:
            return
        self.micro_steps = client_state.get("micro_steps", 0)
        self.global_steps = client_state.get("global_steps", 0)
        self.global_samples = client_state.get("global_samples", 0)
        self.skipped_steps = client_state.get("skipped_steps", 0)
        sched_sd = client_state.get("lr_scheduler")
        if load_lr_scheduler_states and sched_sd and hasattr(self.lr_scheduler, "load_state_dict"):
            self.lr_scheduler.load_state_dict(sched_sd)

    def save_16bit_model(self, save_dir, save_filename="pytorch_model.bin", exclude_frozen_parameters=False):
        """Consolidated half-precision export (reference save_16bit_model
        :4135 / _zero3_consolidated_16bit_state_dict :4066): gather shards to
        host and save one file."""
        from deepspeed_tpu.checkpoint.engine import save_16bit_model as _save16

        return _save16(save_dir, save_filename, self.params)
