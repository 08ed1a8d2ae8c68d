"""InferenceEngineV2: paged-KV continuous-batching engine.

Reference: ``InferenceEngineV2.put()`` (inference/v2/engine_v2.py:107) — each
call advances every scheduled sequence by its packed tokens against the
blocked KV cache and returns next-token logits per sequence.

TPU adaptation:
  * the paged KV cache is [L, num_blocks, block_size, n_kv, d] per k/v, L the
    layers that attend over keys and values (``kv_layers``); a model with
    recurrent layers (``layer_kinds``: Gated DeltaNet or Mamba) has a second
    kind of cache beside it, one fixed-size slot a tracked sequence: the
    layers' states [Lr * slots, ...] float32 (DeltaNet [nv, dk, dv], Mamba
    [N, d / 128, 128]) and conv inputs [Lr * slots, (K - 1) * C];
    a stack that mixes window and global layers holds the window layers' K/V
    in a window pool [Lw, slots * wb, block_size, n_kv, d], a ring of wb
    blocks a tracked sequence (kv_pool.py), and the block pool the global
    layers' alone; a latent-attention model (``kv_lora_rank``) has ONE plane,
    [L, num_blocks, latent_dim, block_size]: a token's keys and values are
    one vector every head shares (ops/attention/latent_pallas.py), and where
    a layer holds two latent attentions (``moe_shortcut``) L counts the
    attentions: plane 2 l + i is sub-block i of layer l's;
  * paged attention = block-table gather → dense attention with a length
    mask, or the Pallas paged kernel underneath (``paged_attention``);
  * a step is one compiled program over a fixed grid (the SplitFuse
    "fixed-shape friendly" re-think for compiled step functions): the split
    step or the speculative verify step.

Every step goes one way: ``_stage_<shape>`` (numpy only: the inputs by name,
and the step's ``StepStats``) → ``_launch`` (the one call site of a step
program; the pools go in and come back as one donated argument), both under
``_dispatch``, which returns the ``StepInFlight`` → ``_collect`` (host
copies, wait, materialize). A served split step is the pair ``launch_step``
/ ``collect_step``: the serving core and ``generate()`` launch step n+1
before they collect step n (``launch_ahead``), and a decode row of n+1 takes
the token step n sampled from the device (``last_tokens`` / ``tok_src``),
never through the host.
"""

import dataclasses
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.accelerator.device import on_tpu, setup_compile_cache
from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.ragged_manager import DSStateManager
from deepspeed_tpu.inference.v2.scheduler import RaggedBatch, RaggedScheduler
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.observability.setup_record import get_setup_record, setup_span
from deepspeed_tpu.observability.tracing import get_tracer
from deepspeed_tpu.ops.stack_matmul import Stacked, stack_dot
from deepspeed_tpu.utils.logging import log_dist
from deepspeed_tpu.utils.timer import device_synchronize

# the host's clock for a step's enqueue and ready stamps: the span tracer's
# (``time.monotonic``), under a name of its own so that a test can drive it
_now = time.monotonic

# step program of each cache key's kind: ("split", (rc, tq)) | ("verify", k)
_BUILDERS = {
    "split": "_build_split_step",
    "verify": "_build_verify_step",
}

# a layer's attention projections, which an unrolled stack never slices out of
# their stack (_static_layer)
_READ_IN_PLACE = ("wq", "wk", "wv", "wo", "kda_qkv", "kda_out")


def _start_host_copies(arrays) -> None:
    """Ask for each array's copy to the host NOW, queued behind the program
    that computes it, so a blocking wait that follows ends with the values
    already on their way. Asked only after the wait, the copy costs one more
    host round trip with the device idle (``np.asarray`` of an array still
    being computed queues it the same way, which is what a step did before
    its wait became a span of its own). Test doubles' numpy arrays have
    nothing to copy."""
    for arr in arrays:
        start = getattr(arr, "copy_to_host_async", None)
        if start is not None:
            start()


def _is_ready(arr) -> bool:
    """Whether a step's output has been computed, asked without waiting
    (``jax.Array.is_ready``). Test doubles' numpy arrays always have."""
    ready = getattr(arr, "is_ready", None)
    return True if ready is None else bool(ready())


def _materialize_rows(res: dict) -> dict:
    """{uid: (logits array, row)} -> {uid: host row}, pulling each distinct
    ARRAY from the device exactly once (rows of one step share their
    array)."""
    hosts = {}
    out = {}
    for uid, (arr, idx) in res.items():
        key = id(arr)
        if key not in hosts:
            # memoized by id(): each distinct device array transfers once
            hosts[key] = np.asarray(arr)  # dstpu: noqa[host-sync-in-loop]
        out[uid] = hosts[key][idx]
    return out


# layers of one kind in a row that a hybrid stack's step programs loop over
# and do not unroll (_drive_layers)
RUN_LOOP = 5


@dataclasses.dataclass
class StepStats:
    """What one step was sized to and what it carried, filled where
    the step is staged (``moe`` after its wait). The serving core folds it
    into grid_slots_total / scheduled_tokens_total / steps_with_prefill_total,
    paged_live_blocks_total / paged_table_slots_total / paged_programs_total,
    chunk_live_blocks_total
    / chunk_table_slots_total, moe_*_total, the recurrent kind's <kind>_*_total, kv_*_total,
    paged_window_live_blocks_total, latent_decode_rows_total /
    latent_decode_blocks_total / latent_live_blocks_total, latent_chunk_rows_total /
    latent_chunk_expanded_rows_total,
    moe_group_hit_tokens_total, steps_ahead_total,
    ahead_rows_dropped_total, and the step's time on the device by KIND:
    decode_step_seconds_total / chunk_step_seconds_total with the counts of the
    steps they hold, and steps_starved_total.

    The kind is ``prefill_tokens``: a split step that carried a prompt chunk
    is a CHUNK step, every other split step a DECODE step. A verify step is
    timed the same way and counted as a decode step (up to ``k + 1`` tokens a
    row in one program: its seconds are a step's, not a token's)."""

    grid_slots: int = 0
    scheduled_tokens: int = 0
    prefill_tokens: int = 0
    # decode attention: blocks the decode rows' contexts cover against the
    # slots of their tables, summed over one layer's calls (_count_paged)
    paged_live_blocks: int = 0
    paged_table_slots: int = 0
    # expert models: {"routed", "computed", "hot", "calls", "hit"} (rows,
    # layer calls, experts with a row) of the step's expert layers
    # (_count_moe); None for a dense model
    moe: Optional[dict] = None
    # recurrent layers (the stack's ``recurrent_kind``, a key of T.RECURRENT):
    # rows whose state took the one-token update and the prompt tokens the
    # chunk rule walked, of ONE layer (every such layer sees the same); 0 for
    # a model without them
    recurrent_decode_rows: int = 0
    recurrent_chunk_tokens: int = 0
    # chunk attention: key blocks the chunk rows hold (pool blocks below the
    # chunk's start + the chunk's own) against the slots a walk of whole
    # tables and whole chunks covers, of one layer (_count_chunk)
    chunk_live_blocks: int = 0
    chunk_table_slots: int = 0
    # the cache as the step found it: blocks of the block pool held, ring
    # blocks of the window pool held (0 without one), tokens of context the
    # tracked sequences hold; and, of ONE window layer, the blocks the decode
    # rows' walks visit (beside paged_live_blocks, what a global layer's do)
    kv_global_blocks: int = 0
    kv_window_blocks: int = 0
    kv_context_tokens: int = 0
    paged_window_live_blocks: int = 0
    # ... and the programs of dstpu_paged_decode that read paged_live_blocks:
    # a program reads blocks_a_program of a row's blocks (_count_paged)
    paged_programs: int = 0
    # a latent pool: the decode rows of the step and the pool blocks ONE
    # PLANE's absorbed decode walks for them (a layer's, where a layer has one
    # latent attention; a step walks kv_layers planes); 0 elsewhere
    latent_decode_rows: int = 0
    latent_decode_blocks: int = 0
    # ... the chunk rows of the step (one plane's again) and those of them that
    # attended in the EXPANDED form: all or none, by the step's bucket
    # (latent_pallas.chunk_expands)
    latent_chunk_rows: int = 0
    latent_chunk_expanded_rows: int = 0
    # ... and the pool blocks the tracked sequences' tables hold (kv_global_blocks
    # also counts what the prefix cache retains until a request needs the room)
    latent_live_blocks: int = 0
    # one step in flight (the serving core fills both): the step was launched
    # before its predecessor was collected; rows it computed for a request
    # that had stopped by the time it was collected
    ahead: bool = False
    ahead_rows_dropped: int = 0
    # the step on the device, on the host's clock (_collect fills the first
    # two; no part of what a step WAS, so not compared): ``t_ready``, when the
    # wait on its outputs returned (None: nothing was launched, nothing timed);
    # ``device_s``, from the later of the step collected before it turning
    # ready and its own enqueue to ``t_ready``. ``starved`` (_launch): the step
    # in flight had ALREADY finished when this one was enqueued, so the chip
    # ran dry before it: the host was the pace for this step, and the end of
    # the step before it is seen late (its seconds leak into this one's)
    t_ready: Optional[float] = dataclasses.field(default=None, compare=False)
    device_s: float = dataclasses.field(default=0.0, compare=False)
    starved: bool = dataclasses.field(default=False, compare=False)


@dataclasses.dataclass
class StepInFlight:
    """A launched step, not yet waited for: everything that is the
    STEP's and not the engine's, so that the next step may be launched before
    this one is collected. ``waited``: the program's outputs the results come
    from (never the engine's pools: by collect time those are the next
    step's); ``finish``: outputs on the host -> the entry point's result;
    ``stats``: the step's StepStats (``moe`` filled at collect from ``moe``,
    an expert model's routed rows, still on the device); ``rows``: for a
    split step, each completed row's uid -> its slot of the program's
    ``last_tokens`` output (decode slot i, or R + chunk row j);
    ``t_enqueued``: the host's clock just before the jitted call, after the
    host-to-device transfers (None: nothing was launched)."""

    waited: list
    finish: object
    stats: StepStats
    moe: object = None
    rows: Dict[int, int] = dataclasses.field(default_factory=dict)
    t_enqueued: Optional[float] = None


class InferenceEngineV2:
    @setup_span("setup.build_engine")
    def __init__(self, model_config: T.TransformerConfig, params, config: Optional[RaggedInferenceEngineConfig] = None):
        setup_compile_cache()
        self.config = config or RaggedInferenceEngineConfig()
        self._mc = model_config
        if model_config.position == "alibi":
            raise NotImplementedError(
                "v2 paged engine: alibi (bloom) is not supported — the paged "
                "attention kernel takes no bias; serve bloom through the v1 engine"
            )

        if not model_config.attn_causal:
            raise ValueError(
                "v2 paged engine: encoder models (attn_causal=False) do not "
                "autoregressively generate — run models.transformer.forward()"
            )
        dtype = T.DTYPES.get(self.config.dtype, jnp.bfloat16)
        params = jax.tree.map(
            lambda p: p.astype(dtype) if jnp.issubdtype(p.dtype, jnp.floating) else p, params
        )
        quantized = bool(getattr(self.config, "quant", None) and self.config.quant.enabled)
        tp = int(getattr(self.config, "tp_size", 1) or 1)
        self._hybrid = model_config.hybrid
        # a stack that mixes window and global layers: a window pool beside
        # the block pool, a ring a tracked sequence
        self._windowed = model_config.window_layers > 0
        # what a sequence holds beside its K/V blocks, for the refusals' words
        # the stack's recurrent layer kind (models.transformer.RECURRENT), or None
        self._rec = T.RECURRENT.get(model_config.recurrent_kind)
        self._beside = (self._rec.words if self._hybrid else
                        "window layers keep their K/V in a window pool" if self._windowed else None)
        if self._beside:
            self._refuse_at_build(model_config, quantized, tp)
        # latent attention: a pool of one plane, one vector a token
        self._latent = model_config.latent
        if self._latent:
            self._refuse_latent_at_build(quantized, tp)
        if model_config.n_experts > 0 and (quantized or tp > 1):
            # the expert layer's grouped matmul reads whole bf16 expert
            # weights on one device; neither variant has a test
            raise NotImplementedError(
                "v2 paged engine: a mixture-of-experts model with "
                + ("quantized weights" if quantized else f"tp_size={tp}")
                + " is not supported: the grouped expert matmul "
                "(parallel/moe/grouped.py) has no int8 or model-sharded form yet"
            )
        if T.qk_norm_full(model_config) and tp > 1:
            raise NotImplementedError(
                f"v2 paged engine: qk_norm_kind='rmsnorm_full' with tp_size={tp}: "
                "the norm spans every head, so under head sharding it needs a "
                "reduction over the model axis that has no test yet"
            )
        if quantized:
            from deepspeed_tpu.inference.quantization import quantize_inference_params

            params = quantize_inference_params(
                params, bits=self.config.quant.bits, group_size=self.config.quant.group_size
            )
        self.params = params
        kv = self.config.kv_cache
        # a model with recurrent layers, or one with a window pool: one slot a tracked
        # sequence, and one spare that the padding of a step's grid points at
        self._state_slots = (
            self.config.state_manager.max_tracked_sequences + 1 if self._beside else 0)
        from deepspeed_tpu.inference.v2.kv_pool import window_blocks

        # ring blocks a slot a window layer
        self._win_blocks = (
            window_blocks(model_config.sliding_window, kv.block_size) if self._windowed else 0)
        self.state_manager = DSStateManager(
            self.config.state_manager, kv, state_slots=max(0, self._state_slots - 1),
            window_blocks=self._win_blocks)
        self.scheduler = RaggedScheduler(
            self.config.state_manager,
            self.state_manager,
            prompt_chunk=int(getattr(self.config, "prompt_chunk", 0) or 0),
            max_prompt_chunks=int(getattr(self.config, "max_prompt_chunks", 0) or 0),
        )
        c = model_config
        # --- tensor parallelism (reference config_v2.py:16 tp_size / :33
        # tensor_parallel): GSPMD shards the dense algebra from the param
        # shardings below; the Pallas paged-attention call gets an explicit
        # shard_map island over the model axis (_paged_attention_sharded) —
        # kernels are opaque to GSPMD's auto-partitioner.
        self._tp = int(getattr(self.config, "tp_size", 1) or 1)
        self._mesh = None
        self._kv_sharding = None  # tp>1: head-sharded pool layout
        self._kv_scale_sharding = None  # tp>1 + int8: scale planes ride along
        if self._tp > 1:
            from deepspeed_tpu.models import param_partition_specs
            from deepspeed_tpu.parallel.topology import MODEL_AXIS, get_topology

            if c.kv_heads % self._tp or c.n_heads % self._tp:
                raise ValueError(
                    f"tp_size={self._tp} must divide n_heads={c.n_heads} and "
                    f"kv_heads={c.kv_heads} (contiguous head sharding keeps "
                    "GQA groups rank-local)"
                )
            topo = get_topology()
            if topo.axis_size(MODEL_AXIS) != self._tp:
                raise ValueError(
                    f"tp_size={self._tp} needs a topology whose '{MODEL_AXIS}' axis "
                    f"is {self._tp} (got {topo.axis_size(MODEL_AXIS)}): set one up "
                    "with set_topology(Topology(model=...)) before building the engine"
                )
            self._mesh = topo.mesh
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            specs = self._match_specs(self.params, param_partition_specs(c))
            self.params = jax.tree.map(
                lambda p, s: jax.device_put(p, NamedSharding(self._mesh, s)),
                self.params,
                specs,
            )
            self._kv_sharding = NamedSharding(
                self._mesh, P(None, None, None, MODEL_AXIS, None)
            )
            # the int8 scale planes drop the head_dim axis but shard the
            # same kv-head dim; stored so the sharded handoff import can
            # re-lay-out incoming scale windows without rebuilding specs
            self._kv_scale_sharding = NamedSharding(
                self._mesh, P(None, None, None, MODEL_AXIS)
            )
        # --- quantized TP collectives: "int8" replaces the implicit GSPMD
        # psum behind the attention-output and MLP down projections with an
        # int8 reduce-scatter + re-quantized int8 all-gather inside an
        # explicit shard_map island (comm/quantized.quantized_psum_tp). A
        # typo raises here; tp_size=1 makes "int8" a validated no-op.
        from deepspeed_tpu.comm.quantized import check_comm_quant

        self._comm_quant = check_comm_quant(
            str(getattr(self.config, "comm_quant", "none") or "none")
        )
        self._tp_quant = self._comm_quant == "int8" and self._tp > 1
        # --- tile-granular overlap (comm/overlap_tiled.py): "tiled" splits
        # each TP row wire into tp_overlap_tiles independent per-tile
        # reduce-scatter→all-gather rings (ppermute peers the latency-hiding
        # scheduler can interleave with compute); the int8 planes ride the
        # same tiles. tp_size=1 makes it a validated no-op. The wire
        # registry resets here so wire_stats() describes THIS engine's
        # traced wires, not a previous configuration's.
        from deepspeed_tpu.comm.overlap_tiled import (
            check_comm_overlap,
            check_overlap_tiles,
        )
        from deepspeed_tpu.comm.quantized import reset_wire_stats

        reset_wire_stats()
        self._comm_overlap = check_comm_overlap(
            str(getattr(self.config, "comm_overlap", "none") or "none")
        )
        self._overlap_tiles = check_overlap_tiles(
            getattr(self.config, "tp_overlap_tiles", 4)
        )
        self._tp_tiled = self._comm_overlap == "tiled" and self._tp > 1
        # any explicit-wire mode routes the row projections through the
        # shard_map island in _tp_row_matmul instead of the implicit psum
        self._tp_wire = self._tp_quant or self._tp_tiled
        # --- KV payload dtype + decode-attention impl (ISSUE 6): int8 pools
        # store quantize_kv payloads + per-vector fp32 scale planes (half
        # the HBM per block → ~2x blocks per byte budget, kv_pool.py);
        # decode attention dispatches through paged_attention, with the
        # Pallas kernel resolved on TPU and the dense gather elsewhere.
        from deepspeed_tpu.inference.v2.kv_pool import _check_dtype

        self._kv_dtype = _check_dtype(
            str(getattr(kv, "kv_cache_dtype", "bf16") or "bf16")
        )
        self._kv_int8 = self._kv_dtype == "int8"
        impl = str(getattr(self.config, "paged_attention_impl", "auto") or "auto")
        if impl not in ("auto", "kernel", "dense"):
            raise ValueError(
                f"paged_attention_impl={impl!r}: expected 'auto', 'kernel' or "
                "'dense' (a typo must not silently fall back to the gather "
                "path — the seam that kept the kernel unreachable)"
            )
        from deepspeed_tpu.inference.v2.kv_pool import plane_widths, pool_geometry
        from deepspeed_tpu.ops.attention.paged_pallas import (
            blocks_a_program, kernels_take, keys_flat)

        # each pool's (kv_heads, key width, value width): the block pool is the
        # global layers', a mixed stack's window pool its window layers'
        self._geom = {}
        for pool in ("block", "window") if self._windowed else ("block",):
            heads, dim, planes = pool_geometry(c, pool)
            widths = plane_widths(dim, planes)
            self._geom[pool] = (heads, widths[0], widths[-1])
        # pool blocks a program of dstpu_paged_decode reads off the block pool
        # (_count_paged): the kernel's own rule on the bytes of a block of K
        # and V; 0 for a latent pool, which that kernel never walks
        nkv, dk, dv = self._geom["block"]
        self._blocks_a_program = 0 if c.latent else blocks_a_program(
            kv.block_size * nkv * (dk + dv) * (1 if self._kv_int8 else jnp.dtype(dtype).itemsize))
        if impl == "auto":
            # tp>1 stays dense: the Pallas kernel is opaque to GSPMD and
            # has no shard_map island; the gather shards on the kv-head dim.
            # A geometry the kernels do not read in place (kernels_take: the
            # chunk kernel's predicate too) stays dense as well, in every pool
            impl = "kernel" if (
                on_tpu() and self._tp == 1
                and (c.latent or all(kernels_take(*g) for g in self._geom.values()))
            ) else "dense"
        elif impl == "kernel" and self._tp > 1:
            raise NotImplementedError(
                "paged_attention_impl='kernel' with tp_size>1: the paged "
                "kernel has no shard_map island yet — use 'auto' or 'dense'"
            )
        self._attn_impl = impl
        # +1 trash block: padded tail tokens of bucketed chunks scatter there
        # instead of corrupting block 0 (which belongs to a live sequence)
        pool_dtype = jnp.int8 if self._kv_int8 else dtype
        nkv, dk, dv = self._geom["block"]
        shape = (c.kv_layers, kv.num_blocks + 1, kv.block_size, nkv, dk)
        sshape = shape[:-1]  # fp32 scale planes: one scalar per head vector
        self._ks_cache = self._vs_cache = None
        if (keys_flat(dk) or dv != dk) and (self._kv_int8 or self._tp > 1):
            raise NotImplementedError(
                f"v2 paged engine: keys of {dk} and values of {dv} a head (keys wider than the "
                "lanes that do not fill them are stored a token a row: paged_pallas.keys_flat) "
                "have no int8 or head-sharded pool yet")
        if self._latent:
            # ONE plane, a block latent_dim rows of its tokens (_k_cache names it)
            self._k_cache = jnp.zeros(shape[:2] + (c.latent_dim, kv.block_size), dtype)
            self._v_cache = None
        elif self._tp > 1:
            zeros = jax.jit(lambda: jnp.zeros(shape, pool_dtype), out_shardings=self._kv_sharding)
            self._k_cache = zeros()
            self._v_cache = zeros()
            if self._kv_int8:
                zeros_s = jax.jit(
                    lambda: jnp.zeros(sshape, jnp.float32),
                    out_shardings=self._kv_scale_sharding,
                )
                self._ks_cache = zeros_s()
                self._vs_cache = zeros_s()
        else:
            self._k_cache = jnp.zeros(self._k_shape(shape[:3], nkv, dk), pool_dtype)
            self._v_cache = jnp.zeros(shape[:3] + (nkv, dv), pool_dtype)
            if self._kv_int8:
                self._ks_cache = jnp.zeros(sshape, jnp.float32)
                self._vs_cache = jnp.zeros(sshape, jnp.float32)
        # the second kind of cache, flat over the recurrent layers so that the
        # decode kernel indexes [layer's ordinal * slots + slot] in place:
        # recurrent states float32 (as transformers keeps them) and the conv's
        # last K - 1 inputs in the compute dtype
        self._rec_state = self._rec_conv = None
        self._rec_impl = None  # the decode rule's pick by platform; tests name one
        self._cache_kinds = T.cache_kinds(c)
        self._ordinals = np.asarray(T.cache_ordinals(c), np.int32)
        # the window pool: the spare slot's ring is its trash
        self._wk_cache = self._wv_cache = None
        if self._windowed:
            # of its own geometry: the window layers' KV heads
            nkv, dk, dv = self._geom["window"]
            lead = (c.window_layers, self._state_slots * self._win_blocks, kv.block_size)
            self._wk_cache = jnp.zeros(self._k_shape(lead, nkv, dk), dtype)
            self._wv_cache = jnp.zeros(lead + (nkv, dv), dtype)
        if self._hybrid:
            n = c.kind_count(c.recurrent_kind) * self._state_slots
            self._rec_state = jnp.zeros((n,) + self._rec.state_shape(c), jnp.float32)
            # (a slot's K - 1 inputs flat in one row: a [.., 3, C] pool takes a
            # padded tiling and a layout copy a step on either side of the loop)
            self._rec_conv = jnp.zeros(
                (n, (self._rec.kernel(c) - 1) * self._rec.channels(c)), dtype)
        self._programs = {}  # (kind, shape) -> compiled step program (_launch)
        self._kv_scatter_jit = None  # handoff import: donated pool scatter
        # chunked re-import: ONE fixed window shape (tail padded into the
        # trash row) so the donated scatter never recompiles in steady state
        self._kv_readmit_jit = None
        # device-resident handoff export: fixed-window pool gather (the
        # zero-copy wire's dual of _kv_readmit_jit) — one trace per plane
        # family, never per block count
        self._kv_export_jit = None
        # --- host block tier (host_tier.py, ROADMAP item 3): LRU-evicted
        # prefix-trie blocks demote their KV to a byte-budgeted host store
        # instead of vanishing; a trie miss the store covers re-imports
        # through the donated scatter instead of re-prefilling. Outputs are
        # bit-identical tier on vs off.
        self._host_tier = None
        htb = int(getattr(kv, "host_tier_bytes", 0) or 0)
        if htb > 0:
            if self.state_manager.prefix_cache is None:
                raise ValueError(
                    "kv_cache.host_tier_bytes requires kv_cache.prefix_cache: "
                    "the host tier spills and readmits through the prefix trie"
                )
            from deepspeed_tpu.inference.v2.host_tier import HostBlockStore

            self._host_tier = HostBlockStore(
                htb, validate=self._check_tier_entry)
            self.state_manager.prefix_cache.spill_fn = self._spill_block
            self.state_manager.host_readmit = self._host_readmit
        self._spec_rr = 0  # rotation cursor for budget-capped spec rounds
        self.last_spec = {"drafted": 0, "accepted": 0, "per_uid": {}}
        self.last_step = StepStats()
        # an expert model's [.., L, E] routed rows of the step being launched:
        # _dispatch moves them into the step's StepInFlight
        self._moe_pending = None
        # the device's step timed where it is collected: the enqueue stamp of
        # the step being launched (_launch parks it for _dispatch, like the
        # routed rows), the step launched and not collected yet (what a launch
        # asks whether the chip ran dry), and when the last collected step was
        # seen ready (where the next one's time on the device starts)
        self._enqueued = None
        self._uncollected = None
        self._last_ready = 0.0
        # the last split step's sampled tokens by output slot, on the device:
        # the next one's ``last_tokens``
        self._last_tokens = jnp.zeros(
            self.config.state_manager.max_ragged_sequence_count
            + self.scheduler.max_prompt_chunks, jnp.int32)
        self.last_capped = set()
        # sampling state: one base key; programs fold in each row's (uid,
        # source position) so a token's key is content-addressed — invariant
        # to batch packing, prompt chunking and
        # prefix-cache hits (sampling.row_keys)
        self._rng = jax.random.key(int(getattr(self.config, "seed", 0) or 0))
        self.last_logprobs: Dict[int, np.ndarray] = {}
        log_dist(
            f"InferenceEngineV2: {kv.num_blocks} KV blocks × {kv.block_size} tokens, "
            f"budget {self.config.state_manager.max_ragged_batch_size} tok/step, "
            f"kv={self._kv_dtype}, attn={self._attn_impl}"
            + (f", tp={self._tp}" if self._tp > 1 else "")
            + (", comm_quant=int8" if self._tp_quant else "")
            + (f", comm_overlap=tiled({self._overlap_tiles})" if self._tp_tiled else "")
            + (", prefix_cache=on" if self.state_manager.prefix_cache is not None else "")
            + (f", host_tier={htb}B" if self._host_tier is not None else "")
            + (f", latent pool {c.latent_dim} a token a layer" if self._latent else "")
            + (f", state_slots={self._state_slots}" if self._hybrid else "")
            + (f", window_pool={c.window_layers} layers x {self._state_slots} rings of "
               f"{self._win_blocks} blocks" if self._windowed else ""),
            ranks=[0],
        )

    @staticmethod
    def _k_shape(lead, nkv: int, dk: int):
        """A K plane's shape behind its ``lead`` dims (layers, blocks, block
        size): a row a head, or where a head of more than 128 does not fill the
        lanes (192) a token's keys as one row (``paged_pallas.keys_flat``: the
        same bytes)."""
        from deepspeed_tpu.ops.attention.paged_pallas import keys_flat

        return tuple(lead) + ((nkv * dk,) if keys_flat(dk) else (nkv, dk))

    def _refuse_at_build(self, c, quantized: bool, tp: int) -> None:
        """A model with a second kind of cache (recurrent layers' states,
        DeltaNet's or Mamba's, or window layers' window pool): what cannot
        carry it yet raises here or is switched off with one log line, and
        never drops it. A cache hit, a spilled block, an exported block or a
        rejected draft names K/V blocks alone; the state a sequence's
        recurrent layers hold
        after the same tokens, or the window layers' keys and values of
        them, would be lost, stale or misread."""
        kv = self.config.kv_cache
        what = None
        if quantized or tp > 1:
            what = "quantized weights" if quantized else f"tp_size={tp}"
        elif str(getattr(kv, "kv_cache_dtype", "bf16") or "bf16") != "bf16":
            what = f"kv_cache_dtype={kv.kv_cache_dtype!r}"
        elif int(getattr(kv, "host_tier_bytes", 0) or 0) > 0:
            what = "a host block tier (host_tier_bytes)"
        elif int(getattr(self.config, "spec_k", 0) or 0) > 0:
            what = "speculative decoding (spec_k): a rejected draft would need the state rolled back"
        if what is not None:
            raise NotImplementedError(
                f"v2 paged engine: a model whose {self._beside} beside the K/V blocks, "
                f"with {what}, is not supported: that pool has no such form yet")
        if getattr(kv, "prefix_cache", False):
            log_dist(
                "InferenceEngineV2: prefix cache switched off: a hit shares K/V blocks, and this "
                f"model's {self._beside} beside them, which a hit would skip for those tokens",
                ranks=[0])
            self.config.kv_cache = dataclasses.replace(kv, prefix_cache=False)

    def _refuse_latent_at_build(self, quantized: bool, tp: int) -> None:
        """A latent-attention model caches ONE plane, ``latent_dim`` rows of a
        block's tokens a layer: what has no form for it yet raises here, with
        its reason, and never misreads the plane as K and V. (The prefix cache
        stays on: a hit shares blocks by table, whatever a block holds.)"""
        kv = self.config.kv_cache
        what = None
        if quantized or tp > 1:
            what = ("quantized weights" if quantized else f"tp_size={tp}") + (
                ": the latent projections have no int8 or model-sharded form")
        elif str(getattr(kv, "kv_cache_dtype", "bf16") or "bf16") != "bf16":
            what = (f"kv_cache_dtype={kv.kv_cache_dtype!r}: the int8 pool's scale planes are a "
                    "scalar a head vector, and a latent vector is no head's")
        elif int(getattr(kv, "host_tier_bytes", 0) or 0) > 0:
            what = ("a host block tier (host_tier_bytes): a spilled block is checked and "
                    "readmitted as K and V planes")
        elif int(getattr(self.config, "spec_k", 0) or 0) > 0:
            what = "speculative decoding (spec_k): the verify step has no absorbed form"
        if what is not None:
            raise NotImplementedError(
                "v2 paged engine: a latent-attention model (one latent vector a token in "
                f"place of per-head keys and values) with {what}")

    def _refuse_state_loss(self, what: str) -> None:
        """Raise where an operation moves or rolls back a sequence's cache by
        K/V blocks alone (handoff, recovery, host tier, speculative verify):
        with recurrent layers their states would be dropped, with a
        window pool the window layers' keys and values."""
        if self._beside:
            raise NotImplementedError(
                f"{what}: this model's {self._beside} "
                "beside the K/V blocks, and nothing moves or snapshots it yet")
        if self._latent:
            raise NotImplementedError(
                f"{what}: this model's pool is one latent plane [layers, blocks, latent_dim, "
                "block_size], and what moves blocks between pools carries K and V planes "
                "[.., kv_heads, head_dim] alone")

    @property
    def prefix_cache(self):
        """The pool's automatic prefix cache (None when kv_cache.prefix_cache
        is off). Cache-seeded sequences enter prefill with a pre-populated
        block table and a nonzero start offset; the split-phase step already
        serves that shape — every prompt chunk after the first is exactly a
        nonzero-start prefill against existing blocks, and the chunk
        program's pool gather (``pool_limit=chk_start``) reads the shared
        blocks' KV like any other context below the chunk."""
        return self.state_manager.prefix_cache

    @property
    def kv_cache_dtype(self) -> str:
        """Pool payload dtype knob value: "bf16" (compute dtype) or "int8"."""
        return self._kv_dtype

    @property
    def comm_quant(self) -> str:
        """Quantized-collectives knob value ("none" or "int8")."""
        return self._comm_quant

    @property
    def comm_overlap(self) -> str:
        """Tile-granular overlap knob value ("none" or "tiled")."""
        return self._comm_overlap

    def comm_wire_info(self) -> Dict:
        """Per-wire collective byte accounting for health()/metrics: the
        trace-time counters from comm.quantized (per compiled call site —
        a fori_loop layer body counts once for all its iterations; each
        entry carries its tile-granular overlap factor), plus whether the
        quantized / tiled TP paths are actually active."""
        from deepspeed_tpu.comm.quantized import wire_stats

        return {
            "comm_quant": self._comm_quant,
            "tp_quant_active": bool(self._tp_quant),
            "comm_overlap": self._comm_overlap,
            "tp_overlap_tiles": int(self._overlap_tiles),
            "tp_tiled_active": bool(self._tp_tiled),
            "wires": wire_stats(),
        }

    @property
    def paged_attention_impl(self) -> str:
        """The RESOLVED decode-attention impl ("kernel" or "dense")."""
        return self._attn_impl

    def kv_pool_info(self) -> Dict:
        """Byte-accounting snapshot for health()/metrics: pool bytes,
        bytes/block, dtype, capacity multiplier vs bf16 (kv_pool.describe),
        plus the resolved attention impl, and for a model with recurrent
        layers the state slots and their bytes."""
        from deepspeed_tpu.inference.v2.kv_pool import describe, pool_geometry

        c, kv = self._mc, self.config.kv_cache
        kv_heads, head_dim, planes = pool_geometry(c)
        info = describe(
            kv.num_blocks, kv.block_size, kv_heads, head_dim,
            c.kv_layers, self._kv_dtype, planes,
        )
        info["paged_attention_impl"] = self._attn_impl
        if self._windowed:
            # by kind: the block pool is the global layers'; the window layers'
            # rings, one a tracked sequence + a spare, are paid beside it, at
            # the window pool's own geometry
            from deepspeed_tpu.inference.v2.kv_pool import plane_widths, window_slot_bytes

            per_slot = window_slot_bytes(c, kv.block_size)
            w_heads, w_dim, w_planes = pool_geometry(c, "window")
            info.update(
                window_pool_geometry={"layers": c.window_layers, "kv_heads": w_heads,
                                      "plane_widths": list(plane_widths(w_dim, w_planes))},
                window_bytes_per_block=per_slot // self._win_blocks,
                window_slots=self._state_slots,
                window_slots_in_use=self.state_manager.state_slot_accounting()["live"],
                window_blocks_per_slot=self._win_blocks,
                window_bytes_per_slot=per_slot,
                window_pool_bytes=self._state_slots * per_slot,
            )
        if self._hybrid:
            # the second kind of cache: one slot a tracked sequence + a spare
            from deepspeed_tpu.inference.v2.kv_pool import state_slot_bytes

            per_slot = state_slot_bytes(c, self._rec_conv.dtype.itemsize)
            info.update(
                state_kind=c.recurrent_kind,
                state_slots=self._state_slots,
                state_slots_in_use=self.state_manager.state_slot_accounting()["live"],
                state_bytes_per_slot=per_slot,
                state_pool_bytes=self._state_slots * per_slot,
            )
        return info

    # -- cross-engine KV-block handoff (disaggregated prefill/decode) ------
    def export_kv_blocks(self, block_ids) -> Dict[str, np.ndarray]:
        """Gather the pool planes for ``block_ids`` to host numpy, keyed by
        plane name. The payload is the unit of prefill→decode handoff: it
        carries the quantized int8 codes + fp32 scale planes verbatim when
        the pool is int8, so a re-import is bitwise (no requantization)."""
        self._refuse_state_loss("export_kv_blocks")
        idx = jnp.asarray(np.asarray(list(block_ids), np.int32))
        out = {
            "k": np.asarray(self._k_cache[:, idx]),
            "v": np.asarray(self._v_cache[:, idx]),
        }
        if self._kv_int8:
            out["k_scale"] = np.asarray(self._ks_cache[:, idx])
            out["v_scale"] = np.asarray(self._vs_cache[:, idx])
        return out

    def _kv_pool_planes(self) -> Dict[str, "jnp.ndarray"]:
        if self._latent:
            return {"c": self._k_cache}
        planes = {"k": self._k_cache, "v": self._v_cache}
        if self._kv_int8:
            planes["k_scale"] = self._ks_cache
            planes["v_scale"] = self._vs_cache
        return planes

    def _kv_payload_spec(self) -> "KVPayloadSpec":
        """The strict per-plane contract every KV mover validates against:
        plane name -> ((n_layers, *per_block_tail), pool dtype)."""
        return {
            name: ((pool.shape[0],) + tuple(pool.shape[2:]),
                   np.dtype(pool.dtype))
            for name, pool in self._kv_pool_planes().items()
        }

    def _check_kv_payload(self, n: int, payload: Dict[str, np.ndarray],
                          context: str = "import_kv_blocks") -> None:
        """Validate a payload against the shared pool contract
        (kv_pool.check_kv_payload) before any scatter touches live KV —
        the same check the host-tier store and every handoff transport
        run, so the contracts cannot drift."""
        from deepspeed_tpu.inference.v2.kv_pool import check_kv_payload

        check_kv_payload(self._kv_payload_spec(), n, payload, context=context)

    def import_kv_blocks(self, block_ids, payload: Dict[str, np.ndarray]) -> None:
        """Scatter an exported payload into THIS pool at ``block_ids`` (the
        importer's freshly allocated table slots — ids need not match the
        exporter's). Donated functional update: the pool array is consumed
        and reassigned, same discipline as the step programs' KV carry, so
        callers must serialize this against stepping (router step_lock)."""
        self._refuse_state_loss("import_kv_blocks")
        n = len(block_ids)
        if n == 0:
            return
        self._check_kv_payload(n, payload)
        if self._kv_scatter_jit is None:
            self._kv_scatter_jit = jax.jit(
                lambda pool, idx, vals: pool.at[:, idx].set(vals),
                donate_argnums=(0,),
            )
        idx = jnp.asarray(np.asarray(list(block_ids), np.int32))
        scatter = self._kv_scatter_jit
        self._k_cache = scatter(
            self._k_cache, idx, jnp.asarray(payload["k"], self._k_cache.dtype))
        self._v_cache = scatter(
            self._v_cache, idx, jnp.asarray(payload["v"], self._v_cache.dtype))
        if self._kv_int8:
            self._ks_cache = scatter(
                self._ks_cache, idx, jnp.asarray(payload["k_scale"], jnp.float32))
            self._vs_cache = scatter(
                self._vs_cache, idx, jnp.asarray(payload["v_scale"], jnp.float32))

    def import_kv_blocks_chunked(self, block_ids, payload: Dict[str, np.ndarray],
                                 chunk_blocks: int = 0) -> None:
        """``import_kv_blocks`` in fixed-size double-buffered windows — the
        streamed-AdamW pattern (runtime/zero/streamed_adam.py) applied to
        the host→HBM re-import: window w+1's host→device transfer is
        issued (async ``device_put``) before window w's donated scatter is
        consumed, so the PCIe copy overlaps the scatter already in flight
        and the step loop never stalls on one bulk transfer.

        Every window has the SAME shape: the tail window's index vector is
        padded with the pool's trash row (``num_blocks``, the +1 row
        padded prefill tokens already scatter into) and its values
        zero-padded, so the donated scatter compiles exactly once per
        plane family — zero steady-state recompiles (Tier-B
        ``verify_host_tier`` pins this). Same locking contract as
        ``import_kv_blocks``."""
        self._refuse_state_loss("import_kv_blocks_chunked")
        n = len(block_ids)
        if n == 0:
            return
        self._check_kv_payload(n, payload)
        kv = self.config.kv_cache
        chunk = int(chunk_blocks) or int(
            getattr(kv, "host_tier_chunk_blocks", 8) or 8)
        if n <= chunk and chunk_blocks == 0:
            # small imports reuse the handoff scatter: no window win below
            # one chunk, and the shapes stay off the readmit jit's cache
            return self.import_kv_blocks(block_ids, payload)
        trash = kv.num_blocks  # the +1 trash row: pad writes land there
        n_win = -(-n // chunk)
        idx_host = np.full(n_win * chunk, trash, np.int32)
        idx_host[:n] = np.asarray(list(block_ids), np.int32)
        if self._kv_readmit_jit is None:
            self._kv_readmit_jit = jax.jit(
                lambda pool, idx, vals: pool.at[:, idx].set(vals),
                donate_argnums=(0,),
            )
        scatter = self._kv_readmit_jit
        names = sorted(payload)
        attrs = {"k": "_k_cache", "v": "_v_cache",
                 "k_scale": "_ks_cache", "v_scale": "_vs_cache"}

        def _stage(w: int):
            """Issue window w's host→device copies (async)."""
            lo, hi = w * chunk, (w + 1) * chunk
            idx = jnp.asarray(idx_host[lo:hi])
            vals = {}
            for name in names:
                v = payload[name][:, lo:min(hi, n)]
                if v.shape[1] < chunk:  # tail: zero-fill the trash columns
                    pad = [(0, 0)] * v.ndim
                    pad[1] = (0, chunk - v.shape[1])
                    v = np.pad(v, pad)
                vals[name] = jax.device_put(v)
            return idx, vals

        staged = _stage(0)
        for w in range(n_win):
            # double buffer: stage w+1's transfer BEFORE consuming w, so
            # the copy rides behind the in-flight donated scatter
            nxt = _stage(w + 1) if w + 1 < n_win else None
            idx, vals = staged
            for name in names:
                attr = attrs[name]
                setattr(self, attr, scatter(getattr(self, attr), idx, vals[name]))
            staged = nxt

    # -- device-resident handoff (zero-copy KV transport) ------------------
    def export_kv_blocks_device(self, block_ids) -> Dict[str, "jnp.ndarray"]:
        """``export_kv_blocks`` without the host round-trip: gather the
        pool planes for ``block_ids`` into fresh DEVICE arrays. The gather
        output owns its buffers, so the source sequence can release (and
        its pool rows be re-written by later donated steps) while the
        payload is still in flight to the importer. Shape varies with the
        block count — the fixed-window pipelined path below is the one
        steady-state handoffs ride."""
        self._refuse_state_loss("export_kv_blocks_device")
        idx = jnp.asarray(np.asarray(list(block_ids), np.int32))
        return {name: pool[:, idx]
                for name, pool in self._kv_pool_planes().items()}

    def export_kv_blocks_windows(self, block_ids, chunk_blocks: int = 0):
        """Chunked pipelined device-resident export: the dual of
        ``import_kv_blocks_chunked``. Returns ``(windows, chunk)`` where
        each window maps plane name -> a device array of exactly
        ``chunk`` block columns — the tail window's index vector is
        padded with the pool's trash row, so the jitted gather compiles
        once per plane family and never per block count (the warm-spare
        zero-trace contract). All window gathers are dispatched
        asynchronously up front: the importer can scatter (and the
        decode replica can start its first round on the trie-covered
        prefix) while the tail windows are still materializing."""
        self._refuse_state_loss("export_kv_blocks_windows")
        kv = self.config.kv_cache
        chunk = int(chunk_blocks) or int(
            getattr(kv, "host_tier_chunk_blocks", 8) or 8)
        n = len(block_ids)
        if n == 0:
            return [], chunk
        trash = kv.num_blocks
        n_win = -(-n // chunk)
        idx_host = np.full(n_win * chunk, trash, np.int32)
        idx_host[:n] = np.asarray(list(block_ids), np.int32)
        if self._kv_export_jit is None:
            self._kv_export_jit = jax.jit(lambda pool, idx: pool[:, idx])
        gather = self._kv_export_jit
        planes = self._kv_pool_planes()
        windows = []
        for w in range(n_win):
            idx = jnp.asarray(idx_host[w * chunk:(w + 1) * chunk])
            windows.append({name: gather(pool, idx)
                            for name, pool in planes.items()})
        return windows, chunk

    def import_kv_blocks_device(self, block_ids, windows,
                                chunk_blocks: int, skip_blocks: int = 0):
        """Scatter a windowed device-resident export into THIS pool at
        ``block_ids`` (the full per-sequence destination table, in source
        column order) without ever materializing a host copy. The first
        ``skip_blocks`` destinations (prefix already covered by this
        replica's trie/host tier) and the padded tail redirect to the
        trash row instead of slicing the device arrays — every window
        keeps the ONE compiled readmit-scatter shape. At tp>1 each
        window is re-laid-out onto this replica's mesh (head-sharded KV,
        scale planes riding along) by an async ``device_put`` before the
        donated scatter, which is the per-shard import the TP>1 decode
        placement rides. Same locking contract as ``import_kv_blocks``;
        returns the number of block columns actually scattered."""
        self._refuse_state_loss("import_kv_blocks_device")
        n = len(block_ids)
        chunk = int(chunk_blocks)
        if n == 0 or not windows:
            return 0
        if chunk <= 0:
            raise ValueError(
                f"import_kv_blocks_device: chunk_blocks={chunk_blocks} "
                "must be positive (the exporter's window size)")
        n_win = -(-n // chunk)
        if len(windows) != n_win:
            raise ValueError(
                f"import_kv_blocks_device: {len(windows)} windows != "
                f"{n_win} expected for {n} blocks at chunk {chunk}")
        spec = self._kv_payload_spec()
        for win in windows:
            self._check_kv_payload(chunk, win,
                                   context="import_kv_blocks_device")
        kv = self.config.kv_cache
        trash = kv.num_blocks
        idx_host = np.full(n_win * chunk, trash, np.int32)
        idx_host[:n] = np.asarray(list(block_ids), np.int32)
        idx_host[:max(0, int(skip_blocks))] = trash
        if self._kv_readmit_jit is None:
            self._kv_readmit_jit = jax.jit(
                lambda pool, idx, vals: pool.at[:, idx].set(vals),
                donate_argnums=(0,),
            )
        scatter = self._kv_readmit_jit
        attrs = {"k": "_k_cache", "v": "_v_cache",
                 "k_scale": "_ks_cache", "v_scale": "_vs_cache"}
        shardings = {}
        if self._tp > 1:
            shardings = {"k": self._kv_sharding, "v": self._kv_sharding,
                         "k_scale": self._kv_scale_sharding,
                         "v_scale": self._kv_scale_sharding}
        # windows fully below the covered prefix carry nothing to keep
        w0 = max(0, int(skip_blocks)) // chunk
        copied = 0
        for w in range(w0, n_win):
            idx = jnp.asarray(idx_host[w * chunk:(w + 1) * chunk])
            for name in sorted(spec):
                vals = windows[w][name]
                sh = shardings.get(name)
                if sh is not None:
                    vals = jax.device_put(vals, sh)
                attr = attrs[name]
                setattr(self, attr, scatter(getattr(self, attr), idx, vals))
            copied += int(np.sum(idx_host[w * chunk:(w + 1) * chunk] != trash))
        return copied

    # -- host block tier (HBM → host → peer, host_tier.py) -----------------
    @property
    def host_tier(self):
        """The host-memory block tier (None when kv_cache.host_tier_bytes
        is 0). Spill/readmit hooks are wired at construction; peers (the
        router's PrefixDirectory pull) inject entries directly."""
        return self._host_tier

    def _check_tier_entry(self, payload: Dict[str, np.ndarray]) -> None:
        """Host-tier entries are single-block columns of the export
        payload ([L, block_size, kv_heads(, head_dim)] per plane); restore
        the block axis and validate against the SAME shared pool contract
        the handoff import uses — one contract, not two drifting copies.
        Peer-pulled entries from the router's directory validate here too,
        so a malformed wire payload fails at injection, not readmit."""
        self._check_kv_payload(
            1, {name: p[:, None] for name, p in payload.items()},
            context="host_tier.put")

    def _spill_block(self, hkey: bytes, block: int) -> None:
        """Prefix-trie eviction hook: demote one idle cached block's KV to
        the host tier before its pool row returns to the free list. Runs
        under the engine's step serialization (eviction happens inside
        extend/insert); failures degrade to a re-prefill, never a stall."""
        store = self._host_tier
        if store is None:
            return
        tr = get_tracer()
        if tr.enabled:
            tr.instant("host_tier.spill",
                       track=getattr(self, "_trace_name", "engine"),
                       args={"block": int(block)})
        payload = self.export_kv_blocks([block])
        store.put(hkey, {name: plane[:, 0] for name, plane in payload.items()})

    def _host_readmit(self, seq, prompt_tokens, n_cached: int) -> int:
        """``seed_from_cache`` continuation: after the trie covered
        ``n_cached`` prompt tokens, cover the next contiguous run of FULL
        blocks from the host tier — allocate fresh pool blocks, re-import
        the stored payloads through the chunked donated scatter, and
        register the readmitted prefix back into the trie. Returns the new
        cached-token count; prefill then charges only the truly-cold tail
        (the scheduler's chunk budget never sees readmitted tokens)."""
        store = self._host_tier
        cache = self.state_manager.prefix_cache
        if store is None or cache is None or len(store) == 0:
            return n_cached
        from deepspeed_tpu.serving.resilience.faults import (
            InjectedFault, get_fault_injector)

        faults = get_fault_injector()
        if faults.enabled:
            try:
                faults.check("host_tier.readmit",
                             replica=getattr(self, "_trace_name", None))
            except InjectedFault:
                # a faulted readmit degrades to re-prefilling the tail —
                # bit-identical by construction (the tier is best-effort),
                # just slower; firing BEFORE extend() keeps the pool
                # untouched on the faulted path
                return n_cached
        from deepspeed_tpu.inference.v2.host_tier import chain_hashes

        toks = np.asarray(prompt_tokens).reshape(-1)
        bs = cache.block_size
        matchable = cache._matchable_blocks(len(toks))
        start = n_cached // bs
        if start >= matchable:
            return n_cached
        keys = chain_hashes(toks, bs, matchable)
        run = store.match(keys, start)
        if run == 0:
            return n_cached
        # fetch payloads BEFORE allocating: the extend() below may evict →
        # spill → LRU-drop matched store entries; holding the dicts keeps
        # the arrays alive regardless
        payloads = []
        for key in keys[start : start + run]:
            entry = store.get(key)
            if entry is None:  # pragma: no cover — single-threaded store
                break
            payloads.append(entry)
        run = len(payloads)
        if run == 0:
            return n_cached
        mgr = self.state_manager
        if not mgr.extend(seq, run * bs):
            return n_cached  # pool too tight even after eviction: re-prefill
        fresh = seq.block_table[start:]
        stacked = {
            name: np.stack([p[name] for p in payloads], axis=1)
            for name in payloads[0]
        }
        tr = get_tracer()
        with tr.span("host_tier.readmit",
                     track=getattr(self, "_trace_name", "engine"),
                     args={"blocks": run} if tr.enabled else None):
            self.import_kv_blocks_chunked(fresh, stacked)
        seq.seen_tokens = n_cached + run * bs
        store.note_readmits(run)
        # re-register the readmitted prefix: the trie takes its own
        # reference per block, so the KV outlives this sequence again
        cache.insert(toks[: seq.seen_tokens], seq.block_table)
        return seq.seen_tokens

    # -- warm spares (elastic serving) -------------------------------------
    def trace_signature(self) -> Dict[str, int]:
        """Snapshot of every step-program jit cache: key -> compiled-variant
        count. The warm-spare admission contract compares two snapshots —
        any growth is a compile the serving path paid at admission time."""
        def _n(fn) -> int:
            return int(fn._cache_size())

        sig: Dict[str, int] = {
            f"{kind}[{shape}]": _n(fn) for (kind, shape), fn in self._programs.items()
        }
        for name in ("_kv_scatter_jit", "_kv_readmit_jit", "_kv_export_jit"):
            fn = getattr(self, name, None)
            if fn is not None:
                sig[name] = _n(fn)
        return sig

    def warm_split_shapes(self, uid: int = (1 << 30) + 7, while_running=None) -> None:
        """Build every shape of the split step before a request is admitted,
        whatever clients send first. A shape is (chunk rows, bucket): for
        each count of rows the scheduler can cut, 1 .. ``max_prompt_chunks``,
        that many throwaway prompts submitted TOGETHER make one step of as
        many chunk rows, in each bucket that count has (_chunk_bucket): short
        ones trace the 128 bucket, and with the longest prompt one chunk can
        hold as the first of them the ``prompt_chunk`` bucket; the first short
        prompt's first decode step traces the decode-only shape (no chunk
        row). The serving entry points call this on every engine they build
        (``inference/cli.py``), so no prompt, alone or beside another,
        compiles anything in the middle of its TTFT. ``while_running(uid)``
        runs once, while the first sequence is running with a token fed back
        (warm_trace's verify step). The throwaway sequences are finished and
        scrubbed from the prefix trie afterwards, and sampling keys are
        content-addressed — warming never perturbs later streams. Call AFTER
        the final ``set_sampling`` (it invalidates the programs)."""
        sched = self.scheduler
        kv = self.config.kv_cache
        vocab = int(getattr(self._mc, "vocab_size", 0) or 2)
        cache = self.state_manager.prefix_cache
        spill = getattr(cache, "spill_fn", None) if cache is not None else None
        if cache is not None:
            cache.spill_fn = None  # warm KV must not demote into the tier
        pc = int(sched.prompt_chunk)
        # the longest chunk an admissible prompt can make: past 128 tokens
        # it lands in the prompt_chunk bucket
        longest = min(pc, int(self.config.state_manager.max_context) - 1,
                      min(kv.max_blocks_per_seq, kv.num_blocks) * kv.block_size)
        # a chunk row is a tracked sequence of its own
        most_rows = min(sched.max_prompt_chunks,
                        int(self.config.state_manager.max_tracked_sequences))

        def first_tokens(wuids, length, shape):
            # under the first-call span of the shape these steps build: _launch's
            # own (builder to enqueue) nests in it, the wait for the tokens is its
            got = {}
            with get_setup_record().span("program.first_call", key=f"split[{shape}]"):
                for _ in range(8 + length // max(1, pc) + len(wuids)):
                    got.update(self.step_tokens())
                    if all(w in got for w in wuids):
                        return got
            raise RuntimeError(
                f"warm_split_shapes: a prompt of {length} tokens never produced a token")

        try:
            for rows in range(1, most_rows + 1):
                # a prompt length for each bucket this count of rows has
                lens = {self._chunk_bucket(rows, n): n for n in (longest, 8)}
                for length in sorted(lens.values()):
                    wuids = [uid + k for k in range(rows)]
                    try:
                        for k, wuid in enumerate(wuids):
                            # the bucket is the longest row's; no shared first
                            # token: a prefix hit would shorten a chunk
                            toks = np.arange(length if k == 0 else 8, dtype=np.int32) + k
                            sched.submit(wuid, toks % max(1, vocab - 1) + 1)
                        got = first_tokens(wuids, length, (rows, self._chunk_bucket(rows, length)))
                        if rows == 1 and length == 8:
                            # the short prompt's first decode step has no chunk beside it
                            sched.feedback(uid, got[uid])
                            sched.feedback(uid, first_tokens([uid], 1, (0, 0))[uid])
                            if while_running is not None:
                                while_running(uid)
                    finally:
                        for wuid in wuids:
                            sched.finish(wuid)
                        if cache is not None:
                            # warm prefixes must never serve a hit, the next
                            # round's prompts neither
                            cache.clear()
        finally:
            if cache is not None:
                cache.spill_fn = spill

    def warm_trace(self, spec_k: int = 0, uid: int = (1 << 30) + 7) -> Dict[str, int]:
        """Pre-trace every step program the serving loop will drive, so a
        warm-spare engine admits requests with ZERO admission-time
        compiles: the split-phase step at every shape (decode-only, one
        chunk row in the 128 bucket, and each count of chunk rows in the
        ``prompt_chunk`` bucket: warm_split_shapes), the speculative verify
        step at ``spec_k``, and
        the fixed-window chunked re-import scatter (preemption resume /
        host-tier readmit). Returns the post-warm ``trace_signature`` (the
        baseline scale-up asserts against). Call BEFORE serving and AFTER
        the final ``set_sampling`` (sampling knobs shape the programs and
        invalidate these caches)."""

        def verify(wuid):
            if spec_k > 0:
                self.spec_round(int(spec_k), drafts={wuid: [1] * int(spec_k)})

        self.warm_split_shapes(uid, while_running=verify)
        # the fixed-window re-import scatter (resume/readmit path): one
        # chunk+1-block round trip traces the padded-tail window shape
        kv = self.config.kv_cache
        chunk = int(getattr(kv, "host_tier_chunk_blocks", 8) or 8)
        n = min(chunk + 1, int(kv.num_blocks))
        if n > chunk and not self._beside:  # no K/V mover serves a second kind of cache
            blocks = list(range(n))
            self.import_kv_blocks_chunked(
                blocks, self.export_kv_blocks(blocks), chunk_blocks=chunk
            )
            # ... and the device-resident wire (zero-copy handoff): the
            # windowed gather + the same readmit scatter fed device
            # windows, so a device-transport import on a warm spare traces
            # nothing at admission time either
            wins, ch = self.export_kv_blocks_windows(
                blocks, chunk_blocks=chunk)
            self.import_kv_blocks_device(blocks, wins, ch)
        return self.trace_signature()

    def set_sampling(self, greedy=None, temperature=None, top_k=None,
                     top_p=None, seed=None):
        """Update sampling knobs. greedy/top_k/top_p are compile-time
        (they shape the programs), so compiled steps are invalidated."""
        cfg = self.config
        if greedy is not None:
            cfg.greedy = bool(greedy)
        if temperature is not None:
            cfg.temperature = float(temperature)
        if top_k is not None:
            cfg.top_k = int(top_k)
        if top_p is not None:
            cfg.top_p = float(top_p)
        if seed is not None:
            self._rng = jax.random.key(int(seed))
        self._programs = {}

    def _sampling_kw(self):
        cfg = self.config
        return dict(
            greedy=bool(getattr(cfg, "greedy", True)),
            top_k=int(getattr(cfg, "top_k", 0) or 0),
            top_p=float(getattr(cfg, "top_p", 0.0) or 0.0),
        )

    @staticmethod
    def _match_specs(params, specs):
        """Align the spec tree to the (possibly quantized) param tree: leaves
        absent from the spec tree (quantized payload/scale leaves) replicate."""
        from jax.sharding import PartitionSpec as P

        def pick(path, leaf):
            node = specs
            try:
                for k in path:
                    node = node[k.key if hasattr(k, "key") else k.idx]
                return node if isinstance(node, P) else P()
            except (KeyError, TypeError, IndexError):
                return P()

        return jax.tree_util.tree_map_with_path(pick, params)

    # ------------------------------------------------------------------
    def _pool_views(self, k_cache, v_cache):
        """Flat multi-layer block-pool views [L*NBp, bs, nkv, d] of the
        5-D pools as the step received them — reshapes of contiguous
        leading dims (free), never a per-layer slice. The layer loops read
        these views and nothing else of the pools: they are loop
        invariants, and the one write of a step comes after the loop
        (_scatter_kv)."""
        return (k_cache.reshape((-1,) + k_cache.shape[2:]),
                v_cache.reshape((-1,) + v_cache.shape[2:]))

    def _cache_views(self, pools, second):
        """What a step's layers read of the caches, by name, for ``meta``:
        the flat views of the block pools (and scale planes) and, for a mixed
        stack, of the window pools ``second`` [Lw * NWp, bs, nkv, d]."""
        if self._latent:
            (c_pool,) = pools  # [L, NBp, D, bs] -> flat blocks, layer-offset tables
            return {"k_pool0": c_pool.reshape((-1,) + c_pool.shape[2:])}
        k_pool0, v_pool0 = self._pool_views(*pools[:2])
        ks_pool0, vs_pool0 = self._scale_views(*pools[2:])
        views = {"k_pool0": k_pool0, "v_pool0": v_pool0,
                 "ks_pool0": ks_pool0, "vs_pool0": vs_pool0}
        if self._windowed:
            wk_pool0, wv_pool0 = self._pool_views(*second)
            views.update(wk_pool0=wk_pool0, wv_pool0=wv_pool0)
        return views

    def _ring_tables(self, slots):
        """[R, B] tables of a window layer for rows at ``slots`` [R]: logical
        block ``j`` of a row is ring block ``slot * wb + j % wb``, whatever
        ``j``: the walk is bounded by the window, so it visits the blocks the
        ring still holds. Padding rows sit at the spare slot, the pool's trash."""
        B, wb = self.config.kv_cache.max_blocks_per_seq, self._win_blocks
        return slots[:, None] * wb + (jnp.arange(B, dtype=jnp.int32) % wb)[None]

    def _kv_source(self, meta, li, tables: str):
        """(K view, V view, layer-offset tables, layer-offset trash block) of
        what layer ``li``'s attention reads: the block pool under
        the rows' block tables ``meta[tables]``, or for a window layer the
        window pool under the rows' ring tables ``meta["win_" + tables]``."""
        kv = self.config.kv_cache
        o = self._ordinal(li)
        if self._windowed and self._cache_kinds[li] == "window":
            NWp = self._state_slots * self._win_blocks
            return (meta["wk_pool0"], meta["wv_pool0"], o * NWp + meta["win_" + tables],
                    o * NWp + NWp - self._win_blocks)
        NBp = kv.num_blocks + 1
        # (a latent pool is one plane: no v_pool0)
        return (meta["k_pool0"], meta.get("v_pool0"), o * NBp + meta[tables],
                o * NBp + kv.num_blocks)

    def _side_names(self, li):
        """(the names of layer ``li``'s side buffers in a step's carry, its
        index in them): "k" / "v" at the layer's ordinal among the layers of
        the block pool, a mixed stack's window layers "wk" / "wv" at theirs
        among the window pool's: each pool's buffers have its geometry."""
        if self._windowed and self._cache_kinds[li] == "window":
            return "wk", "wv", self._ordinal(li)
        return "k", "v", self._ordinal(li)

    def _scale_views(self, *scales):
        """Flat views [L*NBp, bs, nkv] of the int8 pools' fp32 scale planes
        (same layer-offset indexing as _pool_views); (None, None) for a
        bf16 pool, whose steps take no scale planes."""
        if not scales:
            return None, None
        ks_cache, vs_cache = scales
        return (ks_cache.reshape((-1,) + ks_cache.shape[2:]),
                vs_cache.reshape((-1,) + vs_cache.shape[2:]))

    def _pools(self):
        """The pools as the step programs take them and give them back: ONE
        argument, ``(k, v)``, a latent model's ``(c,)``, with an int8 pool
        ``(k, v, ks, vs)``, with recurrent layers ``(k, v, states, conv inputs)``, donated whole —
        whatever else a program takes, and whichever kinds of cache the model
        has, every leaf of it is updated in place."""
        second = ((self._rec_state, self._rec_conv) if self._hybrid else
                  (self._wk_cache, self._wv_cache) if self._windowed else ())
        return tuple(self._kv_pool_planes().values()) + second

    def _split_pools(self, pools):
        """(the K/V planes, the second kind of cache or ()) of a program's
        ``pools``: a recurrent kind's state pools, which ride the layer
        loop's carry, or a mixed stack's window pools (k, v), which are its
        invariants like the block pools."""
        n = len(pools) - (2 if self._beside else 0)
        return pools[:n], pools[n:]

    def _ordinal(self, li):
        """Layer ``li``'s ordinal among the layers of its kind (its index in
        its kind's parameter stack and cache pool): ``li`` itself where every
        layer is alike, else read off a constant table, traced or not."""
        if not self._beside:
            return li
        return int(self._ordinals[li]) if isinstance(li, int) else jnp.asarray(self._ordinals)[li]

    def _embed(self, params, tokens, positions):
        """The one prologue of a step program: ``tokens`` [t] at
        ``positions`` [t] -> x [1, t, h] (scaled embedding, learned
        positions, embedding norm)."""
        c = self._mc
        dtype = T.DTYPES[c.dtype]
        x = T._scale_embed(params["embed"].astype(dtype)[tokens][None], c, dtype)
        if c.position == "learned":
            x = x + params["pos_embed"][jnp.clip(positions, 0, c.max_seq_len - 1)][None]
        if c.embed_norm:
            x = T._embed_norm(params, c, x, stream=False)
        return x

    def _sample_rows(self, params, x, rows, rng, temperature, uids, src_pos,
                     return_logprobs=False):
        """The one epilogue of a step program: final norm over the step's
        hidden states x [1, t, h], ``rows`` of them (a slice or an index
        array) through the LM head, and one next token a row computed
        IN-program (sampled or greedy per the static config knobs). Keys are
        per-row, content-addressed on (uid, logits-source position)
        (sampling.row_keys): the token for a given position is the same
        whether the split step or a verify step produced it, whatever the batch packing, prompt
        chunking and prefix-cache state. Returns (fp32 logits [n, vocab],
        what ``sample_tokens`` returns: tokens, or (tokens, logprobs))."""
        from deepspeed_tpu.inference.sampling import row_keys, sample_tokens

        c = self._mc
        x = T._norm(x, params["final_norm"], params.get("final_norm_b"), c.norm, c.norm_eps)
        logits = T._apply_lm_head(params, x[0, rows], c).astype(jnp.float32)
        return logits, sample_tokens(
            logits, row_keys(rng, uids, src_pos), temperature=temperature,
            return_logprobs=return_logprobs, **self._sampling_kw(),
        )

    def _attn_decode(self, q, k_pool, v_pool, tables_l, positions, window,
                     trash_l, extra_kv=None, pool_limit=None, k_scale=None,
                     v_scale=None, sinks=None):
        """Decode attention: one token per row, per-ROW layer-offset tables
        [R, B] into the flat pools, dispatched through ``paged_attention``
        with the impl resolved at engine init — on TPU the Pallas kernel,
        whose grid is the blocks the rows' contexts cover, as many to a
        program as make a megabyte (``paged_pallas.blocks_a_program``), a
        row's last program also its finish (it folds this step's K/V and
        writes the row; a row that holds no block is one program), read off
        ``positions`` / ``pool_limit`` / ``window`` (a table slot a row does
        not hold costs nothing; a program's blocks are folded in one product
        batched over the KV heads, in the pool's dtype; int8 pools dequantize in-VMEM behind
        the halved HBM reads), elsewhere the dense XLA
        gather+einsum over whole tables (``impl="dense"``: GSPMD shards it
        on the kv-head dim without a shard_map island; CPU and tp shapes).
        ``extra_kv``/``pool_limit``: this step's K/V ride alongside and
        the pool is read below the step's first position only, so no read
        needs this step's writes from the pool (they wait for _scatter_kv).
        ``k_scale``/``v_scale``: flat int8 dequant planes
        (_scale_views). ``sinks`` [nh]: a window layer's learned logits in
        the softmax's denominator, None for a layer that has none."""
        from deepspeed_tpu.ops.attention.paged_pallas import paged_attention

        c = self._mc
        return paged_attention(
            q, k_pool, v_pool, tables_l, positions, trash_l,
            impl=self._attn_impl,
            window=int(window), scale=c.attn_scale,
            k_scale=k_scale, v_scale=v_scale,
            extra_kv=extra_kv, pool_limit=pool_limit, sinks=sinks,
        )

    def _count_paged(self, pool_tokens, calls: int = 1):
        """What one layer's decode attention had to read against what it was
        handed, as ``StepStats``' paged_live_blocks, paged_table_slots and
        paged_programs: ``pool_tokens`` [R] are the tokens each row's pool
        window holds (<= 0: an inactive slot), a row's live blocks are
        ``ceil(tokens / bs)``, its table has B slots, and the programs of
        ``dstpu_paged_decode`` that read its blocks are ``ceil(blocks / G)``,
        ``G`` the kernel's ``blocks_a_program`` of the block pool (a row that
        holds nothing is a program that reads nothing, and is not counted:
        live blocks over programs is 1 where ``G`` is, and ``G`` where every
        group is full); ``calls`` kernel calls a layer walk the same rows (a
        verify step's K1 queries a row)."""
        kv = self.config.kv_cache
        held = np.maximum(np.asarray(pool_tokens, np.int64), 0)
        blocks = -(-held // kv.block_size)
        G = self._blocks_a_program
        return {"paged_live_blocks": calls * int(blocks.sum()),
                "paged_table_slots": calls * len(held) * kv.max_blocks_per_seq,
                "paged_programs": calls * int((-(-blocks // G)).sum()) if G else 0}

    def _count_cache(self, dec_pos, new_tokens: int):
        """The cache as the step finds it, as ``StepStats``' kv_global_blocks
        (blocks of the block pool held), kv_window_blocks (ring blocks held: a
        tracked sequence's ``wb``), kv_context_tokens (tokens the tracked
        sequences hold, this step's ``new_tokens`` included) and paged_window_live_blocks:
        the blocks ONE window layer's decode walks visit, for a row at
        position p those of keys p - window + 1 .. p - 1 (``dec_pos`` [R], < 0
        an inactive slot). Host arithmetic over the
        tracked sequences, inside ``engine.stage``."""
        sm, kv = self.state_manager, self.config.kv_cache
        bs = kv.block_size
        out = {"kv_global_blocks": kv.num_blocks - sm.free_blocks,
               "kv_context_tokens": sm.context_tokens + int(new_tokens)}
        if self._windowed:
            p = np.asarray(dec_pos, np.int64)
            lo = np.maximum(p - self._mc.sliding_window + 1, 0) // bs
            walks = np.where(p > 0, (p - 1) // bs - lo + 1, 0)
            out.update(kv_window_blocks=sm.state_slots_in_use * self._win_blocks,
                       paged_window_live_blocks=int(walks.sum()))
        return out

    def _chunk_bucket(self, rows: int, longest: int) -> int:
        """The slots a chunk row has on the grid of a step with ``rows`` chunk
        rows, the longest of ``longest`` tokens. Two buckets keep a short
        prompt or a short tail off the full ``prompt_chunk`` pad without a
        program per ragged length: 128 for ONE row of at most 128 tokens,
        ``prompt_chunk`` for everything else. Rows that arrive together share
        the one bucket: a shape is a program, traced and lowered before a
        request is admitted at ~1.5 s of set-up each on the serving host
        (PERF.md, PR 38), and two short chunks in one step are rare."""
        pc = self.scheduler.prompt_chunk
        return min(128, pc) if rows == 1 and longest <= 128 else pc

    def _count_chunk(self, chk_rows, tq: int):
        """What one layer's chunk attention had to read against what the
        dense form walks, as ``StepStats``' chunk_live_blocks and
        chunk_table_slots: a chunk row (uid, tokens, start, chunked) holds
        ``ceil(start / bs)`` pool blocks below it and ``ceil(n / bs)`` key
        blocks of its own; the grid's rows, one a chunk row of the batch, have
        ``B`` table slots and ``tq / bs`` chunk blocks each. Zeros for a step
        with no chunk."""
        kv = self.config.kv_cache
        bs = kv.block_size
        live = sum(-(-start // bs) + -(-len(toks) // bs) for _, toks, start, _ in chk_rows)
        slots = len(chk_rows) * (kv.max_blocks_per_seq + -(-tq // bs))
        return {"chunk_live_blocks": live, "chunk_table_slots": slots}

    def _side_buffers(self, n_tokens: int):
        """What a step's layer loop carries, by name: ``k`` / ``v``, a zeroed
        pair [L, n_tokens, nkv, d] in compute dtype in place of the K/V
        pools (L the block pool's layers), and for a mixed stack ``wk`` / ``wv``
        for its window pool's layers, each pair at ITS pool's geometry (KV
        heads; a value as wide as the V plane). Each such layer records its new
        K/V at its ordinal (_record_kv) and _scatter_kv writes all of them
        back after the loop. Sized by the tokens of one step, not by the
        pool; at tp>1 sharded on the kv-head dim like the pool, so the
        write-back moves nothing across devices. ``moe``: an expert model's
        routed rows. A DeltaNet model's state pools ride the same carry
        (``_with_state``): they are never a loop's invariant, every read and
        the in-place update of a layer go through the carried value."""
        c = self._mc
        if self._latent:
            # one vector a token: [L, n_tokens, latent_dim] under "k" alone
            carry = {"k": jnp.zeros((c.kv_layers, n_tokens, c.latent_dim), T.DTYPES[c.dtype])}
            if c.n_experts > 0:
                carry["moe"] = jnp.zeros((c.n_layers, self._moe_width), jnp.int32)
            return carry
        dt = T.DTYPES[c.dtype]
        nkv, dk, dv = self._geom["block"]
        shape = (c.kv_layers, n_tokens, nkv, dk)
        side = jnp.zeros(shape, dt)
        if self._mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from deepspeed_tpu.parallel.topology import MODEL_AXIS

            spec = P(None, None, MODEL_AXIS, None)
            side = jax.lax.with_sharding_constraint(side, NamedSharding(self._mesh, spec))
        carry = {"k": side, "v": side if dv == dk else jnp.zeros(shape[:-1] + (dv,), dt)}
        if self._windowed:
            # the window pool's layers, at its geometry
            nkv, dk, dv = self._geom["window"]
            lead = (c.window_layers, n_tokens)
            carry.update(wk=jnp.zeros(lead + (nkv, dk), dt), wv=jnp.zeros(lead + (nkv, dv), dt))
        if c.n_experts > 0:
            # an expert model's loop also carries what each layer routed:
            # [L, E] rows an expert, returned with the step's tokens
            carry["moe"] = jnp.zeros((c.n_layers, self._moe_width), jnp.int32)
        return carry

    @property
    def _moe_width(self) -> int:
        """Entries of one layer's routed-rows record: a row count an expert
        held and, for a grouped router, the tokens whose kept groups include
        a held one, for one with identity experts the pairs that chose one
        (grouped.experts_grouped)."""
        c = self._mc
        return c.n_experts + (1 if c.moe_n_group > 1 or c.moe_zero_experts else 0)

    def _with_state(self, carry, second):
        """The carry with a recurrent kind's (states, conv inputs) in it (a
        window pool is no part of a carry: the layers read it as it was)."""
        if self._hybrid:
            carry = dict(carry, rec_state=second[0], rec_conv=second[1])
        return carry

    @staticmethod
    def _state_of(carry):
        """The state pools a layer loop gives back: the tail of ``_pools()``."""
        return (carry["rec_state"], carry["rec_conv"]) if "rec_state" in carry else ()

    @staticmethod
    def _record_moe(carry, li, moe_counts):
        """Layer ``li``'s rows routed to each expert into the carry's ``moe``
        (expert models; a dense layer's None leaves the carry as it is)."""
        if moe_counts is None:
            return carry
        return dict(carry, moe=jax.lax.dynamic_update_index_in_dim(carry["moe"], moe_counts, li, 0))

    @staticmethod
    def _moe_rows(carry):
        """An expert model's routed rows as a step program returns them;
        None for a dense model."""
        return carry.get("moe")

    def _record_kv(self, carry, li, k, v, moe_counts=None):
        """Layer ``li``'s new K/V [..., nkv, d] into its pool's side buffers at
        the layer's ordinal in that pool (_side_names), and what it routed
        beside them (_record_moe)."""
        kn, vn, fi = self._side_names(li)
        carry = dict(carry, **{
            kn: jax.lax.dynamic_update_index_in_dim(carry[kn], k, fi, 0),
            vn: jax.lax.dynamic_update_index_in_dim(carry[vn], v, fi, 0),
        })
        return self._record_moe(carry, li, moe_counts)

    def _scatter_kv(self, caches, blk, row, side):
        """THE pool write of a serving step: after the layer loop, ONE
        single-dimension scatter a pool on its flat slot view
        [L*NBp*bs, nkv, d] puts every layer's new K/V in place, at slot
        ``(li*NBp + blk)*bs + row``. ``caches`` are the step's donated pool
        arguments (k, v[, ks, vs]), untouched until here: the layer loop
        reads them as invariants and carries the side buffers
        (_side_buffers), so XLA updates the donated buffers in place and
        copies no pool. It must stay outside every loop: a scatter inside
        one makes the pool both the invariant the loop reads and the carry
        it mutates, which XLA resolves with two whole-pool copies a pool a
        step (PERF.md, PR 24; analysis/verify.check_pool_copies guards it).

        ``blk``/``row``: [n] block and row of each of the step's n token
        slots, the same for every layer (padded slots name the trash
        block); ``side``: the new (k, v), each [L, n..., nkv, d], L the pools'
        layers (_write_back hands each kind of pool its layers). int8 pools quantize
        here (block_quant.quantize_kv, per head vector: the granularity
        that needs no read-modify-write of neighbor slots) and the fp32
        scales scatter through the same slot ids. Returns the pools in the
        order given."""
        L, NBp, bs = caches[0].shape[:3]
        n = blk.shape[0]
        li = jnp.arange(L, dtype=jnp.int32)[:, None]
        slot = ((li * NBp + blk[None]) * bs + row[None]).reshape(L * n)
        # a token's entry as its plane stores it (keys a token a row: _k_shape)
        new = [a.reshape((L * n,) + pool.shape[3:]) for a, pool in zip(side, caches)]
        if len(caches) == 4:
            from deepspeed_tpu.ops.quantizer.block_quant import quantize_kv

            (k, sk), (v, sv) = (quantize_kv(a) for a in new)
            new = [k, v, sk, sv]
        return tuple(
            pool.reshape((L * NBp * bs,) + pool.shape[3:]).at[slot].set(a).reshape(pool.shape)
            for pool, a in zip(caches, new)
        )

    def _write_back(self, pools, second, blk, row, side, wblk, x, visits=None):
        """Every pool of a step's ``pools`` argument as the program returns
        it: the block pools written from the side buffers (_scatter_kv), then
        a mixed stack's window pools from their layers' part of the same
        buffers at the tokens' ring blocks ``wblk`` [n] (a token that no later
        query can see names the spare ring: no ring slot is written twice;
        ``wk`` / ``wv``: _side_names),
        or a DeltaNet model's state pools as the carry holds them; a latent
        model's one plane through ``latent_write`` (``visits``: its programs,
        staged on the host). ``x``: the
        stream the stack left, which orders a window pool's and a latent
        plane's write behind an UNROLLED stack's last layer and changes nothing
        of it."""
        if self._latent:
            from deepspeed_tpu.ops.attention.latent_pallas import latent_write

            # the one plane, in place: merged by kernel on the chip under the
            # host-staged ``visits`` (XLA's scatter of a column copies the pool
            # twice), scattered elsewhere. An unrolled stack (the dense lead
            # layer) hands on its last layer's vectors before that layer has
            # read the pool: the write waits for the stream as below
            p = x[0, 0, 0]
            never = (p != p) & (p == p)
            blk = jnp.where(never, self.config.kv_cache.num_blocks, blk)
            visits = visits and (visits[0], visits[1], jnp.where(never, 0, visits[2]))
            return (latent_write(pools[0], side["k"], blk, row, visits, impl=self._attn_impl),
                    ) + self._state_of(side)
        if not self._windowed:
            return self._scatter_kv(pools, blk, row, (side["k"], side["v"])) + self._state_of(side)
        # A loop ends before the pool write that follows it. An unrolled
        # stack's last layer hands on its new K/V before its attention has
        # read the pool, so the write may come first, and XLA keeps the
        # two apart by copying every pool that layer reads, twice a step
        # (check_pool_copies; an optimization_barrier does not survive to
        # where that is decided, and a loop of one pass hoists every
        # layer's weight slices out as copies). So the write's indices are
        # made to wait for the stream the last layer left, through a
        # predicate that is False whatever the stream holds (a NaN is
        # unequal to itself, and then not equal either): every token's
        # K/V lands where it belongs, and the compiler cannot know it.
        p = x[0, 0, 0]
        never = (p != p) & (p == p)
        blk = jnp.where(never, self.config.kv_cache.num_blocks, blk)
        wblk = jnp.where(never, (self._state_slots - 1) * self._win_blocks, wblk)
        return (self._scatter_kv(pools, blk, row, (side["k"], side["v"]))
                + self._scatter_kv(second, wblk, row, (side["wk"], side["wv"])))

    def _layer_windows(self):
        """Static per-layer window values: an int (uniform — one loop body
        serves every layer) or a list (alternating local/global stacks,
        unrolled). All-equal patterns (gpt_neo all-local stacks) collapse to
        the uniform int — unrolling them only multiplied compile time
        (round-4 advisor finding)."""
        c = self._mc
        vals = [int(c.sliding_window or 0) if f else 0
                for f in c.attn_layer_pattern or (1,) * c.n_layers]
        # (lead layers of another shape are unrolled too)
        if len(set(vals)) == 1 and not c.moe_dense_lead:
            return vals[0]
        return vals

    def _drive_layers(self, layer_fn, params, x, carry):
        """Run ``layer_fn(lp, x, li, carry, window=...) -> (x, carry)`` over
        the stack, ``li`` the layer's index in it. Three shapes of one loop:
        uniform layers run a lax.fori_loop with a traced layer index;
        per-layer windows (true alternating patterns) an unrolled Python loop
        with static indices; layers of two KINDS (``layer_kinds``) a
        fori_loop over the PERIODS of the pattern with one period's layers
        unrolled in its body, so there is one traced body a kind whatever the
        depth (a stack of a single period is that body alone; a stack of two
        kinds behind a dense LEAD layer, whose MLP is of another shape, is
        unrolled whole like the per-layer windows). A layer's
        parameters are what every layer has at ``li`` and its kind's stack
        at the layer's ordinal among its kind (``T.kind_ordinals``); the
        body knows its kind from the keys it is handed. Two sets of keys
        STAY IN THEIR STACK, because a slice in front of a product is a copy a
        step to XLA on the TPU: an expert model's per-expert weights in every
        shape of the loop (the grouped kernel indexes its layer's blocks),
        and, in the unrolled shape, the attention projections ``wq`` / ``wk``
        / ``wv`` / ``wo`` of the common stack and of a kind's own alike
        (``_static_layer``: ``Stacked``, read in place by ``stack_dot``). The
        two looped shapes index their stacks with a traced layer, which
        ``stack_matmul`` does not take. ``carry`` holds the
        step's side buffers, an expert model's routed rows and a DeltaNet
        model's state pools, and never a K/V pool: those are invariants of
        every loop."""
        c = self._mc
        windows = self._layer_windows()
        L = c.n_layers
        # an expert model's per-expert weights stay whole: the grouped kernel
        # indexes its layer's blocks itself (_mlp_tail passes ``li`` on); a
        # slice in front of a custom call would be copied, every layer
        whole, whole_keys = {}, ()
        if c.n_experts > 0:
            from deepspeed_tpu.parallel.moe.sharded_moe import EXPERT_STACKS

            whole_keys = EXPERT_STACKS
            whole = {k: v for k, v in params["layers"].items() if k in whole_keys}
        sliced = {k: v for k, v in params["layers"].items() if k not in whole}
        # a layer of two sub-blocks (moe_shortcut): their stacks, [2 L, ...],
        # are indexed by sub-block, 2 li + i, as the cache's planes are
        subs = sliced.pop("sub", None)

        def traced(a, i):
            return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)

        if self._hybrid and not c.moe_dense_lead:
            period, n = T.layer_period(c)
            P = len(period)
            ords = T.kind_ordinals(c)[:P]
            common = {k: v for k, v in sliced.items() if not isinstance(v, dict)}

            # a period's layers in RUNS of one kind. A short run is unrolled
            # (Qwen3-Next's three DeltaNet layers and one attention layer, as
            # ever); a run of RUN_LOOP layers or more (Jamba's seven and six
            # Mamba layers either side of its attention layer) is a loop with
            # ONE traced body: fourteen unrolled layers a program cost 147 s
            # of tracing in four programs (my chip run, PR 53)
            runs, j = [], 0
            while j < P:
                k = j
                while k < P and period[k] == period[j]:
                    k += 1
                runs.append((j, k))
                j = k

            def run_period(pi, x, carry, take):
                def layer(j, j0, x, carry, take):
                    # layer j of the period, in the run that starts at j0
                    kind = period[j0]
                    li, ki = pi * P + j, pi * period.count(kind) + ords[j0] + (j - j0)
                    lp = {**jax.tree.map(lambda a: take(a, li), common),
                          **jax.tree.map(lambda a: take(a, ki), sliced[kind]), **whole}
                    return layer_fn(lp, x, li, carry, window=windows)

                for j0, j1 in runs:
                    if j1 - j0 >= RUN_LOOP:
                        x, carry = jax.lax.fori_loop(
                            j0, j1, lambda j, st, j0=j0: layer(j, j0, *st, traced), (x, carry))
                    else:
                        for j in range(j0, j1):
                            x, carry = layer(j, j0, x, carry, take)
                return x, carry

            if n == 1:
                return run_period(0, x, carry, lambda a, i: a[i])
            return jax.lax.fori_loop(
                0, n, lambda pi, st: run_period(pi, *st, traced), (x, carry))

        def at_layer(stacks, i):
            # a looped latent layer's ``wkv_b`` goes on unsliced, as _static_layer
            # hands an unrolled one's: the chunk kernel indexes the layer itself
            lp = jax.tree.map(lambda a: traced(a, i), stacks)
            if "wkv_b" in stacks and self._mesh is None:
                lp["wkv_b"] = Stacked(stacks["wkv_b"], i)
            return lp

        if not isinstance(windows, list):
            def body(li, st):
                x, carry = st
                lp = {**at_layer(sliced, li), **whole}
                if subs is not None:
                    lp["sub"] = tuple(at_layer(subs, 2 * li + i) for i in range(2))
                return layer_fn(lp, x, li, carry, window=windows)

            x, carry = jax.lax.fori_loop(0, L, body, (x, carry))
            return x, carry
        # unrolled: a layer's own sub-stacks (a dense lead layer's MLP or an
        # expert layer's block, its kind's attention: T.layer_stacks) beside
        # what every layer has; an expert layer reads the whole expert stacks
        # at its index in them
        common = {k: v for k, v in sliced.items() if not isinstance(v, dict)}
        for li, w in enumerate(windows):
            lp = {**self._static_layer(common, li), **whole}
            for name, ki in T.layer_stacks(c, li):
                own = dict(params["layers"][name])
                # (_mlp_tail knows an expert layer's index in the stacks from li)
                lp.update({k: own.pop(k) for k in whole_keys if name == "sparse" and k in own})
                lp.update(self._static_layer(own, ki))
            x, carry = layer_fn(lp, x, li, carry, window=w)
        return x, carry

    def _static_layer(self, stacks, i):
        """Layer ``i`` (static) of a dict of parameters stacked ``[layers,
        ...]``, for an unrolled stack. The attention projections ``wq`` /
        ``wk`` / ``wv`` / ``wo`` are NOT sliced: they go on as ``Stacked(stack,
        i)`` and ``stack_dot`` multiplies with them in place. What decides is
        what the code can see: a plain array (a quantized leaf widens a layer
        at a time, from its slice), more than one layer in the stack (``a[0]``
        of a stack of one is a bitcast), one device (``_tp_row_matmul`` and
        GSPMD take arrays), and a layer whose products are ``stack_dot``'s
        (``_layer_qkv`` / ``_layer_tail`` / ``T.kind_qkv``, a KDA layer's two wide
        projections through ``T._proj``; ``_latent_layer`` multiplies its ``wo``
        itself, and its ``wq`` stays sliced beside it). A latent layer's
        ``wkv_b`` stays in its stack too: the expanded chunk kernel indexes the
        layer's columns out of it (``latent_pallas.latent_chunk``), and
        ``T.latent_up`` slices it for the decode rows as it always did."""
        in_place = self._mesh is None
        stay = _READ_IN_PLACE
        if self._latent:
            stay = tuple(k for k in stay if k not in ("wq", "wo")) + ("wkv_b",)

        def take(k, a):
            if in_place and k in stay and isinstance(a, jax.Array) and a.shape[0] > 1:
                return Stacked(a, i)
            return jax.tree.map(lambda b: b[i], a)

        return {k: take(k, a) for k, a in stacks.items()}

    def _layer_qkv(self, lp, x, positions, live, window=None):
        """Shared per-layer prologue for the serving step bodies: pre-norm →
        QKV projections (+ biases) → qk-norm → rope. One definition so the
        split step and the verify step cannot drift on arch features
        (qk_layernorm, biases, rope scaling). lp must be pre-dequantized.
        ``window``: the layer's (static) window, which says whether a
        ``rope_window_only`` model's layer rotates at all. Returns (a, q, k,
        v): the normed activations (the stream itself where the block's
        output is normed instead) and [t, nh|nkv, d] heads."""
        c = self._mc
        nh, nkv, d = c.n_heads, c.kv_heads, c.head_dim
        t = x.shape[1]
        a = x if c.norm_scheme == "out" else T._norm(
            x, lp["attn_norm"], lp.get("attn_norm_b"), c.norm, c.norm_eps)
        if c.attn_by_kind:
            # the layer's own KV heads, value width, rotary base, value scale
            return (a,) + T.kind_qkv(c, lp, a[0], positions, "window" if window else "full", live)
        q, k, v = (stack_dot(a[0], lp[n]) for n in ("wq", "wk", "wv"))
        if c.attn_qkv_bias:
            q, k, v = q + lp["wq_b"], k + lp["wk_b"], v + lp["wv_b"]
        if T.qk_norm_full(c):
            q = T.qk_norm_apply(c, q, lp["q_norm"], head_axis=-1)
            k = T.qk_norm_apply(c, k, lp["k_norm"], head_axis=-1)
        q, k = T.as_written((q, k))
        q = q.reshape(t, nh, d)
        k = k.reshape(t, nkv, d)
        v = v.reshape(t, nkv, d)
        if c.qk_norm and not T.qk_norm_full(c):
            q = T.qk_norm_apply(c, q, lp["q_norm"], head_axis=1, b=lp.get("q_norm_b"))
            k = T.qk_norm_apply(c, k, lp["k_norm"], head_axis=1, b=lp.get("k_norm_b"))
        if c.position == "rope" and not (c.rope_window_only and not window):
            q = T._rope(q.transpose(1, 0, 2)[None], positions[None], c, live)[0].transpose(1, 0, 2)
            k = T._rope(k.transpose(1, 0, 2)[None], positions[None], c, live)[0].transpose(1, 0, 2)
        return a, q, k, v

    def _tp_row_matmul(self, x2d, w, tag):
        """``x2d @ w`` with the contraction dim sharded over MODEL_AXIS and
        the reduction wire rewritten inside a shard_map island (GSPMD cannot
        rewrite its own implicit psum). comm_overlap="tiled" decomposes the
        wire into tp_overlap_tiles independent per-tile reduce-scatter→
        all-gather ppermute rings (comm/overlap_tiled.tiled_tp_matmul; the
        comm_quant="int8" payload+scale planes ride the same tiles);
        otherwise the monolithic ``quantized_psum_tp`` int8 two-hop.
        x2d: [t, K] activations (K = heads*d or ffn dim, column-sharded by
        GSPMD from the param shardings); w: [K, h] row-sharded. Returns
        [t, h] replicated over the model axis."""
        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu.comm.quantized import quantized_psum_tp
        from deepspeed_tpu.parallel.topology import MODEL_AXIS

        if self._tp_tiled:
            from deepspeed_tpu.comm.overlap_tiled import tiled_tp_matmul

            return tiled_tp_matmul(
                x2d, w, self._mesh, self._overlap_tiles,
                comm_quant=self._comm_quant, tag=tag,
            )

        def local(xl, wl):
            return quantized_psum_tp(xl @ wl, MODEL_AXIS, tag=tag)

        return jax.shard_map(
            local,
            mesh=self._mesh,
            in_specs=(P(None, MODEL_AXIS), P(MODEL_AXIS, None)),
            out_specs=P(None, None),
            axis_names={MODEL_AXIS},
            check_vma=False,
        )(x2d, w)

    def _mlp_quant(self, lp, m):
        """Dense-MLP mirror of ``T._mlp_block`` for the quantized TP path:
        w_up/w_gate stay implicit GSPMD column-parallel (no psum on that
        wire), the w_down row-parallel matmul runs through the quantized
        psum island. Dense models only: an MoE model at tp>1 is refused at
        engine build."""
        c = self._mc
        up = T._proj(c, m, lp["w_up"])
        if c.mlp_bias:
            up = up + lp["w_up_b"]
        if c.activation in ("swiglu", "geglu"):
            gate = T._proj(c, m, lp["w_gate"])
            if c.mlp_bias:
                gate = gate + lp["w_gate_b"]
            act = (jax.nn.gelu(gate) if c.activation == "geglu" else jax.nn.silu(gate)) * up
        elif c.activation == "relu":
            act = jax.nn.relu(up)
        elif c.activation == "quick_gelu":
            act = up * jax.nn.sigmoid(1.702 * up)
        else:
            act = jax.nn.gelu(up, approximate=c.activation != "gelu_exact")
        t = act.shape[1]
        out = self._tp_row_matmul(act.reshape(t, -1), lp["w_down"], "tp_mlp_down")[None]
        if c.mlp_bias:
            out = out + lp["w_down_b"]
        return out

    def _layer_tail(self, lp, x, out, live, li, a=None):
        """Shared epilogue of an attention layer: the heads' output under its
        gate where the model has one (``a``: the normed input the gate is
        projected from), the wo projection (+ bias), then ``_mlp_tail``. With
        comm_quant="int8" at tp>1, the MODEL_AXIS reduction behind wo runs
        int8-inside-the-collective."""
        c = self._mc
        t = x.shape[1]
        out = out.reshape(t, c.n_heads * c.value_dim)
        if c.attn_out_gate:
            out = T.attn_gate(out, a[0] @ lp["wq_gate"])
        if self._tp_wire:
            attn_out = self._tp_row_matmul(out, lp["wo"], "tp_attn_out")[None]
        else:
            attn_out = stack_dot(out, lp["wo"])[None]
        if c.attn_out_bias:
            attn_out = attn_out + lp["wo_b"]
        if c.norm_scheme == "out":
            attn_out = T._norm(attn_out, lp["attn_norm"], lp.get("attn_norm_b"), c.norm, c.norm_eps)
        return self._mlp_tail(lp, x, attn_out, live, li)

    def _mlp_tail(self, lp, x, attn_out, live, li):
        """What follows a layer's token mixer, attention or DeltaNet alike:
        the parallel-block (falcon/phi) or sequential residual + MLP.
        ``live`` [t] bool: the slots of the step's grid that hold a token; an
        expert layer routes and counts those alone, and reads its weights at
        layer ``li`` of the whole stacks (_drive_layers). Returns (x, rows
        routed to each expert [E] int32, or None for a dense MLP)."""
        c = self._mc
        out_norm = c.norm_scheme == "out"  # the block's OUTPUT is normed, its input is not
        if not c.parallel_block:
            x = x + attn_out
        m = x if out_norm else T._norm(x, lp["mlp_norm"], lp.get("mlp_norm_b"), c.norm, c.norm_eps)
        counts = None
        if "router" in lp:  # an expert layer: its own keys say so
            from deepspeed_tpu.parallel.moe import moe_mlp

            # (its index in the whole stacks: behind the dense lead layers)
            mlp_out, _, counts = moe_mlp(
                c, lp, m, live=live[None], layer=li - c.moe_dense_lead if c.moe_dense_lead else li)
        elif self._tp_wire:
            mlp_out = self._mlp_quant(lp, m)
        else:
            mlp_out = T._mlp_block(c, lp, m)[0]
        if out_norm:
            mlp_out = T._norm(mlp_out, lp["mlp_norm"], lp.get("mlp_norm_b"), c.norm, c.norm_eps)
        x = x + attn_out + mlp_out if c.parallel_block else x + mlp_out
        return x, counts

    def _recurrent_layer(self, lp, x, li, rows, carry):
        """One recurrent layer of a step (Gated DeltaNet or Mamba:
        ``T.RECURRENT`` gives the kind's projections and its two rules), on the
        carried state pools. ``rows`` describes the step's grid: its first
        ``R`` slots are decode rows, one token each, at ``slots`` [R] with
        ``live`` [R]; a split step with chunks has ``Rc`` rows of ``tq``
        tokens behind them (``chk_slots`` / ``chk_start`` [Rc], ``chk_pos``
        [Rc, tq], -1 where a slot holds no token). Decode rows take the
        one-token update IN the pool (the kind's ``decode``: on a TPU a
        kernel, one read and one write of a row's state); chunk rows run the
        kind's ``chunk`` rule from the slot's state to the slot's state, a
        chunk at position 0 from zero whatever the slot holds. A slot of the
        grid that is not live takes no conv input and leaves its state as it
        was (the kind's rules see to that); padding points at the spare slot.
        Returns (x, carry, the layer's routed rows)."""
        c, kind = self._mc, self._rec
        R = rows["R"]
        base = self._ordinal(li) * self._state_slots
        state, conv = carry["rec_state"], carry["rec_conv"]
        a = T._norm(x, lp["attn_norm"], lp.get("attn_norm_b"), c.norm, c.norm_eps)
        y_in, extras = kind.project(c, lp, a[0])

        taps = (kind.kernel(c) - 1, kind.channels(c))  # a slot's row of the conv pool
        slots, live = base + rows["slots"], rows["live"]
        y, new = T.recurrent_conv(kind, lp, y_in[:R, None], conv[slots].reshape((R,) + taps),
                                  n=live.astype(jnp.int32))
        conv = conv.at[slots].set(new.reshape(R, -1))
        o, state = kind.decode(c, lp, y[:, 0], tuple(e[:R] for e in extras), live, state, slots,
                               self._rec_impl)
        if rows["tq"]:
            Rc, tq = rows["Rc"], rows["tq"]
            slots = base + rows["chk_slots"]
            tok_live = rows["chk_pos"] >= 0                              # [Rc, tq]
            fresh = rows["chk_start"] == 0
            conv0 = jnp.where(fresh[:, None, None], 0, conv[slots].reshape((Rc,) + taps))
            y, new = T.recurrent_conv(kind, lp, y_in[R:].reshape(Rc, tq, -1), conv0,
                                      n=jnp.sum(tok_live, axis=1, dtype=jnp.int32))
            conv = conv.at[slots].set(new.reshape(Rc, -1))
            old = state[slots]
            o_c, new = kind.chunk(
                c, lp, y, tuple(e[R:].reshape((Rc, tq) + e.shape[1:]) for e in extras), tok_live,
                jnp.where(fresh.reshape((Rc,) + (1,) * (old.ndim - 1)), 0.0, old), self._rec_impl)
            state = state.at[slots].set(new)
            o = jnp.concatenate([o, o_c.reshape((Rc * tq,) + o_c.shape[2:])], axis=0)
        out = kind.output(c, lp, o, extras, x.dtype)[None]
        x, moe = self._mlp_tail(lp, x, out, rows["slot_live"], li)
        return x, self._record_moe(dict(carry, rec_state=state, rec_conv=conv), li, moe)

    # ------------------------------------------------------------------
    def _split_layer(self, lp, x, li, meta, carry, window=None):
        """One transformer layer of the SPLIT-PHASE step: the packed token
        axis is [R decode slots | Rc chunks x tq tokens]. QKV/MLP/norms run
        on the whole packed batch (real MXU work); attention splits —
        decode rows through _attn_decode (their own new K/V as the
        extra_kv self column), chunk rows through paged_chunk_attention
        (in-chunk causal over the chunk's fresh K/V + pool context below
        the chunk start; with the kernel impl the flash kernel
        ``dstpu_paged_chunk``, which walks the blocks a row holds, else
        the dense gather over whole tables). At ``tq == 0`` the grid is
        the R decode slots alone and the chunk half is absent, not empty:
        no chunk attention is traced. No read needs this step's K/V from the pool, so the
        layer only records them in ``carry`` (the side buffers) and the
        pool is written once, after the loop. A recurrent layer of the same
        step (``layer_kinds``) goes through ``_recurrent_layer`` instead."""
        c = self._mc
        w = c.sliding_window if window is None else window
        nh, nkv, d = c.n_heads, c.kv_heads, c.head_dim
        R, Rc, tq = meta["R"], meta["Rc"], meta["tq"]
        lp = T._dequant_tree(lp, T.DTYPES[c.dtype])
        if self._hybrid and "wo" not in lp:  # a recurrent layer: no K/V, the state pools instead
            return self._recurrent_layer(lp, x, li, {
                "R": R, "Rc": Rc, "tq": tq, "slots": meta.get("dec_slots"),
                "live": meta["dec_pos"] >= 0, "slot_live": meta["slot_live"],
                "chk_slots": meta.get("chk_slots"), "chk_start": meta.get("chk_start"),
                "chk_pos": meta.get("chk_pos")}, carry)
        if "sub" in lp:  # a layer of two sub-blocks (moe_shortcut): its own keys say so
            return self._shortcut_layer(lp, x, li, meta, carry)
        if self._latent:
            return self._latent_layer(lp, x, li, meta, carry)
        a, q, k, v = self._layer_qkv(lp, x, meta["positions"], meta["live"], w)
        nkv, dv = v.shape[1:]  # the layer's own (a stack stacked by kind)
        # a window layer's learned sinks (mimo_v2_flash), in the kernels' finish
        sinks = lp.get("sink")
        ks_pool, vs_pool = meta["ks_pool0"], meta["vs_pool0"]
        # the pool of the layer's kind at the layer's ordinal in it
        k_pool, v_pool, tables_l, trash_l = self._kv_source(meta, li, "dec_tables")
        out = self._attn_decode(
            q[:R], k_pool, v_pool, tables_l, meta["dec_pos"], w, trash_l,
            extra_kv=(k[:R, None], v[:R, None], meta["dec_pos"][:, None]),
            pool_limit=meta["dec_pos"],
            k_scale=ks_pool, v_scale=vs_pool, sinks=sinks,
        )
        if tq:
            from deepspeed_tpu.ops.attention.paged_pallas import paged_chunk_attention

            _, _, tables_l, trash_l = self._kv_source(meta, li, "chk_tables")
            out_c = paged_chunk_attention(
                q[R:].reshape(Rc, tq, nh, d), k_pool, v_pool,
                tables_l, meta["chk_pos"], trash_l,
                window=int(w), scale=c.attn_scale,
                new_kv=(k[R:].reshape(Rc, tq, nkv, d), v[R:].reshape(Rc, tq, nkv, dv)),
                pool_limit=meta["chk_start"],
                k_scale=ks_pool, v_scale=vs_pool,
                impl=self._attn_impl, sinks=sinks,
            )
            out = jnp.concatenate([out, out_c.reshape(Rc * tq, nh, dv)], axis=0)
        x, moe = self._layer_tail(lp, x, out, meta["slot_live"], li, a)
        return x, self._record_kv(carry, li, k, v, moe)

    def _latent_attention(self, lp, x, plane, meta):
        """Latent attention of the split step on the cache's plane ``plane`` (as
        ``_kv_source`` takes a layer: the plane itself, or in a stack with
        recurrent layers the LAYER, whose ordinal among the latent ones is its
        plane), each kind of row in the form its queries a key pay for
        (ops/attention/latent_pallas.py). Decode rows ABSORBED, one query a
        key: ``W_UK`` moved to the query (``q = [q_nope W_UK | q_rope]``, every
        head against the one cached vector a token), ``W_UV`` behind the
        output, through ``latent_decode`` (the pool below their position and
        their own new vector as the extra column). Chunk rows through
        ``latent_chunk`` (the pool below the chunk's start, then the chunk's
        own vectors, causal), which takes the query projection AS WRITTEN
        (``latent_q``: the dims without rotary are read where they lie),
        ``q_rope`` as ``latent_qkv`` rotated it and ``wkv_b`` as the checkpoint
        stores it: a row of ``prompt_chunk`` slots attends EXPANDED, a head's
        keys and values made of the cached latents inside the kernel; the
        128-slot bucket absorbed. On the chip the kernels ``dstpu_mla_decode``
        / ``dstpu_mla_chunk``, elsewhere the dense forms. Returns (the block's
        output [1, t, h], the new vectors [t, latent_dim])."""
        from deepspeed_tpu.ops.attention.latent_pallas import latent_chunk, latent_decode

        c = self._mc
        R, Rc, tq = meta["R"], meta["Rc"], meta["tq"]
        nh, rank, D = c.n_heads, c.kv_lora_rank, c.latent_dim
        scale = c.attn_scale if c.attn_scale is not None else c.head_dim ** -0.5
        a = T._norm(x, lp["attn_norm"], lp.get("attn_norm_b"), c.norm, c.norm_eps)
        q = T.latent_q(c, lp, a[0])
        q_nope, q_rope, ckv = T.latent_qkv(c, lp, a[0], meta["positions"], meta["live"], q=q)
        w_uk, w_uv = T.latent_up(c, lp)
        qa = jnp.concatenate([jnp.einsum("thd,chd->thc", q_nope[:R], w_uk), q_rope[:R]], axis=-1)
        pool, _, tables_l, trash_l = self._kv_source(meta, plane, "dec_tables")
        out = latent_decode(
            qa, pool, tables_l, meta["dec_pos"], trash_l, rank=rank, scale=scale,
            extra=(ckv[:R, None], meta["dec_pos"][:, None]), pool_limit=meta["dec_pos"],
            impl=self._attn_impl)
        out = jnp.einsum("thc,chd->thd", out, w_uv).reshape(R, nh * c.v_head_dim)
        if tq:
            _, _, tables_l, trash_l = self._kv_source(meta, plane, "chk_tables")
            out_c = latent_chunk(
                q[R:].reshape(Rc, tq, -1), q_rope[R:].reshape(Rc, tq, nh, -1), lp["wkv_b"],
                pool, tables_l, meta["chk_pos"], trash_l, ckv[R:].reshape(Rc, tq, D),
                meta["chk_start"], scale=scale, impl=self._attn_impl)
            out = jnp.concatenate([out, out_c.reshape(Rc * tq, nh * c.v_head_dim)], axis=0)
        return (out @ lp["wo"])[None], ckv

    def _latent_layer(self, lp, x, li, meta, carry):
        """One latent-attention layer of the split step: ``_latent_attention``
        on the layer's plane (``li``, or in a stack with recurrent layers beside
        it the layer's ordinal among the latent ones), then the MLP or the
        expert block. The layer records its new vectors in ``carry``; the pool
        is written once, after the loop."""
        lp = T._dequant_tree(lp, T.DTYPES[self._mc.dtype])
        attn_out, ckv = self._latent_attention(lp, x, li, meta)
        x, moe = self._mlp_tail(lp, x, attn_out, meta["slot_live"], li)
        carry = dict(carry, k=jax.lax.dynamic_update_index_in_dim(
            carry["k"], ckv, self._ordinal(li), 0))
        return x, self._record_moe(carry, li, moe)

    def _shortcut_layer(self, lp, x, li, meta, carry):
        """One ``moe_shortcut`` layer of the split step (longcat_flash): two
        sub-blocks, each ``_latent_attention`` on its own plane (2 li + i) and a
        dense MLP on the stream, and the expert block, which reads the FIRST
        sub-block's normed MLP input and joins the stream behind the SECOND
        sub-block's MLP. Nothing but that sum orders the expert block against
        the second attention: across chips its exchange would hide behind it.
        ``lp``: the expert block's parameters (its weights the whole stacks,
        read at ``li``) and under "sub" the two sub-blocks'. Each sub-block
        records its new vectors at its plane, the layer what it routed."""
        from deepspeed_tpu.parallel.moe import moe_mlp

        c = self._mc
        shortcut = moe = None
        for i, sp in enumerate(lp["sub"]):
            with jax.named_scope(f"sub_block_{i}"):
                plane = 2 * li + i
                attn_out, ckv = self._latent_attention(sp, x, plane, meta)
                carry = dict(carry, k=jax.lax.dynamic_update_index_in_dim(carry["k"], ckv, plane, 0))
                x = x + attn_out
                m = T._norm(x, sp["mlp_norm"], None, c.norm, c.norm_eps)
                if i == 0:
                    with jax.named_scope("shortcut_experts"):
                        shortcut, _, moe = moe_mlp(c, lp, m, live=meta["slot_live"][None], layer=li)
                x = x + T._mlp_block(c, sp, m)[0]
        return x + shortcut, self._record_moe(carry, li, moe)

    def _build_split_step(self, shape):
        """ONE compiled step over the split-phase batch: R decode slots +
        Rc prompt chunks of tq tokens (the static-shape SplitFuse), ``shape``
        = (Rc, tq). blk/row/positions come pre-staged from the host
        (_stage_split) — data-dependent anyway. The shapes of the one
        program are what the batch holds: ``Rc`` the chunk rows the
        scheduler cut (1 .. ``max_prompt_chunks``: a row nobody cut is not
        on the grid), ``tq`` 128 or ``prompt_chunk`` (the chunk-length
        buckets: _chunk_bucket), and (0, 0) for a batch with no chunk row:
        the grid is the R decode slots, the program takes no ``chk_*`` input
        and runs no chunk attention (the verify step's grid is R rows wide
        already). Outputs: (decode logits [R, vocab],
        chunk logits [Rc, vocab], decode tokens [R], chunk tokens [Rc],
        ``last_tokens`` [R + max_prompt_chunks]); the chunk pair is None at
        ``tq == 0``, where no row reads it.

        One step in flight: every shape takes the PREVIOUS split step's
        ``last_tokens`` (its sampled tokens by output slot, the same length
        whatever shape that step had: decode slots, then chunk rows, zeros
        where it had none) and ``tok_src`` [R]: the slot a decode row's
        token comes from, or -1 for the host's ``tokens[i]``. So a row whose
        token is still on the device is launched without it, and the program
        returns its own ``last_tokens`` for the next."""
        R = self.config.state_manager.max_ragged_sequence_count
        Rc, tq = shape

        def step(params, inputs, rng, temperature, pools):
            tokens, positions = inputs["tokens"], inputs["positions"]
            dec_pos = inputs["dec_pos"]
            tok_src = inputs["tok_src"]
            tokens = tokens.at[:R].set(jnp.where(
                tok_src >= 0, inputs["last_tokens"][jnp.maximum(tok_src, 0)], tokens[:R]))
            x = self._embed(params, tokens, positions)
            pools, second = self._split_pools(pools)
            meta = {
                "R": R, "Rc": Rc, "tq": tq, "positions": positions,
                **self._cache_views(pools, second),
                # a second kind of cache: the rows' slots (None otherwise)
                "dec_slots": inputs.get("dec_slots"), "chk_slots": inputs.get("chk_slots"),
                # live length (HF max(position_ids)+1) for the rope-scaling
                # switch: padded slots carry position 0, so the plain max works
                "live": jnp.max(positions) + 1,
                "dec_tables": inputs["dec_tables"], "dec_pos": dec_pos,
                # the grid's padding: decode slots with no row
                "slot_live": dec_pos >= 0,
            }
            if self._windowed:
                meta["win_dec_tables"] = self._ring_tables(inputs["dec_slots"])
            if tq:
                chk_pos = inputs["chk_pos"]
                meta.update(
                    chk_tables=inputs["chk_tables"], chk_pos=chk_pos,
                    chk_start=inputs["chk_start"],
                    # ... and chunk tails
                    slot_live=jnp.concatenate([dec_pos >= 0, chk_pos.reshape(Rc * tq) >= 0]),
                )
                if self._windowed:
                    meta["win_chk_tables"] = self._ring_tables(inputs["chk_slots"])

            def layer_fn(lp, x, li, carry, window=None):
                return self._split_layer(lp, x, li, meta, carry, window=window)

            x, side = self._drive_layers(
                layer_fn, params, x,
                self._with_state(self._side_buffers(tokens.shape[0]), second))
            visits = tuple(inputs[k] for k in ("lat_vblk", "lat_vtile", "lat_vflag")
                           ) if "lat_vblk" in inputs else None
            pools = self._write_back(
                pools, second, inputs["blk"], inputs["row"], side, inputs.get("wblk"), x,
                visits=visits)
            logits_dec, toks_dec = self._sample_rows(
                params, x, slice(0, R), rng, temperature, inputs["dec_uids"], dec_pos)
            logits_chk = toks_chk = None
            if tq:
                chk_at = jnp.clip(inputs["chk_last"], 0, tokens.shape[0] - 1)
                logits_chk, toks_chk = self._sample_rows(
                    params, x, chk_at, rng, temperature, inputs["chk_uids"], positions[chk_at])
            # by output slot, the same length in every shape: the rows this
            # shape has, zeros behind them
            last_tokens = jnp.concatenate([
                toks_dec.astype(jnp.int32),
                *([toks_chk.astype(jnp.int32)] if tq else []),
                jnp.zeros(self.scheduler.max_prompt_chunks - Rc, jnp.int32)])
            return ((logits_dec, logits_chk, toks_dec, toks_chk, last_tokens), pools,
                    self._moe_rows(side))

        return jax.jit(step, donate_argnums=(4,))

    # ------------------------------------------------------------------
    def _build_verify_step(self, k: int):
        """ONE compiled speculative verify step: every active row scores its
        pending token plus up to ``k`` draft tokens in a single (k+1)-token
        forward pass — the chunk-attention shape the split step already
        serves, so no new attention kernel. Per row the program

          * feeds tokens x_0..x_K at positions p..p+K (x_0 = the pending
            sampled token; rows with fewer drafts pad, and padded positions
            carry qpos -1 so attention masks them and their KV scatters to
            the trash block);
          * samples the TARGET token for every position with the same
            content-addressed key plain decode would use —
            ``row_keys(rng, uid, position)`` — so target t_i is exactly the
            token plain decode emits at p+i given the same history;
          * accepts the longest draft prefix matching those targets
            (in-program cumprod) and returns n_emit = accepted + 1 tokens
            t_0..t_a per row (n_emit ∈ [1, k+1]: a fully rejected draft
            still yields the one token plain decode would have).

        Exact-match acceptance against the deterministic sampler is what
        makes spec-on output BIT-IDENTICAL to spec-off for greedy and
        sampled streams alike — speculation changes how many serialized
        passes the stream costs, never its contents. Both KV pools are
        donated; rejected drafts leave stale KV only at positions past the
        new write cursor (masked by position on every later read, and
        overwritten before they re-enter any pool window)."""
        self._refuse_state_loss("the speculative verify step")
        c = self._mc
        kv = self.config.kv_cache
        bs = kv.block_size
        B = kv.max_blocks_per_seq
        trash = kv.num_blocks
        NBp = kv.num_blocks + 1
        R = self.config.state_manager.max_ragged_sequence_count
        dtype = T.DTYPES[c.dtype]
        K1 = k + 1

        def verify(params, inputs, rng, temperature, pools):
            tokens, active, n_input = inputs["tokens"], inputs["active"], inputs["n_input"]
            positions0 = inputs["positions"]
            nh, nkv, d = c.n_heads, c.kv_heads, c.head_dim
            tok_tables = jnp.where(active[:, None], inputs["tables"], trash)
            j = jnp.arange(K1, dtype=jnp.int32)
            pos = positions0[:, None] + j[None]  # [R, K1]
            valid = (j[None] < n_input[:, None]) & active[:, None]
            qpos = jnp.where(valid, pos, -1)  # -1: padded query/key slot
            flat_pos = pos.reshape(R * K1)
            x = self._embed(params, tokens.reshape(R * K1), flat_pos)
            # rope live length from VALID positions only (padded slots would
            # flip a longrope factor switch early)
            live = jnp.max(jnp.where(valid, pos, 0)) + 1
            blk = jnp.take_along_axis(tok_tables, jnp.clip(pos // bs, 0, B - 1), axis=1)
            blk = jnp.where(valid, blk, trash).reshape(R * K1)
            row = flat_pos % bs
            # step-start pool views: reads below each row's write cursor
            # only (pool_limit); the K1 fresh K/V of a row ride alongside and
            # reach the pool in one write-back after the loop, as in the
            # split step
            k_pool0, v_pool0 = self._pool_views(*pools[:2])
            ks_pool0, vs_pool0 = self._scale_views(*pools[2:])
            pool_lim = jnp.where(active, positions0, 0)
            from deepspeed_tpu.ops.attention.paged_pallas import paged_chunk_attention

            use_kernel = self._attn_impl == "kernel"
            if use_kernel:
                # flattened per-token form for paged_attention: every one of
                # the row's K1 tokens carries the row's table/pool window,
                # and the row's K1 fresh K/V ride as shared extra columns —
                # the extras mask (epos >= 0) & (epos <= qpos) IS the
                # in-chunk causal mask, so padded slots (qpos -1) see
                # nothing and emit 0 like the chunk form
                rep_tables = jnp.repeat(tok_tables, K1, axis=0)  # [R*K1, B]
                rep_lim = jnp.repeat(pool_lim, K1)
                qpos_flat = qpos.reshape(R * K1)
                epos_flat = jnp.broadcast_to(
                    qpos[:, None, :], (R, K1, K1)
                ).reshape(R * K1, K1)

            def layer_fn(lp, x, li, carry, window=None):
                w = c.sliding_window if window is None else window
                lp = T._dequant_tree(lp, dtype)
                a, q, k_, v_ = self._layer_qkv(lp, x, flat_pos, live)
                if use_kernel:
                    ke = jnp.broadcast_to(
                        k_.reshape(R, 1, K1, nkv, d), (R, K1, K1, nkv, d)
                    ).reshape(R * K1, K1, nkv, d)
                    ve = jnp.broadcast_to(
                        v_.reshape(R, 1, K1, nkv, d), (R, K1, K1, nkv, d)
                    ).reshape(R * K1, K1, nkv, d)
                    out = self._attn_decode(
                        q, k_pool0, v_pool0, li * NBp + rep_tables,
                        qpos_flat, w, li * NBp + trash,
                        extra_kv=(ke, ve, epos_flat), pool_limit=rep_lim,
                        k_scale=ks_pool0, v_scale=vs_pool0,
                    )
                else:
                    out = paged_chunk_attention(
                        q.reshape(R, K1, nh, d), k_pool0, v_pool0,
                        li * NBp + tok_tables, qpos, li * NBp + trash,
                        window=int(w), scale=c.attn_scale,
                        new_kv=(k_.reshape(R, K1, nkv, d), v_.reshape(R, K1, nkv, d)),
                        pool_limit=pool_lim,
                        k_scale=ks_pool0, v_scale=vs_pool0,
                    ).reshape(R * K1, nh, d)
                x, moe = self._layer_tail(
                    lp, x, out.reshape(R * K1, nh, d), valid.reshape(R * K1), li, a)
                return x, self._record_kv(carry, li, k_, v_, moe)

            x, side = self._drive_layers(
                layer_fn, params, x, self._side_buffers(R * K1)
            )
            pools = self._scatter_kv(pools, blk, row, (side["k"], side["v"]))
            _, (tgt, logp) = self._sample_rows(
                params, x, slice(None), rng, temperature,
                jnp.repeat(inputs["uids"], K1), qpos.reshape(R * K1), return_logprobs=True)
            tgt = tgt.reshape(R, K1)
            logp = logp.reshape(R, K1)
            jj = jnp.arange(k, dtype=jnp.int32)
            match = (tokens[:, 1:] == tgt[:, :k]) & (jj[None] < (n_input - 1)[:, None])
            n_acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
            n_emit = jnp.where(active, n_acc + 1, 0)
            return (tgt, n_emit, logp), pools, self._moe_rows(side)

        return jax.jit(verify, donate_argnums=(4,))

    # -- the step protocol: stage -> launch -> collect ----------------------
    def _stage_split(self, total_tokens, dec_rows, chk_rows):
        """The scheduler's batch onto the [R decode slots | Rc chunks x tq]
        grid, ``Rc`` the chunk rows it holds: ``dec_rows`` (uid, tokens,
        start, src: the slot of the previous split step's ``last_tokens`` its
        token is in, -1 for a token the host has), ``chk_rows`` (uid, tokens,
        start, chunked). Returns the split step's cache key, ("split", (Rc,
        tq)), and its inputs by name: the nine a decode slot needs and, for a
        batch that holds a chunk, the five ``chk_*``."""
        kv = self.config.kv_cache
        R = self.config.state_manager.max_ragged_sequence_count
        Rc = len(chk_rows)
        B = kv.max_blocks_per_seq
        bs = kv.block_size
        trash = kv.num_blocks
        if len(dec_rows) > R or Rc > self.scheduler.max_prompt_chunks:
            raise RuntimeError(
                f"split-phase batch overflow: {len(dec_rows)} decode rows "
                f"(cap {R}), {Rc} prompt chunks (cap {self.scheduler.max_prompt_chunks})"
            )
        # the grid has a row a chunk the scheduler cut, as long as the bucket
        # of the longest; a batch with no chunk takes the grid of its decode
        # slots alone
        tq = self._chunk_bucket(Rc, max(len(t) for _, t, _, _ in chk_rows)) if Rc else 0
        T_ = R + Rc * tq

        tokens = np.zeros(T_, np.int32)
        positions = np.zeros(T_, np.int32)
        blk = np.full(T_, trash, np.int32)
        row = np.zeros(T_, np.int32)
        dec_tables = np.full((R, B), trash, np.int32)
        dec_pos = np.full(R, -1, np.int32)  # -1 = inactive slot (masks all)
        dec_uids = np.zeros(R, np.int32)
        tok_src = np.full(R, -1, np.int32)
        chk_tables = np.full((Rc, B), trash, np.int32)
        chk_pos = np.full((Rc, tq), -1, np.int32)
        chk_start = np.zeros(Rc, np.int32)
        chk_last = np.zeros(Rc, np.int32)
        chk_uids = np.zeros(Rc, np.int32)
        # a second kind of cache: each row's slot; padding points at the spare
        spare = self._state_slots - 1
        dec_slots = np.full(R, spare, np.int32)
        chk_slots = np.full(Rc, spare, np.int32)
        # a window pool: each token's ring block (the spare ring: not written)
        wb = self._win_blocks
        wblk = np.full(T_, spare * wb, np.int32)

        for i, (uid, toks, start, src) in enumerate(dec_rows):
            seq = self.state_manager.get_sequence(uid)
            dec_slots[i] = seq.state_slot
            tokens[i] = toks[0]
            tok_src[i] = src
            positions[i] = start
            nblk = len(seq.block_table)
            dec_tables[i, :nblk] = seq.block_table
            dec_pos[i] = start
            dec_uids[i] = uid
            blk[i] = seq.block_table[min(start // bs, nblk - 1)]
            row[i] = start % bs
            if wb:
                wblk[i] = seq.state_slot * wb + (start // bs) % wb
        for j, (uid, toks, start, _chunked) in enumerate(chk_rows):
            seq = self.state_manager.get_sequence(uid)
            n = len(toks)
            off = R + j * tq
            tokens[off : off + n] = toks
            pos = start + np.arange(n)
            positions[off : off + n] = pos
            nblk = len(seq.block_table)
            chk_tables[j, :nblk] = seq.block_table
            chk_pos[j, :n] = pos
            chk_start[j] = start
            chk_uids[j] = uid
            chk_slots[j] = seq.state_slot
            # host-side scheduler metadata, not a device value
            blk[off : off + n] = np.asarray(seq.block_table, np.int32)[  # dstpu: noqa[host-sync-in-loop]
                np.minimum(pos // bs, nblk - 1)
            ]
            row[off : off + n] = pos % bs
            chk_last[j] = off + n - 1
            if wb:
                # a chunk longer than a ring keeps its last ring of tokens, all a
                # later query can see, and no ring slot is written twice
                wblk[off : off + n] = np.where(
                    pos >= start + n - wb * bs, seq.state_slot * wb + (pos // bs) % wb, spare * wb)
        prefill = sum(len(t) for _, t, _, _ in chk_rows)
        self.last_step = StepStats(
            T_, total_tokens, prefill, **self._count_paged(dec_pos),
            # of ONE recurrent layer: the rows its one-token update took, the
            # prompt tokens its chunk rule walked
            **({"recurrent_decode_rows": len(dec_rows), "recurrent_chunk_tokens": prefill}
               if self._hybrid else {}),
            **self._count_chunk(chk_rows, tq), **self._count_cache(dec_pos, total_tokens),
        )
        if self._latent:
            # what ONE layer's absorbed decode walks: paged_live_blocks again,
            # under the latent kernel's name, beside the rows it walks them for
            self.last_step.latent_decode_rows = len(dec_rows)
            self.last_step.latent_decode_blocks = self.last_step.paged_live_blocks
            self.last_step.latent_live_blocks = self.state_manager.live_blocks
            from deepspeed_tpu.ops.attention.latent_pallas import chunk_expands

            self.last_step.latent_chunk_rows = len(chk_rows)
            self.last_step.latent_chunk_expanded_rows = len(chk_rows) if chunk_expands(tq) else 0
        inputs = {
            "tokens": tokens, "positions": positions, "blk": blk, "row": row,
            "dec_tables": dec_tables, "dec_pos": dec_pos, "dec_uids": dec_uids,
            # the previous split step's tokens, where they are: on the device
            "tok_src": tok_src, "last_tokens": self._last_tokens,
        }
        if tq:  # the decode-only shape takes, and is sent, no chunk input
            inputs.update(
                chk_tables=chk_tables, chk_pos=chk_pos, chk_start=chk_start,
                chk_last=chk_last, chk_uids=chk_uids,
            )
        if self._beside:
            inputs["dec_slots"] = dec_slots
            if tq:
                inputs["chk_slots"] = chk_slots
        if wb:
            inputs["wblk"] = wblk
        if self._latent and self._attn_impl == "kernel":
            # the programs of the pool write (dstpu_mla_write), a fixed count a
            # step shape: a decode row's block, and a chunk's blocks by the
            # 128-token tiles of the grid they come from
            from deepspeed_tpu.ops.attention.latent_pallas import WRITE_TILE, write_visits

            inputs["lat_vblk"], inputs["lat_vtile"], inputs["lat_vflag"] = write_visits(
                blk, trash, R + Rc * (tq // bs + tq // WRITE_TILE + 3))
        return ("split", (Rc, tq)), inputs

    def _stage_verify(self, uids, row_drafts, k: int):
        """A verify step of up to ``k`` drafts a row (``row_drafts`` beside
        ``uids``): cache key and inputs by name, one row a running sequence
        of ``uids``, [R]-shaped (tokens [R, k + 1]: a row's pending token, then
        its drafts), inactive rows all-trash at position 0."""
        kv = self.config.kv_cache
        R = self.config.state_manager.max_ragged_sequence_count
        inputs = {
            "tokens": np.zeros((R, k + 1), np.int32),
            "positions": np.zeros(R, np.int32),
            "tables": np.full((R, kv.max_blocks_per_seq), kv.num_blocks, np.int32),
            "uids": np.zeros(R, np.int32),
            "active": np.zeros(R, bool),
            "n_input": np.ones(R, np.int32),
        }
        for i, (uid, d) in enumerate(zip(uids, row_drafts)):
            seq = self.state_manager.get_sequence(uid)
            inputs["tokens"][i, 0] = self.scheduler.peek_next_token(uid)
            inputs["tokens"][i, 1 : 1 + len(d)] = d
            inputs["n_input"][i] = 1 + len(d)
            inputs["positions"][i] = seq.seen_tokens
            inputs["tables"][i, : len(seq.block_table)] = seq.block_table
            inputs["uids"][i] = uid
            inputs["active"][i] = True
        self.last_step = StepStats(
            R * (k + 1), len(uids) + sum(len(d) for d in row_drafts), 0,
            **self._count_paged(inputs["positions"], calls=k + 1),
            **self._count_cache((), len(uids) + sum(len(d) for d in row_drafts)),
        )
        return ("verify", k), inputs

    def _launch(self, key, inputs):
        """THE call site of a step program. Looks the program of ``key`` up
        (or builds it), moves the staged arrays to the device one
        ``jnp.asarray`` an input, passes the sampling state and the pools,
        stores the pools the program returns and parks an expert model's
        routed rows for _count_moe. Returns the program's outputs: device
        arrays, nothing waited for.

        Between the transfers and the jitted call it stamps the step's
        enqueue (``_enqueued``, for _dispatch) and asks, without waiting,
        whether the step in flight has finished already (``starved``)."""
        fn = self._programs.get(key)
        first_call = None
        if fn is None:
            # the program's first call, builder to the jitted call's return:
            # its trace, lower and compile (or the cache's answer) nest in it
            kind, shape = key
            first_call = get_setup_record().span("program.first_call", key=f"{kind}[{shape}]")
            first_call.__enter__()
            fn = self._programs[key] = getattr(self, _BUILDERS[kind])(shape)
        args = (
            self.params,
            {name: jnp.asarray(a) for name, a in inputs.items()},
            self._rng,
            jnp.float32(getattr(self.config, "temperature", 1.0) or 1.0),
            self._pools(),
        )
        prev = self._uncollected
        self.last_step.starved = prev is not None and _is_ready(prev.waited[0])
        self._enqueued = _now()
        try:
            outputs, pools, self._moe_pending = fn(*args)
        finally:
            if first_call is not None:
                first_call.__exit__(None, None, None)
        pools, second = self._split_pools(pools)
        if self._latent:
            (self._k_cache,) = pools
        else:
            self._k_cache, self._v_cache = pools[:2]
        if self._kv_int8:
            self._ks_cache, self._vs_cache = pools[2:]
        if self._hybrid:
            self._rec_state, self._rec_conv = second
        elif self._windowed:
            self._wk_cache, self._wv_cache = second
        return outputs

    def _start(self, stage, *args):
        """``stage(*args)`` then _launch, each under its span; sync-free."""
        tr = get_tracer()
        track = getattr(self, "_trace_name", "engine")
        with tr.span("engine.stage", track=track):
            key, inputs = stage(*args)
        with tr.span("engine.launch", track=track):
            return self._launch(key, inputs)

    def _dispatch(self, dispatch) -> StepInFlight:
        """The first half of a served step, on this replica's engine track.
        ``dispatch()`` runs under ``engine.dispatch`` (host-side scheduling,
        staging and the async launch: ``engine.schedule`` / ``engine.stage``
        / ``engine.launch`` nest in it) and returns (the device arrays the
        results come from, ``finish``, the dispatch span's args[, the rows'
        output slots]). What staging and the launch left on ``self`` for the
        step (its StepStats, an expert model's routed rows) moves into the
        record, and the arrays' host copies are requested now, queued
        behind the program: nothing here waits. One path, traced or not:
        the null tracer's spans are a shared no-op."""
        tr = get_tracer()
        with tr.span("engine.dispatch", track=getattr(self, "_trace_name", "engine")) as sp:
            waited, finish, span_args, *rows = dispatch()
            if tr.enabled:
                sp.args = span_args
        moe, self._moe_pending = self._moe_pending, None
        enqueued, self._enqueued = self._enqueued, None
        # an expert model's routed rows come from the same program: nothing
        # more to wait for, but for a step that completed no row
        flight = StepInFlight(
            list(waited) + ([] if moe is None else [moe]), finish, self.last_step, moe, *rows,
            t_enqueued=enqueued)
        _start_host_copies(flight.waited)
        if flight.waited:
            self._uncollected = flight
        return flight

    def _collect(self, flight: StepInFlight):
        """The second half: ``engine.device_wait`` blocks on the step's OWN
        outputs and, as the wait returns, stamps the step ready and counts
        its seconds on the device (``StepStats.t_ready`` / ``device_s``: one
        path, tracing off or on); ``engine.materialize`` reduces an expert
        model's routed rows and runs ``finish()``, whose value is returned.
        So host-side queueing and device time separate on the timeline.
        ``engine.last_step`` is the collected step's again."""
        tr = get_tracer()
        track = getattr(self, "_trace_name", "engine")
        with tr.span("engine.device_wait", track=track):
            # A step that completed no row (prompt chunks with more to come
            # and no decode row) is waited for all the same, on the tokens
            # its program returns. Left to run on, a long prompt's chunk
            # steps queue on the device and the last one's wait holds the
            # serving loop for all of them (no admission, no cancel, for up
            # to max_context / prompt_chunk steps), and a chunk step costs
            # one thing beside a decode row and another without: a
            # first-token tail then reads which of the two its arrivals
            # happened to meet. So at most ONE step is in flight beyond the
            # one waited for here (EngineCore.step_once).
            device_synchronize(flight.waited)
            if flight.waited:
                # the step's time on the device: it began when the step
                # collected before it ended (it ran ahead: every step of a
                # loop under load) or, after an idle chip, at its own enqueue
                stats = flight.stats
                stats.t_ready = _now()
                stats.device_s = stats.t_ready - max(self._last_ready, flight.t_enqueued)
                self._last_ready = stats.t_ready
        if self._uncollected is flight:
            self._uncollected = None
        with tr.span("engine.materialize", track=track):
            self.last_step = flight.stats
            self._count_moe(flight)
            return flight.finish()

    def _count_moe(self, flight: StepInFlight) -> None:
        """After the wait: reduce the step's routed rows [L, E] to its
        ``stats.moe``. One layer call a row
        of E: rows routed, rows the dispatch computed (the grouped kernel:
        its tile size for every tile visit; the capacity dispatch: E x
        capacity), the fullest expert's rows and the experts that had a row.
        None stays for a dense model or a step that launched nothing."""
        pending, flight.moe = flight.moe, None
        if pending is None:
            return
        from deepspeed_tpu.parallel.moe import grouped, sharded_moe

        c = self._mc
        rows = np.asarray(pending)[c.moe_dense_lead:]  # the layers that have experts
        beside = rows[:, c.n_experts:].sum()  # the entry behind the experts', where there is one
        group_hit = beside if c.moe_n_group > 1 else None
        counts = rows[:, : c.n_experts]
        # tokens of one layer call: the grid
        pairs = flight.stats.grid_slots * c.moe_top_k
        if c.moe_drop_tokens:
            computed = counts.shape[0] * c.n_experts * sharded_moe._capacity(
                pairs, c.n_experts, c.moe_capacity_factor)
        else:
            itemsize = jnp.dtype(T.DTYPES[c.dtype]).itemsize
            computed = grouped.computed_rows(counts, grouped.row_tile(pairs, itemsize))
        flight.stats.moe = {
            "routed": int(counts.sum()), "computed": int(computed),
            "hot": int(counts.max(axis=-1).sum()), "calls": int(counts.shape[0]),
            "hit": int((counts > 0).sum()),
        }
        if c.moe_zero_experts:
            # a router with identity experts: every (token, choice) pair of the
            # layer calls, held here or not, and those that chose an identity one
            flight.stats.moe.update(
                pairs=flight.stats.scheduled_tokens * counts.shape[0] * c.moe_top_k,
                zero_pairs=int(beside))
        if group_hit is not None:
            # a grouped router: tokens whose kept groups include a held one,
            # summed over the expert layers' calls, beside the tokens routed
            flight.stats.moe.update(
                group_hit=int(group_hit), group_tokens=flight.stats.scheduled_tokens * counts.shape[0])

    # -- entry points --------------------------------------------------------
    def spec_round(self, k: Optional[int] = None, drafts=None) -> Dict[int, np.ndarray]:
        """One speculative draft-and-verify round over eligible RUNNING
        rows. ``drafts``: {uid: proposed next tokens (≤ k)}; rows without an
        entry verify zero drafts — a plain one-token decode riding the same
        program, so undrafted requests never starve behind spec rounds.
        Returns {uid: emitted tokens (1..k+1, bit-identical to the plain
        decode stream)}; per-round draft/accept counts land in
        ``self.last_spec`` for the driver's metrics and adaptive-K control.

        Rows near max_context / the block cap / out of pool blocks are left
        to the split step, which caps, stops and waits, with
        each row extended by only the blocks ITS draft needs; rejected
        drafts' blocks are rolled back via ``scheduler.apply_spec_round``.
        Rows are capped so rows x (k+1) fits the step token budget, with a
        rotating start so a capped round cannot starve later uids."""
        self._refuse_state_loss("spec_round")
        k = int(k if k is not None else getattr(self.config, "spec_k", 0) or 0)
        if k < 1:
            raise ValueError(f"spec_round needs k >= 1 draft slots, got {k}")
        drafts = drafts or {}
        sched = self.scheduler
        if sched.has_pending():
            raise RuntimeError(
                "spec_round: prompt chunks are still pending — drive step() "
                "until prefill completes before speculative decode"
            )
        max_context = self.config.state_manager.max_context
        R = self.config.state_manager.max_ragged_sequence_count
        budget = self.config.state_manager.max_ragged_batch_size
        max_rows = min(R, max(1, budget // (k + 1)))
        run = sched.running_uids()
        if len(run) > max_rows:
            off = self._spec_rr % len(run)
            run = run[off:] + run[:off]
            self._spec_rr += max_rows
        uids, row_drafts = [], []
        pre_blocks: Dict[int, int] = {}
        for uid in run:
            if len(uids) >= max_rows:
                break
            seq = self.state_manager.get_sequence(uid)
            d = [int(t) for t in drafts.get(uid, ())][:k]
            n = len(d) + 1
            if seq.seen_tokens + n > max_context:
                continue  # near the context limit: per-step path stops it
            if self.state_manager.seq_capped(seq, n):
                continue  # near the block cap: per-step path caps it
            pre = len(seq.block_table)
            if not self.state_manager.extend(seq, n):
                continue  # pool momentarily exhausted: sequence waits
            uids.append(uid)
            row_drafts.append(d)
            pre_blocks[uid] = pre
        if not uids:
            return {}

        def dispatch():
            outputs = self._start(self._stage_verify, uids, row_drafts, k)

            def finish():
                tgt, n_emit, logp = (np.asarray(a) for a in outputs)
                results: Dict[int, np.ndarray] = {}
                self.last_logprobs = {}
                drafted_total = accepted_total = 0
                per_uid: Dict[int, Tuple[int, int]] = {}
                for i, (uid, drafted) in enumerate(zip(uids, row_drafts)):
                    n = int(n_emit[i])
                    gen = tgt[i, :n].astype(np.int32)
                    sched.apply_spec_round(uid, gen, pre_blocks[uid])
                    results[uid] = gen
                    self.last_logprobs[uid] = logp[i, :n]
                    drafted_total += len(drafted)
                    accepted_total += n - 1
                    per_uid[uid] = (len(drafted), n - 1)
                self.last_spec = {
                    "drafted": drafted_total, "accepted": accepted_total,
                    "per_uid": per_uid,
                }
                return results

            return outputs, finish, {"rows": len(uids), "k": k}

        return self._collect(self._dispatch(dispatch))

    def put(self, batch_uids, batch_tokens) -> Dict[int, np.ndarray]:
        """Submit new sequences (reference put :107) and run ONE engine step.
        Returns {uid: logits} for sequences whose scheduled tokens completed a
        prompt or decode step this round."""
        for uid, toks in zip(batch_uids, batch_tokens):
            self.scheduler.submit(uid, toks)
        return self.step()

    def step(self) -> Dict[int, np.ndarray]:
        """One engine step: the scheduler's packed batch advances in a single
        device call (multi-sequence decode + prompt chunks fused). Returns
        host logits (the reference's ``put`` contract); the caller feeds a
        token of its own choosing back (``scheduler.feedback``)."""
        return _materialize_rows(self._launch_batch()[0])

    def launch_step(self) -> StepInFlight:
        """The first half of a served step: schedule, stage and launch the
        split step, nothing waited for. Takes the IN-PROGRAM sampled token
        (greedy or sampled per the engine's static sampling config), never a
        host argmax."""

        def dispatch():
            _, rows, last = self._launch_batch()

            def finish():
                host = np.asarray(last) if rows else ()
                return {uid: int(host[slot]) for uid, slot in rows.items()}

            # a step that launched nothing (no batch) has nothing to wait on
            return ([] if last is None else [last]), finish, {
                "rows": len(rows), "tokens": self.last_step.scheduled_tokens,
                "grid_slots": self.last_step.grid_slots}, rows

        return self._dispatch(dispatch)

    def launch_ahead(self, prev: Optional[StepInFlight], wants) -> Optional[StepInFlight]:
        """ONE STEP IN FLIGHT, the order ``generate()`` and the serving core
        both keep: launch step n+1 while step n (``prev``; None after an idle
        loop) is still uncollected, THEN ``collect_step(prev)``. Every row of
        ``prev`` whose sequence ``wants(uid)`` another token is handed on with
        its token where it is, on the device (``scheduler.expect``: the
        program reads it from the previous one's ``last_tokens``); a row whose
        token in flight is its last by length, or whose owner is gone, is not
        (no row-step is wasted there). Returns the step launched, or None
        where the scheduler then holds no work. The caller collects ``prev``
        next and feeds every token back or finishes its sequence; a row that
        stops on a token only the collect shows (EOS, a cancel) has its
        successor in flight already, and the caller drops that one's token."""
        if prev is not None:
            for uid, slot in prev.rows.items():
                if wants(uid):
                    self.scheduler.expect(uid, slot)
        if not self.scheduler.has_work():
            return None
        flight = self.launch_step()
        flight.stats.ahead = prev is not None and bool(flight.waited)
        return flight

    def collect_step(self, flight: StepInFlight) -> Dict[int, int]:
        """The second half: wait for ``flight``'s own outputs and return
        ``{uid: next-token int}`` for the rows that completed a prompt or a
        decode token (``{}`` for a step that completed none: waited for all
        the same)."""
        return self._collect(flight)

    def step_tokens(self) -> Dict[int, int]:
        """One served step, waited for where it is launched:
        ``collect_step(launch_step())``. What ``warm_*``, the probe and
        ``analysis/verify.py`` drive, and a serving core that cannot run one
        step ahead."""
        return self.collect_step(self.launch_step())

    def _launch_batch(self):
        """Schedule, stage the batch (_stage_split), run ONE compiled
        program; sync-free. Returns (``{uid: (logits array, row)}`` for the
        rows that completed, ``{uid: slot of last_tokens}`` for the same
        rows, the program's ``last_tokens``); ({}, {}, None) when the
        scheduler had no batch."""
        tr = get_tracer()
        with tr.span("engine.schedule", track=getattr(self, "_trace_name", "engine")):
            batch = self.scheduler.next_batch()
            self.last_capped |= self.scheduler.drain_capped()
        self.last_step = StepStats()
        self._moe_pending = self._enqueued = None
        if batch is None:
            return {}, {}, None
        dec_rows = [
            (uid, toks, start, src)
            for uid, toks, start, src, dec in zip(
                batch.uids, batch.tokens, batch.start_positions, batch.token_src,
                batch.is_decode,
            )
            if dec
        ]
        chk_rows = [
            (uid, toks, start, chunked)
            for uid, toks, start, chunked, dec in zip(
                batch.uids, batch.tokens, batch.start_positions,
                batch.is_prompt_chunk, batch.is_decode,
            )
            if not dec
        ]
        logits_dec, logits_chk, _, _, self._last_tokens = self._start(
            self._stage_split, batch.total_tokens, dec_rows, chk_rows)
        # rows are referenced as (logits array, row index): slicing
        # logits_dec[i] here would issue one tiny device op per completed row
        # per step. step() materializes each ARRAY once.
        R = self.config.state_manager.max_ragged_sequence_count
        results: Dict[int, tuple] = {}
        slots: Dict[int, int] = {}
        for i, (uid, toks, _start, _src) in enumerate(dec_rows):
            seq = self.state_manager.get_sequence(uid)
            seq.seen_tokens += len(toks)
            results[uid] = (logits_dec, i)
            slots[uid] = i
        for j, (uid, toks, _start, chunked) in enumerate(chk_rows):
            seq = self.state_manager.get_sequence(uid)
            seq.seen_tokens += len(toks)
            if not chunked:  # prompt complete: last-token logits usable
                results[uid] = (logits_chk, j)
                slots[uid] = R + j
        return results, slots, self._last_tokens

    def generate(self, prompts, max_new_tokens: int = 32, eos_token_id: Optional[int] = None):
        """Serve a list of prompts to completion through the served step,
        one step in flight (``launch_ahead`` then ``collect_step``, as the
        serving core drives it), the tokens the programs sample (greedy or
        sampled per the static config). Returns a list of np arrays (prompt +
        generated), a row cut at ``max_new_tokens``, after ``eos_token_id``,
        or where the scheduler capped it (``last_capped``)."""
        sched = self.scheduler
        uids = list(range(len(prompts)))
        for uid, p in zip(uids, prompts):
            sched.submit(uid, p)
        # tokens a row may still take; 0 once it has stopped
        remaining = {uid: max_new_tokens for uid in uids}
        outputs = {uid: list(np.asarray(p, np.int32).reshape(-1)) for uid, p in zip(uids, prompts)}
        self.last_capped = set()
        prev = None
        while prev is not None or sched.has_work():
            flight = self.launch_ahead(prev, lambda uid: remaining[uid] > 1)
            if flight is not None and not flight.waited:
                flight = None  # no batch: rows wait on KV blocks
            if prev is None and flight is None and sched.has_work():
                # Liveness: nothing is in flight and nothing was scheduled, so
                # no call made here can change scheduler state — fail loudly
                # instead of busy-looping (e.g. KV pool too fragmented for any
                # pending prompt with no running sequence left to free blocks).
                raise RuntimeError(
                    "scheduler deadlock: work pending but nothing schedulable "
                    f"(free KV blocks={self.state_manager.free_blocks}); "
                    "increase kv_cache.num_blocks or reduce concurrency"
                )
            for uid, tok in (self.collect_step(prev) if prev is not None else {}).items():
                if remaining[uid] <= 0:
                    continue  # stopped on EOS while this step was in flight
                outputs[uid].append(tok)
                remaining[uid] -= 1
                if eos_token_id is not None and tok == eos_token_id:
                    remaining[uid] = 0
                if remaining[uid] <= 0:
                    sched.finish(uid)
                else:
                    sched.feedback(uid, tok)
            prev = flight
        return [np.asarray(outputs[uid], np.int32) for uid in uids]
