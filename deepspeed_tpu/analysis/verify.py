"""Tier-B compile-time verifier: donation-alias coverage + recompile counts.

Tier A's ``donate-arity`` rule proves the *indices* line up with the
signature; this module proves the *compiled artifact* actually aliases
every declared donated buffer. It lowers the repo's jitted entry points on
CPU with representative (tiny-model) arguments and checks, per declared
donated input, that the lowered module carries ``tf.aliasing_output`` for
it — the annotation XLA turns into ``input_output_alias``. A donated
buffer that fails to alias (shape/dtype drifted from the output, or the
index points at the wrong argument) is a silent full-buffer copy per step:
the exact class of bug the split-step's ``donate_argnums=(13, 14)``
off-by-one would have been.

Aliasing is necessary, not sufficient: a step whose layer loop both reads
the step-start pool and scatters into the carried pool aliases every donated
buffer and still copies each pool twice (into a second buffer before the
loop, back after it). ``check_pool_copies`` reads the COMPILED module of
the split step and the verify step and fails on any
``copy`` the size of a KV pool or a scale plane.

It also counts retraces: a fixed-shape entry point that traces more than
once across representative same-shape calls is quietly recompiling on the
hot path (weak-typed scalars, python-hash-unstable statics, ...).

Entry points covered (the compiled hot paths every perf PR leans on):
  * ``engine_v2`` split step (a chunk bucket and the decode-only shape),
    speculative verify step
  * ``runtime.engine`` fused ZeRO-3 train step (bucketed-collective overlap)
  * ``runtime.streamed_adam`` per-leaf donated update
  * quantized-collective variants: TP decode through the int8 psum islands,
    pipelined train step through int8 ppermute activation sends
  * tiled-overlap variants (``comm_overlap="tiled"``): tp2 decode through
    the per-tile ppermute rings, ZeRO-3 train step through tiled
    prefetch-bucket all-gathers
  * tiered-KV readmit (``import_kv_blocks_chunked``): the double-buffered
    host→HBM window scatter, bf16 and int8 pools

Run via ``dstpu lint --verify`` (wired into tools/run_smoke.sh).
"""

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "CheckResult",
    "DonatedBuffer",
    "check_donation",
    "check_pool_copies",
    "check_recompile",
    "run_verify",
    "verify_disagg",
    "verify_elastic",
    "verify_engine_v2",
    "verify_host_tier",
    "verify_lock_order",
    "verify_quantized_comm",
    "verify_ring_train",
    "verify_splash",
    "verify_streamed_adam",
    "verify_tiled_overlap",
    "verify_train_engine",
]


@dataclass
class DonatedBuffer:
    flat_index: int
    shape: Tuple[int, ...]
    dtype: str
    aliased: bool

    def render(self) -> str:
        mark = "aliased" if self.aliased else "NOT ALIASED"
        return f"arg[{self.flat_index}] {self.dtype}{list(self.shape)}: {mark}"


@dataclass
class CheckResult:
    name: str
    kind: str  # "donation" | "recompile" | "pool-copy"
    ok: bool
    detail: str = ""
    buffers: List[DonatedBuffer] = field(default_factory=list)

    def render(self) -> str:
        status = "ok" if self.ok else "FAIL"
        line = f"[{status}] {self.kind}: {self.name}"
        if self.detail:
            line += f" — {self.detail}"
        return line

    def to_dict(self) -> dict:
        return {
            "name": self.name, "kind": self.kind, "ok": self.ok,
            "detail": self.detail,
            "buffers": [
                {"flat_index": b.flat_index, "shape": list(b.shape),
                 "dtype": b.dtype, "aliased": b.aliased}
                for b in self.buffers
            ],
        }


# ---------------------------------------------------------------------------
# core checks
# ---------------------------------------------------------------------------
# an optimized-HLO copy: "%copy.3 = f32[2,129,4,4,32]{4,3,2,1,0} copy(%k_cache.1)"
_COPY_RE = re.compile(r"(%[\w.\-]+) = (\w+)\[([\d,]*)\]\S* copy\(([^)]*)\)")
_HLO_DTYPES = {"float32": "f32", "bfloat16": "bf16", "float16": "f16", "int8": "s8"}


def _alias_positions(lowered_text: str) -> Dict[int, bool]:
    """Lowered-module position -> carries tf.aliasing_output. Positions are
    the KEPT flat inputs in order (jit drops unused arguments)."""
    try:
        sig = lowered_text.split("@main(", 1)[1]
    except IndexError:
        return {}
    end = sig.find(") ->")
    if end == -1:
        end = sig.find(")")
    sig = sig[:end]
    out = {}
    # Split on the argument markers instead of regex-matching each attr
    # dict: attr values (mhlo.sharding strings under a mesh) contain nested
    # braces a non-recursive pattern cannot span.
    parts = re.split(r"%arg(\d+):", sig)
    for i in range(1, len(parts) - 1, 2):
        out[int(parts[i])] = "tf.aliasing_output" in parts[i + 1]
    return out


def _donor_positions(lowered_text: str) -> Dict[int, bool]:
    """Lowered-module position -> carries ``jax.buffer_donor``. Under
    committed input shardings (TP engines, mesh train steps) jit defers the
    donated-input → output match to XLA and emits this attribute instead of
    ``tf.aliasing_output``; the lowering text alone under-reports donation
    there."""
    try:
        sig = lowered_text.split("@main(", 1)[1]
    except IndexError:
        return {}
    end = sig.find(") ->")
    if end == -1:
        end = sig.find(")")
    sig = sig[:end]
    out = {}
    parts = re.split(r"%arg(\d+):", sig)
    for i in range(1, len(parts) - 1, 2):
        out[int(parts[i])] = "jax.buffer_donor" in parts[i + 1]
    return out


def _compiled_alias_params(lowered) -> set:
    """Parameter indices XLA actually aliased, from the compiled module's
    ``input_output_alias`` header — the ground truth the buffer-donor path
    resolves to at compile time."""
    try:
        hlo = lowered.compile().as_text()
    except Exception:
        return set()
    start = hlo.find("input_output_alias={")
    if start == -1:
        return set()
    i = start + len("input_output_alias=")
    depth = 0
    block = ""
    for j in range(i, len(hlo)):
        ch = hlo[j]
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
            if depth == 0:
                block = hlo[i:j + 1]
                break
    return {int(m) for m in re.findall(r"\((\d+),", block)}


def _arg_info(lowered):
    """Flat (donated, shape, dtype) per input, in flattening order."""
    import jax

    leaves = jax.tree_util.tree_leaves(lowered.args_info)
    out = []
    for ai in leaves:
        shape = tuple(getattr(ai, "shape", ()) or ())
        dtype = str(getattr(ai, "dtype", "?"))
        out.append((bool(ai.donated), shape, dtype))
    return out


def _kept_indices(lowered, n_flat: int) -> List[int]:
    kept = None
    try:
        kept = lowered._lowering.compile_args.get("kept_var_idx")
    except AttributeError:
        pass
    return sorted(kept) if kept is not None else list(range(n_flat))


def check_donation(name: str, jitted, args: Sequence, kwargs: Optional[dict] = None,
                   lowered=None) -> CheckResult:
    """Lower ``jitted(*args)`` and verify every declared donated input is
    aliased to an output in the lowered module."""
    import warnings

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        low = lowered if lowered is not None else jitted.lower(*args, **(kwargs or {}))
        info = _arg_info(low)
        text = low.as_text()
        alias_by_pos = _alias_positions(text)
        donor_by_pos = _donor_positions(text)
    kept = _kept_indices(low, len(info))
    pos_of = {flat: pos for pos, flat in enumerate(kept)}

    buffers = []
    compiled_alias = None  # lazy: only compiled when a buffer-donor arg shows up
    via_donor = 0
    for i, (donated, shape, dtype) in enumerate(info):
        if not donated:
            continue
        pos = pos_of.get(i)
        aliased = pos is not None and alias_by_pos.get(pos, False)
        if not aliased and pos is not None and donor_by_pos.get(pos, False):
            if compiled_alias is None:
                compiled_alias = _compiled_alias_params(low)
            aliased = pos in compiled_alias
            via_donor += aliased
        buffers.append(DonatedBuffer(i, shape, dtype, aliased))

    missing = [b for b in buffers if not b.aliased]
    notes = [str(w.message).splitlines()[0] for w in caught
             if "donated" in str(w.message).lower()]
    if not buffers:
        return CheckResult(name, "donation", False,
                           "no donated inputs declared — donation annotation lost", buffers)
    if missing:
        detail = "; ".join(b.render() for b in missing)
        if notes:
            detail += " | " + "; ".join(notes)
        return CheckResult(name, "donation", False, detail, buffers)
    detail = f"{len(buffers)} donated buffer(s) all aliased"
    if via_donor:
        detail += f" ({via_donor} resolved via XLA buffer-donor)"
    return CheckResult(name, "donation", True, detail, buffers)


def check_pool_copies(name: str, jitted, args: Sequence, pools: Sequence,
                      lowered=None) -> CheckResult:
    """Compile ``jitted(*args)`` and fail on every ``copy`` instruction whose
    result is as large as one of ``pools`` (arrays or shape structs: the KV
    pools, for int8 their scale planes, for a model with DeltaNet layers its
    recurrent-state and conv pools) in that pool's dtype. Sizes are
    compared by element count, so a copy of a reshaped view counts too. A
    serving step moves the tokens of one step; a copy the size of the pool
    is XLA resolving a buffer that is read as an invariant and written as a
    carry in one loop (PERF.md, PR 24), and costs the same whatever the
    step holds."""
    import math

    low = lowered if lowered is not None else jitted.lower(*args)
    hlo = low.compile().as_text()
    sizes = {(_HLO_DTYPES.get(str(p.dtype), str(p.dtype)), math.prod(p.shape))
             for p in pools}
    found = []
    for m in _COPY_RE.finditer(hlo):
        dims = [int(d) for d in m.group(3).split(",") if d]
        if (m.group(2), math.prod(dims)) in sizes:
            found.append(f"{m.group(1)} = {m.group(2)}[{m.group(3)}] copy({m.group(4)})")
    if found:
        return CheckResult(name, "pool-copy", False,
                           f"{len(found)} pool-sized copy(ies): " + "; ".join(found))
    return CheckResult(name, "pool-copy", True,
                       f"no copy the size of any of {len(sizes)} pool shape(s)")


def check_recompile(name: str, jitted, max_traces: int = 1) -> CheckResult:
    """A fixed-shape entry point must trace once across representative
    calls; every extra cache entry is a silent recompile on the hot path."""
    try:
        n = jitted._cache_size()
    except AttributeError:
        return CheckResult(name, "recompile", True, "cache size unavailable; skipped")
    ok = n <= max_traces
    return CheckResult(
        name, "recompile", ok,
        f"{n} compiled variant(s) after representative calls (max {max_traces})")


# ---------------------------------------------------------------------------
# entry-point harnesses (tiny models, CPU)
# ---------------------------------------------------------------------------
def _capture_builder(obj, attr: str, store: dict, key):
    """Shadow a lazy jit-builder method on one instance so the first real
    call records (compiled_fn, concrete_args) without changing behavior.
    ``key``: the name to record under, or a function of the builder's
    arguments that gives it (one builder, a program a shape)."""
    orig = getattr(obj, attr)

    def build(*bargs, **bkw):
        fn = orig(*bargs, **bkw)
        name = key(*bargs, **bkw) if callable(key) else key

        def call(*args):
            store.setdefault(name, (fn, args))
            return fn(*args)

        return call

    setattr(obj, attr, build)


def _tiny_model_config(model: str = "dense"):
    """A tiny model of each kind of cache the serving engine keeps."""
    from deepspeed_tpu.models import get_config

    if model == "gdn":
        # the second kind of cache: two periods of one Gated DeltaNet layer
        # and one attention layer, so that the loop over periods is a loop
        return get_config(
            "tiny", n_layers=4, dtype="float32", max_seq_len=512,
            layer_kinds=("gdn", "full") * 2, gdn_key_heads=2, gdn_value_heads=4,
            gdn_key_dim=16, gdn_value_dim=16)
    if model == "window":
        # ... or a window pool: window and global layers in one stack
        return get_config(
            "tiny", n_layers=4, dtype="float32", max_seq_len=512,
            sliding_window=6, attn_layer_pattern=(1, 1, 1, 0))
    if model == "geometry":
        # ... two pools each of its OWN geometry (mimo_v2_flash): window layers
        # of 4 KV heads with a sink beside global ones of 2, keys of 24 (stored
        # a token a row) against values of 16, behind a dense lead layer
        return get_config(
            "tiny", n_layers=4, dtype="float32", max_seq_len=512, n_kv_heads=2,
            head_dim_override=24, v_head_dim=16, window_kv_heads=4, attn_sink_window=True,
            window_rope_theta=1e4, rope_frac=0.334, attn_value_scale=0.707, sliding_window=6,
            attn_layer_pattern=(0, 1, 1, 0), n_experts=4, moe_top_k=2, moe_drop_tokens=False,
            moe_dense_lead=1, moe_score="sigmoid")
    if model == "latent":
        # ... or a latent pool of one plane, behind a dense lead layer (an
        # unrolled stack) and grouped experts
        return get_config(
            "tiny", n_layers=3, dtype="float32", max_seq_len=512, head_dim_override=24,
            kv_lora_rank=16, q_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
            rope_interleave=True, n_experts=8, moe_top_k=2, moe_drop_tokens=False,
            moe_dense_lead=1, moe_score="sigmoid", moe_router_bias=False, moe_n_group=4,
            moe_topk_group=2)
    if model == "planes":
        # ... or two planes a layer: a layer of two latent-attention sub-blocks
        # with the expert block on a shortcut across them (longcat_flash), a
        # looped stack whose sub-block stacks are indexed at 2 li + i
        return get_config(
            "tiny", n_layers=2, dtype="float32", max_seq_len=512, head_dim_override=24,
            kv_lora_rank=16, q_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
            rope_interleave=True, latent_q_scale=2.0, latent_kv_scale=2.8, moe_shortcut=True,
            n_experts=4, moe_experts_total=8, moe_zero_experts=4, moe_top_k=3,
            moe_drop_tokens=False, moe_norm_topk_prob=False, moe_routed_scale=6.0)
    return get_config("tiny", n_layers=2, dtype="float32", max_seq_len=512)


def _split_program(shape) -> str:
    """The name of the split step's program of ``shape`` in a capture: its
    decode-only shape apart from those that carry a prompt chunk."""
    return "decode_only_step" if shape == (0, 0) else "split_step"


def _tiny_v2_engine(kv_dtype: str = "bf16", kv_extra: Optional[dict] = None,
                    model: str = "dense"):
    import jax

    from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import init_params

    cfg = _tiny_model_config(model)
    params = init_params(cfg, jax.random.key(0))
    kv = {"block_size": 4, "num_blocks": 128, "max_blocks_per_seq": 32,
          "kv_cache_dtype": kv_dtype}
    kv.update(kv_extra or {})
    rc = RaggedInferenceEngineConfig.from_dict({
        "dtype": "float32",
        # two chunk rows a step at most, so the split step has a one-row shape
        # and a two-row shape beside its decode-only one
        "prompt_chunk": 128, "max_prompt_chunks": 2,
        "kv_cache": kv,
        "state_manager": {"max_tracked_sequences": 16,
                          "max_ragged_batch_size": 256,
                          "max_ragged_sequence_count": 4, "max_context": 256},
    })
    return cfg, InferenceEngineV2(cfg, params, rc)


def _engine_v2_programs(kv_dtype: str, model: str = "dense"):
    """The v2 serving programs of a tiny engine with a ``kv_dtype`` pool:
    (engine, {name: (jitted, args)}). The split step's two-row shape (the
    passes' two prompts go in one step) and its decode-only shape are
    captured from two same-shape ``generate()`` passes (pass 1 traces, pass 2
    must hit the caches); the verify step is lowered directly with the
    inputs of an empty step, the split step's one-row
    shape with those of a step that holds one prompt (lowering reads shapes
    only, so passing the live pools is safe). Every program takes
    ``(params, inputs, rng, temperature,
    pools)`` and donates ``pools`` whole: int8 adds the scale planes as two
    more leaves of it, ``model="gdn"`` (a model with Gated DeltaNet layers)
    the recurrent-state and conv pools, ``model="window"`` (window and global
    layers in one stack) the window pools; such models have no verify step
    (the engine refuses it); ``model="latent"`` (latent attention) has one
    plane, ``model="planes"`` two a layer, and no verify step."""
    import jax.numpy as jnp
    import numpy as np

    cfg, eng = _tiny_v2_engine(kv_dtype=kv_dtype, model=model)
    programs: dict = {}
    _capture_builder(eng, "_build_split_step", programs, _split_program)
    for seed in (0, 1):
        rng = np.random.default_rng(seed)
        eng.generate([rng.integers(1, cfg.vocab_size, size=(12,)).astype(np.int32)
                      for _ in range(2)], max_new_tokens=6)

    def staged(build, inputs):
        return build, (
            eng.params,
            {name: jnp.asarray(a) for name, a in inputs.items()},
            eng._rng,
            jnp.float32(1.0),
            eng._pools(),
        )

    # the split step of a batch with ONE chunk row where the scheduler could
    # have cut two: the grid follows the batch, so this is a program of its own
    # (the class's builder: the instance's is shadowed by the capture above)
    eng.scheduler.submit(0, np.arange(1, 13, dtype=np.int32))
    batch = eng.scheduler.next_batch()
    (_, shape), inputs = eng._stage_split(batch.total_tokens, [], [
        (batch.uids[0], batch.tokens[0], batch.start_positions[0], batch.is_prompt_chunk[0])])
    eng.scheduler.finish(0)
    programs["one_row_step"] = staged(type(eng)._build_split_step(eng, shape), inputs)
    # speculative verify step (serving/spec): the K+1-token draft-and-verify
    # program declares the pools donated — without aliasing, every spec
    # round would copy the whole paged pool, erasing the subsystem's win.
    # Its inputs are what the engine stages for a round with no row.
    if not eng._beside and not eng._latent:
        _, inputs = eng._stage_verify([], [], 4)
        programs["verify_step"] = staged(eng._build_verify_step(4), inputs)
    return eng, programs


def _engine_v2_pass(kv_dtype: str, model: str = "dense") -> List[CheckResult]:
    """One donation / pool-copy / recompile sweep over the v2 serving
    programs for a pool payload dtype. int8 mode adds the fp32 scale planes
    to the donated pools argument of every step, so both dtypes get the
    full sweep."""
    tag = "".join(f"[{t}]" for t in (kv_dtype, model) if t not in ("bf16", "dense"))
    results: List[CheckResult] = []
    eng, programs = _engine_v2_programs(kv_dtype, model)
    for key in ("split_step", "decode_only_step", "one_row_step", "verify_step"):
        if key == "verify_step" and eng._beside:
            continue  # refused at build: a rejected draft would need the second cache rolled back
        if key == "verify_step" and eng._latent:
            continue  # refused at build: it has no absorbed form
        label = f"engine_v2.{key}{tag}"
        if key not in programs:
            results.append(CheckResult(label, "donation", False,
                                       "entry point never executed in harness"))
            continue
        fn, args = programs[key]
        lowered = fn.lower(*args)
        results.append(check_donation(label, fn, args, lowered=lowered))
        if not eng._latent:
            # (a latent pool is written by a kernel on the chip; off it XLA's
            # scatter of a column transposes the pool, which is this check's
            # finding and no news: the program compiled for a described v5e is
            # held to 0 pool-sized copies in tests/unit/ops/test_latent_attention.py)
            results.append(check_pool_copies(label, fn, args, eng._pools(), lowered=lowered))
        if key in ("split_step", "decode_only_step"):  # the captured, live jits
            results.append(check_recompile(label, fn))
    return results


def verify_engine_v2() -> List[CheckResult]:
    # both pool payload dtypes: int8 adds donated scale-plane leaves to
    # every serving program (split, verify)
    # ... a model with DeltaNet layers the state pools, and one that mixes
    # window and global layers the window pools
    # (and one whose two pools differ in KV heads and whose planes in width)
    # ... and a latent-attention model its pool of one plane, or of two a layer
    return (_engine_v2_pass("bf16") + _engine_v2_pass("int8") + _engine_v2_pass("bf16", "gdn")
            + _engine_v2_pass("bf16", "window") + _engine_v2_pass("bf16", "geometry")
            + _engine_v2_pass("bf16", "latent") + _engine_v2_pass("bf16", "planes"))


def verify_streamed_adam() -> List[CheckResult]:
    import jax.numpy as jnp

    from deepspeed_tpu.runtime.streamed_adam import StreamedAdamW

    opt = StreamedAdamW(chunk_elems=64, overlap=True)
    fn = opt._leaf_jit(quantized=False)

    def args():
        # param is bf16 as in real training: with an fp32 param the updated
        # param equals the fp32 master output bit-for-bit, XLA emits one
        # tensor for both outputs, and only one donated input can back it.
        return (
            jnp.zeros((128,), jnp.float32),    # grad
            jnp.ones((128,), jnp.float32),     # master
            jnp.zeros((128,), jnp.float32),    # mu
            jnp.zeros((128,), jnp.float32),    # nu
            jnp.ones((128,), jnp.bfloat16),    # param
            jnp.float32(1e-3),
            jnp.int32(1),
        )

    results = [check_donation("streamed_adam.leaf_step", fn, args())]
    fn(*args())
    fn(*args())
    results.append(check_recompile("streamed_adam.leaf_step", fn))
    return results


def _mlp_loss(params, batch):
    import jax
    import jax.numpy as jnp

    h = batch["x"]
    n = len(params)
    for i in range(n):
        layer = params[f"layer_{i}"]
        h = h @ layer["w"] + layer["b"]
        if i < n - 1:
            h = jax.nn.relu(h)
    return jnp.mean(jnp.square(h - batch["y"]))


def verify_train_engine() -> List[CheckResult]:
    """ZeRO-3 + bucketed-collective overlap train step (the runtime/zero/
    overlap.py machinery) on a W-way virtual CPU mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu

    W = 8 if len(jax.devices()) >= 8 else 1
    key = jax.random.key(0)
    keys = jax.random.split(key, 2)
    params = {
        f"layer_{i}": {
            "w": (jax.random.normal(keys[i], (16, 16)) * 0.1).astype(jnp.float32),
            "b": jnp.zeros((16,), jnp.float32),
        }
        for i in range(2)
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=_mlp_loss,
        model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": 8,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 3, "param_persistence_threshold": 0},
            "mesh": {"data": W},
            "steps_per_print": 10**9,
        },
    )
    captured: dict = {}
    _capture_builder(engine, "_build_train_step", captured, "train_step")

    rng = np.random.default_rng(0)

    def batch():
        x = rng.normal(size=(8 * W, 16)).astype(np.float32)
        return {"x": x, "y": (x * 0.5).astype(np.float32)}

    engine.train_batch(batch=batch())
    engine.train_batch(batch=batch())

    name = "runtime.engine.train_step[zero3+overlap]"
    results: List[CheckResult] = []
    if "train_step" not in captured:
        return [CheckResult(name, "donation", False,
                            "train step never executed in harness")]
    fn, args = captured["train_step"]
    results.append(check_donation(name, fn, args))

    # The first call traces against the engine's unsharded init params;
    # donation hands back zero3-sharded outputs, so call 2 legitimately
    # traces once more. Steady state = no cache growth after that warmup.
    try:
        warm = fn._cache_size()
    except AttributeError:
        results.append(CheckResult(name, "recompile", True,
                                   "cache size unavailable; skipped"))
        return results
    engine.train_batch(batch=batch())
    n = fn._cache_size()
    results.append(CheckResult(
        name, "recompile", n <= warm and warm <= 2,
        f"{n} compiled variant(s) at steady state "
        f"(warmup {warm}: trace 2 picks up the zero3-sharded donated outputs)"))
    return results


def verify_ring_train() -> List[CheckResult]:
    """Train step through the context-parallel ring attention path
    (ops/attention/sharded.ring_flash_attention) on a data×context virtual
    CPU mesh. The ring body runs inside shard_map with a custom_vjp whose
    residuals cross the shard boundary — exactly where a donated buffer can
    silently lose its alias (the XLA annotation must survive the shard_map
    lowering, not just the outer jit), so the donation check runs against
    the full sharded step artifact."""
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, init_params, make_loss_fn

    if len(jax.devices()) < 8:
        return [CheckResult("runtime.engine.train_step[ring-cp]", "donation",
                            True, "needs 8 devices; skipped")]
    cfg = TransformerConfig(
        vocab_size=64, hidden_size=32, n_layers=2, n_heads=4, max_seq_len=64,
        dtype="float32", attention_impl="flash_ring",
    )
    params = init_params(cfg, jax.random.key(0))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=make_loss_fn(cfg),
        model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0},
            "mesh": {"data": 2, "context": 4},
            "steps_per_print": 10**9,
        },
    )
    captured: dict = {}
    _capture_builder(engine, "_build_train_step", captured, "train_step")
    toks = np.random.default_rng(0).integers(0, 64, size=(4, 65)).astype(np.int32)
    engine.train_batch(batch={"input_ids": toks})
    engine.train_batch(batch={"input_ids": toks})

    name = "runtime.engine.train_step[ring-cp]"
    if "train_step" not in captured:
        return [CheckResult(name, "donation", False,
                            "train step never executed in harness")]
    fn, args = captured["train_step"]
    return [check_donation(name, fn, args)]


def verify_quantized_comm() -> List[CheckResult]:
    """Donation coverage for the ``comm_quant="int8"`` step artifacts: the
    serving TP decode programs routed through the quantized-psum shard_map
    islands, and the pipelined train step whose inter-stage activation sends
    ride ``quantized_ppermute``. Each quantized wire rebuilds its payload as
    int8 + fp32 block scales inside shard_map — fresh intermediates sitting
    next to the donated KV pools and grad buffers, exactly where an alias
    annotation can fail to survive the lowering — so both quantized steps
    get the full donation check against the compiled artifact."""
    import jax
    import numpy as np

    from deepspeed_tpu.parallel.topology import (
        Topology,
        reset_topology,
        set_topology,
    )

    if len(jax.devices()) < 8:
        return [CheckResult("quantized_comm", "donation", True,
                            "needs 8 devices; skipped")]

    results: List[CheckResult] = []

    # --- TP decode: int8 psum behind attention-out / MLP-down projections --
    from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import get_config, init_params

    reset_topology()
    try:
        set_topology(Topology(data=4, model=2))
        cfg = get_config("tiny", n_layers=2, dtype="float32", max_seq_len=512)
        params = init_params(cfg, jax.random.key(0))
        rc = RaggedInferenceEngineConfig.from_dict({
            "dtype": "float32",
            "tp_size": 2,
            "comm_quant": "int8",
            "kv_cache": {"block_size": 4, "num_blocks": 128,
                         "max_blocks_per_seq": 32},
            "state_manager": {"max_tracked_sequences": 16,
                              "max_ragged_batch_size": 256,
                              "max_ragged_sequence_count": 4,
                              "max_context": 256},
        })
        eng = InferenceEngineV2(cfg, params, rc)
        captured: dict = {}
        _capture_builder(eng, "_build_split_step", captured, _split_program)

        def prompts(seed):
            rng = np.random.default_rng(seed)
            return [rng.integers(1, cfg.vocab_size, size=(12,)).astype(np.int32)
                    for _ in range(2)]

        eng.generate(prompts(0), max_new_tokens=6)
        eng.generate(prompts(1), max_new_tokens=6)
        # call 1 traces against host arrays; donation hands back sharded
        # outputs, so call 2 legitimately traces once more (same warmup as
        # verify_train_engine). Steady state = no growth on pass 3.
        warm = {k: v[0]._cache_size() for k, v in captured.items()
                if hasattr(v[0], "_cache_size")}
        eng.generate(prompts(2), max_new_tokens=6)
        for key, label in (
            ("split_step", "engine_v2.split_step[tp2+commq8]"),
            ("decode_only_step", "engine_v2.decode_only_step[tp2+commq8]"),
        ):
            if key not in captured:
                results.append(CheckResult(
                    label, "donation", False,
                    "entry point never executed in harness"))
                continue
            fn, args = captured[key]
            results.append(check_donation(label, fn, args))
            if key not in warm:
                results.append(CheckResult(label, "recompile", True,
                                           "cache size unavailable; skipped"))
                continue
            n = fn._cache_size()
            results.append(CheckResult(
                label, "recompile", n <= warm[key] and warm[key] <= 2,
                f"{n} compiled variant(s) at steady state "
                f"(warmup {warm[key]}: trace 2 picks up the sharded donated "
                "outputs)"))
    finally:
        reset_topology()

    # --- pipelined train step: int8 inter-stage activation sends -----------
    import deepspeed_tpu
    from deepspeed_tpu.runtime.pipe import (
        make_pipelined_loss_fn,
        pipeline_partition_specs,
    )

    try:
        topo = Topology(pipe=2, data=2, model=2)
        set_topology(topo)
        cfg = get_config("tiny", n_layers=4, dtype="float32", remat=False)
        params = init_params(cfg, jax.random.key(0))
        loss_fn = make_pipelined_loss_fn(cfg, micro_batches=2, topo=topo,
                                         comm_quant="int8")
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=loss_fn,
            model_parameters=params,
            mpu=topo,
            config={
                "train_batch_size": 4,
                "optimizer": {"type": "Adam", "params": {"lr": 5e-3}},
                "zero_optimization": {"stage": 1},
                "steps_per_print": 10**9,
            },
            param_specs=pipeline_partition_specs(cfg, topo),
        )
        captured2: dict = {}
        _capture_builder(engine, "_build_train_step", captured2, "train_step")
        toks = np.random.default_rng(0).integers(
            0, cfg.vocab_size, size=(4, 33)).astype(np.int32)
        engine.train_batch(batch={"input_ids": toks})
        engine.train_batch(batch={"input_ids": toks})

        name = "runtime.engine.train_step[pipe2+commq8]"
        if "train_step" not in captured2:
            results.append(CheckResult(name, "donation", False,
                                       "train step never executed in harness"))
        else:
            fn, args = captured2["train_step"]
            results.append(check_donation(name, fn, args))
    finally:
        reset_topology()
    return results


def verify_tiled_overlap() -> List[CheckResult]:
    """Donation coverage for the ``comm_overlap="tiled"`` step artifacts:
    the tp2 serving decode whose row wires decompose into per-tile ppermute
    rings (comm/overlap_tiled.py), and the ZeRO-3 train step whose prefetch
    bucket all-gathers split into per-tile collectives. Each tile's ring
    builds fresh per-chunk intermediates inside shard_map right next to the
    donated KV pools / grad buffers — more lowering surface between the
    donation annotation and the compiled alias than the monolithic wire, so
    both tiled steps get the full donation check."""
    import jax
    import numpy as np

    from deepspeed_tpu.parallel.topology import (
        Topology,
        reset_topology,
        set_topology,
    )

    if len(jax.devices()) < 8:
        return [CheckResult("tiled_overlap", "donation", True,
                            "needs 8 devices; skipped")]

    results: List[CheckResult] = []

    # --- TP decode: per-tile rings behind attention-out / MLP-down ---------
    from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import get_config, init_params

    reset_topology()
    try:
        set_topology(Topology(data=4, model=2))
        cfg = get_config("tiny", n_layers=2, dtype="float32", max_seq_len=512)
        params = init_params(cfg, jax.random.key(0))
        rc = RaggedInferenceEngineConfig.from_dict({
            "dtype": "float32",
            "tp_size": 2,
            "comm_overlap": "tiled",
            "tp_overlap_tiles": 2,
            "kv_cache": {"block_size": 4, "num_blocks": 128,
                         "max_blocks_per_seq": 32},
            "state_manager": {"max_tracked_sequences": 16,
                              "max_ragged_batch_size": 256,
                              "max_ragged_sequence_count": 4,
                              "max_context": 256},
        })
        eng = InferenceEngineV2(cfg, params, rc)
        captured: dict = {}
        _capture_builder(eng, "_build_split_step", captured, _split_program)

        def prompts(seed):
            rng = np.random.default_rng(seed)
            return [rng.integers(1, cfg.vocab_size, size=(12,)).astype(np.int32)
                    for _ in range(2)]

        eng.generate(prompts(0), max_new_tokens=6)
        eng.generate(prompts(1), max_new_tokens=6)
        for key, label in (
            ("split_step", "engine_v2.split_step[tp2+tiled]"),
            ("decode_only_step", "engine_v2.decode_only_step[tp2+tiled]"),
        ):
            if key not in captured:
                results.append(CheckResult(
                    label, "donation", False,
                    "entry point never executed in harness"))
                continue
            fn, args = captured[key]
            results.append(check_donation(label, fn, args))
    finally:
        reset_topology()

    # --- ZeRO-3 train step: tiled prefetch-bucket all-gathers --------------
    import deepspeed_tpu
    import jax.numpy as jnp

    W = 8
    key = jax.random.key(0)
    keys = jax.random.split(key, 2)
    params = {
        f"layer_{i}": {
            "w": (jax.random.normal(keys[i], (16, 16)) * 0.1).astype(jnp.float32),
            "b": jnp.zeros((16,), jnp.float32),
        }
        for i in range(2)
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=_mlp_loss,
        model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": 8,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 3, "param_persistence_threshold": 0},
            "comm_overlap": "tiled",
            "tp_overlap_tiles": 2,
            "mesh": {"data": W},
            "steps_per_print": 10**9,
        },
    )
    captured2: dict = {}
    _capture_builder(engine, "_build_train_step", captured2, "train_step")
    rng = np.random.default_rng(0)

    def batch():
        x = rng.normal(size=(8 * W, 16)).astype(np.float32)
        return {"x": x, "y": (x * 0.5).astype(np.float32)}

    engine.train_batch(batch=batch())
    engine.train_batch(batch=batch())

    name = "runtime.engine.train_step[zero3+tiled]"
    if "train_step" not in captured2:
        results.append(CheckResult(name, "donation", False,
                                   "train step never executed in harness"))
    else:
        fn, args = captured2["train_step"]
        results.append(check_donation(name, fn, args))
    return results


def verify_disagg() -> List[CheckResult]:
    """Disaggregated serving: the Router's extracted scheduling loop must
    leave each engine's donated step programs intact. The prefill worker's
    split step and the decode replicas' decode steps both consume
    and reassign the donated KV pools, and the KV-handoff import path
    reassigns them too (``import_kv_blocks`` scatter) — a broken donation
    here would copy a full paged pool every step on every replica."""
    import numpy as np

    from deepspeed_tpu.serving.cluster import Router
    from deepspeed_tpu.serving.request import SamplingParams

    results: List[CheckResult] = []
    engines = [_tiny_v2_engine()[1] for _ in range(3)]
    captured: dict = {}
    _capture_builder(engines[0], "_build_split_step", captured, "split")
    for eng in engines[1:]:
        # both replicas store under one key; setdefault keeps the first
        _capture_builder(eng, "_build_split_step", captured, "decode")
    router = Router(engines=engines, num_prefill_workers=1).start()
    try:
        reqs = [
            router.submit(
                np.arange(1 + i, 13 + i, dtype=np.int32),
                params=SamplingParams(max_new_tokens=6, ignore_eos=True),
            )
            for i in range(4)
        ]
        for r in reqs:
            if not r.wait(300):
                raise RuntimeError("disagg verify request did not finish")
    finally:
        router.shutdown()
    for key, label in (("split", "disagg.prefill_split_step"),
                       ("decode", "disagg.decode_step")):
        if key not in captured:
            results.append(CheckResult(label, "donation", False,
                                       "entry point never executed under the router"))
            continue
        fn, args = captured[key]
        results.append(check_donation(label, fn, args))
        results.append(check_recompile(label, fn))
    return results


def verify_host_tier() -> List[CheckResult]:
    """Tiered-KV re-import (``engine_v2.import_kv_blocks_chunked``): the
    double-buffered window scatter must keep the pool donated (a lost alias
    copies the full paged pool once per window, per readmitted prefix) and
    must compile exactly once per plane family — the tail window pads its
    index vector with the trash row and zero-fills values precisely so the
    shapes never vary. bf16 pools scatter one (payload) shape; int8 pools
    add the fp32 scale-plane shape, so their steady state is two cache
    entries, not one."""
    import jax.numpy as jnp
    import numpy as np

    results: List[CheckResult] = []
    for kv_dtype, max_traces in (("bf16", 1), ("int8", 2)):
        tag = "" if kv_dtype == "bf16" else f"[{kv_dtype}]"
        label = f"engine_v2.kv_readmit{tag}"
        _, eng = _tiny_v2_engine(kv_dtype=kv_dtype, kv_extra={
            "prefix_cache": True,
            "host_tier_bytes": 1 << 20,
            "host_tier_chunk_blocks": 2,
        })
        blocks = [1, 2, 3, 4, 5]  # 5 blocks @ chunk 2 -> 3 windows, padded tail
        payload = eng.export_kv_blocks(blocks)
        # two identical chunked imports: pass 1 traces, pass 2 must hit the
        # cache — any growth is a per-window recompile on the readmit path
        eng.import_kv_blocks_chunked(blocks, payload, chunk_blocks=2)
        eng.import_kv_blocks_chunked(blocks, payload, chunk_blocks=2)
        fn = eng._kv_readmit_jit
        if fn is None:
            results.append(CheckResult(
                label, "donation", False,
                "chunked import never built the readmit scatter"))
            continue
        pool = eng._k_cache
        vals = jnp.zeros((pool.shape[0], 2) + tuple(pool.shape[2:]), pool.dtype)
        results.append(check_donation(
            label, fn, (pool, jnp.zeros((2,), jnp.int32), vals)))
        results.append(check_recompile(label, fn, max_traces=max_traces))
    return results


def verify_kv_transport() -> List[CheckResult]:
    """Zero-copy KV handoff wire (``export_kv_blocks_windows`` +
    ``import_kv_blocks_device``): the pipelined device transport must ride
    the SAME compiled programs as the host-tier readmit path — a fixed
    chunk-window export gather that traces once per plane family, and the
    donated ``_kv_readmit_jit`` scatter (a lost alias would copy the whole
    paged pool once per in-flight window, per handoff). The tp=2 leg
    re-lays each window onto the decode replica's head-sharded mesh via
    ``device_put`` before the scatter; the donated sharded import must
    still alias and must not retrace per window."""
    import jax
    import jax.numpy as jnp

    results: List[CheckResult] = []
    engines = {}  # kv_dtype -> tp1 engine, reused as the tp2 leg's source
    for kv_dtype, max_traces in (("bf16", 1), ("int8", 2)):
        tag = "" if kv_dtype == "bf16" else f"[{kv_dtype}]"
        _, eng = _tiny_v2_engine(kv_dtype=kv_dtype)
        engines[kv_dtype] = eng
        blocks = [1, 2, 3, 4, 5]  # 5 blocks @ chunk 2 -> 3 windows, padded tail
        # round 1 traces; round 2 (with a covered prefix, redirected to the
        # trash row — NOT a narrower scatter) must hit both caches
        wins, ch = eng.export_kv_blocks_windows(blocks, chunk_blocks=2)
        eng.import_kv_blocks_device(blocks, wins, ch)
        wins, ch = eng.export_kv_blocks_windows(blocks, chunk_blocks=2)
        eng.import_kv_blocks_device(blocks, wins, ch, skip_blocks=2)
        gather = eng._kv_export_jit
        if gather is None:
            results.append(CheckResult(
                f"engine_v2.kv_export{tag}", "recompile", False,
                "windowed export never built the gather"))
        else:
            results.append(check_recompile(
                f"engine_v2.kv_export{tag}", gather, max_traces=max_traces))
        fn = eng._kv_readmit_jit
        label = f"engine_v2.kv_device_import{tag}"
        if fn is None:
            results.append(CheckResult(
                label, "donation", False,
                "device import never built the readmit scatter"))
            continue
        pool = eng._k_cache
        vals = jnp.zeros((pool.shape[0], 2) + tuple(pool.shape[2:]), pool.dtype)
        results.append(check_donation(
            label, fn, (pool, jnp.zeros((2,), jnp.int32), vals)))
        results.append(check_recompile(label, fn, max_traces=max_traces))

    # --- tp=2 decode replica: head-sharded import off a tp=1 export --------
    if len(jax.devices()) < 8:
        results.append(CheckResult("kv_transport[tp2]", "donation", True,
                                   "needs 8 devices; skipped"))
        return results

    from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import get_config, init_params
    from deepspeed_tpu.parallel.topology import (
        Topology,
        reset_topology,
        set_topology,
    )

    reset_topology()
    try:
        set_topology(Topology(data=4, model=2))
        for kv_dtype, max_traces in (("bf16", 1), ("int8", 2)):
            tag = f"[tp2,{kv_dtype}]"
            cfg = get_config("tiny", n_layers=2, dtype="float32",
                             max_seq_len=512)
            params = init_params(cfg, jax.random.key(0))
            rc = RaggedInferenceEngineConfig.from_dict({
                "dtype": "float32",
                "tp_size": 2,
                "kv_cache": {"block_size": 4, "num_blocks": 128,
                             "max_blocks_per_seq": 32,
                             "kv_cache_dtype": kv_dtype},
                "state_manager": {"max_tracked_sequences": 16,
                                  "max_ragged_batch_size": 256,
                                  "max_ragged_sequence_count": 4,
                                  "max_context": 256},
            })
            dst = InferenceEngineV2(cfg, params, rc)
            src = engines[kv_dtype]  # tp=1 exporter (the prefill side)
            blocks = [1, 2, 3, 4, 5]
            wins, ch = src.export_kv_blocks_windows(blocks, chunk_blocks=2)
            dst.import_kv_blocks_device(blocks, wins, ch)
            wins, ch = src.export_kv_blocks_windows(blocks, chunk_blocks=2)
            dst.import_kv_blocks_device(blocks, wins, ch, skip_blocks=2)
            fn = dst._kv_readmit_jit
            label = f"engine_v2.kv_device_import{tag}"
            if fn is None:
                results.append(CheckResult(
                    label, "donation", False,
                    "sharded device import never built the readmit scatter"))
                continue
            pool = dst._k_cache
            vals = jax.device_put(
                jnp.zeros((pool.shape[0], 2) + tuple(pool.shape[2:]),
                          pool.dtype),
                dst._kv_sharding)
            results.append(check_donation(
                label, fn, (pool, jnp.zeros((2,), jnp.int32), vals)))
            results.append(check_recompile(label, fn, max_traces=max_traces))
    finally:
        reset_topology()
    return results


def verify_elastic() -> List[CheckResult]:
    """Elastic serving: a warm spare's ``warm_trace`` must cover EVERY step
    program the serving loop drives, so post-warm serving traffic — prefill,
    decode steps, and the preempt-checkpoint resume import — runs
    entirely inside the jit caches (zero admission-time compiles), and a
    preempted-then-resumed greedy stream must replay bit-identically to the
    uninterrupted one (content-addressed sampling + exact KV cursor
    restore)."""
    import numpy as np

    from deepspeed_tpu.serving.elastic import (
        WarmSparePool,
        assert_no_new_traces,
        preempt_sequence,
        resume_sequence,
    )

    results: List[CheckResult] = []

    # -- warm spare: serving-shaped traffic after warm_trace is compile-free
    # the spare's baseline holds every split shape: decode-only, and one
    # chunk row and two of them at its one bucket (prompt_chunk = 128)
    pool = WarmSparePool(
        factory=lambda: _tiny_v2_engine(kv_extra={"max_blocks_per_seq": 64})[1],
        count=1,
        warm_kw={"spec_k": 0},
    )
    eng, baseline = pool.acquire()
    sched = eng.scheduler
    uid = 7
    sched.submit(uid, np.arange(1, 13, dtype=np.int32))
    tok = None
    for _ in range(8):
        out = eng.step_tokens()
        if uid in out:
            tok = out[uid]
            break
    def decode(n):
        for _ in range(n):
            sched.feedback(uid, eng.step_tokens()[uid])

    sched.feedback(uid, tok)
    decode(6)
    label = "elastic.warm_spare"
    try:
        assert_no_new_traces(eng, baseline, label=label)
        results.append(CheckResult(
            label, "recompile", True,
            f"{len(baseline)} warmed program(s), zero new traces under "
            "serving traffic"))
    except RuntimeError as e:
        results.append(CheckResult(label, "recompile", False, str(e)))

    # -- preempt → resume on the warm engine: KV-cursor restore is exact
    # and the resumed stream continues the same greedy tokens; the resume
    # import must also stay inside the warmed caches
    seq = eng.state_manager.get_sequence(uid)
    pre_tokens = list(seq.tokens)
    ho = preempt_sequence(eng, uid)
    sched.finish(uid)
    resume_sequence(eng, ho)
    seq2 = eng.state_manager.get_sequence(uid)
    label = "elastic.preempt_resume"
    ok = (list(seq2.tokens) == pre_tokens
          and int(seq2.seen_tokens) == len(pre_tokens) - 1)
    results.append(CheckResult(
        label, "parity", ok,
        "checkpoint restored token history + KV cursor exactly" if ok
        else f"history/cursor drifted: {len(seq2.tokens)} tokens, "
             f"cursor {seq2.seen_tokens} (want {len(pre_tokens)} / "
             f"{len(pre_tokens) - 1})"))
    decode(4)
    label = "elastic.resume_no_retrace"
    try:
        assert_no_new_traces(eng, baseline, label=label)
        results.append(CheckResult(
            label, "recompile", True,
            "resume import + post-resume decode hit the warmed caches"))
    except RuntimeError as e:
        results.append(CheckResult(label, "recompile", False, str(e)))
    sched.finish(uid)

    # the warmed split program itself must be single-trace per shape
    for (kind, shape), fn in eng._programs.items():
        if kind == "split":
            results.append(check_recompile(f"elastic.split_step[rows, tq={shape}]", fn))
    return results


def verify_lock_order() -> List[CheckResult]:
    """Lock discipline, both halves (see ``analysis/locks.py`` +
    ``analysis/lockwitness.py``): the static whole-tree acquisition graph
    must be acyclic with no reentrancy hazards, and the chaos smoke
    scenario — the nastiest concurrent path the repo has (worker kill
    mid-stream, faulted handoff import, faulted peer pull, recovery +
    probation on a 2-replica router) — run under the runtime witness must
    observe no inversion and no acquisition order the static model does
    not declare. A subgraph failure means either the model's inference
    misses a call path (annotate it) or the code broke the documented
    hierarchy (docs/ANALYSIS.md)."""
    import os
    import sys

    from deepspeed_tpu.analysis import locks
    from deepspeed_tpu.analysis.lockwitness import (
        LockOrderViolation,
        witness_locks,
    )

    results: List[CheckResult] = []
    pkg_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    model = locks.build_model_from_paths([pkg_dir])

    cycles = model.cycles()
    hazards = model.reentrant_hazards
    static_ok = not cycles and not hazards
    detail = (f"{len(model.order_edges)} acquisition edge(s), acyclic, "
              f"no reentrancy hazards")
    if cycles:
        detail = "cycle(s): " + "; ".join(
            " -> ".join(c + [c[0]]) for c in cycles)
    elif hazards:
        detail = "reentrancy hazard(s): " + "; ".join(
            f"{key} at {site.path}:{site.line} ({why})"
            for key, site, why in hazards)
    results.append(CheckResult("lock_model.static", "lock-order",
                               static_ok, detail))

    # the runtime half replays the chaos gate's scenario; it needs the test
    # fixtures importable (repo root on sys.path — true under run_smoke.sh
    # and pytest, restored if this runs from an installed copy)
    repo_root = os.path.dirname(pkg_dir)
    added = repo_root not in sys.path
    if added:
        sys.path.insert(0, repo_root)
    try:
        import numpy as np

        from deepspeed_tpu.serving import Router, SamplingParams
        from deepspeed_tpu.serving.resilience import (
            FaultSpec,
            ResilienceConfig,
            inject,
        )
        from tests.unit.test_serving import FakeEngine, _expected_tokens
    except ImportError as e:
        results.append(CheckResult(
            "lock_witness.chaos_smoke", "lock-order", True,
            f"test fixtures unavailable ({e}); witness run skipped"))
        return results
    finally:
        if added:
            sys.path.remove(repo_root)

    prompts = [np.arange(1 + 10 * i, 6 + 10 * i, dtype=np.int32)
               for i in range(6)]
    want = [_expected_tokens(p, 20) for p in prompts]
    schedule = (
        FaultSpec("worker.crash", nth=10, replica="d0"),
        FaultSpec("handoff.import", nth=2),
        FaultSpec("peer_pull", nth=1),
    )
    cfg = ResilienceConfig(hung_step_s=2.0, probe_backoff_s=0.05,
                           retry_backoff_s=0.001)
    with witness_locks() as wit:  # record-only: assert after the run
        with inject(*schedule):
            router = Router(
                engines=[FakeEngine(step_delay=0.001) for _ in range(2)],
                num_prefill_workers=0, resilience=cfg).start()
            try:
                reqs = [router.submit(p, params=SamplingParams(
                            max_new_tokens=20, ignore_eos=True))
                        for p in prompts]
                for r in reqs:
                    if not r.wait(60):
                        results.append(CheckResult(
                            "lock_witness.chaos_smoke", "lock-order", False,
                            f"scenario wedged: uid={r.uid} never finished "
                            f"({r.state})"))
                        return results
                for r, w in zip(reqs, want):
                    if list(r.generated) != w:
                        results.append(CheckResult(
                            "lock_witness.chaos_smoke", "lock-order", False,
                            f"recovery diverged for uid={r.uid} — witness "
                            "run is not the scenario it claims to cover"))
                        return results
            finally:
                router.shutdown()

    observed = wit.graph()
    static_edges = model.edge_closure() | set(model.order_edges)
    try:
        wit.assert_subgraph(static_edges)
        results.append(CheckResult(
            "lock_witness.chaos_smoke", "lock-order", True,
            f"{len(observed)} observed edge(s) across "
            f"{sum(observed.values())} nested acquisition(s), no inversion, "
            f"all within the static model"))
    except LockOrderViolation as e:
        results.append(CheckResult(
            "lock_witness.chaos_smoke", "lock-order", False, str(e)))
    return results


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
def verify_splash() -> List[CheckResult]:
    """Splash scheduled sparse attention through the model train step: the
    step donates its params, reaches steady state in ONE compiled program,
    and the block schedule is a trace-time constant — retracing hits the
    lru cache instead of rebuilding it."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.ops.attention.core import _derived_splash_schedule

    cfg = T.get_config("tiny", n_layers=2, dtype="float32", max_seq_len=256,
                       attention_impl="splash", sliding_window=96)
    tok = jnp.zeros((2, 256), jnp.int32)

    def step(params, tokens):
        def loss(p):
            logits, aux = T.forward(p, tokens, cfg)
            return jnp.mean(jnp.square(logits.astype(jnp.float32))) + aux

        grads = jax.grad(loss)(params)
        return jax.tree.map(lambda p, g: p - 1e-3 * g, params, grads)

    # a one-device topology for the whole harness: forward()'s sharding
    # constraints name the topology's mesh, and the default one spans every
    # device the process has while the params below are committed to one
    from deepspeed_tpu.parallel.topology import Topology, reset_topology, set_topology

    reset_topology()
    set_topology(Topology(devices=jax.devices()[:1]))
    try:
        fn = jax.jit(step, donate_argnums=(0,))
        results = [check_donation(
            "splash.train_step", fn, (T.init_params(cfg, jax.random.key(0)), tok))]

        # committed params (device_put) so step 1's host-staged signature
        # equals the steady state — exactly how a real trainer holds them
        p = jax.device_put(T.init_params(cfg, jax.random.key(0)), jax.devices()[0])
        before = _derived_splash_schedule.cache_info()
        for _ in range(3):
            p = fn(p, tok)
        results.append(check_recompile("splash.train_step", fn))
    finally:
        reset_topology()

    # trace-time-constant schedule: however many times the step traces or
    # runs, the schedule is BUILT at most once more (first trace) and then
    # served from the lru cache — never rebuilt per step
    after = _derived_splash_schedule.cache_info()
    ok = after.misses <= before.misses + 1
    results.append(CheckResult(
        "splash.schedule_constant", "recompile", ok,
        f"schedule builds {before.misses}->{after.misses} across 3 steps "
        "(<=1 new build: a trace-time constant, not per-step work)"))
    return results


def run_verify(verbose: bool = True) -> Tuple[List[CheckResult], bool]:
    """Run every entry-point harness; returns (results, all_ok). Harness
    crashes surface as failed results, never as silent skips."""
    results: List[CheckResult] = []
    for fn, label in (
        (verify_engine_v2, "engine_v2"),
        (verify_streamed_adam, "streamed_adam"),
        (verify_train_engine, "train_engine"),
        (verify_ring_train, "ring_train"),
        (verify_quantized_comm, "quantized_comm"),
        (verify_tiled_overlap, "tiled_overlap"),
        (verify_disagg, "disagg"),
        (verify_host_tier, "host_tier"),
        (verify_kv_transport, "kv_transport"),
        (verify_elastic, "elastic"),
        (verify_splash, "splash"),
        (verify_lock_order, "lock_order"),
    ):
        try:
            results.extend(fn())
        except Exception as e:  # harness must report, not die mid-suite
            results.append(CheckResult(label, "donation", False,
                                       f"harness error: {type(e).__name__}: {e}"))
    ok = all(r.ok for r in results)
    if verbose:
        for r in results:
            print(r.render())
        print(f"dstpu verify: {sum(r.ok for r in results)}/{len(results)} checks passed")
    return results, ok
