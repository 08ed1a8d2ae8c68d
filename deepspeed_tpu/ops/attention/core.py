"""Core attention dispatch.

``attention(q, k, v, causal=..., segment_ids=...)`` picks the best backend:
  * TPU: Pallas flash attention (ops/attention/flash_pallas.py) when shapes
    allow tiling onto the MXU (head_dim and block sizes aligned),
  * otherwise: a numerically-stable jnp implementation that XLA fuses well.

Shapes follow the head-major layout [batch, num_heads, seq, head_dim]
(q) / [batch, num_kv_heads, seq, head_dim] (k, v); grouped-query attention
(num_heads a multiple of num_kv_heads) is handled in all backends.
``attention`` also takes the projections' own [batch, seq, heads * head_dim]
(rank 3, with ``head_dim``): the flash kernels read it in place where a head
is whole lanes, every other backend gets head-major operands by a transpose
here, where the backend is chosen.

Reference parity: the fused softmax/attention CUDA ops of
csrc/transformer/inference/csrc/pt_binding.cpp (softmax_context etc.) and the
blocked flash kernels of deepspeed/inference/v2/kernels/ragged_ops.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.accelerator.device import on_tpu


def window_too_far(q_pos, k_pos, window: int, window_flag=None):
    """THE sliding-window band convention, shared by every implementation
    (flash kernel, reference einsum, ring loop, decode mask) so the masks
    cannot drift: key k is out of band for query q iff ``q - k >= window``
    (query sees keys in ``(q - window, q]``). ``window_flag`` (traced 0/1
    scalar from attn_layer_pattern) gates the band per layer — flag 0 means
    the layer is global and nothing is masked. Returns a boolean array of
    ``broadcast(q_pos, k_pos)`` shape, True = mask out."""
    far = (q_pos - k_pos) >= window
    if window_flag is not None:
        far = jnp.logical_and(far, window_flag > 0)
    return far


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """Expand kv heads for grouped-query attention: [b, h_kv, s, d] -> [b, h, s, d]."""
    if n_rep == 1:
        return k
    b, h_kv, s, d = k.shape
    return jnp.broadcast_to(k[:, :, None], (b, h_kv, n_rep, s, d)).reshape(b, h_kv * n_rep, s, d)


def mha_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    alibi_slopes: Optional[jax.Array] = None,
    alibi_positions: Optional[jax.Array] = None,
    window: int = 0,
    window_flag: Optional[jax.Array] = None,
) -> jax.Array:
    """Numerically-stable reference attention in jnp (fp32 softmax).

    q: [b, h, sq, d]; k, v: [b, h_kv, sk, d]. Returns [b, h, sq, d].
    ``alibi_slopes`` ([h]): adds ``slope_h * key_position`` to the logits
    (bloom's absolute-position ALiBi; positions default to arange(sk)).
    ``window``: sliding-window band (query i sees keys in (i - window, i],
    requires causal); ``window_flag`` (traced 0/1 scalar) toggles the band
    per layer for alternating local/global stacks.
    """
    if window and not causal:
        # fail-fast to match flash_attention — silently computing full
        # bidirectional attention would be platform-dependent wrongness
        raise ValueError("mha_reference: window > 0 requires causal=True")
    b, h, sq, d = q.shape
    h_kv = k.shape[1]
    k = _repeat_kv(k, h // h_kv)
    v = _repeat_kv(v, h // h_kv)
    scale = scale if scale is not None else (1.0 / (d ** 0.5))
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if alibi_slopes is not None:
        kp = (
            jnp.arange(k.shape[2], dtype=jnp.float32)[None]
            if alibi_positions is None
            else jnp.asarray(alibi_positions, jnp.float32)
        )
        if kp.ndim == 1:
            kp = kp[None]
        slopes = jnp.asarray(alibi_slopes, jnp.float32)
        logits = logits + slopes[None, :, None, None] * kp[:, None, None, :]
    sk = k.shape[2]
    if causal:
        # offset so the last q position attends to all sk keys (decode-friendly)
        q_pos = jnp.arange(sq)[:, None] + (sk - sq)
        k_pos = jnp.arange(sk)[None, :]
        mask = q_pos >= k_pos
        if window:
            mask = jnp.logical_and(
                mask, jnp.logical_not(window_too_far(q_pos, k_pos, window, window_flag))
            )
        logits = jnp.where(mask[None, None], logits, jnp.float32(-1e30))
    if segment_ids is not None:
        # segment_ids: [b, s] per position; requires sq == sk (training path)
        seg_mask = segment_ids[:, :, None] == segment_ids[:, None, :]
        logits = jnp.where(seg_mask[:, None], logits, jnp.float32(-1e30))
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights.astype(v.dtype), v)


_warned_alibi_fallback = False
_warned_window_fallback = False
_warned_splash_fallback = False


@functools.lru_cache(maxsize=64)
def _derived_splash_schedule(sq: int, sk: int, causal: bool, window: int,
                             block: int):
    """Schedule for the mask implied by (causal, window) alone — the
    impl='splash' path with no explicit mask configured. Cached: the
    schedule is a trace-time constant, never rebuilt per step."""
    from deepspeed_tpu.ops.sparse_attention.mask import (
        CausalMask, FullMask, LocalMask,
    )
    from deepspeed_tpu.ops.sparse_attention.schedule import schedule_from_mask

    if window and causal:
        mask = LocalMask((sq, sk), window)
    elif causal:
        mask = CausalMask((sq, sk))
    else:
        mask = FullMask((sq, sk))
    return schedule_from_mask(mask, block)


def _splash_block(s: int) -> int:
    import os

    from deepspeed_tpu.ops.attention.flash_pallas import _pick_block

    return _pick_block(s, int(os.environ.get("DSTPU_SPLASH_BLOCK", 512)))


def _splash_dispatch(q, k, v, causal, segment_ids, bias, scale, window,
                     window_flag, schedule, strict):
    """impl='splash' (strict) or auto-promotion (a schedule was configured).
    Returns None when the shapes/arguments cannot take the scheduled path
    (the caller falls back to the dense dispatch chain); strict mode raises
    instead, matching the other explicit impls."""
    from deepspeed_tpu.ops.sparse_attention.splash_pallas import splash_attention

    def bail(msg):
        if strict:
            raise ValueError(f"attention(impl='splash'): {msg}")
        return None

    if bias is not None:
        return bail("dense bias is not supported on the scheduled path")
    if window_flag is not None:
        return bail("a traced per-layer window flag cannot alter a static "
                    "schedule (use the dense/flash path for flag-gated "
                    "local layers)")
    sq, sk = q.shape[2], k.shape[2]
    if schedule is None:
        block = _splash_block(min(sq, sk))
        if sq % block or sk % block:
            return bail(f"seq ({sq}, {sk}) does not divide block {block}")
        schedule = _derived_splash_schedule(sq, sk, bool(causal),
                                            int(window or 0), block)
    from deepspeed_tpu.parallel.topology import get_topology

    if get_topology().world_size > 1:
        from deepspeed_tpu.ops.attention.sharded import head_sharded_splash

        out = head_sharded_splash(q, k, v, schedule, segment_ids=segment_ids,
                                  scale=scale,
                                  interpret=not on_tpu())
        if out is not None:
            return out
        # shapes don't divide the mesh: run the kernel unsharded (GSPMD
        # replicates the pallas_call) — scheduling still prunes, only the
        # head parallelism is lost
    return splash_attention(q, k, v, schedule, segment_ids=segment_ids,
                            scale=scale, interpret=not on_tpu())


def _flash_sharded(q, k, v, causal, segment_ids, scale, alibi_slopes=None,
                   alibi_positions=None, window=0, window_flag=None,
                   head_dim=None):
    """Run the Pallas flash kernel under a multi-device mesh (batch/head
    sharding — ops.attention.sharded.head_sharded_flash). Returns None when
    the shapes don't divide; the caller falls back to the reference einsum
    (GSPMD partitions that, but it materializes O(s²) scores — warn when
    that happens with alibi at long sequence, the expensive case)."""
    from deepspeed_tpu.ops.attention.sharded import head_sharded_flash

    out = head_sharded_flash(
        q, k, v, causal=causal, segment_ids=segment_ids, scale=scale,
        alibi_slopes=alibi_slopes, alibi_positions=alibi_positions,
        window=window, window_flag=window_flag, head_dim=head_dim,
    )
    if out is None and alibi_slopes is not None:
        global _warned_alibi_fallback
        if not _warned_alibi_fallback:
            _warned_alibi_fallback = True
            from deepspeed_tpu.utils.logging import logger

            logger.warning(
                "alibi attention fell back to the dense reference path "
                "(O(seq²) HBM for scores): batch/head shapes do not divide "
                "the mesh for the head-sharded flash kernel"
            )
    return out


def _ring_eligible(b, h, h_kv, s, sk, d, bias, causal, window):
    """Whether 'auto' dispatch may take the ring context-parallel path: the
    topology's ``context`` axis is >1 (explicit opt-in via mesh config) and
    the schedule/shapes fit the ring's contract."""
    from deepspeed_tpu.parallel.topology import get_topology

    topo = get_topology()
    n = topo.context_parallel_size
    if n <= 1 or bias is not None or not causal or window:
        return False
    if s != sk or d not in (64, 128, 256) or s % n or (s // n) % 128:
        return False
    from deepspeed_tpu.ops.attention.sharded import _divisible

    return _divisible(topo, b, h, h_kv, s=s)


def _auto_flash(impl, bias, d, sq, sk):
    """Whether the dense dispatch chain ends at the flash kernel."""
    return impl == "flash" or (
        impl in (None, "auto")
        and on_tpu()
        and bias is None
        and d in (64, 128, 256)
        and sq % 128 == 0
        and sk % 128 == 0
        and sq == sk  # self-attention training path; decode uses reference
    )


def _flash_reads_in_place(impl, bias, schedule, ring, b, h, h_kv, sq, sk, d):
    """Whether a token-major call ([b, s, heads * d] operands) stays so: the
    dispatch ends at the flash kernel (not at splash, the ring or the
    reference), which takes a head of whole lanes where the projections wrote
    it, and the mesh divides, so nothing falls back behind it."""
    if d % 128 or schedule is not None or ring:
        return False
    if not (_auto_flash(impl, bias, d, sq, sk) or (impl == "flash_head_sharded" and bias is None)):
        return False
    from deepspeed_tpu.ops.attention.sharded import _divisible
    from deepspeed_tpu.parallel.topology import get_topology

    topo = get_topology()
    return topo.world_size == 1 or _divisible(topo, b, h, h_kv)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    impl: Optional[str] = None,
    alibi_slopes: Optional[jax.Array] = None,
    alibi_positions: Optional[jax.Array] = None,
    window: int = 0,
    window_flag: Optional[jax.Array] = None,
    schedule=None,
    head_dim: Optional[int] = None,
) -> jax.Array:
    """Dispatching attention entry point.

    The layout is the operands' rank. Rank 4, head-major: q [b, h, sq, d]; k, v
    [b, h_kv, sk, d] → [b, h, sq, d]. Rank 3, token-major, with ``head_dim``:
    q [b, sq, h * head_dim]; k, v [b, sk, h_kv * head_dim] → [b, sq,
    h * head_dim]: what a layer's projections write and its output projection
    reads. A rank-3 call that resolves to the flash kernel at a head of whole
    lanes (``head_dim % 128 == 0``) hands them over as they are; any other
    backend (splash, the ring, the reference, a head of 64) takes head-major
    operands, transposed here.

    ``impl`` selects the backend:
      * None / 'auto' — splash when a block ``schedule`` (or sparse mask)
        is configured, flash when the platform/shapes allow (ring context
        parallelism when the topology's ``context`` axis is >1 and the
        schedule supports it), else the jnp reference;
      * 'flash' — flash kernel, auto-sharded over batch/head axes;
      * 'flash_head_sharded' — splash-style head sharding, hard error if the
        shapes don't divide the mesh;
      * 'flash_ring' — context-parallel ring over the ``context`` mesh axis
        (causal only; hard error on unsupported schedules);
      * 'splash' — the scheduled block-sparse kernel
        (ops/sparse_attention/splash_pallas.py): ``schedule`` (a
        BlockSchedule) or, absent that, the (causal, window) pair compiles
        into a compacted active-block schedule — masked blocks are never
        visited. Head-sharded automatically on multi-device meshes;
      * 'reference' — the jnp einsum.
    ALiBi and sliding windows ride the flash path (in-kernel masking; a
    static window additionally prunes out-of-band kv blocks from the grid);
    a dense ``bias`` forces the reference path."""
    token_major = q.ndim == 3
    if token_major:
        if not head_dim:
            raise ValueError("attention: [b, s, heads * d] operands need head_dim")
        (b, sq, _), sk, d = q.shape, k.shape[1], head_dim
        h, h_kv = q.shape[2] // d, k.shape[2] // d
    else:
        (b, h, sq, d), h_kv, sk = q.shape, k.shape[1], k.shape[2]
    ring = impl in (None, "auto") and _ring_eligible(b, h, h_kv, sq, sk, d, bias, causal, window)
    if token_major and not _flash_reads_in_place(impl, bias, schedule, ring, b, h, h_kv, sq, sk, d):
        from deepspeed_tpu.ops.attention import flash_pallas

        q, k, v = (flash_pallas.head_major(x, d) for x in (q, k, v))
        return flash_pallas.token_major(attention(
            q, k, v, causal=causal, segment_ids=segment_ids, bias=bias, scale=scale,
            impl=impl, alibi_slopes=alibi_slopes, alibi_positions=alibi_positions,
            window=window, window_flag=window_flag, schedule=schedule,
        ))
    if alibi_slopes is not None and (impl == "splash" or schedule is not None):
        raise ValueError("attention: ALiBi is not supported on the splash "
                         "scheduled path")
    if impl == "splash":
        out = _splash_dispatch(q, k, v, causal, segment_ids, bias, scale,
                               window, window_flag, schedule, strict=True)
        if out is not None:
            return out
    elif impl in (None, "auto") and schedule is not None:
        # auto promotion: a sparse mask/window schedule was configured
        out = _splash_dispatch(q, k, v, causal, segment_ids, bias, scale,
                               window, window_flag, schedule, strict=False)
        if out is not None:
            return out
        global _warned_splash_fallback
        if not _warned_splash_fallback:
            _warned_splash_fallback = True
            from deepspeed_tpu.utils.logging import logger

            logger.warning(
                "configured splash schedule fell back to the dense dispatch "
                "chain (bias/window-flag/mesh constraints) — sparsity will "
                "be masked, not pruned")
    if impl == "reference":
        return mha_reference(
            q, k, v, causal=causal, segment_ids=segment_ids, bias=bias,
            scale=scale, alibi_slopes=alibi_slopes,
            alibi_positions=alibi_positions, window=window,
            window_flag=window_flag,
        )
    if impl in ("flash_head_sharded", "flash_ring"):
        from deepspeed_tpu.ops.attention import sharded

        if bias is not None:
            raise ValueError(f"attention(impl={impl!r}): dense bias is not "
                             "supported on the flash paths")
        if impl == "flash_ring":
            return sharded.ring_flash_attention(
                q, k, v, causal=causal, segment_ids=segment_ids, scale=scale,
                alibi_slopes=alibi_slopes, window=window,
                interpret=not on_tpu(),
            )
        out = sharded.head_sharded_flash(
            q, k, v, causal=causal, segment_ids=segment_ids, scale=scale,
            alibi_slopes=alibi_slopes, alibi_positions=alibi_positions,
            window=window, window_flag=window_flag,
            interpret=not on_tpu(), head_dim=head_dim,
        )
        if out is None:
            raise ValueError(
                "attention(impl='flash_head_sharded'): batch/head shapes "
                f"{q.shape} do not divide the mesh"
            )
        return out
    if ring:
        from deepspeed_tpu.ops.attention import sharded

        return sharded.ring_flash_attention(
            q, k, v, causal=causal, segment_ids=segment_ids, scale=scale,
            alibi_slopes=alibi_slopes, interpret=not on_tpu(),
        )
    if _auto_flash(impl, bias, d, sq, sk):
        out = _flash_sharded(q, k, v, causal, segment_ids, scale, alibi_slopes,
                             alibi_positions, window, window_flag, head_dim)
        if out is not None:
            return out
    if token_major:  # _flash_reads_in_place said the kernel takes it, above
        raise RuntimeError("attention: a token-major call fell through the flash dispatch")
    if window and sq == sk and sq >= 4096:
        global _warned_window_fallback
        if not _warned_window_fallback:
            _warned_window_fallback = True
            from deepspeed_tpu.utils.logging import logger

            logger.warning(
                f"sliding-window attention fell back to the dense reference "
                f"path at seq={sq} (flash needs TPU, head_dim in 64/128/256, "
                "seq % 128 == 0) — [b, h, s, s] fp32 scores materialize in HBM"
            )
    return mha_reference(
        q, k, v, causal=causal, segment_ids=segment_ids, bias=bias, scale=scale,
        alibi_slopes=alibi_slopes, alibi_positions=alibi_positions,
        window=window, window_flag=window_flag,
    )
