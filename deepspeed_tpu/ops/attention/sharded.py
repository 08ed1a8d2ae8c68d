"""Sharded long-context flash attention: head-sharded + ring (context) paths.

Two ways to run the Pallas flash kernel (ops/attention/flash_pallas.py) on a
multi-device mesh — pallas_call is opaque to the GSPMD partitioner, so both
wrap it in a fully-manual ``shard_map`` (``topology.manual_over``):

  * :func:`head_sharded_flash` — splash-style: batch and heads are
    embarrassingly parallel for self-attention, so each device runs the
    kernel over its local (batch, head) slab and the FULL sequence. Per-device
    activations stay O(s). ALiBi slopes shard along the head axes with the
    heads they belong to.

  * :func:`ring_flash_attention` — context parallel: the SEQUENCE dimension
    itself is sharded over the ``context`` mesh axis. Each device holds a
    [b, h, s/N, d] q/k/v shard and k/v chunks rotate around the ring via
    ``jax.lax.ppermute`` (next hop issued before the current chunk's kernel,
    so the copy overlaps compute). Per-device activations drop to O(s/N) —
    the long-context enabler.

Ring numerics are BIT-IDENTICAL to one unsharded ``flash_attention`` call
(same block size), not merely close: the raw softmax state (m, l, acc) and
the raw gradient accumulators thread through the ring hops via the kernel's
carry refs (``flash_fwd_chunk``/``flash_dq_chunk``/``flash_dkv_chunk``), and
the ring schedule arranges chunk arrival in ASCENDING global order — the
same streaming order as the single kernel's grid — so every accumulation
happens in the same order on the same values:

  * forward + dq (ring A): k/v pre-rotate one hop, then device ``i`` at step
    ``t`` holds chunk ``(i + t + 1) % N`` — active causal chunks arrive
    ``0, 1, …, i`` with the diagonal LAST (statically at step N−1, so the
    causal diagonal kernel call needs no traced branch);
  * dk/dv (ring B): the q-side payload (q, out, do, lse) rotates the same
    direction, compute-before-rotate, so the home k/v chunk sees q chunks
    ``i, i+1, …, N−1`` ascending with the diagonal FIRST (step 0) — the
    single kernel's q-minor grid order.

Inactive hops skip compute under ``lax.cond`` while the ppermute stays
unconditional (collectives must be uniform across the axis). Causal-only:
a uniform rotation cannot produce ascending arrival for the non-causal
all-pairs schedule, and bitwise parity is the contract here.
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.ops.attention.flash_pallas import (
    LANES,
    flash_attention,
    flash_carry_init,
    flash_dkv_chunk,
    flash_dkv_finalize,
    flash_dq_chunk,
    flash_dq_finalize,
    flash_finalize,
    flash_fwd_chunk,
)
from deepspeed_tpu.parallel.topology import (
    BATCH_AXES,
    CONTEXT_AXIS,
    MODEL_AXIS,
    SEQUENCE_AXIS,
    get_topology,
    manual_over,
)

HEAD_AXES = (MODEL_AXIS, SEQUENCE_AXIS)


def _divisible(topo, b, h, h_kv, s=None):
    """Whether the canonical layout divides over the mesh (batch over
    data/expert, heads over model+sequence, optionally seq over context)."""
    batch_div = topo.data_parallel_size * topo.expert_parallel_size
    head_div = topo.model_parallel_size * topo.sequence_parallel_size
    if b % batch_div or h % head_div or h_kv % head_div:
        return False
    if (h // h_kv) > 1 and (h // head_div) % (h // h_kv) != 0:
        return False  # GQA group would straddle a head shard
    if s is not None and s % topo.context_parallel_size:
        return False
    return True


def head_sharded_flash(q, k, v, causal=True, segment_ids=None, scale=None,
                       alibi_slopes=None, alibi_positions=None, window=0,
                       window_flag=None, interpret=False, head_dim=None):
    """Flash attention with batch/head sharding under ``shard_map``.

    Pins the canonical layout (batch over data/expert, heads over
    model+sequence — the TP and post-Ulysses placements) and runs the kernel
    manually per device. ALiBi slopes ride along SHARDED over the head axes,
    so each device's kernel sees exactly its local heads' slopes. Returns
    ``None`` when the shapes don't divide over the mesh (caller falls back).

    Takes both of ``flash_attention``'s layouts, by the operands' rank:
    head-major q [b, h, s, d], k / v [b, h_kv, s, d], or token-major, the
    projections' own q [b, s, h * head_dim], k / v [b, s, h_kv * head_dim].
    The heads are pinned where they are (axis 1, or the lanes: a shard is a
    run of whole heads) and the output comes back in q's layout.
    """
    topo = get_topology()
    if topo.world_size == 1:
        return flash_attention(
            q, k, v, causal=causal, segment_ids=segment_ids, scale=scale,
            alibi_slopes=alibi_slopes, alibi_positions=alibi_positions,
            window=window, window_flag=window_flag, interpret=interpret,
            head_dim=head_dim,
        )
    if q.ndim == 3:
        h, h_kv = q.shape[2] // head_dim, k.shape[2] // head_dim
        spec = P(BATCH_AXES, None, HEAD_AXES)
    else:
        h, h_kv = q.shape[1], k.shape[1]
        spec = P(BATCH_AXES, HEAD_AXES, None, None)
    if not _divisible(topo, q.shape[0], h, h_kv):
        return None
    sharding = NamedSharding(topo.mesh, spec)
    q, k, v = (jax.lax.with_sharding_constraint(x, sharding) for x in (q, k, v))

    # optional extra operands, each pinned to its manual-region placement
    extra_ops, extra_specs = [], []
    has_seg = segment_ids is not None
    if has_seg:
        seg_spec = P(BATCH_AXES, None)
        extra_ops.append(jax.lax.with_sharding_constraint(
            segment_ids, NamedSharding(topo.mesh, seg_spec)))
        extra_specs.append(seg_spec)
    has_alibi = alibi_slopes is not None
    if has_alibi:
        # the slope vector shards WITH the heads it biases
        slopes = jnp.asarray(alibi_slopes, jnp.float32)
        extra_ops.append(jax.lax.with_sharding_constraint(
            slopes, NamedSharding(topo.mesh, P(HEAD_AXES))))
        extra_specs.append(P(HEAD_AXES))
    has_pos = has_alibi and alibi_positions is not None
    if has_pos:
        pos = jnp.asarray(alibi_positions, jnp.int32)
        pos_spec = P(BATCH_AXES, None) if pos.ndim == 2 else P(None)
        extra_ops.append(pos)
        extra_specs.append(pos_spec)
    has_wf = window > 0 and window_flag is not None
    if has_wf:
        extra_ops.append(jnp.asarray(window_flag, jnp.int32))
        extra_specs.append(P())

    def body(q_, k_, v_, *rest):
        rest = list(rest)
        seg = rest.pop(0) if has_seg else None
        sl = rest.pop(0) if has_alibi else None
        pos = rest.pop(0) if has_pos else None
        wf = rest.pop(0) if has_wf else None
        return flash_attention(q_, k_, v_, causal=causal, segment_ids=seg,
                               scale=scale, alibi_slopes=sl,
                               alibi_positions=pos, window=window,
                               window_flag=wf, interpret=interpret,
                               head_dim=head_dim)

    fn = manual_over(body, topo.mesh, (spec, spec, spec, *extra_specs), spec)
    return fn(q, k, v, *extra_ops)


def head_sharded_splash(q, k, v, schedule, segment_ids=None, scale=None,
                        interpret=False):
    """Scheduled block-sparse (splash) attention with batch/head sharding.

    Same placement contract as :func:`head_sharded_flash`. The schedule's
    scalar-prefetch arrays ride INTO the manual region as operands: a
    per-head schedule ([h, nq, w]) shards over the head axes with the
    heads it drives, a shared one ([1, nq, w]) replicates. Returns ``None``
    when the shapes don't divide the mesh (caller falls back).
    """
    from deepspeed_tpu.ops.sparse_attention.splash_pallas import (
        _SplashParams, _splash_core, splash_attention,
    )

    topo = get_topology()
    b, h, s, d = q.shape
    h_kv = k.shape[1]
    if topo.world_size == 1:
        return splash_attention(q, k, v, schedule, segment_ids=segment_ids,
                                scale=scale, interpret=interpret)
    if not _divisible(topo, b, h, h_kv):
        return None
    head_div = topo.model_parallel_size * topo.sequence_parallel_size
    per_head = schedule.num_heads > 1
    if per_head and schedule.num_heads % head_div:
        return None

    spec = P(BATCH_AXES, HEAD_AXES, None, None)
    sharding = NamedSharding(topo.mesh, spec)
    q, k, v = (jax.lax.with_sharding_constraint(x, sharding) for x in (q, k, v))

    seg_mode = "none"
    seg = None
    if schedule.segment_ids is not None:
        if segment_ids is not None:
            raise ValueError("schedule already carries segment ids")
        seg_mode = "schedule"
        seg = jnp.broadcast_to(
            jnp.asarray(schedule.segment_ids, jnp.int32)[None], (b, s))
    elif segment_ids is not None:
        seg_mode = "all"
        seg = jnp.asarray(segment_ids, jnp.int32)
    params = _SplashParams(
        bq=schedule.block_q, bk=schedule.block_kv,
        causal=schedule.causal, window=schedule.window,
        scale=float(scale if scale is not None else d ** -0.5),
        has_partial=schedule.num_partial > 0, seg_mode=seg_mode,
        interpret=interpret, vmem_limit=None,
    )
    sched_spec = P(HEAD_AXES, None, None) if per_head else P(None, None, None)
    sched_ops = [jnp.asarray(a) for a in (
        schedule.kv_index, schedule.step_kind,
        schedule.q_index, schedule.step_kind_t)]
    base = jnp.zeros((1,), jnp.int32)

    has_seg = seg is not None
    seg_specs = [P(BATCH_AXES, None)] if has_seg else []
    seg_ops = [seg] if has_seg else []

    def body(q_, k_, v_, kvi_, kind_, kvi_t_, kind_t_, base_, *rest):
        seg_ = rest[0] if has_seg else None
        return _splash_core(q_, k_, v_, seg_, kvi_, kind_, kvi_t_, kind_t_,
                            base_, params)

    fn = manual_over(
        body, topo.mesh,
        (spec, spec, spec, sched_spec, sched_spec, sched_spec, sched_spec,
         P(None), *seg_specs),
        spec,
    )
    return fn(q, k, v, *sched_ops, base, *seg_ops)


# ---------------------------------------------------------------------------
# Ring (context-parallel) flash attention
# ---------------------------------------------------------------------------


def _rotate(payload, axis_name, perm):
    """One ring hop: every leaf moves to the previous device (so each device
    RECEIVES the next chunk index). Uniform — never under a cond."""
    return jax.tree_util.tree_map(
        lambda x: jax.lax.ppermute(x, axis_name, perm), payload
    )


def _kv_payload(k, v, seg_k, kpos):
    p = {"k": k, "v": v}
    if seg_k is not None:
        p["seg"] = seg_k
    if kpos is not None:
        p["kpos"] = kpos
    return p


def _lane_slopes(slopes, h):
    if slopes is None:
        return None
    return jnp.broadcast_to(
        jnp.asarray(slopes, jnp.float32)[:, None], (h, LANES)
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _ring_core(q, k, v, segment_ids, slopes, axis_name, n, scale, block,
               interpret):
    out, _ = _ring_fwd(q, k, v, segment_ids, slopes, axis_name, n, scale,
                       block, interpret)
    return out


def _ring_fwd(q, k, v, segment_ids, slopes, axis_name, n, scale, block,
              interpret):
    b, h, sc, d = q.shape
    i = jax.lax.axis_index(axis_name)
    perm = [(r, (r - 1) % n) for r in range(n)]
    kpos = None
    if slopes is not None:
        # global key positions rotate with their chunk: slope·kpos must see
        # the same absolute positions as the unsharded kernel
        kpos = jnp.broadcast_to(
            (i * sc + jnp.arange(sc, dtype=jnp.int32))[None], (b, sc)
        )
    slopes_lane = _lane_slopes(slopes, h)
    carry = flash_carry_init(b, h, sc, d)
    payload = _rotate(_kv_payload(k, v, segment_ids, kpos), axis_name, perm)
    for t in range(n):
        src = (i + t + 1) % n  # chunk index this hop delivered
        nxt = _rotate(payload, axis_name, perm) if t < n - 1 else payload
        seg_pair = ((segment_ids, payload["seg"])
                    if segment_ids is not None else None)
        al = (slopes_lane, payload["kpos"]) if slopes is not None else None
        if t == n - 1:
            # the diagonal lands at the LAST step for every device —
            # statically, so the causal kernel call needs no traced branch
            carry = flash_fwd_chunk(
                q, payload["k"], payload["v"], carry, segment_ids=seg_pair,
                alibi=al, causal=True, scale=scale, block=block,
                interpret=interpret,
            )
        else:
            kc, vc = payload["k"], payload["v"]

            def _step(c, kc=kc, vc=vc, seg_pair=seg_pair, al=al):
                return flash_fwd_chunk(
                    q, kc, vc, c, segment_ids=seg_pair, alibi=al,
                    causal=False, scale=scale, block=block,
                    interpret=interpret,
                )

            carry = jax.lax.cond(src < i, _step, lambda c: c, carry)
        payload = nxt
    out, lse = flash_finalize(carry, q.dtype)

    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    q = checkpoint_name(q, "flash_qkv")
    k = checkpoint_name(k, "flash_qkv")
    v = checkpoint_name(v, "flash_qkv")
    return out, (q, k, v, segment_ids, slopes, out, lse)


def _ring_bwd(axis_name, n, scale, block, interpret, res, g):
    q, k, v, segment_ids, slopes, out, lse = res
    b, h, sc, d = q.shape
    h_kv = k.shape[1]
    scale_v = scale if scale is not None else d ** -0.5
    i = jax.lax.axis_index(axis_name)
    perm = [(r, (r - 1) % n) for r in range(n)]
    kpos_home = None
    if slopes is not None:
        kpos_home = jnp.broadcast_to(
            (i * sc + jnp.arange(sc, dtype=jnp.int32))[None], (b, sc)
        )
    slopes_lane = _lane_slopes(slopes, h)

    # ---- ring A: dq. Same schedule as forward — k/v rotate, the raw f32 dq
    # accumulator stays home and sees chunks 0..i ascending, diagonal last.
    dq_acc = jnp.zeros((b, h, sc, d), jnp.float32)
    payload = _rotate(
        _kv_payload(k, v, segment_ids, kpos_home), axis_name, perm
    )
    for t in range(n):
        src = (i + t + 1) % n
        nxt = _rotate(payload, axis_name, perm) if t < n - 1 else payload
        seg_pair = ((segment_ids, payload["seg"])
                    if segment_ids is not None else None)
        al = (slopes_lane, payload["kpos"]) if slopes is not None else None
        if t == n - 1:
            dq_acc = flash_dq_chunk(
                q, payload["k"], payload["v"], out, g, lse, dq_acc,
                segment_ids=seg_pair, alibi=al, causal=True, scale=scale,
                block=block, interpret=interpret,
            )
        else:
            kc, vc = payload["k"], payload["v"]

            def _step(acc, kc=kc, vc=vc, seg_pair=seg_pair, al=al):
                return flash_dq_chunk(
                    q, kc, vc, out, g, lse, acc, segment_ids=seg_pair,
                    alibi=al, causal=False, scale=scale, block=block,
                    interpret=interpret,
                )

            dq_acc = jax.lax.cond(src < i, _step, lambda acc: acc, dq_acc)
        payload = nxt
    dq = flash_dq_finalize(dq_acc, scale_v, q.dtype)

    # ---- ring B: dk/dv. The q side (q, out, do, lse, seg_q) rotates the
    # SAME direction, compute-before-rotate: the home kv chunk sees q chunks
    # i..N-1 ascending, diagonal first (step 0, static) — the kernel's
    # q-minor grid order. ALiBi kpos is the home chunk's — it never moves.
    dk_acc = jnp.zeros((b, h, sc, d), jnp.float32)
    dv_acc = jnp.zeros((b, h, sc, d), jnp.float32)
    al_home = (slopes_lane, kpos_home) if slopes is not None else None
    qpay = {"q": q, "o": out, "do": g, "lse": lse}
    if segment_ids is not None:
        qpay["seg"] = segment_ids
    for t in range(n):
        nxt = _rotate(qpay, axis_name, perm) if t < n - 1 else qpay
        seg_pair = ((qpay["seg"], segment_ids)
                    if segment_ids is not None else None)
        if t == 0:
            dk_acc, dv_acc = flash_dkv_chunk(
                qpay["q"], k, v, qpay["o"], qpay["do"], qpay["lse"],
                dk_acc, dv_acc, segment_ids=seg_pair, alibi=al_home,
                causal=True, scale=scale, block=block, interpret=interpret,
            )
        else:
            src = (i + t) % n  # q chunk visiting this hop
            qc, oc, doc, lsec = qpay["q"], qpay["o"], qpay["do"], qpay["lse"]

            def _step(accs, qc=qc, oc=oc, doc=doc, lsec=lsec,
                      seg_pair=seg_pair):
                return flash_dkv_chunk(
                    qc, k, v, oc, doc, lsec, accs[0], accs[1],
                    segment_ids=seg_pair, alibi=al_home, causal=False,
                    scale=scale, block=block, interpret=interpret,
                )

            dk_acc, dv_acc = jax.lax.cond(
                src > i, _step, lambda accs: accs, (dk_acc, dv_acc)
            )
        qpay = nxt
    dk, dv = flash_dkv_finalize(dk_acc, dv_acc, scale_v, k.dtype, h_kv)
    return dq, dk, dv, None, None


_ring_core.defvjp(_ring_fwd, _ring_bwd)


def ring_attention_local(q, k, v, segment_ids=None, scale=None,
                         alibi_slopes=None, axis_name=CONTEXT_AXIS,
                         axis_size=None, block=None, interpret=False):
    """The per-device ring body — call INSIDE an enclosing ``shard_map``
    whose ``axis_name`` axis shards the sequence dimension of q/k/v
    ([b, h, s/N, d] locals). Causal only. ``segment_ids`` is the local
    [b, s/N] id plane; ``alibi_slopes`` the full (local-head) slope vector.
    Differentiable (custom_vjp: two gradient rings)."""
    n = axis_size if axis_size is not None else jax.lax.axis_size(axis_name)
    if n == 1:
        return flash_attention(q, k, v, causal=True, segment_ids=segment_ids,
                               scale=scale, alibi_slopes=alibi_slopes,
                               interpret=interpret)
    slopes = (jnp.asarray(alibi_slopes, jnp.float32)
              if alibi_slopes is not None else None)
    return _ring_core(q, k, v, segment_ids, slopes, axis_name, int(n), scale,
                      block, interpret)


def ring_flash_attention(q, k, v, causal=True, segment_ids=None, scale=None,
                         alibi_slopes=None, window=0, block=None,
                         interpret=False):
    """Context-parallel flash attention over the ``context`` mesh axis.

    q: [b, h, s, d] GLOBAL arrays (inside jit, GSPMD-placed); the wrapper
    pins sequence over ``context`` (plus the canonical batch/head axes) and
    runs the ring manually per device. Per-device activation footprint is
    O(s/N). Bitwise-identical to the unsharded kernel when the block size
    matches (``block`` ≤ s/N; the default env/1024 pick applies per chunk,
    so pin DSTPU_FLASH_BLOCK ≤ s/N when comparing).

    Raises on the structurally-unsupported cases rather than silently
    falling back: non-causal (a uniform ring rotation cannot visit chunks in
    ascending order bidirectionally), sliding windows (local-position band
    masks are wrong across chunks), and shapes that don't divide the mesh.
    """
    if not causal:
        raise NotImplementedError(
            "ring_flash_attention: causal=False not supported — the ring "
            "schedule needs ascending chunk arrival, which a uniform "
            "rotation only yields for the causal triangle"
        )
    if window:
        raise NotImplementedError(
            "ring_flash_attention: sliding window not supported on the ring "
            "path (band masks are global-position; use head sharding)"
        )
    topo = get_topology()
    n = topo.context_parallel_size
    if n == 1:
        out = head_sharded_flash(
            q, k, v, causal=True, segment_ids=segment_ids, scale=scale,
            alibi_slopes=alibi_slopes, interpret=interpret,
        )
        if out is None:
            raise ValueError(
                "ring_flash_attention: context=1 and batch/head shapes "
                f"{q.shape} do not divide the mesh {topo.mesh.shape}"
            )
        return out
    b, h, s, d = q.shape
    h_kv = k.shape[1]
    if not _divisible(topo, b, h, h_kv, s=s):
        raise ValueError(
            f"ring_flash_attention: shapes b={b} h={h} h_kv={h_kv} s={s} do "
            f"not divide mesh {dict(topo.mesh.shape)}"
        )

    spec = P(BATCH_AXES, HEAD_AXES, CONTEXT_AXIS, None)
    sharding = NamedSharding(topo.mesh, spec)
    q, k, v = (jax.lax.with_sharding_constraint(x, sharding) for x in (q, k, v))

    extra_ops, extra_specs = [], []
    has_seg = segment_ids is not None
    if has_seg:
        seg_spec = P(BATCH_AXES, CONTEXT_AXIS)
        extra_ops.append(jax.lax.with_sharding_constraint(
            segment_ids, NamedSharding(topo.mesh, seg_spec)))
        extra_specs.append(seg_spec)
    has_alibi = alibi_slopes is not None
    if has_alibi:
        slopes = jnp.asarray(alibi_slopes, jnp.float32)
        extra_ops.append(jax.lax.with_sharding_constraint(
            slopes, NamedSharding(topo.mesh, P(HEAD_AXES))))
        extra_specs.append(P(HEAD_AXES))

    def body(q_, k_, v_, *rest):
        rest = list(rest)
        seg = rest.pop(0) if has_seg else None
        sl = rest.pop(0) if has_alibi else None
        return ring_attention_local(
            q_, k_, v_, segment_ids=seg, scale=scale, alibi_slopes=sl,
            axis_name=CONTEXT_AXIS, axis_size=n, block=block,
            interpret=interpret,
        )

    fn = manual_over(body, topo.mesh, (spec, spec, spec, *extra_specs), spec)
    return fn(q, k, v, *extra_ops)
