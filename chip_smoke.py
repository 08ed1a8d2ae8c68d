#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives train and serve once, through the entry points a user calls, at the
full width and depth of the ``bench-767m`` preset with seeded random weights:

  kernels  every Pallas entry point a TPU default selects, compiled by Mosaic
           at the preset's head geometry and checked against its jnp reference
  train    ``deepspeed_tpu.initialize`` -> ``engine.train_batch`` (ZeRO-3, bf16,
           AdamW, batch 6 x seq 2048, int8 forward projections) for a few
           steps on a repeated batch: losses finite and falling
  serve    ``serve_parse_args`` -> ``build_serving_stack`` -> ``driver.start``
           -> ``start_server``; POST /generate bodies of mixed length, one of
           them streamed: sent one at a time they must return the tokens
           ``engine.generate()`` gives, sent all at once every token must be
           a near-argmax of ``models.forward`` over the request's own
           history; /health returns to idle; server drains
  multi    with >= 4 devices: the same train path under ZeRO-3 over data=4,
           shards on four distinct devices, first-step loss equal to one chip

One process, so one owner of the chip. Exit code 0 and a last stdout line
``{"ok": true, "device": {...}}`` only when every phase passed on a TPU; any
exception, a non-finite loss, a token mismatch or a missing chip exits
non-zero and prints no result. Times printed are smoke readings, not
benchmark results.

``--size tiny`` is for development: the same control flow at the ``tiny``
preset with interpreted kernels. It needs ``JAX_PLATFORMS=cpu`` and marks
every line of output ``platform: cpu``.
"""

import argparse
import dataclasses
import gc
import json
import os
import sys
import threading
import time
import traceback
import urllib.request

PHASES = ("kernels", "train", "serve", "multi")
TINY = False  # set by main(); the only switch that may select interpret mode
PALLAS_CALLS = []  # (kernel name, interpret) of every pallas_call traced


def say(msg=""):
    prefix = "platform: cpu | " if TINY else ""
    for line in str(msg).splitlines() or [""]:
        print(prefix + line, flush=True)


def fail(msg):
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    return 1


# ---------------------------------------------------------------------------
# instrumentation: what was traced, what was cached
# ---------------------------------------------------------------------------
def record_pallas_calls():
    """Note the ``interpret`` flag of every pallas_call the package traces."""
    from jax.experimental import pallas as pl

    real = pl.pallas_call

    def recording(kernel, *args, **kwargs):
        name = getattr(kernel, "__name__", None) or getattr(
            getattr(kernel, "func", None), "__name__", repr(kernel))
        PALLAS_CALLS.append((name, bool(kwargs.get("interpret", False))))
        return real(kernel, *args, **kwargs)

    pl.pallas_call = recording


def assert_mosaic_since(mark, what):
    """Kernels were traced into ``what`` and none of them interpreted."""
    calls = PALLAS_CALLS[mark:]
    names = sorted({n for n, _ in calls})
    if TINY:
        say(f"  pallas_calls traced in {what}: {names or 'none (jnp paths on cpu)'}")
        return
    if not calls:
        raise AssertionError(f"{what}: no pallas_call was traced")
    interpreted = sorted({n for n, i in calls if i})
    if interpreted:
        raise AssertionError(f"{what}: interpreted kernels {interpreted}")
    say(f"  pallas_calls traced in {what}: {names} (none interpreted)")


class CacheCounter:
    """Persistent-compilation-cache hits and misses, from jax.monitoring."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def cache_entries(path):
    try:
        return sum(1 for n in os.listdir(path) if not n.endswith("-atime"))
    except FileNotFoundError:
        return 0


def abstract(tree):
    import jax

    def leaf(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            # an uncommitted scalar sits on device 0 without belonging there
            placed = x.sharding if getattr(x, "committed", False) else None
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=placed)
        return x

    return jax.tree.map(leaf, tree)


class CaptureArgs:
    """Stand-in for a jitted step that remembers the avals of its last call,
    so the same program can be lowered again and read."""

    def __init__(self, jitted):
        self.jitted = jitted
        self.avals = None

    def __call__(self, *args):
        self.avals = abstract(args)
        return self.jitted(*args)

    def __getattr__(self, name):
        return getattr(self.jitted, name)

    def assert_tpu_custom_call(self, what):
        if self.avals is None:
            raise AssertionError(f"{what}: step was never called")
        n = self.jitted.lower(*self.avals).as_text().count("tpu_custom_call")
        if n == 0 and not TINY:
            raise AssertionError(f"{what}: lowered program has no tpu_custom_call")
        say(f"  lowered {what}: {n} tpu_custom_call (Mosaic) sites")


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------
def kernel_cases(size):
    """[(name, fn, args, reference fn, tolerance)] at the preset's head
    geometry. ``fn`` and the reference take the same args and return the same
    pytree (outputs, then gradients where the kernel has a backward)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.transformer import get_config
    from deepspeed_tpu.ops.attention.core import mha_reference
    from deepspeed_tpu.ops.attention.flash_pallas import flash_attention
    from deepspeed_tpu.ops.attention.paged_pallas import paged_attention
    from deepspeed_tpu.ops.normalization.fused_norm import (
        fused_rms_norm,
        rms_norm_reference,
    )
    from deepspeed_tpu.ops.quantizer.block_quant import quantize_kv
    from deepspeed_tpu.ops.sparse_attention import (
        CausalMask,
        LocalMask,
        schedule_from_mask,
        splash_attention,
    )

    cfg = get_config(size)
    nh, nkv, d, h = cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.hidden_size
    s = cfg.max_seq_len
    b = 2
    dt = jnp.bfloat16
    interp = TINY
    keys = iter(jax.random.split(jax.random.key(0), 64))

    def rnd(shape, dtype=dt, scale=1.0):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dtype)

    def with_grads(f):
        """(out, d/dq, d/dk, d/dv) of ``f(q, k, v, ...)`` under a fixed cotangent."""
        def run(q, k, v, do, *rest):
            out, vjp = jax.vjp(lambda q_, k_, v_: f(q_, k_, v_, *rest), q, k, v)
            return (out,) + tuple(vjp(do))
        return run

    cases = []
    q, k, v = rnd((b, nh, s, d)), rnd((b, nkv, s, d)), rnd((b, nkv, s, d))
    do = rnd((b, nh, s, d))
    # two packed documents per row, cut at a different place in each row
    cut = jnp.asarray([s // 2, s // 4 * 3])[:b, None]
    seg = (jnp.arange(s)[None] >= cut).astype(jnp.int32)
    window = s // 4

    cases.append((
        "flash fwd+bwd causal",
        with_grads(lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=interp)),
        (q, k, v, do),
        with_grads(lambda q, k, v: mha_reference(q, k, v, causal=True)),
        4e-2,
    ))
    cases.append((
        "flash fwd+bwd causal + segment ids",
        with_grads(lambda q, k, v, sg: flash_attention(
            q, k, v, causal=True, segment_ids=sg, interpret=interp)),
        (q, k, v, do, seg),
        with_grads(lambda q, k, v, sg: mha_reference(q, k, v, causal=True, segment_ids=sg)),
        4e-2,
    ))
    cases.append((
        f"flash fwd+bwd causal + static window {window}",
        with_grads(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window, interpret=interp)),
        (q, k, v, do),
        with_grads(lambda q, k, v: mha_reference(q, k, v, causal=True, window=window)),
        4e-2,
    ))

    slopes = 2.0 ** -jnp.arange(1, nh + 1, dtype=jnp.float32)
    cases.append((
        "flash fwd+bwd causal + alibi",
        with_grads(lambda q, k, v, sl: flash_attention(
            q, k, v, causal=True, alibi_slopes=sl, interpret=interp)),
        (q, k, v, do, slopes),
        with_grads(lambda q, k, v, sl: mha_reference(q, k, v, causal=True, alibi_slopes=sl)),
        4e-2,
    ))

    block = min(512, s)
    for label, mask in (("causal", CausalMask((s, s))),
                        (f"local {window}", LocalMask((s, s), window))):
        sched = schedule_from_mask(mask, block)
        cases.append((
            f"splash fwd+dq+dkv {label}",
            with_grads(lambda q, k, v, sched=sched: splash_attention(q, k, v, sched)),
            (q, k, v, do),
            with_grads(lambda q, k, v, w=(window if "local" in label else 0):
                       mha_reference(q, k, v, causal=True, window=w)),
            4e-2,
        ))

    cases.append((
        "splash fwd+dq+dkv causal + segment ids",
        with_grads(lambda q, k, v, sg, sched=schedule_from_mask(CausalMask((s, s)), block):
                   splash_attention(q, k, v, sched, segment_ids=sg)),
        (q, k, v, do, seg),
        with_grads(lambda q, k, v, sg: mha_reference(q, k, v, causal=True, segment_ids=sg)),
        4e-2,
    ))

    x, w, g = rnd((b * s, h)), rnd((h,), scale=0.1) + 1.0, rnd((b * s, h))

    def norm_with_grads(f):
        def run(x, w, g):
            out, vjp = jax.vjp(f, x, w)
            return (out,) + tuple(vjp(g))
        return run

    cases.append((
        "fused rms norm fwd+bwd",
        norm_with_grads(lambda x, w: fused_rms_norm(x, w, 1e-5, interp)),
        (x, w, g),
        norm_with_grads(lambda x, w: rms_norm_reference(x, w, 1e-5)),
        # dw sums b*s bf16 products: the tolerance scales with the result
        4e-2,
    ))

    # paged decode at the serving geometry: R rows, B table slots of bs tokens
    R, B, bs, NB = 32, 32, 128, 256
    if TINY:
        R, B, bs, NB = 8, 4, 16, 24
    trash = NB  # the pool's last row
    rs = np.random.default_rng(0)
    ctx = rs.integers(1, B * bs - 16, size=R)            # tokens already cached
    tables = np.full((R, B), trash, np.int32)
    for r in range(R):
        n = -(-int(ctx[r] + 16) // bs)
        tables[r, :n] = rs.choice(NB, size=n, replace=False)
    tables = jnp.asarray(tables)
    pos0 = jnp.asarray(ctx, jnp.int32)
    kp, vp = rnd((NB + 1, bs, nkv, d)), rnd((NB + 1, bs, nkv, d))
    kq, ks = quantize_kv(kp)
    vq, vs = quantize_kv(vp)

    def paged(impl):
        def run(q, kc, vc, tb, qpos, scales, extra, limit):
            return paged_attention(
                q, kc, vc, tb, qpos, trash, impl=impl, interpret=interp,
                k_scale=scales[0] if scales else None,
                v_scale=scales[1] if scales else None,
                extra_kv=extra, pool_limit=limit,
            )
        return run

    def extras(T, E, base, n_valid):
        """(ke, ve, epos): E not-yet-cached tokens per row at base+j, the
        first ``n_valid`` of them live."""
        j = jnp.arange(E, dtype=jnp.int32)
        epos = jnp.where(j[None] < n_valid, base[:, None] + j[None], -1)
        return rnd((T, E, nkv, d)), rnd((T, E, nkv, d)), epos

    k1 = 4
    qd = rnd((R, nh, d))
    forms = {
        "plain": (qd, tables, pos0 - 1, None, None),
        "extra_kv E=1 + pool_limit (split step)":
            (qd, tables, pos0, extras(R, 1, pos0, 1), pos0),
    }
    # flattened verify form: each row's K1 tokens share its table and extras
    rep = lambda a: jnp.repeat(a, k1, axis=0)
    vpos = (pos0[:, None] + jnp.arange(k1)[None]).reshape(R * k1)
    ke, ve, epos = extras(R, k1, pos0, k1)
    forms[f"flattened verify K1={k1}"] = (
        rnd((R * k1, nh, d)), rep(tables), vpos,
        (rep(ke), rep(ve), rep(epos)), rep(pos0),
    )
    for pool, (kc, vc, scales) in (("bf16", (kp, vp, None)),
                                   ("int8", (kq, vq, (ks, vs)))):
        for label, (qq, tb, qpos, extra, limit) in forms.items():
            cases.append((
                f"paged decode {pool} {label}",
                paged("kernel"), (qq, kc, vc, tb, qpos, scales, extra, limit),
                paged("dense"), 2e-2,
            ))

    # a split step's prompt chunks at the Qwen3 cells' geometry (16 query / 8
    # KV heads of 128, tq 512, 32-slot tables of 128-token blocks): a chunk at
    # 1,024 over 8 pool blocks, a prompt's first 400 tokens, and an empty row
    from deepspeed_tpu.ops.attention.paged_pallas import paged_chunk_attention

    cnh, cnkv, cd, tq, cbs, cB = (4, 2, 32, 32, 16, 8) if TINY else (16, 8, 128, 512, 128, 32)
    chunks = [(2 * tq, tq), (0, tq - tq // 4), (0, 0)]  # (start, live tokens) a row
    ctab = np.full((len(chunks), cB), trash, np.int32)
    cpos = np.full((len(chunks), tq), -1, np.int32)
    for r, (start, n) in enumerate(chunks):
        nb = -(-(start + n) // cbs)
        ctab[r, :nb] = rs.choice(NB, size=nb, replace=False)
        cpos[r, :n] = start + np.arange(n)

    def chunk(impl):
        def run(q, kc, vc, tb, qpos, ke, ve, limit):
            return paged_chunk_attention(q, kc, vc, tb, qpos, trash, new_kv=(ke, ve),
                                         pool_limit=limit, impl=impl, interpret=interp)
        return run

    cases.append((
        "paged chunk, the pool in place",
        chunk("kernel"),
        (rnd((len(chunks), tq, cnh, cd)), rnd((NB + 1, cbs, cnkv, cd)), rnd((NB + 1, cbs, cnkv, cd)),
         jnp.asarray(ctab), jnp.asarray(cpos), rnd((len(chunks), tq, cnkv, cd)),
         rnd((len(chunks), tq, cnkv, cd)), jnp.asarray([c[0] for c in chunks], jnp.int32)),
        chunk("dense"), 2e-2,
    ))

    # the one-token Gated DeltaNet update on the state pool in place, at the
    # Qwen3-Next geometry: 32 rows (28 live on scattered slots, the grid's
    # padding on the spare slot with g = beta = 0), 16 key / 32 value heads of
    # 128, a layer's 33 slots of [32, 128, 128] float32
    from deepspeed_tpu.ops.linear_attention.gated_delta import gdn_decode, qk_heads

    Rg, nkg, nvg, dg, NS = (4, 2, 4, 16, 6) if TINY else (32, 16, 32, 128, 33)
    live = jnp.arange(Rg) < Rg - Rg // 8
    slots = jnp.where(live, jnp.asarray(rs.permutation(NS - 1)[:Rg], jnp.int32), NS - 1)
    qg, kg = qk_heads(rnd((Rg, nkg, dg), jnp.float32), rnd((Rg, nkg, dg), jnp.float32))
    gates = jnp.where(live[:, None], -jnp.abs(rnd((Rg, nvg), jnp.float32, 0.1)), 0.0)
    betas = jnp.where(live[:, None], jax.nn.sigmoid(rnd((Rg, nvg), jnp.float32)), 0.0)

    def gdn(impl):
        return lambda *a: gdn_decode(*a, impl=impl)

    # a mixed stack's WINDOW layer at the K-EXAONE geometry (64 query / 8 KV
    # heads of 128, window 128 over blocks of 128: rings of 2): both paged
    # kernels walk ring tables (logical block j of slot s is ring block
    # 2 s + j % 2) bounded by the window, rows deep into their contexts, the
    # rings wrapped many times; the grid's padding sits on the spare ring
    wnh, wnkv, wd, win, wbs, wB, wtq, WS = (
        (4, 2, 32, 16, 16, 8, 32, 5) if TINY else (64, 8, 128, 128, 128, 80, 512, 33))
    wb = -(-(win - 1) // wbs) + 1
    wpool = lambda: rnd((WS * wb, wbs, wnkv, wd))  # noqa: E731
    ring = lambda slots: slots[:, None] * wb + (jnp.arange(wB, dtype=jnp.int32) % wb)[None]  # noqa: E731
    wtrash = (WS - 1) * wb
    Rw = 4 if TINY else 32
    wslots = jnp.where(jnp.arange(Rw) < Rw - 1, jnp.arange(Rw, dtype=jnp.int32) % (WS - 1), WS - 1)
    wpos = jnp.where(jnp.arange(Rw) < Rw - 1,
                     jnp.asarray(rs.integers(1, wB * wbs - 1, size=Rw), jnp.int32), -1)

    def ring_decode(impl):
        def run(q, kc, vc, tb, qpos, ke, ve):
            return paged_attention(q, kc, vc, tb, qpos, wtrash, impl=impl, interpret=interp,
                                   window=win, extra_kv=(ke, ve, qpos[:, None]), pool_limit=qpos)
        return run

    cases.append((
        "paged decode, a window layer's ring tables",
        ring_decode("kernel"),
        (rnd((Rw, wnh, wd)), wpool(), wpool(), ring(wslots), wpos,
         rnd((Rw, 1, wnkv, wd)), rnd((Rw, 1, wnkv, wd))),
        ring_decode("dense"), 2e-2,
    ))
    wstart = jnp.asarray([3 * wtq, 0], jnp.int32)   # a chunk behind three others; a first chunk
    wcpos = jnp.stack([3 * wtq + jnp.arange(wtq), jnp.where(jnp.arange(wtq) < wtq - wtq // 4,
                                                          jnp.arange(wtq), -1)]).astype(jnp.int32)

    def ring_chunk(impl):
        def run(q, kc, vc, tb, qpos, ke, ve, limit):
            return paged_chunk_attention(q, kc, vc, tb, qpos, wtrash, window=win, new_kv=(ke, ve),
                                         pool_limit=limit, impl=impl, interpret=interp)
        return run

    cases.append((
        "paged chunk, a window layer's ring tables",
        ring_chunk("kernel"),
        (rnd((2, wtq, wnh, wd)), wpool(), wpool(), ring(jnp.asarray([1, 2], jnp.int32)), wcpos,
         rnd((2, wtq, wnkv, wd)), rnd((2, wtq, wnkv, wd)), wstart),
        ring_chunk("dense"), 2e-2,
    ))

    # a LATENT pool at the A.X-K1 geometry (64 heads against blocks of [576,
    # 128]: the normed 512-wide latent + 64 rotated key dims a token, a block
    # its tokens on the lanes): the absorbed decode with the row's own vector
    # beside the pool, prompt chunks over the pool and their own vectors, and
    # the pool write that merges a step's vectors into the blocks they land in
    from deepspeed_tpu.ops.attention import latent_pallas as LP

    lnh, lrank, lD, lbs, lB, lP, ltq, lR = (
        (4, 16, 24, 16, 6, 40, 32, 4) if TINY else (64, 512, 576, 128, 112, 4096, 512, 32))
    lpool = rnd((lP + 1, lD, lbs))
    lctx = rs.integers(1, lB * lbs - 1, size=lR)
    ltab = np.full((lR, lB), lP, np.int32)
    free = list(rs.permutation(lP))
    for r in range(lR - 1):   # the last slot is the grid's padding
        for j in range(-(-int(lctx[r]) // lbs)):
            ltab[r, j] = free.pop()
    lpos = jnp.asarray(np.where(np.arange(lR) < lR - 1, lctx, -1), jnp.int32)

    def latent_dec(impl):
        def run(q, pool, tb, qpos, own):
            return LP.latent_decode(q, pool, tb, qpos, lP, rank=lrank, scale=lD ** -0.5,
                                    extra=(own, qpos[:, None]), pool_limit=qpos, impl=impl,
                                    interpret=interp)
        return run

    cases.append((
        "latent decode, absorbed, the row's own vector beside the pool",
        latent_dec("kernel"), (rnd((lR, lnh, lD)), lpool, jnp.asarray(ltab), lpos, rnd((lR, 1, lD))),
        latent_dec("dense"), 2e-2,
    ))
    cB = lB if TINY else 24   # (the dense oracle's scores are [2, tq, heads, B x bs + tq])
    cstart = jnp.asarray([min(3 * ltq + 5, (cB - 5) * lbs), 0], jnp.int32)
    ccpos = jnp.stack([cstart[0] + jnp.arange(ltq), jnp.where(
        jnp.arange(ltq) < ltq - ltq // 4, jnp.arange(ltq), -1)]).astype(jnp.int32)
    ctab = np.full((2, cB), lP, np.int32)
    for r, n in enumerate((-(-(int(cstart[0]) + ltq) // lbs), ltq // lbs)):
        ctab[r, :n] = [free.pop() for _ in range(n)]   # (no block is two rows': the write below)

    # (a chunk row of 512 slots attends EXPANDED, a head's keys and values made
    # of the cached latents inside the kernel; the tiny size's 32 slots absorbed)
    ldr = lD - lrank
    ldn = ldv = 8 if TINY else 128

    def latent_chk(impl):
        def run(q, qr, w, pool, tb, qpos, new, limit):
            return LP.latent_chunk(q, qr, w, pool, tb, qpos, lP, new, limit, scale=lD ** -0.5,
                                   impl=impl, interpret=interp)
        return run

    cases.append((
        "latent chunk, the pool below the chunk and its own vectors",
        latent_chk("kernel"),
        (rnd((2, ltq, lnh * (ldn + ldr))), rnd((2, ltq, lnh, ldr)),
         rnd((lrank, lnh * (ldn + ldv))) * lrank ** -0.5, lpool, jnp.asarray(ctab), ccpos,
         rnd((2, ltq, lD)), cstart),
        latent_chk("dense"), 2e-2,
    ))
    # the write: lR decode rows' blocks and a chunk's, two layers of the pool
    wn = lR + ltq
    wblk = np.full(wn, lP, np.int32)
    wrow = np.zeros(wn, np.int32)
    wblk[: lR - 1], wrow[: lR - 1] = ltab[np.arange(lR - 1), lctx[: lR - 1] // lbs], lctx[: lR - 1] % lbs
    cp = int(cstart[0]) + np.arange(ltq)
    wblk[lR:], wrow[lR:] = ctab[0][cp // lbs], cp % lbs
    visits = tuple(jnp.asarray(v) for v in LP.write_visits(
        wblk, lP, lR + ltq // lbs + ltq // LP.WRITE_TILE + 3))

    def latent_wr(impl):
        def run(pool, new, blk, row, vis):
            out = LP.latent_write(pool, new, blk, row, vis, impl=impl, interpret=interp)
            return out[:, :lP]   # (the trash block holds whatever the padding left)
        return run

    cases.append((
        "latent write, a step's vectors merged into the pool's blocks",
        latent_wr("kernel"),
        (jnp.stack([lpool, lpool * 0.5]), rnd((2, wn, lD)), jnp.asarray(wblk), jnp.asarray(wrow), visits),
        latent_wr("dense"), 0.0,
    ))

    cases.append((
        "gdn decode, the state pool in place",
        gdn("interpret" if interp else "kernel"),
        (qg, kg, rnd((Rg, nvg, dg), jnp.float32), gates, betas,
         rnd((NS, nvg, dg, dg), jnp.float32, 0.1), slots),
        gdn("jnp"), 1e-4,
    ))
    return cases


def phase_kernels(size):
    import jax
    import numpy as np

    failures = []
    for name, fn, args, ref, tol in kernel_cases(size):
        mark = len(PALLAS_CALLS)
        try:
            t0 = time.perf_counter()
            lowered = jax.jit(fn).lower(*args)
            if not TINY and "tpu_custom_call" not in lowered.as_text():
                raise AssertionError("lowered program has no tpu_custom_call")
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0
            got = jax.block_until_ready(compiled(*args))
            t0 = time.perf_counter()
            got = jax.block_until_ready(compiled(*args))
            t_run = time.perf_counter() - t0
            ref_jit = jax.jit(ref)
            want = jax.block_until_ready(ref_jit(*args))
            t0 = time.perf_counter()
            jax.block_until_ready(ref_jit(*args))
            t_ref = time.perf_counter() - t0
            calls = PALLAS_CALLS[mark:]
            if not calls or any(i for _, i in calls) != TINY:
                raise AssertionError(f"pallas_calls {calls}")
            worst = 0.0
            for a, w_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                a, w_ = np.asarray(a, np.float32), np.asarray(w_, np.float32)
                if not np.all(np.isfinite(a)):
                    raise AssertionError("non-finite kernel output")
                worst = max(worst, float(np.max(np.abs(a - w_)) / max(1.0, np.max(np.abs(w_)))))
            if worst > tol:
                raise AssertionError(f"error {worst:.3e} exceeds {tol:.0e} of the reference's range")
            say(f"  ok   {name}: compile {t_compile:.1f}s run {t_run * 1e3:.2f}ms "
                f"(jnp reference {t_ref * 1e3:.2f}ms) err {worst:.2e} (tol {tol:.0e})")
        except Exception as e:  # report every refusal, then fail the phase
            failures.append(name)
            say(f"  FAIL {name}: {type(e).__name__}: {str(e)[:1500]}")
    if failures:
        raise AssertionError(f"{len(failures)} kernel case(s) failed: {failures}")


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------
def train_config(size):
    from deepspeed_tpu.models.transformer import get_config

    # ``bench-767m`` as it is trained: bf16, remat_policy=flash (in the
    # preset), int8 forward projections
    return get_config(size, dtype="bfloat16", matmul_precision="int8")


def run_train(cfg, bsz, steps, devices, mesh=None, label="train"):
    """initialize -> train_batch x steps on a repeated batch. Returns
    (losses, engine); the caller drops the engine to free the chip."""
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import init_params, make_loss_fn
    from deepspeed_tpu.parallel.topology import Topology, reset_topology

    reset_topology()
    seq = cfg.max_seq_len
    ds_config = {
        "train_batch_size": bsz,
        "bf16": {"enabled": True},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 3},
        "steps_per_print": 10**9,
    }
    with jax.default_device(devices[0]):
        params = init_params(cfg, jax.random.key(0))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=make_loss_fn(cfg), model_parameters=params, config=ds_config,
        mpu=Topology(devices=devices, **(mesh or {})),
    )
    del params
    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(bsz, seq + 1)).astype(np.int32)
    batch = {"input_ids": toks}
    mark = len(PALLAS_CALLS)
    losses, secs = [], []
    step_jit = None
    for i in range(steps):
        if i == 1:
            step_jit = engine._train_step_jit = CaptureArgs(engine._train_step_jit)
        t0 = time.perf_counter()
        losses.append(float(engine.train_batch(batch=batch)))  # float() syncs
        secs.append(time.perf_counter() - t0)
    say(f"  {label}: batch {bsz} x seq {seq} on {len(devices)} device(s), "
        f"losses {[round(l, 4) for l in losses]}")
    say(f"  {label}: step seconds {[round(t, 3) for t in secs]} "
        f"(first includes compile; smoke reading, not a benchmark)")
    if not all(np.isfinite(losses)):
        raise AssertionError(f"{label}: non-finite loss {losses}")
    if steps > 1 and not losses[-1] < losses[0]:
        raise AssertionError(f"{label}: loss did not fall: {losses}")
    assert_mosaic_since(mark, f"{label} step")
    if step_jit is not None:
        step_jit.assert_tpu_custom_call(f"{label} step")
    return losses, engine


def phase_train(size, devices):
    cfg = train_config(size)
    bsz = 2 if TINY else 6
    _, engine = run_train(cfg, bsz, steps=5, devices=devices[:1])
    say(f"  train: peak_bytes_in_use {peak_bytes(devices[0])}")
    del engine
    gc.collect()


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------
def post(port, body, timeout=600):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        raw = resp.read().decode()
    if body.get("stream"):
        return [json.loads(line)["token"] for line in raw.splitlines() if line.strip()]
    out = json.loads(raw)
    if out.get("error"):
        raise AssertionError(f"/generate error: {out}")
    return out["tokens"]


def get_health(port):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/health", timeout=60) as resp:
        return json.loads(resp.read().decode())


# A served token may sit this far below the best next-token logit of the
# teacher-forced training forward pass. Two bf16 paths through ten layers
# disagree by a few hundredths on logits of unit scale (largest seen on the
# v5e: see PERF.md); a token from a corrupted history lands whole units below.
NEAR_ARGMAX = 0.25


def teacher_forced_shortfall(cfg, params, prompts, streams):
    """How far below the best next-token logit each served token sits when
    ``models.forward`` — the training forward pass, not the serving engine —
    reads the request's own history. One [len(stream)] array per request."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models import forward

    longest = max(len(p) + len(s) for p, s in zip(prompts, streams))
    width = -(-longest // 128) * 128
    toks = np.zeros((len(prompts), width), np.int32)
    for i, (p, s) in enumerate(zip(prompts, streams)):
        toks[i, : len(p) + len(s)] = np.concatenate([p, np.asarray(s, np.int32)])

    def shortfall(params, toks):
        logits = forward(params, toks, cfg)[0].astype(jnp.float32)[:, :-1]
        chosen = jnp.take_along_axis(logits, toks[:, 1:, None], axis=-1)[..., 0]
        return logits.max(-1) - chosen  # [n, width - 1]

    gap = np.asarray(jax.jit(shortfall)(params, jnp.asarray(toks)))
    return [gap[i, len(p) - 1 : len(p) - 1 + len(s)] for i, (p, s) in enumerate(zip(prompts, streams))]


def serve_leg(size, devices, tp=1):
    """Build the serving stack from CLI arguments, serve over HTTP, check the
    tokens. One chip decodes through the Pallas kernels (at 2 KV heads: see
    below); a tensor-parallel engine (``tp`` > 1) takes the dense gather
    instead."""
    import jax
    import numpy as np

    from deepspeed_tpu.inference.cli import build_serving_stack, serve_parse_args
    from deepspeed_tpu.models import init_params
    from deepspeed_tpu.parallel.topology import Topology, reset_topology, set_topology
    from deepspeed_tpu.serving.server import start_server

    kernel = tp == 1
    label = "serve" if kernel else f"multi/serve tp={tp}"
    reset_topology()
    if kernel:  # one chip, whatever the host has
        set_topology(Topology(devices=devices[:1]))
    cfg = dataclasses.replace(train_config(size), remat=False, matmul_precision="default")
    if kernel and not TINY:
        # the preset's 6 KV heads are a geometry the paged kernels do not read
        # in place (paged_pallas.kernels_take: the compiler copies both pools
        # in front of every call), so ``auto`` serves it through the dense
        # gather; at 2 KV heads the same widths go through both kernels
        cfg = dataclasses.replace(cfg, n_kv_heads=2)
    max_new = 16
    lengths = [9, 40, 70, 130, 20] if TINY else [37, 150, 260, 411, 96]
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32) for n in lengths]
    n_req = len(prompts)

    params = init_params(cfg, jax.random.key(0))
    # CLI defaults; the stack compiles every shape of the split step as it is built
    args = serve_parse_args(["--model", "", "--port", "0", "--tp", str(tp)])
    mark = len(PALLAS_CALLS)
    driver, _ = build_serving_stack(args, cfg=cfg, params=params)
    engine = driver.engine
    say(f"  {label}: engine attention impl {engine._attn_impl!r}, tp {args.tp}, "
        f"kv blocks {args.num_blocks} x {args.block_size}")
    if kernel and not TINY and engine._attn_impl != "kernel":
        raise AssertionError(f"decode attention resolved to {engine._attn_impl!r}, not the kernel")

    def generate_each():
        return [[int(t) for t in engine.generate([p], max_new_tokens=max_new)[0][len(p):]]
                for p in prompts]

    t0 = time.perf_counter()
    generate_each()
    t_cold = time.perf_counter() - t0
    say(f"  {label}: engine.generate() over {n_req} prompts, first pass {t_cold:.1f}s "
        "(the programs were built with the stack)")
    if kernel:
        assert_mosaic_since(mark, f"{label} programs")
    # The oracle is the SECOND pass. In bf16 on the chip a token stream is
    # reproducible only under the same sequence of programs and the same
    # prefix-cache state: a prompt whose full blocks are cached prefills its
    # tail only, the logits move in their last bits, and a near-tie between
    # the two best tokens (random weights make many) can flip. The first pass
    # filled the cache, so the second sees what the HTTP requests will see.
    t0 = time.perf_counter()
    want = generate_each()
    t_warm = time.perf_counter() - t0
    decode_key = ("split", (0, 0))  # the split step of a batch with no chunk row
    decode_jit = engine._programs[decode_key] = CaptureArgs(engine._programs[decode_key])

    def body(i):
        return {"tokens": [int(t) for t in prompts[i]], "max_new_tokens": max_new,
                "stream": i == 1}

    driver.start()
    server = start_server(driver, port=0)
    try:
        port = server.server_address[1]
        # one at a time: the schedule generate() ran, so the tokens are equal
        t0 = time.perf_counter()
        one_by_one = [post(port, body(i)) for i in range(n_req)]
        t_seq = time.perf_counter() - t0
        for i, (g, w) in enumerate(zip(one_by_one, want)):
            if g != w:
                raise AssertionError(
                    f"request {i} (prompt {lengths[i]} tokens"
                    f"{', streamed' if i == 1 else ''}): served {g} != generate() {w}")
        say(f"  {label}: {n_req} POST /generate one at a time (request 1 streamed): "
            f"{sum(map(len, one_by_one))} tokens, all equal to engine.generate(); "
            f"{t_seq:.2f}s vs {t_warm:.2f}s for generate() (smoke readings)")
        if kernel:
            decode_jit.assert_tpu_custom_call(f"{label} decode step")

        # all at once: continuous batching interleaves them as they arrive, so
        # a row's tokens come from other shapes of the split step, beside other
        # rows, than generate() ran for it alone. Equality is not promised in
        # bf16; every token must still be one the model itself ranks (nearly)
        # first given the request's own history.
        together = [None] * n_req
        errors = []

        def client(i):
            try:
                together[i] = post(port, body(i))
            except Exception as e:  # surfaced below, in the main thread
                errors.append(f"request {i}: {type(e).__name__}: {e}")

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,)) for i in range(n_req)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        t_conc = time.perf_counter() - t0
        if errors or any(t.is_alive() for t in threads):
            raise AssertionError(f"concurrent HTTP requests failed or hung: {errors}")
        if any(len(g) != max_new for g in together):
            raise AssertionError(f"concurrent requests returned {[len(g) for g in together]} tokens")
        same = sum(g == w for g, w in zip(together, want))
        say(f"  {label}: {n_req} concurrent POST /generate: {sum(map(len, together))} tokens in "
            f"{t_conc:.2f}s (smoke reading); {same} of {n_req} streams equal generate() token "
            f"for token")

        deadline = time.time() + 30
        while True:
            health = get_health(port)
            blocks = engine.state_manager.kv_block_accounting()
            idle = (health["active_requests"] == 0 and health["queue_depth"] == 0
                    and blocks["live"] == 0
                    and health["kv_free_blocks"] == blocks["total"] - blocks["cached_only"])
            if idle or time.time() > deadline:
                break
            time.sleep(0.2)
        say(f"  {label}: /health status {health['status']!r} active {health['active_requests']} "
            f"queue {health['queue_depth']} kv_free_blocks {health['kv_free_blocks']} "
            f"of {health['kv_total_blocks']} ({blocks['cached_only']} held by the prefix cache)")
        if not idle:
            raise AssertionError(f"/health did not return to idle: {health}, {blocks}")
    finally:
        server.shutdown()
        server.server_close()
        driver.shutdown(drain=True, timeout=60)
    if driver._thread is not None and driver._thread.is_alive():
        raise AssertionError("serving driver thread did not stop")
    say(f"  {label}: drained and shut down; peak_bytes_in_use {peak_bytes(devices[0])}")
    del driver, engine
    gc.collect()
    # the reference forward pass runs on one device whatever the engine used
    reset_topology()
    set_topology(Topology(devices=devices[:1]))
    gaps = teacher_forced_shortfall(cfg, params, prompts * 2, one_by_one + together)
    worst_seq = max(float(g.max()) for g in gaps[:n_req])
    worst_conc = max(float(g.max()) for g in gaps[n_req:])
    say(f"  {label}: teacher-forced models.forward: worst shortfall below the best logit "
        f"{worst_seq:.3f} one at a time, {worst_conc:.3f} concurrent (limit {NEAR_ARGMAX})")
    if not max(worst_seq, worst_conc) <= NEAR_ARGMAX:
        raise AssertionError("a served token is not a near-argmax of the model's own logits")
    reset_topology()


# ---------------------------------------------------------------------------
# phase: multi (>= 4 devices)
# ---------------------------------------------------------------------------
def phase_multi(size, devices):
    import jax

    cfg = train_config(size)
    bsz = 4
    four = devices[:4]
    (one_loss, *_), engine = run_train(cfg, bsz, steps=1, devices=four[:1], label="multi/1-chip")
    del engine
    gc.collect()
    losses, engine = run_train(cfg, bsz, steps=4, devices=four, mesh={"data": 4},
                               label="multi/zero3 data=4")
    gap = abs(losses[0] - one_loss)
    say(f"  multi: first-step loss {losses[0]:.5f} on four chips vs {one_loss:.5f} on one "
        f"(gap {gap:.2e})")
    if gap > 2e-2:
        raise AssertionError("first-step loss disagrees between one chip and four")
    for what, tree in (("params", engine.params), ("fp32 masters", engine.opt_state.master)):
        leaves = jax.tree.leaves(tree)
        split = [x for x in leaves if x.addressable_shards[0].data.size < x.size]
        for leaf in split:
            shards = leaf.addressable_shards
            if len({s.device for s in shards}) != 4 or shards[0].data.size * 4 != leaf.size:
                raise AssertionError(f"{what}: {leaf.shape} is not split four ways: {leaf.sharding}")
        big = max(leaves, key=lambda x: x.size)
        say(f"  multi: {what}: {len(split)} of {len(leaves)} leaves partitioned, each over 4 "
            f"distinct devices; largest {big.shape} -> shards "
            f"{big.addressable_shards[0].data.shape} on "
            f"{sorted(s.device.id for s in big.addressable_shards)}")
        if big.addressable_shards[0].data.size == big.size:
            raise AssertionError(f"{what}: the largest leaf is replicated: {big.sharding}")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in four]
    say(f"  multi: per-device bytes_in_use {in_use}")
    if None not in in_use and max(in_use) > 1.5 * min(in_use):
        raise AssertionError(f"per-device memory is uneven: {in_use}")
    del engine
    gc.collect()
    # the same serving check with the engine split two ways over the model
    # axis; its tokens are held to the model's own logits, not to tp=1's
    # stream (another reduction order is another set of last bits)
    serve_leg(size, devices, tp=2)


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    global TINY
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--size", default="bench-767m", choices=("bench-767m", "tiny"),
                    help="tiny: CPU development run with interpreted kernels "
                    "(needs JAX_PLATFORMS=cpu; never a result)")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help=f"comma-separated subset of {PHASES}")
    args = ap.parse_args(argv)
    phases = [p for p in args.phases.split(",") if p]
    if not phases or any(p not in PHASES for p in phases):
        return fail(f"--phases takes a subset of {PHASES}")
    TINY = args.size == "tiny"
    if TINY and os.environ.get("JAX_PLATFORMS") != "cpu":
        return fail("--size tiny is a CPU development run: set JAX_PLATFORMS=cpu")

    import jax

    devices = jax.devices()
    dev = devices[0]
    if not TINY and dev.platform != "tpu":
        return fail(f"no TPU: JAX reports platform {dev.platform!r} "
                    f"({dev.device_kind}); this check runs on the chip only")
    if TINY and dev.platform != "cpu":
        return fail("--size tiny runs on the CPU only")

    import jaxlib

    from deepspeed_tpu.accelerator.device import device_peaks, setup_compile_cache
    from deepspeed_tpu.utils.logging import logger

    for handler in logger.handlers:  # stdout carries this script's lines only
        handler.setStream(sys.stderr)
    record_pallas_calls()
    counter = CacheCounter()
    cache_dir = setup_compile_cache()
    entries_before = cache_entries(cache_dir)
    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "not installed"
    say(f"platform: {dev.platform}  device_kind: {dev.device_kind}  devices: {len(devices)}")
    say(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  libtpu {libtpu}")
    say(f"compile cache: {cache_dir} ({entries_before} entries before)")
    if not TINY:
        say(f"peaks: {device_peaks()}")

    import jax.numpy as jnp

    bump = jax.jit(lambda x: x + 1)
    x = jax.block_until_ready(bump(jnp.zeros((), jnp.int32)))
    trips = []
    for _ in range(50):
        t0 = time.perf_counter()
        x = jax.block_until_ready(bump(x))
        trips.append(time.perf_counter() - t0)
    trips.sort()
    say(f"dispatch+sync round trip of a trivial jitted program: median "
        f"{trips[len(trips) // 2] * 1e6:.0f} us, max {trips[-1] * 1e6:.0f} us (50 calls)")

    runners = {
        "kernels": lambda: phase_kernels(args.size),
        "train": lambda: phase_train(args.size, devices),
        "serve": lambda: serve_leg(args.size, devices),
        "multi": lambda: phase_multi(args.size, devices),
    }
    t_all = time.perf_counter()
    for phase in phases:
        if phase == "multi" and len(devices) < 4:
            say(f"[multi] skipped: {len(devices)} device(s), needs 4")
            continue
        say(f"[{phase}]")
        t0 = time.perf_counter()
        hits0, miss0 = counter.hits, counter.misses
        try:
            runners[phase]()
        except Exception:
            traceback.print_exc()
            return fail(f"phase {phase} failed")
        say(f"[{phase}] passed in {time.perf_counter() - t0:.1f}s "
            f"(compile cache: {counter.hits - hits0} hits, {counter.misses - miss0} misses)")
    say(f"all phases passed in {time.perf_counter() - t_all:.1f}s; compile cache "
        f"{counter.hits} hits {counter.misses} misses, {cache_entries(cache_dir)} entries after "
        f"({entries_before} before); peak_bytes_in_use {peak_bytes(dev)}")
    if list(phases) != list(PHASES):
        say(f"partial run ({','.join(phases)}): no result line without every phase")
        return 0
    say(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
