"""Shared token sampling for the inference engines.

Reference semantics: v1 guard-railed generate (reference
inference/engine.py:585) + the FastGen/MII sampling layer on top of v2
logits (greedy, temperature, top-k, top-p nucleus). One jittable function
serves both engines so the two paths cannot drift; the fused multi-step
decode calls it in-device with a per-step folded rng (host round-trips per
token are the classic serving bottleneck — PERF.md serving roofline).

``top_k``/``top_p``/``greedy`` are STATIC (compile-time) knobs: top-p needs
a vocab sort that should not be paid when off, and lax.top_k takes a static
k. Temperature is traced.
"""

import jax
import jax.numpy as jnp

# Sharding-invariant random bits. The legacy threefry lowering lets GSPMD
# partition the counter math differently per mesh, so the SAME
# (seed, uid, position) key could sample different tokens on a tp=2 replica
# than on a tp=1 engine — breaking the content-addressed-stream guarantee
# that disaggregated placement relies on (a sequence must stream the same
# bytes wherever it decodes). The partitionable implementation generates
# bits as a pure per-element function of the key and counter, identical
# under any partitioning, which makes seeded streams bit-stable across
# tp layouts. It changes the raw stream vs the legacy lowering, so it is
# scoped to THIS module's key derivation and sampling (eager calls and
# jit traces alike — the context governs trace-time lowering), never set
# globally: flipping the process-wide flag would silently shift every
# other jax.random consumer's bits (training init, dropout, test data).
# jax.threefry_partitionable returns a single-use context manager, so a
# fresh one is minted per entry.
def _partitionable_bits():
    return jax.threefry_partitionable(True)

NEG_INF = -1e30


def filter_logits(logits, top_k: int = 0, top_p: float = 0.0):
    """Mask logits outside the top-k set and/or the top-p nucleus.
    logits: [..., vocab] fp32. Static knobs; 0 disables each filter."""
    if top_k and top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, NEG_INF, logits)
    if top_p and top_p > 0.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens until the cumulative mass crosses top_p (the crossing
        # token itself stays — HF convention)
        keep_sorted = cum - probs < top_p
        kth = jnp.max(jnp.where(keep_sorted, sorted_logits, NEG_INF), axis=-1, keepdims=True)
        logits = jnp.where(logits < kth, NEG_INF, logits)
    return logits


def row_keys(rng, uids, positions):
    """Content-addressed per-row sampling keys: fold each row's sequence
    uid and the GLOBAL position of its logits source into the base key.
    A token's key then depends only on (seed, uid, position) — never on
    how the scheduler packed the batch, how a prompt was chunked, which
    step program sampled it, or whether a prefix-cache hit skipped part
    of prefill — so sampled streams are bit-identical across all of those
    execution choices."""
    with _partitionable_bits():
        return jax.vmap(
            lambda u, p: jax.random.fold_in(jax.random.fold_in(rng, u), p)
        )(jnp.asarray(uids, jnp.int32), jnp.asarray(positions, jnp.int32))


def _is_key_batch(rng) -> bool:
    try:
        if jnp.issubdtype(rng.dtype, jax.dtypes.prng_key):
            return rng.ndim >= 1
    except (AttributeError, TypeError):
        pass
    return getattr(rng, "ndim", 0) >= 2  # raw uint32 keys: [R, 2]


def sample_tokens(
    logits,
    rng,
    temperature=1.0,
    greedy: bool = True,
    top_k: int = 0,
    top_p: float = 0.0,
    return_logprobs: bool = False,
):
    """Sample one token per row. logits: [R, vocab] fp32; rng: a PRNG key
    shared by all rows, or a batch of per-row keys (see ``row_keys``) for
    packing-invariant streams. Returns int32 [R] tokens, or (tokens,
    logprobs [R]) — the log-probability of the sampled token under the
    POST-filter, post-temperature distribution (greedy rows report the
    same quantity at the argmax)."""
    logits = logits.astype(jnp.float32)
    if greedy:
        toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        dist = logits
    else:
        # temperature FIRST, then top-k/top-p on the scaled logits (the HF /
        # MII LogitsWarper order: top_p mass is measured on the tempered
        # distribution, so temperature changes WHICH tokens survive the cut)
        dist = filter_logits(
            logits / jnp.maximum(temperature, 1e-4), top_k=top_k, top_p=top_p
        )
        with _partitionable_bits():
            if _is_key_batch(rng):
                toks = jax.vmap(
                    lambda k, d: jax.random.categorical(k, d)
                )(rng, dist).astype(jnp.int32)
            else:
                toks = jax.random.categorical(rng, dist).astype(jnp.int32)
    if not return_logprobs:
        return toks
    logp = jax.nn.log_softmax(dist, axis=-1)
    return toks, jnp.take_along_axis(logp, toks[:, None], axis=-1)[:, 0]
