"""Prefix-cache tests: refcounted allocator invariants, token-block trie
properties, pool conservation under serve/cancel/timeout, and the
acceptance bar — generated tokens bit-identical cache-on vs cache-off.

The serving-level tests reuse the compute-free FakeEngine pattern from
test_serving.py (real scheduler/allocator/cache stack, pure-Python steps);
the parity tests run the real v2 engine on a tiny model.
"""

import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from deepspeed_tpu.inference.config import KVCacheConfig, StateManagerConfig
from deepspeed_tpu.inference.v2.blocked_allocator import BlockedAllocator
from deepspeed_tpu.inference.v2.prefix_cache import PrefixCache
from deepspeed_tpu.inference.v2.ragged_manager import DSStateManager
from deepspeed_tpu.inference.v2.scheduler import RaggedScheduler
from deepspeed_tpu.serving.driver import ServingDriver
from deepspeed_tpu.serving.request import RequestState, SamplingParams

pytestmark = []


# ---------------------------------------------------------------------------
# refcounted allocator
# ---------------------------------------------------------------------------
class TestRefcountedAllocator:
    def test_share_free_lifecycle(self):
        a = BlockedAllocator(8)
        blocks = a.allocate(3)
        assert a.free_blocks == 5
        assert list(a.refcounts(blocks)) == [1, 1, 1]
        a.share(blocks)  # second holder
        assert list(a.refcounts(blocks)) == [2, 2, 2]
        a.free(blocks)  # first holder leaves: blocks stay allocated
        assert a.free_blocks == 5
        assert list(a.refcounts(blocks)) == [1, 1, 1]
        a.free(blocks)  # last holder leaves: blocks return to the pool
        assert a.free_blocks == 8
        assert list(a.refcounts(blocks)) == [0, 0, 0]

    def test_double_free_still_raises_after_sharing(self):
        a = BlockedAllocator(4)
        b = a.allocate(2)
        a.free(b)
        with pytest.raises(ValueError, match="double free"):
            a.free(b)

    def test_share_unallocated_raises(self):
        a = BlockedAllocator(4)
        with pytest.raises(ValueError, match="double free"):
            a.share([0])

    def test_failed_free_mutates_nothing(self):
        a = BlockedAllocator(8)
        good = a.allocate(2)
        a.share(good)
        bad = np.concatenate([good, np.asarray([good[0]], np.int64)])
        with pytest.raises(ValueError):
            a.free(bad)  # duplicate in one call: whole set rejected
        assert list(a.refcounts(good)) == [2, 2]
        assert a.free_blocks == 6

    def test_vectorized_ops_match_reference_model(self):
        """Randomized allocate/share/free against a dict-refcount model:
        the numpy stack splices must preserve exact conservation."""
        rng = np.random.default_rng(42)
        a = BlockedAllocator(64)
        model = {}  # block -> refcount
        held = []  # flat multiset of (block,) holder handles
        for _ in range(400):
            op = rng.integers(0, 3)
            if op == 0:  # allocate
                n = int(rng.integers(0, 9))
                if n <= a.free_blocks:
                    out = a.allocate(n)
                    assert len(set(int(b) for b in out)) == n
                    for b in out:
                        assert model.get(int(b), 0) == 0
                        model[int(b)] = 1
                        held.append(int(b))
                else:
                    with pytest.raises(ValueError):
                        a.allocate(n)
            elif op == 1 and held:  # share a random subset of holders
                pick = list({held[i] for i in rng.integers(0, len(held), 3)})
                a.share(pick)
                for b in pick:
                    model[b] += 1
                    held.append(b)
            elif op == 2 and held:  # free a random batch of holders
                uniq = list(set(held))
                rng.shuffle(uniq)
                pick = uniq[: int(rng.integers(1, 4))]
                a.free(pick)
                for b in pick:
                    model[b] -= 1
                    held.remove(b)
            # conservation + exact per-block agreement
            live = {b for b, c in model.items() if c > 0}
            assert a.free_blocks == a.total_blocks - len(live)
            assert set(int(b) for b in a.allocated_blocks) == live
            for b, c in model.items():
                assert a.refcount(b) == c

    def test_allocate_is_array_and_free_accepts_arrays(self):
        a = BlockedAllocator(16)
        out = a.allocate(5)
        assert isinstance(out, np.ndarray)
        a.free(out[:2])
        a.free(list(int(b) for b in out[2:]))
        assert a.free_blocks == 16


# ---------------------------------------------------------------------------
# token-block trie
# ---------------------------------------------------------------------------
def _cache(num_blocks=64, bs=4, max_cached=0):
    alloc = BlockedAllocator(num_blocks)
    return alloc, PrefixCache(bs, alloc, max_cached_blocks=max_cached)


def _prefill(alloc, cache, tokens):
    """Simulate a sequence prefilling ``tokens``: allocate its blocks and
    register the full ones. Returns the block table."""
    bs = cache.block_size
    table = alloc.allocate((len(tokens) + bs - 1) // bs)
    cache.insert(tokens[: (len(tokens) // bs) * bs], table)
    return table


class TestPrefixTrie:
    def test_insert_then_acquire_shares_blocks(self):
        alloc, cache = _cache()
        toks = list(range(10))  # 2 full blocks + partial
        table = _prefill(alloc, cache, toks)
        assert len(cache) == 2  # only FULL blocks cached
        # a new prompt with the same prefix hits both cached blocks
        blocks, n = cache.acquire(list(range(10)) + [99])
        assert n == 8 and list(blocks) == [int(table[0]), int(table[1])]
        assert alloc.refcount(table[0]) == 3  # seq + cache + new holder

    def test_match_capped_below_full_prompt(self):
        """A fully cached prompt still leaves >= 1 token to prefill (the
        engine needs next-token logits)."""
        alloc, cache = _cache(bs=4)
        toks = list(range(8))  # exactly 2 blocks
        _prefill(alloc, cache, toks)
        assert cache.peek(toks) == 1  # NOT 2: last block excluded
        blocks, n = cache.acquire(toks)
        assert n == 4
        assert cache.peek(list(range(9))) == 2  # one extra token: both match

    def test_peek_has_no_side_effects(self):
        alloc, cache = _cache()
        table = _prefill(alloc, cache, list(range(8)))
        before = list(alloc.refcounts(table))
        q0 = cache.stats()["queries"]
        assert cache.peek(list(range(12))) == 2
        assert list(alloc.refcounts(table)) == before
        assert cache.stats()["queries"] == q0

    def test_first_writer_wins_dedupe(self):
        alloc, cache = _cache()
        toks = list(range(12))
        t1 = _prefill(alloc, cache, toks)
        cached_before = set(cache.cached_block_ids())
        t2 = alloc.allocate(3)  # a second sequence prefilled the same prompt
        assert cache.insert(toks, t2) == 0  # nothing new cached
        assert set(cache.cached_block_ids()) == cached_before
        assert all(alloc.refcount(b) == 1 for b in t2)  # t2 stays private
        assert all(alloc.refcount(b) == 2 for b in t1)

    def test_divergent_prompts_share_common_prefix_only(self):
        alloc, cache = _cache(bs=4)
        common = list(range(4))
        _prefill(alloc, cache, common + [10, 11, 12, 13])
        _prefill(alloc, cache, common + [20, 21, 22, 23])
        assert len(cache) == 3  # 1 shared root block + 2 divergent children
        assert cache.peek(common + [20, 21, 22, 23] + [0]) == 2

    def test_lru_eviction_order(self):
        alloc, cache = _cache(bs=4)
        t1 = _prefill(alloc, cache, list(range(100, 104)))
        t2 = _prefill(alloc, cache, list(range(200, 204)))
        alloc.free(t1)
        alloc.free(t2)  # both sequences gone: cache-only blocks
        cache.acquire(list(range(100, 104)) + [0])  # touch t1's entry...
        alloc.free([int(t1[0])])  # ...and release the acquired ref again
        assert cache.evict(1) == 1
        assert cache.cached_block_ids() == [int(t1[0])]  # t2 (LRU) went first

    def test_eviction_respects_live_refs(self):
        alloc, cache = _cache(bs=4)
        t1 = _prefill(alloc, cache, list(range(8)))  # live sequence holds refs
        assert cache.evict(10) == 0  # nothing evictable
        alloc.free(t1)  # sequence finishes
        assert cache.evict(10) == 2
        assert alloc.free_blocks == alloc.total_blocks

    def test_eviction_leaves_first(self):
        alloc, cache = _cache(bs=4)
        t = _prefill(alloc, cache, list(range(12)))  # chain of 3 blocks
        alloc.free(t)
        assert cache.evict(1) == 1
        # the LEAF (deepest block) went; the chain's first two remain
        assert set(cache.cached_block_ids()) == {int(t[0]), int(t[1])}
        assert cache.evict(10) == 2
        assert len(cache) == 0

    def test_max_cached_blocks_cap(self):
        alloc, cache = _cache(bs=4, max_cached=2)
        t1 = _prefill(alloc, cache, list(range(8)))  # fills the cap
        alloc.free(t1)  # idle: evictable
        t2 = alloc.allocate(1)
        added = cache.insert(list(range(50, 54)), t2)
        assert added == 1
        assert len(cache) <= 2  # cap held via LRU eviction

    def test_clear_frees_idle_blocks(self):
        alloc, cache = _cache(bs=4)
        t = _prefill(alloc, cache, list(range(8)))
        alloc.free(t)
        assert cache.clear() == 2
        assert alloc.free_blocks == alloc.total_blocks
        assert len(cache) == 0 and cache.peek(list(range(9))) == 0

    def test_randomized_trie_conservation(self):
        """Random insert/acquire/release/evict interleavings: the pool
        conservation law holds at every step and every cached block keeps
        at least the cache's own reference."""
        rng = np.random.default_rng(7)
        alloc, cache = _cache(num_blocks=96, bs=4)
        live_tables = []  # block tables of "live sequences" (ref holders)
        for _ in range(300):
            op = rng.integers(0, 4)
            if op == 0:  # new sequence prefill (shared small vocab -> hits)
                n_tok = int(rng.integers(1, 24))
                toks = rng.integers(0, 3, size=n_tok).tolist()
                blocks, n_cached = cache.acquire(toks)
                need = (n_tok + 3) // 4 - len(blocks)
                if need <= alloc.free_blocks:
                    rest = alloc.allocate(need)
                    table = list(blocks) + list(rest)
                    cache.insert(toks[: (n_tok // 4) * 4], table)
                    live_tables.append(table)
                elif len(blocks):
                    alloc.free(blocks)  # admission failed: release the hit
            elif op == 1 and live_tables:  # finish a sequence
                idx = int(rng.integers(0, len(live_tables)))
                alloc.free(live_tables.pop(idx))
            elif op == 2:  # pressure eviction
                cache.evict(int(rng.integers(0, 4)))
            else:  # probe
                cache.peek(rng.integers(0, 3, size=int(rng.integers(1, 20))).tolist())
            # invariants
            live = {int(b) for t in live_tables for b in t}
            cached = set(cache.cached_block_ids())
            assert alloc.free_blocks + len(live | cached) == alloc.total_blocks
            for b in cached:
                assert alloc.refcount(b) >= 1
        for t in live_tables:
            alloc.free(t)
        cache.evict(10**6)
        assert alloc.free_blocks == alloc.total_blocks


class TestEvictionRegression:
    """Pins the eviction contract the host tier's spill path leans on:
    blocks a live sequence still references are NEVER evicted no matter
    the pressure, idle blocks go in strict LRU order, and the spill hook
    fires exactly on eviction (never on ``clear``), before the block
    returns to the free list."""

    def test_live_refs_survive_arbitrary_pressure(self):
        alloc, cache = _cache(num_blocks=32, bs=4)
        live = _prefill(alloc, cache, list(range(100, 112)))  # 3-block chain
        idle = _prefill(alloc, cache, list(range(200, 212)))
        alloc.free(idle)  # this chain is cache-only: fair game
        for _ in range(5):  # repeated mass evictions, way past pool size
            cache.evict(10**6)
        cached = set(cache.cached_block_ids())
        assert {int(b) for b in live} <= cached  # live chain untouched
        assert not ({int(b) for b in idle} & cached)  # idle chain gone
        # the live sequence's refs are intact: seq + cache on each block
        assert list(alloc.refcounts(live)) == [2, 2, 2]
        # once the sequence finishes, the same blocks become evictable
        alloc.free(live)
        assert cache.evict(10**6) == 3
        assert alloc.free_blocks == alloc.total_blocks

    def test_partial_chain_pins_prefix(self):
        """A live sequence sharing only the chain HEAD pins that head:
        eviction may take the idle tail leaves but never the shared
        prefix blocks above them."""
        alloc, cache = _cache(bs=4)
        common = list(range(4))
        t1 = _prefill(alloc, cache, common + [10, 11, 12, 13])
        # second sequence acquires (shares) only the common head block
        head, n = cache.acquire(common + [99])
        assert n == 4 and list(head) == [int(t1[0])]
        alloc.free(t1)  # first sequence finishes; head still shared
        assert cache.evict(10**6) == 1  # only the idle leaf went
        assert cache.cached_block_ids() == [int(t1[0])]
        alloc.free(head)
        assert cache.evict(10**6) == 1

    def test_strict_lru_idle_order(self):
        """Idle blocks leave in exactly last-touched order, one evict(1)
        at a time — the order the host tier's spill stream sees."""
        alloc, cache = _cache(num_blocks=32, bs=4)
        chains = {}
        for i in range(4):
            toks = [400 + 10 * i + j for j in range(4)]  # disjoint chains
            t = _prefill(alloc, cache, toks)
            alloc.free(t)
            chains[i] = (toks, int(t[0]))
        touch_order = [2, 0, 3, 1]  # recency, oldest first after touching
        for i in touch_order:
            toks, block = chains[i]
            got, n = cache.acquire(toks + [7])  # distinct last_used each
            assert n == 4 and list(got) == [block]
            alloc.free(got)
        evicted = []
        while True:
            before = set(cache.cached_block_ids())
            if not cache.evict(1):
                break
            evicted += list(before - set(cache.cached_block_ids()))
        assert evicted == [chains[i][1] for i in touch_order]

    def test_spill_hook_on_evict_only_and_before_free(self):
        from deepspeed_tpu.inference.v2.host_tier import chain_hashes

        alloc, cache = _cache(bs=4)
        toks = list(range(8))
        t = _prefill(alloc, cache, toks)
        alloc.free(t)
        spilled = []

        def spill(hkey, block):
            # spill runs BEFORE the block returns to the free list: the
            # pool rows are still safe to export at this point
            assert alloc.refcount(block) == 1
            spilled.append((hkey, block))

        cache.spill_fn = spill
        assert cache.evict(10**6) == 2
        # hooks fired for both blocks with the content-addressed chain
        # hashes (leaf first), matching chain_hashes exactly
        keys = chain_hashes(toks, 4)
        assert spilled == [(keys[1], int(t[1])), (keys[0], int(t[0]))]
        # clear() is failure recovery — device KV may be garbage, so it
        # must NOT feed the host tier
        t2 = _prefill(alloc, cache, toks)
        alloc.free(t2)
        spilled.clear()
        assert cache.clear() == 2
        assert spilled == []


# ---------------------------------------------------------------------------
# state-manager bridge
# ---------------------------------------------------------------------------
def _manager(bs=4, num_blocks=32, max_per_seq=8, cache_on=True):
    kv = KVCacheConfig(block_size=bs, num_blocks=num_blocks,
                       max_blocks_per_seq=max_per_seq, prefix_cache=cache_on)
    sm = StateManagerConfig(max_tracked_sequences=16, max_ragged_batch_size=64,
                            max_ragged_sequence_count=8, max_context=4096)
    return DSStateManager(sm, kv), sm, kv


class TestManagerBridge:
    def test_seed_from_cache_and_accounting(self):
        mgr, _, _ = _manager()
        a = mgr.get_or_create_sequence(1)
        a.tokens = list(range(12))
        assert mgr.extend(a, 12)
        a.seen_tokens = 12
        mgr.cache_prefill_blocks(a, 12)
        b = mgr.get_or_create_sequence(2)
        n = mgr.seed_from_cache(b, list(range(12)) + [99, 100])
        assert n == 12 and b.seen_tokens == 12
        assert b.block_table == a.block_table[:3]
        acct = mgr.kv_block_accounting()
        assert acct["free"] + acct["live"] + acct["cached_only"] == acct["total"]
        mgr.flush_sequence(1)
        mgr.flush_sequence(2)
        acct = mgr.kv_block_accounting()
        assert acct["live"] == 0 and acct["cached_only"] == 3
        assert acct["free"] + acct["cached_only"] == acct["total"]

    def test_seed_noop_for_nonfresh_or_cacheless(self):
        mgr, _, _ = _manager(cache_on=False)
        s = mgr.get_or_create_sequence(1)
        assert mgr.seed_from_cache(s, list(range(8))) == 0
        mgr2, _, _ = _manager()
        s2 = mgr2.get_or_create_sequence(1)
        s2.seen_tokens = 4  # mid-flight: never reseed
        assert mgr2.seed_from_cache(s2, list(range(8))) == 0

    def test_extend_evicts_idle_cache_under_pressure(self):
        mgr, _, _ = _manager(bs=4, num_blocks=8, max_per_seq=8)
        a = mgr.get_or_create_sequence(1)
        a.tokens = list(range(24))
        assert mgr.extend(a, 24)  # 6 of 8 blocks
        mgr.cache_prefill_blocks(a, 24)
        mgr.flush_sequence(1)  # cache keeps all 6 blocks; 2 free
        b = mgr.get_or_create_sequence(2)
        b.tokens = list(range(100, 120))
        assert mgr.extend(b, 20)  # needs 5: evicts 3 LRU cached blocks
        assert mgr.prefix_cache.evictions >= 3
        acct = mgr.kv_block_accounting()
        assert acct["free"] + acct["live"] + acct["cached_only"] == acct["total"]


# ---------------------------------------------------------------------------
# scheduler packing: oldest-first anti-starvation
# ---------------------------------------------------------------------------
class TestSchedulerPacking:
    def _sched(self, max_chunks=1, chunk=8):
        mgr, sm, _ = _manager(bs=4, num_blocks=128, max_per_seq=32)
        return RaggedScheduler(sm, mgr, prompt_chunk=chunk,
                               max_prompt_chunks=max_chunks), mgr

    def test_oldest_pending_gets_first_chunk_slot(self):
        """Shorter (cache-hit-like) prompts arriving later cannot starve
        the oldest cold prompt out of the single chunk slot."""
        sched, _ = self._sched(max_chunks=1, chunk=8)
        sched.submit(1, list(range(500, 524)))  # cold: 24 tokens, 3 chunks
        sched.submit(2, [1, 2])  # short latecomers
        sched.submit(3, [3, 4])
        batch = sched.next_batch()
        assert batch.uids == [1]  # oldest wins the slot, not the shortest

    def test_shortest_remaining_fills_later_slots(self):
        sched, _ = self._sched(max_chunks=2, chunk=8)
        sched.submit(1, list(range(500, 524)))
        sched.submit(2, list(range(600, 606)))  # 6 tokens
        sched.submit(3, [3, 4])  # 2 tokens: shortest
        batch = sched.next_batch()
        assert batch.uids == [1, 3]  # oldest first, then shortest-remaining

    def test_arrival_order_breaks_ties(self):
        sched, _ = self._sched(max_chunks=3, chunk=8)
        sched.submit(1, list(range(24)))
        sched.submit(2, [1, 2])
        sched.submit(3, [3, 4])  # same length as uid 2: earlier arrival wins
        batch = sched.next_batch()
        assert batch.uids == [1, 2, 3]


# ---------------------------------------------------------------------------
# serving stack: conservation under serve/cancel/timeout + failure recovery
# ---------------------------------------------------------------------------
class CachedFakeEngine:
    """test_serving.FakeEngine with the prefix cache ON (next token =
    last + 1; the scheduler/allocator/cache stack underneath is real)."""

    def __init__(self, block_size=4, num_blocks=256, max_blocks_per_seq=16,
                 max_tracked=32, batch_budget=64, max_rows=16,
                 max_context=4096, step_delay=0.0):
        kv = KVCacheConfig(block_size=block_size, num_blocks=num_blocks,
                           max_blocks_per_seq=max_blocks_per_seq,
                           prefix_cache=True)
        sm = StateManagerConfig(
            max_tracked_sequences=max_tracked,
            max_ragged_batch_size=batch_budget,
            max_ragged_sequence_count=max_rows,
            max_context=max_context,
        )
        self.config = SimpleNamespace(kv_cache=kv, state_manager=sm)
        self.state_manager = DSStateManager(sm, kv)
        self.scheduler = RaggedScheduler(sm, self.state_manager)
        self.last_capped = set()
        self.step_delay = step_delay
        self.fail_next = 0

    def step_tokens(self):
        if self.fail_next > 0:
            self.fail_next -= 1
            raise RuntimeError("injected engine failure")
        if self.step_delay:
            time.sleep(self.step_delay)
        batch = self.scheduler.next_batch()
        self.last_capped |= self.scheduler.drain_capped()
        if batch is None:
            return {}
        out = {}
        for uid, toks, chunked in zip(batch.uids, batch.tokens, batch.is_prompt_chunk):
            seq = self.state_manager.get_sequence(uid)
            seq.seen_tokens += len(toks)
            if not chunked:
                out[uid] = int(toks[-1]) + 1
        return out


class TestServingConservation:
    def test_invariant_under_serve_cancel_timeout(self):
        """The PR acceptance invariant: free + live(deduped) + cached(idle)
        == total after a mixed serve/cancel/timeout workload, and again
        after drain (live == 0)."""
        # max_blocks_per_seq=64 gives the open-ended requests a ~240-step
        # runway so cancel/timeout land while they are genuinely mid-decode
        eng = CachedFakeEngine(step_delay=0.002, max_blocks_per_seq=64,
                               max_context=256)
        driver = ServingDriver(eng, max_queue=64)
        driver.start()

        shared = list(range(1000, 1012))  # 3 full blocks shared
        warm = driver.submit(np.asarray(shared + [1], np.int32),
                             params=SamplingParams(max_new_tokens=2, ignore_eos=True))
        assert warm.wait(30)  # prefix now cached: the rest all hit
        reqs = []
        for i in range(8):
            reqs.append(driver.submit(
                np.asarray(shared + [2000 + 10 * i, 2001 + 10 * i], np.int32),
                params=SamplingParams(max_new_tokens=8, ignore_eos=True)))
        victim = driver.submit(
            np.asarray(shared + [3000], np.int32),
            params=SamplingParams(max_new_tokens=10000, ignore_eos=True))
        timed = driver.submit(
            np.asarray(shared + [4000], np.int32),
            params=SamplingParams(max_new_tokens=10000, ignore_eos=True),
            timeout_s=0.05)
        time.sleep(0.03)
        assert driver.cancel(victim.uid)

        for r in reqs:
            assert r.wait(30)
        assert victim.wait(30) and timed.wait(30)
        assert victim.state == RequestState.CANCELLED
        assert timed.state == RequestState.TIMED_OUT
        for r in reqs:
            assert r.state == RequestState.FINISHED

        driver.shutdown(drain=True, timeout=30)
        acct = eng.state_manager.kv_block_accounting()
        assert acct["free"] + acct["live"] + acct["cached_only"] == acct["total"]
        assert acct["live"] == 0  # everything flushed
        assert acct["cached_only"] >= 3  # the shared prefix stayed cached
        # every cached block's only holder is now the cache itself
        cache = eng.state_manager.prefix_cache
        for b in cache.cached_block_ids():
            assert eng.state_manager._alloc.refcount(b) == 1
        assert cache.stats()["hits"] >= 10  # every post-warm request hit

    def test_admission_charges_only_uncached_blocks(self):
        """A hot shared prefix multiplies effective capacity: requests that
        would NOT fit if fully charged are admitted when the cache covers
        their prefix."""
        # pool of 16; shared prefix takes 3 + each request needs 2 private
        eng = CachedFakeEngine(num_blocks=16, max_blocks_per_seq=8,
                               batch_budget=256, step_delay=0.0)
        driver = ServingDriver(eng, max_queue=32)
        driver.start()
        shared = list(range(1000, 1012))  # 3 full blocks
        warm = driver.submit(np.asarray(shared + [1], np.int32),
                             params=SamplingParams(max_new_tokens=2, ignore_eos=True))
        assert warm.wait(30)
        # charged need per request: prompt 13 + 2 new = ceil(15/4) = 4 blocks,
        # minus 3 cached = 1. Five concurrent requests charge 5 blocks total
        # (uncharged would be 20 > pool).
        reqs = [driver.submit(np.asarray(shared + [10 + i], np.int32),
                              params=SamplingParams(max_new_tokens=2, ignore_eos=True))
                for i in range(5)]
        for r in reqs:
            assert r.wait(30)
            assert r.state == RequestState.FINISHED
        driver.shutdown(drain=True, timeout=30)
        assert eng.state_manager.prefix_cache.stats()["hits"] >= 5

    def test_engine_failure_clears_cache(self):
        """After an engine-level step failure the cached KV is untrusted:
        the driver fails the in-flight set AND drops the whole trie."""
        eng = CachedFakeEngine(step_delay=0.001)
        driver = ServingDriver(eng, max_queue=16)
        driver.start()
        warm = driver.submit(np.arange(100, 112, dtype=np.int32),
                             params=SamplingParams(max_new_tokens=2, ignore_eos=True))
        assert warm.wait(30)
        assert len(eng.state_manager.prefix_cache) > 0
        r = driver.submit(np.arange(200, 212, dtype=np.int32),
                          params=SamplingParams(max_new_tokens=50, ignore_eos=True))
        time.sleep(0.02)
        eng.fail_next = 1
        assert r.wait(30)
        assert r.state == RequestState.FAILED
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and len(eng.state_manager.prefix_cache):
            time.sleep(0.01)
        assert len(eng.state_manager.prefix_cache) == 0
        # a fresh request still serves fine (cold)
        r2 = driver.submit(np.arange(300, 306, dtype=np.int32),
                           params=SamplingParams(max_new_tokens=3, ignore_eos=True))
        assert r2.wait(30) and r2.state == RequestState.FINISHED
        driver.shutdown(drain=True, timeout=30)
        assert eng.state_manager.free_blocks + len(eng.state_manager.prefix_cache) \
            == eng.state_manager._alloc.total_blocks

    def test_cache_off_returns_pool_to_fully_free(self):
        """With the cache off nothing holds blocks after drain (the
        pre-existing test_serving expectation stays true)."""
        from tests.unit.test_serving import FakeEngine

        eng = FakeEngine()
        driver = ServingDriver(eng, max_queue=8)
        driver.start()
        r = driver.submit(np.arange(1, 13, dtype=np.int32),
                          params=SamplingParams(max_new_tokens=4, ignore_eos=True))
        assert r.wait(30)
        driver.shutdown(drain=True, timeout=30)
        assert eng.state_manager.free_blocks == eng.config.kv_cache.num_blocks


# ---------------------------------------------------------------------------
# output parity: cache on vs off must be bit-identical (acceptance bar)
# ---------------------------------------------------------------------------
def _tiny_engine(prefix_cache, greedy, seed=7):
    import jax

    from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import get_config, init_params

    cfg = get_config("tiny", n_layers=2, dtype="float32", max_seq_len=512)
    params = init_params(cfg, jax.random.key(0))
    rc = RaggedInferenceEngineConfig.from_dict({
        "dtype": "float32",
        "greedy": greedy, "temperature": 0.9, "seed": seed,
        "kv_cache": {"block_size": 4, "num_blocks": 128,
                     "max_blocks_per_seq": 32, "prefix_cache": prefix_cache},
        "state_manager": {"max_tracked_sequences": 16,
                          "max_ragged_batch_size": 256,
                          "max_ragged_sequence_count": 8, "max_context": 256},
    })
    return InferenceEngineV2(cfg, params, rc)


def _two_wave_generate(engine, prompts, max_new=10):
    """Wave 1 warms the cache, wave 2 hits it — mirrors real serving."""
    outs = [np.asarray(o) for o in engine.generate(
        [list(prompts[0])], max_new_tokens=max_new)]
    outs += [np.asarray(o) for o in engine.generate(
        [list(p) for p in prompts[1:]], max_new_tokens=max_new)]
    return outs


def _parity_prompts():
    rng = np.random.default_rng(0)
    sys_prompt = rng.integers(0, 128, size=13).tolist()
    prompts = [sys_prompt + rng.integers(0, 128, size=n).tolist()
               for n in (5, 9, 3)]
    prompts.append(rng.integers(0, 128, size=11).tolist())  # cold
    return prompts


class TestOutputParity:
    def test_greedy_bit_identical(self):
        prompts = _parity_prompts()
        off = _two_wave_generate(_tiny_engine(False, greedy=True), prompts)
        eng = _tiny_engine(True, greedy=True)
        on = _two_wave_generate(eng, prompts)
        assert eng.prefix_cache.stats()["hits"] >= 1  # the cache actually hit
        for a, b in zip(off, on):
            np.testing.assert_array_equal(a, b)

    def test_sampled_bit_identical(self):
        """Seeded temperature sampling: per-row keys are content-addressed
        on (seed, uid, position), so a prefix-cache hit skipping part of
        prefill cannot shift the sampled stream."""
        prompts = _parity_prompts()
        off = _two_wave_generate(_tiny_engine(False, greedy=False), prompts)
        eng = _tiny_engine(True, greedy=False)
        on = _two_wave_generate(eng, prompts)
        assert eng.prefix_cache.stats()["hits"] >= 1
        for a, b in zip(off, on):
            np.testing.assert_array_equal(a, b)

    def test_sampled_parity_with_the_serving_driver(self):
        """``generate()`` and the serving driver sample identical streams
        over a warm cache: one step in flight on both, a hit on both."""
        from tests.unit.simple_model import served_tokens

        prompts = _parity_prompts()
        engines = [_tiny_engine(True, greedy=False) for _ in range(2)]
        for eng in engines:  # warm: the shared system prompt is cached
            eng.generate([list(prompts[0])], max_new_tokens=10)
        outs = engines[0].generate([list(p) for p in prompts[1:]], max_new_tokens=10)
        driven = served_tokens(engines[1], [np.asarray(p, np.int32) for p in prompts[1:]], 10)
        assert all(eng.prefix_cache.stats()["hits"] >= 1 for eng in engines)
        for p, out, got in zip(prompts[1:], outs, driven):
            assert [int(t) for t in out[len(p):]] == got
