"""One step in flight: the serving core launches step n+1 before it collects
step n, and a decode row of n+1 takes the token step n sampled from the
device (``last_tokens`` / ``tok_src``), never through the host.

Every case runs on three toys, float32, on the CPU: a dense stack, the hybrid
(Gated DeltaNet layers: a state slot a sequence beside its K/V blocks) and the
window stack (a ring of window blocks a sequence). The oracle is a plain loop
of synchronous ``engine.step_tokens()`` on a second engine of the same toy:
per request the same token at the same position. The driver is never started:
the test is its loop, pass by pass, so that what is in flight at each stop,
cancel or failure is the same in every run."""

import time

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2 import engine_v2
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.observability import NULL_TRACER, SpanTracer, set_tracer
from deepspeed_tpu.serving.cluster.core import EngineCore
from deepspeed_tpu.serving.driver import ServingDriver
from deepspeed_tpu.serving.request import RequestState, SamplingParams
from tests.unit import test_k_exaone_serving as window_toy
from tests.unit import test_qwen3_next_serving as hybrid_toy

R = 4  # decode slots of a step
CHUNK = 40


def _dense_model():
    from deepspeed_tpu.models import get_config, init_params

    cfg = get_config("tiny", n_layers=2, dtype="float32", max_seq_len=512)
    return cfg, init_params(cfg, jax.random.key(0))


MODELS = {"dense": _dense_model, "hybrid": hybrid_toy._model, "window": window_toy._model}
_built = {}


@pytest.fixture(params=sorted(MODELS))
def toy(request):
    """(name, a maker of engines of that toy): blocks of 8 tokens, chunks of
    40, R = 4 decode slots and 2 chunk rows a step."""
    name = request.param
    if name not in _built:
        _built[name] = MODELS[name]()
    cfg, params = _built[name]

    def make(**extra):
        rc = {
            "dtype": "float32", "prompt_chunk": CHUNK, "max_prompt_chunks": 2,
            "kv_cache": {"block_size": 8, "num_blocks": 96, "max_blocks_per_seq": 32},
            "state_manager": {"max_tracked_sequences": 6, "max_ragged_batch_size": 128,
                              "max_ragged_sequence_count": R, "max_context": 256},
            **extra,
        }
        return InferenceEngineV2(cfg, params, RaggedInferenceEngineConfig.from_dict(rc))

    return name, make


def _prompt(i, n, vocab=120):
    return (np.random.default_rng(100 + i).integers(1, vocab, size=n)).astype(np.int32)


# (the pass it arrives at, prompt length, max_new_tokens): chunked prompts (40
# a chunk), a one-token answer, five requests for four decode slots, and
# arrivals while others decode
MIX = [(0, 5, 6), (0, 70, 9), (3, 100, 4), (7, 33, 12), (9, 12, 1)]


def _sync_reference(engine, work, stops=None):
    """The plain loop: one ``step_tokens()`` a pass, every token through the
    host. ``work``: (arrival pass, prompt, max_new); uid = its index.
    ``stops``: {uid: token that ends it}."""
    sched = engine.scheduler
    out = {uid: [] for uid in range(len(work))}
    nxt = step = 0
    while nxt < len(work) or sched.has_work():
        while nxt < len(work) and work[nxt][0] <= step:
            sched.submit(nxt, work[nxt][1])
            nxt += 1
        for uid, tok in engine.step_tokens().items():
            out[uid].append(tok)
            if len(out[uid]) >= work[uid][2] or tok == (stops or {}).get(uid):
                sched.finish(uid)
            else:
                sched.feedback(uid, tok)
        step += 1
        assert step < 500
    return out


def _one_pass(driver):
    """One pass of ``ServingDriver._loop``, by hand."""
    with driver._cond:
        driver._expire_locked()
        driver._admit_locked()
    stepped = driver.core.has_work() and driver._step_once()
    with driver._cond:
        driver._admit_locked()
        driver._update_metrics_locked()
    return stepped


def _submit(driver, prompt, max_new, **kw):
    return driver.submit(prompt, params=SamplingParams(
        max_new_tokens=max_new, ignore_eos=True, **kw.pop("params", {})), **kw)


def _serve(driver, work):
    """The driver's loop over ``work``, arrivals by pass; returns the requests."""
    reqs = []
    step = 0
    while len(reqs) < len(work) or driver.core.has_work() or driver._queue:
        while len(reqs) < len(work) and work[len(reqs)][0] <= step:
            uid = len(reqs)
            reqs.append(_submit(driver, work[uid][1], work[uid][2]))
            assert reqs[-1].uid == uid
        _one_pass(driver)
        step += 1
        assert step < 500
    return reqs


def _all_free(engine):
    acct = engine.state_manager.kv_block_accounting()
    slots = engine.state_manager.state_slot_accounting()
    return (acct["free"] == acct["total"] and slots["free"] == slots["total"]
            and acct.get("window_free") == acct.get("window_total"))


def _work(mix=MIX):
    return [(at, _prompt(i, n), new) for i, (at, n, new) in enumerate(mix)]


def test_served_tokens_equal_the_synchronous_loop(toy):
    """(a), (c), (g): a ragged mix with chunked prompts and arrivals
    mid-stream, token for token; stops by length drop nothing; the last step
    in flight is collected, and every program compiled once."""
    _name, make = toy
    work = _work()
    want = _sync_reference(make(), work)
    engine = make()
    driver = ServingDriver(engine)
    reqs = _serve(driver, work)
    for uid, req in enumerate(reqs):
        assert req.state == RequestState.FINISHED and req.finish_reason == "max_tokens"
        assert req.generated == want[uid], f"uid {uid}"
    c = driver.metrics.counters
    assert c["decode_tokens_total"] == sum(new for _, _, new in MIX)
    assert c["ahead_rows_dropped_total"] == 0
    # every step but the first was launched with its predecessor in flight
    assert c["steps_ahead_total"] == c["engine_steps_total"] - 1 > 10
    assert not driver.core.has_work() and not driver.core.step_in_flight and _all_free(engine)
    assert driver._idle.is_set()
    # one program a key, whatever ``last_tokens`` was (zeros, then an output)
    assert sorted(engine._programs) == [("split", (0, 0)), ("split", (1, CHUNK)), ("split", (2, CHUNK))]
    assert all(fn._cache_size() == 1 for fn in engine._programs.values())


def _stop_token(tokens, at_least=2):
    """(index, token) of the first token from ``at_least`` on that did not
    occur before it: a stop token that ends the stream exactly there."""
    for i in range(at_least, len(tokens) - 1):
        if tokens[i] not in tokens[:i]:
            return i, tokens[i]
    raise AssertionError(f"no fresh token in {tokens}")


def test_a_stop_only_the_token_shows_drops_the_row_in_flight(toy):
    """(b): the request stops on a token. By then its next row is in flight:
    that row's token is never delivered nor counted, its blocks, state slot
    and ring are free at once, and the request that gets them next (its first
    chunk in the very next launch) serves its reference tokens."""
    _name, make = toy
    first = [(0, _prompt(0, 20), 12)]
    at, stop = _stop_token(_sync_reference(make(), first)[0])
    work = first + [(0, _prompt(1, 50), 6)]
    want = _sync_reference(make(), work, stops={0: stop})
    assert len(want[0]) == at + 1

    engine = make()
    driver = ServingDriver(engine)
    a = _submit(driver, work[0][1], 12, params={"stop_token_ids": [stop]})
    while not a.is_terminal:
        assert _one_pass(driver)
    assert a.finish_reason == "stop_token" and a.generated == want[0]
    # stopped at its collect, with the step after it launched: its row there
    assert driver.core.step_in_flight and 0 in driver.core._flight[0].rows
    assert _all_free(engine), "freed at once, the row in flight notwithstanding"
    b = _submit(driver, work[1][1], 6)
    _one_pass(driver)  # launches b's first chunk, then collects and drops a's row
    c = driver.metrics.counters
    assert c["ahead_rows_dropped_total"] == 1 and c["decode_tokens_total"] == at + 1
    assert a.generated == want[0]
    while not b.is_terminal:
        assert _one_pass(driver)
    assert b.generated == want[1] and c["ahead_rows_dropped_total"] == 1
    assert c["decode_tokens_total"] == at + 1 + 6
    assert not driver.core.has_work() and _all_free(engine)


@pytest.mark.parametrize("how", ["cancel", "timeout"])
def test_cancel_and_timeout_with_a_row_in_flight(toy, how):
    """(d): the request goes between two passes; its row in flight is
    computed and dropped, the others' streams are untouched."""
    _name, make = toy
    work = [(0, _prompt(0, 30), 30), (0, _prompt(1, 12), 8)]
    want = _sync_reference(make(), work)
    engine = make()
    driver = ServingDriver(engine)
    a = _submit(driver, work[0][1], 30, **({"timeout_s": 3600.0} if how == "timeout" else {}))
    b = _submit(driver, work[1][1], 8)
    while len(a.generated) < 3:
        assert _one_pass(driver)
    assert a.uid in driver.core._flight[0].rows
    if how == "cancel":
        assert driver.cancel(a.uid)
    else:
        a.deadline = time.monotonic() - 1.0
    while driver.core.has_work():
        _one_pass(driver)
    assert a.state == (RequestState.CANCELLED if how == "cancel" else RequestState.TIMED_OUT)
    assert a.generated == want[0][: len(a.generated)] and 3 <= len(a.generated) < 30
    assert b.state == RequestState.FINISHED and b.generated == want[1]
    c = driver.metrics.counters
    assert c["ahead_rows_dropped_total"] == 1
    assert c["decode_tokens_total"] == len(a.generated) + 8
    assert _all_free(engine)


@pytest.mark.parametrize("where", ["launch_step", "collect_step"])
def test_an_engine_failure_with_a_step_in_flight_fails_the_active_set_once(toy, where,
                                                                           monkeypatch):
    """(e): the launch of step n+1 or the collect of step n raises. Both
    active requests fail, once; nothing stays in flight or expected; the
    next request is served, to its reference."""
    _name, make = toy
    engine = make()
    driver = ServingDriver(engine)
    reqs = [_submit(driver, _prompt(i, 20), 20) for i in range(2)]
    while min(len(r.generated) for r in reqs) < 2:
        assert _one_pass(driver)
    assert driver.core.step_in_flight
    real = getattr(engine, where)

    def boom(*args):
        monkeypatch.setattr(engine, where, real)
        raise RuntimeError("injected")

    monkeypatch.setattr(engine, where, boom)
    _one_pass(driver)
    assert all(r.state == RequestState.FAILED and r.finish_reason == "engine_error" for r in reqs)
    c = driver.metrics.counters
    assert c["requests_failed_total"] == 2
    assert not driver.core.has_work() and not driver.core.step_in_flight
    sched = engine.scheduler
    assert not sched._in_flight and not sched._owed and _all_free(engine)
    work = [(0, _prompt(5, 45), 5)]
    after = _submit(driver, work[0][1], 5)
    while not after.is_terminal:
        assert _one_pass(driver)
    # sampling is keyed by (uid, position) and greedy here: uid 0 serves the same
    assert after.state == RequestState.FINISHED and after.generated == _sync_reference(make(), work)[0]
    assert c["requests_failed_total"] == 2


@pytest.mark.parametrize("kind", ["spec_k", "prefill_role"])
def test_a_core_that_cannot_run_ahead_never_does(toy, kind):
    """(f): a speculative controller takes a row's token from the host, and
    a prefill worker hands K/V off after its step: such a core collects where
    it launches, through the same two primitives."""
    name, make = toy
    work = _work([(0, 5, 6), (0, 70, 9), (2, 33, 5)])
    want = _sync_reference(make(), work)
    if kind == "prefill_role":
        core = EngineCore(make(), role="prefill")
        assert not core._runs_ahead() and EngineCore(make(), role="decode")._runs_ahead()
        return
    if name != "dense":
        # spec_round is refused for a second kind of cache: the core that
        # would drive it still collects where it launches
        assert not EngineCore(make(), spec_k=2)._runs_ahead()
        return
    driver = ServingDriver(make(spec_k=2), spec_k=2)
    reqs = _serve(driver, work)
    for uid, req in enumerate(reqs):
        assert req.generated == want[uid], f"uid {uid}"
    c = driver.metrics.counters
    assert c["steps_ahead_total"] == 0 and c["ahead_rows_dropped_total"] == 0
    assert c["engine_steps_total"] > 0 and not driver.core.step_in_flight


def test_a_step_that_completed_no_row_is_waited_on_its_own_outputs(toy, monkeypatch):
    """(h): chunks with more to come complete no row. Each is waited for, one
    pass after its launch, on the tokens ITS program returned: not on the
    engine's pools (the next step's by then) nor on the engine's
    ``_last_tokens`` (the next step's too). So at most one step is in flight
    beyond the one waited for."""
    _name, make = toy
    engine = make()
    waits = []
    real_wait = engine_v2.device_synchronize
    monkeypatch.setattr(engine_v2, "device_synchronize",
                        lambda tree=None: (waits.append(list(tree)), real_wait(tree))[1])
    driver = ServingDriver(engine)
    req = _submit(driver, _prompt(0, 100), 2)  # 40 + 40 + 20
    flights = []
    for chunk in range(3):
        assert _one_pass(driver), f"chunk {chunk} is progress"
        flights.append(driver.core._flight[0])
        assert len(waits) == chunk and req.generated == []
        assert bool(flights[-1].rows) == (chunk == 2)
    own = {id(a) for f in flights[:2] for a in f.waited}
    pools = {id(a) for a in jax.tree_util.tree_leaves(engine._pools())}
    for w, f in zip(waits, flights):
        assert w == f.waited and w and not ({id(a) for a in w} & pools)
        assert all(a is not engine._last_tokens for a in w)
    assert len(own) == sum(len(f.waited) for f in flights[:2])
    while not req.is_terminal:
        assert _one_pass(driver)
    assert len(req.generated) == 2 and not driver.core.has_work()


# -- the device's step timed where it is collected, by kind ------------------
TICK, DECODE_S, CHUNK_S, IDLE_S = 0.001, 0.010, 0.030, 5.0
STEP_SPANS = ("step.decode", "step.chunk")


class _Clock:
    """The engine's clock (``engine_v2._now``) in the test's hands. A read
    returns ``t`` and moves it one TICK on, so that in a pass the enqueue of
    step n+1 lies BEFORE the ready stamp of step n; the test adds the rest:
    a step's length before the pass that collects it, the idle between two
    bursts."""

    def __init__(self):
        self.t = 1000.0
        self.reads = []

    def __call__(self):
        self.reads.append(self.t)
        self.t += TICK
        return self.reads[-1]


def _timed_run(make, monkeypatch, tracer):
    """Two bursts with an idle loop between them: a prompt of 100 (chunks of
    40, 40, 20) beside one of 12, then one of 50. Before each pass the step
    in flight is given its length by its kind. Returns (driver, clock,
    requests, the steps' kinds in order)."""
    clock = _Clock()
    monkeypatch.setattr(engine_v2, "_now", clock)
    # whether a step had finished by the next launch is the machine's, not the run's
    monkeypatch.setattr(engine_v2, "_is_ready", lambda arr: False)
    set_tracer(tracer)
    try:
        driver = ServingDriver(make())
        kinds = []

        def burst(*work):
            reqs = [_submit(driver, _prompt(i, n), new) for i, n, new in work]
            while driver.core.has_work() or driver._queue:
                if driver.core.step_in_flight:
                    chunk = driver.core._flight[0].stats.prefill_tokens > 0
                    kinds.append("chunk" if chunk else "decode")
                    clock.t += CHUNK_S if chunk else DECODE_S
                _one_pass(driver)
            return reqs

        reqs = burst((0, 100, 5), (1, 12, 3))
        clock.t += IDLE_S
        reqs += burst((2, 50, 4))
    finally:
        set_tracer(NULL_TRACER)
    assert all(r.state == RequestState.FINISHED for r in reqs)
    return driver, clock, reqs, kinds


def test_a_steps_seconds_go_to_its_kind_and_sum_to_the_busy_time(toy, monkeypatch):
    """Every step launched with its predecessor in flight begins where that
    one was seen ready; the first of a burst at its own enqueue. So each
    step's seconds are what the test gave it (+ the reads between its bounds:
    the one it starts at and the next step's enqueue), under its own kind,
    and all of them together are the span from the first enqueue to the last
    ready less the idle between the bursts."""
    _name, make = toy
    driver, clock, _reqs, kinds = _timed_run(make, monkeypatch, NULL_TRACER)
    c = driver.metrics.counters
    n_chunk, n_decode = kinds.count("chunk"), kinds.count("decode")
    assert n_chunk == 5 and n_decode >= 6
    assert (c["chunk_steps_timed_total"], c["decode_steps_timed_total"]) == (n_chunk, n_decode)
    assert c["steps_with_prefill_total"] == n_chunk
    assert c["engine_steps_total"] == n_chunk + n_decode  # every one launched a program
    assert c["chunk_step_seconds_total"] == pytest.approx(n_chunk * (CHUNK_S + 2 * TICK))
    # a burst's last step, a decode step, is collected with no launch before it
    assert c["decode_step_seconds_total"] == pytest.approx(
        n_decode * (DECODE_S + 2 * TICK) - 2 * TICK)
    # the reads are the enqueues and the readies, nothing else; the last
    # ready of the first burst moved the clock one TICK on before the idle
    assert len(clock.reads) == 2 * len(kinds) and kinds[-1] == "decode"
    busy = clock.reads[-1] - clock.reads[0] - (IDLE_S + TICK)
    assert c["chunk_step_seconds_total"] + c["decode_step_seconds_total"] == pytest.approx(busy)
    # the first step of each burst followed an idle loop: not ahead, and no
    # launch found a finished step in flight that the loop had not reached...
    assert c["steps_ahead_total"] == c["engine_steps_total"] - 2 and c["steps_starved_total"] == 0
    text = driver.metrics.prometheus_text()
    assert f"chunk_steps_timed_total {n_chunk}" in text and "decode_step_seconds_total" in text


def test_step_counters_are_the_same_traced_or_not_and_the_spans_hold_them(toy, monkeypatch):
    """One seeded run, tracing off and on: identical counters. Traced, each
    step is one ``step.decode`` / ``step.chunk`` span on the engine's track
    over the interval its seconds count, mirrored into the tree of each
    request it completed a row for, under the phase the request was in."""
    _name, make = toy
    off, _, _, _ = _timed_run(make, monkeypatch, NULL_TRACER)
    tracer = SpanTracer()
    on, _clock, reqs, kinds = _timed_run(make, monkeypatch, tracer)
    c = on.metrics.counters
    assert dict(off.metrics.counters) == dict(c)
    ring = tracer.ring_spans()
    assert not [sp.name for sp in ring if sp.name == "step.split"]
    steps = [sp for sp in ring if sp.name in STEP_SPANS]
    assert [sp.name for sp in steps] == ["step." + k for k in kinds]
    assert {sp.track for sp in steps} == {on.core.name}
    for name, counter in zip(STEP_SPANS, ("decode_step_seconds_total", "chunk_step_seconds_total")):
        assert sum(sp.t1 - sp.t0 for sp in steps if sp.name == name) == pytest.approx(c[counter])
    assert [sp.args["ahead"] for sp in steps].count(False) == 2  # the first of each burst
    for req in reqs:
        tree = tracer.trace(req.uid)["spans"]
        phase = {sp.name: sp.span_id for sp in tree if sp.name in ("prefill", "decode")}
        mine = [sp for sp in tree if sp.name in STEP_SPANS]
        # the step that completed its prompt, then one a later token
        assert [sp.name for sp in mine if sp.parent_id == phase["prefill"]] == ["step.chunk"]
        assert sum(sp.parent_id == phase["decode"] for sp in mine) == len(req.generated) - 1
        assert len(mine) == len(req.generated)


def test_a_launch_after_the_step_in_flight_ended_is_starved_one_after_idle_is_not(
        toy, monkeypatch):
    """``steps_starved_total``: at its enqueue a step found the step in
    flight already finished (asked without waiting), so the chip ran dry in
    front of it. A launch with nothing in flight is not one, whatever the
    outputs say: that is the complement of ``steps_ahead_total``."""
    _name, make = toy
    driver = ServingDriver(make())
    c = driver.metrics.counters
    real, asked = engine_v2._is_ready, []
    answer = [True]
    monkeypatch.setattr(engine_v2, "_is_ready", lambda arr: (asked.append(arr), answer[0])[1])
    a = _submit(driver, _prompt(0, 20), 6)
    assert _one_pass(driver) and not asked  # after an idle loop: nothing in flight to ask
    first = driver.core._flight[0]
    assert _one_pass(driver)  # step 2 is enqueued behind a finished step 1
    assert asked == [first.waited[0]] and driver.core._flight[0].stats.starved
    answer[0] = False
    while not a.is_terminal:
        assert _one_pass(driver)
    assert c["steps_starved_total"] == 1 and c["engine_steps_total"] == 6
    assert not driver.core.has_work()
    # the real question, of a step this test has waited for itself
    b = _submit(driver, _prompt(1, 20), 4)
    assert _one_pass(driver)
    jax.block_until_ready(driver.core._flight[0].waited)
    monkeypatch.setattr(engine_v2, "_is_ready", real)
    assert _one_pass(driver) and driver.core._flight[0].stats.starved
    monkeypatch.setattr(engine_v2, "_is_ready", lambda arr: False)
    while not b.is_terminal:
        assert _one_pass(driver)
    assert c["steps_starved_total"] == 2 and c["engine_steps_total"] == 10
    assert c["steps_ahead_total"] == c["engine_steps_total"] - 2
