"""The serving step measured from inside: the ring spans of every phase
between two device programs, the scheduler's grid counters, the profiler
bridge of ``SpanTracer.span()``, and the single step path that runs traced or
not. On the real v2 engine at a toy size (CPU); the request-tree side of the
tracer is covered in test_tracing.py.
"""

import sys
import threading

import numpy as np
import pytest

from deepspeed_tpu.observability import NULL_TRACER, SpanTracer, get_tracer, set_tracer
from deepspeed_tpu.observability import tracing
from deepspeed_tpu.serving.driver import ServingDriver
from deepspeed_tpu.serving.request import RequestState, SamplingParams

# the contract of names: benchmarks/metrics readers and docs/OBSERVABILITY.md
STEP_SPANS = {"engine.schedule", "engine.stage", "engine.launch", "engine.dispatch",
              "engine.device_wait", "engine.materialize", "step.decode", "step.chunk",
              "step.deliver"}
# a step's time on the device, recorded after the fact from the step's own
# stamps (no ``with``, no profiler annotation): it lies across the host's spans
DEVICE_SPANS = ("step.decode", "step.chunk")
LOOP_SPANS = {"loop.admit", "loop.bookkeeping", "loop.wait"}


@pytest.fixture(autouse=True)
def _isolated_tracer():
    set_tracer(NULL_TRACER)
    yield
    set_tracer(NULL_TRACER)


@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from deepspeed_tpu.models import get_config, init_params

    cfg = get_config("tiny", n_layers=2, dtype="float32", max_seq_len=1024)
    return cfg, init_params(cfg, jax.random.key(0))


def _engine(tiny_model):
    """R = 4 decode slots and one prompt chunk of at most 512 tokens a step:
    the split step's grid is 4 + 128 or 4 + 512 slots."""
    from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    cfg, params = tiny_model
    rc = RaggedInferenceEngineConfig.from_dict({
        "dtype": "float32",
        "kv_cache": {"block_size": 16, "num_blocks": 96, "max_blocks_per_seq": 32},
        "state_manager": {"max_tracked_sequences": 8, "max_ragged_batch_size": 512,
                          "max_ragged_sequence_count": 4, "max_context": 512},
    })
    return InferenceEngineV2(cfg, params, rc)


def _serve(engine, prompts_and_new):
    """Queue every request BEFORE the loop starts, so that the loop admits
    them all in its first pass and the steps that follow are the same in
    every run. Returns the driver (stopped) and the finished requests."""
    driver = ServingDriver(engine)
    reqs = [driver.submit(np.arange(1, n + 1, dtype=np.int32) + 7 * i,
                          params=SamplingParams(max_new_tokens=new, ignore_eos=True))
            for i, (n, new) in enumerate(prompts_and_new)]
    driver.start()
    try:
        for r in reqs:
            assert r.wait(300), "request did not finish"
    finally:
        driver.shutdown(drain=False)
    assert all(r.state == RequestState.FINISHED for r in reqs)
    return driver, reqs


def _assert_overlap_only_by_nesting(spans):
    """Spans of one thread, as a profiler's host line needs them: any two are
    disjoint or one lies inside the other."""
    stack = []
    for sp in sorted(spans, key=lambda s: (s.t0, -(s.t1 - s.t0))):
        while stack and stack[-1].t1 <= sp.t0:
            stack.pop()
        if stack:
            assert sp.t1 <= stack[-1].t1, (
                f"{sp.name} [{sp.t0}, {sp.t1}] straddles the end of "
                f"{stack[-1].name} [{stack[-1].t0}, {stack[-1].t1}]")
        stack.append(sp)


def _inside(inner, outer):
    return outer.t0 <= inner.t0 and inner.t1 <= outer.t1


class TestServingSpans:
    def test_one_traced_run_yields_every_phase_nested_on_one_timeline(self, tiny_model):
        tracer = set_tracer(SpanTracer())
        _serve(_engine(tiny_model), [(200, 3), (20, 2)])
        ring = tracer.ring_spans()
        assert all(sp.t1 is not None for sp in ring)
        names = {sp.name for sp in ring}
        assert STEP_SPANS | LOOP_SPANS <= names, sorted((STEP_SPANS | LOOP_SPANS) - names)
        host = [sp for sp in ring if sp.name not in DEVICE_SPANS]
        _assert_overlap_only_by_nesting(host)  # the loop is one thread
        dispatches = [sp for sp in ring if sp.name == "engine.dispatch"]
        for part in ("engine.schedule", "engine.stage", "engine.launch"):
            parts = [sp for sp in ring if sp.name == part]
            assert len(parts) == len(dispatches)
            for sp in parts:
                assert any(_inside(sp, d) for d in dispatches), part
        # one step in flight: a pass of the core dispatches step n+1, THEN
        # waits for step n and brings its tokens to the host; delivery
        # follows it. The first pass only launches, the last only collects
        # (both requests' last tokens: nothing left to launch).
        # A step is one span named for its kind, over its time on the
        # device: [a chunk of 200], [a decode row + a chunk of 20], [two
        # decode rows]. It starts where the step before it was seen ready
        # (it was launched ahead) and ends inside its own wait.
        steps = sorted((sp for sp in ring if sp.name in DEVICE_SPANS), key=lambda s: s.t0)
        assert len(dispatches) == 3
        # ... and a dispatch says the grid its step was sized to: R + rc x tq
        # for the rc chunk rows of the batch in the bucket tq
        assert [sp.args for sp in sorted(dispatches, key=lambda s: s.t0)] == [
            {"rows": 1, "tokens": 200, "grid_slots": 4 + 512},
            {"rows": 2, "tokens": 21, "grid_slots": 4 + 128},
            {"rows": 2, "tokens": 2, "grid_slots": 4}]
        assert [sp.name for sp in steps] == ["step.chunk", "step.chunk", "step.decode"]
        assert [sp.args for sp in steps] == [
            {"rows": 1, "tokens": 200, "ahead": False},
            {"rows": 2, "tokens": 21, "ahead": True},
            {"rows": 2, "tokens": 2, "ahead": True}]
        waits = sorted((sp for sp in ring if sp.name == "engine.device_wait"),
                       key=lambda s: s.t0)
        launches = sorted((sp for sp in ring if sp.name == "engine.launch"), key=lambda s: s.t0)
        for i, (st, wait, launch) in enumerate(zip(steps, waits, launches)):
            assert wait.t0 <= st.t1 <= wait.t1, "seen ready inside its own wait"
            assert st.t0 >= launch.t0, "never before its own transfers began"
            assert st.t0 == (steps[i - 1].t1 if i else st.t0)
            assert st.track == dispatches[0].track
        order = [sp.name for sp in sorted(ring, key=lambda s: s.t0)
                 if sp.name in ("engine.dispatch", "engine.device_wait",
                                "engine.materialize", "step.deliver")]
        collect = ["engine.device_wait", "engine.materialize", "step.deliver"]
        assert order == (["engine.dispatch", "step.deliver"]
                         + ["engine.dispatch"] + collect + ["engine.dispatch"] + collect + collect)
        # the dispatch span still says what the step carried
        assert [d.args["tokens"] for d in dispatches] == [200, 21, 2]
        assert tracer.stats()["dropped_spans"] == 0
        assert tracer.stats()["ring_evicted_spans"] == 0

    def test_ring_overflow_is_counted(self):
        tracer = SpanTracer(max_events=256)
        for _ in range(260):
            with tracer.span("engine.stage"):
                pass
        assert len(tracer.ring_spans()) == 256
        # wrap of the ring is counted apart from request-tree spans lost
        assert tracer.stats()["ring_evicted_spans"] == 4
        assert tracer.stats()["dropped_spans"] == 0


class TestGridCounters:
    def test_counters_equal_the_hand_computed_grid_with_tracing_off(self, tiny_model):
        """Three steps: [chunk of 200 in the 512 bucket], [1 decode row + a
        chunk of 20 in the 128 bucket], [2 decode rows and NO chunk: the
        decode-only shape, the R slots alone]. R = 4, Rc = 1."""
        assert get_tracer() is NULL_TRACER
        driver, reqs = _serve(_engine(tiny_model), [(200, 3), (20, 2)])
        c = driver.metrics.counters
        assert c["engine_steps_total"] == 3
        assert c["grid_slots_total"] == (4 + 512) + (4 + 128) + 4
        assert c["scheduled_tokens_total"] == 200 + (1 + 20) + 2
        assert c["prefill_tokens_total"] == 200 + 20
        assert c["steps_with_prefill_total"] == 2
        assert c["decode_tokens_total"] == 3 + 2
        text = driver.metrics.prometheus_text()
        assert "grid_slots_total 652" in text and "steps_with_prefill_total 2" in text

    def test_paged_counters_are_live_blocks_over_table_slots(self, tiny_model):
        """``ceil(pool tokens / block size)`` summed over the decode rows
        against ``R x B``, for one layer's decode attention calls, and the
        kernel's programs that read those blocks."""
        # the three steps of the first test. Decode rows: none; one whose pool
        # holds 200 tokens = 13 blocks of 16; that row at 201 (13) and one at
        # 20 (2). Every step's tables have R x B = 4 x 32 slots. Blocks this
        # small go four to a program: 13 blocks are 4 programs, 2 are one
        live, slots, programs = 0 + 13 + (13 + 2), 3 * 128, 0 + 4 + (4 + 1)
        driver, _ = _serve(_engine(tiny_model), [(200, 3), (20, 2)])
        c = driver.metrics.counters
        assert c["paged_live_blocks_total"] == live
        assert c["paged_table_slots_total"] == slots
        assert c["paged_programs_total"] == programs
        assert f"paged_table_slots_total {slots}" in driver.metrics.prometheus_text()
        assert f"paged_programs_total {programs}" in driver.metrics.prometheus_text()


    def test_chunk_counters_are_held_blocks_over_whole_walks(self, tiny_model):
        """The first test's three steps, Rc = 1, blocks of 16, B = 32. A chunk
        of 200 at position 0 in the 512 bucket: 0 pool blocks + 13 of its own
        over 32 + 32 slots; one of 20 in the 128 bucket: 2 over 32 + 8; the
        step with no chunk counts nothing."""
        driver, _ = _serve(_engine(tiny_model), [(200, 3), (20, 2)])
        c = driver.metrics.counters
        assert c["chunk_live_blocks_total"] == 13 + 2
        assert c["chunk_table_slots_total"] == (32 + 32) + (32 + 8)
        assert "chunk_table_slots_total 104" in driver.metrics.prometheus_text()


class TestOnePath:
    def test_off_path_is_the_on_path_with_the_shared_null_span(self, tiny_model, monkeypatch):
        """Traced or not, a step runs the same statements: the spans are
        entered on both, and with tracing off every one of them is the one
        shared no-op object (nothing allocated per call) and the device wait
        still happens."""
        from deepspeed_tpu.inference.v2 import engine_v2

        entered, waits = [], []

        class Recording(type(NULL_TRACER)):
            def span(self, name, **kw):
                entered.append(name)
                return super().span(name, **kw)

        real_sync = engine_v2.device_synchronize
        monkeypatch.setattr(engine_v2, "device_synchronize",
                            lambda tree=None: (waits.append(1), real_sync(tree))[1])
        off = set_tracer(Recording())
        assert not off.enabled and off.span("x") is NULL_TRACER.span("y")
        _, reqs = _serve(_engine(tiny_model), [(200, 3), (20, 2)])
        off_names, off_waits = list(entered), len(waits)
        off_tokens = [r.generated for r in reqs]
        assert off.ring_spans() == [] and off.recent() == []

        entered.clear()
        waits.clear()
        on = set_tracer(SpanTracer())
        _, reqs = _serve(_engine(tiny_model), [(200, 3), (20, 2)])
        on_names = [sp.name for sp in sorted(on.ring_spans(), key=lambda s: s.span_id)
                    if sp.name not in DEVICE_SPANS]  # recorded after the fact, both ways
        assert [r.generated for r in reqs] == off_tokens
        assert len(waits) == off_waits == 3

        def steps_only(names):
            return [n for n in names if n.startswith(("engine.", "step."))]

        assert steps_only(off_names) == steps_only(on_names)
        assert set(off_names) >= (STEP_SPANS - set(DEVICE_SPANS)) | (LOOP_SPANS - {"loop.wait"})


class TestProfilerBridge:
    def test_ring_spans_are_annotated_request_spans_are_not(self, monkeypatch):
        seen = []

        class FakeAnnotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                seen.append(("enter", self.name))

            def __exit__(self, *exc):
                seen.append(("exit", self.name))

        monkeypatch.setattr(tracing, "_trace_annotation", lambda: FakeAnnotation)
        tr = SpanTracer()
        with tr.span("engine.stage"):
            with tr.span("engine.launch"):
                pass
        tr.begin_trace(5, "request")
        with tr.span("prefill", key=5):
            pass
        assert seen == [("enter", "dstpu.engine.stage"), ("enter", "dstpu.engine.launch"),
                        ("exit", "dstpu.engine.launch"), ("exit", "dstpu.engine.stage")]

    def test_records_with_no_profiler_session(self):
        """The real annotation, outside any profiler session: a no-op check."""
        pytest.importorskip("jax")
        tr = SpanTracer()
        assert tr._annotation is not None
        with tr.span("engine.stage") as sp:
            pass
        assert sp.t1 is not None and [s.name for s in tr.ring_spans()] == ["engine.stage"]

    def test_records_with_jax_unimportable(self, monkeypatch):
        monkeypatch.setitem(sys.modules, "jax", None)
        monkeypatch.setitem(sys.modules, "jax.profiler", None)
        tr = SpanTracer()
        assert tr._annotation is None
        with tr.span("engine.stage") as sp:
            pass
        assert sp.t1 is not None and sp.t1 >= sp.t0
        assert [s.name for s in tr.ring_spans()] == ["engine.stage"]

    def test_annotations_from_two_threads_do_not_cross(self):
        """Each span enters and leaves its annotation on its own thread."""
        tr = SpanTracer()
        errors = []

        def work(name):
            try:
                for _ in range(200):
                    with tr.span(name):
                        pass
            except Exception as e:  # pragma: no cover - the assertion below reports it
                errors.append(e)

        threads = [threading.Thread(target=work, args=(f"t{i}",)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors and not any(t.is_alive() for t in threads)
        assert len(tr.ring_spans()) == 800
