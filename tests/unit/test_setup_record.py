"""Set-up measured from inside: the bounded record of observability/
setup_record.py on a fake clock, the spans both entry points leave at toy
size on the CPU, the ``setup`` block of /health and /metrics, and the nine
``setup_*`` readers of the benchmark on a synthetic record.
"""

import importlib
import json
import logging
import threading
import urllib.request

import numpy as np
import pytest

from deepspeed_tpu.observability import (
    SetupRecord,
    get_setup_record,
    set_setup_record,
    setup_report,
)
from deepspeed_tpu.observability import setup_record as sr

TRACE, LOWER, BACKEND = sr.COMPILE_SPANS  # JAX's three duration events, in order

# the contract of names: benchmarks/metrics/setup_*.py and docs/OBSERVABILITY.md
SECONDS = ("setup_import_s", "setup_state_s", "setup_trace_s", "setup_lower_s",
           "setup_backend_compile_s", "setup_first_run_s", "setup_outside_s")
READERS = SECONDS + ("setup_cache_hit_pct", "setup_programs")


class Clock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


@pytest.fixture
def record():
    """A fresh record as the process's own, the old one back afterwards."""
    old = get_setup_record()
    yield set_setup_record(SetupRecord())
    set_setup_record(old)


def stage(rec, clock, event, seconds, **kw):
    """One compile stage as JAX reports it: a scalar at its start, the clock
    moved on, the duration at its end."""
    rec.on_scalar(event, 0.0, **kw)
    clock.t += seconds
    rec.on_duration(event, seconds, **kw)


def compile_program(rec, clock, name, trace=1.0, lower=0.5, backend=2.0, cache=None):
    """trace, lower, backend of one program; ``cache`` is what the persistent
    cache answers (None: not asked)."""
    stage(rec, clock, TRACE, trace, fun_name=name)
    stage(rec, clock, LOWER, lower, fun_name=name)
    rec.on_scalar(BACKEND, 0.0, fun_name=name)
    if cache is not None:
        rec.on_event(sr.CACHE_ASKED)
        if cache == "hit":
            rec.on_event(sr.CACHE_HIT)
            rec.on_duration(sr.CACHE_RETRIEVAL, backend / 2)
    clock.t += backend
    rec.on_duration(BACKEND, backend, fun_name=name)


# ---------------------------------------------------------------- the record
def test_spans_nest_by_interval_and_every_instant_is_charged_once():
    clock = Clock()
    rec = SetupRecord(clock=clock)
    with rec.span("setup.build_stack") as outer:
        clock.t += 1.0
        with rec.span("setup.build_engine") as inner:
            clock.t += 2.0
        clock.t += 0.5
    assert inner.parent_id == outer.span_id and outer.parent_id is None
    assert (outer.t0, outer.t1, inner.t0, inner.t1) == (100.0, 103.5, 101.0, 103.0)
    rep = rec.report()
    assert rep["by_span"] == {"setup.build_stack": 1.5, "setup.build_engine": 2.0}
    assert rep["phases"]["state"] == 3.5 and sum(rep["phases"].values()) == 3.5


def test_a_span_belongs_to_the_span_open_on_its_own_thread():
    clock = Clock()
    rec = SetupRecord(clock=clock)
    seen = {}

    def warm():
        with rec.span("program.first_call", key="split[(0, 0)]") as first:
            compile_program(rec, clock, "step")
        seen["first"] = first

    with rec.span("setup.build_stack") as stack:
        clock.t += 1.0
        t = threading.Thread(target=warm, name="serving-warm_0")
        t.start()
        t.join(30)
        assert not t.is_alive()
        clock.t += 1.0
    first = seen["first"]
    # not the main thread's open span: the other thread had none open
    assert first.parent_id is None and first.track == "serving-warm_0"
    stages = [s for s in rec.spans() if s.name.startswith("compile.")]
    assert [s.name for s in stages] == ["compile.trace", "compile.lower", "compile.backend"]
    assert all(s.parent_id == first.span_id and s.track == "serving-warm_0" for s in stages)
    # ... but the thread that waits gives the instants to the span that started last
    rep = rec.report()
    assert rep["phases"] == {"import": 0.0, "state": 2.0, "trace": 1.0, "lower": 0.5,
                             "backend_compile": 2.0, "first_run": 0.0}
    assert rep["programs"]["split[(0, 0)]"] == {
        "trace_s": 1.0, "lower_s": 0.5, "compile_s": 2.0, "first_run_s": 0.0,
        "compiles": 1, "cache_hits": 0}
    assert stack.t1 - stack.t0 == sum(rep["phases"].values())


def test_nested_trace_events_are_a_union_not_a_sum():
    clock = Clock()
    rec = SetupRecord(clock=clock)
    with rec.span("program.first_call", key="train_step"):
        rec.on_scalar(TRACE, 0.0, fun_name="train_step")
        clock.t += 1.0
        for _ in range(3):  # three inner jits, each 0.5 s, one of them two deep
            rec.on_scalar(TRACE, 0.0, fun_name="inner")
            rec.on_scalar(TRACE, 0.0, fun_name="innermost")
            clock.t += 0.25
            rec.on_duration(TRACE, 0.25, fun_name="innermost")
            clock.t += 0.25
            rec.on_duration(TRACE, 0.5, fun_name="inner")
        clock.t += 0.5
        rec.on_duration(TRACE, 3.0, fun_name="train_step")
    traces = [s for s in rec.spans() if s.name == "compile.trace"]
    assert len(traces) == 1
    assert traces[0].args == {"fun_name": "train_step", "nested": 6}
    assert (traces[0].t0, traces[0].t1) == (100.0, 103.0)
    # 3.0 s of tracing, where the events' durations sum to 5.25
    assert rec.report()["phases"]["trace"] == 3.0


def test_overlapping_spans_of_one_name_count_an_instant_once():
    """Two spans recorded after the fact whose intervals overlap (two threads'
    events): the flattened seconds are the union's."""
    clock = Clock()
    rec = SetupRecord(clock=clock)
    with rec.span("setup.initialize"):
        rec.add("compile.lower", 100.0, 102.0)
        rec.add("compile.lower", 101.0, 103.0)
        clock.t = 104.0
    rep = rec.report()
    assert rep["phases"]["lower"] == 3.0 and rep["phases"]["state"] == 1.0


def test_the_record_is_bounded_and_counts_what_it_drops():
    clock = Clock()
    rec = SetupRecord(max_spans=4, clock=clock)
    for i in range(6):
        with rec.span("setup.state"):
            clock.t += 1.0
    compile_program(rec, clock, "late")
    assert len(rec.spans()) == 4 and rec.dropped == 5
    assert rec.report()["dropped"] == 5 and rec.report()["spans"] == 4
    # the counters count on past the bound
    assert rec.counters()["compile_events"] == 1


def test_the_listeners_are_registered_once_a_process():
    from jax._src import monitoring

    assert sr.install_compile_listeners()  # importing the package did it already
    counts = [len(monitoring._event_duration_secs_listeners), len(monitoring._event_listeners),
              len(monitoring._scalar_listeners)]
    assert sr.install_compile_listeners() and sr.install_compile_listeners()
    assert counts == [len(monitoring._event_duration_secs_listeners),
                      len(monitoring._event_listeners), len(monitoring._scalar_listeners)]


def test_a_compile_after_set_up_is_still_a_span_and_a_count(record):
    import jax
    import jax.numpy as jnp

    with record.span("setup.initialize"):
        pass
    before = record.counters()["compile_events"]
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    late = [s for s in record.spans() if s.name.startswith("compile.")]
    assert {"compile.trace", "compile.lower", "compile.backend"} <= {s.name for s in late}
    assert all(s.parent_id is None for s in late)  # nobody's: set-up was over
    assert record.counters()["compile_events"] > before
    rep = setup_report()
    assert rep["unowned"]["backend_compile"] > 0 and rep["phases"]["backend_compile"] == 0
    assert rep["compile"]["programs"] == 0


@pytest.mark.parametrize("cache,said", [(None, "off"), ("miss", "miss"), ("hit", "hit")])
def test_a_backend_span_says_what_the_cache_answered(cache, said):
    clock = Clock()
    rec = SetupRecord(clock=clock)
    with rec.span("program.first_call", key="eval"):
        compile_program(rec, clock, "eval_fn", cache=cache)
        compile_program(rec, clock, "next")  # what was said does not leak into the next
    first, second = [s for s in rec.spans() if s.name == "compile.backend"]
    assert first.args["cache"] == said and second.args["cache"] == "off"
    assert ("retrieval_s" in first.args) == (cache == "hit")
    c = rec.counters()
    assert (c["compile_events"], c["cache_hits"], c["cache_misses"]) == (
        2, int(cache == "hit"), int(cache == "miss"))
    comp = rec.report()["compile"]
    assert comp == {"programs": 2, "asked_cache": int(cache is not None),
                    "cache_hits": int(cache == "hit")}


def test_a_report_is_clipped_to_the_interval_asked_for():
    clock = Clock()
    rec = SetupRecord(clock=clock)
    with rec.span("setup.initialize"):
        clock.t += 4.0
    assert rec.report(101.0, 103.0)["phases"]["state"] == 2.0
    assert rec.report(90.0, 101.0)["phases"]["state"] == 1.0
    assert rec.report(110.0, 120.0)["phases"]["state"] == 0.0


def test_the_log_line():
    clock = Clock()
    rec = SetupRecord(clock=clock)
    rec.add("setup.import", 97.9, 100.0)
    with rec.span("program.first_call", key="train_step"):
        compile_program(rec, clock, "train_step", trace=5.0, lower=1.1, backend=0.6, cache="hit")
        clock.t += 1.3
    line = sr.setup_line(rec.report())
    assert line == ("set-up: import 2.1 s, state 0.0 s, trace 5.0 s, lower 1.1 s, "
                    "compile 0.6 s (1 hits, 0 misses), first run 1.3 s")


# ------------------------------------------------------- the two entry points
@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from deepspeed_tpu.models import get_config, init_params

    cfg = get_config("tiny", n_layers=2, dtype="float32", max_seq_len=1024)
    return cfg, init_params(cfg, jax.random.key(0))


def _engine(tiny_model):
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


def _first_calls(record):
    return [s for s in record.spans() if s.name == sr.FIRST_CALL]


def _stages_under(record, span):
    return sorted(s.name for s in record.spans() if s.parent_id == span.span_id)


def test_a_serving_engine_leaves_its_set_up_and_one_first_call_a_program(record, tiny_model):
    engine = _engine(tiny_model)
    build = [s for s in record.spans() if s.name == "setup.build_engine"]
    assert len(build) == 1 and build[0].t1 is not None
    assert not _first_calls(record)

    out = engine.generate([np.arange(1, 9, dtype=np.int32)], max_new_tokens=3)
    assert len(out[0]) == 11
    firsts = _first_calls(record)
    keys = [s.args["key"] for s in firsts]
    # one a program key, named as trace_signature() names them
    assert len(keys) == len(set(keys)) and set(keys) == set(engine.trace_signature())
    assert "split[(0, 0)]" in keys
    for s in firsts:
        stages = _stages_under(record, s)
        assert {"compile.trace", "compile.lower", "compile.backend"} <= set(stages), s.args
    rows = setup_report()["programs"]
    assert set(rows) == set(keys)
    assert all(r["trace_s"] > 0 and r["lower_s"] > 0 and r["compile_s"] > 0 and r["compiles"] == 1
               for r in rows.values())

    # a second call of the same keys leaves none
    n_spans = len(record.spans())
    engine.generate([np.arange(2, 10, dtype=np.int32)], max_new_tokens=3)
    assert len(_first_calls(record)) == len(firsts)
    assert len(record.spans()) == n_spans


def test_the_serving_stack_warms_every_shape_under_its_own_first_call(record, tiny_model):
    from deepspeed_tpu.inference.cli import build_serving_stack, serve_parse_args

    cfg, params = tiny_model
    args = serve_parse_args(["--model", "", "--port", "0", "--block-size", "16", "--num-blocks",
                             "96", "--max-context", "512", "--max-concurrent", "8"])
    driver, _ = build_serving_stack(args, cfg=cfg, params=params)
    spans = {s.span_id: s for s in record.spans()}
    stack = [s for s in spans.values() if s.name == "setup.build_stack"]
    build = [s for s in spans.values() if s.name == "setup.build_engine"]
    assert len(stack) == 1 and len(build) == 1 and build[0].parent_id == stack[0].span_id
    assert not [s for s in spans.values() if s.name == "setup.load_weights"]  # weights were passed
    rows = setup_report()["programs"]
    assert set(rows) == set(driver.core.engine.trace_signature())
    for key, r in rows.items():
        assert r["compiles"] == 1 and r["first_run_s"] > 0, key
    # the warm thread's spans lie inside the stack's interval, on a thread of their own
    firsts = _first_calls(record)
    assert all(s.track.startswith("serving-warm") for s in firsts)
    assert all(stack[0].t0 <= s.t0 and s.t1 <= stack[0].t1 for s in firsts)
    # _launch's span (builder to enqueue) nests in the warm round's (to the tokens)
    inner = [s for s in firsts if s.parent_id is not None]
    assert len(inner) == len(rows)
    assert all(spans[s.parent_id].args == s.args for s in inner)


def test_a_train_engine_leaves_its_set_up_and_its_two_programs(record, caplog):
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.utils.logging import logger
    from tests.unit.simple_model import batch_of, make_mlp_params, mlp_loss_fn, random_dataset

    config = {"train_micro_batch_size_per_gpu": 1,
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
              "zero_optimization": {"stage": 3, "param_persistence_threshold": 0},
              "steps_per_print": 1000}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=mlp_loss_fn, model_parameters=make_mlp_params(jax.random.key(0)), config=config)
    by_name = {}
    for s in record.spans():
        by_name.setdefault(s.name, []).append(s)
    (init,), (state,) = by_name["setup.initialize"], by_name["setup.state"]
    assert state.parent_id == init.span_id and init.t0 <= state.t0 <= state.t1 <= init.t1
    # the optimizer state's creation compiles inside setup.state
    assert "compile.backend" in _stages_under(record, state)
    assert not _first_calls(record)

    batch = batch_of(random_dataset(64), 0, 8)
    lines = []
    handler = _Collect(lines)
    logger.addHandler(handler)
    try:
        engine.eval_batch(batch)
        engine.train_batch(batch=batch)
        firsts = _first_calls(record)
        assert [s.args["key"] for s in firsts] == ["eval", "train_step"]
        for s in firsts:
            assert _stages_under(record, s) == ["compile.backend", "compile.lower", "compile.trace"]
        # a second call of either leaves none, and the line is logged once
        n_spans = len(record.spans())
        engine.eval_batch(batch)
        engine.train_batch(batch=batch)
        assert len(record.spans()) == n_spans
    finally:
        logger.removeHandler(handler)
    said = [m for m in lines if "set-up: import" in m]
    assert len(said) == 1 and "first run" in said[0] and "misses)" in said[0]
    rows = engine.setup_report()["programs"]
    assert set(rows) == {"eval", "train_step"}
    assert all(r["trace_s"] > 0 and r["compile_s"] > 0 for r in rows.values())


class _Collect(logging.Handler):
    def __init__(self, lines):
        super().__init__()
        self.lines = lines

    def emit(self, rec):
        self.lines.append(rec.getMessage())


def test_importing_the_package_left_its_span():
    """In the process's own record, from the import that brought this test
    here (unless an earlier test filled the record first)."""
    rec = get_setup_record()
    first = rec.spans()[0]
    assert first.name == "setup.import" and first.span_id == 1 and first.t1 > first.t0


# ------------------------------------------------------- /health and /metrics
def test_health_and_metrics_carry_the_set_up_block(record):
    from deepspeed_tpu.serving.driver import ServingDriver
    from deepspeed_tpu.serving.server import start_server
    from tests.unit.test_serving import FakeEngine

    with record.span("setup.build_stack"):
        with record.span("program.first_call", key="split[(0, 0)]"):
            record.on_event(sr.CACHE_ASKED)
            record.on_duration(BACKEND, 0.25, fun_name="jit(step)")
    driver = ServingDriver(FakeEngine(), max_queue=4)
    driver.start()
    server = start_server(driver, host="127.0.0.1", port=0, tokenizer=None)
    host, port = server.server_address[:2]
    try:
        with urllib.request.urlopen(f"http://{host}:{port}/health", timeout=10) as r:
            health = json.loads(r.read())
        with urllib.request.urlopen(f"http://{host}:{port}/metrics", timeout=10) as r:
            text = r.read().decode()
    finally:
        server.shutdown()
        driver.shutdown(drain=False)
    block = health["setup"]
    assert set(block["phases_s"]) == set(sr.PHASES)
    assert block["programs"]["split[(0, 0)]"]["compiles"] == 1
    assert (block["compile_events"], block["cache_hits"], block["cache_misses"]) == (1, 0, 1)
    assert block["dropped"] == 0 and block["spans"] == 3
    for phase in sr.PHASES:
        assert f'dstpu_setup_seconds{{phase="{phase}"}} ' in text
    assert "# TYPE dstpu_setup_seconds gauge" in text
    assert "dstpu_compile_events_total 1.0" in text
    assert "dstpu_compile_cache_hits_total 0.0" in text
    assert "dstpu_compile_cache_misses_total 1.0" in text


def test_the_router_health_carries_the_block_too(record):
    from deepspeed_tpu.serving.cluster import Router
    from tests.unit.test_serving import FakeEngine

    router = Router(engines=[FakeEngine(), FakeEngine()], num_prefill_workers=0)
    try:
        assert set(router.health()["setup"]["phases_s"]) == set(sr.PHASES)
    finally:
        router.shutdown(drain=False)


# ---------------------------------------------------- the benchmark's readers
def reader(name):
    return importlib.import_module(f"benchmarks.metrics.{name}").read


def synthetic(record_cls=SetupRecord, cache="hit"):
    """A run's set-up on a fake clock: process start at 10, import, a stack
    with an engine and two programs warmed on a thread of their own, the
    benchmark's own compile outside every span, the window's start at 40."""
    clock = Clock(10.0)
    rec = record_cls(clock=clock)
    clock.t = 12.0
    rec.add("setup.import", 10.75, 12.0)
    compile_program(rec, clock, "init_params", trace=0.5, lower=0.25, backend=1.25, cache=cache)
    with rec.span("setup.build_stack"):
        clock.t += 0.5
        with rec.span("setup.build_engine"):
            clock.t += 1.0
            compile_program(rec, clock, "zeros", trace=0.125, lower=0.125, backend=0.25,
                            cache=cache)

        def warm():
            for key in ("split[(1, 128)]", "split[(0, 0)]"):
                with rec.span("program.first_call", key=key):
                    with rec.span("program.first_call", key=key):
                        clock.t += 0.125
                        compile_program(rec, clock, "step", trace=0.75, lower=0.875, backend=0.5,
                                        cache=cache)
                        clock.t += 0.125
                    clock.t += 0.375

        t = threading.Thread(target=warm)
        t.start()
        t.join(30)
        clock.t += 0.25
    clock.t = 60.0
    return rec, {"t_proc0": 10.0, "t_window0": 40.0}


def test_the_seven_seconds_add_up_to_setup_s():
    old = get_setup_record()
    try:
        rec, run = synthetic()
        set_setup_record(rec)
        got = {name: reader(name)(run) for name in READERS}
    finally:
        set_setup_record(old)
    assert got["setup_import_s"] == 1.25
    assert got["setup_state_s"] == 0.5 + 1.0 + 0.25
    assert got["setup_trace_s"] == 0.125 + 2 * 0.75
    assert got["setup_lower_s"] == 0.125 + 2 * 0.875
    assert got["setup_backend_compile_s"] == 0.25 + 2 * 0.5
    assert got["setup_first_run_s"] == 2 * (0.125 + 0.125 + 0.375)
    # the benchmark's own compile is nobody's span: it is outside, with the rest
    assert abs(sum(got[name] for name in SECONDS) - (run["t_window0"] - run["t_proc0"])) < 1e-3
    assert got["setup_outside_s"] == 30.0 - sum(got[name] for name in SECONDS[:-1])
    assert got["setup_programs"] == 3.0 and got["setup_cache_hit_pct"] == 100.0


@pytest.mark.parametrize("cache,share", [("miss", 0.0), ("hit", 100.0), (None, None)])
def test_a_cold_and_a_warm_report_read_0_and_100(cache, share):
    old = get_setup_record()
    try:
        rec, run = synthetic(cache=cache)
        set_setup_record(rec)
        assert reader("setup_cache_hit_pct")(run) == share
        assert reader("setup_programs")(run) == 3.0  # the same cold and warm
    finally:
        set_setup_record(old)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_in_an_empty_record(name, record):
    assert reader(name)({"t_proc0": 10.0, "t_window0": 40.0}) is None


@pytest.mark.parametrize("name", READERS)
def test_a_reader_is_in_the_index_as_the_issue_states_it(name):
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    index = json.load(open(os.path.join(root, "BENCHMARK.json")))
    (entry,) = [m for m in index["per_layer"] if m["name"] == name]
    assert entry["layer"] == "entry points" and entry["moves"] == "setup_s"
    # (since PR 53 each lists its cells: every cell reports setup_s, and the
    # driver's check refuses a NEW cell over a metric that lists none)
    assert entry["source"] == "program_span"
    assert entry["workloads"] == [w["name"] for w in index["workloads"]]
    assert entry["better"] == ("higher" if name == "setup_cache_hit_pct" else "lower")
