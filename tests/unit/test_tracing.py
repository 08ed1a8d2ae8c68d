"""End-to-end request tracing tests: span tracer semantics, capture
policy, Chrome-trace export, the driver/router span threading, that the
histograms and the span trees read the same stamps, and the observability satellites
(label escaping/validation, quantile clamp, device_synchronize, the
to_events -> Monitor bridge).

The serving tests run socket-free on ``FakeEngine`` (real scheduler +
allocator, deterministic fake compute) so span trees can be asserted
token-for-token; the HTTP surface is covered in test_serving_http.py.
"""

import json
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from deepspeed_tpu.observability import (
    NULL_TRACER,
    EventLog,
    SpanTracer,
    begin_request_trace,
    configure_tracing,
    finish_request_trace,
    get_event_log,
    get_tracer,
    log_event,
    mark_admitted,
    mark_first_token,
    set_tracer,
    to_chrome_trace,
    trace_to_chrome,
    validate_chrome_trace,
    write_trace,
)
from deepspeed_tpu.serving.cluster import Router
from deepspeed_tpu.serving.driver import ServingDriver
from deepspeed_tpu.serving.metrics import Histogram, ServingMetrics
from deepspeed_tpu.serving.request import Request, RequestState, SamplingParams
from tests.unit.test_serving import FakeEngine, _expected_tokens


@pytest.fixture(autouse=True)
def _isolated_tracer():
    """Every test starts and ends with tracing OFF and an empty event log
    (the tracer is a process-global; leaking one across tests would make
    unrelated serving tests allocate spans)."""
    set_tracer(NULL_TRACER)
    get_event_log().clear()
    yield
    set_tracer(NULL_TRACER)
    get_event_log().clear()


def _params(n_new, **kw):
    return SamplingParams(max_new_tokens=n_new, ignore_eos=True, **kw)


def _by_name(spans):
    out = {}
    for sp in spans:
        out.setdefault(sp.name, []).append(sp)
    return out


def _assert_single_rooted(spans):
    """Exactly one root; every other span's parent chain reaches it."""
    ids = {sp.span_id: sp for sp in spans}
    roots = [sp for sp in spans if sp.parent_id is None]
    assert len(roots) == 1, f"want one root, got {[r.name for r in roots]}"
    root = roots[0]
    for sp in spans:
        seen = set()
        cur = sp
        while cur.parent_id is not None:
            assert cur.span_id not in seen, f"parent cycle at {cur.name}"
            seen.add(cur.span_id)
            assert cur.parent_id in ids, (
                f"{cur.name} parents onto a span outside the tree")
            cur = ids[cur.parent_id]
        assert cur is root
    return root


# -- tracer core ---------------------------------------------------------
class TestSpanTracer:
    def test_tree_lifecycle_and_parent_default(self):
        tr = SpanTracer()
        root = tr.begin_trace(7, "request", t0=1.0, args={"uid": 7})
        child = tr.start(7, "queued", t0=1.0)
        assert child.parent_id == root.span_id  # defaults onto the root
        grand = tr.start(7, "placement", parent=child, t0=1.5)
        assert grand.parent_id == child.span_id
        tr.end(grand, t1=1.6, args={"core": "d0"})
        assert grand.duration_s == pytest.approx(0.1)
        assert grand.args["core"] == "d0"
        assert tr.end_trace(7, meta={"finish_reason": "stop"})
        rec = tr.trace(7)
        assert rec["complete"] and rec["meta"]["finish_reason"] == "stop"
        assert [s.name for s in rec["spans"]] == ["request", "queued", "placement"]
        _assert_single_rooted(rec["spans"])

    def test_unknown_key_spans_dropped(self):
        tr = SpanTracer()
        sp = tr.start(999, "late", t0=0.0)
        assert sp.name == "late"  # caller still gets a span to end()
        assert tr.trace(999) is None
        assert tr.dropped_spans == 1

    def test_ring_and_instant_and_ctx_manager(self):
        tr = SpanTracer()
        with tr.span("round.verify", track="d0", args={"rows": 3}) as sp:
            pass
        assert sp.t1 is not None
        mark = tr.instant("host_tier.spill", track="d0", args={"block": 5})
        assert mark.t0 == mark.t1
        ring = tr.ring_spans()
        assert [s.name for s in ring] == ["round.verify", "host_tier.spill"]
        assert all(s.track == "d0" for s in ring)

    def test_ring_bounded_and_min_clamp(self):
        tr = SpanTracer(max_events=10)  # clamps up to 256
        assert tr.max_events == 256
        for i in range(300):
            tr.instant(f"e{i}")
        assert len(tr.ring_spans()) == 256

    def test_completed_trace_budget_eviction(self):
        tr = SpanTracer(max_events=256)
        for uid in range(4):
            tr.begin_trace(uid, "request", t0=0.0)
            for j in range(99):
                tr.end(tr.start(uid, f"s{j}", t0=0.0), t1=0.0)
            tr.end(tr.trace(uid)["spans"][0], t1=1.0)
            tr.end_trace(uid)
        # 4 * 100 spans > 256 budget: oldest trees evicted, newest kept
        keys = [rec["key"] for rec in tr.traces()]
        assert 3 in keys and 0 not in keys
        assert tr.stats()["completed_spans"] <= 256
        assert tr.dropped_traces >= 1

    def test_begin_trace_replaces_stale_tree(self):
        tr = SpanTracer()
        tr.begin_trace(1, "request", t0=0.0)
        tr.start(1, "queued", t0=0.0)
        tr.begin_trace(1, "request", t0=5.0)  # uid reuse: stale tree gone
        assert len(tr.trace(1)["spans"]) == 1

    def test_stats_shape(self):
        tr = SpanTracer()
        tr.begin_trace(1, "request")
        st = tr.stats()
        assert st["enabled"] and st["active_traces"] == 1
        assert st["completed_traces"] == 0


class TestCapturePolicy:
    def _finished_trace(self, tr, uid, e2e, slow_hint=False):
        root = tr.begin_trace(uid, "request", t0=0.0)
        tr.end(root, t1=e2e)
        return tr.end_trace(uid, slow_hint=slow_hint)

    def test_warmup_keeps_everything(self):
        tr = SpanTracer(capture="slow")
        assert all(self._finished_trace(tr, uid, 0.001)
                   for uid in range(tr.WARMUP))

    def test_post_warmup_keeps_only_slow(self):
        tr = SpanTracer(capture="slow")
        tr._e2e_samples.extend([1.0] * tr.RESERVOIR)  # saturate the reservoir
        assert not self._finished_trace(tr, 1, 0.001)       # fast: dropped
        assert self._finished_trace(tr, 2, 2.0)             # >= p90: kept
        assert self._finished_trace(tr, 3, 0.001, slow_hint=True)  # errors: kept
        # never-finished trees are retained regardless of latency
        tr.begin_trace(4, "request", t0=0.0)
        assert tr.end_trace(4)

    def test_capture_all_keeps_fast(self):
        tr = SpanTracer(capture="all")
        tr._e2e_samples.extend([1.0] * tr.RESERVOIR)
        assert self._finished_trace(tr, 1, 0.001)

    def test_bad_capture_mode_rejected(self):
        with pytest.raises(ValueError, match="capture"):
            SpanTracer(capture="sometimes")


class TestNullTracer:
    def test_noop_identity_no_per_call_allocation(self):
        """The tracing-off acceptance bar: every call returns the SAME
        shared singleton — the hot path allocates nothing per token."""
        tr = NULL_TRACER
        assert not tr.enabled
        handles = {
            id(tr.span("a")), id(tr.span("b")),
            id(tr.start(None, "c")), id(tr.begin_trace(1, "d")),
            id(tr.complete("e", 0.0)), id(tr.instant("f")),
        }
        assert len(handles) == 1  # one object, reused forever
        with tr.span("g") as sp:
            assert sp is tr.span("h")
        tr.end(sp)  # no-op, no error
        assert tr.end_trace(1) is False
        assert tr.stats() == {"enabled": False}

    def test_configure_tracing_switches_global(self):
        live = configure_tracing(enabled=True, max_events=512, capture="slow")
        assert get_tracer() is live and live.enabled
        assert live.max_events == 512 and live.capture == "slow"
        configure_tracing(enabled=False)
        assert get_tracer() is NULL_TRACER


# -- control-plane event log ---------------------------------------------
class TestEventLog:
    def test_bounded_newest_first(self):
        log = EventLog(maxlen=4)
        for i in range(6):
            log.emit("shed_level", level=i)
        assert len(log) == 4 and log.total == 6
        recent = log.recent(2)
        assert [e["level"] for e in recent] == [5, 4]
        assert all(e["kind"] == "shed_level" for e in recent)
        oldest_first = [e.fields["level"] for e in log.events()]
        assert oldest_first == [2, 3, 4, 5]

    def test_global_log(self):
        log_event("scale_up", replica="d1")
        assert get_event_log().recent(1)[0]["kind"] == "scale_up"


# -- Chrome-trace export -------------------------------------------------
class TestChromeExport:
    def _small_tracer(self):
        tr = SpanTracer()
        root = tr.begin_trace(3, "request", t0=1.0, args={"uid": 3})
        tr.end(tr.start(3, "queued", t0=1.0), t1=1.1)
        tr.end(root, t1=2.0)
        tr.end_trace(3)
        tr.complete("round.verify", 1.2, 1.3, track="d0", args={"rows": 2})
        return tr

    def test_export_layout_and_validation(self):
        tr = self._small_tracer()
        log = EventLog()
        log.emit("preempt", uid=3)
        doc = to_chrome_trace(tracer=tr, event_log=log)
        assert validate_chrome_trace(doc) == []
        evs = doc["traceEvents"]
        assert doc["displayTimeUnit"] == "ms"
        xs = [e for e in evs if e["ph"] == "X"]
        assert {e["pid"] for e in xs} == {1, 2}  # requests + engines
        req_evs = [e for e in xs if e["pid"] == 1]
        assert {e["name"] for e in req_evs} == {"request", "queued"}
        assert all(e["tid"] == 3 for e in req_evs)  # tid == uid
        root_ev = next(e for e in req_evs if e["name"] == "request")
        assert root_ev["ts"] == 1.0e6 and root_ev["dur"] == 1.0e6  # microseconds
        instants = [e for e in evs if e["ph"] == "i"]
        assert [e["name"] for e in instants] == ["preempt"]
        assert instants[0]["pid"] == 3 and instants[0]["s"] == "g"
        names = {(e["pid"], e["args"]["name"]) for e in evs
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert names == {(1, "requests"), (2, "engines"), (3, "control")}
        json.dumps(doc)  # must be serializable as-is

    def test_open_spans_export_with_marker(self):
        tr = SpanTracer()
        tr.begin_trace(1, "request", t0=1.0)
        doc = trace_to_chrome(tr.trace(1), now=4.0)
        ev = next(e for e in doc["traceEvents"] if e["ph"] == "X")
        assert ev["args"]["open"] is True
        assert ev["dur"] == 3.0e6  # extends to `now`
        assert validate_chrome_trace(doc) == []

    def test_validator_rejects_garbage(self):
        assert validate_chrome_trace([]) != []
        assert validate_chrome_trace({"traceEvents": 3}) != []
        bad = {"traceEvents": [
            {"ph": "Q", "name": "x", "pid": 1},
            {"ph": "X", "pid": 1, "ts": 0.0, "dur": 1.0},          # no name
            {"ph": "X", "name": "y", "pid": 1, "ts": float("nan"), "dur": 1.0},
            {"ph": "X", "name": "z", "pid": 1, "ts": 0.0, "dur": -1.0},
        ]}
        errs = validate_chrome_trace(bad)
        assert len(errs) == 4

    def test_write_trace_validates(self, tmp_path):
        tr = self._small_tracer()
        path = str(tmp_path / "out.trace.json")
        write_trace(path, to_chrome_trace(tracer=tr))
        with open(path) as f:
            assert validate_chrome_trace(json.load(f)) == []
        with pytest.raises(ValueError, match="invalid"):
            write_trace(str(tmp_path / "bad.json"), {"traceEvents": [{}]})


# -- serving integration: single driver ----------------------------------
class TestDriverTracing:
    def test_rooted_tree_endpoints_are_the_requests_stamps(self):
        tracer = set_tracer(SpanTracer())
        eng = FakeEngine()
        driver = ServingDriver(eng, max_queue=8)
        driver.start()
        try:
            prompt = np.asarray([5, 6, 7], np.int32)
            req = driver.submit(prompt, params=_params(4))
            assert req.wait(30) and req.state == RequestState.FINISHED
            assert req.generated == _expected_tokens(prompt, 4)
        finally:
            driver.shutdown(drain=False)
        assert req.trace is None  # detached at finish
        rec = tracer.trace(req.uid)
        assert rec is not None and rec["complete"]
        root = _assert_single_rooted(rec["spans"])
        names = _by_name(rec["spans"])
        # lifecycle phases in causal order, parented on the root
        for phase in ("queued", "prefill", "decode"):
            assert phase in names, f"missing {phase} in {sorted(names)}"
            assert names[phase][0].parent_id == root.span_id
        assert names["queued"][0].t1 == names["prefill"][0].t0
        assert names["prefill"][0].t1 == names["decode"][0].t0
        assert root.args["finish_reason"] == "max_tokens"
        assert root.args["tokens"] == 4
        assert rec["meta"]["tenant"] == "default"
        # the tree's endpoints ARE the request's own stamps, so the timeline
        # and the histograms (folded from the stamps, traced or not) agree
        assert (root.t0, root.t1) == (req.t_submit, req.t_finish)
        assert names["prefill"][0].t0 == req.t_admitted
        assert names["decode"][0].t0 == req.t_first_token
        assert driver.metrics.e2e.count == 1
        assert driver.metrics.ttft.count == 1
        assert driver.metrics.e2e.total == pytest.approx(root.t1 - root.t0)
        assert driver.metrics.ttft.total == pytest.approx(
            names["decode"][0].t0 - root.t0)
        # and the tree exports as a valid Chrome-trace document
        assert validate_chrome_trace(trace_to_chrome(rec)) == []

    def test_tracing_off_leaves_requests_clean(self):
        eng = FakeEngine()
        driver = ServingDriver(eng, max_queue=8)
        driver.start()
        try:
            req = driver.submit(np.asarray([3], np.int32), params=_params(2))
            assert req.wait(30)
        finally:
            driver.shutdown(drain=False)
        assert req.trace is None
        assert get_tracer() is NULL_TRACER
        assert driver.metrics.e2e.count == 1


# -- serving integration: router (disagg + elastic) ----------------------
class TestRouterTracing:
    def test_disagg_tree_covers_placement_handoff_rounds(self):
        """The PR acceptance bar: admission -> placement -> prefill ->
        handoff -> decode rounds -> finish, one rooted tree."""
        tracer = set_tracer(SpanTracer())
        engines = [FakeEngine(step_delay=0.001) for _ in range(2)]
        router = Router(engines=engines, num_prefill_workers=1).start()
        try:
            prompt = np.arange(1, 7, dtype=np.int32)
            req = router.submit(prompt, params=_params(6))
            assert req.wait(30) and req.state == RequestState.FINISHED
            assert req.generated == _expected_tokens(prompt, 6)
        finally:
            router.shutdown(drain=False)
        rec = tracer.trace(req.uid)
        assert rec is not None and rec["complete"]
        root = _assert_single_rooted(rec["spans"])
        names = _by_name(rec["spans"])
        for required in ("queued", "placement", "prefill", "handoff.export",
                        "handoff.import", "decode", "step.decode"):
            assert required in names, f"missing {required} in {sorted(names)}"
        place = names["placement"][0]
        assert "prefill" in place.args and "decode" in place.args
        assert names["handoff.export"][0].args["blocks"] >= 1
        assert names["handoff.import"][0].args["blocks"] >= 1
        # decode rounds land inside the decode phase
        decode = names["decode"][0]
        in_decode = [sp for sp in names["step.decode"]
                     if sp.parent_id == decode.span_id]
        assert in_decode, "no step rounds parented on the decode phase"
        assert root.args["finish_reason"] == "max_tokens"
        # the engine ring carries the per-replica timeline of the same rounds
        ring_tracks = {sp.track for sp in tracer.ring_spans()}
        assert "p0" in ring_tracks and "d0" in ring_tracks
        assert validate_chrome_trace(to_chrome_trace(tracer=tracer)) == []

    def test_preempt_resume_spans_and_events(self):
        from deepspeed_tpu.serving.elastic import ElasticServingConfig

        tracer = set_tracer(SpanTracer())
        eng = FakeEngine(step_delay=0.003)
        cfg = ElasticServingConfig(max_decode_replicas=1)
        router = Router(engines=[eng], num_prefill_workers=0,
                        elastic=cfg).start()
        try:
            prompt = np.arange(1, 9, dtype=np.int32)
            req = router.submit(prompt, params=_params(24, qos="batch"))
            assert req.stream.get(timeout=10) is not None
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and not req.is_terminal:
                if router.preempt(req.uid):
                    break
                time.sleep(0.002)
            assert req.preemptions == 1
            assert req.wait(30) and req.state == RequestState.FINISHED
            assert req.generated == _expected_tokens(prompt, 24)
        finally:
            router.shutdown(drain=False)
        rec = tracer.trace(req.uid)
        _assert_single_rooted(rec["spans"])
        names = _by_name(rec["spans"])
        for required in ("preempted", "preempt", "resume"):
            assert required in names, f"missing {required} in {sorted(names)}"
        assert len(names["decode"]) == 2  # decode -> preempted -> decode again
        assert names["preempt"][0].args["blocks"] >= 1
        # slow-capture treats preempted requests as always-interesting
        assert rec["slow"]
        kinds = [e["kind"] for e in get_event_log().recent()]
        assert "preempt" in kinds and "resume" in kinds


# -- one source for the histograms: the request's stamps ------------------
def _stamped_request(uid, tracer=None):
    req = Request(uid=uid, prompt_tokens=np.asarray([1, 2], np.int32),
                  params=_params(8))
    req.t_submit = 100.0
    req.generated = [3, 4, 5, 6]
    if tracer is not None:
        begin_request_trace(tracer, req)
    req.t_admitted = 100.5
    mark_admitted(req, core="d0")
    req.t_first_token = 101.0
    mark_first_token(req)
    req.t_finish = 103.0
    req.finish_reason = "max_tokens"
    return req


class TestHistogramsFoldTheStamps:
    def test_traced_and_untraced_requests_fold_the_same_values(self):
        """A traced run records more; it does not compute its figures
        differently: the same stamps fold the same histogram values."""
        tracer = SpanTracer()
        traced_req, plain_req = _stamped_request(11, tracer), _stamped_request(12)
        assert traced_req.trace is not None and plain_req.trace is None
        traced, plain = ServingMetrics(), ServingMetrics()
        traced.observe_request(traced_req)
        finish_request_trace(traced_req)
        plain.observe_request(plain_req)
        for attr in ("ttft", "tpot", "e2e"):
            a, b = getattr(traced, attr), getattr(plain, attr)
            assert (a.count, a.total) == (b.count, b.total), attr
            assert a.counts == b.counts, attr
        assert traced.ttft.total == pytest.approx(1.0)
        assert traced.tpot.total == pytest.approx(2.0 / 3.0)
        assert traced.e2e.total == pytest.approx(3.0)

    def test_span_tree_endpoints_equal_the_stamps(self):
        tracer = SpanTracer()
        req = _stamped_request(13, tracer)
        finish_request_trace(req)
        names = _by_name(tracer.trace(13)["spans"])
        root = names["request"][0]
        assert (root.t0, root.t1) == (100.0, 103.0)
        assert (names["queued"][0].t0, names["queued"][0].t1) == (100.0, 100.5)
        assert (names["prefill"][0].t0, names["prefill"][0].t1) == (100.5, 101.0)
        assert (names["decode"][0].t0, names["decode"][0].t1) == (101.0, 103.0)


# -- satellite: quantile clamp -------------------------------------------
class TestQuantileClamp:
    def test_inf_bucket_clamps_to_largest_finite_edge(self):
        h = Histogram(buckets=(1.0, 2.0))
        h.observe(5.0)  # lands in +Inf
        assert h.quantile(0.99) == 2.0  # finite, not float("inf")
        assert h.quantile(0.5) == 2.0

    def test_normal_quantiles_unchanged(self):
        h = Histogram(buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 3.0):
            h.observe(v)
        assert h.quantile(0.33) == 1.0
        assert h.quantile(1.0) == 4.0
        assert Histogram(buckets=(1.0,)).quantile(0.5) == 0.0  # empty


# -- satellite: Prometheus label escaping + input validation -------------
class TestLabelSafety:
    def test_escape_label_value(self):
        from deepspeed_tpu.monitor.monitor import escape_label_value

        assert escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
        assert escape_label_value("plain") == "plain"

    def test_renderer_escapes_injected_labels(self):
        from deepspeed_tpu.monitor.monitor import render_prometheus_text

        evil = 'x"} 1\nevil_metric{t="'
        text = render_prometheus_text([("m", {"tenant": evil}, 1.0, "gauge")])
        assert "\nevil_metric" not in text  # newline neutralized
        assert '\\"' in text and "\\n" in text
        assert len([l for l in text.splitlines() if l]) == 2  # TYPE + sample

    @pytest.mark.parametrize("tenant", ["", "a\nb", "a\x00b", "x" * 65, "\x7f"])
    def test_bad_tenant_rejected_at_admission(self, tenant):
        with pytest.raises(ValueError, match="tenant"):
            SamplingParams(tenant=tenant)

    def test_bad_trace_id_rejected(self):
        with pytest.raises(ValueError, match="trace_id"):
            SamplingParams(trace_id="a\nb")
        assert SamplingParams(trace_id="req-01").trace_id == "req-01"

    def test_tier_metrics_with_hostile_tenant_stay_parseable(self):
        m = ServingMetrics()
        m.observe_tier('ten"ant', "batch", "finished_total")
        text = m.prometheus_text()
        for line in text.splitlines():
            assert not line.startswith("evil")
            if "tier_finished_total{" in line:
                assert 'tenant="ten\\"ant"' in line


# -- satellite: device_synchronize ---------------------------------------
class TestDeviceSynchronize:
    def test_barrier_runs_and_caches_probe(self):
        import deepspeed_tpu.utils.timer as timer_mod

        timer_mod.device_synchronize()
        first = timer_mod._SYNC_FN
        assert first is not None  # jitted probe built once...
        timer_mod.device_synchronize()
        assert timer_mod._SYNC_FN is first  # ...and reused

    def test_tree_argument_blocks_on_given_arrays(self):
        import jax.numpy as jnp

        from deepspeed_tpu.utils.timer import device_synchronize

        device_synchronize([jnp.zeros((2,)), jnp.ones((3,))])
        device_synchronize(np.zeros(2))  # host arrays are fine too
        device_synchronize(None)

    def test_legacy_alias(self):
        from deepspeed_tpu.utils.timer import (
            _device_synchronize,
            device_synchronize,
        )

        assert _device_synchronize is device_synchronize


# -- satellite: to_events -> Monitor bridge ------------------------------
class TestMonitorBridge:
    def _labeled_metrics(self):
        m = ServingMetrics()
        m.inc("requests_finished_total", 3)
        m.observe_request(SimpleNamespace(ttft_s=0.5, tpot_s=0.01, e2e_s=1.0))
        m.update_replica("d0", {"free_blocks": 7, "resident": 2.0,
                                "role_str": "decode"}, role="decode")
        m.observe_tier("acme", "interactive", "finished_total")
        m.observe_tier("acme", "interactive", "ttft_s", 0.25)
        return m

    def test_to_events_carries_labeled_families(self):
        events = {name: value for name, value, _ in
                  self._labeled_metrics().to_events()}
        assert events["Serving/replica_d0_free_blocks"] == 7
        assert events["Serving/replica_d0_resident"] == 2.0
        assert "Serving/replica_d0_role_str" not in events  # non-numeric dropped
        assert events["Serving/tier_acme_interactive_finished_total"] == 1.0
        assert events["Serving/tier_acme_interactive_ttft_sum_s"] == 0.25
        assert events["Serving/ttft_s_mean"] == pytest.approx(0.5)
        steps = {step for _, _, step in self._labeled_metrics().to_events()}
        assert steps == {3}  # finished count is the default serving clock

    def test_csv_monitor_lands_tier_and_replica_files(self, tmp_path):
        from deepspeed_tpu.monitor.monitor import csvMonitor

        mon = csvMonitor(SimpleNamespace(enabled=True,
                                         output_path=str(tmp_path),
                                         job_name="serve"))
        mon.write_events(self._labeled_metrics().to_events())
        tier = tmp_path / "serve" / "Serving_tier_acme_interactive_finished_total.csv"
        replica = tmp_path / "serve" / "Serving_replica_d0_free_blocks.csv"
        assert tier.exists() and replica.exists()
        rows = tier.read_text().splitlines()
        assert rows[0].startswith("step,") and rows[1] == "3,1.0"

    def test_prometheus_monitor_exposes_bridged_metrics(self):
        from deepspeed_tpu.monitor.monitor import PrometheusMonitor

        mon = PrometheusMonitor(SimpleNamespace(enabled=True, output_path=""))
        mon.write_events(self._labeled_metrics().to_events())
        text = mon.expose()
        assert "Serving_replica_d0_free_blocks 7.0" in text
        assert "Serving_tier_acme_interactive_finished_total 1.0" in text


# -- one step path, traced or not ----------------------------------------
class TestTracingOverheadShape:
    def test_disabled_step_path_records_nothing_and_serves_the_same(self):
        """With the NULL tracer installed a driver run records zero spans
        anywhere, and serves exactly the tokens the traced run serves: the
        step has ONE path, whose spans are the shared no-op when off (the
        real-engine side is in test_serving_spans.py)."""
        served = {}
        for label, tracer in (("off", NULL_TRACER), ("on", SpanTracer())):
            set_tracer(tracer)
            driver = ServingDriver(FakeEngine(), max_queue=8)
            driver.start()
            try:
                req = driver.submit(np.asarray([2], np.int32), params=_params(3))
                assert req.wait(30)
            finally:
                driver.shutdown(drain=False)
            served[label] = (req.generated, dict(driver.metrics.counters))
        assert NULL_TRACER.ring_spans() == []
        assert NULL_TRACER.recent() == []
        assert served["off"] == served["on"]
        names = {sp.name for sp in tracer.ring_spans()}
        # an engine that has only step_tokens() stamps nothing: the bracket of
        # the call is the step, named for its kind (no ``last_step``: decode)
        assert {"step.decode", "step.deliver", "loop.admit", "loop.bookkeeping"} <= names
        assert "step.split" not in names

    def test_an_engine_without_stamps_counts_steps_and_no_step_time(self):
        """The step's seconds on the device come from the engine's own stamps
        (``StepStats.t_ready`` / ``device_s``). A compute-free fake has none:
        its steps are counted, its step time and its timed steps stay 0, and
        no launch of its is starved; a ``last_step`` that shows prefill
        tokens names the bracket ``step.chunk``."""
        tracer = set_tracer(SpanTracer())
        eng = FakeEngine()
        real_step = eng.step_tokens

        def step_tokens():
            out = real_step()
            eng.last_step = SimpleNamespace(
                scheduled_tokens=len(out), prefill_tokens=5 if eng.steps == 1 else 0)
            return out

        eng.step_tokens = step_tokens
        driver = ServingDriver(eng, max_queue=8)
        driver.start()
        try:
            req = driver.submit(np.arange(1, 6, dtype=np.int32), params=_params(3))
            assert req.wait(30)
        finally:
            driver.shutdown(drain=False)
        c = driver.metrics.counters
        assert c["engine_steps_total"] >= 3 and c["steps_with_prefill_total"] == 1
        for name in ("decode_step_seconds_total", "chunk_step_seconds_total",
                     "decode_steps_timed_total", "chunk_steps_timed_total",
                     "steps_starved_total", "steps_ahead_total"):
            assert c[name] == 0, name
        steps = [sp.name for sp in tracer.ring_spans() if sp.name.startswith("step.")
                 and sp.name != "step.deliver"]
        assert steps[0] == "step.chunk" and set(steps[1:]) == {"step.decode"}
