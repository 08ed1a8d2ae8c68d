"""Elastic serving control plane tests.

Same split as test_disagg.py: the compute-free ``FakeEngine`` (real
scheduler + allocator + state manager) exercises QoS admission order,
preempt-and-requeue bookkeeping, the degradation ladder, warm-spare
scale-up/down, and Retry-After in milliseconds; the real-engine tests
prove the acceptance bars — a preempted-and-resumed stream is
BIT-IDENTICAL to an uninterrupted one (greedy and seeded; int8 KV marked
slow), and scale-up from a warm spare performs ZERO new compilations
(recompile-counter assertion over the engine's jit caches).
"""

import time
from types import SimpleNamespace

import numpy as np
import pytest

from deepspeed_tpu.elasticity import ElasticityConfigError
from deepspeed_tpu.elasticity.elasticity import ElasticityConfig
from deepspeed_tpu.serving import (
    DegradationLadder,
    ElasticServingConfig,
    RequestRejected,
    Router,
    SamplingParams,
    ServingDriver,
    WarmSparePool,
)
from deepspeed_tpu.serving.elastic import (
    ScalingSignals,
    assert_no_new_traces,
    plan_scaling,
    preempt_sequence,
    preemptible,
    resume_sequence,
)
from deepspeed_tpu.serving.metrics import ServingMetrics
from deepspeed_tpu.serving.request import QOS_LOWEST, QOS_TIERS, RequestState
from tests.unit.test_serving import FakeEngine, _expected_tokens


def _params(n_new, qos="standard", tenant="default", **kw):
    return SamplingParams(max_new_tokens=n_new, ignore_eos=True, qos=qos,
                          tenant=tenant, **kw)


def _preempt_soon(router, req, timeout=10):
    """Preempt ``req`` once it reaches steady-state decode (retry the race
    where the worker holds the pending token mid-step)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline and not req.is_terminal:
        if router.preempt(req.uid):
            return True
        time.sleep(0.002)
    return False


# -- configuration ------------------------------------------------------
class TestElasticConfig:
    def test_defaults_valid(self):
        cfg = ElasticServingConfig()
        assert cfg.min_decode_replicas == cfg.max_decode_replicas == 1

    @pytest.mark.parametrize("kw", [
        {"min_decode_replicas": 0},
        {"min_decode_replicas": 3, "max_decode_replicas": 2},
        {"control_interval_s": 0.0},
        {"scale_up_after": 0},
        {"scale_up_queue_per_replica": 0.0},
        {"shed_degrade_at": 0.0},
        {"shed_reject_at": 1.5},
        {"shed_degrade_at": 0.9, "shed_spec_off_at": 0.5},
        {"shed_max_new_tokens": 0},
    ])
    def test_invalid_bounds_are_loud(self, kw):
        with pytest.raises(ValueError):
            ElasticServingConfig(**kw)

    def test_from_dict_rejects_unknown_keys(self):
        cfg = ElasticServingConfig.from_dict({"max_decode_replicas": 3})
        assert cfg.max_decode_replicas == 3
        with pytest.raises(ValueError, match="unknown elastic serving keys"):
            ElasticServingConfig.from_dict({"max_gpus": 3})

    def test_from_elasticity_bridge(self):
        """The dormant training-side elasticity section drives the serving
        bounds: chip bounds become decode-replica bounds."""
        ecfg = ElasticityConfig(enabled=True, max_train_batch_size=64,
                                micro_batch_sizes=[2, 4], min_gpus=2,
                                max_gpus=6)
        cfg = ElasticServingConfig.from_elasticity(ecfg, scale_up_after=5)
        assert cfg.min_decode_replicas == 2
        assert cfg.max_decode_replicas == 6
        assert cfg.scale_up_after == 5

    def test_validate_fleet(self):
        cfg = ElasticServingConfig(min_decode_replicas=2, max_decode_replicas=4)
        cfg.validate_fleet(2, 2)
        with pytest.raises(ValueError, match="min_decode_replicas"):
            cfg.validate_fleet(1, 8)
        with pytest.raises(ValueError, match="warm spares"):
            cfg.validate_fleet(2, 1)

    def test_elasticity_config_validation_is_valueerror(self):
        """The training-side config validates loudly too, and its error is
        a ValueError so callers can catch either surface uniformly."""
        with pytest.raises(ValueError, match="min_gpus"):
            ElasticityConfig(min_gpus=0)
        assert issubclass(ElasticityConfigError, ValueError)
        with pytest.raises(ElasticityConfigError, match="micro_batch_sizes"):
            ElasticityConfig(micro_batch_sizes=[])
        with pytest.raises(ElasticityConfigError, match="max_gpus"):
            ElasticityConfig(min_gpus=4, max_gpus=2)


# -- degradation ladder -------------------------------------------------
class TestDegradationLadder:
    def _ladder(self, **kw):
        return DegradationLadder(ElasticServingConfig(
            shed_degrade_at=0.5, shed_spec_off_at=0.75, shed_reject_at=0.9,
            shed_max_new_tokens=32, **kw))

    def test_rung_ordering(self):
        lad = self._ladder()
        levels = [lad.level(d, 100) for d in (0, 49, 50, 74, 75, 89, 90, 100)]
        assert levels == [0, 0, 1, 1, 2, 2, 3, 3]
        assert levels == sorted(levels)  # monotone in occupancy

    def test_rungs_strictly_contain_each_other(self):
        lad = self._ladder()
        p = _params(500, qos="standard")
        d1 = lad.apply(p, 50, 100)
        assert d1.level == 1 and d1.degraded and not d1.reject
        assert d1.params.max_new_tokens == 32
        assert d1.params.spec is None  # rung 1 leaves spec alone
        d2 = lad.apply(p, 75, 100)
        assert d2.level == 2 and d2.params.max_new_tokens == 32
        assert d2.params.spec is not None and not d2.params.spec.enabled
        # the caller's params object is never mutated
        assert p.max_new_tokens == 500 and p.spec is None

    def test_interactive_rides_above_the_ladder(self):
        lad = self._ladder()
        p = _params(500, qos="interactive")
        for depth in (50, 75, 90, 100):
            d = lad.apply(p, depth, 100)
            assert not d.reject and not d.degraded and d.params is p

    def test_only_lowest_tier_rejected(self):
        lad = self._ladder()
        assert lad.apply(_params(8, qos="batch"), 95, 100).reject
        assert QOS_TIERS[QOS_LOWEST] == max(QOS_TIERS.values())
        d = lad.apply(_params(500, qos="standard"), 95, 100)
        assert not d.reject and d.degraded  # degraded, still admitted

    def test_short_requests_below_cap_untouched_at_rung_1(self):
        d = self._ladder().apply(_params(8, qos="batch"), 50, 100)
        assert not d.degraded and d.params.max_new_tokens == 8


# -- autoscaling plan (pure) --------------------------------------------
class TestPlanScaling:
    CFG = ElasticServingConfig(
        min_decode_replicas=1, max_decode_replicas=4,
        scale_up_queue_per_replica=2.0, scale_up_after=2, scale_down_after=3)

    def _sig(self, q, active=0, n=1, spares=1, slack=None):
        return ScalingSignals(queue_depth=q, active_requests=active,
                              n_decode=n, spares_available=spares,
                              min_queue_slack_s=slack)

    def test_scale_up_needs_sustained_pressure(self):
        d, up, down = plan_scaling(self._sig(4), self.CFG)
        assert (d, up) == (0, 1)  # first pressured sample only arms it
        d, up, down = plan_scaling(self._sig(4), self.CFG, up, down)
        assert d == 1  # second consecutive sample fires
        # a blip resets the streak
        d, up, down = plan_scaling(self._sig(4), self.CFG)
        d, up, down = plan_scaling(self._sig(0, active=2, n=2), self.CFG, up, down)
        assert d == 0 and up == 0

    def test_urgent_deadline_slack_counts_as_pressure(self):
        d, up, _ = plan_scaling(self._sig(1, slack=0.2), self.CFG)
        assert d == 0 and up == 1  # pressured despite queue/replica < 2

    def test_scale_down_needs_long_idle_streak(self):
        up = down = 0
        for i in range(3):
            d, up, down = plan_scaling(self._sig(0, active=0, n=2),
                                       self.CFG, up, down)
        assert d == -1 and i == 2

    def test_bounds_respected(self):
        d, _, _ = plan_scaling(self._sig(50, n=4), self.CFG, up_streak=9)
        assert d == 0  # at max: never exceeds
        d, _, _ = plan_scaling(self._sig(0, n=1), self.CFG, down_streak=99)
        assert d == 0  # at min: never retires the floor


# -- QoS tiers + preemption (FakeEngine) --------------------------------
class TestQoSPreemption:
    def test_preempt_resume_stream_identity(self):
        """Explicit preemption mid-stream: the request checkpoints off the
        engine, requeues, resumes, and the FULL stream matches the
        uninterrupted expectation exactly."""
        eng = FakeEngine(step_delay=0.003)
        cfg = ElasticServingConfig(max_decode_replicas=1)
        router = Router(engines=[eng], num_prefill_workers=0,
                        elastic=cfg).start()
        try:
            prompt = np.arange(1, 9, dtype=np.int32)
            r = router.submit(prompt, params=_params(24, qos="batch"))
            assert r.stream.get(timeout=10) is not None
            assert _preempt_soon(router, r)
            assert r.preemptions == 1
            assert r.wait(30) and r.state == RequestState.FINISHED
            assert r.generated == _expected_tokens(prompt, 24)
            snap = router.metrics.snapshot()
            assert snap["requests_preempted_total"] == 1
            assert snap["requests_resumed_total"] == 1
        finally:
            router.shutdown(drain=False)
        assert eng.state_manager.free_blocks == eng.config.kv_cache.num_blocks
        assert not eng.scheduler.has_work()

    def test_interactive_preempts_batch_under_pressure(self):
        """Capacity pressure: a batch-tier decode hogs the only pool; an
        interactive submit evicts it (strictly-lower-tier victim), runs
        first, and the victim resumes to a correct full stream."""
        eng = FakeEngine(block_size=4, num_blocks=8, max_blocks_per_seq=8,
                         max_context=64, step_delay=0.004)
        cfg = ElasticServingConfig(max_decode_replicas=1)
        router = Router(engines=[eng], num_prefill_workers=0,
                        elastic=cfg).start()
        try:
            prompt = np.arange(1, 9, dtype=np.int32)
            # (8 prompt + 24 new) / 4 = 8 blocks: the whole pool
            low = router.submit(prompt, params=_params(24, qos="batch"))
            assert low.stream.get(timeout=10) is not None  # decoding
            # high also needs the WHOLE pool: admission can never seat it
            # beside low, so the only way in is preempting the batch tier
            high = router.submit(prompt, params=_params(24, qos="interactive"))
            assert high.wait(30) and high.state == RequestState.FINISHED
            assert low.preemptions >= 1
            assert high.generated == _expected_tokens(prompt, 24)
            assert low.wait(30) and low.state == RequestState.FINISHED
            assert low.generated == _expected_tokens(prompt, 24)
            assert high.t_finish < low.t_finish
        finally:
            router.shutdown(drain=False)
        assert eng.state_manager.free_blocks == 8

    def test_equal_tier_never_preempts(self):
        """Victims must be STRICTLY lower tier: a standard request cannot
        evict another standard decode — it waits for capacity."""
        eng = FakeEngine(block_size=4, num_blocks=8, max_blocks_per_seq=8,
                         max_context=64, step_delay=0.002)
        cfg = ElasticServingConfig(max_decode_replicas=1)
        router = Router(engines=[eng], num_prefill_workers=0,
                        elastic=cfg).start()
        try:
            prompt = np.arange(1, 9, dtype=np.int32)
            a = router.submit(prompt, params=_params(24, qos="standard"))
            assert a.stream.get(timeout=10) is not None
            b = router.submit(prompt, params=_params(8, qos="standard"))
            assert a.wait(30) and b.wait(30)
            assert a.preemptions == 0
            assert a.generated == _expected_tokens(prompt, 24)
            assert b.generated == _expected_tokens(prompt, 8)
        finally:
            router.shutdown(drain=False)

    def test_admission_order_is_priority_then_arrival(self):
        """With one slow replica and a backlog, queued interactive work is
        seated before earlier-arriving batch work."""
        eng = FakeEngine(block_size=4, num_blocks=8, max_blocks_per_seq=8,
                         max_context=64, step_delay=0.004)
        router = Router(engines=[eng], num_prefill_workers=0,
                        elastic=ElasticServingConfig()).start()
        try:
            prompt = np.arange(1, 9, dtype=np.int32)
            running = router.submit(prompt, params=_params(16, qos="interactive"))
            assert running.stream.get(timeout=10) is not None
            low = router.submit(prompt, params=_params(4, qos="batch"))
            high = router.submit(prompt, params=_params(4, qos="interactive"))
            for r in (running, low, high):
                assert r.wait(30) and r.state == RequestState.FINISHED
            assert high.t_first_token < low.t_first_token
        finally:
            router.shutdown(drain=False)

    def test_preemptible_gates_and_checkpoint_shape(self):
        """Direct checkpoint invariants: only steady-state decode rows are
        preemptible; the checkpoint strips the pending token and the
        resumed row adopts it back through the scheduler."""
        eng = FakeEngine()
        assert not preemptible(eng, 7)  # no sequence
        eng.scheduler.submit(7, np.arange(1, 9, dtype=np.int32))
        assert not preemptible(eng, 7)  # mid-prefill: no pending token
        tok = eng.step_tokens()[7]
        eng.scheduler.feedback(7, tok)
        assert preemptible(eng, 7)
        seq = eng.state_manager.get_sequence(7)
        n_hist = len(seq.tokens)
        ck = preempt_sequence(eng, 7)
        assert ck.tokens == list(seq.tokens[:-1])
        assert ck.pending_token == tok
        assert ck.seen_tokens == n_hist - 1 == len(ck.tokens)
        eng.scheduler.finish(7)
        assert eng.state_manager.free_blocks == eng.config.kv_cache.num_blocks
        resume_sequence(eng, ck)
        seq2 = eng.state_manager.get_sequence(7)
        assert list(seq2.tokens) == ck.tokens + [tok]
        assert eng.scheduler.peek_next_token(7) == tok
        eng.scheduler.finish(7)


# -- load shedding on the router ----------------------------------------
class TestShedding:
    def test_lowest_tier_sheds_with_retry_after(self):
        """At the reject rung the bottom tier sheds with a Retry-After
        while interactive still admits; queue_full also carries one."""
        eng = FakeEngine()
        cfg = ElasticServingConfig(shed_degrade_at=0.01, shed_spec_off_at=0.01,
                                   shed_reject_at=0.01)
        # submit BEFORE start: nothing drains, so the occupancy each
        # admission decision sees is exactly what the test arranged
        router = Router(engines=[eng], num_prefill_workers=0, elastic=cfg,
                        max_queue=4)
        try:
            prompt = np.asarray([1, 2], np.int32)
            keep = [router.submit(prompt, params=_params(8)) for _ in range(2)]
            with pytest.raises(RequestRejected) as ei:
                router.submit(prompt, params=_params(4, qos="batch",
                                                     tenant="acme"))
            assert ei.value.reason == "shed"
            assert ei.value.retry_after_s >= 1.0
            ok = router.submit(prompt, params=_params(4, qos="interactive"))
            snap = router.metrics.snapshot()
            assert snap["requests_shed_total"] == 1
            assert snap["tier_acme_batch_shed_total"] == 1
            router.start()
            for r in keep + [ok]:
                assert r.wait(30)
        finally:
            router.shutdown(drain=False)

    def test_degraded_admission_caps_tokens(self):
        """Above the degrade rung a standard request is admitted with the
        capped budget — it finishes with shed_max_new_tokens tokens."""
        eng = FakeEngine()
        cfg = ElasticServingConfig(shed_degrade_at=0.01, shed_spec_off_at=0.02,
                                   shed_reject_at=0.9, shed_max_new_tokens=3)
        router = Router(engines=[eng], num_prefill_workers=0, elastic=cfg,
                        max_queue=100)
        try:
            prompt = np.asarray([1, 2], np.int32)
            first = router.submit(prompt, params=_params(30))   # rung 0
            degraded = router.submit(prompt, params=_params(30))  # rung 1
            router.start()
            assert first.wait(30) and degraded.wait(30)
            assert len(first.generated) == 30  # admitted at rung 0
            assert len(degraded.generated) == 3
            assert degraded.finish_reason == "max_tokens"
        finally:
            router.shutdown(drain=False)

    def test_queue_full_has_retry_after(self):
        eng = FakeEngine()
        router = Router(engines=[eng], num_prefill_workers=0, max_queue=1)
        try:
            router.submit(np.asarray([1], np.int32), params=_params(4))
            with pytest.raises(RequestRejected) as ei:
                router.submit(np.asarray([1], np.int32), params=_params(4))
            assert ei.value.reason == "queue_full"
            assert 1.0 <= ei.value.retry_after_s <= 120.0
        finally:
            router.shutdown(drain=False)


# -- autoscaling against the router (FakeEngine) ------------------------
class TestScaling:
    def _router(self, n_spares=1, **cfg_kw):
        # small pools: one resident request per replica, so a burst BUILDS
        # a queue (the pressure signal the control loop scales on)
        def mk():
            return FakeEngine(block_size=4, num_blocks=8, max_blocks_per_seq=8,
                              max_context=64, step_delay=0.004)

        cfg = ElasticServingConfig(
            min_decode_replicas=1, max_decode_replicas=1 + n_spares,
            control_interval_s=30.0, scale_up_after=1, scale_down_after=2,
            **cfg_kw)
        pool = WarmSparePool(factory=mk, count=n_spares)
        router = Router(engines=[mk()], num_prefill_workers=0, elastic=cfg,
                        spare_pool=pool).start()
        return router, pool

    def test_burst_scales_up_from_warm_spare_then_down(self):
        """Queue pressure pulls the warm spare into the fleet (no cold
        spawn), every request still streams exactly; a sustained idle
        streak retires the extra replica back into the pool re-warmed."""
        router, pool = self._router()
        ctl = router._controller
        try:
            # (8 prompt + 24 new) / 4 = the whole 8-block pool: one
            # resident per replica, so the burst queues — and queue
            # pressure is the scale-up signal
            prompt = np.arange(1, 9, dtype=np.int32)
            reqs = [router.submit(prompt, params=_params(24))
                    for _ in range(6)]
            assert ctl.step() == 1  # queue/replica >= 2 for scale_up_after=1
            assert pool.available == 0 and pool.spawned == 1
            assert len(router.decode) == 2
            assert router.health()["elastic"]["decode_replicas"] == 2
            assert router.assert_warm_replicas() >= 1
            for r in reqs:
                assert r.wait(30)
                assert r.generated == _expected_tokens(prompt, 24)
            # both replicas took work (round-robin over free capacity)
            assert all(c.engine.steps > 0 for c in router.decode)

            deadline = time.monotonic() + 10
            while len(router.decode) > 1:
                ctl.step()
                assert time.monotonic() < deadline, "never scaled down"
                time.sleep(0.01)
            assert pool.available == 1  # retiree parked back as a spare
            snap = router.metrics.snapshot()
            assert snap["scale_up_total"] == 1
            assert snap["scale_down_total"] == 1
            assert snap["decode_replicas"] == 1
        finally:
            router.shutdown(drain=False)

    def test_scale_up_bounded_by_pool(self):
        router, pool = self._router(n_spares=1)
        try:
            assert router.add_decode_replica() is not None
            assert router.add_decode_replica() is None  # pool empty
            assert len(router.decode) == 2
        finally:
            router.shutdown(drain=False)

    def test_scale_down_never_below_min(self):
        router, _ = self._router()
        try:
            assert router.remove_decode_replica() is None
        finally:
            router.shutdown(drain=False)

    def test_fleet_validated_at_construction(self):
        cfg = ElasticServingConfig(min_decode_replicas=2,
                                   max_decode_replicas=2)
        with pytest.raises(ValueError, match="min_decode_replicas"):
            Router(engines=[FakeEngine()], num_prefill_workers=0, elastic=cfg)

    def test_warm_spare_pool_counters_and_assert(self):
        pool = WarmSparePool(factory=FakeEngine, count=2)
        assert pool.available == 2 and pool.spawned == 2
        eng, baseline = pool.acquire()
        assert eng is not None and baseline == {}  # fakes have no jit caches
        assert_no_new_traces(eng, baseline)  # vacuously holds
        assert pool.available == 1
        pool.add(eng)
        assert pool.available == 2
        with pytest.raises(ValueError, match="needs a factory"):
            WarmSparePool(count=1)


# -- per-tenant / per-tier metrics --------------------------------------
class TestTierMetrics:
    def test_tier_labels_render(self):
        m = ServingMetrics()
        m.observe_tier("acme", "interactive", "finished_total")
        m.observe_tier("acme", "interactive", "ttft_s", 0.25)
        m.observe_tier("bulk", "batch", "shed_total")
        m.set_tier_queue_depth({("bulk", "batch"): 3})
        text = m.prometheus_text()
        assert ('dstpu_serving_tier_finished_total'
                '{tenant="acme",tier="interactive"} 1' in text)
        assert ('dstpu_serving_tier_queue_depth'
                '{tenant="bulk",tier="batch"} 3' in text)
        assert ('dstpu_serving_tier_shed_total'
                '{tenant="bulk",tier="batch"} 1' in text)
        snap = m.snapshot()
        assert snap["tier_acme_interactive_ttft_count"] == 1
        assert snap["tier_acme_interactive_ttft_sum_s"] == pytest.approx(0.25)

    def test_router_health_has_elastic_and_qos_blocks(self):
        eng = FakeEngine()
        cfg = ElasticServingConfig(max_decode_replicas=1)
        router = Router(engines=[eng], num_prefill_workers=0,
                        elastic=cfg).start()
        try:
            r = router.submit(np.asarray([1, 2], np.int32),
                              params=_params(4, qos="interactive",
                                             tenant="acme"))
            assert r.wait(30)
            h = router.health()
            assert h["elastic"]["enabled"] is True
            assert h["elastic"]["decode_replicas"] == 1
            assert h["elastic"]["max_decode_replicas"] == 1
            assert h["qos"]["acme/interactive"]["finished_total"] == 1
            assert h["qos"]["acme/interactive"]["ttft_count"] == 1
        finally:
            router.shutdown(drain=False)

    def test_plain_router_health_reports_elastic_disabled(self):
        router = Router(engines=[FakeEngine()], num_prefill_workers=0)
        h = router.health()
        assert h["elastic"]["enabled"] is False


# -- real engine: the acceptance bars -----------------------------------
@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from deepspeed_tpu.models import get_config, init_params

    cfg = get_config("tiny", n_layers=2, dtype="float32", max_seq_len=512)
    return cfg, init_params(cfg, jax.random.key(0))


def _real_engine(tiny_model, kv_dtype, sampling):
    from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    cfg, params = tiny_model
    rc = RaggedInferenceEngineConfig.from_dict({
        "dtype": "float32",
        "seed": 7,
        "kv_cache": {"block_size": 16, "num_blocks": 64,
                     "max_blocks_per_seq": 8, "kv_cache_dtype": kv_dtype},
        "state_manager": {"max_tracked_sequences": 8,
                          "max_ragged_batch_size": 128,
                          "max_ragged_sequence_count": 4,
                          "max_context": 256},
    })
    eng = InferenceEngineV2(cfg, params, rc)
    eng.set_sampling(**sampling)
    return eng


def _elastic_real_roundtrip(tiny_model, kv_dtype, sampling):
    """Acceptance bars on the real engine: (1) a stream preempted
    mid-decode and resumed is bit-identical to the single-engine driver's;
    (2) scale-up admits the warm spare with ZERO new compilations."""
    prompts = [np.arange(1 + 3 * i, 25 + 3 * i, dtype=np.int32)
               for i in range(2)]
    single = _real_engine(tiny_model, kv_dtype, sampling)
    drv = ServingDriver(single).start()
    want = []
    for p in prompts:
        r = drv.submit(p, params=_params(10))
        assert r.wait(300)
        want.append(list(r.generated))
    drv.shutdown()

    pool = WarmSparePool(
        factory=lambda: _real_engine(tiny_model, kv_dtype, sampling),
        count=1, warm_kw={"spec_k": 0})
    cfg = ElasticServingConfig(min_decode_replicas=1, max_decode_replicas=2,
                               control_interval_s=30.0)
    router = Router(engines=[_real_engine(tiny_model, kv_dtype, sampling)],
                    num_prefill_workers=0, elastic=cfg,
                    spare_pool=pool).start()
    try:
        r0 = router.submit(prompts[0], params=_params(10))
        assert r0.stream.get(timeout=300) is not None
        assert _preempt_soon(router, r0, timeout=60)
        assert router.add_decode_replica() is not None
        r1 = router.submit(prompts[1], params=_params(10))
        assert r0.wait(300) and r1.wait(300)
        assert [list(r0.generated), list(r1.generated)] == want, (
            f"elastic streams diverged ({kv_dtype}, {sampling})")
        assert r0.preemptions == 1
        # the warm spare's admission traced NOTHING new
        assert router.assert_warm_replicas() >= 1
    finally:
        router.shutdown(drain=False)


class TestElasticRealEngine:
    def test_preempt_resume_and_warm_scale_up_bf16(self, tiny_model):
        _elastic_real_roundtrip(tiny_model, "bf16", {"greedy": True})
        _elastic_real_roundtrip(
            tiny_model, "bf16",
            {"greedy": False, "temperature": 0.8, "seed": 123})

    @pytest.mark.slow
    def test_preempt_resume_int8_seeded(self, tiny_model):
        """int8 KV: quantized codes + scales checkpoint and resume
        bit-exactly, so the seeded stream still matches."""
        _elastic_real_roundtrip(
            tiny_model, "int8",
            {"greedy": False, "temperature": 0.8, "seed": 123})
