"""Serving subsystem tests: continuous-batching driver, streaming,
admission control, metrics, and the Prometheus monitor sink.

The driver tests run WITHOUT sockets and (mostly) without a model: a
compute-free ``FakeEngine`` implements the driver's engine protocol —
``scheduler`` / ``state_manager`` / ``step_tokens()`` — over the REAL
``RaggedScheduler`` + ``DSStateManager`` + ``BlockedAllocator`` stack, so
admission, KV accounting, capping, and cleanup are exercised for real
while each "engine step" is pure Python (next token = last token + 1).
"""

import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from deepspeed_tpu.inference.config import KVCacheConfig, StateManagerConfig
from deepspeed_tpu.inference.v2.ragged_manager import DSStateManager
from deepspeed_tpu.inference.v2.scheduler import RaggedScheduler
from deepspeed_tpu.models.transformer import RECURRENT
from deepspeed_tpu.serving.driver import RequestRejected, ServingDriver
from deepspeed_tpu.serving.metrics import Histogram, ServingMetrics
from deepspeed_tpu.serving.request import Request, RequestState, SamplingParams
from deepspeed_tpu.serving.streaming import (
    IncrementalDetokenizer,
    StreamClosed,
    TokenStream,
)


class FakeEngine:
    """Driver engine protocol over the real scheduler/allocator stack.

    Deterministic generation: each completed row emits last-token + 1, so a
    prompt ending in ``p`` streams ``p+1, p+2, ...`` — assertions can check
    exact token sequences without a model.
    """

    def __init__(self, block_size=4, num_blocks=256, max_blocks_per_seq=16,
                 max_tracked=32, batch_budget=64, max_rows=16,
                 max_context=4096, step_delay=0.0, vocab=1 << 30):
        kv = KVCacheConfig(block_size=block_size, num_blocks=num_blocks,
                           max_blocks_per_seq=max_blocks_per_seq)
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
        self.steps = 0
        self.step_delay = step_delay
        self.vocab = vocab
        self.fail_next = 0  # >0: that many step_tokens() calls raise

    def step_tokens(self):
        self.steps += 1
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
            if not chunked:  # decode row or final prompt chunk: token ready
                out[uid] = (int(toks[-1]) + 1) % self.vocab
        return out


@pytest.mark.parametrize("kind", sorted(RECURRENT))
def test_a_recurrent_kinds_counters_are_named_for_it(kind):
    """``T.RECURRENT`` is the one description of a recurrent kind, its
    counters included: every kind's pair is exported at zero, and a step's
    ``recurrent_*`` pair goes to the pair of the engine's kind alone."""
    from deepspeed_tpu.inference.v2.engine_v2 import StepStats
    from deepspeed_tpu.serving.cluster.core import EngineCore

    metrics = ServingMetrics()
    names = {k: (f"{k}_decode_rows_total", f"{k}_chunk_tokens_total") for k in RECURRENT}
    assert all(metrics.counters[n] == 0 for pair in names.values() for n in pair)
    eng = FakeEngine()
    eng._mc = SimpleNamespace(recurrent_kind=kind)
    core = EngineCore(eng, metrics=metrics)
    core._count_step(StepStats(recurrent_decode_rows=3, recurrent_chunk_tokens=20))
    core._count_step(StepStats(recurrent_decode_rows=4))
    for k, pair in names.items():
        assert [metrics.counters[n] for n in pair] == ([7, 20] if k == kind else [0, 0])
    assert f"{kind}_decode_rows_total 7" in metrics.prometheus_text()
    # a model without recurrent layers moves none of them
    plain = ServingMetrics()
    EngineCore(FakeEngine(), metrics=plain)._count_step(StepStats(scheduled_tokens=5))
    assert all(plain.counters[n] == 0 for pair in names.values() for n in pair)


def _expected_tokens(prompt, n):
    last = int(prompt[-1])
    return [last + 1 + i for i in range(n)]


class TestServingDriver:
    def test_acceptance_concurrent_requests(self):
        """The PR acceptance bar: >= 8 concurrent requests stream to
        completion while one injected timeout and one injected failure are
        isolated (KV blocks freed, others unaffected), then graceful drain
        completes the running set while rejecting new submits."""
        eng = FakeEngine(step_delay=0.002)
        driver = ServingDriver(eng, max_queue=64)
        driver.start()

        streamed = {}
        threads = []

        def consume(req):
            streamed[req.uid] = list(req.stream)

        prompts = [np.arange(1 + 100 * i, 6 + 100 * i, dtype=np.int32) for i in range(8)]
        reqs = []
        for p in prompts:
            r = driver.submit(p, params=SamplingParams(max_new_tokens=12, ignore_eos=True))
            t = threading.Thread(target=consume, args=(r,))
            t.start()
            reqs.append(r)
            threads.append(t)

        # injected timeout: a generation far too long for its deadline
        r_timeout = driver.submit(
            np.asarray([7, 8, 9], np.int32),
            params=SamplingParams(max_new_tokens=10000, ignore_eos=True),
            timeout_s=0.15,
        )
        # injected failure: stop_fn raises after 3 tokens
        def boom(req, tok):
            if len(req.generated) >= 3:
                raise RuntimeError("boom")
            return False

        r_fail = driver.submit(
            np.asarray([50, 51], np.int32),
            params=SamplingParams(max_new_tokens=10000, ignore_eos=True),
            stop_fn=boom,
        )

        for r in reqs:
            assert r.wait(30), f"request {r.uid} did not finish"
        assert r_timeout.wait(30) and r_fail.wait(30)
        for t in threads:
            t.join(10)

        for r, p in zip(reqs, prompts):
            assert r.state == RequestState.FINISHED
            assert r.finish_reason == "max_tokens"
            assert r.generated == _expected_tokens(p, 12)
            assert streamed[r.uid] == r.generated  # stream == record
            assert r.ttft_s is not None and r.e2e_s is not None

        assert r_timeout.state == RequestState.TIMED_OUT
        assert r_timeout.stream.finish_reason == "timeout"
        assert r_fail.state == RequestState.FAILED
        assert "boom" in r_fail.error
        assert len(r_fail.generated) == 3  # failed AFTER its third token

        # graceful drain: running set completes, new submits rejected
        d1 = driver.submit(np.asarray([500], np.int32),
                           params=SamplingParams(max_new_tokens=40, ignore_eos=True))
        d2 = driver.submit(np.asarray([600], np.int32),
                           params=SamplingParams(max_new_tokens=40, ignore_eos=True))
        drained = threading.Event()
        threading.Thread(target=lambda: (driver.drain(30), drained.set())).start()
        deadline = time.monotonic() + 5
        while driver.health()["status"] != "draining":
            assert time.monotonic() < deadline
            time.sleep(0.001)
        with pytest.raises(RequestRejected) as ei:
            driver.submit(np.asarray([1], np.int32))
        assert ei.value.reason == "draining"
        assert drained.wait(30)
        assert d1.state == RequestState.FINISHED and len(d1.generated) == 40
        assert d2.state == RequestState.FINISHED and len(d2.generated) == 40

        driver.shutdown()
        # every terminal path released its KV blocks
        assert eng.state_manager.free_blocks == eng.config.kv_cache.num_blocks
        assert not eng.scheduler.has_work()
        snap = driver.metrics.snapshot()
        assert snap["requests_finished_total"] == 10
        assert snap["requests_timed_out_total"] == 1
        assert snap["requests_failed_total"] == 1
        assert snap["requests_rejected_total"] == 1

    def test_admission_waits_without_busy_loop(self):
        """free_blocks exhausted: the queued request WAITS (no engine spin)
        and admits once the blocker's blocks come back."""
        eng = FakeEngine(block_size=4, num_blocks=8, max_blocks_per_seq=8,
                         max_context=64, step_delay=0.005)
        driver = ServingDriver(eng, poll_interval_s=0.02)
        driver.start()

        time.sleep(0.25)
        assert eng.steps == 0  # idle driver makes no engine calls

        # A reserves the whole pool: (8 prompt + 24 new) / 4 = 8 blocks
        a = driver.submit(np.arange(1, 9, dtype=np.int32),
                          params=SamplingParams(max_new_tokens=24, ignore_eos=True))
        deadline = time.monotonic() + 5
        while driver.num_active == 0:  # wait for A's admission
            assert time.monotonic() < deadline
            time.sleep(0.001)
        b = driver.submit(np.arange(1, 9, dtype=np.int32),
                          params=SamplingParams(max_new_tokens=24, ignore_eos=True))
        assert b.state == RequestState.QUEUED  # pool can't fit 8 more blocks

        assert a.wait(30) and b.wait(30)
        assert a.state == RequestState.FINISHED and len(a.generated) == 24
        assert b.state == RequestState.FINISHED and len(b.generated) == 24
        driver.shutdown()

        # bounded work: ~1 step per generated token + prompt chunks + slack;
        # a busy loop would be thousands of steps over these ~0.5 s
        assert eng.steps < 120
        assert driver.metrics.snapshot()["admission_blocked_total"] >= 1
        assert eng.state_manager.free_blocks == 8

    def test_length_cap_reports_length_cap_finish(self):
        """A request hitting max_blocks_per_seq finishes as length_cap (the
        scheduler's capped set reaped by the driver), blocks freed."""
        eng = FakeEngine(block_size=4, num_blocks=64, max_blocks_per_seq=2,
                         max_context=256)
        with ServingDriver(eng) as driver:
            r = driver.submit(np.arange(1, 5, dtype=np.int32),
                              params=SamplingParams(max_new_tokens=50, ignore_eos=True))
            assert r.wait(30)
        assert r.state == RequestState.FINISHED
        assert r.finish_reason == "length_cap"
        # 2 blocks * 4 tokens = 8 positions; 4 prompt + first token leaves
        # room to *decode* positions 4..7, then the cap trips
        assert 0 < len(r.generated) <= 5
        assert eng.state_manager.free_blocks == 64

    def test_cancel_active_frees_blocks(self):
        eng = FakeEngine(step_delay=0.005)
        with ServingDriver(eng) as driver:
            r = driver.submit(np.asarray([1, 2, 3], np.int32),
                              params=SamplingParams(max_new_tokens=10000, ignore_eos=True))
            first = r.stream.get(timeout=10)  # wait until it's decoding
            assert first == 4
            assert driver.cancel(r.uid)
            assert r.wait(10)
            assert r.state == RequestState.CANCELLED
            assert not driver.cancel(12345)  # unknown uid
        assert eng.state_manager.free_blocks == eng.config.kv_cache.num_blocks

    def test_engine_error_isolated_loop_survives(self):
        """An engine-level step failure fails the in-flight set but the
        driver keeps serving subsequent requests."""
        eng = FakeEngine()
        with ServingDriver(eng) as driver:
            eng.fail_next = 1
            r1 = driver.submit(np.asarray([1, 2], np.int32),
                               params=SamplingParams(max_new_tokens=4, ignore_eos=True))
            assert r1.wait(30)
            assert r1.state == RequestState.FAILED
            assert "injected engine failure" in r1.error
            assert eng.state_manager.free_blocks == eng.config.kv_cache.num_blocks

            r2 = driver.submit(np.asarray([1, 2], np.int32),
                               params=SamplingParams(max_new_tokens=4, ignore_eos=True))
            assert r2.wait(30)
            assert r2.state == RequestState.FINISHED
            assert r2.generated == [3, 4, 5, 6]

    def test_submit_rejections(self):
        eng = FakeEngine(block_size=4, num_blocks=8, max_blocks_per_seq=4,
                         max_context=16)
        driver = ServingDriver(eng, max_queue=1)
        # no need to start the loop: rejection happens at submit
        with pytest.raises(RequestRejected) as ei:
            driver.submit(np.asarray([], np.int32))
        assert ei.value.reason == "empty_prompt"
        with pytest.raises(RequestRejected) as ei:
            driver.submit(np.arange(20, dtype=np.int32))  # >= max_context
        assert ei.value.reason == "max_context"
        driver.submit(np.asarray([1], np.int32))  # fills the queue
        with pytest.raises(RequestRejected) as ei:
            driver.submit(np.asarray([1], np.int32))
        assert ei.value.reason == "queue_full"
        with pytest.raises(ValueError):
            SamplingParams(max_new_tokens=0)

    def test_eos_and_stop_tokens(self):
        eng = FakeEngine()
        with ServingDriver(eng, eos_token_id=13) as driver:
            # generation 11,12,13 -> stops ON the default eos
            r = driver.submit(np.asarray([10], np.int32),
                              params=SamplingParams(max_new_tokens=50))
            assert r.wait(30)
            assert r.finish_reason == "eos" and r.generated == [11, 12, 13]
            # per-request stop id overrides run past the driver default
            r2 = driver.submit(
                np.asarray([10], np.int32),
                params=SamplingParams(max_new_tokens=50, ignore_eos=True,
                                      stop_token_ids=(15,)),
            )
            assert r2.wait(30)
            assert r2.finish_reason == "stop_token" and r2.generated == [11, 12, 13, 14, 15]


class TestStreaming:
    def test_token_stream_iterate_and_close(self):
        s = TokenStream(uid=1)
        s.put(1), s.put(2)
        s.close("done")
        s.put(99)  # post-close tokens dropped
        assert list(s) == [1, 2]
        assert s.finish_reason == "done"
        with pytest.raises(StreamClosed):
            s.get()

    def test_token_stream_get_timeout(self):
        s = TokenStream(uid=1)
        with pytest.raises(TimeoutError):
            s.get(timeout=0.01)

    def test_token_stream_concurrent_producer(self):
        s = TokenStream(uid=1)

        def produce():
            for i in range(100):
                s.put(i)
            s.close("max_tokens")

        t = threading.Thread(target=produce)
        t.start()
        assert list(s) == list(range(100))
        t.join()

    def test_incremental_detok_holds_partial_utf8(self):
        class ByteTok:  # token id == one utf-8 byte
            def decode(self, ids):
                return bytes(ids).decode("utf-8", errors="replace")

        d = IncrementalDetokenizer(ByteTok())
        assert d.push(ord("a")) == "a"
        assert d.push(0xC3) == ""  # first byte of é: held back
        assert d.push(0xA9) == "é"  # completed codepoint emitted once
        assert d.push(ord("b")) == "b"
        assert d.flush() == ""

    def test_incremental_detok_flush_emits_trailing_replacement(self):
        class ByteTok:
            def decode(self, ids):
                return bytes(ids).decode("utf-8", errors="replace")

        d = IncrementalDetokenizer(ByteTok())
        assert d.push(0xC3) == ""
        assert d.flush() == "�"  # stream ended mid-codepoint: it's real now


class TestServingMetrics:
    def test_histogram_counts_and_quantile(self):
        h = Histogram(buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0):
            h.observe(v)
        assert h.count == 4 and h.mean == pytest.approx(1.5125)
        assert h.quantile(0.5) == 1.0
        assert h.quantile(1.0) == 10.0
        samples = h.prom_samples("x")
        by_le = {s[1]["le"]: s[2] for s in samples if s[0] == "x_bucket"}
        assert by_le["0.1"] == 1 and by_le["1.0"] == 3  # cumulative
        assert by_le["+Inf"] == 4

    def test_prometheus_text_exposition(self):
        m = ServingMetrics()
        m.inc("requests_submitted_total", 3)
        m.update_kv(free_blocks=96, total_blocks=128)
        req = Request(uid=0, prompt_tokens=np.asarray([1], np.int32))
        req.t_first_token = req.t_submit + 0.02
        req.t_finish = req.t_submit + 0.1
        req.generated = [1, 2, 3]
        m.observe_request(req)
        text = m.prometheus_text()
        assert "# TYPE dstpu_serving_requests_submitted_total counter" in text
        assert "dstpu_serving_requests_submitted_total 3" in text
        assert "# TYPE dstpu_serving_kv_occupancy gauge" in text
        assert "dstpu_serving_kv_occupancy 0.25" in text
        assert "# TYPE dstpu_serving_ttft_seconds histogram" in text
        assert 'dstpu_serving_ttft_seconds_bucket{le="+Inf"} 1' in text
        assert "dstpu_serving_ttft_seconds_count 1" in text

    def test_to_events_bridges_to_monitor(self):
        m = ServingMetrics()
        m.inc("requests_finished_total", 2)
        events = dict((n, v) for n, v, _ in m.to_events())
        assert events["Serving/requests_finished_total"] == 2
        steps = {s for _, _, s in m.to_events()}
        assert steps == {2}  # finished count is the default step clock


class TestPrometheusMonitor:
    def test_expose_and_textfile(self, tmp_path):
        from deepspeed_tpu.monitor.monitor import PrometheusMonitor

        cfg = SimpleNamespace(enabled=True, output_path=str(tmp_path),
                              job_name="unittest")
        mon = PrometheusMonitor(cfg)
        mon.write_events([("Train/Samples/loss", 2.5, 10), ("bad name!", 1.0, 1)])
        text = mon.expose()
        assert "Train_Samples_loss 2.5" in text
        assert "Train_Samples_loss_last_step 10" in text
        assert "bad_name_ 1.0" in text  # sanitized, not dropped
        assert (tmp_path / "unittest.prom").read_text() == text

    def test_monitor_master_wiring(self, tmp_path):
        from deepspeed_tpu.monitor.monitor import MonitorMaster
        from deepspeed_tpu.runtime.config import DeepSpeedConfig

        ds = DeepSpeedConfig.from_dict({
            "train_batch_size": 8,
            "prometheus": {"enabled": True, "output_path": str(tmp_path),
                           "job_name": "wired"},
        })
        master = MonitorMaster(ds)
        assert master.enabled and master.prometheus_monitor.enabled
        master.write_events([("Serving/queue_depth", 4, 7)])
        assert "Serving_queue_depth 4" in master.prometheus_monitor.expose()
        assert (tmp_path / "wired.prom").exists()


@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from deepspeed_tpu.models import get_config, init_params

    cfg = get_config("tiny", n_layers=2, dtype="float32", max_seq_len=512)
    return cfg, init_params(cfg, jax.random.key(0))


class TestServingRealEngine:
    def test_driver_over_inference_engine_v2(self, tiny_model):
        """End-to-end over the real v2 engine (CPU): concurrent requests
        admitted, decoded via continuous batching, streamed to completion."""
        from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
        from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

        cfg, params = tiny_model
        rc = RaggedInferenceEngineConfig.from_dict({
            "dtype": "float32",
            "kv_cache": {"block_size": 16, "num_blocks": 64, "max_blocks_per_seq": 8},
            "state_manager": {"max_tracked_sequences": 8,
                              "max_ragged_batch_size": 128,
                              "max_ragged_sequence_count": 4,
                              "max_context": 256},
        })
        engine = InferenceEngineV2(cfg, params, rc)
        with ServingDriver(engine) as driver:
            reqs = [
                driver.submit(np.arange(1 + i, 9 + i, dtype=np.int32),
                              params=SamplingParams(max_new_tokens=6, ignore_eos=True))
                for i in range(3)
            ]
            for r in reqs:
                assert r.wait(300), "real-engine request did not finish"
        for r in reqs:
            assert r.state == RequestState.FINISHED
            assert len(r.generated) == 6
            assert all(0 <= t < cfg.vocab_size for t in r.generated)
        assert engine.state_manager.free_blocks == 64

    @pytest.mark.parametrize("arrivals", ["alone", "overlapping", "together"])
    def test_serve_entry_point_builds_every_split_shape_first(self, arrivals):
        """The stack as ``dstpu serve`` builds it has every shape of the
        split step (decode-only; one chunk row in the 128 bucket and in
        ``prompt_chunk``; two chunk rows, which share the one bucket) before
        the first request is admitted: prompts whose chunks and tails land in
        every bucket, one at a time, all at once, or two SUBMITTED TOGETHER
        before the loop's first step (so that one step carries two chunk rows,
        short ones and long ones), trace nothing."""
        import jax

        from deepspeed_tpu.inference.cli import build_serving_stack, serve_parse_args
        from deepspeed_tpu.models import get_config, init_params
        from deepspeed_tpu.serving.elastic import assert_no_new_traces

        cfg = get_config("tiny", n_layers=2, dtype="float32", max_seq_len=2048)
        args = serve_parse_args([
            "--model", "", "--port", "0", "--dtype", "float32", "--block-size", "16",
            "--num-blocks", "256", "--max-blocks-per-seq", "96", "--max-context", "1536",
            "--max-concurrent", "4",
        ])
        driver, _ = build_serving_stack(args, cfg=cfg, params=init_params(cfg, jax.random.key(0)))
        engine = driver.engine
        pc = engine.scheduler.prompt_chunk
        baseline = engine.trace_signature()
        shapes = [(0, 0), (1, 128), (1, pc), (2, pc)]
        assert sorted(baseline) == sorted(f"split[{shape}]" for shape in shapes) and pc > 128
        assert all(n == 1 for n in baseline.values())
        rng = np.random.default_rng(0)
        lengths = [1, 128, 129, pc, pc + 100, 2 * pc + 300]
        launched, launch = set(), engine._launch
        engine._launch = lambda key, inputs: (launched.add(key), launch(key, inputs))[1]
        reqs = []
        if arrivals == "together":
            # the oldest and the shortest share the first step, the two
            # that are left the second
            lengths = [10, 20, pc, 200]
            reqs = [driver.submit(
                rng.integers(1, cfg.vocab_size, size=n, dtype=np.int32),
                params=SamplingParams(max_new_tokens=3, ignore_eos=True)) for n in lengths]
        with driver:
            for n in lengths[len(reqs):]:
                reqs.append(driver.submit(
                    rng.integers(1, cfg.vocab_size, size=n, dtype=np.int32),
                    params=SamplingParams(max_new_tokens=3, ignore_eos=True)))
                if arrivals == "alone":
                    assert reqs[-1].wait(300), f"a prompt of {n} tokens did not finish"
            for n, r in zip(lengths, reqs):
                assert r.wait(300), f"a prompt of {n} tokens did not finish"
                assert r.state == RequestState.FINISHED and len(r.generated) == 3
        assert_no_new_traces(engine, baseline, label=f"served {arrivals}")
        assert launched <= {("split", shape) for shape in shapes}
        if arrivals == "together":
            assert ("split", (2, pc)) in launched

    def test_a_step_that_lands_no_token_is_waited_for_and_is_progress(self, tiny_model,
                                                                      monkeypatch):
        """A step of prompt chunks with more to come completes no row. It is
        waited for all the same, on its OWN outputs (never the engine's
        pools, which by then are the next step's), and the core reports it
        as progress: at most one step is in flight beyond the one waited
        for, so a long prompt's chunk steps cannot queue on the device, and
        the loop does not take such a step for a stall on KV blocks and poll
        between two chunks of one prompt. A pass that schedules nothing is
        still none."""
        from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
        from deepspeed_tpu.inference.v2 import engine_v2

        cfg, params = tiny_model
        rc = RaggedInferenceEngineConfig.from_dict({
            "dtype": "float32", "prompt_chunk": 32,
            "kv_cache": {"block_size": 16, "num_blocks": 64, "max_blocks_per_seq": 8},
            "state_manager": {"max_tracked_sequences": 8, "max_ragged_batch_size": 64,
                              "max_ragged_sequence_count": 4, "max_context": 128},
        })
        engine = engine_v2.InferenceEngineV2(cfg, params, rc)
        waits = []
        real_wait = engine_v2.device_synchronize
        monkeypatch.setattr(engine_v2, "device_synchronize",
                            lambda tree=None: (waits.append(list(tree)), real_wait(tree))[1])
        driver = ServingDriver(engine)  # never started: the test is its loop
        req = driver.submit(np.arange(1, 81, dtype=np.int32),
                            params=SamplingParams(max_new_tokens=2, ignore_eos=True))
        with driver._cond:
            driver._admit_locked()
        flights = []
        for chunk in range(3):  # 32 + 32 + 16 of 80 tokens
            assert driver._step_once(), f"chunk {chunk} of three is progress"
            assert req.generated == [] and driver.core.has_work()
            flights.append(driver.core._flight[0])
            # launched, not waited for; the step before it was, on its own outputs
            assert len(waits) == chunk and flights[-1].stats.prefill_tokens == (32, 32, 16)[chunk]
            assert bool(flights[-1].rows) == (chunk == 2) and flights[-1].stats.ahead == (chunk > 0)
            if chunk:
                assert waits[-1] == flights[-2].waited and len(waits[-1]) == 1
                assert waits[-1][0] is not engine._k_cache
                assert waits[-1][0] is not engine._last_tokens  # that is THIS step's
        # the tail's token is taken on the device by the decode step launched
        # before the tail is collected; the request's last token launches nothing
        assert driver._step_once() and len(req.generated) == 1 and len(waits) == 3
        assert driver._step_once() and req.state == RequestState.FINISHED
        assert len(waits) == 4 and not driver.core.has_work()
        assert not driver._step_once(), "a pass that scheduled nothing is no progress"
        assert engine.last_step.scheduled_tokens == 1  # the last step collected: nothing ran since
        counters = driver.metrics.counters
        assert counters["engine_steps_total"] == 4 and counters["steps_ahead_total"] == 3
        assert counters["ahead_rows_dropped_total"] == 0 and counters["decode_tokens_total"] == 2
