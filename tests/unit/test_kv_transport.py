"""KV handoff transport seam tests (serving/cluster/handoff.py).

Three payload representations behind one ``KVTransport`` protocol: the
portable ``host`` numpy wire, the single-gather ``in_process`` device
wire, and the pipelined chunked ``device`` wire. The acceptance bar is
the same as disagg serving's: a request prefilled on worker p0 and
decoded on a replica — including a tp=2 head-sharded replica — streams
BIT-IDENTICAL tokens to the single-engine driver, greedy and seeded,
bf16 and int8 KV, over every transport. The device wire must do it
without ever materializing a host copy (no ``np.ndarray`` payload), with
the export windows dispatched ahead of the import, and without tracing
any step program after a warm-spare ``warm_trace``.
"""

import numpy as np
import pytest

from deepspeed_tpu.serving import Router, SamplingParams, ServingDriver
from deepspeed_tpu.serving.cluster.handoff import (
    KV_TRANSPORTS,
    HandoffError,
    export_sequence,
    get_transport,
    import_sequence,
)
from tests.unit.test_disagg import _run_all
from tests.unit.test_serving import FakeEngine


@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from deepspeed_tpu.models import get_config, init_params

    cfg = get_config("tiny", n_layers=2, dtype="float32", max_seq_len=512)
    return cfg, init_params(cfg, jax.random.key(0))


def _real_engine(tiny_model, kv_dtype, tp=1, chunk_blocks=1):
    """chunk_blocks=1 makes every multi-block handoff genuinely
    multi-window on the device wire (2 blocks -> 2 in-flight windows)."""
    from deepspeed_tpu.inference.config import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    cfg, params = tiny_model
    rc = RaggedInferenceEngineConfig.from_dict({
        "dtype": "float32",
        "seed": 7,
        "tp_size": tp,
        "kv_cache": {"block_size": 16, "num_blocks": 64,
                     "max_blocks_per_seq": 8, "kv_cache_dtype": kv_dtype,
                     "host_tier_chunk_blocks": chunk_blocks},
        "state_manager": {"max_tracked_sequences": 8,
                          "max_ragged_batch_size": 128,
                          "max_ragged_sequence_count": 4,
                          "max_context": 256},
    })
    return InferenceEngineV2(cfg, params, rc)


def _tp2_engine(tiny_model, kv_dtype, devices):
    """A tp=2 decode replica (head-sharded KV pools on a 4x2 mesh).
    Topology is reset right after construction — the engine owns its mesh
    through its NamedShardings, so later tp=1 engines build unsharded."""
    from deepspeed_tpu.parallel.topology import (
        Topology,
        reset_topology,
        set_topology,
    )

    set_topology(Topology(data=4, model=2, devices=devices[:8]))
    try:
        return _real_engine(tiny_model, kv_dtype, tp=2)
    finally:
        reset_topology()


def _prefill_one(engine, uid, prompt):
    """Drive one prompt to its first token on ``engine`` (single-chunk
    prefill at these sizes); returns the pending first token."""
    engine.scheduler.submit(uid, prompt)
    for _ in range(8):
        out = engine.step_tokens()
        if uid in out:
            return int(out[uid])
    raise AssertionError("prefill produced no token")


# ---------------------------------------------------------------------------
# transport seam: registry + config errors
# ---------------------------------------------------------------------------
class TestTransportSeam:
    def test_registry(self):
        assert KV_TRANSPORTS == ("device", "host", "in_process", "remote")
        for name in KV_TRANSPORTS:
            tr = get_transport(name)
            assert tr.name == name
            assert get_transport(tr) is tr  # instances pass through

    def test_unknown_transport_rejected(self):
        with pytest.raises(ValueError, match="kv_transport='warp'"):
            get_transport("warp")
        with pytest.raises(ValueError, match="kv_transport"):
            Router(engines=[FakeEngine()], kv_transport="warp")

    def test_fake_engines_ride_every_transport(self):
        """Engines without device pools hand off with payload=None over
        any transport — the table/history bookkeeping is identical."""
        for name in KV_TRANSPORTS:
            src, tgt = FakeEngine(), FakeEngine()
            src.scheduler.submit(3, np.arange(1, 9, dtype=np.int32))
            tok = src.step_tokens()[3]
            ho = export_sequence(src, 3, int(tok), transport=name)
            src.scheduler.finish(3)
            assert ho.transport == name and ho.payload is None
            assert ho.nbytes == 0
            assert import_sequence(tgt, ho) >= 0
            assert tgt.scheduler.peek_next_token(3) == ho.pending_token
            tgt.scheduler.finish(3)


# ---------------------------------------------------------------------------
# device wire: zero host copy, pipelined windows, warm-trace contract
# ---------------------------------------------------------------------------
class TestDeviceWire:
    def test_export_never_touches_host(self, tiny_model):
        """The headline property: a device-transport handoff carries NO
        ``np.ndarray`` — every window plane is a jax device array, and the
        byte counter is computed from shapes (no sync)."""
        import jax

        src = _real_engine(tiny_model, "bf16")
        tgt = _real_engine(tiny_model, "bf16")
        tok = _prefill_one(src, 11, np.arange(1, 25, dtype=np.int32))
        ho = export_sequence(src, 11, tok, transport="device")
        src.scheduler.finish(11)

        assert ho.transport == "device"
        assert ho.payload is None, "device transport must not fill .payload"
        assert ho.windows and ho.chunk_blocks == 1
        assert ho.inflight_windows == len(ho.windows) == 2  # 2 blocks @ chunk 1
        expect_bytes = 0
        for win in ho.windows:
            for plane in win.values():
                assert isinstance(plane, jax.Array)
                assert not isinstance(plane, np.ndarray)
                expect_bytes += (int(np.prod(plane.shape))
                                 * np.dtype(plane.dtype).itemsize)
        assert ho.nbytes == expect_bytes > 0

        copied = import_sequence(tgt, ho)
        assert copied == 2
        assert tgt.scheduler.peek_next_token(11) == ho.pending_token
        tgt.scheduler.finish(11)
        assert tgt.state_manager.free_blocks == 64

    def test_int8_scale_planes_ride_along(self, tiny_model):
        src = _real_engine(tiny_model, "int8")
        tok = _prefill_one(src, 12, np.arange(1, 25, dtype=np.int32))
        ho = export_sequence(src, 12, tok, transport="device")
        src.scheduler.finish(12)
        assert set(ho.windows[0]) == {"k", "v", "k_scale", "v_scale"}
        tgt = _real_engine(tiny_model, "int8")
        assert import_sequence(tgt, ho) == 2
        tgt.scheduler.finish(12)

    def test_warm_spare_zero_trace_over_device_wire(self, tiny_model):
        """warm_trace pre-traces the windowed export gather and the device
        import scatter, so a device-transport handoff onto a warm spare
        compiles NOTHING at admission time."""
        from deepspeed_tpu.serving.elastic import assert_no_new_traces

        src = _real_engine(tiny_model, "bf16")
        tgt = _real_engine(tiny_model, "bf16")
        base_src = src.warm_trace()
        base_tgt = tgt.warm_trace()
        tok = _prefill_one(src, 13, np.arange(1, 25, dtype=np.int32))
        ho = export_sequence(src, 13, tok, transport="device")
        src.scheduler.finish(13)
        import_sequence(tgt, ho)
        for _ in range(4):
            tgt.scheduler.feedback(13, tgt.step_tokens()[13])
        assert_no_new_traces(src, base_src, label="device-wire exporter")
        assert_no_new_traces(tgt, base_tgt, label="device-wire importer")
        tgt.scheduler.finish(13)

    def test_device_import_needs_engine_pool(self, tiny_model):
        """A device-windowed handoff aimed at an engine without the
        windowed import (a fake) fails loudly and unwinds — never a
        silent host fallback."""
        src = _real_engine(tiny_model, "bf16")
        tok = _prefill_one(src, 14, np.arange(1, 25, dtype=np.int32))
        ho = export_sequence(src, 14, tok, transport="device")
        src.scheduler.finish(14)
        tgt = FakeEngine()
        free = tgt.state_manager.free_blocks
        with pytest.raises(HandoffError):
            import_sequence(tgt, ho)
        assert tgt.state_manager.free_blocks == free
        assert tgt.state_manager.get_sequence(14) is None


# ---------------------------------------------------------------------------
# payload contract: negative tests per transport (shared check_kv_payload)
# ---------------------------------------------------------------------------
class TestPayloadContract:
    def _export(self, tiny_model, transport):
        src = _real_engine(tiny_model, "int8")  # int8: scale planes in play
        tok = _prefill_one(src, 21, np.arange(1, 25, dtype=np.int32))
        ho = export_sequence(src, 21, tok, transport=transport)
        src.scheduler.finish(21)
        return ho

    def _assert_rejected(self, tiny_model, ho, match):
        tgt = _real_engine(tiny_model, "int8")
        free = tgt.state_manager.free_blocks
        with pytest.raises(ValueError, match=match):
            import_sequence(tgt, ho)
        # the failed import unwound every seeded/allocated block
        assert tgt.state_manager.free_blocks == free
        assert tgt.state_manager.get_sequence(ho.uid) is None

    def test_host_missing_plane(self, tiny_model):
        ho = self._export(tiny_model, "host")
        del ho.payload["k_scale"]
        self._assert_rejected(tiny_model, ho, "missing")

    def test_host_wrong_dtype(self, tiny_model):
        ho = self._export(tiny_model, "host")
        ho.payload["k"] = ho.payload["k"].astype(np.float32)
        self._assert_rejected(tiny_model, ho, "dtype")

    def test_in_process_stray_plane(self, tiny_model):
        ho = self._export(tiny_model, "in_process")
        ho.payload["junk"] = ho.payload["k"]
        self._assert_rejected(tiny_model, ho, "unexpected")

    def test_device_tampered_window(self, tiny_model):
        ho = self._export(tiny_model, "device")
        ho.windows[0] = {k: v for k, v in ho.windows[0].items()
                         if k != "v_scale"}
        self._assert_rejected(tiny_model, ho, "missing")

    def test_device_window_count_mismatch(self, tiny_model):
        ho = self._export(tiny_model, "device")
        ho.windows = ho.windows[:1]
        self._assert_rejected(tiny_model, ho, "window")


# ---------------------------------------------------------------------------
# the acceptance bar: router-level stream parity vs the single engine
# ---------------------------------------------------------------------------
_PARITY_PROMPTS = [np.arange(1 + 3 * i, 25 + 3 * i, dtype=np.int32)
                   for i in range(3)]
_PARITY_WANT = {}  # (kv_dtype, greedy) -> single-engine reference streams


def _reference_streams(tiny_model, kv_dtype, sampling):
    """Single-engine oracle streams, computed once per (dtype, mode):
    every parity test compares against the same reference, so rebuilding
    the single engine per test only re-proved engine determinism."""
    key = (kv_dtype, sampling.get("greedy", True))
    if key not in _PARITY_WANT:
        single = _real_engine(tiny_model, kv_dtype)
        single.set_sampling(**sampling)
        drv = ServingDriver(single).start()
        _PARITY_WANT[key] = [
            list(r.generated)
            for r in _run_all(drv, _PARITY_PROMPTS, 6, timeout=300)]
        drv.shutdown()
        if single.state_manager.free_blocks != 64:
            raise RuntimeError("reference engine leaked KV blocks")
    return _PARITY_WANT[key]


def _transport_parity(tiny_model, kv_dtype, transport, decode_tp=1,
                      devices=None):
    """1 prefill worker + decode replica(s) behind the Router stream
    bit-identically to the single-engine driver over ``transport`` —
    greedy, then seeded sampling, on the SAME engines. With
    ``decode_tp=2`` the lone decode replica holds head-sharded KV and
    imports per-shard through the replica's mesh."""
    prompts = _PARITY_PROMPTS
    workers = [_real_engine(tiny_model, kv_dtype)]
    if decode_tp > 1:
        decodes = [_tp2_engine(tiny_model, kv_dtype, devices)]
    else:
        decodes = [_real_engine(tiny_model, kv_dtype) for _ in range(2)]
    cluster = workers + decodes

    for sampling in ({"greedy": True},
                     {"greedy": False, "temperature": 0.8, "seed": 123}):
        want = _reference_streams(tiny_model, kv_dtype, sampling)
        for e in cluster:
            e.set_sampling(**sampling)

        router = Router(engines=cluster, num_prefill_workers=1,
                        kv_transport=transport).start()
        try:
            got = [list(r.generated)
                   for r in _run_all(router, prompts, 6, timeout=300)]
            health = router.health()
            text = router.metrics.prometheus_text()
        finally:
            router.shutdown()
        assert got == want, (
            f"streams diverged ({kv_dtype}, {transport}, tp{decode_tp}, "
            f"{sampling})")

        # transport observability landed with the handoffs
        kt = health["kv_transport"]
        assert kt["transport"] == transport
        per = kt["per_transport"]
        assert per[transport]["handoffs"] == len(prompts)
        assert per[transport]["bytes"] > 0  # real pools: bytes counted
        if transport == "device":
            # chunk_blocks=1, 2-block prompts: pipelined multi-window
            # export (the decode replica seeds/steps behind the tail)
            assert per[transport]["chunks"] >= 2 * len(prompts)
        assert kt["latency_mean_s"] >= 0.0
        assert f'transport="{transport}"' in text
        assert "dstpu_serving_kv_handoff_bytes" in text
        assert "dstpu_serving_kv_handoff_seconds_bucket" in text
    for e in cluster:
        assert e.state_manager.free_blocks == 64


class TestStreamParity:
    # tier-1 keeps the device wire (the new representation); in_process is
    # slow-marked — run_smoke.sh runs this file unfiltered, so every commit
    # still proves all three transports
    @pytest.mark.parametrize("transport", [
        pytest.param("in_process", marks=pytest.mark.slow), "device"])
    def test_parity_bf16(self, tiny_model, transport):
        _transport_parity(tiny_model, "bf16", transport)

    @pytest.mark.slow
    @pytest.mark.parametrize("transport", ["in_process", "device"])
    def test_parity_int8(self, tiny_model, transport):
        """Quantized codes + fp32 scale planes cross the device wires
        bit-exactly (no requantization)."""
        _transport_parity(tiny_model, "int8", transport)


class TestTP2Decode:
    # tier-1 runs the device wire at tp2; the host-wire tp2 leg rides the
    # unfiltered run_smoke.sh gate
    @pytest.mark.parametrize("transport", [
        pytest.param("host", marks=pytest.mark.slow), "device"])
    def test_parity_tp2_bf16(self, tiny_model, devices8, transport):
        """1-prefill(tp1) -> tp2-decode streams match the single engine:
        sharding-invariant sampling + per-shard block import under the
        replica's mesh."""
        _transport_parity(tiny_model, "bf16", transport, decode_tp=2,
                          devices=devices8)

    @pytest.mark.slow
    def test_parity_tp2_int8(self, tiny_model, devices8):
        _transport_parity(tiny_model, "int8", "device", decode_tp=2,
                          devices=devices8)

    def test_tp2_replica_stats_and_placement(self, tiny_model, devices8):
        """The tp width surfaces in replica stats, and SLO placement
        discounts a tp=2 replica's load by its shard count."""
        from deepspeed_tpu.serving.cluster.core import EngineCore
        from deepspeed_tpu.serving.cluster.placement import SLOPlacement

        eng = _tp2_engine(tiny_model, "bf16", devices8)
        core = EngineCore(eng, name="d0", role="decode")
        assert core.tp_shards() == 2
        assert core.replica_stats()["tp_shards"] == 2
        assert SLOPlacement.name == "slo"  # tp-aware scoring lives there


# ---------------------------------------------------------------------------
# trace spans + CLI flag
# ---------------------------------------------------------------------------
class TestTransportObservability:
    def test_handoff_spans_carry_transport(self):
        from deepspeed_tpu.observability.tracing import (
            NULL_TRACER,
            SpanTracer,
            set_tracer,
        )

        tracer = set_tracer(SpanTracer())
        try:
            engines = [FakeEngine(step_delay=0.001) for _ in range(2)]
            router = Router(engines=engines, num_prefill_workers=1,
                            kv_transport="device").start()
            try:
                req = router.submit(
                    np.arange(1, 7, dtype=np.int32),
                    params=SamplingParams(max_new_tokens=4, ignore_eos=True))
                assert req.wait(30)
            finally:
                router.shutdown(drain=False)
            rec = tracer.trace(req.uid)
            spans = {sp.name: sp for sp in rec["spans"]}
            for name in ("handoff.export", "handoff.import"):
                assert spans[name].args["transport"] == "device"
                assert "chunks" in spans[name].args
        finally:
            set_tracer(NULL_TRACER)

    def test_inflight_window_gauge(self, tiny_model):
        from deepspeed_tpu.serving.metrics import ServingMetrics

        m = ServingMetrics()
        m.observe_handoff("device", nbytes=1024, seconds=0.01,
                          inflight_windows=3)
        snap = m.snapshot()
        assert snap["kv_handoff_inflight_windows"] == 3
        assert snap["kv_handoff_device_bytes"] == 1024
        assert snap["kv_handoff_device_handoffs"] == 1
        text = m.prometheus_text()
        assert 'dstpu_serving_kv_handoff_bytes{transport="device"} 1024' in text
        assert "dstpu_serving_kv_handoff_inflight_windows 3" in text


class TestServeCLI:
    def test_kv_transport_flag(self, tiny_model):
        from types import SimpleNamespace

        from deepspeed_tpu.inference.cli import (
            build_serving_stack,
            serve_parse_args,
        )

        cfg, params = tiny_model
        tok = SimpleNamespace(eos_token_id=None)
        flags = ["--model", "unused", "--dtype", "float32",
                 "--block-size", "16", "--num-blocks", "64",
                 "--max-blocks-per-seq", "8", "--max-context", "256",
                 "--max-concurrent", "8",
                 "--num-prefill-workers", "1", "--num-decode-replicas", "1"]
        front, _ = build_serving_stack(
            serve_parse_args(flags + ["--kv-transport", "device"]),
            cfg=cfg, params=params, tok=tok)
        assert isinstance(front, Router)
        assert front._kv_transport.name == "device"
        assert front.health()["kv_transport"]["transport"] == "device"

        front, _ = build_serving_stack(serve_parse_args(flags),
                                       cfg=cfg, params=params, tok=tok)
        assert front._kv_transport.name == "host"  # default: portable wire

        with pytest.raises(SystemExit):
            serve_parse_args(flags + ["--kv-transport", "warp"])
