"""Remote KV transport tests (serving/net/): wire, flow, endpoint, seam.

Layered like the subsystem: strict frame encode/decode negatives
(truncation, checksum, version skew, foreign magic), the credit window's
accounting and leak audit, the loopback endpoint (roundtrip parity,
unknown transfer ids, exporter crash mid-window with stage survival and
retry), the transport-seam contract (lazy registry, transport-mismatch
guard, fake engines), and finally the acceptance bar: Router streams over
``--kv-transport remote`` bit-identical to the single-engine reference,
greedy + seeded, with chaos kills at every ``net.*`` fault site losing no
request and leaking no pool block, window credit, or staged transfer.
The cross-PROCESS leg (two subprocess engines over loopback, bootstrapped
by a META frame) rides tools/run_smoke.sh.
"""

import time

import numpy as np
import pytest

from deepspeed_tpu.serving import Router, ServingDriver
from deepspeed_tpu.serving.cluster.handoff import (
    KV_TRANSPORTS,
    HandoffError,
    export_sequence,
    get_transport,
    import_sequence,
)
from deepspeed_tpu.serving.net import wire
from deepspeed_tpu.serving.net.endpoint import KVEndpoint, fetch_chunks
from deepspeed_tpu.serving.net.flow import CreditError, CreditWindow
from deepspeed_tpu.serving.resilience import (
    FaultSpec,
    InjectedFault,
    ResilienceConfig,
    inject,
)
from tests.unit.test_disagg import _run_all
from tests.unit.test_kv_transport import (
    _PARITY_PROMPTS,
    _prefill_one,
    _real_engine,
    _reference_streams,
    tiny_model,  # noqa: F401  (module-scoped fixture reused here)
)
from tests.unit.test_serving import FakeEngine


def _planes(n_blocks=10, dtype=np.float32, with_scales=False):
    """A payload-shaped plane dict ([n_layers, n_blocks, bs, heads])."""
    rng = np.random.RandomState(3)
    shape = (2, n_blocks, 4, 3)
    planes = {
        "k": rng.rand(*shape).astype(dtype),
        "v": rng.rand(*shape).astype(dtype),
    }
    if with_scales:
        planes["k_scale"] = rng.rand(2, n_blocks, 4).astype(np.float32)
        planes["v_scale"] = rng.rand(2, n_blocks, 4).astype(np.float32)
    return planes


def _fast_cfg(**kw):
    base = dict(hung_step_s=5.0, probe_backoff_s=0.05,
                retry_backoff_s=0.001)
    base.update(kw)
    base.setdefault("probe_backoff_max_s", max(30.0, base["probe_backoff_s"]))
    return ResilienceConfig(**base)


# ---------------------------------------------------------------------------
# wire.py: strict frames
# ---------------------------------------------------------------------------
class TestWireFormat:
    def test_chunk_roundtrip_preserves_every_byte(self):
        import ml_dtypes

        planes = _planes(with_scales=True)
        planes["k"] = planes["k"].astype(ml_dtypes.bfloat16)
        planes["v"] = (planes["v"] * 127).astype(np.int8)
        frame = wire.encode_chunk(2, 10, planes)
        ftype, payload, end = wire.decode_frame(frame)
        assert ftype == wire.F_CHUNK and end == len(frame)
        lo, hi, out = wire.decode_chunk(payload)
        assert (lo, hi) == (2, 10)
        assert set(out) == set(planes)
        for name, arr in planes.items():
            assert out[name].dtype == arr.dtype, name
            assert out[name].shape == arr.shape, name
            assert out[name].tobytes() == arr.tobytes(), name

    def test_truncated_frame_rejected(self):
        frame = wire.encode_chunk(0, 10, _planes())
        with pytest.raises(wire.WireError, match="truncated"):
            wire.decode_frame(frame[: wire.HEADER_BYTES - 1])
        with pytest.raises(wire.WireError, match="truncated"):
            wire.decode_frame(frame[:-1])

    def test_checksum_mismatch_rejected(self):
        frame = bytearray(wire.encode_chunk(0, 10, _planes()))
        frame[-1] ^= 0xFF  # flip one payload bit
        with pytest.raises(wire.WireError, match="checksum mismatch"):
            wire.decode_frame(bytes(frame))

    def test_version_skew_rejected(self):
        frame = bytearray(wire.encode_frame(wire.F_HELLO))
        frame[4] = wire.PROTOCOL_VERSION + 1  # version u16 lives at offset 4
        with pytest.raises(wire.WireError, match="version skew"):
            wire.decode_frame(bytes(frame))

    def test_foreign_magic_rejected(self):
        frame = b"HTTP" + wire.encode_frame(wire.F_HELLO)[4:]
        with pytest.raises(wire.WireError, match="foreign frame"):
            wire.decode_frame(frame)

    def test_unknown_frame_type_rejected(self):
        frame = bytearray(wire.encode_frame(wire.F_HELLO))
        frame[6] = 0x7F
        with pytest.raises(wire.WireError, match="unknown frame type"):
            wire.decode_frame(bytes(frame))

    def test_chunk_negatives(self):
        planes = {"k": _planes()["k"][:, 3:4]}  # one float32 block column
        payload = wire.decode_frame(wire.encode_chunk(3, 4, planes))[1]
        # empty range: hi := lo
        bad = bytearray(payload)
        bad[4:8] = bad[0:4]
        with pytest.raises(wire.WireError, match="empty or inverted"):
            wire.decode_chunk(bytes(bad))
        # inverted range: lo := 9 > hi = 4
        bad = bytearray(payload)
        bad[0:4] = (9).to_bytes(4, "little")
        with pytest.raises(wire.WireError, match="empty or inverted"):
            wire.decode_chunk(bytes(bad))
        # trailing garbage after the plane records
        with pytest.raises(wire.WireError, match="trailing bytes"):
            wire.decode_chunk(payload + b"xx")
        # short final plane record
        with pytest.raises(wire.WireError, match="truncated plane record"):
            wire.decode_chunk(payload[:-4])
        # declared shape inconsistent with the payload byte count: grow the
        # first dim of plane "k" (records start at offset 8: count u16,
        # name_len u16 + "k", dtype_len u16 + "float32", ndim u8, dims u32)
        bad = bytearray(payload)
        dim0_off = 8 + 2 + 2 + 1 + 2 + 7 + 1
        bad[dim0_off:dim0_off + 4] = (99).to_bytes(4, "little")
        with pytest.raises(wire.WireError, match="payload bytes"):
            wire.decode_chunk(bytes(bad))

    def test_handoff_meta_roundtrip(self):
        from deepspeed_tpu.serving.cluster.handoff import KVHandoff

        ho = KVHandoff(
            uid=41, tokens=list(range(1, 25)), seen_tokens=24,
            pending_token=9, n_blocks=2, payload=None, transport="remote",
            chunk_blocks=8, nbytes=4096,
            endpoint=("127.0.0.1", 45555), transfer_id="abc123",
        )
        back = wire.decode_handoff_meta(wire.encode_handoff_meta(ho))
        assert back.uid == ho.uid and back.tokens == ho.tokens
        assert back.seen_tokens == 24 and back.pending_token == 9
        assert back.n_blocks == 2 and back.transport == "remote"
        assert back.chunk_blocks == 8 and back.nbytes == 4096
        assert back.endpoint == ("127.0.0.1", 45555)
        assert back.transfer_id == "abc123"
        assert back.payload is None

    def test_handoff_meta_requires_remote_export(self):
        from deepspeed_tpu.serving.cluster.handoff import KVHandoff

        ho = KVHandoff(uid=1, tokens=[1, 2], seen_tokens=2, pending_token=3,
                       n_blocks=1, payload=None)  # host export: no endpoint
        with pytest.raises(wire.WireError, match="no endpoint"):
            wire.encode_handoff_meta(ho)


# ---------------------------------------------------------------------------
# flow.py: credit window
# ---------------------------------------------------------------------------
class TestCreditWindow:
    def test_grant_take_settle_accounting(self):
        w = CreditWindow(4)
        w.take(4)
        assert w.available == 0 and w.outstanding == 4
        assert not w.try_take(1)
        w.grant(2)
        assert w.try_take(2)
        w.settle(4)
        w.settle(2)
        assert w.outstanding == 0
        assert w.granted == 6
        assert w.reset() == 0  # clean transfer: no leaked credit

    def test_take_timeout_is_a_credit_stall(self):
        w = CreditWindow(1)
        with pytest.raises(CreditError, match="credit stall"):
            w.take(2, timeout=0.02)

    def test_fail_wakes_blocked_takers(self):
        import threading

        w = CreditWindow(0)
        errs = []

        def taker():
            try:
                w.take(1, timeout=5.0)
            except CreditError as e:
                errs.append(str(e))

        t = threading.Thread(target=taker)
        t.start()
        time.sleep(0.02)
        w.fail("peer died")
        t.join(timeout=2.0)
        assert errs and "peer died" in errs[0]

    def test_reset_reports_leaked_credit(self):
        """The unwind audit: an aborted transfer with taken-but-unsettled
        credit reports exactly how much was in flight."""
        w = CreditWindow(8)
        w.take(3)
        w.take(2)
        w.settle(3)
        assert w.reset() == 2
        assert w.outstanding == 0 and w.available == 0

    def test_over_settle_rejected(self):
        w = CreditWindow(4)
        w.take(2)
        with pytest.raises(CreditError, match="double settle"):
            w.settle(3)

    def test_inflight_window_peak_tracked(self):
        w = CreditWindow(10)
        w.take(2)
        w.take(2)
        w.take(2)  # 3 concurrently outstanding windows
        w.settle(2)
        w.take(2)
        assert w.max_inflight_windows == 3


# ---------------------------------------------------------------------------
# endpoint.py: loopback serving
# ---------------------------------------------------------------------------
class TestEndpoint:
    def _fetch_all(self, ep, tid, n_blocks, chunk, start=0):
        got = {}

        def on_chunk(lo, hi, planes):
            for name, arr in planes.items():
                got.setdefault(name, []).append((lo, np.array(arr)))

        stats = fetch_chunks(ep.address, tid, start_block=start,
                             n_blocks=n_blocks, chunk_blocks=chunk,
                             on_chunk=on_chunk)
        joined = {
            name: np.concatenate(
                [a for _, a in sorted(parts, key=lambda t: t[0])], axis=1)
            for name, parts in got.items()
        }
        return joined, stats

    def test_loopback_roundtrip_and_release(self):
        planes = _planes(n_blocks=10, with_scales=True)
        ep = KVEndpoint(name="p0").start()
        try:
            tid = ep.stage(7, planes, chunk_blocks=3)
            joined, stats = self._fetch_all(ep, tid, 10, 3, start=2)
            for name, arr in planes.items():
                assert joined[name].tobytes() == arr[:, 2:].tobytes(), name
            assert stats["windows"] == 3  # blocks 2..10 at width 3: 3,3,2
            assert stats["leaked_credits"] == 0
            assert stats["max_inflight_windows"] == 2  # double-buffered
            deadline = time.monotonic() + 5
            # DONE releases the stage asynchronously, and the sender counts
            # the transfer served only after its last chunk has left: the
            # importer can be through both before that thread runs again
            while ((ep.staged_count() or not ep.stats()["served"])
                   and time.monotonic() < deadline):
                time.sleep(0.005)
            assert ep.staged_count() == 0
            assert ep.stats()["served"] == 1
        finally:
            ep.close()

    def test_unknown_transfer_id_is_a_clear_error(self):
        ep = KVEndpoint(name="p0").start()
        try:
            with pytest.raises(wire.WireError, match="unknown transfer id"):
                fetch_chunks(ep.address, "bogus", start_block=0, n_blocks=4,
                             chunk_blocks=2, on_chunk=lambda *a: None)
        finally:
            ep.close()

    def test_exporter_crash_mid_window_stage_survives_retry(self):
        """The chaos acceptance at the wire layer: kill exactly window 2
        of the export (``net.send`` nth=2). The importer sees a dead wire
        (not corrupt data), the staged payload survives, no credit leaks,
        and the SAME transfer id re-fetches bit-exactly."""
        planes = _planes(n_blocks=10)
        ep = KVEndpoint(name="p0").start()
        try:
            tid = ep.stage(7, planes, chunk_blocks=3)
            with inject(FaultSpec("net.send", nth=2)) as inj:
                with pytest.raises((wire.WireError, OSError)):
                    self._fetch_all(ep, tid, 10, 3)
                assert [f["site"] for f in inj.fired()] == ["net.send"]
            assert ep.staged_count() == 1  # stage survived the crash
            joined, stats = self._fetch_all(ep, tid, 10, 3)
            for name, arr in planes.items():
                assert joined[name].tobytes() == arr.tobytes(), name
            assert stats["leaked_credits"] == 0
            assert ep.stats()["errors"] >= 1
        finally:
            ep.close()

    def test_importer_chaos_sites_fire(self):
        planes = _planes(n_blocks=6)
        ep = KVEndpoint(name="p0").start()
        try:
            tid = ep.stage(9, planes, chunk_blocks=3)
            with inject(FaultSpec("net.connect", nth=1)):
                with pytest.raises(InjectedFault):
                    self._fetch_all(ep, tid, 6, 3)
            with inject(FaultSpec("net.recv", nth=2)):
                with pytest.raises(InjectedFault):
                    self._fetch_all(ep, tid, 6, 3)
            assert ep.staged_count() == 1  # both failures left the stage
            joined, _ = self._fetch_all(ep, tid, 6, 3)
            assert joined["k"].tobytes() == planes["k"].tobytes()
        finally:
            ep.close()

    def test_release_is_idempotent_and_staging_bounded(self):
        planes = _planes(n_blocks=2)
        ep = KVEndpoint(name="p0", max_staged=2).start()
        try:
            t1 = ep.stage(1, planes, chunk_blocks=2)
            ep.stage(2, planes, chunk_blocks=2)
            with pytest.raises(RuntimeError, match="max_staged"):
                ep.stage(3, planes, chunk_blocks=2)
            assert ep.release(t1) is True
            assert ep.release(t1) is False
            ep.stage(3, planes, chunk_blocks=2)  # slot freed
        finally:
            ep.close()

    def test_closed_endpoint_refuses_staging(self):
        ep = KVEndpoint(name="p0").start()
        ep.close()
        with pytest.raises(RuntimeError, match="closed"):
            ep.stage(1, _planes(n_blocks=2), chunk_blocks=2)


# ---------------------------------------------------------------------------
# transport seam: registry, mismatch guard, fakes, direct engine pairs
# ---------------------------------------------------------------------------
class TestRemoteSeam:
    def test_remote_registered_lazily(self):
        assert "remote" in KV_TRANSPORTS
        tr = get_transport("remote")
        assert tr.name == "remote"
        assert get_transport("remote") is tr  # cached after first resolve
        with pytest.raises(ValueError, match="remote"):
            get_transport("warp")  # error names the full registry

    def test_transport_mismatch_is_a_clear_handoff_error(self, tiny_model):
        """Satellite 2: a handoff exported as ``remote`` but replayed
        through an in-process transport fails naming BOTH transports —
        never a scatter shape error (the remote descriptor carries no
        payload to even mis-scatter)."""
        src = _real_engine(tiny_model, "bf16")
        tok = _prefill_one(src, 31, np.arange(1, 25, dtype=np.int32))
        ho = export_sequence(src, 31, tok, transport="remote")
        try:
            src.scheduler.finish(31)
            assert ho.transport == "remote" and ho.payload is None
            assert ho.endpoint is not None and ho.transfer_id
            tgt = _real_engine(tiny_model, "bf16")
            for wrong in ("host", "in_process", "device"):
                with pytest.raises(HandoffError) as ei:
                    get_transport(wrong).import_payload(
                        tgt, ho, None, 0, [0, 1])
                assert "remote" in str(ei.value) and wrong in str(ei.value)
            # and the right transport still lands it
            assert import_sequence(tgt, ho) == 2
            tgt.scheduler.finish(31)
            assert tgt.state_manager.free_blocks == 64
        finally:
            src._kv_endpoint.close()

    def test_fake_engines_ride_remote(self):
        """No exportable pool -> bookkeeping-only handoff: no endpoint is
        opened and the import no-ops (same contract as host/device)."""
        src, tgt = FakeEngine(), FakeEngine()
        src.scheduler.submit(3, np.arange(1, 9, dtype=np.int32))
        tok = src.step_tokens()[3]
        ho = export_sequence(src, 3, int(tok), transport="remote")
        src.scheduler.finish(3)
        assert ho.endpoint is None and ho.transfer_id is None
        assert getattr(src, "_kv_endpoint", None) is None
        assert import_sequence(tgt, ho) >= 0
        assert tgt.scheduler.peek_next_token(3) == ho.pending_token
        tgt.scheduler.finish(3)

    def test_direct_engine_pair_over_loopback(self, tiny_model):
        """export_sequence/import_sequence over the real wire without a
        Router: the payload crosses a socket, pools conserve on both
        sides, and the stage drains after the import's DONE."""
        src = _real_engine(tiny_model, "int8")  # scale planes on the wire
        tgt = _real_engine(tiny_model, "int8")
        tok = _prefill_one(src, 33, np.arange(1, 25, dtype=np.int32))
        ho = export_sequence(src, 33, tok, transport="remote")
        try:
            src.scheduler.finish(33)
            assert src.state_manager.free_blocks == 64
            assert ho.nbytes > 0  # staged bytes counted without payload
            assert import_sequence(tgt, ho) == 2
            assert tgt.scheduler.peek_next_token(33) == ho.pending_token
            tgt.scheduler.finish(33)
            assert tgt.state_manager.free_blocks == 64
            ep = src._kv_endpoint
            deadline = time.monotonic() + 5
            while ep.staged_count() and time.monotonic() < deadline:
                time.sleep(0.005)
            assert ep.staged_count() == 0  # DONE released the stage
            assert ep.stats()["wire_bytes_sent"] > ho.nbytes  # framing tax
        finally:
            src._kv_endpoint.close()


# ---------------------------------------------------------------------------
# acceptance: Router stream parity + chaos over the remote wire
# ---------------------------------------------------------------------------
def _remote_parity(tiny_model, kv_dtype):
    for sampling in ({"greedy": True},
                     {"greedy": False, "temperature": 0.8, "seed": 123}):
        want = _reference_streams(tiny_model, kv_dtype, sampling)
        cluster = [_real_engine(tiny_model, kv_dtype) for _ in range(3)]
        for e in cluster:
            e.set_sampling(**sampling)
        router = Router(engines=cluster, num_prefill_workers=1,
                        kv_transport="remote").start()
        try:
            got = [list(r.generated)
                   for r in _run_all(router, _PARITY_PROMPTS, 6, timeout=300)]
            health = router.health()
        finally:
            router.shutdown()
        assert got == want, f"remote streams diverged ({kv_dtype}, {sampling})"

        kt = health["kv_transport"]
        assert kt["transport"] == "remote"
        per = kt["per_transport"]["remote"]
        assert per["handoffs"] == len(_PARITY_PROMPTS)
        assert per["bytes"] > 0
        assert per["chunks"] >= 2 * len(_PARITY_PROMPTS)  # pipelined windows
        # discovery: the prefill worker's endpoint is in replica metadata
        # and its per-endpoint wire stats in the transport health block
        assert health["replicas"]["p0"]["kv_endpoint"][0] == "127.0.0.1"
        ep_stats = kt["endpoints"]["p0"]
        assert ep_stats["served"] == len(_PARITY_PROMPTS)
        assert ep_stats["staged_now"] == 0  # every stage released
        assert ep_stats["wire_bytes_sent"] > per["bytes"]
        for e in cluster:
            assert e.state_manager.free_blocks == 64


class TestRemoteStreamParity:
    def test_parity_bf16(self, tiny_model):
        _remote_parity(tiny_model, "bf16")

    @pytest.mark.slow
    def test_parity_int8(self, tiny_model):
        """int8 codes + fp32 scale planes cross the socket bit-exactly."""
        _remote_parity(tiny_model, "int8")


class TestRemoteChaos:
    def test_wire_faults_retry_to_bit_identical_streams(self, tiny_model):
        """Chaos at every net.* site under the Router: a killed dial, a
        killed chunk send, and a killed frame recv each abort one import
        attempt; bounded retries land the SAME staged transfer and every
        stream matches the fault-free single engine. No pool block, window
        credit, or staged transfer leaks."""
        sampling = {"greedy": False, "temperature": 0.8, "seed": 123}
        want = _reference_streams(tiny_model, "bf16", sampling)
        cluster = [_real_engine(tiny_model, "bf16") for _ in range(3)]
        for e in cluster:
            e.set_sampling(**sampling)
        specs = [FaultSpec("net.connect", nth=1),
                 FaultSpec("net.send", nth=3),
                 FaultSpec("net.recv", nth=5)]
        with inject(*specs) as inj:
            router = Router(engines=cluster, num_prefill_workers=1,
                            kv_transport="remote",
                            resilience=_fast_cfg()).start()
            try:
                got = [list(r.generated)
                       for r in _run_all(router, _PARITY_PROMPTS, 6,
                                         timeout=300)]
                health = router.health()
            finally:
                router.shutdown()
        assert got == want, "remote streams diverged under wire chaos"
        assert {f["site"] for f in inj.fired()} \
            == {"net.connect", "net.send", "net.recv"}
        assert health["resilience"]["handoff_retries"] >= 3
        kt = health["kv_transport"]
        assert kt["aborts"] == 0  # every faulted attempt had retries left
        assert kt["endpoints"]["p0"]["staged_now"] == 0
        for e in cluster:
            assert e.state_manager.free_blocks == 64

    def test_exhausted_retries_abort_unwinds_gauge_and_stage(self, tiny_model):
        """Satellite 1 at the router level: kill EVERY attempt of the
        first import (3 = retry budget). The request replays to a
        bit-identical stream, the abort is counted, the inflight-window
        gauge unwinds to zero, and the aborted handoff's staged transfer
        is released at the exporter."""
        sampling = {"greedy": True}
        want = _reference_streams(tiny_model, "bf16", sampling)
        cluster = [_real_engine(tiny_model, "bf16") for _ in range(3)]
        for e in cluster:
            e.set_sampling(**sampling)
        specs = [FaultSpec("net.connect", nth=n) for n in (1, 2, 3)]
        with inject(*specs) as inj:
            router = Router(engines=cluster, num_prefill_workers=1,
                            kv_transport="remote",
                            resilience=_fast_cfg()).start()
            try:
                got = [list(r.generated)
                       for r in _run_all(router, _PARITY_PROMPTS, 6,
                                         timeout=300)]
                health = router.health()
                snap = router.metrics.snapshot()
            finally:
                router.shutdown()
        assert got == want, "replayed stream diverged after aborted handoff"
        assert len(inj.fired()) == 3  # all three attempts of one import
        kt = health["kv_transport"]
        assert kt["aborts"] == 1
        assert snap["kv_handoff_aborts_total"] == 1
        # the abort zeroed the gauge (metrics-level proof rides
        # test_resilience); the final value is the LAST completed
        # handoff's pipeline depth — 2-block transfers, double-buffered
        assert snap["kv_handoff_inflight_windows"] == 2
        assert health["resilience"]["recoveries"] >= 1  # replay, not 500
        assert kt["endpoints"]["p0"]["staged_now"] == 0  # stage released
        assert kt["endpoints"]["p0"]["released"] >= 1
        for e in cluster:
            assert e.state_manager.free_blocks == 64


class TestRemoteCLI:
    def test_kv_transport_remote_flag(self, tiny_model):
        from types import SimpleNamespace

        from deepspeed_tpu.inference.cli import (
            build_serving_stack,
            serve_parse_args,
        )

        cfg, params = tiny_model
        tok = SimpleNamespace(eos_token_id=None)
        args = serve_parse_args([
            "--model", "unused", "--dtype", "float32",
            "--block-size", "16", "--num-blocks", "64",
            "--max-blocks-per-seq", "8", "--max-context", "256",
            "--max-concurrent", "8",
            "--num-prefill-workers", "1", "--num-decode-replicas", "1",
            "--kv-transport", "remote"])
        front, _ = build_serving_stack(args, cfg=cfg, params=params, tok=tok)
        try:
            assert isinstance(front, Router)
            assert front._kv_transport.name == "remote"
            health = front.health()
            assert health["kv_transport"]["transport"] == "remote"
            # registration happened at construction: the prefill worker
            # is listening before the first request arrives
            assert health["replicas"]["p0"]["kv_endpoint"][1] > 0
        finally:
            front.shutdown(drain=False)
