"""Multi-host control plane tests (serving/cluster/agent.py +
remote_core.py behind the Router).

Three layers, cheapest first: (1) `RemoteEngineHandle` admission math and
cache bookkeeping with no sockets at all; (2) in-process contract tests —
a real :class:`ReplicaAgent` over the compute-free ``FakeEngine`` dials a
real ``Router.serve_control()`` listener in the same process, proving
join/decode/cancel/loss/re-join semantics in milliseconds; (3) the
acceptance gate — a REAL agent subprocess (``python -m
tests.unit.test_multihost agent ...``, the same code path as ``dstpu
serve-agent --join``) decodes tiny-model streams BIT-IDENTICAL to the
single-engine driver over the remote KV wire, survives a SIGKILL
mid-decode (quarantine + replay on the surviving local replica, KV pools
conserved on both sides), and re-admits a restarted agent through the
probation probe.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from deepspeed_tpu.observability.events import get_event_log
from deepspeed_tpu.serving import Router, SamplingParams, ServingDriver
from deepspeed_tpu.serving.cluster import EngineCore, ReplicaAgent
from deepspeed_tpu.serving.cluster.remote_core import RemoteEngineHandle
from deepspeed_tpu.serving.metrics import ServingMetrics
from deepspeed_tpu.serving.net.control import ControlChannel
from deepspeed_tpu.serving.net.transport import ensure_endpoint
from deepspeed_tpu.serving.request import Request
from deepspeed_tpu.serving.resilience import ResilienceConfig
from tests.unit.test_disagg import _run_all
from tests.unit.test_kv_transport import (
    _PARITY_PROMPTS,
    _real_engine,
    _reference_streams,
    tiny_model,  # noqa: F401  (module-scoped fixture reused here)
)
from tests.unit.test_serving import FakeEngine, _expected_tokens

REPO_ROOT = Path(__file__).resolve().parents[2]


def _fast_cfg(**kw):
    base = dict(hung_step_s=30.0, probe_backoff_s=0.05,
                retry_backoff_s=0.001)
    base.update(kw)
    base.setdefault("probe_backoff_max_s", max(30.0, base["probe_backoff_s"]))
    return ResilienceConfig(**base)


def _wait_for(pred, timeout=15.0, msg="condition", interval=0.005):
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, f"timed out waiting for {msg}"
        time.sleep(interval)


def _req(uid=1, n_prompt=8, max_new=8):
    return Request(uid=uid,
                   prompt_tokens=np.arange(1, n_prompt + 1, dtype=np.int32),
                   params=SamplingParams(max_new_tokens=max_new,
                                         ignore_eos=True))


# ---------------------------------------------------------------------------
# RemoteEngineHandle: admission math over cached META/STATS, no sockets
# ---------------------------------------------------------------------------
class _RecordingOwner:
    """The handle's owner surface (the Router, normally): record hooks."""

    eos_token_id = None

    def __init__(self):
        self.tokens, self.stats, self.events, self.lost = [], [], [], []

    def _remote_token(self, core, obj):
        self.tokens.append(obj)

    def _remote_stats(self, core, obj):
        self.stats.append(obj)

    def _remote_event(self, core, obj):
        self.events.append(obj)

    def _agent_lost(self, core, err):
        self.lost.append(str(err))


def _meta(**over):
    meta = {
        "tp_shards": 1, "kv_headroom": 0.0,
        "kv": {"num_blocks": 16, "block_size": 4, "max_blocks_per_seq": 8},
        "sm": {"max_tracked_sequences": 4, "max_context": 128},
        "kv_info": {}, "free_blocks": 16, "prefix": [], "stats": {},
        "kv_endpoint": ["127.0.0.1", 4242], "kv_endpoint_stats": {},
    }
    meta.update(over)
    return meta


class TestRemoteHandleMath:
    def test_disconnected_handle_takes_no_placements(self):
        h = RemoteEngineHandle("r0", _meta(), _RecordingOwner())
        assert h.is_remote and h.role == "decode"
        assert not h.connected
        assert not h.admissible(_req())  # no wire, no placement
        # geometry math still answers from the bootstrap META
        assert h.blocks_needed(_req(n_prompt=8, max_new=8)) == 4
        assert h.free_blocks() == 16 and h.kv_total == 16
        assert h.committed_blocks() == 0
        # the router's never-fits pre-check rides the facade
        with pytest.raises(ValueError, match="max_context=128"):
            h.engine.state_manager.check_admissible(128)
        h.engine.state_manager.check_admissible(127)

    def test_admission_tracks_stats_pushes(self):
        owner = _RecordingOwner()
        h = RemoteEngineHandle("r0", _meta(), owner)
        a, b = socket.socketpair()
        c, d = socket.socketpair()
        try:
            h.attach_rpc(ControlChannel(a, name="rpc"))
            h.attach_events(ControlChannel(c, name="events"))
            assert h.connected
            assert h.admissible(_req(n_prompt=8, max_new=8))  # 4 <= 16
            h._apply_stats({"free_blocks": 3, "prefix": ["p1", "p2", "p3"]})
            assert h.free_blocks() == 3
            assert not h.admissible(_req(n_prompt=8, max_new=8))  # 4 > 3
            # prefix coverage is the CONTIGUOUS run, like the local trie
            assert h.prefix_coverage(["p1", "p2", "zz", "p3"]) == 2
            assert h.prefix_coverage(["zz"]) == 0
            assert h.prefix_coverage([]) == 0
            # max_tracked gate counts residents + reservations
            h._apply_stats({"free_blocks": 16})
            for uid in range(4):
                h.requests[uid] = _req(uid=uid, max_new=4)
            assert not h.admissible(_req(uid=9))
        finally:
            h.close()
            for s in (b, d):
                s.close()

    def test_release_rides_outbox_and_disconnect_is_idempotent(self):
        h = RemoteEngineHandle("r0", _meta(), _RecordingOwner())
        h.requests[5] = _req(uid=5)
        h.requests[6] = _req(uid=6)
        h.release(5)  # router-side finish: CANCEL must reach the agent
        h.release(6, scheduler_done=True)  # agent already dropped it
        assert 5 not in h.requests and 6 not in h.requests
        assert list(h._outbox) == [5]  # only the live-agent release flushes
        a, b = socket.socketpair()
        try:
            h.attach_rpc(ControlChannel(a, name="rpc"))
            # sever: first loss handler wins, the second is a no-op
            assert h.mark_disconnected() is True
            assert h.mark_disconnected() is False
            assert not h.connected and not h._outbox
        finally:
            h.close()
            b.close()

    def test_update_meta_refreshes_geometry_on_rejoin(self):
        h = RemoteEngineHandle("r0", _meta(), _RecordingOwner())
        assert h.kv_endpoint_address() == ("127.0.0.1", 4242)
        h.update_meta({"kv": {"num_blocks": 32, "block_size": 4,
                              "max_blocks_per_seq": 8},
                       "free_blocks": 32,
                       "kv_endpoint": ["10.0.0.2", 999]})
        assert h.kv_total == 32 and h.free_blocks() == 32
        assert h.kv_endpoint_address() == ("10.0.0.2", 999)
        st = h.replica_stats()
        assert st["kv_free_blocks"] == 32 and st["kv_total_blocks"] == 32


# ---------------------------------------------------------------------------
# In-process contract: a real agent over FakeEngine dials a real Router
# ---------------------------------------------------------------------------
class _AgentRunner:
    """``agent.run()`` on a thread, exit code captured."""

    def __init__(self, agent):
        self.agent = agent
        self.rc = None
        self.thread = threading.Thread(target=self._main,
                                       name="agent-run", daemon=True)
        self.thread.start()

    def _main(self):
        self.rc = self.agent.run()

    def join(self, timeout=15):
        self.thread.join(timeout)
        assert not self.thread.is_alive(), "agent run loop did not exit"
        return self.rc


def _fake_agent(addr, name="ra0", engine=None):
    core = EngineCore(engine if engine is not None else FakeEngine(),
                      name=name, role="decode", metrics=ServingMetrics())
    return ReplicaAgent(core, addr, name=name,
                        stats_interval_s=0.05, poll_interval_s=0.002)


def _remote_handle(router):
    return next(c for c in router.decode if getattr(c, "is_remote", False))


def _wait_joined(router, name, timeout=15):
    _wait_for(
        lambda: router.health()["control_plane"]["remote_replicas"]
        .get(name, {}).get("connected", False),
        timeout=timeout, msg=f"agent {name} join")


class TestInProcessContract:
    def test_join_decode_observability_goodbye(self):
        """An agent joins, colocated placement seats streams on it, tokens
        pump back through ``Router.deliver``, /health and /metrics carry
        the remote labels, and the router's shutdown GOODBYE ends the
        agent loop cleanly."""
        local = FakeEngine()
        router = Router(engines=[local], num_prefill_workers=0,
                        placement="round_robin").start()
        addr = router.serve_control()
        assert router.serve_control() == addr  # idempotent
        agent = _fake_agent(addr, name="ra0")
        runner = _AgentRunner(agent)
        try:
            _wait_joined(router, "ra0")
            prompts = [np.asarray([10 * (i + 1)], np.int32) for i in range(6)]
            reqs = _run_all(router, prompts, 4)
            for p, r in zip(prompts, reqs):
                assert r.generated == _expected_tokens(p, 4)
            health = router.health()
            cp = health["control_plane"]
            assert cp["enabled"] and cp["address"] == list(addr)
            assert cp["remote_replicas"]["ra0"]["connected"]
            rep = health["replicas"]["ra0"]
            assert rep["remote"] is True and rep["connected"] is True
            # round-robin over [local, remote]: the agent really decoded
            assert rep["requests_finished_total"] == 3
            assert health["replicas"]["d0"]["requests_finished_total"] == 3
            assert 'remote="1"' in router.metrics.prometheus_text()
            snap = router.metrics.snapshot()
            assert snap.get("control_rpcs_total", 0) >= 3  # SUBMITs
            assert snap.get("control_frames_total", 0) > 0
            kinds = {e["kind"] for e in get_event_log().recent(100)}
            assert "agent_joined" in kinds
        finally:
            router.shutdown()
        assert runner.join() == 0  # GOODBYE, not a crash
        # both pools conserved after the streams finished
        assert local.state_manager.free_blocks == 256
        assert agent.core.engine.state_manager.free_blocks == 256

    def test_router_cancel_flushes_to_agent(self):
        """A router-side cancel must free the AGENT's scheduler/KV state
        via the CANCEL flusher (release itself runs under router locks and
        never touches the wire)."""
        # local pool too small for the request: placement must go remote
        local = FakeEngine(block_size=4, num_blocks=2, max_blocks_per_seq=8)
        router = Router(engines=[local], num_prefill_workers=0).start()
        addr = router.serve_control()
        agent = _fake_agent(addr, name="ra0",
                            engine=FakeEngine(step_delay=0.002))
        runner = _AgentRunner(agent)
        try:
            _wait_joined(router, "ra0")
            req = router.submit(np.arange(1, 9, dtype=np.int32),
                                params=SamplingParams(max_new_tokens=512,
                                                      ignore_eos=True))
            req.stream.get(timeout=15)  # decoding, on the agent
            assert req.uid in _remote_handle(router).requests
            assert req.uid in agent.core.requests
            assert router.cancel(req.uid)
            _wait_for(lambda: req.uid not in agent.core.requests,
                      msg="CANCEL to reach the agent")
            _wait_for(
                lambda: agent.core.engine.state_manager.free_blocks == 256,
                msg="agent KV blocks to free")
        finally:
            router.shutdown()
        assert runner.join() == 0

    def test_agent_loss_quarantines_replays_and_rejoins(self):
        """Severing the control wire without a goodbye (= an agent crash)
        quarantines the replica, replays its residents bit-identically on
        the surviving local replica, and the agent's own reconnect loop
        re-joins under the same name — the probation probe re-admits it."""
        local = FakeEngine(step_delay=0.003)
        router = Router(engines=[local], num_prefill_workers=0,
                        placement="round_robin",
                        resilience=_fast_cfg()).start()
        addr = router.serve_control()
        agent = _fake_agent(addr, name="ra0",
                            engine=FakeEngine(step_delay=0.003))
        runner = _AgentRunner(agent)
        try:
            _wait_joined(router, "ra0")
            handle = _remote_handle(router)
            prompts = [np.asarray([100 * (i + 1)], np.int32) for i in range(2)]
            reqs = [router.submit(p, params=SamplingParams(max_new_tokens=60,
                                                           ignore_eos=True))
                    for p in prompts]
            # round-robin seats one stream on the agent; wait for it to be
            # genuinely mid-decode there before pulling the cable
            _wait_for(lambda: any(r.uid in handle.requests
                                  and len(r.generated) >= 2 for r in reqs),
                      msg="remote stream mid-decode")
            for chan in (agent._rpc, agent._events):
                try:
                    chan._conn.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
            for p, r in zip(prompts, reqs):
                assert r.wait(30), "stream did not recover from agent loss"
                assert r.generated == _expected_tokens(p, 60)
            snap = router.metrics.snapshot()
            assert snap.get("replica_failures_total", 0) >= 1
            assert snap.get("recovery_replays_total", 0) >= 1
            kinds = {e["kind"] for e in get_event_log().recent(200)}
            assert "agent_lost" in kinds
            # the agent re-dials on its own; probation probes re-admit it
            _wait_joined(router, "ra0", timeout=20)
            _wait_for(lambda: router.health()["replicas"]["ra0"]["health"]
                      ["state"] == "healthy", timeout=20,
                      msg="probation re-admit")
            kinds = {e["kind"] for e in get_event_log().recent(200)}
            assert "agent_rejoined" in kinds and "probe_passed" in kinds
            # and it takes (round-robin) traffic again
            more = _run_all(router, [np.asarray([7], np.int32)] * 4, 4)
            for r in more:
                assert r.generated == [8, 9, 10, 11]
            assert len(handle.requests) == 0
        finally:
            router.shutdown()
        assert runner.join() == 0
        assert local.state_manager.free_blocks == 256
        assert agent.core.engine.state_manager.free_blocks == 256

    def test_advertised_kv_endpoint_host(self, monkeypatch):
        """DSTPU_KV_ENDPOINT_HOST separates discovery from binding: the
        listener stays on its bind interface while handoff descriptors,
        the agent's bootstrap META, and /health advertise the configured
        address (the satellite regression for multi-NIC hosts)."""
        monkeypatch.setenv("DSTPU_KV_ENDPOINT_HOST", "198.51.100.7")
        router = Router(engines=[FakeEngine()], num_prefill_workers=0).start()
        addr = router.serve_control()
        agent = _fake_agent(addr, name="adv0")
        runner = _AgentRunner(agent)
        try:
            ep = agent._endpoint
            assert ep.bind_address[0] == "127.0.0.1"  # still dialable
            assert ep.address == ("198.51.100.7", ep.bind_address[1])
            assert agent._bootstrap_meta()["kv_endpoint"][0] == "198.51.100.7"
            _wait_joined(router, "adv0")
            # the router counts a replica connected from the moment it
            # attaches the events channel, one META ack BEFORE the agent's own
            # connect() returns; nothing else in this test takes time, so
            # without this wait the shutdown below can land inside that dial,
            # which then finds the listener gone and raises out of run()
            _wait_for(lambda: agent._rpc_thread is not None,
                      msg="the agent's own connect() to return")
            health = router.health()
            assert (health["control_plane"]["remote_replicas"]["adv0"]
                    ["kv_endpoint"][0]) == "198.51.100.7"
            assert health["replicas"]["adv0"]["kv_endpoint"][0] == \
                "198.51.100.7"
        finally:
            router.shutdown()
        assert runner.join() == 0

    def test_local_name_collision_refused(self):
        """An agent claiming a LOCAL replica's name is refused at the
        handshake — it must not shadow an engine the router steps."""
        router = Router(engines=[FakeEngine()], num_prefill_workers=0).start()
        addr = router.serve_control()
        agent = _fake_agent(addr, name="d0")  # d0 = the local replica
        try:
            from deepspeed_tpu.serving.net.wire import WireError
            with pytest.raises(WireError, match="taken by a local engine"):
                agent.connect()
            assert len(router.decode) == 1  # nothing was registered
        finally:
            agent.close()
            router.shutdown()


# ---------------------------------------------------------------------------
# Cross-process acceptance gate: real agent subprocess, real tiny engines
# ---------------------------------------------------------------------------
def _spawn_agent_child(addr, name, kv_dtype, sampling):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.Popen(
        [sys.executable, "-m", "tests.unit.test_multihost", "agent",
         addr[0], str(addr[1]), name, kv_dtype, json.dumps(sampling)],
        cwd=str(REPO_ROOT), env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


def _child_tail(proc, limit=2000):
    try:
        out = proc.stdout.read() or ""
    except Exception:
        out = ""
    return out[-limit:]


def _wait_child_joined(router, name, proc, timeout=240):
    deadline = time.monotonic() + timeout
    while True:
        cp = router.health()["control_plane"]["remote_replicas"]
        if cp.get(name, {}).get("connected", False):
            return
        if proc.poll() is not None:
            raise AssertionError(
                f"agent child died rc={proc.returncode} before joining:\n"
                f"{_child_tail(proc)}")
        assert time.monotonic() < deadline, "agent child never joined"
        time.sleep(0.05)


def _reap_clean(proc, timeout=60):
    """The router's shutdown GOODBYE must end the agent with rc=0."""
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait(timeout=10)
        raise AssertionError("agent child did not exit on router shutdown")
    assert rc == 0, f"agent child rc={rc}:\n{_child_tail(proc)}"


class TestCrossProcess:
    def _parity(self, tiny_model, kv_dtype, sampling):
        """1 prefill worker + 1 local decode + 1 AGENT SUBPROCESS behind
        ``--kv-transport remote``: streams bit-identical to the
        single-engine driver, with the agent demonstrably decoding its
        round-robin share (KV fetched straight from the worker's
        endpoint, token bytes over the events channel)."""
        want = _reference_streams(tiny_model, kv_dtype, sampling)
        worker = _real_engine(tiny_model, kv_dtype)
        decode = _real_engine(tiny_model, kv_dtype)
        for e in (worker, decode):
            e.set_sampling(**sampling)
        router = Router(engines=[worker, decode], num_prefill_workers=1,
                        kv_transport="remote",
                        placement="round_robin").start()
        proc = None
        try:
            addr = router.serve_control()
            proc = _spawn_agent_child(addr, "ragent", kv_dtype, sampling)
            _wait_child_joined(router, "ragent", proc)
            got = [list(r.generated)
                   for r in _run_all(router, _PARITY_PROMPTS, 6, timeout=300)]
            health = router.health()
        finally:
            try:
                router.shutdown()
            finally:
                if proc is not None and proc.poll() is None:
                    _reap_clean(proc)
        assert got == want, f"streams diverged ({kv_dtype}, {sampling})"
        rep = health["replicas"]["ragent"]
        assert rep["remote"] is True and rep["connected"] is True
        assert rep["requests_finished_total"] >= 1  # it really decoded
        assert rep["requests_finished_total"] + \
            health["replicas"]["d0"]["requests_finished_total"] == 3
        assert health["control_plane"]["remote_replicas"]["ragent"][
            "kv_endpoint"] is not None
        for e in (worker, decode):
            assert e.state_manager.free_blocks == 64, "parent pool leaked"

    # tier-1 carries the greedy acceptance; the seeded / int8 combos and
    # the SIGKILL chaos leg ride the slow tier, which run_smoke.sh runs
    # unfiltered (the tier-1 wall-clock budget is the binding constraint)
    @pytest.mark.parametrize(
        "sampling",
        [{"greedy": True},
         pytest.param({"greedy": False, "temperature": 0.8, "seed": 123},
                      marks=pytest.mark.slow)],
        ids=["greedy", "seeded"])
    def test_cross_process_parity_bf16(self, tiny_model, sampling):
        self._parity(tiny_model, "bf16", sampling)

    @pytest.mark.slow
    def test_cross_process_parity_int8(self, tiny_model):
        self._parity(tiny_model, "int8", {"greedy": True})

    @pytest.mark.slow
    def test_cross_process_sigkill_recovery_and_readmit(self, tiny_model):
        """The chaos leg: SIGKILL the agent process mid-decode. The pump
        EOF quarantines the replica, every resident replays bit-identical
        on the surviving local replica, parent pools conserve, and a
        RESTARTED agent under the same name passes its probation probe and
        decodes again (child pool conservation read off its STATS push)."""
        kv_dtype, sampling = "bf16", {"greedy": True}
        n_long = 64
        prompts = _PARITY_PROMPTS[:2]
        single = _real_engine(tiny_model, kv_dtype)
        single.set_sampling(**sampling)
        drv = ServingDriver(single).start()
        want = [list(r.generated)
                for r in _run_all(drv, prompts, n_long, timeout=300)]
        drv.shutdown()
        assert single.state_manager.free_blocks == 64

        worker = _real_engine(tiny_model, kv_dtype)
        decode = _real_engine(tiny_model, kv_dtype)
        for e in (worker, decode):
            e.set_sampling(**sampling)
        router = Router(engines=[worker, decode], num_prefill_workers=1,
                        kv_transport="remote", placement="round_robin",
                        resilience=_fast_cfg()).start()
        proc = proc2 = None
        try:
            addr = router.serve_control()
            proc = _spawn_agent_child(addr, "ragent", kv_dtype, sampling)
            _wait_child_joined(router, "ragent", proc)
            handle = _remote_handle(router)
            reqs = [router.submit(p,
                                  params=SamplingParams(max_new_tokens=n_long,
                                                        ignore_eos=True))
                    for p in prompts]
            # round-robin seats one stream on the agent: kill -9 once it
            # is provably mid-decode there (tokens pumped, still resident)
            _wait_for(lambda: any(r.uid in handle.requests
                                  and len(r.generated) >= 2 for r in reqs),
                      timeout=240, msg="remote decode underway")
            os.kill(proc.pid, signal.SIGKILL)
            proc.wait(timeout=30)
            for r, w in zip(reqs, want):
                assert r.wait(300), "stream did not recover from SIGKILL"
                assert list(r.generated) == w, "replayed stream diverged"
            snap = router.metrics.snapshot()
            assert snap.get("replica_failures_total", 0) >= 1
            assert snap.get("recovery_replays_total", 0) >= 1
            kinds = {e["kind"] for e in get_event_log().recent(300)}
            assert "agent_lost" in kinds
            assert router.health()["replicas"]["ragent"]["health"][
                "quarantines"] >= 1

            # restart under the same name: re-join + probation re-admit
            proc2 = _spawn_agent_child(addr, "ragent", kv_dtype, sampling)
            _wait_child_joined(router, "ragent", proc2)
            _wait_for(lambda: router.health()["replicas"]["ragent"]["health"]
                      ["state"] == "healthy", timeout=60,
                      msg="probation re-admit")
            got = [list(r.generated)
                   for r in _run_all(router, _PARITY_PROMPTS, 6, timeout=300)]
            assert got == _reference_streams(tiny_model, kv_dtype, sampling)
            # child-side pool conservation, read off its STATS pushes
            _wait_for(lambda: router.health()["replicas"]["ragent"]
                      ["kv_free_blocks"] == 64, timeout=30,
                      msg="agent KV pool to drain back to 64")
        finally:
            try:
                router.shutdown()
            finally:
                for p in (proc, proc2):
                    if p is not None and p.poll() is None:
                        _reap_clean(p)
        for e in (worker, decode):
            assert e.state_manager.free_blocks == 64, "parent pool leaked"


# ---------------------------------------------------------------------------
# agent child entry (``python -m tests.unit.test_multihost agent ...``):
# the same EngineCore+ReplicaAgent stack ``dstpu serve-agent --join`` runs,
# over the deterministic tiny model the parity fixtures use.
# ---------------------------------------------------------------------------
def _agent_child_main(argv):
    host, port, name, kv_dtype, sampling_json = argv[:5]
    import jax

    from deepspeed_tpu.models import get_config, init_params

    cfg = get_config("tiny", n_layers=2, dtype="float32", max_seq_len=512)
    params = init_params(cfg, jax.random.key(0))
    engine = _real_engine((cfg, params), kv_dtype)
    engine.set_sampling(**json.loads(sampling_json))
    core = EngineCore(engine, name=name, role="decode",
                      metrics=ServingMetrics())
    agent = ReplicaAgent(core, (host, int(port)), name=name,
                         stats_interval_s=0.05, poll_interval_s=0.001)
    try:
        return agent.run()
    except KeyboardInterrupt:
        agent.close()
        return 0


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "agent":
        sys.exit(_agent_child_main(sys.argv[2:]))
    sys.exit("usage: python -m tests.unit.test_multihost agent "
             "HOST PORT NAME KV_DTYPE SAMPLING_JSON")
