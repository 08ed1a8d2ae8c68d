"""Leg-isolation runner for the multichip dryrun gate.

Round-4 lesson (an abort, rc=134): one process running every jit-heavy
leg with unbounded thread pools starves XLA's 40s collective-rendezvous
timer under host load. The orchestrator in ``__graft_entry__`` must
(a) cap per-leg thread pools, (b) isolate each leg in a subprocess, and
(c) retry once on transient failure — mirroring the per-test process
isolation of the reference harness (reference tests/unit/common.py:134,265).
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import __graft_entry__ as ge  # noqa: E402


pytestmark = pytest.mark.smoke


class TestLegEnv:
    def test_thread_caps_and_mesh(self):
        env = ge._leg_env(8)
        assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
        assert "--xla_cpu_multi_thread_eigen=false" in env["XLA_FLAGS"]
        for var in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS"):
            assert env[var] == "1"
        assert env["JAX_PLATFORMS"] == "cpu"

    def test_existing_flags_not_duplicated(self):
        saved = os.environ.get("XLA_FLAGS")
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        try:
            env = ge._leg_env(8)
            # respects an explicit operator override instead of stacking two
            assert env["XLA_FLAGS"].count("xla_force_host_platform_device_count") == 1
        finally:
            if saved is None:
                os.environ.pop("XLA_FLAGS", None)
            else:
                os.environ["XLA_FLAGS"] = saved


class TestRunWithRetry:
    def test_success_first_try(self):
        res = ge._run_with_retry(
            [sys.executable, "-c", "print('ok')"], dict(os.environ), timeout_s=30
        )
        assert res.returncode == 0
        assert "ok" in res.stdout

    def test_transient_failure_recovers_on_retry(self, tmp_path):
        # fails on first invocation, succeeds on the second (marker file) —
        # the rc=134 rendezvous-abort shape the retry exists for
        marker = tmp_path / "attempted"
        script = (
            "import os, sys\n"
            f"m = {str(marker)!r}\n"
            "if os.path.exists(m): print('recovered'); sys.exit(0)\n"
            "open(m, 'w').close(); sys.exit(134)\n"
        )
        res = ge._run_with_retry(
            [sys.executable, "-c", script], dict(os.environ), timeout_s=30,
            log=lambda *_: None,
        )
        assert res.returncode == 0
        assert "recovered" in res.stdout

    def test_persistent_failure_reported(self):
        res = ge._run_with_retry(
            [sys.executable, "-c", "import sys; sys.exit(7)"],
            dict(os.environ), timeout_s=30, log=lambda *_: None,
        )
        assert res.returncode == 7

    def test_timeout_returns_nonzero(self):
        res = ge._run_with_retry(
            [sys.executable, "-c", "import time; time.sleep(60)"],
            dict(os.environ), timeout_s=1.0, retries=0, log=lambda *_: None,
        )
        assert res.returncode != 0
        assert "timeout" in res.stderr


class TestLegRegistry:
    def test_all_legs_have_bodies(self):
        for k, (name, fn_name, cond) in ge._LEGS.items():
            assert callable(getattr(ge, fn_name)), (k, name)
            assert callable(cond)

    def test_conditions_match_divisibility(self):
        # odd device counts must skip every leg that needs pairs/quads
        runnable = [k for k, (_, _, c) in ge._LEGS.items() if c(3)]
        assert runnable == [1, 6, 7]  # DP-only legs tolerate odd worlds
        assert [k for k, (_, _, c) in ge._LEGS.items() if c(8)] == list(range(1, 9))
