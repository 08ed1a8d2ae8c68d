"""What every runner shares: finding a cell's files by name, counting
compilations, tracing a sub-window, and comparing with the plain reference."""

import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

from benchmarks.harness import stats

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
REHEARSAL = os.path.join(BENCH, "tests", "rehearsal")  # toy-size overlays, one JSON file or more

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg):
    """Standard output carries the result; everything for a human goes here."""
    print(msg, file=sys.stderr, flush=True)


def start_jax(rehearse: bool, chips: int):
    """The devices of this process, or SystemExit(3) where the run cannot be a
    measurement: no TPU (no CPU, when rehearsing), or fewer chips than asked.
    Also routes the program's logger to standard error and places the compile
    cache: the program's own choice (``JAX_COMPILATION_CACHE_DIR`` if set, else
    the fixed ``<checkout>/.jax_cache``), with small programs cached too, so
    that a second run compiles nothing."""
    if rehearse:
        if os.environ.get("JAX_PLATFORMS") != "cpu":
            log("--rehearse is a CPU run: set JAX_PLATFORMS=cpu")
            raise SystemExit(3)
        flag = "--xla_force_host_platform_device_count=4"
        if flag not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
    import jax

    devices = jax.devices()
    want = "cpu" if rehearse else "tpu"
    if devices[0].platform != want:
        log(f"JAX reports platform {devices[0].platform!r} ({devices[0].device_kind}); "
            f"this run needs {want!r}")
        raise SystemExit(3)
    if len(devices) < chips:
        log(f"{len(devices)} device(s), the cell asks for {chips}")
        raise SystemExit(3)

    from deepspeed_tpu.accelerator.device import setup_compile_cache
    from deepspeed_tpu.utils.logging import logger

    for handler in logger.handlers:
        handler.setStream(sys.stderr)
    if rehearse:
        # a compile for a described chip written here could not be read back
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        cache_dir = setup_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        log(f"compile cache: {cache_dir}")
    return devices


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Catalog:
    """The benchmark's files, found by name in ``BENCHMARK.json``. A rehearsal
    reads the same files and lays the toy sizes of ``tests/rehearsal/*.json``
    over them: ``config`` (the one toy configuration), ``run_seconds``, and
    under ``traffic`` and ``cell`` a toy value for each key, which replaces
    that key wherever a mix or a cell has it."""

    def __init__(self, rehearse: bool = False):
        self.index = read_json(os.path.join(REPO, "BENCHMARK.json"))
        self.toy = {"traffic": {}, "cell": {}}
        if rehearse:
            for fn in sorted(os.listdir(REHEARSAL)):
                for key, value in read_json(os.path.join(REHEARSAL, fn)).items():
                    if isinstance(value, dict):
                        self.toy[key].update(value)
                    else:
                        self.toy[key] = value
        self.run_seconds = self.toy.get("run_seconds", self.index["run_seconds"])

    def _shrunk(self, group: str, real: dict) -> dict:
        return {k: self.toy[group].get(k, v) for k, v in real.items()}

    def cell(self, name: str) -> dict:
        for w in self.index["workloads"]:
            if w["name"] == name:
                return self._shrunk("cell", {**w, **read_json(os.path.join(BENCH, "cells", name + ".json"))})
        known = ", ".join(w["name"] for w in self.index["workloads"])
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json (have: {known})")

    def config(self, name: str) -> dict:
        for c in self.index["configs"]:
            if c["name"] == name:
                return read_json(os.path.join(REPO, self.toy.get("config", c["file"])))
        raise SystemExit(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return self._shrunk("traffic", read_json(os.path.join(BENCH, "traffic", name + ".json")))

    def metrics(self, group: str, cell_name: str) -> List[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.index[group]
                if "workloads" not in m or cell_name in m["workloads"]]


class CompileCounter:
    """Every backend compilation JAX makes in this process (a hit in the
    persistent cache still counts: the shape was new to the process), with
    the host time at which it ended."""

    def __init__(self):
        import jax.monitoring

        self.events: List = []  # (t_end monotonic, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.events.append((time.monotonic(), float(duration)))

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.events if t0 <= t < t1)


class SubWindowTrace:
    """Profile the last ``trace_s`` seconds of the measured window. The trace
    goes to a temporary directory (``TMPDIR``) that is removed once reduced."""

    def __init__(self, enabled: bool, trace_s: float, keep: Optional[str] = None):
        self.enabled = enabled
        self.keep = keep
        self.trace_s = float(trace_s)
        self.dir: Optional[str] = None
        self._annot = None
        self.running = False
        self.done = False
        self.t_start = self.t_stop = None

    def maybe_start(self, now: float, t_end: float):
        if not self.enabled or self.running or self.done or now < t_end - self.trace_s:
            return
        import jax

        from benchmarks.harness import xplane

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._annot = jax.profiler.TraceAnnotation(xplane.WINDOW_EVENT)
        self._annot.__enter__()
        self.t_start = time.monotonic()
        self.running = True

    def stop(self):
        if not self.running:
            return
        import jax

        self.t_stop = time.monotonic()
        self._annot.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.running, self.done = False, True

    def reduce(self) -> Optional[Dict]:
        if not self.done:
            return None
        from benchmarks.harness import xplane

        try:
            path = xplane.find_xplane(self.dir)
            if self.keep:
                os.makedirs(self.keep, exist_ok=True)
                shutil.copy(path, self.keep)
                with open(os.path.join(self.keep, "describe.txt"), "w") as f:
                    f.write(xplane.describe(path) + "\n")
            trace = xplane.load(path)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        window = next(((s, e) for n, s, e in trace["host"] if n == xplane.WINDOW_EVENT), None)
        return xplane.reduce_trace(trace, window)


def log_blocks(marks):
    """The window's blocks for a human: the judged number is their median."""
    rates = stats.block_rates(marks)
    log(f"{len(rates)} block(s), tokens/s in each: " + " ".join(f"{r:.1f}" for r in rates))


def reference_module(hf: dict):
    return importlib.import_module(hf["reference"])


def device_info(used) -> Dict:
    """``device`` of the result line: the chips the run used, as JAX reports
    them, and the peak on the fullest."""
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in used)
    return {"platform": used[0].platform, "kind": used[0].device_kind, "count": len(used),
            "memory_peak_bytes": peak}
