"""Set-up measured from inside: one bounded record of what a process spent
before its first step, and of every compile after it.

The ring of :mod:`tracing` evicts oldest first, so set-up is the first thing
a long run loses, and it records nothing while tracing is off. Set-up runs
once and is not the hot path: its spans are kept APART, in a
:class:`SetupRecord`, whether tracing is on or off. The record reuses the
tracer's :class:`~deepspeed_tpu.observability.tracing.Span`, its clock
(``time.monotonic``, the clock of ``SpanTracer.now()`` and ``Request.t_*``)
and its profiler bridge (a span opened with ``span()`` enters
``TraceAnnotation("dstpu.<name>")``).

Three kinds of span land here (docs/OBSERVABILITY.md, "Set-up"):

* ``setup.*``: the program's own phases, opened where the work happens
  (``setup.import``, ``setup.initialize`` > ``setup.state``,
  ``setup.build_stack`` > ``setup.load_weights`` / ``setup.build_engine``);
* ``program.first_call`` (``args: key``): the first call of a step program,
  from its builder to the return of the jitted call;
* ``compile.trace`` / ``compile.lower`` / ``compile.backend``: JAX's own
  stage timings, from ``jax.monitoring`` (``install_compile_listeners``),
  recorded after the fact with start = the listener's ``now()`` less the
  duration. They keep coming after set-up: a compile in the middle of
  serving or training is a span and a count here.

A span's parent is the span open on the SAME THREAD when it was recorded:
that is how a program gets its trace / lower / compile seconds without a
rename of any jitted function. ``report()`` flattens the spans so that every
instant is charged once, to the innermost span that covers it (self time).
JAX's trace events NEST (an inner ``jit``'s fires inside the outer's
interval): the record keeps the outermost of a thread alone
(``args: nested`` counts the rest), and seconds here are always a union of
intervals, never a sum of events.
"""

from __future__ import annotations

import functools
import heapq
import threading
import time
from typing import Callable, Dict, List, Optional

from deepspeed_tpu.observability.tracing import Span, _trace_annotation

__all__ = [
    "PHASES",
    "SetupRecord",
    "get_setup_record",
    "install_compile_listeners",
    "set_setup_record",
    "setup_line",
    "setup_report",
    "setup_span",
]

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
#: JAX's duration events -> the span each becomes
COMPILE_SPANS = {
    TRACE_EVENT: "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}
CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT = "/jax/compilation_cache/cache_hits"

FIRST_CALL = "program.first_call"
#: the phases of ``report()["phases"]``: with what lies outside every span of
#: the program they partition the reported interval
PHASES = ("import", "state", "trace", "lower", "backend_compile", "first_run")
#: a compile span's phase, and its column in a program's row
_COMPILE_PHASE = {"compile.trace": ("trace", "trace_s"), "compile.lower": ("lower", "lower_s"),
                  "compile.backend": ("backend_compile", "compile_s")}


class _ThreadState(threading.local):
    """What the record keeps a thread: its stack of open spans, how deep it
    is in traces and how many it swallowed, what the persistent cache said
    since its last backend stage ended."""

    def __init__(self):
        self.stack: List[Span] = []
        self.tracing = 0
        self.nested = 0
        self.cache_said: dict = {}


class _Handle:
    """``with record.span(...) as sp:``: pushes the span on its thread's
    stack of open spans, pops and ends it on the way out."""

    __slots__ = ("_record", "span", "_annotation")

    def __init__(self, record: "SetupRecord", span: Span, annotation):
        self._record = record
        self.span = span
        self._annotation = annotation

    def __enter__(self) -> Span:
        if self._annotation is not None:
            self._annotation.__enter__()
        self._record._local.stack.append(self.span)
        return self.span

    def __exit__(self, exc_type, exc, tb):
        stack = self._record._local.stack
        while stack and stack.pop() is not self.span:
            pass
        self.span.t1 = self._record.now()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        return False


class SetupRecord:
    """Bounded list of set-up spans (``max_spans``, a ``dropped`` count
    beyond) and the compile counters, which count on past the bound."""

    def __init__(self, max_spans: int = 4096,
                 clock: Callable[[], float] = time.monotonic):
        self.max_spans = int(max_spans)
        self._clock = clock
        self._lock = threading.Lock()
        self._spans: List[Span] = []
        self._next_id = 1
        self._local = _ThreadState()
        self._annotation = None  # resolved on the first ``span()``
        self.dropped = 0
        # every backend compile of the process, and what the persistent
        # cache answered those that asked it
        self.compile_events = 0
        self.cache_hits = 0
        self.cache_misses = 0

    def now(self) -> float:
        return self._clock()

    # ---- write side -------------------------------------------------------

    def _new(self, name: str, t0: float, args: Optional[dict]) -> Span:
        stack = self._local.stack
        parent = stack[-1].span_id if stack else None
        with self._lock:
            sp = Span(self._next_id, parent, name,
                      threading.current_thread().name, t0, args)
            self._next_id += 1
            if len(self._spans) < self.max_spans:
                self._spans.append(sp)
            else:
                self.dropped += 1
        return sp

    def span(self, name: str, **args) -> _Handle:
        """Open ``name`` on this thread until the ``with`` block ends."""
        if self._annotation is None:
            self._annotation = _trace_annotation() or False
        annotation = self._annotation("dstpu." + name) if self._annotation else None
        return _Handle(self, self._new(name, self.now(), args or None), annotation)

    def add(self, name: str, t0: float, t1: Optional[float] = None, **args) -> Span:
        """Record an already-timed ``[t0, t1]`` span under the span open on
        this thread."""
        sp = self._new(name, t0, args or None)
        sp.t1 = self.now() if t1 is None else t1
        return sp

    def on_scalar(self, event: str, _value=None, **_) -> None:
        """``jax.monitoring`` scalar listener: JAX stamps a stage's START with
        one. Every ``jnp`` call in a traced function is a ``jit`` of its own
        whose trace event fires inside the outer's interval (a toy MLP's train
        step: 200 of them; a model's: thousands), so the record counts how
        deep this thread is in traces and keeps the outermost alone."""
        if event == TRACE_EVENT:
            self._local.tracing += 1

    def on_duration(self, event: str, duration: float, **kw) -> None:
        """``jax.monitoring`` duration listener: a compile stage has ended
        on this thread."""
        name = COMPILE_SPANS.get(event)
        if name is None:
            if event == CACHE_RETRIEVAL:
                self._local.cache_said["retrieval_s"] = float(duration)
            return
        t1 = self.now()
        args = {"fun_name": kw.get("fun_name")}
        if name == "compile.trace":
            local = self._local
            local.tracing = max(local.tracing - 1, 0)
            if local.tracing:
                local.nested += 1
                return
            if local.nested:
                args["nested"], local.nested = local.nested, 0
        elif name == "compile.backend":
            said = self._local.cache_said
            asked, hit = said.pop("asked", False), said.pop("hit", False)
            args["cache"] = "hit" if hit else "miss" if asked else "off"
            if "retrieval_s" in said:
                args["retrieval_s"] = said.pop("retrieval_s")
            with self._lock:
                self.compile_events += 1
                self.cache_hits += hit
                self.cache_misses += asked and not hit
        self.add(name, t1 - float(duration), t1, **args)

    def on_event(self, event: str, **_) -> None:
        """``jax.monitoring`` event listener: what the persistent cache was
        asked and answered, inside the backend stage that follows."""
        if event == CACHE_ASKED:
            self._local.cache_said["asked"] = True
        elif event == CACHE_HIT:
            self._local.cache_said["hit"] = True

    # ---- read side --------------------------------------------------------

    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def counters(self) -> Dict[str, int]:
        with self._lock:
            return {"compile_events": self.compile_events, "cache_hits": self.cache_hits,
                    "cache_misses": self.cache_misses, "spans": len(self._spans),
                    "dropped": self.dropped}

    def report(self, t0: Optional[float] = None, t1: Optional[float] = None) -> dict:
        """The spans flattened over ``[t0, t1]`` (default: first start to
        ``now()``): every instant charged once, to the covering span that
        started last, so a nested span takes its interval out of the span
        that holds it, on its thread or on the thread that waits for it.

        ``phases``: self seconds of ``import`` (``setup.import``), ``state``
        (every other ``setup.*``), ``first_run`` (``program.first_call``) and
        ``trace`` / ``lower`` / ``backend_compile`` (``compile.*`` under a
        span of the program; a compile that no span of the program holds is
        the caller's own and is ``unowned``). ``programs``: one row a
        ``program.first_call`` key. ``compile``: backend compiles under the
        program's spans (``programs``), how many asked the persistent cache
        and how many it answered."""
        now = self.now()
        spans = self.spans()
        lo = t0 if t0 is not None else min((s.t0 for s in spans), default=now)
        hi = t1 if t1 is not None else now
        by_id = {s.span_id: s for s in spans}
        clipped = []
        for s in spans:
            a, b = max(s.t0, lo), min(now if s.t1 is None else s.t1, hi)
            if b > a:
                clipped.append((a, b, s))
        self_s = _self_seconds(clipped)

        phases = {p: 0.0 for p in PHASES}
        unowned = {p: 0.0 for p, _ in _COMPILE_PHASE.values()}
        by_span: Dict[str, float] = {}
        programs: Dict[str, dict] = {}
        compiled = asked = hits = 0

        def row(sp: Span) -> dict:
            key = str((sp.args or {}).get("key"))
            return programs.setdefault(key, {
                "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0, "first_run_s": 0.0,
                "compiles": 0, "cache_hits": 0})

        for _, _, s in clipped:
            sec = self_s.get(s.span_id, 0.0)
            by_span[s.name] = by_span.get(s.name, 0.0) + sec
            if s.name == "setup.import":
                phases["import"] += sec
            elif s.name.startswith("setup."):
                phases["state"] += sec
            elif s.name == FIRST_CALL:
                phases["first_run"] += sec
                row(s)["first_run_s"] += sec
            elif s.name in _COMPILE_PHASE:
                phase, column = _COMPILE_PHASE[s.name]
                owner = by_id.get(s.parent_id)
                if owner is None:
                    unowned[phase] += sec
                    continue
                phases[phase] += sec
                backend = s.name == "compile.backend"
                cache = (s.args or {}).get("cache")
                if backend:
                    compiled += 1
                    asked += cache in ("hit", "miss")
                    hits += cache == "hit"
                if owner.name == FIRST_CALL:
                    r = row(owner)
                    r[column] += sec
                    if backend:
                        r["compiles"] += 1
                        r["cache_hits"] += cache == "hit"
        return {
            "t0": lo, "t1": hi, "spans": len(spans), "dropped": self.counters()["dropped"],
            "phases": phases, "unowned": unowned, "by_span": by_span, "programs": programs,
            "compile": {"programs": compiled, "asked_cache": asked, "cache_hits": hits},
        }


    def health(self) -> dict:
        """The ``setup`` block of ``/health``: seconds by phase and by
        program, and the compile counters of the process so far."""
        rep = self.report()
        return {
            "phases_s": {p: round(v, 3) for p, v in rep["phases"].items()},
            "programs": {key: {k: round(v, 3) for k, v in r.items()}
                         for key, r in rep["programs"].items()},
            **self.counters(),
        }

    def prometheus_samples(self) -> list:
        """``(name, labels, value, type)`` samples for ``/metrics``."""
        c = self.counters()
        return [("dstpu_setup_seconds", {"phase": p}, v, "gauge")
                for p, v in self.report()["phases"].items()] + [
            ("dstpu_compile_events_total", None, c["compile_events"], "counter"),
            ("dstpu_compile_cache_hits_total", None, c["cache_hits"], "counter"),
            ("dstpu_compile_cache_misses_total", None, c["cache_misses"], "counter"),
        ]


def _self_seconds(clipped) -> Dict[int, float]:
    """``{span_id: seconds}`` of ``(start, end, span)`` intervals, every
    instant charged to the covering interval that started last (then the one
    that ends first, then the one recorded last)."""
    out: Dict[int, float] = {}
    starts = sorted(clipped, key=lambda x: x[0])
    bounds = sorted({t for a, b, _ in clipped for t in (a, b)})
    live: list = []  # heap of (-start, end, -span_id, span_id)
    i = 0
    for t, t_next in zip(bounds, bounds[1:]):
        while i < len(starts) and starts[i][0] <= t:
            a, b, s = starts[i]
            heapq.heappush(live, (-a, b, -s.span_id))
            i += 1
        while live and live[0][1] <= t:
            heapq.heappop(live)
        # an interval deeper in the heap that has ended is not on top, so it
        # is never charged; it goes when it surfaces
        if live:
            sid = -live[0][2]
            out[sid] = out.get(sid, 0.0) + (t_next - t)
    return out


# ---- the process's record ---------------------------------------------------

_RECORD = SetupRecord()
_LISTENING = False
_LISTEN_LOCK = threading.Lock()


def get_setup_record() -> SetupRecord:
    return _RECORD


def set_setup_record(record: SetupRecord) -> SetupRecord:
    """Swap the process's record (a test's fresh one); the listeners follow."""
    global _RECORD
    _RECORD = record
    return record


def install_compile_listeners() -> bool:
    """Register the ``jax.monitoring`` listeners, once a process however often
    it is called (JAX has no public unregister). They hand every event to the
    record that is current when it fires. False where ``jax`` cannot be
    imported."""
    global _LISTENING
    with _LISTEN_LOCK:
        if _LISTENING:
            return True
        try:
            import jax.monitoring as monitoring
        except ImportError:
            return False
        monitoring.register_event_duration_secs_listener(
            lambda event, duration, **kw: _RECORD.on_duration(event, duration, **kw))
        monitoring.register_event_listener(lambda event, **kw: _RECORD.on_event(event, **kw))
        monitoring.register_scalar_listener(
            lambda event, value, **kw: _RECORD.on_scalar(event, value, **kw))
        _LISTENING = True
        return True


def setup_span(name: str, **args):
    """Decorator: the call runs under ``name`` in the process's record."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*a, **kw):
            with _RECORD.span(name, **args):
                return fn(*a, **kw)

        return wrapper

    return deco


def setup_report(t0: Optional[float] = None, t1: Optional[float] = None) -> dict:
    """``report()`` of the process's record."""
    return _RECORD.report(t0, t1)


def setup_line(report: dict) -> str:
    """One line for a log: ``set-up: import 2.1 s, state 3.4 s, trace 5.0 s,
    lower 1.1 s, compile 0.6 s (3 hits, 0 misses), first run 1.3 s``."""
    p, c = report["phases"], report["compile"]
    return (f"set-up: import {p['import']:.1f} s, state {p['state']:.1f} s, "
            f"trace {p['trace']:.1f} s, lower {p['lower']:.1f} s, "
            f"compile {p['backend_compile']:.1f} s ({c['cache_hits']} hits, "
            f"{c['asked_cache'] - c['cache_hits']} misses), first run {p['first_run']:.1f} s")
