"""Request tracing, Perfetto export, and the control-plane event log.

This package is dependency-free (stdlib only).  The tracer is a global
singleton selected by :func:`configure_tracing`; when tracing is off the
singleton is a :class:`NullTracer` whose methods all return one shared
no-op span, so the disabled path costs an attribute check and zero
allocations per call.

Set-up is measured apart, tracing on or off: :mod:`setup_record` keeps the
spans of import, state placement, trace, lower, compile-or-cache and first
run in one bounded record (``setup_report()``).
"""

from deepspeed_tpu.observability.events import (
    Event,
    EventLog,
    get_event_log,
    log_event,
)
from deepspeed_tpu.observability.export import (
    to_chrome_trace,
    trace_to_chrome,
    validate_chrome_trace,
    write_trace,
)
from deepspeed_tpu.observability.setup_record import (
    SetupRecord,
    get_setup_record,
    set_setup_record,
    setup_report,
)
from deepspeed_tpu.observability.tracing import (
    NULL_TRACER,
    NullTracer,
    Span,
    SpanTracer,
    TraceContext,
    begin_request_trace,
    configure_tracing,
    finish_request_trace,
    get_tracer,
    mark_admitted,
    mark_first_token,
    mark_preempted,
    mark_resumed,
    set_tracer,
)

__all__ = [
    "Event",
    "EventLog",
    "NULL_TRACER",
    "NullTracer",
    "SetupRecord",
    "Span",
    "SpanTracer",
    "TraceContext",
    "begin_request_trace",
    "configure_tracing",
    "finish_request_trace",
    "get_event_log",
    "get_setup_record",
    "get_tracer",
    "log_event",
    "mark_admitted",
    "mark_first_token",
    "mark_preempted",
    "mark_resumed",
    "set_setup_record",
    "set_tracer",
    "setup_report",
    "to_chrome_trace",
    "trace_to_chrome",
    "validate_chrome_trace",
    "write_trace",
]
