"""Fleet-wide observability: metrics registry + request tracing.

:mod:`repro.obs.metrics` is the dependency-free metrics core (counters,
gauges, fixed-bucket histograms with percentile estimation, JSON
snapshot + Prometheus text exposition); :mod:`repro.obs.trace` is the
span-based request-tracing layer and the unified request latency clock.
``docs/OBSERVABILITY.md`` catalogues every metric and span the serving
stack emits.

Observability is **opt-in and zero-cost when disabled**: the process
default is the :class:`~repro.obs.metrics.NullRegistry` /
:class:`~repro.obs.trace.NullTraceRecorder` pair — no-op recorders
behind the real interface — and instrumented components resolve the
globals at construction time::

    from repro import obs
    reg = obs.enable_metrics()              # before building the stack
    rec = obs.enable_tracing(sample=8)
    ...  # construct sessions / services / routers, serve traffic
    json.dump(reg.snapshot(), fh)
    print(reg.to_prometheus())
    traces = rec.to_dicts()

Components also accept an explicit ``metrics=`` / ``tracer=`` argument
(tests use private registries this way); ``None`` means "the global
default at construction time".  Nothing here ever enters a jax trace,
so enabling observability cannot perturb bit-exactness — and
:func:`jax_trace` is the separate, explicitly opt-in
``jax.profiler`` capture for kernel-level timelines.

:func:`span` opens a host span on the profiler's own clock around each
phase of the serving step; with no profiler running it records nothing,
so the spans need no switch.  :func:`compile_count` counts the backend
compiles of the process, which the service step reports as a span
argument.
"""

from __future__ import annotations

import contextlib
import pathlib
import threading

from repro.obs.metrics import (Counter, Gauge, Histogram, HistogramState,
                               MetricsRegistry, NullRegistry, RATIO_BUCKETS,
                               TIME_BUCKETS_S, exponential_buckets,
                               linear_buckets)
from repro.obs.trace import (NullTraceRecorder, RequestTimeline, Span, Trace,
                             TraceRecorder, assemble_trace)

#: The process-wide disabled-mode singletons.
NULL_METRICS = NullRegistry()
NULL_TRACER = NullTraceRecorder()

_metrics: MetricsRegistry = NULL_METRICS
_tracer: TraceRecorder = NULL_TRACER


def enable_metrics(registry: MetricsRegistry | None = None
                   ) -> MetricsRegistry:
    """Install ``registry`` (default: a fresh one) as the global default.

    Components constructed *after* this call record into it; already-
    constructed components keep whatever they resolved.
    """
    global _metrics
    _metrics = registry if registry is not None else MetricsRegistry()
    return _metrics


def enable_tracing(sample: int = 8,
                   recorder: TraceRecorder | None = None) -> TraceRecorder:
    """Install a trace recorder sampling the first ``sample`` requests."""
    global _tracer
    _tracer = recorder if recorder is not None else TraceRecorder(sample)
    return _tracer


def disable() -> None:
    """Reset both globals to the no-op recorders (observability off)."""
    global _metrics, _tracer
    _metrics = NULL_METRICS
    _tracer = NULL_TRACER


def metrics() -> MetricsRegistry:
    """The current global metrics registry (Null when disabled)."""
    return _metrics


def tracer() -> TraceRecorder:
    """The current global trace recorder (Null when disabled)."""
    return _tracer


def resolve_metrics(explicit: MetricsRegistry | None) -> MetricsRegistry:
    """Constructor helper: an explicit registry, or the global default."""
    return explicit if explicit is not None else _metrics


def resolve_tracer(explicit: TraceRecorder | None) -> TraceRecorder:
    """Constructor helper: an explicit recorder, or the global default."""
    return explicit if explicit is not None else _tracer


@contextlib.contextmanager
def jax_trace(log_dir: str | pathlib.Path | None):
    """Opt-in ``jax.profiler`` capture around a hot path.

    ``None`` is a no-op (the default everywhere), so callers can wrap
    their serving loop unconditionally::

        with obs.jax_trace(args.jax_profile):
            router.run_until_idle()

    With a directory, the device/XLA timeline lands there for TensorBoard
    or Perfetto — this is the only observability feature that touches
    jax, and it is never on unless a path is passed.
    """
    if log_dir is None:
        yield
        return
    import jax

    with jax.profiler.trace(str(log_dir)):
        yield


def span(name: str, **args):
    """A host span named ``name`` on the device trace's clock.

    A context manager over ``jax.profiler.TraceAnnotation``: under a
    running ``jax.profiler`` capture (:func:`jax_trace`, or any
    ``jax.profiler.start_trace``) the span lands on the host plane of the
    same trace as the device's ops, with ``args`` (ints, floats, strings)
    as its event stats; with no capture running it records nothing and
    costs about a microsecond.  ``set_metadata(**more)`` on the returned
    object adds arguments before the span closes.
    """
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name, **args)


def capturing() -> bool:
    """Whether a ``jax.profiler`` capture is running, so that a
    :func:`span` records: for span arguments that cost more to work out
    than the span itself."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation.is_enabled()


#: The ``jax.monitoring`` duration event of one backend compile (or
#: persistent-cache load) of a jitted program.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_compile_lock = threading.Lock()
_compiles: int | None = None      # None until the listener is registered


def _on_duration(event: str, duration: float, **kwargs) -> None:
    global _compiles
    if event == BACKEND_COMPILE_EVENT:
        with _compile_lock:
            _compiles += 1


def compile_count() -> int:
    """Backend compiles in this process since the first call.

    The first call registers one process-wide ``jax.monitoring``
    listener; the difference of two calls is the number of compiles in
    between, on any thread.
    """
    global _compiles
    with _compile_lock:
        if _compiles is None:
            import jax

            _compiles = 0
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
        return _compiles


__all__ = [
    "Counter", "Gauge", "Histogram", "HistogramState", "MetricsRegistry",
    "NullRegistry", "RATIO_BUCKETS", "TIME_BUCKETS_S",
    "exponential_buckets", "linear_buckets",
    "NullTraceRecorder", "RequestTimeline", "Span", "Trace",
    "TraceRecorder", "assemble_trace",
    "NULL_METRICS", "NULL_TRACER",
    "enable_metrics", "enable_tracing", "disable", "metrics", "tracer",
    "resolve_metrics", "resolve_tracer", "jax_trace", "span", "capturing",
    "BACKEND_COMPILE_EVENT", "compile_count",
]
