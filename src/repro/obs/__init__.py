"""End-to-end telemetry: metrics registry, span tracing, device counters.

The subsystem has ONE on/off contract, designed so the disabled state is
free on serving hot paths:

  * ``obs.metrics()`` / ``obs.tracer()`` return the active
    :class:`~repro.obs.metrics.Registry` / :class:`~repro.obs.trace.Tracer`
    or ``None`` when disabled;
  * instrumentation sites fetch them **once per batch / publish**, never
    per item, and skip all recording when disabled — no metric calls, no
    allocations, no device work on the per-query path (pinned by
    ``tests/test_obs.py``);
  * device-side pipeline counters (``engine.stages.pipeline_counters``)
    are computed in-graph and fetched as one small host transfer **per
    publish only** — never per query batch — so enabling metrics adds
    zero device syncs to the query path;
  * every span the tracer opens is mirrored, on the same thread, into a
    ``jax.profiler.TraceAnnotation`` of the same name and args, so a
    profiler trace holds the program's spans on the device's clock;
    with the tracer enabled, a ``gc.collect`` span marks each full
    collection (a hook on ``gc.callbacks``, removed by ``disable()``).
    Disabled, no span and no annotation is built.

Enable programmatically (``obs.enable()``), via the serving launcher's
``--metrics-json`` / ``--trace-out`` flags, or with ``REPRO_OBS=1`` in
the environment (CI runs the async serving suite this way).
"""
from __future__ import annotations

import contextlib
import gc
import os
import threading

from repro.obs.metrics import Counter, Gauge, Histogram, Registry
from repro.obs.trace import Tracer, validate_chrome_trace

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "Tracer",
    "validate_chrome_trace", "enable", "disable", "enabled", "metrics",
    "tracer", "count_kernel_trace", "NO_SPAN",
]

# The context a site enters in place of ``tracer().span(...)`` when the
# tracer is disabled: one shared, stateless object, so the disabled path
# builds nothing.
NO_SPAN = contextlib.nullcontext()

_lock = threading.Lock()
_registry: Registry | None = None
_tracer: Tracer | None = None


def enable(metrics: bool = True, trace: bool = True,
           max_trace_events: int = 200_000) -> tuple[Registry | None,
                                                     Tracer | None]:
    """Turn telemetry on (idempotent; keeps existing instruments/events).
    Returns the active (registry, tracer) — either may be ``None`` when
    that half stays disabled."""
    global _registry, _tracer
    with _lock:
        if metrics and _registry is None:
            _registry = Registry()
        if trace and _tracer is None:
            _tracer = Tracer(max_events=max_trace_events)
            gc.callbacks.append(_tracer.on_gc)
        return _registry, _tracer


def disable() -> None:
    """Drop the registry and tracer — instrumentation reverts to the
    no-op fast path."""
    global _registry, _tracer
    with _lock:
        if _tracer is not None and _tracer.on_gc in gc.callbacks:
            gc.callbacks.remove(_tracer.on_gc)
        _registry = None
        _tracer = None


def enabled() -> bool:
    return _registry is not None or _tracer is not None


def metrics() -> Registry | None:
    """The active metrics registry, or ``None`` (disabled fast path)."""
    return _registry


def tracer() -> Tracer | None:
    """The active span tracer, or ``None`` (disabled fast path)."""
    return _tracer


def count_kernel_trace(kernel: str, path: str,
                       variant: str | None = None) -> None:
    """Count one jit trace of a kernel dispatch path (``ref``/``pallas``).

    Called from the ``kernels/*/ops.py`` dispatchers, which only execute
    Python at *trace* time — so this counts (re)compilations, a
    compile-churn signal, and costs nothing at execution time.

    ``variant`` (a QueryPlan bucket tag like ``np8xd4``) additionally
    increments a per-bucket counter
    ``kernel_traces_total_{kernel}_{path}_{variant}`` — the regression
    signal that steady-state compile count equals the number of plan
    *buckets*, never the number of distinct requested plans. The
    aggregate counter keeps its historical name either way."""
    reg = _registry
    if reg is not None:
        reg.counter(f"kernel_traces_total_{kernel}_{path}",
                    help="jit traces of this kernel dispatch path").inc()
        if variant is not None:
            reg.counter(f"kernel_traces_total_{kernel}_{path}_{variant}",
                        help="jit traces of this kernel dispatch path, "
                             "per plan bucket").inc()


if os.environ.get("REPRO_OBS", "0") == "1":  # pragma: no cover - env hook
    enable()
