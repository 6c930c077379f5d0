"""Serve-path observability: metrics, span timelines, step tracing.

The package has one process-wide switch, :data:`enabled`.  Components
that want instrumentation call :func:`telemetry` at construction time:
with the switch off (the default) they get the shared no-op
:data:`~repro.obs.telemetry.NULL_TELEMETRY` and the serve path stays a
true zero — no clocks read, no state allocated.  With the switch on
they get a live :class:`~repro.obs.telemetry.Telemetry` carrying a
:class:`~repro.obs.registry.MetricsRegistry`, per-request
:class:`~repro.obs.spans.RequestTimeline` records and (optionally) a
:class:`~repro.obs.trace.ChromeTracer`.

Typical use::

    import repro.obs as obs

    obs.enable()                     # metrics + timelines + Chrome trace
    eng = ServeEngine(...)           # picks up a live telemetry
    eng.run()
    print(eng.metrics()["obs"])      # structured snapshot
    eng.obs.export_chrome_trace("trace.json")   # load in Perfetto
    obs.disable()

``enable(trace=False)`` keeps metrics/timelines but skips trace-event
collection; ``enable(jax_annotations=True)`` additionally writes the
engine step's ``serve.*`` spans as ``jax.profiler.TraceAnnotation``
scopes, so they sit on an XLA device profile's clock.  See
``docs/observability.md``.
"""

from __future__ import annotations

from typing import Optional

from repro.obs import clock
from repro.obs import costs  # noqa: F401  (public surface)
from repro.obs.costs import (  # noqa: F401
    CostLedger,
    LinearSpec,
    ModelDims,
    OpCost,
    decode_step_costs,
    fork_cost,
    gemv_cost,
    linear_specs,
    model_dims,
    prefill_chunk_costs,
    specs_from_dims,
    total_cost,
)
from repro.obs.registry import (  # noqa: F401  (public surface)
    DEFAULT_LATENCY_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.obs.spans import RequestTimeline  # noqa: F401
from repro.obs.telemetry import (  # noqa: F401
    NULL_TELEMETRY,
    NullTelemetry,
    Telemetry,
)
from repro.obs.trace import ChromeTracer, validate_trace  # noqa: F401

# process-wide switch + the options enable() captured
enabled = False
_trace = True
_jax_annotations = False
_global_registry: Optional[MetricsRegistry] = None


def enable(trace: bool = True, jax_annotations: bool = False) -> None:
    """Turn instrumentation on for subsequently built components."""
    global enabled, _trace, _jax_annotations
    enabled = True
    _trace = trace
    _jax_annotations = jax_annotations


def disable() -> None:
    """Back to the no-op path for subsequently built components."""
    global enabled
    enabled = False


def telemetry(clock_fn=None):
    """The telemetry for a component built *now*: live iff enabled."""
    if not enabled:
        return NULL_TELEMETRY
    return Telemetry(clock_fn, trace=_trace,
                     jax_annotations=_jax_annotations)


def global_registry() -> MetricsRegistry:
    """A process-wide registry for code with no engine in hand (the
    bench timer helpers feed this).  Created lazily; survives
    enable()/disable() flips so accumulated bench walls persist."""
    global _global_registry
    if _global_registry is None:
        _global_registry = MetricsRegistry()
    return _global_registry
