"""repro.obs — spans, metrics and phase-attributed tracing.

Zero-overhead-when-disabled, host-side-only observability for the
whole stack: plan emitters open ``plan/*`` spans, the runtime and the
streaming front door open ``wave/*``/``stream/*``/``run``/``slab``
spans around host work (no span waits for the device) and emit
compile-cache events, and the serving tier keeps
queue/slab/cache/latency metrics in a Prometheus-style registry.
Every enabled span is also a ``jax.profiler.TraceAnnotation``, so under
the JAX profiler the spans share the device trace's clock.

    from repro import obs

    with obs.capture() as tr:
        generate(spec, P=8)
    print(tr.phase_totals())          # {'plan_s': .., 'exec_s': .., 'sink_s': ..}
    tr.export_chrome("trace.json")    # load in ui.perfetto.dev

See ``src/repro/obs/README.md`` for the span/metric inventory and the
profiling recipes.
"""
from .metrics import (Counter, Gauge, Histogram, Registry, parse_exposition,
                      DEFAULT_BUCKETS)
from .tracer import (NULL_SPAN, PHASES, Span, SpanRecord, Tracer, capture,
                     disable, enable, event, export_chrome, is_enabled,
                     phase_totals, trace, tracer)

__all__ = [
    # tracer
    "NULL_SPAN", "PHASES", "Span", "SpanRecord", "Tracer", "capture",
    "disable", "enable", "event", "export_chrome", "is_enabled",
    "phase_totals", "trace", "tracer",
    # metrics
    "Counter", "Gauge", "Histogram", "Registry", "parse_exposition",
    "DEFAULT_BUCKETS",
]
