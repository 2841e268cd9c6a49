"""Unified serving telemetry (DESIGN.md §11): metrics registry + tracing.

``Observability`` is the bundle the serving layer passes around: a
``MetricsRegistry`` (always live — the legacy stats-dict views read from
it), a tracer (``NullTracer`` by default — per-request Perfetto tracing is
the opt-in half), and the injectable clock every serve-side timestamp goes
through (abclint ABC601 bans raw ``time.perf_counter()`` calls in
``serve/``).

Three invariants every recording site obeys (the §11 contract):

1. **No host sync**: only already-host-resident scalars are recorded.
   Device values cross through the metered ``core.cascade.host_fetch``
   BEFORE they may touch a metric or a trace arg — telemetry never adds a
   device→host transfer the byte meter cannot see (ABC2xx stays clean).
2. **Injectable time**: timestamps come from ``obs.clock`` /
   ``Tracer._clock``, so tests inject fake clocks and traces become
   deterministic; wall-clock never leaks into traced jax programs (ABC3xx).
3. **Near-zero when disabled**: the registry records via pre-resolved
   attribute updates (resolve metrics once at construction); the tracer is
   guarded by a single ``tracer.enabled`` check per site.

Step spans (``Observability.span``) go into the profiler's own trace,
through the annotation class the serve layer hands the bundle
(``jax.profiler.TraceAnnotation``): the serving loop's host work lands on
the device trace's clock, where a profiler session records it, and costs
one ``is_enabled`` check per span where none does.

This package imports only the stdlib — no jax, no repro modules — so any
layer (core, serve, benchmarks, tools) may depend on it without cycles.
"""
from __future__ import annotations

from repro.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Scope,
    StatsView,
    TIME_BUCKETS_S,
    UNIT_BUCKETS,
)
from repro.obs.trace import (
    NullTracer,
    REQUEST_PID,
    Tracer,
    perf_clock,
    validate_trace,
)


class _NoSpan:
    """The span of a bundle with no annotation, or with no profiler
    session recording."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args):
        pass


_NO_SPAN = _NoSpan()


class Observability:
    """The telemetry bundle: registry + tracer + clock.

    Components that are not handed one create a PRIVATE bundle (own
    registry, disabled tracer) — their legacy stats views keep working and
    nothing is shared accidentally.  Pass one ``Observability`` down a
    serving stack to get a unified registry namespace and a single
    per-request trace across tiers, pools, and transports.

    ``annotation`` is the host-span class ``span`` writes through (the
    serve layer sets ``jax.profiler.TraceAnnotation``); None keeps every
    span off."""

    __slots__ = ("registry", "tracer", "clock", "annotation", "_anchored")

    def __init__(self, registry=None, tracer=None, clock=None):
        self.registry = registry if registry is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else NullTracer()
        self.clock = clock if clock is not None else perf_clock
        self.annotation = None
        self._anchored = False

    def span(self, name: str, args=None, **kw):
        """A host span ``name`` in the profiler's trace, as a context
        manager; its ``set_metadata(**kw)`` adds args known only inside.
        ``kw`` are host scalars the caller already holds; ``args``, a
        zero-argument callable returning more of them, runs only while a
        profiler session records.  The first span of each session also
        writes an ``obs.anchor`` annotation whose args are the bundle's
        clock (``clock_us``) and, with a recording tracer, its own
        timestamp (``ts``, µs): ``start_ns - ts * 1e3`` maps the tracer's
        request tracks onto the profiler's clock."""
        ann = self.annotation
        if ann is None or not ann.is_enabled():
            self._anchored = False
            return _NO_SPAN
        if not self._anchored:
            self._anchored = True
            anchor = {"clock_us": self.clock() * 1e6}
            if self.tracer.enabled:
                anchor["ts"] = self.tracer.now_us()
            with ann("obs.anchor", **anchor):
                pass
        if args is not None:
            kw.update(args())
        return ann(name, **kw)

    @classmethod
    def private(cls) -> "Observability":
        """A self-contained bundle (fresh registry, disabled tracer)."""
        return cls()

    def scope(self, prefix: str) -> Scope:
        """A name-prefix handle over this bundle's registry."""
        return Scope(self.registry, prefix)


def null_obs() -> Observability:
    """A fresh private bundle — the disabled-collector default."""
    return Observability()


_GLOBAL_REGISTRY: MetricsRegistry = MetricsRegistry()


def global_registry() -> MetricsRegistry:
    """The process-wide registry for module-level meters that predate any
    ``Observability`` (``core.cascade.host_fetch``'s byte/call counters)."""
    return _GLOBAL_REGISTRY


__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NullTracer",
    "Observability",
    "REQUEST_PID",
    "Scope",
    "StatsView",
    "TIME_BUCKETS_S",
    "Tracer",
    "UNIT_BUCKETS",
    "global_registry",
    "null_obs",
    "perf_clock",
    "validate_trace",
]
