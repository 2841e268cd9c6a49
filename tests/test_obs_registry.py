"""Metrics-registry tests (DESIGN.md §11).

Three contracts are pinned here:

(a) registry primitives: counters/gauges/histograms record host scalars,
    names are get-or-create with one-kind-per-name, ``StatsView`` is a
    read-only Mapping facade;
(b) EQUIVALENCE: the legacy stats-dict surfaces (``SlotStream.stats``,
    ``PagePool.stats``, ``ServingEngine.stats``, ``host_fetch_stats``) are
    views over the registry — after a ``serve_continuous`` run (E=1 and
    E=3, paged and dense) every legacy total equals the registry value for
    its fully-qualified name, bit for bit;
(c) OVERHEAD: the disabled collector (private registry + NullTracer — the
    default every component gets — and the step spans with no profiler
    session recording) costs well under 5% of a decode step's host time.
"""
import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import ensemble as ens
from repro.core.cascade import (
    TierSpec,
    host_fetch,
    host_fetch_stats,
    reset_host_fetch_stats,
)
from repro.models import api
from repro.models.params import unbox
from repro.obs import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NullTracer,
    Observability,
    Scope,
    StatsView,
    UNIT_BUCKETS,
    global_registry,
    perf_clock,
)
from repro.serve import CascadeServer, CascadeTier, Request, ServingEngine

CFG = ModelConfig(
    name="obs-dense", family="dense", n_layers=2, d_model=64, d_ff=128,
    vocab_size=64, n_heads=4, n_kv_heads=2, remat=False,
)


@pytest.fixture(scope="module")
def stack():
    return unbox(ens.init_ensemble(CFG, 3, jax.random.PRNGKey(0)))[0]


@pytest.fixture(scope="module")
def params():
    return unbox(api.init_params(CFG, jax.random.PRNGKey(1)))[0]


def _requests(seed, n, *, hi=14, max_new=4):
    rng = np.random.default_rng(seed)
    return [
        Request(
            tokens=rng.integers(0, 64, int(rng.integers(4, hi))).astype(np.int32),
            max_new_tokens=max_new,
        )
        for _ in range(n)
    ]


# ---------------------------------------------------------------------------
# (a) registry primitives
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("x.calls")
    c.add()
    c.add(4)
    assert c.value == 5
    g = reg.gauge("x.level")
    g.set(7)
    g.set(3)
    assert (g.value, g.peak) == (3, 7)
    h = reg.histogram("x.time_s")
    for v in (1e-5, 2e-4, 0.5):
        h.record(v)
    assert h.count == 3
    assert h.sum == pytest.approx(1e-5 + 2e-4 + 0.5)
    assert h.mean == pytest.approx(h.sum / 3)
    assert 0.0 < h.percentile(0.5) <= 0.5
    assert h.percentile(1.0) == pytest.approx(0.5)


def test_histogram_sum_matches_adhoc_accumulator_bitwise():
    # the StatsView contract: hist.sum IS the float the old ``+=`` produced
    rng = np.random.default_rng(3)
    vals = rng.random(257).tolist()
    h = Histogram("h")
    acc = 0.0
    for v in vals:
        h.record(v)
        acc += v
    assert h.sum == acc  # same additions in the same order: bitwise equal


def test_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    assert reg.counter("a.b") is reg.counter("a.b")
    with pytest.raises(TypeError):
        reg.gauge("a.b")
    sc = Scope(reg, "tier0")
    assert sc.counter("hits").name == "tier0.hits"
    assert sc.histogram("m", UNIT_BUCKETS).buckets == UNIT_BUCKETS


def test_stats_view_is_read_only_mapping():
    reg = MetricsRegistry()
    c = reg.counter("n")
    view = StatsView({"n": lambda: c.value})
    c.add(2)
    assert view["n"] == 2
    assert dict(view) == {"n": 2}
    assert len(view) == 1 and list(view) == ["n"]
    with pytest.raises(TypeError):
        view["n"] = 5  # Mapping, not MutableMapping


def test_snapshot_shape():
    reg = MetricsRegistry()
    reg.counter("c").add(2)
    reg.gauge("g").set(4)
    reg.histogram("h").record(0.25)
    snap = reg.snapshot()
    assert snap["c"] == 2 and snap["g"] == 4 and snap["g.peak"] == 4
    assert snap["h.sum"] == pytest.approx(0.25) and snap["h.count"] == 1
    assert "h.p50" in snap and "h.p99" in snap


def test_host_fetch_stats_is_registry_backed():
    reset_host_fetch_stats()
    host_fetch(jax.numpy.arange(8, dtype=jax.numpy.int32))
    legacy = host_fetch_stats()
    reg = global_registry()
    assert legacy["bytes"] == reg.value("host_fetch.bytes") == 32
    assert legacy["calls"] == reg.value("host_fetch.calls") == 1


# ---------------------------------------------------------------------------
# (b) legacy stats == registry, across serve_continuous
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("paged", [True, False])
def test_engine_serve_equivalence(params, paged):
    ob = Observability()
    eng = ServingEngine(CFG, params, max_seq=64)
    done = eng.serve_continuous(
        _requests(11, 6), n_slots=2, max_seq=32, paged=paged, obs=ob,
    )
    assert len(done) == 6
    reg = ob.registry
    st = eng.last_stream_stats
    for key in ("admitted", "admit_failures", "forced_completions",
                "chunk_calls", "chunk_tokens", "shared_tokens",
                "decode_tokens", "inflight_admitted"):
        assert st[key] == reg.value(f"slot_stream.{key}"), key
    if paged:
        assert reg.value("paging.allocated") > 0
        assert reg.get("paging.pool_occupancy").peak > 0
    assert reg.get("serve.request_latency_s").count == 6


@pytest.mark.parametrize("paged", [True, False])
def test_cascade_serve_equivalence(stack, paged):
    ob = Observability()
    server = CascadeServer(
        [CascadeTier(CFG, stack, TierSpec("t0", "vote", 0.67, k=3, cost=1.0))]
    )
    done = server.serve_continuous(
        _requests(12, 6), n_slots=2, max_seq=32, paged=paged, obs=ob,
    )
    assert len(done) == 6
    reg = ob.registry
    st = server.last_stream_stats[0]
    for key in ("admitted", "chunk_calls", "chunk_tokens", "decode_tokens",
                "forced_completions"):
        assert st[key] == reg.value(f"slot_stream.tier0.{key}"), key
    # every request either answered or deferred exactly once at tier 0
    # (single tier: deferrals are impossible)
    assert reg.value("cascade.tier0.answered") == 6
    assert reg.value("cascade.tier0.deferred") == 0
    assert reg.get("cascade.tier0.agreement_margin").count == 6
    assert reg.value("cascade.tier0.output_tokens") == sum(
        len(r.output) for r in done
    )
    assert reg.get("serve.request_latency_s").count == 6
    if paged:
        assert st and reg.value("paging.tier0.allocated") > 0


def test_pool_stats_view_equivalence():
    from repro.serve.paging import PagePool

    ob = Observability()
    pool = PagePool(9, 4, n_slots=2, max_seq=16, obs=ob, name="paging")
    toks = np.arange(10, dtype=np.int32)
    assert pool.admit(0, toks) == 0
    assert pool.admit(1, toks) == 8  # two full shared prefix pages
    pool.release(0)
    pool.release(1)
    st = dict(pool.stats)
    reg = ob.registry
    assert st["allocated"] == reg.value("paging.allocated")
    assert st["shared_hits"] == reg.value("paging.shared_hits")
    assert st["freed"] == reg.value("paging.freed")
    assert st["peak_pages_in_use"] == reg.get("paging.pool_occupancy").peak
    assert reg.get("paging.pool_occupancy").value == 0  # all released


# ---------------------------------------------------------------------------
# (c) the disabled collector is near-free
# ---------------------------------------------------------------------------


def test_disabled_collector_overhead_under_5pct(params):
    """Per decode step the stream records: 2 clock reads, 1 histogram
    record, 1 counter add (plus the ``tracer.enabled`` checks), and opens
    its refill, prepare, decode (with an arg builder), fetch and advance
    step spans, off with no profiler session recording.  Measure that
    recording cost directly and compare it to the measured decode-step
    host time of a real serve — the telemetry share must stay far under the
    5%% budget."""
    eng = ServingEngine(CFG, params, max_seq=64)
    ob = Observability()  # private registry + NullTracer: the default
    eng.serve_continuous(_requests(13, 6), n_slots=2, max_seq=32, obs=ob)
    h = ob.registry.get("slot_stream.decode.dispatch_s")
    assert h.count > 0
    step_host_s = h.mean  # measured host time of one decode dispatch

    reg = MetricsRegistry()
    c = reg.counter("bench.c")
    hh = reg.histogram("bench.h")
    tr = NullTracer()
    ob.annotation = jax.profiler.TraceAnnotation
    assert not jax.profiler.TraceAnnotation.is_enabled()

    def never():  # pragma: no cover - no session records
        raise AssertionError("an arg builder ran with no profiler session")

    n = 20_000
    t0 = perf_clock()
    for _ in range(n):
        with ob.span("s.refill"):
            pass
        with ob.span("s.prepare"):
            pass
        with ob.span("s.decode", never, active=4):
            with ob.span("s.fetch"):
                a = perf_clock()
                hh.record(perf_clock() - a)
        c.add(4)
        with ob.span("s.advance"):
            pass
        if tr.enabled:  # pragma: no cover - never taken
            tr.begin(0, "x")
        if tr.enabled:  # pragma: no cover - never taken
            tr.end(0, "x")
    per_step_telemetry_s = (perf_clock() - t0) / n
    assert per_step_telemetry_s < 0.05 * step_host_s, (
        f"telemetry {per_step_telemetry_s * 1e6:.2f}us/step vs decode "
        f"dispatch {step_host_s * 1e6:.2f}us/step"
    )
