"""Step spans in the profiler's own trace (DESIGN.md §11).

A reduced two-tier cascade serves a few sweeps under ``jax.profiler``; the
``.xplane.pb`` it leaves must hold every span of the vocabulary, each
``fetch`` nested in its stream's ``decode``, and decode args equal to what
the stream decoded.  Off a profiler session the arg builders never run,
and the whole path stays clean under a device-to-host transfer guard.  The
``obs.anchor`` event maps the per-request ``Tracer`` onto the profiler's
clock.
"""
import glob
import os
import warnings

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import ensemble as ens
from repro.core.cascade import TierSpec
from repro.models.params import unbox
from repro.obs import Observability, Tracer
from repro.serve import CascadeServer, CascadeTier, Request, ServeConfig
from repro.serve.cascade_server import _CascadeRun
from repro.serve.slot_stream import SlotStream

SMALL = ModelConfig(
    name="osp-s", family="dense", n_layers=2, d_model=64, d_ff=128,
    vocab_size=64, n_heads=4, n_kv_heads=2, remat=False,
)
BIG = ModelConfig(
    name="osp-b", family="dense", n_layers=3, d_model=96, d_ff=192,
    vocab_size=64, n_heads=4, n_kv_heads=4, remat=False,
)
STEPS = ("refill", "prefill_chunk", "prepare", "decode", "fetch", "advance")


@pytest.fixture(scope="module")
def server():
    v1, _ = unbox(ens.init_ensemble(SMALL, 3, jax.random.PRNGKey(0)))
    v2, _ = unbox(ens.init_ensemble(BIG, 1, jax.random.PRNGKey(1)))
    return CascadeServer([
        CascadeTier(SMALL, v1, TierSpec("t1", "vote", 0.67, k=3, cost=1.0)),
        CascadeTier(BIG, v2, TierSpec("t2", "confidence", -1.0, k=1, cost=50.0)),
    ])


def _requests(n=6, seed=6):
    rng = np.random.default_rng(seed)
    return [Request(tokens=rng.integers(0, 64, int(rng.integers(6, 20))).astype(np.int32),
                    max_new_tokens=5) for _ in range(n)]


def _record_decodes(run):
    """Per stream, (active, rows) of every decode call, read from the
    stream's own state as the call starts."""
    seen = {st.name: [] for st in run.streams}
    for st in run.streams:
        dec = st.backend.decode

        def decode(tok, pos, st=st, dec=dec):
            lens = [int(pos[s]) + 1 for s, r in enumerate(st.slot_req) if r is not None]
            seen[st.name].append((len(lens), sum(lens)))
            return dec(tok, pos)

        st.backend.decode = decode
    return seen


def _host_events(directory):
    """(name, start_ns, end_ns, args) of every host event of the newest
    trace under ``directory``, by start."""
    path = sorted(glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in pd.planes:
            if plane.name.startswith("/host"):
                for line in plane.lines:
                    out += [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                            for e in line.events]
    return sorted(out, key=lambda e: e[1])


@pytest.fixture(scope="module")
def traced(server, tmp_path_factory):
    """A cascade served for every sweep under the profiler, with a
    device-to-host transfer guard on: (run, decodes seen, host events)."""
    directory = str(tmp_path_factory.mktemp("spans"))
    # compile every program first: compiling under the profiler is slow
    server.serve_continuous(_requests(), ServeConfig(n_slots=2, max_seq=48, max_chunk=8))
    run = _CascadeRun(server, ServeConfig(n_slots=2, max_seq=48, max_chunk=8),
                      Observability())
    seen = _record_decodes(run)
    run.submit(_requests())
    jax.profiler.start_trace(directory)
    try:
        with jax.transfer_guard_device_to_host("disallow"):
            while run.active:
                run.sweep()
    finally:
        jax.profiler.stop_trace()
    return run, seen, _host_events(directory)


def test_every_span_of_the_vocabulary_appears(traced):
    run, _, events = traced
    names = {e[0] for e in events}
    want = {f"slot_stream.tier{i}.{s}" for i in (0, 1) for s in STEPS}
    want |= {"cascade.tier0.vote", "cascade.tier1.vote", "obs.anchor"}
    assert want <= names, want - names
    assert run.ob.registry.value("cascade.tier0.deferred") > 0
    votes = [e for e in events if e[0] == "cascade.tier0.vote"]
    assert sum(e[3]["defer"] for e in votes) == run.ob.registry.value(
        "cascade.tier0.deferred")


def test_fetch_nests_in_decode(traced):
    _, _, events = traced
    for i in (0, 1):
        decodes = [e for e in events if e[0] == f"slot_stream.tier{i}.decode"]
        fetches = [e for e in events if e[0] == f"slot_stream.tier{i}.fetch"]
        assert len(fetches) == len(decodes) > 0
        for f, d in zip(fetches, decodes):
            assert d[1] <= f[1] and f[2] <= d[2]


def test_decode_args_are_what_the_stream_decoded(traced):
    run, seen, events = traced
    for st in run.streams:
        args = [(e[3]["active"], e[3]["rows"]) for e in events
                if e[0] == f"{st.name}.decode"]
        assert args == seen[st.name] and len(args) == st.steps


def test_chunk_args_cover_the_prompts(traced):
    run, _, events = traced
    chunks = [e[3] for e in events if e[0] == "slot_stream.tier0.prefill_chunk"]
    assert sum(c["tokens"] for c in chunks) == run.ob.registry.value(
        "slot_stream.tier0.chunk_tokens")
    assert all(c["start"] >= 0 for c in chunks)


def test_arg_builders_never_run_off_a_session(server, monkeypatch):
    calls = []

    def builder(self):
        calls.append(1)
        return {"rows": 0}

    monkeypatch.setattr(SlotStream, "_decode_args", builder)
    ob = Observability()
    ob.annotation = jax.profiler.TraceAnnotation
    with ob.span("x", lambda: calls.append(1) or {}):
        pass
    done = server.serve_continuous(_requests(3, seed=2), ServeConfig(n_slots=2, max_seq=48))
    assert len(done) == 3 and not calls


def test_no_annotation_keeps_spans_off():
    ob = Observability()
    with ob.span("x", lambda: pytest.fail("builder ran")) as span:
        span.set_metadata(defer=1)


def test_engine_stream_spans_take_the_stream_name(tmp_path):
    from repro.serve import ServingEngine
    from repro.models import api

    params, _ = unbox(api.init_params(SMALL, jax.random.PRNGKey(3)))
    eng = ServingEngine(SMALL, params, max_seq=48)
    cfg = ServeConfig(n_slots=2, max_seq=48)
    eng.serve_continuous(_requests(2, seed=3), cfg)  # compiles off the profiler
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.serve_continuous(_requests(2, seed=3), cfg)
    finally:
        jax.profiler.stop_trace()
    names = {e[0] for e in _host_events(str(tmp_path))}
    assert {f"slot_stream.{s}" for s in STEPS} <= names


def test_anchor_maps_the_tracer_onto_the_profiler_clock(tmp_path):
    ob = Observability(tracer=Tracer())
    ob.annotation = jax.profiler.TraceAnnotation
    jax.profiler.start_trace(str(tmp_path))
    try:
        ob.tracer.begin(1, "probe")
        with ob.span("probe"):
            pass
        ob.tracer.end(1, "probe")
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    anchor = next(e for e in events if e[0] == "obs.anchor")
    probe = next(e for e in events if e[0] == "probe")
    begin = next(e for e in ob.tracer.export()["traceEvents"]
                 if e.get("name") == "probe" and e["ph"] == "B")
    at_ns = anchor[1] + (begin["ts"] - anchor[3]["ts"]) * 1e3
    assert abs(at_ns - probe[1]) < 50e3
    assert "obs.anchor" in ob.tracer.export()["otherData"]["clock"]
