"""The device's idle time put down to the program's step spans, and the
paged decode kernel's roofline from the decode spans' args: on a small
hand-made trace, on a trace of the CPU, and on a slice of a trace
recorded on a TPU v5e."""
import json
import os
from types import SimpleNamespace

import pytest

from bench import counts as K
from bench import layers
from bench import spans as SP
from bench import tracing as T

MS = 1e6  # ns
V5E = "TPU v5 lite"


def plain(planes):
    """The planes as ``bench/tracing.reduce`` takes them (host args dropped)."""
    return {p: {ln: [ev[:3] for ev in evs] for ln, evs in lines.items()}
            for p, lines in planes.items()}


def synthetic():
    serving = [
        (T.WINDOW, 0.0, 100 * MS, {}),
        ("bench.sweep", 0.0, 60 * MS, {}),
        ("slot_stream.tier0.refill", 1 * MS, 4 * MS, {}),
        ("slot_stream.tier0.prefill_chunk", 2 * MS, 2 * MS, {"start": 0, "tokens": 8}),
        ("slot_stream.tier0.prepare", 5 * MS, 1 * MS, {}),
        ("slot_stream.tier0.decode", 6 * MS, 20 * MS, {"active": 2, "rows": 10}),
        ("slot_stream.tier0.fetch", 16 * MS, 10 * MS, {}),
        ("slot_stream.tier0.advance", 26 * MS, 4 * MS, {}),
        ("cascade.tier0.vote", 30 * MS, 10 * MS, {"defer": 1}),
        ("bench.submit", 60 * MS, 1 * MS, {}),
    ]
    # a step span on another thread is not the serving loop's
    other = [("slot_stream.tier0.decode", 40 * MS, 50 * MS, {"active": 9, "rows": 99})]
    ops = [
        ("fusion.1", 3 * MS, 1 * MS),
        ("%decode_attention_paged_bkgd.9 bf16[2,4,16,1,128]", 8 * MS, 8 * MS),
        ("fusion.2", 20 * MS, 2 * MS),
        ("copy.3", 35 * MS, 1 * MS),
        ("fusion.4", 50 * MS, 5 * MS),
        ("late", 95 * MS, 10 * MS),  # clipped to the window's end
    ]
    return {"/host:CPU": {"python3": serving, "worker": other},
            "/device:TPU:0": {"XLA Ops": ops}}


def test_idle_split_hand_count():
    planes = synthetic()
    # idle: [0,3] [4,8] [16,20] [22,35] [36,50] [55,95] = 78 ms; innermost
    # spans: none [0,1), refill [1,2), chunk [2,4), refill [4,5), prepare
    # [5,6), decode [6,16), fetch [16,26), advance [26,30), vote [30,40)
    shares = SP.split(planes)
    assert shares == pytest.approx({"admit": 8.0, "dispatch": 2.0, "fetch": 8.0,
                                    "vote": 9.0, "unspanned": 51.0})
    red = T.reduce(plain(planes))
    assert sum(shares.values()) == pytest.approx(layers.idle_share({"trace": red}))


def test_innermost_clips_a_child_to_its_parent():
    pieces = SP.innermost([(0, 10, "a"), (2, 12, "b"), (12, 14, "c")], -1, 15)
    assert pieces == [(-1, 0, None), (0, 2, "a"), (2, 10, "b"), (10, 12, None),
                      (12, 14, "c"), (14, 15, None)]


def test_span_names_and_kinds():
    assert SP.kind("slot_stream.tier1.prefill_chunk") == "admit"
    assert SP.kind("slot_stream.decode") == "dispatch"
    assert SP.kind("slot_stream.tier0.fetch") == "fetch"
    assert SP.kind("cascade.tier0.vote") == "vote"
    assert SP.kind("bench.sweep") is None and SP.kind("obs.anchor") is None
    assert SP.tier_of("slot_stream.tier1.decode") == 1 and SP.tier_of("slot_stream.decode") == 0


def _abc_tiers():
    from bench import spec

    _, config, _ = spec.cell("abc.mixed.backlog", spec.benchmark())
    return config["tiers"]


def test_span_roofline_is_the_call_log_one():
    planes = synthetic()
    red = T.reduce(plain(planes))
    tiers = _abc_tiers()
    # the same call as the harness's log sees it: two slots, 4 + 6 rows
    dlog = SimpleNamespace(decode=[(0, 6.5 * MS, 25 * MS, [4, 6])])
    rec = {"trace": red, "decode_log": dlog, "trace_host": {"t0": 0.0, "t1": 100 * MS},
           "config": {"tiers": tiers}, "device_kind": V5E}
    want = layers.paged_decode_roofline(rec)
    got = SP.decode_roofline(planes, red["kernel_s"], tiers, K.peaks(V5E))
    assert got == pytest.approx(want, rel=1e-12) and got > 0


def test_a_trace_without_step_spans_reads_nothing():
    planes = synthetic()
    planes["/host:CPU"]["python3"] = [ev for ev in planes["/host:CPU"]["python3"]
                                      if SP.kind(ev[0]) is None]
    red = T.reduce(plain(planes))
    assert SP.split(planes) is None
    assert SP.decode_roofline(planes, red["kernel_s"], _abc_tiers(), K.peaks(V5E)) is None
    assert SP.idle_share({"trace": None}, "admit") is None
    assert SP.paged_decode_roofline({"trace": None}) is None


def test_readers_take_the_newest_trace_of_the_run(tmp_path, monkeypatch):
    """A CPU trace of a few spans goes through the harness's path: its
    host args are read, and with no device plane nothing is reported."""
    import jax

    from bench import spec

    monkeypatch.setattr(SP, "TRACE_DIR", str(tmp_path))
    T.start(str(tmp_path))
    with jax.profiler.TraceAnnotation(T.WINDOW):
        with jax.profiler.TraceAnnotation("slot_stream.tier0.decode", active=3, rows=40):
            jax.numpy.ones(4).block_until_ready()
    T.stop()
    planes = SP.planes_of({"trace": {"kernel_s": 1.0}})
    _, _, evs = SP.serving_line(planes)
    dec = [ev for ev in evs if ev[0] == "slot_stream.tier0.decode"]
    assert dec and dec[0][3] == {"active": 3, "rows": 40}
    assert SP.split(planes) is None
    for name in ("idle_share.admit.tput", "idle_share.unspanned.tput",
                 "roofline.paged_decode.spans.tput"):
        assert spec.reader(name)({"trace": None}) is None


DATA = os.path.join(os.path.dirname(__file__), "data", "tpu_trace_spans.json")


@pytest.fixture(scope="module")
def recorded():
    """A slice of a traced run of ``abc.mixed.backlog`` on a TPU v5e: the
    serving thread's step spans with their args, the device's ops and
    programs, and the harness's call log of the decode calls in it (times
    moved onto the profiler's clock through the ``obs.anchor`` event)."""
    with open(DATA) as f:
        return json.load(f)


def test_recorded_slice_idle_split_adds_up(recorded):
    planes = recorded["planes"]
    shares = SP.split(planes)
    idle = layers.idle_share({"trace": T.reduce(plain(planes))})
    assert set(shares) == set(SP.KINDS) and idle > 0
    assert sum(shares.values()) == pytest.approx(idle, abs=0.05)
    assert shares["unspanned"] <= idle / 4


def test_recorded_slice_span_roofline_matches_call_log(recorded):
    from bench import spec

    planes = recorded["planes"]
    red = T.reduce(plain(planes))
    _, config, _ = spec.cell(recorded["workload"], spec.benchmark())
    w0, w1, _ = SP.serving_line(planes)
    rec = {"trace": red, "config": config, "device_kind": V5E,
           "decode_log": SimpleNamespace(decode=[tuple(e) for e in recorded["decode_log"]]),
           "trace_host": {"t0": w0, "t1": w1}}
    want = layers.paged_decode_roofline(rec)
    got = SP.decode_roofline(planes, red["kernel_s"], config["tiers"], K.peaks(V5E))
    assert want > 0 and got == pytest.approx(want, rel=0.02)
