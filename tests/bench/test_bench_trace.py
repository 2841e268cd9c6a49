"""The reduction from a profiler trace to busy time, kernel time, program
totals and blamed idle gaps, on small hand-made traces and on a slice of a
trace recorded on a TPU v5e."""
import json
import os

import pytest

from bench import layers
from bench import tracing as T

MS = 1e6  # ns


def synthetic():
    host = {"python": [
        (T.WINDOW, 0.0, 100 * MS),
        ("bench.sweep", 0.0, 60 * MS),
        ("bench.submit", 60 * MS, 5 * MS),
        ("bench.wait_arrival", 65 * MS, 35 * MS),
    ]}
    ops = [
        ("fusion.1", 5 * MS, 10 * MS),
        ("%decode_attention_paged_bkgd.9 bf16[2,4,16,1,128]", 20 * MS, 10 * MS),
        ("fusion.2", 25 * MS, 10 * MS),  # overlaps the kernel: counted once in busy
        ("copy.3", 50 * MS, 5 * MS),
        ("late", 95 * MS, 20 * MS),  # clipped to the window's end
    ]
    modules = [
        ("jit_wrapped(11)", 5 * MS, 11 * MS),  # no kernel inside: a prefill
        ("jit_wrapped(22)", 19 * MS, 17 * MS),  # holds the kernel: a decode step
        ("jit_other", 50 * MS, 5 * MS),
    ]
    return {"/host:CPU": host,
            "/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules}}


def test_busy_kernel_and_gaps_hand_count():
    red = T.reduce(synthetic())
    assert red["window_s"] == pytest.approx(0.100)
    # busy: [5,15] + [20,35] + [50,55] + [95,100] = 10 + 15 + 5 + 5 ms
    assert red["busy_s"] == pytest.approx(0.035)
    assert red["kernel_s"] == pytest.approx(0.010) and red["kernel_calls"] == 1
    assert red["modules_with_kernel"] == {"jit_wrapped": pytest.approx(0.017)}
    assert red["modules"]["jit_wrapped"] == pytest.approx(0.028)
    gaps = red["idle_gaps"]
    # longest first: [55,95] mostly waiting for arrivals, [35,50], [0,5], [15,20]
    assert [g[1] for g in gaps] == pytest.approx([0.040, 0.015, 0.005, 0.005])
    assert gaps[0][0] == "host: bench.wait_arrival"
    assert gaps[1][0] == "host: bench.sweep"


def test_layer_readings_from_the_reduction():
    red = T.reduce(synthetic())
    rec = {"trace": red}
    assert layers.idle_share(rec) == pytest.approx(65.0)
    assert layers.prefill_share(rec) == pytest.approx(100 * 11 / 28)


def test_no_device_plane_reads_nothing():
    planes = synthetic()
    del planes["/device:TPU:0"]
    assert T.reduce(planes) is None
    assert layers.idle_share({"trace": None}) is None


DATA = os.path.join(os.path.dirname(__file__), "data", "tpu_trace_slice.json")


def test_recorded_tpu_slice_reduces():
    with open(DATA) as f:
        planes = json.load(f)
    planes = {p: {l: [tuple(e) for e in evs] for l, evs in ls.items()}
              for p, ls in planes.items()}
    red = T.reduce(planes)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["kernel_calls"] > 0 and red["kernel_s"] > 0
    assert 0 < layers.prefill_share({"trace": red}) < 100
