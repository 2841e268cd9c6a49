"""The profiler trace of a traced run, and its reduction to numbers.

The traced slice of the window runs under ``jax.profiler`` (Python tracer
off, host annotations on).  ``load`` turns the ``.xplane.pb`` into plain
event lists, and ``reduce`` turns those into: the traced window, the time
in which some operation ran on the device (the union of the op intervals),
the device time per op name and per program, the paged decode kernel's
time, and the longest idle gaps, each blamed on the harness's own host
span (``bench.sweep``, ``bench.submit``, ``bench.wait_arrival``) that
covers most of it.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

import jax

WINDOW = "bench.window"
HOST_SPANS = ("bench.sweep", "bench.submit", "bench.wait_arrival")
# the paged decode Pallas kernel: its op events carry the HLO instruction's
# text, which names the kernel's jitted wrapper
KERNEL = "decode_attention_paged_bkgd"


def start(directory: str) -> None:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(directory, profiler_options=opts)


def stop() -> None:
    jax.profiler.stop_trace()


def latest(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


Event = Tuple[str, float, float]  # name, start_ns, duration_ns


def load(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """{plane name: {line name: [(name, start_ns, duration_ns), ...]}}."""
    pd = jax.profiler.ProfileData.from_file(path)
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                   for e in line.events]
            if evs:
                lines[line.name] = evs
        if lines:
            out[plane.name] = lines
    return out


def _union(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _clip(a, b, w0, w1):
    return max(a, w0), min(b, w1)


def short(name: str) -> str:
    """An op's HLO text cut to its instruction name and the start of its
    result shape, layouts dropped (``%while.5 (s32[], bf16[2,6,1,2048], ...``)."""
    if " = " not in name:
        return name[:160]
    head, rest = name.split(" = ", 1)
    shape = re.sub(r"\{[^}]*\}", "", rest)
    shape = shape.split(")", 1)[0] + ")" if shape.startswith("(") else shape.split(" ", 1)[0]
    return f"{head} {shape}"[:96]


def program(name: str) -> str:
    """A program's name without its fingerprint: ``jit_f(123)`` -> ``jit_f``."""
    return name.split("(", 1)[0]


def reduce(planes: Dict[str, Dict[str, List[Event]]], top: int = 10) -> dict:
    """Busy and idle time, op and program totals, kernel time and the
    longest idle gaps of the traced window, averaged over the devices;
    None where the trace holds no device plane (a run off the chip)."""
    host = [ev for name, lines in planes.items() if name.startswith("/host")
            for evs in lines.values() for ev in evs]
    win = [ev for ev in host if ev[0] == WINDOW]
    if not win:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    w0 = win[0][1]
    w1 = w0 + win[0][2]
    devices = {n: l for n, l in planes.items() if n.startswith("/device:TPU")
               and "XLA Ops" in l}
    if not devices:
        return None
    ops, modules = defaultdict(float), defaultdict(float)
    kernel_s, kernel_calls, busy_s = 0.0, 0, 0.0
    gaps: List[Tuple[float, float]] = []
    module_kernel = defaultdict(float)  # program -> time of modules that ran the kernel
    for lines in devices.values():
        ivs = []
        for name, s, d in lines["XLA Ops"]:
            a, b = _clip(s, s + d, w0, w1)
            if b <= a:
                continue
            ivs.append((a, b))
            ops[short(name)] += (b - a) / 1e9
            if KERNEL in name:
                kernel_s += (b - a) / 1e9
                kernel_calls += 1
        merged = _union(ivs)
        busy_s += sum(b - a for a, b in merged) / 1e9
        prev = w0
        for a, b in merged:
            if a > prev:
                gaps.append((prev, a))
            prev = b
        if w1 > prev:
            gaps.append((prev, w1))
        kstarts = sorted(s for name, s, d in lines["XLA Ops"] if KERNEL in name)
        for name, s, d in lines.get("XLA Modules", []):
            a, b = _clip(s, s + d, w0, w1)
            if b <= a:
                continue
            modules[program(name)] += (b - a) / 1e9
            if _any_in(kstarts, s, s + d):
                module_kernel[program(name)] += (b - a) / 1e9
    n = len(devices)
    spans = [ev for ev in host if ev[0] in HOST_SPANS]
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_blame(a, b, spans), (b - a) / 1e9] for a, b in gaps[:top]]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_s / n,
        "devices": n,
        "kernel_s": kernel_s / n,
        "kernel_calls": kernel_calls,
        "ops": dict(ops),
        "modules": dict(modules),
        "modules_with_kernel": dict(module_kernel),
        "device_ops": [[k, v / n] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": idle,
    }


def _any_in(sorted_starts, a, b) -> bool:
    import bisect

    i = bisect.bisect_left(sorted_starts, a)
    return i < len(sorted_starts) and sorted_starts[i] < b


def _blame(a: float, b: float, spans) -> str:
    """The host span that overlaps the gap [a, b) most."""
    best, best_ov = "host: none", 0.0
    for name, s, d in spans:
        ov = min(b, s + d) - max(a, s)
        if ov > best_ov:
            best, best_ov = "host: " + name, ov
    return best
