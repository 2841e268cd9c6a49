"""The device's idle time of a traced run, put down to the program's own
step spans.

The serving loop writes host spans into the profiler's trace
(``repro.obs.Observability.span``): per slot stream ``<stream>.refill``,
``.prefill_chunk``, ``.prepare``, ``.decode`` (args ``active``, ``rows``)
holding ``.fetch``, and ``.advance``; per finished slot
``cascade.tier<i>.vote``.  ``split`` takes the device's idle intervals
inside ``bench.window`` (the complement of the union of its ops, as
``bench/tracing.reduce`` finds them) and gives each idle nanosecond to the
innermost program span open on the serving thread (the thread that holds
``bench.window``) at that moment, or to none.  A run of a program without
these spans reads nothing.
"""
from __future__ import annotations

import functools
import os
import re
import warnings
from typing import Dict, List, Optional

from bench import counts as K
from bench import spec as S
from bench import tracing as TRC

# where bench/run.py writes the profiler's trace
TRACE_DIR = os.path.join(S.ROOT, ".bench_trace")

# what each step span's idle time is put down to
KIND = {
    "refill": "admit", "prefill_chunk": "admit", "prepare": "admit",
    "advance": "admit", "decode": "dispatch", "fetch": "fetch",
}
KINDS = ("admit", "dispatch", "fetch", "vote", "unspanned")
_STREAM = re.compile(r"^slot_stream(?:\.tier(\d+))?\.([a-z_]+)$")
_VOTE = re.compile(r"^cascade\.tier\d+\.vote$")


def kind(name: str) -> Optional[str]:
    """What idle time inside host span ``name`` is put down to; None for
    a host event that is no step span."""
    if _VOTE.match(name):
        return "vote"
    m = _STREAM.match(name)
    return KIND.get(m.group(2)) if m else None


def tier_of(name: str) -> int:
    """The tier of a slot stream's span (an engine's stream is tier 0)."""
    return int(_STREAM.match(name).group(1) or 0)


def load(path: str) -> dict:
    """``bench/tracing.load``'s planes, but each host event carries its
    args: ``(name, start_ns, duration_ns, {arg: value})``."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    out: Dict[str, Dict[str, list]] = {}
    with warnings.catch_warnings():
        # the first read of an event's args registers its type, which
        # warns of a missing __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in pd.planes:
            host = plane.name.startswith("/host")
            lines = {}
            for line in plane.lines:
                evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                       + ((dict(e.stats),) if host else ())
                       for e in line.events]
                if evs:
                    lines[line.name] = evs
            if lines:
                out[plane.name] = lines
    return out


def serving_line(planes: dict):
    """(window start, window end, events) of the host thread that holds
    ``bench.window``."""
    for name, lines in planes.items():
        if not name.startswith("/host"):
            continue
        for evs in lines.values():
            for ev in evs:
                if ev[0] == TRC.WINDOW:
                    return ev[1], ev[1] + ev[2], evs
    raise ValueError(f"no {TRC.WINDOW!r} span in the trace")


def innermost(spans, w0: float, w1: float) -> List[tuple]:
    """Disjoint ``(a, b, kind)`` pieces that cover ``[w0, w1)``, each given
    to the innermost of the nested ``(start, end, kind)`` spans (clipped to
    the window) open there, or to None where none is."""
    out, stack, cur = [], [], w0

    def emit(b, k):
        nonlocal cur
        if b > cur:
            out.append((cur, b, k))
            cur = b

    for s, e, k in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            emit(*stack.pop())
        emit(s, stack[-1][1] if stack else None)
        # a child never outlasts its parent
        stack.append((min(e, stack[-1][0]) if stack else e, k))
    while stack:
        emit(*stack.pop())
    emit(w1, None)
    return out


def idle(planes: dict, w0: float, w1: float) -> Optional[List[list]]:
    """Per device, the ``(a, b)`` intervals of the window ``[w0, w1)`` in
    which no op ran, as ``bench/tracing.reduce`` counts them; None without
    a device."""
    devices = [lines["XLA Ops"] for n, lines in planes.items()
               if n.startswith("/device:TPU") and "XLA Ops" in lines]
    if not devices:
        return None
    out = []
    for ops in devices:
        ivs = []
        for ev in ops:
            a, b = TRC._clip(ev[1], ev[1] + ev[2], w0, w1)
            if b > a:
                ivs.append((a, b))
        gaps, prev = [], w0
        for a, b in TRC._union(ivs):
            if a > prev:
                gaps.append((prev, a))
            prev = b
        if w1 > prev:
            gaps.append((prev, w1))
        out.append(gaps)
    return out


def step_spans(evs, w0: float, w1: float) -> List[tuple]:
    """``(start, end, kind)`` of the step spans among the serving thread's
    events ``evs``, clipped to the window ``[w0, w1)``."""
    out = []
    for ev in evs:
        k = kind(ev[0])
        a, b = TRC._clip(ev[1], ev[1] + ev[2], w0, w1)
        if k is not None and b > a:
            out.append((a, b, k))
    return out


def split(planes: dict) -> Optional[dict]:
    """% of the window idle inside each kind of step span (``KINDS``; the
    five add up to ``idle_share``), averaged over the devices; None where
    the trace holds no device or no step span."""
    w0, w1, evs = serving_line(planes)
    spans = step_spans(evs, w0, w1)
    per_device = idle(planes, w0, w1)
    if not spans or per_device is None:
        return None
    pieces = innermost(spans, w0, w1)
    total = dict.fromkeys(KINDS, 0.0)
    for gaps in per_device:
        i = 0
        for a, b, k in pieces:
            # the gaps and the pieces are both sorted and disjoint
            while i < len(gaps) and gaps[i][1] <= a:
                i += 1
            j = i
            while j < len(gaps) and gaps[j][0] < b:
                total[k or "unspanned"] += min(b, gaps[j][1]) - max(a, gaps[j][0])
                j += 1
    n = len(per_device)
    return {k: 100.0 * v / n / (w1 - w0) for k, v in total.items()}


def decode_roofline(planes: dict, kernel_s: float, tiers: list, peak: dict):
    """``bench/layers.paged_decode_roofline`` from the decode spans' args:
    the least time the paged decode calls wholly inside the window need
    at the chip's peaks, over the kernel's device time ``kernel_s``."""
    w0, w1, evs = serving_line(planes)
    need, seen = 0.0, False
    for ev in evs:
        if kind(ev[0]) != "dispatch" or not (w0 <= ev[1] and ev[1] + ev[2] <= w1):
            continue
        args = ev[3] if len(ev) > 3 else {}
        if "rows" not in args:
            continue
        seen = True
        t = tiers[tier_of(ev[0])]
        # paged_decode_call reads only the count and the sum of the lengths
        lens = [args["rows"]] + [0] * (args["active"] - 1)
        fl, by = K.paged_decode_call(t["model"], t["k"], lens)
        need += K.min_seconds(fl, by, peak)
    if not seen or not kernel_s:
        return None
    return 100.0 * need / kernel_s


@functools.lru_cache(maxsize=1)
def _load_latest(path: str, mtime: float) -> dict:
    return load(path)


def planes_of(rec: dict) -> Optional[dict]:
    """The planes (with host args) of the run's trace, read once for all
    the readers; None for a run without a device trace."""
    if rec.get("trace") is None:
        return None
    path = TRC.latest(TRACE_DIR)
    return _load_latest(path, os.path.getmtime(path))


def idle_share(rec: dict, k: str) -> Optional[float]:
    """% of the traced window idle inside step spans of kind ``k``."""
    planes = planes_of(rec)
    if planes is None:
        return None
    shares = split(planes)
    return None if shares is None else shares[k]


def paged_decode_roofline(rec: dict) -> Optional[float]:
    """``roofline.paged_decode.tput`` from the decode spans' args."""
    planes = planes_of(rec)
    if planes is None:
        return None
    return decode_roofline(planes, rec["trace"]["kernel_s"], rec["config"]["tiers"],
                           K.peaks(rec["device_kind"]))
