"""Readings shared by the per-layer metric readers in ``bench/metrics/``.

Each reader takes the run's record and returns a number, or None when the
run holds nothing for it to read (the harness then leaves the metric out).
"""
from __future__ import annotations

from bench import counts as K


def defer_share(rec: dict, tier: int):
    """% of the requests tier ``tier`` decided in the window that it deferred."""
    if tier >= len(rec["config"]["tiers"]) - 1:
        return None
    c = rec["counters"]
    d = c.get(f"cascade.tier{tier}.deferred", 0)
    a = c.get(f"cascade.tier{tier}.answered", 0)
    return 100.0 * d / (a + d) if a + d else None


def decode_ms(rec: dict, tier: int):
    """Mean host time of a decode step, dispatch to tokens on the host."""
    c = rec["counters"]
    n = c.get(f"slot_stream.tier{tier}.decode.dispatch_s.count", 0)
    return 1e3 * c[f"slot_stream.tier{tier}.decode.dispatch_s.sum"] / n if n else None


def idle_share(rec: dict):
    """% of the traced window in which no operation ran on the device."""
    red = rec["trace"]
    if red is None:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def window_seconds(rec: dict) -> float:
    """From the window's start to the loop's stop (what the call log
    covers), less the time the profiler's start and stop held the loop."""
    win = rec["window"]
    return win.t_close - win.t0 - rec.get("trace_host", {}).get("stall_s", 0.0)


def decoded_tok_s(rec: dict):
    """Tokens the decode steps of every tier produced, per second of the
    window: one per active slot per step (from the traced run's call log)."""
    dlog = rec["decode_log"]
    if dlog is None:
        return None
    return sum(len(lens) for _, _, _, lens in dlog.decode) / window_seconds(rec)


def window_flops(rec: dict):
    """Forward FLOPs of every token every member of every tier processed in
    the window: prefill chunks and active decode rows, matmuls plus
    attention over each token's context (from the traced run's call log)."""
    dlog = rec["decode_log"]
    if dlog is None:
        return None
    tiers = rec["config"]["tiers"]
    total = 0.0
    for i, t, s, n in dlog.prefill:
        m = tiers[i]["model"]
        total += tiers[i]["k"] * K.prefill_chunk_flops(m, s, n)
    for i, _, _, lens in dlog.decode:
        m = tiers[i]["model"]
        per_tok = K.token_flops(m, head=True)
        total += tiers[i]["k"] * sum(per_tok + K.attention_flops(m, c) for c in lens)
    return total


def paged_decode_roofline(rec: dict):
    """% of the paged decode kernel's device time that its calls in the
    traced slice would need at the chip's peaks."""
    red, dlog, th = rec["trace"], rec["decode_log"], rec["trace_host"]
    if red is None or dlog is None or not red["kernel_s"]:
        return None
    peak = K.peaks(rec["device_kind"])
    tiers = rec["config"]["tiers"]
    need = 0.0
    for i, t_a, t_b, lens in dlog.decode:
        if th["t0"] <= t_a and t_b <= th["t1"] and lens:
            fl, by = K.paged_decode_call(tiers[i]["model"], tiers[i]["k"], lens)
            need += K.min_seconds(fl, by, peak)
    return 100.0 * need / red["kernel_s"]


def prefill_share(rec: dict):
    """% of the served programs' device time spent outside decode steps.
    The served programs share a name; those runs of it that hold the paged
    decode kernel are decode steps, the rest are prefill chunks."""
    red = rec["trace"]
    if red is None or not red["modules_with_kernel"]:
        return None
    dec = sum(red["modules_with_kernel"].values())
    total = sum(red["modules"][n] for n in red["modules_with_kernel"])
    return 100.0 * (total - dec) / total
