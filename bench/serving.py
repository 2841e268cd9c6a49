"""The timed path: the program's continuous cascade, driven from the
harness's own wall-clock loop.

The window drives ``_CascadeRun`` (``serve/cascade_server.py``), the
machinery that ``serve_continuous`` and ``serve_open_loop`` share, through
``submit`` and ``sweep``: each sweep steps every tier's ``SlotStream`` once
on paged pools (chunked prefill, the paged decode kernel, the per-step
fetch of the tokens), then votes on the members' generations and defers
to the next tier.  The harness keeps its own per-request timestamps.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List

import jax
import numpy as np

from bench import traffic as TR
from bench import weights as W

MODEL_KEYS = (
    "family", "n_layers", "d_model", "d_ff", "vocab_size", "n_heads",
    "n_kv_heads", "head_dim", "norm_type", "norm_eps", "rope_theta",
    "tie_embeddings", "mlp_activation", "dtype",
)


def clock() -> float:
    return time.perf_counter()


def annotate(name: str):
    """A host span in the profiler's own trace (a no-op when not tracing)."""
    return jax.profiler.TraceAnnotation(name)


def model_config(name: str, model: dict):
    from repro.configs.base import ModelConfig

    return ModelConfig(name=name, **{k: model[k] for k in MODEL_KEYS})


def markers(tier: dict):
    """(marker ids, their rows' scale) of a tier; no ids where it has none."""
    mk = tier.get("markers")
    if not mk:
        return (), 0.0
    return tuple(range(mk["first"], mk["first"] + mk["count"])), mk["scale"]


def tier_weights(config: dict, i: int, seed: int, members=None):
    """Tier i's stacked weights made from ``seed`` (``members``: which)."""
    t = config["tiers"][i]
    return W.make_tier(t["model"], t["k"], seed, i, *markers(t), members=members)


def build_server(config: dict, seed: int):
    """The cascade under test, with weights made from ``seed``."""
    from repro.core.cascade import TierSpec
    from repro.serve import CascadeServer, CascadeTier

    tiers = []
    for i, t in enumerate(config["tiers"]):
        spec = TierSpec(name=t["name"], rule=t["rule"], theta=t["theta"], k=t["k"])
        tiers.append(CascadeTier(model_config(t["name"], t["model"]),
                                 tier_weights(config, i, seed), spec))
    jax.block_until_ready([t.values for t in tiers])
    return CascadeServer(tiers)


def serve_config(config: dict, obs):
    from repro.serve import ServeConfig

    s = config["serve"]
    # n_pages None: the pool at its dense-equivalent size, so no request
    # can run out of pages
    return ServeConfig(
        n_slots=s["n_slots"], max_seq=s["max_seq"], page_size=s["page_size"],
        n_pages=None, max_chunk=s["max_chunk"], paged=True,
        obs=obs,
    )


def new_run(server, config: dict):
    from repro.obs import Observability
    from repro.serve.cascade_server import _CascadeRun

    ob = Observability(clock=clock)
    return _CascadeRun(server, serve_config(config, ob), ob)


def chunk_buckets(mix: dict, max_chunk: int) -> List[int]:
    """Every prefill chunk size a prompt length of the mix can use."""
    from repro.core.cascade import prompt_chunks

    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    return sorted({c for p in range(lo, hi + 1)
                   for c in prompt_chunks(p - 1, max_chunk)})


def warm_up(server, config: dict, mix: dict) -> None:
    """Run one request through every tier with a prompt that uses each
    chunk bucket of the mix once, so every program the window runs is
    compiled (or read from the cache) before it starts."""
    from repro.serve import Request

    buckets = chunk_buckets(mix, config["serve"]["max_chunk"])
    n = sum(buckets) + 1
    rng = np.random.default_rng(0)
    reqs = [Request(tokens=rng.integers(*mix["easy_ids"], n).astype(np.int32),
                    max_new_tokens=2)]
    if len(config["tiers"]) > 1:
        reqs.append(Request(
            tokens=rng.integers(*mix["hard_ids"], n).astype(np.int32),
            max_new_tokens=2,
        ))
    run = new_run(server, config)
    run.submit(reqs)
    while run.active:
        run.sweep()
    jax.block_until_ready([st.backend.pool_dev for st in run.streams])
    del run
    gc.collect()


@dataclasses.dataclass
class Window:
    """What the harness saw of one window."""

    seconds: float
    t0: float = 0.0
    t_close: float = 0.0  # when the loop stopped (drain included)
    hard: Dict[int, bool] = dataclasses.field(default_factory=dict)
    sched: Dict[int, float] = dataclasses.field(default_factory=dict)
    done_at: Dict[int, float] = dataclasses.field(default_factory=dict)
    requests: Dict[int, object] = dataclasses.field(default_factory=dict)
    lateness_s: float = 0.0
    sweeps: int = 0
    # tier 0's member generations, as its vote saw them (cascades only)
    member_out: Dict[int, np.ndarray] = dataclasses.field(default_factory=dict)

    def in_window(self, rid) -> bool:
        return rid in self.done_at and self.done_at[rid] - self.t0 <= self.seconds

    def answered(self):
        """Requests answered inside the window, in completion order."""
        return [self.requests[rid] for rid in sorted(
            (r for r in self.done_at if self.in_window(r)),
            key=lambda r: self.done_at[r])]


class DecodeLog:
    """Per-call shapes of the program's decode and prefill calls, recorded
    in a traced run: for each call, the tier, the host time, and the
    context length of every active slot (decode) or the chunk's first
    position and length (prefill).  Installed by wrapping the backends'
    methods; it changes nothing they compute."""

    def __init__(self):
        self.decode: List[tuple] = []
        self.prefill: List[tuple] = []

    def install(self, run) -> None:
        for i, st in enumerate(run.streams):
            b = st.backend
            dec, pre = b.decode, b.prefill_chunk

            def decode(tok, pos, st=st, i=i, dec=dec):
                lens = [int(pos[s]) + 1 for s, r in enumerate(st.slot_req)
                        if r is not None]
                t = clock()
                out = dec(tok, pos)
                self.decode.append((i, t, clock(), lens))
                return out

            def prefill_chunk(tokens, slot, start, i=i, pre=pre):
                self.prefill.append((i, clock(), int(start), len(tokens)))
                return pre(tokens, slot, start)

            b.decode, b.prefill_chunk = decode, prefill_chunk


def _keep_member_outputs(run, win: Window) -> None:
    """Record tier 0's member generations of each request in a cascade, as
    handed to its vote (host arrays the vote reads anyway; nothing that
    the program computes changes)."""
    if len(run.streams) < 2:
        return
    finish = run._finish_slot

    def finish_slot(i, r, gen):
        if i == 0:
            win.member_out[r.rid] = np.array(gen)
        return finish(i, r, gen)

    run._finish_slot = finish_slot


def _submit(run, win: Window, item, t_sched: float) -> None:
    from repro.serve import Request

    r = Request(tokens=item.tokens, max_new_tokens=item.max_new_tokens)
    win.hard[r.rid] = item.hard
    win.sched[r.rid] = t_sched
    win.requests[r.rid] = r
    run.submit([r], t0=t_sched)


def _collect(run, win: Window, n_seen: int, now: float) -> list:
    new = run.done[n_seen:]
    for r in new:
        win.done_at[r.rid] = now
    return new


def closed_loop(run, mix: dict, seed: int, seconds: float, n_slots: int,
                on_sweep=None) -> Window:
    """A closed loop: ``outstanding`` requests in the system, a new one
    entering as one is answered, for ``seconds``."""
    win = Window(seconds)
    _keep_member_outputs(run, win)
    gen = TR.requests(mix, seed)
    win.t0 = t0 = clock()
    for _ in range(TR.outstanding(mix, n_slots)):
        _submit(run, win, next(gen), t0)
    n_seen = 0
    while clock() - t0 < seconds:
        with annotate("bench.sweep"):
            run.sweep()
        win.sweeps += 1
        now = clock()
        new = _collect(run, win, n_seen, now)
        n_seen = len(run.done)
        if now - t0 < seconds:
            with annotate("bench.submit"):
                for _ in new:
                    _submit(run, win, next(gen), now)
        if on_sweep is not None:
            on_sweep(now - t0)
    win.t_close = clock()
    return win


def open_loop(run, mix: dict, seed: int, seconds: float, drain_s: float,
              on_sweep=None) -> Window:
    """An open loop: requests arrive at their scheduled times in
    [0, seconds), each followed to its answer (at most ``drain_s`` past the
    window's close)."""
    win = Window(seconds)
    _keep_member_outputs(run, win)
    times = TR.arrival_times(mix, seconds)
    gen = TR.requests(mix, seed, block=len(times))
    items = [next(gen) for _ in times]
    win.t0 = t0 = clock()
    idx, n_seen = 0, 0
    while True:
        now = clock() - t0
        if idx < len(times) and times[idx] <= now:
            with annotate("bench.submit"):
                while idx < len(times) and times[idx] <= now:
                    win.lateness_s = max(win.lateness_s, now - times[idx])
                    _submit(run, win, items[idx], t0 + times[idx])
                    idx += 1
        if run.runnable:
            with annotate("bench.sweep"):
                run.sweep()
            win.sweeps += 1
            _collect(run, win, n_seen, clock())
            n_seen = len(run.done)
        elif idx < len(times):
            with annotate("bench.wait_arrival"):
                time.sleep(max(0.0, times[idx] - (clock() - t0)))
        else:
            break
        if clock() - t0 > seconds + drain_s:
            break
        if on_sweep is not None:
            on_sweep(clock() - t0)
    win.t_close = clock()
    return win


def stream_counters(run) -> dict:
    """The registry's counters and histogram sums/counts after the window."""
    snap = {}
    reg = run.ob.registry
    for name in reg.names():
        m = reg.get(name)
        if hasattr(m, "value"):
            snap[name] = m.value
        if hasattr(m, "sum") and hasattr(m, "count"):
            snap[name + ".sum"] = m.sum
            snap[name + ".count"] = m.count
    return snap


def _longest_and_drawn(items: list, size, n: int, rng) -> list:
    """The item of largest ``size`` and ``n - 1`` more drawn by ``rng``."""
    if not items:
        return []
    items = sorted(items, key=size, reverse=True)
    rest = items[1:]
    return [items[0]] + [rest[i] for i in sorted(rng.permutation(len(rest))[: n - 1])]


def sample(win: Window, n_per_tier: int, seed: int, n_tiers: int) -> list:
    """Requests to check against the reference: per answering tier, the one
    with the most served tokens and ``n_per_tier - 1`` more drawn from the
    seed."""
    rng = np.random.default_rng([seed, 7])
    out = []
    done = win.answered()
    for t in range(n_tiers):
        rs = [r for r in done if r.tier == t and r.output is not None]
        out.extend(_longest_and_drawn(rs, lambda r: (len(r.output), -r.rid),
                                      n_per_tier, rng))
    return out


def sample_deferred(win: Window, n: int, seed: int) -> list:
    """(prompt, member generations) of requests tier 0 deferred in the
    window: the longest and ``n - 1`` more drawn from the seed."""
    rng = np.random.default_rng([seed, 8])
    rids = [rid for rid in win.member_out
            if not (rid in win.done_at and win.requests[rid].tier == 0)]
    picked = _longest_and_drawn(
        rids, lambda rid: (win.member_out[rid].shape[1], -rid), n, rng)
    return [(win.requests[rid].tokens, win.member_out[rid]) for rid in picked]
