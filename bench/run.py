"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration file and
a traffic mix, both found by name.  The run makes the weights on the
device from ``--seed``, warms up the programs the mix uses (read from the
compile cache at ``.jax_cache/`` in the checkout after the first run),
then drives the program's cascade for ``--seconds`` and prints, as the
last line of stdout, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared for ``correct``
beside its limit (also the last lines of stderr).

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero before anything is timed and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import spec as S  # noqa: E402

DRAIN_S = 120.0  # an open loop follows its arrivals this long past the close
SAMPLE_PER_TIER = 4  # requests per answering tier compared with the reference
SAMPLE_DEFERRED = 2  # requests tier 0 deferred, each member's generation compared
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileClock:
    """Seconds and number of XLA backend compilations, from jax's
    monitoring events."""

    def __init__(self):
        import jax

        self.secs, self.n = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration
            self.n += 1


def find_devices(chips: int, require_tpu: bool):
    """The devices the cell runs on, or None (with a message) if the
    machine lacks them."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        log(f"no TPU: jax found platform {devs[0].platform!r}; refusing to run")
        return None
    if len(devs) < chips:
        log(f"the cell needs {chips} chips, jax found {len(devs)}")
        return None
    return devs[:chips]


def use_compile_cache() -> None:
    """JAX's persistent cache at a fixed directory in the checkout; every
    program is cached, however quickly it compiled."""
    import jax

    jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def quantile(values, q: float) -> float:
    """Nearest-rank quantile; a missing answer reads +inf."""
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


def end_to_end(name: str, win, setup_s: float) -> float:
    if name == "setup_s":
        return setup_s
    if name == "answered_tok_s":
        return sum(len(r.output) for r in win.answered()) / win.seconds
    lat = [
        (win.done_at[rid] - win.sched[rid])
        if rid in win.done_at and not win.requests[rid].truncated else math.inf
        for rid in win.sched if win.sched[rid] - win.t0 < win.seconds
    ]
    q = {"answer_p50_s": 0.50, "answer_p90_s": 0.90}[name]
    v = quantile(lat, q)
    if not math.isfinite(v):
        raise RuntimeError(f"{name}: the quantile falls on an unanswered request")
    return v


def outcome(mix: dict, win):
    """(attempted, failed)."""
    if mix["arrival"] == "closed":
        done = [rid for rid in win.done_at if win.in_window(rid)]
        failed = sum(bool(win.requests[r].truncated) for r in done)
        return len(done), failed
    arrived = list(win.sched)
    failed = sum(1 for r in arrived
                 if r not in win.done_at or win.requests[r].truncated)
    return len(arrived), failed


def main(argv=None, *, require_tpu: bool = True, config=None, mix=None) -> int:
    """One run; returns the exit code.  ``config``/``mix`` replace the cell's
    files (tests run tiny sizes on the CPU this way)."""
    args = parse(argv)
    spec = S.benchmark()
    S.check_names(spec)
    wl, cfg_file, mix_file = S.cell(args.workload, spec)
    config = config or cfg_file
    mix = mix or mix_file
    devs = find_devices(wl["chips"], require_tpu)
    if devs is None:
        return 3

    import jax

    use_compile_cache()
    cc = CompileClock()
    from bench import check as C
    from bench import serving as D
    from bench import tracing as TRC
    from repro.serve.engine import trace_count

    server = D.build_server(config, args.seed)
    D.warm_up(server, config, mix)
    compile_s = cc.secs
    n_compiles0, n_traces0 = cc.n, trace_count()

    run = D.new_run(server, config)
    dlog = None
    trace_state = {}
    on_sweep = None
    if args.trace:
        dlog = D.DecodeLog()
        dlog.install(run)
        t_on = 0.4 * args.seconds
        t_off = t_on + min(4.0, 0.3 * args.seconds)

        def on_sweep(t):
            # the profiler's start and stop stall the loop (writing the
            # trace takes seconds); rates over the window leave that out
            if "ann" not in trace_state and t >= t_on:
                a = D.clock()
                TRC.start(TRACE_DIR)
                trace_state["ann"] = jax.profiler.TraceAnnotation(TRC.WINDOW)
                trace_state["ann"].__enter__()
                trace_state["t0"] = D.clock()
                trace_state["stall_s"] = trace_state["t0"] - a
            elif "t1" not in trace_state and "ann" in trace_state and t >= t_off:
                trace_state["t1"] = D.clock()
                trace_state["ann"].__exit__(None, None, None)
                TRC.stop()
                trace_state["stall_s"] += D.clock() - trace_state["t1"]

    n_slots = config["serve"]["n_slots"]
    if mix["arrival"] == "closed":
        win = D.closed_loop(run, mix, args.seed, args.seconds, n_slots, on_sweep)
    else:
        win = D.open_loop(run, mix, args.seed, args.seconds, DRAIN_S, on_sweep)
    if "ann" in trace_state and "t1" not in trace_state:
        trace_state["t1"] = D.clock()
        trace_state["ann"].__exit__(None, None, None)
        TRC.stop()
    setup_s = win.t0 - T_PROCESS
    window_compiles = cc.n - n_compiles0
    window_traces = trace_count() - n_traces0
    log(f"window: compiles={window_compiles} traces={window_traces} "
        f"sweeps={win.sweeps} lateness_s={win.lateness_s} "
        f"seconds={win.seconds} closed_after_s={win.t_close - win.t0}")
    counters = D.stream_counters(run)
    stats = devs[0].memory_stats() or {}
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
    }
    counts = C.outcome_counts(config, win)
    picked = D.sample(win, SAMPLE_PER_TIER, args.seed, len(config["tiers"]))
    deferred = D.sample_deferred(win, SAMPLE_DEFERRED, args.seed)
    attempted, failed = outcome(mix, win)

    # the program's state goes before the reference runs
    del run, server
    gc.collect()
    tier_gaps = C.gaps(config, picked, deferred, args.seed)
    correct, checks = C.judge(config, counts, tier_gaps)

    result = {"correct": bool(correct), "attempted": attempted, "failed": failed}
    if not args.trace:
        result["metrics"] = {
            m["name"]: {"value": end_to_end(m["name"], win, setup_s), "unit": m["unit"]}
            for m in S.metrics_of(args.workload, spec, "end_to_end")
        }
    else:
        red = None
        if "t1" in trace_state:
            red = TRC.reduce(TRC.load(TRC.latest(TRACE_DIR)))
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
        rec = {
            "config": config, "mix": mix, "window": win, "counters": counters,
            "compile_s": compile_s, "trace": red,
            "decode_log": dlog, "trace_host": trace_state,
            "device_kind": device["kind"], "chips": len(devs),
        }
        result["metrics"] = {}
        for m in S.metrics_of(args.workload, spec, "per_layer"):
            v = S.reader(m["name"])(rec)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    result["device"] = device
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k} {v} limit {lim}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
