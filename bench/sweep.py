"""Sizing sweeps for a cell, run once when a cell is defined, on the chip.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --slots 4,6,8
    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 1,2,3

``--slots`` serves the cell's traffic closed-loop with each slot count in
turn (ascending, one process, so the peak HBM read after each is that
count's) and prints answered tokens/s and peak HBM.  ``--rates`` serves an
open-loop cell at each arrival rate and prints the answer latencies and
how many requests were still unanswered when the window closed: the knee
is the highest rate whose backlog does not grow.  One JSON line per point.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run as RUN  # noqa: E402
from bench import spec as S  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--slots", default="")
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    wl, config, mix = S.cell(args.workload, S.benchmark())
    devs = RUN.find_devices(wl["chips"], True)
    if devs is None:
        return 3
    RUN.use_compile_cache()
    from bench import serving as D

    server = D.build_server(config, args.seed)
    points = []
    for n in [int(x) for x in args.slots.split(",") if x]:
        cfg = copy.deepcopy(config)
        cfg["serve"]["n_slots"] = n
        m = dict(mix, arrival="closed")
        try:
            D.warm_up(server, cfg, m)
            run = D.new_run(server, cfg)
            win = D.closed_loop(run, m, args.seed, args.seconds, n)
            c = D.stream_counters(run)
            del run
            gc.collect()
        except Exception as e:  # an allocation the chip cannot hold
            print(json.dumps({"n_slots": n, "error": repr(e)[:400]}), flush=True)
            break
        done = win.answered()
        points.append({
            "n_slots": n,
            "answered_tok_s": sum(len(r.output) for r in done) / win.seconds,
            "answered": len(done),
            "forced": sum(v for k, v in c.items() if k.endswith("forced_completions")),
            "decode_ms": {k[:-4]: 1e3 * c[k] / max(1, c[k[:-4] + ".count"])
                          for k in c if k.endswith("decode.dispatch_s.sum")},
            "prefill_dispatch_ms": {k[:-4]: 1e3 * c[k] / max(1, c[k[:-4] + ".count"])
                                    for k in c if k.endswith("prefill_dispatch_s.sum")},
            "sweeps": win.sweeps,
            "deferred": c.get("cascade.tier0.deferred"),
            "peak_bytes": devs[0].memory_stats()["peak_bytes_in_use"],
        })
        print(json.dumps(points[-1]), flush=True)
    for rate in [float(x) for x in args.rates.split(",") if x]:
        m = dict(mix, rate_per_s=rate)
        D.warm_up(server, config, m)
        run = D.new_run(server, config)
        win = D.open_loop(run, m, args.seed, args.seconds, RUN.DRAIN_S)
        del run
        gc.collect()
        lat = sorted(win.done_at[r] - win.sched[r] for r in win.done_at)
        open_at_close = sum(1 for r in win.sched
                            if win.done_at.get(r, 1e30) > win.t0 + win.seconds)
        point = {
            "rate_per_s": rate, "arrived": len(win.sched), "answered": len(lat),
            "unanswered_at_close": open_at_close,
            "p50_s": RUN.quantile(lat, 0.5) if lat else None,
            "p90_s": RUN.quantile(lat, 0.9) if lat else None,
            "lateness_s": win.lateness_s,
            "drain_s": win.t_close - win.t0 - win.seconds,
        }
        print(json.dumps(point), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
