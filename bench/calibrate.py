"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds <s> [--fault <name>]

For each seed, in one process (the programs compile once): the cell's
weights and traffic from that seed, a window of ``--seconds`` at the cell's
own load, and then, over the same sample that a run checks, the widest
gap of the served tokens below the float32 reference's best logit (the
program's reading) and the widest gap of the tokens that the float8
control ranks first (the control's reading).  Both go through the
harness's own ``check.judge``: ``correct`` is the program's verdict,
``control_correct`` the verdict had the control served.  ``--fault``
plants one of ``bench/faults.py``'s faults under the timed path first.
Prints one JSON line per seed; the benchmark's own runs never run the
control.
"""
from __future__ import annotations

import argparse
import builtins
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


def readings(config, mix, seed, seconds, *, control="fp8"):
    """One seed's program and control readings (a dict)."""
    from bench import check as C
    from bench import serving as D

    server = D.build_server(config, seed)
    D.warm_up(server, config, mix)
    run = D.new_run(server, config)
    n_slots = config["serve"]["n_slots"]
    if mix["arrival"] == "closed":
        win = D.closed_loop(run, mix, seed, seconds, n_slots)
    else:
        win = D.open_loop(run, mix, seed, seconds, RUN.DRAIN_S)
    counts = C.outcome_counts(config, win)
    picked = D.sample(win, RUN.SAMPLE_PER_TIER, seed, len(config["tiers"]))
    deferred = D.sample_deferred(win, RUN.SAMPLE_DEFERRED, seed)
    del run, server
    gc.collect()
    g = C.gaps(config, picked, deferred, seed, control=control)
    correct, checks = C.judge(config, counts, g)
    control_correct, _ = C.judge(config, counts, {i: (v[1], None) for i, v in g.items()})
    return {
        "seed": seed,
        "correct": correct,
        "control_correct": control_correct,
        "checks": checks,
        "control_gap": {f"tier{i}": v[1] for i, v in g.items()},
        "served_tokens": {
            f"tier{i}": sum(len(r.output) for r in picked if r.tier == i)
            + (sum(gen.size for _, gen in deferred) if i == 0 else 0)
            for i in g
        },
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default="")
    args = ap.parse_args(argv)
    wl, config, mix = S.cell(args.workload, S.benchmark())
    if RUN.find_devices(wl["chips"], True) is None:
        return 3
    RUN.use_compile_cache()
    if args.fault:
        from bench import faults

        faults.FAULTS[args.fault](builtins.setattr)
    for seed in [int(s) for s in args.seeds.split(",")]:
        print(json.dumps(readings(config, mix, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
