"""Cascade serving CLI: stand up an ABC cascade from the arch registry and
serve a batched synthetic workload, reporting per-tier routing and cost.

  PYTHONPATH=src python -m repro.launch.serve \
      --tiers qwen2.5-3b:2 internlm2-1.8b:1 --reduced --requests 64

Without ``--reduced`` every tier runs at its published widths with random
weights made from ``--seed``; on a TPU backend the served path runs the
Pallas kernels (``kernels.config.default_impl``).  ``chip_smoke.py`` at the
repo root builds its tiers through ``build_tiers`` too.
"""
from __future__ import annotations

import argparse
import functools
from typing import List, Sequence

import jax
import numpy as np

from repro.configs import get_config
from repro.core import ensemble as ens
from repro.core.cascade import TierSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.models.params import unbox
from repro.serve import CascadeServer, CascadeTier


@functools.partial(jax.jit, static_argnums=(0, 1))
def _init_values(cfg, k, rng):
    """Seeded random weights of a k-member ensemble, in one program."""
    return unbox(ens.init_ensemble(cfg, k, rng))[0]


def build_tiers(
    tiers: Sequence[str],
    *,
    reduced: bool = False,
    theta: float = 0.67,
    rule: str = "vote",
    seed: int = 0,
) -> List[CascadeTier]:
    """``arch:k`` per tier, cheapest first -> seeded random-weight tiers.

    Every tier but the last defers on ``rule`` at ``theta``; the last
    answers everything (a one-member last tier uses the confidence rule).
    Each ensemble's weights are made by one program on the default
    device."""
    out = []
    rng = jax.random.PRNGKey(seed)
    for i, t in enumerate(tiers):
        arch, k = t.rsplit(":", 1)
        k = int(k)
        cfg = get_config(arch)
        if reduced:
            cfg = cfg.reduced()
        rng, sub = jax.random.split(rng)
        values = _init_values(cfg, k, sub)
        last = i == len(tiers) - 1
        spec = TierSpec(
            name=arch,
            rule="confidence" if (last and k == 1) else rule,
            theta=-1.0 if last else theta,
            k=k,
            cost=cfg.active_param_count() * k / 1e6,  # MFLOP-ish units
        )
        out.append(CascadeTier(cfg, values, spec))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--tiers", nargs="+", required=True,
        help="arch:k per tier, cheapest first, e.g. qwen2.5-3b:2 command-r-plus-104b:1",
    )
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--theta", type=float, default=0.67)
    ap.add_argument("--rule", default="vote", choices=["vote", "score"])
    ap.add_argument("--mode", default="classify", choices=["classify", "generate"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    tiers = build_tiers(
        args.tiers, reduced=args.reduced, theta=args.theta, rule=args.rule,
        seed=args.seed,
    )
    for i, t in enumerate(tiers):
        print(f"tier {i}: {t.spec.name} k={t.k} cost/ex={t.spec.cost:.1f}")

    server = CascadeServer(tiers)
    vocab = min(t.cfg.vocab_size for t in tiers)
    toks = np.random.default_rng(args.seed).integers(
        0, vocab, (args.requests, args.seq)
    ).astype(np.int32)
    if args.mode == "classify":
        res = server.classify(toks)
    else:
        res = server.generate(toks, max_new_tokens=8)
    fr = server.tier_fractions(res)
    print(f"tier fractions: {np.round(fr, 3).tolist()}")
    print(f"evaluated per tier: {res.evaluated.tolist()}")
    print(f"total cost: {res.cost:.1f}  vs all-top-tier: "
          f"{tiers[-1].spec.cost * args.requests:.1f}")


if __name__ == "__main__":
    main()
