"""Whether what the timed path produced is correct.

Compared, once the window has closed and the program's state is freed:

* ``vote_mismatch``: requests whose answering tier is not the one their
  prompt calls for (in a cascade, every easy request is answered at tier 0
  and every hard one deferred).  Limit 0.
* ``forced``: requests force-completed or truncated (a slot or pool that
  ran out).  Limit 0.
* ``gap.tier<i>``: over a sample of the requests tier i answered, drawn
  from the seed and holding the longest, the widest gap by which a served
  token's logit lies below the best logit of the plain reference
  (``bench/reference.py``, float32), teacher-forced over the prompt and
  the served tokens.  At tier 0 of a cascade the sample also holds
  requests that tier 0 deferred: each member's generation is compared
  with the reference of that member, so tier 0's prefill and decode are
  checked where it answers nothing.  Greedy decoding serves the argmax,
  so a sound run reads only rounding here.  The limit is per configuration
  (``limits`` in its file), set from readings of sound runs and of the
  float8 control (PERF.md).
"""
from __future__ import annotations

import gc

from bench import reference as R
from bench import serving as D
from bench import weights as W


def outcome_counts(config: dict, win) -> dict:
    cascade = len(config["tiers"]) > 1
    mismatch = forced = 0
    for rid in win.done_at:
        r = win.requests[rid]
        forced += int(bool(r.truncated))
        if cascade:
            want_defer = win.hard[rid]
            mismatch += int((r.tier == 0) == want_defer)
    return {"vote_mismatch": mismatch, "forced": forced}


def reference_weights(config: dict, i: int, seed: int, e: int = 0):
    """Member e of tier i, made anew from the seed by the benchmark."""
    return W.member(D.tier_weights(config, i, seed, members=(e,)), 0)


def gaps(config: dict, picked, deferred, seed: int, control=None) -> dict:
    """Per tier: (widest served gap, widest control gap or None) over the
    picked requests that tier answered and, at tier 0, every member's
    generation of the ``deferred`` (prompt, (k, n) generations) pairs."""
    out = {}
    pad = config["serve"]["max_seq"]
    for i, t in enumerate(config["tiers"]):
        by_member = {0: [(r.tokens, r.output) for r in picked if r.tier == i]}
        if i == 0:
            for prompt, gen in deferred:
                for e in range(t["k"]):
                    by_member.setdefault(e, []).append((prompt, gen[e]))
        if not any(by_member.values()):
            continue
        g = c = 0.0
        for e, seqs in sorted(by_member.items()):
            if not seqs:
                continue
            w = reference_weights(config, i, seed, e)
            for prompt, served in seqs:
                sg, cg = R.served_gaps(w, prompt, served, t["model"], pad_to=pad,
                                       control=control)
                g = max(g, sg)
                c = max(c, cg) if control else None
            del w
            gc.collect()
        out[i] = (g, c)
    return out


def judge(config: dict, counts: dict, tier_gaps: dict):
    """(correct, checks): each number compared beside its limit."""
    checks = {
        "vote_mismatch": [counts["vote_mismatch"], 0],
        "forced": [counts["forced"], 0],
    }
    for i, (g, _) in sorted(tier_gaps.items()):
        checks[f"gap.tier{i}"] = [g, config["limits"][f"gap.tier{i}"]]
    ok = all(v <= lim for v, lim in checks.values())
    ok = ok and len(tier_gaps) > 0
    return ok, checks

