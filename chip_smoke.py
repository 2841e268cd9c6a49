"""Chip smoke test: the cascade's main serving path on a TPU, at full width.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # tiers on disjoint chip pairs vs one chip

The cascade is built by ``repro.launch.serve.build_tiers``, the path that
``python -m repro.launch.serve --tiers olmo-1b:2 internlm2-1.8b:1`` takes:
tier 0 is olmo-1b with 2 members and the vote rule, tier 1 internlm2-1.8b
with 1 member and the confidence rule, both at their published widths with
random bf16 weights made from ``--seed``.  Tier 0 has 2 members, not 3: the
dense tier programs of phase (a) hold a transposed copy of their stacked
layer weights as scratch (the member vmap around a layer scan over the
stack), 7.3 GB at k=3, and with 10.8 GB of k=3 weights that does not fit
the chip's 16 GB.  The paged programs of phase (b) read each layer in place.

One chip runs two phases through the Pallas kernels:

(a) classify: ``CascadeServer.classify`` on 16 prompts of 128 tokens (flash
    prefill, the agreement reduce, deferral compaction).  The agreement and
    compaction kernels must equal the jnp path on the same inputs.
(b) serve: ``CascadeServer.serve_continuous`` on 16 requests (chunked
    prefill, the paged decode kernel, the deferral hop).  Every request must
    complete with in-vocabulary tokens, and tier 0's prefill logits, cut
    to its first layer, must match the jnp path within a bf16 tolerance
    (random weights make the full 16 layers chaotic; see
    ``check_prefill_logits``).

The lowered classify and paged-decode steps must contain ``tpu_custom_call``.

``--chips 4`` builds a ('pod', 'data', 'model') = (2, 2, 1) mesh, so tier 0
sits on chips {0, 1} and tier 1 on {2, 3} (``pod_placement``); each slice
runs its programs per chip, the two chips sharing the examples.  It runs
phases (a) and (b) there and again with both tiers on chip 0
(``single_host``), and requires the two runs to agree on every decision and
token, and every tier's weights to sit on its own chips only.  Nothing else
runs under the option, and it runs with the persistent compile cache off
(``repro.launch.compile_cache``: programs over several chips read back from
a warm cache have halted a slice's chips).

Every check raises when it fails: the process then exits non-zero and
prints no result.  Without a TPU it exits non-zero before any work.  The
last line of stdout is one JSON object, ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time

import numpy as np

TIERS = ("olmo-1b:2", "internlm2-1.8b:1")
THETA = 0.67
CLASSIFY_SHAPE = (16, 128)
N_REQUESTS = 16
# chunked prefill consumes all but a prompt's last token (the decode step
# feeds that one) in pow2 chunks: 33/65/129 tokens fill one 32/64/128 chunk,
# so a cold run compiles three prefill programs per tier
PROMPT_LENS = (33, 65, 129)
NEW_TOKENS = (16, 32)
SERVE = dict(n_slots=8, max_seq=256, paged=True)
# tier-0 prefill logits of the tier cut to its first layer, kernels vs jnp
# path.  Both run bf16 weights and activations; their f32 reductions differ
# in order and show as bf16 rounding flips (one ulp is 2**-8 relative).  The
# relative L2 difference must stay within five such ulps.  (A one-ulp nudge
# of the embedding moves these logits by 7.4e-3 on the CPU.)
LOGITS_REL_L2 = 5 * 2.0**-8

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))


def require(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class CompileClock:
    """Seconds XLA spends compiling programs, summed from jax's monitoring
    events (tracing and lowering count as run time: their events nest)."""

    def __init__(self):
        import jax

        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration


def timed(name, clock, fn, *args):
    c0, t0 = clock.secs, time.perf_counter()
    out = fn(*args)
    wall = time.perf_counter() - t0
    comp = clock.secs - c0
    print(f"[{name}] wall_s={wall} compile_s={comp} run_s={wall - comp}",
          flush=True)
    return out


def build(seed, **kw):
    """The cascade's tiers, weights ready on the default device."""
    import jax

    from repro.launch.serve import build_tiers

    tiers = build_tiers(TIERS, theta=THETA, seed=seed, **kw)
    jax.block_until_ready([t.values for t in tiers])
    return tiers


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def classify_tokens(server, seed):
    vocab = min(t.cfg.vocab_size for t in server.tiers)
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, CLASSIFY_SHAPE).astype(np.int32)


def phase_classify(server, seed, parts=1):
    """(a): route the prompts through every tier, as ``parts`` consecutive
    equal batches; returns (pred, tier_of, prompts) over all of them."""
    toks = classify_tokens(server, seed)
    n = len(server.tiers)
    vocab_of = np.array([t.cfg.vocab_size for t in server.tiers])
    preds, tiers = [], []
    for part in np.split(toks, parts):
        res = server.classify(part)
        require(res.pred.shape == (len(part),), f"pred shape {res.pred.shape}")
        require(int(res.tier_counts.sum()) == len(part), f"{res.tier_counts}")
        preds.append(res.pred)
        tiers.append(res.tier_of)
        print(f"[classify] tier_fractions={server.tier_fractions(res).tolist()} "
              f"evaluated={res.evaluated.tolist()}", flush=True)
    pred, tier_of = np.concatenate(preds), np.concatenate(tiers)
    require(((tier_of >= 0) & (tier_of < n)).all(),
            f"unrouted examples: tier_of={tier_of}")
    require(((pred >= 0) & (pred < vocab_of[tier_of])).all(),
            f"prediction outside its tier's vocabulary: {pred}")
    return pred, tier_of, toks


def make_requests(server, seed):
    from repro.serve import Request

    vocab = min(t.cfg.vocab_size for t in server.tiers)
    rng = np.random.default_rng(seed + 1)
    lens = rng.choice(PROMPT_LENS, N_REQUESTS)
    return [
        Request(
            tokens=rng.integers(0, vocab, (int(s),)).astype(np.int32),
            max_new_tokens=int(rng.choice(NEW_TOKENS)),
        )
        for s in lens
    ]


def phase_serve(server, seed):
    """(b): continuous batching over paged pools; returns (tier, output)
    per request, in submission order."""
    from repro.serve import ServeConfig

    reqs = make_requests(server, seed)
    done = server.serve_continuous(reqs, ServeConfig(**SERVE))
    by_rid = {r.rid: r for r in done}
    require(sorted(by_rid) == sorted(r.rid for r in reqs),
            f"{len(done)} of {len(reqs)} requests completed")
    vocab_of = [t.cfg.vocab_size for t in server.tiers]
    out = []
    for q in reqs:
        r = by_rid[q.rid]
        require(r.output is not None and len(r.output) > 0,
                f"request {r.rid}: empty output")
        require(0 <= r.tier < len(vocab_of), f"request {r.rid}: tier {r.tier}")
        require(int(r.output.min()) >= 0
                and int(r.output.max()) < vocab_of[r.tier],
                f"request {r.rid}: token outside tier {r.tier}'s vocabulary")
        out.append((r.tier, np.asarray(r.output)))
    answered = np.bincount([t for t, _ in out], minlength=len(vocab_of))
    print(f"[serve] completed={len(done)} "
          f"tokens_served={sum(len(o) for _, o in out)} "
          f"answered_per_tier={answered.tolist()} "
          f"truncated={sum(r.truncated for r in done)}", flush=True)
    return out


# ---------------------------------------------------------------------------
# kernel checks (one chip)
# ---------------------------------------------------------------------------


def _classify_step(values, tokens, *, cfg, spec):
    """One tier's classify step as one program: prefill logits, the
    deferral rule, and the compaction of what it defers."""
    from repro.core import deferral
    from repro.kernels.compaction import ops as compaction_ops
    from repro.serve.cascade_server import tier_programs

    logits = tier_programs(cfg, 0.0).last_logits(values, {"tokens": tokens})
    out = deferral.apply_rule(spec.rule, logits, spec.theta)
    payload, index_map, count = compaction_ops.compact_tree(
        {"tokens": tokens}, out.defer
    )
    return out.pred, payload, index_map, count


def _paged_decode_text(tier):
    """Lowered text of the tier's paged decode step at the serve shapes."""
    import jax
    import jax.numpy as jnp

    from repro.models import api
    from repro.models.params import unbox
    from repro.serve.cascade_server import tier_paged_programs

    n_slots, max_seq = SERVE["n_slots"], SERVE["max_seq"]
    page_size = 16
    n_pages = n_slots * (max_seq // page_size) + 1
    pool = jax.eval_shape(
        lambda: unbox(api.init_paged_pool(tier.cfg, n_pages, page_size))[0]
    )
    pools = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((tier.k,) + a.shape, a.dtype), pool
    )
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    progs = tier_paged_programs(tier.cfg, float(tier.temperature), tier.mesh)
    return progs.decode_slots.lower(
        tier.values, i32(tier.k, n_slots, 1), pools, i32(n_slots),
        i32(n_slots, max_seq // page_size),
        jax.ShapeDtypeStruct((n_slots, 2), jnp.uint32),
    ).as_text()


def check_classify_kernels(server, toks, seed):
    """Agreement and compaction kernels equal the jnp path on the chip."""
    import jax.numpy as jnp

    from repro.core.cascade import host_fetch
    from repro.kernels import config as kcfg
    from repro.kernels.agreement import ops as agree_ops
    from repro.kernels.compaction import ops as compaction_ops
    from repro.serve.cascade_server import tier_programs

    t0 = server.tiers[0]
    tokens = jnp.asarray(toks)
    logits = tier_programs(t0.cfg, 0.0).last_logits(
        t0.values, {"tokens": tokens}
    )
    require(np.isfinite(host_fetch(logits.astype(jnp.float32))).all(),
            "non-finite tier-0 logits")
    ker = host_fetch(agree_ops.agreement(logits))
    with kcfg.use_impl("xla"):
        ref = host_fetch(agree_ops.agreement(logits))
    require(np.array_equal(ker["pred"], ref["pred"]), "agreement pred")
    require(np.array_equal(ker["vote_frac"], ref["vote_frac"]),
            "agreement vote_frac")
    score_err = float(np.abs(ker["mean_score"] - ref["mean_score"]).max())
    print(f"[agreement] pred and vote_frac equal to xla; "
          f"mean_score max_abs_diff={score_err}", flush=True)

    B = len(toks)
    rng = np.random.default_rng(seed + 2)
    h = rng.standard_normal((B, t0.cfg.d_model), dtype=np.float32)
    payload = {
        "tokens": tokens,
        "h": jnp.asarray(h),
        "h_bf16": jnp.asarray(h).astype(jnp.bfloat16),
    }
    vote_defer = ker["vote_frac"] <= t0.spec.theta
    for name, mask in (("seeded", rng.random(B) < 0.5), ("vote", vote_defer)):
        mask = jnp.asarray(mask)
        got = host_fetch(compaction_ops.compact_tree(payload, mask))
        with kcfg.use_impl("xla"):
            want = host_fetch(compaction_ops.compact_tree(payload, mask))
        require(np.array_equal(got[1], want[1]), f"compaction index map ({name})")
        require(int(got[2]) == int(want[2]), f"compaction count ({name})")
        for k in payload:
            require(np.array_equal(np.asarray(got[0][k], np.float32),
                                   np.asarray(want[0][k], np.float32)),
                    f"compaction payload {k!r} ({name})")
        print(f"[compaction] {name} mask: count={int(got[2])}, index map "
              "and payload equal to xla", flush=True)



def check_lowered(server, toks):
    """The lowered classify step (flash prefill, agreement, compaction) and
    every tier's paged decode step hold TPU kernels."""
    import jax
    import jax.numpy as jnp

    t0 = server.tiers[0]
    step = functools.partial(_classify_step, cfg=t0.cfg, spec=t0.spec)
    text = jax.jit(step).lower(t0.values, jnp.asarray(toks)).as_text()
    n_calls = text.count("tpu_custom_call")
    require(n_calls >= 3, f"classify step holds {n_calls} tpu_custom_call(s)")
    print(f"[lowered] classify step: {n_calls} tpu_custom_call", flush=True)
    for i, tier in enumerate(server.tiers):
        n_calls = _paged_decode_text(tier).count("tpu_custom_call")
        require(n_calls >= 1, f"tier {i} paged decode step has no kernel")
        print(f"[lowered] tier {i} paged decode step: {n_calls} "
              "tpu_custom_call", flush=True)


def _rel_l2(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def check_prefill_logits(server, toks):
    """Tier-0 prefill logits through the kernels against the jnp path.

    With random weights the tier is chaotic: a one-ulp nudge of about half
    its bf16 embedding moves the last-position logits by O(1) at full
    depth, as far as two unrelated logit vectors.  Full-depth logits of two
    bf16 paths that round differently therefore cannot agree within any
    bf16 tolerance; they are printed with that nudge as their noise floor.
    The bound LOGITS_REL_L2 is checked on the tier cut to its first layer
    (same widths, the tier's own embedding, layer-0 weights and head),
    where one layer's kernels make the whole difference."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.core import ensemble as ens
    from repro.core.cascade import host_fetch
    from repro.kernels import config as kcfg

    t0 = server.tiers[0]
    batch = {"tokens": jnp.asarray(toks)}
    impl = kcfg.get_impl()

    def logits(cfg, impl, *values):
        with kcfg.use_impl(impl):
            fn = jax.jit(functools.partial(ens.ensemble_last_logits, cfg=cfg))
            outs = [fn(v, batch).astype(jnp.float32) for v in values]
        outs = host_fetch(outs)
        require(all(np.isfinite(o).all() for o in outs), "non-finite logits")
        return outs

    embed = t0.values["embed"]
    nudged = dict(t0.values, embed=(embed.astype(jnp.float32)
                                    * (1 + 2.0**-8)).astype(embed.dtype))
    (ker,) = logits(t0.cfg, impl, t0.values)
    ref, ref_nudged = logits(t0.cfg, "xla", t0.values, nudged)
    print(f"[prefill] full depth ({t0.cfg.n_layers} layers), not bounded: "
          f"{impl} vs xla rel_l2={_rel_l2(ker, ref)}; "
          f"xla one-ulp embedding nudge rel_l2={_rel_l2(ref_nudged, ref)}",
          flush=True)

    cfg1 = dataclasses.replace(t0.cfg, n_layers=1, name=f"{t0.cfg.name}-layer0")
    values1 = dict(t0.values,
                   layers=jax.tree.map(lambda a: a[:, :1], t0.values["layers"]))
    (ker1,) = logits(cfg1, impl, values1)
    (ref1,) = logits(cfg1, "xla", values1)
    rel = _rel_l2(ker1, ref1)
    top1 = float((ker1.argmax(-1) == ref1.argmax(-1)).mean())
    print(f"[prefill] first layer: {impl} vs xla rel_l2={rel} "
          f"(limit {LOGITS_REL_L2}) "
          f"max_abs_diff={float(np.abs(ker1 - ref1).max())} "
          f"top1_agreement={top1}", flush=True)
    require(rel <= LOGITS_REL_L2, f"first-layer prefill logits rel_l2 {rel}")


# ---------------------------------------------------------------------------
# runs
# ---------------------------------------------------------------------------


def run_one_chip(make_tiers, seed, clock, *, lowered=True):
    """Phases (a) and (b) with their kernel checks; ``lowered`` adds the
    custom-call checks, which only a TPU lowering can pass."""
    from repro.serve import CascadeServer

    server = CascadeServer(timed("build", clock, make_tiers))
    _, _, toks = timed("classify", clock, phase_classify, server, seed)
    timed("classify_checks", clock, check_classify_kernels, server, toks, seed)
    timed("serve", clock, phase_serve, server, seed)
    timed("prefill_check", clock, check_prefill_logits, server, toks)
    if lowered:
        timed("lowered_checks", clock, check_lowered, server, toks)


def run_four_chips(make_tiers, devices, seed, clock):
    """Phases (a) and (b) with the tiers on disjoint chip pairs, then with
    both on one chip; the two must agree."""
    import jax
    from jax.sharding import Mesh

    from repro.serve import CascadeServer
    from repro.serve.placement import hosts_disjoint, pod_placement, single_host

    require(len(devices) >= 4, f"--chips 4 needs 4 devices, found {len(devices)}")
    mesh = Mesh(np.array(devices[:4]).reshape(2, 2, 1), ("pod", "data", "model"))
    placement = pod_placement(mesh, 2)
    require(hosts_disjoint(placement), "pod slices share devices")
    # placing copies the weights onto each slice; the unplaced originals,
    # all on chip 0, are dropped once the server holds the copies
    pod = CascadeServer(timed("build", clock, make_tiers), placement=placement)
    for i, (t, host) in enumerate(zip(pod.tiers, placement.hosts)):
        devs = {d for leaf in jax.tree.leaves(t.values) for d in leaf.devices()}
        require(devs and devs <= host.devices(),
                f"tier {i} weights on {sorted(d.id for d in devs)}, its host "
                f"holds {sorted(d.id for d in host.devices())}")
        print(f"[placement] tier {i} weights on chips "
              f"{sorted(d.id for d in devs)}", flush=True)
    pred_pod, tier_pod, _ = timed("classify_pod", clock, phase_classify, pod,
                                  seed)
    out_pod = timed("serve_pod", clock, phase_serve, pod, seed)
    # free the placed weights before chip 0 takes both tiers again: a
    # served run leaves reference cycles, which only the collector breaks
    del pod
    gc.collect()
    stats = devices[0].memory_stats() or {}
    print(f"[memory] chip {devices[0].id} bytes_in_use after the pod run="
          f"{stats.get('bytes_in_use')}", flush=True)

    one = CascadeServer(timed("build", clock, make_tiers),
                        placement=single_host(2))
    # each chip of a slice classifies its share of the batch ('data' splits
    # the examples), so one chip classifies each share as its own batch: a
    # full-depth random-weight tier turns any rounding difference (another
    # batch shape tiles its matmuls otherwise) into other decisions
    n_data = mesh.shape["data"]
    pred_one, tier_one, _ = timed("classify_one", clock, phase_classify, one,
                                  seed, n_data)
    out_one = timed("serve_one", clock, phase_serve, one, seed)

    require(np.array_equal(tier_pod, tier_one),
            "classify: tiers differ between placements")
    require(np.array_equal(pred_pod, pred_one),
            "classify: predictions differ between placements")
    for i, ((tp, op), (to, oo)) in enumerate(zip(out_pod, out_one)):
        require(tp == to and np.array_equal(op, oo),
                f"serve: request {i} differs between placements")
    print(f"[compare] pod placement equals single host: "
          f"{len(pred_one)} decisions, {len(out_one)} generations",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Serve a full-width two-tier cascade on a TPU."
    )
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.kernels import config as kcfg
    from repro.launch.compile_cache import enable_compile_cache

    platform = jax.default_backend()
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU backend; JAX found {platform!r} "
              f"({jax.devices()[0].device_kind})", file=sys.stderr)
        return 1
    cache = enable_compile_cache(several_devices=args.chips > 1)
    require(kcfg.get_impl() == "pallas",
            f"served kernel impl is {kcfg.get_impl()!r} on a TPU")
    devices = jax.devices()
    dev = devices[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} impl={kcfg.get_impl()} compile_cache={cache}",
          flush=True)

    clock = CompileClock()
    t0 = time.perf_counter()
    make_tiers = functools.partial(build, args.seed)
    if args.chips == 4:
        run_four_chips(make_tiers, devices, args.seed, clock)
        used = devices[:4]
    else:
        run_one_chip(make_tiers, args.seed, clock)
        used = devices[:1]
    wall = time.perf_counter() - t0
    for d in used:
        stats = d.memory_stats() or {}
        print(f"[memory] chip {d.id} peak_bytes_in_use="
              f"{stats.get('peak_bytes_in_use')} "
              f"bytes_limit={stats.get('bytes_limit')}", flush=True)
    print(f"[total] wall_s={wall} compile_s={clock.secs} "
          f"run_s={wall - clock.secs}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
