"""CascadeServer: ABC as a first-class serving runtime feature.

Tiers hold ensembles (stacked weights, vmapped members).  Three modes:

* ``classify`` — each tier's ensemble produces last-token logits; the
  agreement rule (Eq. 3/4) selects or defers; deferred examples are
  compacted and re-batched for the next tier (host routing — the form whose
  measured cost reproduces Prop 4.1.2).

* ``generate`` — black-box flavor (§5.2.3): every member of a tier
  generates in ONE vmapped XLA program per decode step (stacked weights,
  the paper's ρ=1 parallel execution); agreement is exact-match voting over
  stable digests of the generated sequences (Eq. 3 with vote_rule_from_preds).

* ``serve_continuous`` — cascade-aware continuous batching: each tier runs
  a ``SlotStream`` (serve/slot_stream.py — the SAME slot state machine the
  single-model engine drives at E=1, here at E=k over stacked-ensemble
  programs, with chunked-prefill admission and constant-state slot reset);
  a slot that finishes votes on its member generations, and freed slots
  admit work from the tier's queue — which is fed live by the *previous*
  tier's deferrals (tier streams are stepped round-robin, so tier i+1
  starts while tier i is still decoding).  All families serve: attention
  tiers rely on the per-slot pos mask, SSM/RWKV/hybrid tiers on the
  admitted slot's state leaves being zeroed.

Compile-once discipline: all jitted programs live in a module-level cache
keyed by (config, temperature) — building a new ``CascadeTier`` or calling
``classify``/``generate`` repeatedly reuses the same programs, and batch
shapes are padded to power-of-two buckets so tier transitions re-enter the
jit cache (``repro.serve.engine.trace_count`` asserts this in the tests).

Cost accounting per tier uses the TierSpec cost units (FLOPs, $/Mtok,
GPU-$/h, comm-delay), so the same server drives all three §5.2 scenarios.
"""
from __future__ import annotations

import dataclasses
import functools
import inspect
import zlib
from types import SimpleNamespace
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import deferral, ensemble as ens
from repro.core.cascade import (
    CascadeResult,
    TierSpec,
    cascade_apply_routed,
    host_fetch,
)
from repro.models import api
from repro.obs import Observability, UNIT_BUCKETS
from repro.serve.batching import Request
from repro.serve.config import UNSET, ServeConfig, resolve_serve_config
from repro.serve.engine import _counted, grow_cache
from repro.serve.slot_stream import SlotStream, TierBackend
from repro.serve.speculative import verify_sampler
from repro.serve.workload import VirtualClock, Workload


# ---------------------------------------------------------------------------
# stable canonicalization of generations (black-box voting)
# ---------------------------------------------------------------------------


def stable_digest(tokens) -> int:
    """PYTHONHASHSEED-independent canonical id for a token sequence.

    ``hash(bytes)`` is salted per process, which made identical member
    generations vote differently across runs; crc32 over the little-endian
    int32 encoding is deterministic everywhere.  Masked to 30 bits so every
    digest stays strictly below ``vote_rule_from_preds``'s 2**30
    not-a-candidate sentinel (a 31-bit digest could BE the sentinel and
    corrupt the majority-id tie-break)."""
    row = np.ascontiguousarray(
        np.asarray(host_fetch(tokens), np.int32)
    ).astype("<i4")
    return zlib.crc32(row.tobytes()) & 0x3FFFFFFF


def digest_generations(out: np.ndarray) -> np.ndarray:
    """(E, B, T) member generations -> (E, B) int32 canonical answer ids."""
    E, B = out.shape[:2]
    # abclint: disable=ABC203(digest matrix is a host list comprehension of ints)
    return np.asarray(
        [[stable_digest(out[e, b]) for b in range(B)] for e in range(E)],
        np.int32,
    )


# ---------------------------------------------------------------------------
# compile-once ensemble programs
# ---------------------------------------------------------------------------


def _slot_sampler(temperature: float):
    """Per-slot, per-position, per-member sampling for continuous batching:
    token = categorical(fold_in(fold_in(slot_key, pos), e)).  The slot's
    key is set once at admission, so a slot's sampled trajectory is a pure
    function of its occupant and position — bitwise invariant to which
    other slots share its decode dispatches (serial, blocking, or
    overlapped transport all see the same votes).  Greedy tiers argmax."""

    def sample(logits, slot_keys, pos):  # (E, B, V), (B, 2), (B,)
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        E = logits.shape[0]

        def one(key, p, ls):  # (2,), (), (E, V)
            kp = jax.random.fold_in(key, p)
            return jax.vmap(
                lambda e, l: jax.random.categorical(
                    jax.random.fold_in(kp, e), l / temperature
                )
            )(jnp.arange(E), ls)

        return jax.vmap(one, in_axes=(0, 0, 1), out_axes=1)(
            slot_keys, pos, logits
        ).astype(jnp.int32)

    return sample


# Where the leading axes of the tier programs' arguments and results go on
# a placed tier's pod slice, by name: the stacked member axis splits over the
# slice's 'pod' axis (as ``place_tier_values`` lays the weights out), the
# example axis over 'data' (as ``ShardedDevicePutTransport`` lands a
# deferral payload).  Every other name is replicated on each chip.
_MEMBER, _EXAMPLE = "member", "example"
_LEADING = {
    "values": (_MEMBER,),
    "caches": (_MEMBER,),
    "pools": (_MEMBER,),
    "tok": (_MEMBER,),
    "examples": (_EXAMPLE,),
    "logits": (_MEMBER, _EXAMPLE),
}


def _per_device(fn, out, *, mesh, temperature):
    """``fn`` as one per-device program over a placed tier's pod slice.

    A jit over several devices cannot partition a Pallas kernel, so on a
    slice of several devices ``fn`` runs under ``shard_map`` whatever the
    kernel impl; on one device (or unplaced) it runs as is.  ``fn``'s
    parameter names and the result names ``out`` (one name, or a tuple)
    place each operand through ``_LEADING``.  An axis that does not divide
    its mesh axis stays replicated.  Members split over several devices
    would draw their sampling keys per device, so only a greedy tier may
    split them."""
    if mesh is None or mesh.size == 1:
        return fn
    from jax.sharding import PartitionSpec as P

    names = tuple(inspect.signature(fn).parameters)
    stacked = next(n for n in names if _LEADING.get(n, ())[:1] == (_MEMBER,))
    n_pod, n_data = mesh.shape["pod"], mesh.shape["data"]

    def lead(name, args):
        return jax.tree.leaves(args[names.index(name)])[0].shape[0]

    def run(*args):
        E = lead(stacked, args)
        split = {
            _MEMBER: n_pod > 1 and E % n_pod == 0,
            _EXAMPLE: (
                "examples" in names and n_data > 1
                and lead("examples", args) % n_data == 0
            ),
        }
        if split[_MEMBER] and temperature > 0.0:
            raise NotImplementedError(
                f"sampled tier (T={temperature}) with its {E} members split "
                f"over {n_pod} devices: per-device keys would change votes"
            )
        axis = {_MEMBER: "pod", _EXAMPLE: "data"}

        def spec(name):
            return P(*(
                axis[role] if split[role] else None
                for role in _LEADING.get(name, ())
            ))

        return jax.shard_map(
            fn, mesh=mesh,
            in_specs=tuple(spec(n) for n in names),
            out_specs=(
                spec(out) if isinstance(out, str)
                else tuple(spec(o) for o in out)
            ),
            check_vma=False,
        )(*args)

    return run


@functools.lru_cache(maxsize=None)
def tier_programs(
    cfg: ModelConfig, temperature: float, mesh=None
) -> SimpleNamespace:
    """Long-lived jitted ensemble programs for one (config, temperature,
    mesh); ``mesh`` is the pod slice of a placed tier (None: one device).

    ``last_logits(values, batch) -> (E, B, V)``
    ``prefill(values, batch, rng) -> (tok (E, B, 1), caches, rng)``
    ``decode(values, tok, caches, pos, rng) -> (tok (E, B, 1), caches, rng)``
    ``decode_slots(values, tok, caches, pos, slot_keys) -> (tok, caches)``

    Sampling lives inside the programs (one XLA program advances every
    member of the tier per step).  Batch mode (``decode``, scalar ``pos``)
    threads one rng chain — every row steps in lockstep, so the chain is
    deterministic.  Continuous mode (``decode_slots``, per-slot (B,) pos)
    samples from per-slot admission keys instead (``_slot_sampler``): slots
    advance independently, and a shared chain would make votes depend on
    slot-step interleaving.
    """

    def _sample(logits, rng):  # logits (E, B, V)
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), rng
        rng, sub = jax.random.split(rng)
        keys = jax.random.split(sub, logits.shape[0])
        tok = jax.vmap(
            lambda k, l: jax.random.categorical(k, l / temperature)
        )(keys, logits)
        return tok.astype(jnp.int32), rng

    sample_slots = _slot_sampler(temperature)

    def prefill(values, batch, rng):
        logits, caches = ens.ensemble_prefill(values, batch, cfg)
        tok, rng = _sample(logits, rng)
        return tok[..., None], caches, rng

    def decode(values, tok, caches, pos, rng):
        logits, caches = ens.ensemble_decode_step(values, tok, caches, pos, cfg)
        nxt, rng = _sample(logits, rng)
        return nxt[..., None], caches, rng

    def decode_slots(values, tok, caches, pos, slot_keys):
        logits, caches = ens.ensemble_decode_step(values, tok, caches, pos, cfg)
        nxt = sample_slots(logits, slot_keys, pos)
        return nxt[..., None], caches

    def prefill_chunk(values, caches, tokens, slot, start):
        return jax.vmap(
            lambda p, c: api.prefill_into_slot(p, tokens, c, slot, start, cfg)
        )(values, caches)

    sample_verify = verify_sampler(temperature)

    def verify_chunk(values, caches, tokens, slot, start, slot_key):
        # speculative verify (serve/speculative.py): chunked prefill that
        # also scores every position, then the decode-equivalent sampler
        logits, caches = jax.vmap(
            lambda p, c: api.prefill_into_slot_logits(
                p, tokens, c, slot, start, cfg
            )
        )(values, caches)
        pos = start + jnp.arange(tokens.shape[0])
        return sample_verify(logits, slot_key, pos), caches

    def reset_slot(caches, slot):
        return jax.vmap(lambda c: api.reset_slot(c, slot, cfg))(caches)

    def last_logits(values, examples):
        return ens.ensemble_last_logits(values, examples, cfg)

    key = f"{cfg.name}@T{temperature:g}"
    on = functools.partial(_per_device, mesh=mesh, temperature=temperature)
    return SimpleNamespace(
        last_logits=jax.jit(_counted(
            f"{key}/ens_last_logits", on(last_logits, "logits")
        )),
        prefill=jax.jit(_counted(
            f"{key}/ens_prefill", on(prefill, ("tok", "caches", "rng"))
        )),
        decode=jax.jit(_counted(
            f"{key}/ens_decode", on(decode, ("tok", "caches", "rng"))
        )),
        decode_slots=jax.jit(_counted(
            f"{key}/ens_decode_slots", on(decode_slots, ("tok", "caches"))
        )),
        prefill_chunk=(
            jax.jit(_counted(
                f"{key}/ens_prefill_chunk", on(prefill_chunk, "caches")
            ))
            if api.supports_chunked_prefill(cfg)
            else None
        ),
        verify_chunk=(
            jax.jit(_counted(
                f"{key}/ens_verify_chunk",
                on(verify_chunk, ("tok", "caches")),
            ))
            if api.supports_draft_verify(cfg)
            else None
        ),
        reset_slot=(
            jax.jit(_counted(
                f"{key}/ens_slot_reset", on(reset_slot, "caches")
            ))
            if api.has_slot_state(cfg)
            else None
        ),
    )


@functools.lru_cache(maxsize=None)
def tier_paged_programs(
    cfg: ModelConfig, temperature: float, mesh=None
) -> SimpleNamespace:
    """Block-paged counterparts of ``tier_programs``'s continuous-mode
    programs: E pool planes advance under ONE shared page table (members
    score the same tokens at the same positions), with per-slot admission
    keys for sampling.  Pool/table geometry is data shape, not static args
    — one trace per geometry."""
    assert api.supports_paging(cfg), cfg.family
    sample_slots = _slot_sampler(temperature)

    def decode_slots(values, tok, pools, pos, pages, slot_keys):
        logits, pools = jax.vmap(
            lambda v, t, pl: api.decode_step_paged(v, t, pl, pos, pages, cfg)
        )(values, tok, pools)
        nxt = sample_slots(logits, slot_keys, pos)
        return nxt[..., None], pools

    def prefill_chunk(values, pools, tokens, pages_row, start):
        return jax.vmap(
            lambda v, pl: api.prefill_into_slot_paged(
                v, tokens, pl, pages_row, start, cfg
            )
        )(values, pools)

    sample_verify = verify_sampler(temperature)

    def verify_chunk(values, pools, tokens, pages_row, start, slot_key):
        logits, pools = jax.vmap(
            lambda v, pl: api.prefill_into_slot_paged_logits(
                v, tokens, pl, pages_row, start, cfg
            )
        )(values, pools)
        pos = start + jnp.arange(tokens.shape[0])
        return sample_verify(logits, slot_key, pos), pools

    def copy_page(pools, src, dst):
        return api.copy_pool_page(pools, src, dst)

    key = f"{cfg.name}@T{temperature:g}"
    on = functools.partial(_per_device, mesh=mesh, temperature=temperature)
    return SimpleNamespace(
        decode_slots=jax.jit(_counted(
            f"{key}/ens_decode_paged", on(decode_slots, ("tok", "pools"))
        )),
        prefill_chunk=jax.jit(_counted(
            f"{key}/ens_prefill_chunk_paged", on(prefill_chunk, "pools")
        )),
        verify_chunk=jax.jit(_counted(
            f"{key}/ens_verify_chunk_paged",
            on(verify_chunk, ("tok", "pools")),
        )),
        copy_page=jax.jit(_counted(
            f"{key}/ens_copy_pool_page", on(copy_page, "pools")
        )),
    )


@dataclasses.dataclass
class CascadeTier:
    """One cascade level at serving time: a stacked k-member ensemble
    (``values`` with a leading 'ensemble' axis) plus its ``TierSpec``
    deferral rule, bound to the compile-once ``tier_programs`` for its
    (config, temperature).  Construction is cheap — programs are shared
    module-level state, so building tiers repeatedly never re-jits."""

    cfg: ModelConfig
    values: dict  # stacked member params (leading ensemble axis)
    spec: TierSpec
    temperature: float = 0.0  # >0 for black-box sampled voting
    mesh: Optional[jax.sharding.Mesh] = None  # a placed tier's pod slice

    def __post_init__(self):
        self.k = ens.member_count(self.values)
        programs = tier_programs(
            self.cfg, float(self.temperature), self.mesh
        )
        self._last_logits = programs.last_logits
        self._prefill = programs.prefill
        self._decode = programs.decode
        self._decode_slots = programs.decode_slots
        self._prefill_chunk = programs.prefill_chunk
        self._verify_chunk = programs.verify_chunk
        self._reset_slot = programs.reset_slot

    def generate(
        self, tokens: np.ndarray, max_new_tokens: int, seed: int = 0
    ) -> np.ndarray:
        """Ensemble generation: tokens (B, S) -> (E, B, max_new).  Every
        decode step is one vmapped XLA program over the stacked (E, ...)
        parameters — no per-member Python loop, no per-member engines."""
        assert max_new_tokens >= 1, max_new_tokens
        B, S = tokens.shape
        rng = jax.random.PRNGKey(seed)
        tok, caches, rng = self._prefill(
            self.values, {"tokens": jnp.asarray(tokens)}, rng
        )
        caches = grow_cache(caches, max_new_tokens, self.cfg, lead=1)
        out = [host_fetch(tok)[..., 0]]
        for t in range(max_new_tokens - 1):
            tok, caches, rng = self._decode(
                self.values, tok, caches, jnp.int32(S + t), rng
            )
            out.append(host_fetch(tok)[..., 0])
        return np.stack(out, axis=2)  # (E, B, T)


@dataclasses.dataclass
class OpenLoopReport:
    """What one ``CascadeServer.serve_open_loop`` run measured.

    ``goodput`` is SLO-attainment: the fraction of OFFERED requests that
    completed within ``slo_s`` of their arrival time — shed requests and
    SLO misses both count against it, so admission control only helps by
    making the requests it keeps finish on time.  ``completed + shed``
    always partitions the offered trace (zero silent drops — asserted by
    the driver); latency percentiles come from the run's
    ``serve.request_latency_s`` registry histogram."""

    offered: int
    completed: List[Request]
    shed: List[Request]
    completed_in_slo: int
    goodput: float
    p50_s: float
    p99_s: float
    makespan_s: float
    controller_actions: List[dict] = dataclasses.field(default_factory=list)

    def __repr__(self):
        return (
            f"OpenLoopReport(offered={self.offered}, "
            f"done={len(self.completed)}, shed={len(self.shed)}, "
            f"goodput={self.goodput:.3f}, p50={self.p50_s:.4g}s, "
            f"p99={self.p99_s:.4g}s, makespan={self.makespan_s:.4g}s)"
        )


class _CascadeRun:
    """One serve run's machinery, shared verbatim by the closed-loop
    (``serve_continuous``) and open-loop (``serve_open_loop``) drivers:
    per-tier ``SlotStream``s over ``TierBackend``s, the vote/defer/complete
    routing, cross-host hop metering, and the telemetry scopes.  The
    drivers differ ONLY in when requests enter (up-front list vs
    arrival-time admission) and how time advances (clock reads vs explicit
    ``VirtualClock`` advances); everything a request experiences after
    submission lives here, which is what makes closed- and open-loop
    results comparable.

    ``theta_offset`` is the online controller's deferral actuation point:
    tier i defers on ``vote_frac <= clamp(spec.theta + theta_offset[i],
    0, 1)``.  Offsets default to 0.0, and the zero-offset path evaluates
    ``spec.theta`` unmodified — the static configuration is bitwise
    identical to the pre-controller code."""

    def __init__(self, server: "CascadeServer", cfg: ServeConfig,
                 ob: Observability):
        self.server = server
        self.tiers = server.tiers
        self.ob = ob
        self.tr = ob.tracer
        self.clk = ob.clock
        self.h_lat = ob.registry.histogram("serve.request_latency_s")
        self.hosts = server._host_names()
        if server.placement is not None:
            for i, link in enumerate(server.placement.links):
                link.attach_obs(ob, f"{self.hosts[i]}_{self.hosts[i + 1]}")
        n = len(self.tiers)
        tier_sc = [ob.scope(f"cascade.tier{i}") for i in range(n)]
        self.c_answered = [sc.counter("answered") for sc in tier_sc]
        self.c_deferred = [sc.counter("deferred") for sc in tier_sc]
        self.c_tokens = [sc.counter("output_tokens") for sc in tier_sc]
        self.h_margin = [
            sc.histogram("agreement_margin", buckets=UNIT_BUCKETS)
            for sc in tier_sc
        ]
        self.h_accept = [
            sc.histogram("draft_accept_rate", buckets=UNIT_BUCKETS)
            for sc in tier_sc
        ]
        # each finished slot's vote and routing is one step span (the
        # streams below hand ``ob`` the profiler's annotation)
        self.span_vote = [f"cascade.tier{i}.vote" for i in range(n)]
        self.speculative = bool(cfg.speculative)
        self.theta_offset: List[float] = [0.0] * n
        self.streams = [
            SlotStream(
                TierBackend(
                    t, n_slots=cfg.n_slots, max_seq=cfg.max_seq,
                    seed=cfg.seed + i, paged=cfg.paged,
                    page_size=cfg.page_size, n_pages=cfg.n_pages,
                    obs=ob, pool_name=f"paging.tier{i}",
                ),
                dataclasses.replace(cfg, obs=ob),
                name=f"slot_stream.tier{i}",
            )
            for i, t in enumerate(self.tiers)
        ]
        for i, st in enumerate(self.streams):
            if i > 0:
                st.on_draft_verified = self._accept_recorder(i)
        self.t_start: dict = {}
        self.done: List[Request] = []

    def _accept_recorder(self, i: int):
        def record(r, n_acc, n_draft):
            self.h_accept[i].record(n_acc / max(1, n_draft))

        return record

    # -- driver surface -----------------------------------------------------
    def submit(self, requests: Sequence[Request], *, t0=None) -> None:
        """Enqueue onto tier 0.  ``t0`` overrides the latency-clock origin
        (open loop passes the ARRIVAL time, so queue wait before admission
        counts against the SLO)."""
        for r in requests:
            self.t_start[r.rid] = self.clk() if t0 is None else t0
        self.streams[0].submit(requests)

    @property
    def active(self) -> bool:
        return any(st.active for st in self.streams)

    @property
    def runnable(self) -> bool:
        return any(st.runnable for st in self.streams)

    def block_on_inflight(self) -> None:
        """Every stream idle but payloads still on the wire: block on the
        oldest in-flight hop (the only legal wait — there is no compute
        left to hide it behind)."""
        next(st for st in self.streams if st.inflight).poll_inflight(
            block=True
        )

    def effective_theta(self, i: int) -> float:
        off = self.theta_offset[i]
        th = self.tiers[i].spec.theta
        return th if off == 0.0 else min(1.0, max(0.0, th + off))

    def sweep(self) -> None:
        """One round-robin pass: step every stream once, routing each
        completed slot through its tier's vote.  Deferred re-queues land on
        tier i+1 BEFORE its step in the same sweep — exactly the legacy
        serve_continuous interleaving."""
        for i, st in enumerate(self.streams):
            for r, gen in st.step():
                self._finish_slot(i, r, gen)

    # -- vote / defer / complete --------------------------------------------
    def _finish_slot(self, i: int, r: Request, gen: np.ndarray) -> None:
        with self.ob.span(self.span_vote[i]) as span:
            deferred = self._route(i, r, gen)
            span.set_metadata(defer=1 if deferred else 0)

    def _route(self, i: int, r: Request, gen: np.ndarray) -> bool:
        """Vote on tier ``i``'s member generations of ``r``, then defer it
        to tier ``i + 1`` or answer it; True when it was deferred."""
        tier = self.tiers[i]
        tr = self.tr
        n_tiers = len(self.streams)
        # abclint: disable=ABC203(gen is host-side — the backend fetched it; this is a host list of digests)
        digests = np.asarray(
            [stable_digest(gen[e]) for e in range(tier.k)],
            np.int32,
        )
        out = deferral.vote_rule_from_preds(
            jnp.asarray(digests[:, None]), self.effective_theta(i)
        )
        # one metered fetch per completed slot: the vote verdict
        # and winning digest scalars (8 bytes)
        defer_h, pred_h = host_fetch((out.defer[0], out.pred[0]))
        defer = bool(defer_h) and i < n_tiers - 1
        # agreement margin: the winning digest's vote share
        # (1.0 = unanimous) — digests is a host array
        vote_counts = np.unique(digests, return_counts=True)[1]
        margin = float(vote_counts.max()) / tier.k
        self.h_margin[i].record(margin)
        if tr.enabled:
            tr.instant(
                r.rid, "defer_vote",
                tier=i, margin=margin, defer=bool(defer_h),
            )
        if defer:
            self.c_deferred[i].add(1)
            # cascade-as-drafter (serve/speculative.py): the plurality
            # generation this tier voted on becomes the next tier's draft
            # — the agreeing work travels with the deferral instead of
            # being thrown away
            draft = None
            if self.speculative and gen.shape[1]:
                # abclint: disable=ABC202(argmax over the host digest array — pred_h fetched above)
                w = int(np.argmax(digests == pred_h))
                draft = gen[w].astype(np.int32)
            placement = self.server.placement
            link = placement.link(i) if placement is not None else None
            if link is not None:
                # cross-host re-queue: the prompt is the payload
                # that actually crosses the boundary.  send_async
                # meters the hop NOW; the handle resolves at a
                # tier-(i+1) admission point, so this tier's
                # remaining slots keep decoding over the hop
                # abclint: disable=ABC203(r.tokens is the host prompt array — the payload is built host-side before the metered send)
                payload = {"tokens": np.asarray(r.tokens, np.int32)}
                n_bytes = int(payload["tokens"].nbytes)
                if draft is not None:
                    # draft tokens ride the same metered hop
                    payload["draft"] = draft
                    n_bytes += int(draft.nbytes)
                hosts = self.hosts
                if tr.enabled:
                    tr.begin(
                        r.rid, "hop",
                        src=hosts[i], dst=hosts[i + 1],
                        n_bytes=n_bytes,
                    )
                handle = link.send_async(
                    hosts[i], hosts[i + 1], payload, n_examples=1,
                )
                hop = link.hops[-1]  # metered at send time

                def _land(delivered, r=r, handle=handle, hop=hop):
                    r.tokens = np.asarray(
                        delivered["tokens"], np.int32
                    )
                    if "draft" in delivered:
                        # abclint: disable=ABC203(delivered payload is host-side — the transport already moved it)
                        r.draft = np.asarray(
                            delivered["draft"], np.int32
                        )
                    if tr.enabled:
                        # the hop span closes at delivery (on
                        # the draining thread); its args carry
                        # the overlap split — blocked is what
                        # result() charged the caller, hidden
                        # is the link time decode covered
                        blocked = float(handle.wait_time)
                        tr.end(
                            r.rid, "hop",
                            link_s=float(hop.latency),
                            blocked_s=blocked,
                            hidden_s=max(
                                0.0, float(hop.latency) - blocked
                            ),
                        )
                    return r

                self.streams[i + 1].submit_inflight(handle, _land)
            else:
                r.draft = draft
                self.streams[i + 1].submit([r])
        else:
            self.c_answered[i].add(1)
            self.c_tokens[i].add(int(gen.shape[1]))
            # abclint: disable=ABC202(argmax over the host digest array — pred_h fetched above)
            winner = int(np.argmax(digests == pred_h))
            r.output = gen[winner].astype(np.int32)
            r.tier = i
            self.h_lat.record(self.clk() - self.t_start[r.rid])
            if tr.enabled:
                tr.instant(r.rid, "complete", tier=i)
            self.done.append(r)
        return defer


class CascadeServer:
    """The ABC serving runtime: a tier list + optional ``TierPlacement``.

    Every tier boundary's traffic contract is the same in all three modes:
    ONLY the compacted deferral payload (batch modes: deferred rows + i32
    index map, padded to their pow2 bucket cover; continuous mode: the
    deferred request's prompt) crosses the placement link, metered by the
    link's ``Transport``.  See the module docstring for the three modes and
    DESIGN.md §8 for how hops overlap with compute."""

    def __init__(
        self,
        tiers: Sequence[CascadeTier],
        *,
        pad_to: int = 8,
        placement=None,
    ):
        """``placement`` (serve/placement.py TierPlacement, optional) pins
        each tier to a host and makes every cross-host deferral an explicit
        metered ``Transport`` hop; tier values are device_put onto their
        host's pod submesh when it has one.  Without a placement, routing
        behaves as a single-host loopback (no metering)."""
        self.tiers = list(tiers)
        self.pad_to = pad_to
        self.placement = placement
        if placement is not None:
            from repro.serve.placement import place_tier_values

            assert placement.n_tiers == len(self.tiers), (
                placement.n_tiers, len(self.tiers),
            )
            # replace, don't mutate: the caller's tier objects keep their
            # original (unplaced) values
            self.tiers = [
                dataclasses.replace(
                    t, values=place_tier_values(t.values, host),
                    mesh=host.mesh,
                )
                for t, host in zip(self.tiers, placement.hosts)
            ]

    def _hop_transports(self):
        """Per-boundary transports from the placement (None = no metering)."""
        if self.placement is None:
            return None
        return list(self.placement.links)

    def _host_names(self):
        """Per-tier host names for hop metering (None = unplaced)."""
        if self.placement is None:
            return None
        return [h.name for h in self.placement.hosts]

    # -- classification serving -------------------------------------------
    def classify(self, tokens: np.ndarray) -> CascadeResult:
        """tokens (B, S) -> CascadeResult with per-tier routing stats."""

        def tier_fn(tier: CascadeTier):
            def fn(batch):
                logits = tier._last_logits(
                    tier.values, {"tokens": jnp.asarray(batch["tokens"])}
                )
                if tier.mesh is not None and tier.mesh.size > 1:
                    # the rule and the deferral compaction read the whole
                    # batch (the compaction is a prefix over it), and a
                    # kernel cannot span devices: they run on the slice's
                    # first device, the logits gathered there d2d
                    logits = jax.device_put(
                        logits,
                        jax.sharding.SingleDeviceSharding(
                            tier.mesh.devices.flat[0]
                        ),
                    )
                return logits

            return fn

        fns = [tier_fn(t) for t in self.tiers]
        specs = [t.spec for t in self.tiers]
        return cascade_apply_routed(
            fns, specs, {"tokens": tokens}, pad_to=self.pad_to,
            transport=self._hop_transports(), hosts=self._host_names(),
        )

    # -- black-box generation serving --------------------------------------
    def generate(
        self, tokens: np.ndarray, max_new_tokens: int = 8, seed: int = 0
    ) -> CascadeResult:
        """Each tier's members generate in one vmapped program; answers are
        digested to stable ids and vote-compared (the paper's API scenario
        where only text comes back)."""

        def tier_fn(tier: CascadeTier):
            def fn(batch):
                # the host-side python generate loop needs the prompt rows;
                # this is the tier's own compute, not the defer path —
                # fetched explicitly (transfer-guard clean, bytes metered)
                toks = host_fetch(batch["tokens"])
                out = tier.generate(toks, max_new_tokens, seed=seed)
                return jnp.asarray(digest_generations(out))  # (E, B) ids

            return fn

        fns = [tier_fn(t) for t in self.tiers]
        specs = [dataclasses.replace(t.spec, rule="vote_preds") for t in self.tiers]
        return cascade_apply_routed(
            fns, specs, {"tokens": tokens}, pad_to=self.pad_to,
            transport=self._hop_transports(), hosts=self._host_names(),
        )

    # -- cascade-aware continuous batching ---------------------------------
    def serve_continuous(
        self,
        requests: Sequence[Request],
        config: Optional[ServeConfig] = None,
        *,
        n_slots=UNSET,
        max_seq=UNSET,
        seed=UNSET,
        chunked_prefill=UNSET,
        paged=UNSET,
        page_size=UNSET,
        n_pages=UNSET,
        obs=UNSET,
    ) -> List[Request]:
        """Continuous-batching generate mode: every tier runs a
        ``SlotStream`` (serve/slot_stream.py, the E=k instantiation of the
        shared slot state machine) over its stacked-ensemble programs;
        streams are stepped round-robin, so a request deferred by tier i is
        admitted into a freed tier-i+1 slot while tier i is still decoding
        its remaining slots.  Admission uses bucketed chunked prefill by
        default; constant-state tiers (SSM/RWKV, hybrid) zero the admitted
        slot's state leaves, so every family serves continuously.  A
        completed slot votes over its member generations (Eq. 3 on stable
        digests): agreement -> the request exits with the majority answer
        and ``r.tier`` set; disagreement -> the request is re-queued
        (prompt intact) on the next tier.  Returns completed requests.

        Cross-host re-queues go through the placement link's ``send_async``
        (serve/transport.py): the hop is metered at send time, the handle
        joins the NEXT tier's in-flight queue, and the loop keeps stepping
        every runnable stream — with an ``AsyncTransport`` link the edge
        tier therefore keeps admitting and decoding while deferral payloads
        are on the wire (DESIGN.md §8).  The loop blocks on a handle only
        when NO stream has runnable work (the all-idle fallback).  Tiers
        generate bitwise-identically whether the link overlaps, blocks, or
        is absent — at ANY temperature: delivery timing only moves WHEN a
        request is re-admitted, never what its slot computes (greedy slots
        are rng-free; sampled slots draw from per-slot admission keys —
        see ``_slot_sampler``).

        Tuning knobs arrive as a ``ServeConfig`` (``config=``) or as the
        legacy kwargs (one deprecation pathway — serve/config.py); the
        run machinery itself is ``_CascadeRun``, shared bitwise with
        ``serve_open_loop``."""
        cfg = resolve_serve_config(
            config, "CascadeServer.serve_continuous",
            n_slots=n_slots, max_seq=max_seq, seed=seed,
            chunked_prefill=chunked_prefill, paged=paged,
            page_size=page_size, n_pages=n_pages, obs=obs,
        ).with_max_seq_default(256)
        for r in requests:
            assert len(r.tokens) + r.max_new_tokens <= cfg.max_seq, (
                f"request {r.rid}: prompt+budget "
                f"{len(r.tokens)}+{r.max_new_tokens} exceeds "
                f"max_seq={cfg.max_seq}"
            )
        # telemetry (DESIGN.md §11): one bundle spans every tier's stream,
        # pool, and placement link — pass ``obs`` to get a unified registry
        # namespace and (with an enabled tracer) the per-request lifecycle
        # trace; the default private bundle keeps legacy behaviour
        run = _CascadeRun(self, cfg, cfg.resolved_obs())
        run.submit(requests)
        while run.active:
            if not run.runnable:
                run.block_on_inflight()
                continue
            run.sweep()
        self.last_stream_stats = [dict(st.stats) for st in run.streams]
        return run.done

    # -- open-loop load-adaptive serving ------------------------------------
    def serve_open_loop(
        self,
        workload: Workload,
        config: Optional[ServeConfig] = None,
        *,
        slo_s: float = 1.0,
        controller=None,
        step_time_s: float = 0.01,
    ) -> OpenLoopReport:
        """Open-loop serving (DESIGN.md §12): admission is driven by the
        workload's ARRIVAL TIMES, not an up-front list — the system sees
        offered load, queues build under bursts, and the report scores
        SLO-attainment (``goodput``) rather than raw throughput.

        The run executes in VIRTUAL time: ``obs.clock`` must be an
        advanceable clock (``repro.serve.workload.VirtualClock``; one is
        created when no bundle is passed), and the driver advances it by
        ``step_time_s`` per round-robin sweep (the modeled service time of
        one decode step across the tiers) and across idle gaps to the next
        arrival.  Identical (workload, config, controller) inputs therefore
        replay bit-for-bit — which is what makes the controller-on vs
        static A/B in ``bench_serving`` a like-for-like comparison.

        ``controller`` (``repro.serve.controller.GreedyController``,
        optional) is bound to the run and ticked on its own interval; it
        may lower per-tier deferral thresholds, cap per-tier slot
        admission, and shed arrivals under overload.  Shed requests come
        back in ``report.shed`` with ``r.shed=True`` — never silently
        dropped: ``offered == len(completed) + len(shed)`` is asserted.
        ``config.seed``/geometry knobs mean the same thing as in
        ``serve_continuous``; a trace whose arrivals are all at t=0 and a
        no-op controller reproduce the closed-loop outputs exactly."""
        cfg = resolve_serve_config(
            config, "CascadeServer.serve_open_loop"
        ).with_max_seq_default(256)
        assert slo_s > 0 and step_time_s > 0, (slo_s, step_time_s)
        if cfg.obs is None:
            ob = Observability(clock=VirtualClock())
        else:
            ob = cfg.obs
        assert hasattr(ob.clock, "advance"), (
            "serve_open_loop runs in virtual time: obs.clock must be "
            "advanceable (repro.serve.workload.VirtualClock), got "
            f"{type(ob.clock).__name__}"
        )
        vt = ob.clock
        arrivals = list(workload)  # fresh Request objects, arrival order
        for _, r in arrivals:
            assert len(r.tokens) + r.max_new_tokens <= cfg.max_seq, (
                f"request {r.rid}: prompt+budget "
                f"{len(r.tokens)}+{r.max_new_tokens} exceeds "
                f"max_seq={cfg.max_seq}"
            )
        run = _CascadeRun(self, cfg, ob)
        sc = ob.scope("serve.open_loop")
        c_offered = sc.counter("offered")
        c_shed = sc.counter("shed")
        c_completed = sc.counter("completed")
        c_in_slo = sc.counter("completed_in_slo")
        if controller is not None:
            controller.bind(run, slo_s=slo_s)
        shed: List[Request] = []
        n_in_slo = 0
        n_seen = 0  # run.done prefix already scored against the SLO
        idx = 0
        next_tick = (
            controller.config.interval_s if controller is not None
            else float("inf")
        )
        while idx < len(arrivals) or run.active:
            # admit everything that has arrived by virtual-now; overload
            # shedding happens HERE, at the admission point, before the
            # request ever touches a stream
            while idx < len(arrivals) and arrivals[idx][0] <= vt.now_s + 1e-12:
                t_arrive, r = arrivals[idx]
                idx += 1
                c_offered.add(1)
                if controller is not None and controller.should_shed():
                    r.shed = True
                    shed.append(r)
                    c_shed.add(1)
                    if run.tr.enabled:
                        run.tr.instant(r.rid, "complete", shed=True)
                    continue
                run.submit([r], t0=t_arrive)
            if run.runnable:
                run.sweep()
                # score completions at their recorded completion time,
                # BEFORE this sweep's time charge moves the clock
                for r in run.done[n_seen:]:
                    c_completed.add(1)
                    if vt.now_s - run.t_start[r.rid] <= slo_s:
                        c_in_slo.add(1)
                        n_in_slo += 1
                n_seen = len(run.done)
                vt.advance(step_time_s)
            elif any(st.inflight for st in run.streams):
                run.block_on_inflight()
            elif idx < len(arrivals):
                # nothing runnable, nothing in flight: jump to next arrival
                vt.advance(arrivals[idx][0] - vt.now_s)
            else:
                break
            if controller is not None and vt.now_s + 1e-12 >= next_tick:
                controller.tick(vt.now_s)
                next_tick = vt.now_s + controller.config.interval_s
        self.last_stream_stats = [dict(st.stats) for st in run.streams]
        assert len(run.done) + len(shed) == len(arrivals), (
            "open-loop invariant violated: "
            f"{len(arrivals)} offered != {len(run.done)} completed "
            f"+ {len(shed)} shed"
        )
        return OpenLoopReport(
            offered=len(arrivals),
            completed=run.done,
            shed=shed,
            completed_in_slo=n_in_slo,
            goodput=n_in_slo / max(1, len(arrivals)),
            p50_s=run.h_lat.percentile(0.50),
            p99_s=run.h_lat.percentile(0.99),
            makespan_s=vt.now_s,
            controller_actions=(
                list(controller.actions) if controller is not None else []
            ),
        )

    # -- accounting ---------------------------------------------------------
    def expected_cost(self, result: CascadeResult) -> float:
        """Total cost of a routed run under the tiers' per-example costs
        (chunk padding included — that is the real serving cost)."""
        return result.cost

    def tier_fractions(self, result: CascadeResult) -> np.ndarray:
        """(n_tiers,) fraction of examples ANSWERED by each tier (the
        paper's exit-fraction breakdown, Table 5)."""
        return result.tier_counts / max(1, result.tier_counts.sum())
