"""Cascade execution (Algorithm 1).

Two execution forms:

``cascade_apply_dense``  — fully-jitted masked form: every tier evaluates the
    whole batch and the first agreeing tier's answer is selected with
    ``jnp.where``.  No FLOPs are saved, but the whole cascade is a single
    XLA program that lowers/shards on the production mesh — this is what the
    cascade dry-run compiles, and it doubles as the reference semantics.

``cascade_apply_routed`` — device-routed compacting form: after tier i only
    the deferred examples flow to tier i+1.  Compaction (defer mask →
    prefix-sum scatter → dense payload + index map) happens ON DEVICE in
    the ``kernels/compaction`` Pallas kernel (or its interpret/XLA
    fallback); the host only ever reads the scalar deferred COUNT to pick
    bucket shapes — the payload itself never crosses device→host on the
    defer path.  When tiers are placed on different hosts, the compacted
    payload takes an explicit ``Transport`` hop (serve/transport.py) whose
    bytes and latency are metered.  This is the deployment path
    (serve/cascade_server.py) and the one whose measured cost reproduces
    Prop 4.1.2.

Both forms take per-tier callables ``tier_fns[i](batch_slice) -> logits
(E_i, B, V)`` so they work for classifier heads, prefill last-token logits,
or sampled-answer ids alike.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import deferral
from repro.kernels.compaction import ops as compaction_ops
from repro.obs import global_registry as _global_registry


@dataclasses.dataclass(frozen=True)
class TierSpec:
    """One cascade level: an ensemble of k models + its deferral rule."""

    name: str
    rule: str  # 'vote' | 'score' | 'confidence' | 'entropy'
    theta: float
    k: int = 1
    cost: float = 1.0  # per-example cost in whatever unit the scenario uses


@dataclasses.dataclass
class CascadeResult:
    pred: np.ndarray  # (B,)
    tier_of: np.ndarray  # (B,) index of the answering tier
    scores: np.ndarray  # (B,) deferral score at the answering tier
    tier_counts: np.ndarray  # (n_tiers,) examples answered per tier
    evaluated: np.ndarray  # (n_tiers,) examples *evaluated* per tier
    cost: float  # total cost under the specs' per-example costs


def cascade_apply_dense(
    tier_fns: Sequence[Callable],
    specs: Sequence[TierSpec],
    batch,
):
    """Jit-friendly masked cascade.  Returns (pred, tier_of, scores)."""
    n = len(tier_fns)
    pred = None
    tier_of = None
    score_out = None
    decided = None
    for i, (fn, spec) in enumerate(zip(tier_fns, specs)):
        logits = fn(batch)
        out = deferral.apply_rule(spec.rule, logits, spec.theta)
        last = i == n - 1
        take = jnp.logical_or(~out.defer, jnp.bool_(last))
        if pred is None:
            pred = out.pred
            tier_of = jnp.zeros_like(out.pred)
            score_out = out.score
            decided = take
        else:
            newly = jnp.logical_and(~decided, take)
            pred = jnp.where(newly, out.pred, pred)
            tier_of = jnp.where(newly, i, tier_of)
            score_out = jnp.where(newly, out.score, score_out)
            decided = jnp.logical_or(decided, take)
    return pred, tier_of, score_out


def bucket_size(n: int, floor: int = 8) -> int:
    """Power-of-two batch bucket (>= floor).  Used everywhere a host-routed
    batch is padded before hitting a jitted program: bucketed shapes bound
    the number of distinct compilations to O(log B) instead of O(B)."""
    p = max(1, floor)
    while p < n:
        p *= 2
    return p


def bucket_chunks(n: int, floor: int = 8) -> List[int]:
    """Greedy power-of-two decomposition of a batch of ``n`` examples into
    bucket-shaped chunks (each a power-of-two multiple of ``floor``).

    This is how deferred examples are re-batched between tiers: every chunk
    shape comes from an O(log B) bucket set (so tier transitions re-enter
    already-compiled programs), while total padding stays < ``2 * floor``
    (a single covering bucket could waste ~2x the batch in padding, which
    would show up directly in the Prop 4.1.2 cost accounting)."""
    sizes: List[int] = []
    rem = n
    while rem > 0:
        c = max(1, floor)
        while c * 2 <= rem:
            c *= 2
        sizes.append(c)  # the last chunk may overshoot rem (that is padding)
        rem -= c
    return sizes


def prompt_chunks(n: int, max_chunk: int = 256) -> List[int]:
    """Exact power-of-two cover of ``n`` prompt tokens (largest-first).

    Chunked-prefill admission (serve/slot_stream.py) consumes a prompt
    prefix through per-bucket jitted prefill programs; every chunk size here
    comes from the O(log S) set {1, 2, 4, ..., max_chunk}, so after warmup
    no admission ever traces a new program.  Unlike ``bucket_chunks`` (batch
    re-padding, where overshoot is just padded rows), prompt chunks must
    tile EXACTLY — a padded prompt token would write a bogus KV row /
    advance SSM state — so the tail reuses ``bucket_chunks`` with floor 1,
    which is the plain binary decomposition and never overshoots."""
    sizes: List[int] = []
    while n >= max_chunk:
        sizes.append(max_chunk)
        n -= max_chunk
    if n > 0:
        sizes.extend(bucket_chunks(n, floor=1))
    return sizes


def _pad_rows(x, n):
    """Edge-pad a device array's leading axis to ``n`` rows."""
    if x.shape[0] == n:
        return x
    pad = x.shape[0]
    reps = [n - pad] + [1] * (x.ndim - 1)
    return jnp.concatenate([x, jnp.tile(x[-1:], reps)], axis=0)


# ---------------------------------------------------------------------------
# host-fetch accounting: every INTENTIONAL device→host read in the routed
# cascade goes through _fetch (explicit jax.device_get, transfer-guard
# clean) and is byte-metered, so tests can assert the defer path moves
# only scalar counts + final results to the host — never payload.  The
# meters are ``host_fetch.*`` counters on the process-wide registry
# (DESIGN.md §11); ``host_fetch_stats()`` is the legacy dict view.
# ---------------------------------------------------------------------------

_C_FETCH_BYTES = _global_registry().counter("host_fetch.bytes")
_C_FETCH_CALLS = _global_registry().counter("host_fetch.calls")


def host_fetch_stats() -> dict:
    return {"bytes": _C_FETCH_BYTES.value, "calls": _C_FETCH_CALLS.value}


def reset_host_fetch_stats() -> None:
    _C_FETCH_BYTES.reset()
    _C_FETCH_CALLS.reset()


def _fetch(tree):
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "dtype"):
            _C_FETCH_BYTES.add(int(leaf.size) * jnp.dtype(leaf.dtype).itemsize)
    _C_FETCH_CALLS.add(1)
    return jax.device_get(tree)


def host_fetch(tree):
    """The public metered explicit fetch (numpy leaves out, bytes counted).

    Every INTENTIONAL device→host read on a serving path goes through here
    (or the module-private ``_fetch``): the serving engines' sampled-token
    reads, the cascade's vote scalars, the per-tier deferred counts.  This
    is what keeps the transfer-guard regressions meaningful — implicit
    transfers raise, and the byte meter sees everything that did cross.
    abclint pass 2 (ABC2xx) enforces the discipline statically."""
    return _fetch(tree)


def _colocate(x, ref):
    """Re-place ``x`` next to ``ref`` (device→device, never via host) so
    result accumulators can merge per-tier answers produced on other hosts'
    device sets (pod placement)."""
    xs, rs = getattr(x, "sharding", None), getattr(ref, "sharding", None)
    if xs is not None and rs is not None and xs != rs:
        return jax.device_put(x, rs)
    return x


def cascade_apply_routed(
    tier_fns: Sequence[Callable],
    specs: Sequence[TierSpec],
    batch: dict,
    *,
    pad_to: int = 8,
    transport=None,
    hosts: Optional[Sequence[str]] = None,
) -> CascadeResult:
    """Device-routed cascade with ON-DEVICE batch compaction between tiers.

    ``batch`` is a dict of numpy/jax arrays with a leading example axis; it
    is moved to device once and never gathered back on host.  After each
    tier, the defer mask drives the ``kernels/compaction`` prefix-sum
    scatter (Pallas on TPU, interpret/XLA fallback elsewhere): deferred
    examples become a dense payload + index map without leaving HBM.  The
    host reads exactly ONE scalar per tier transition (the deferred count,
    via an explicit transfer) to choose greedy power-of-two bucket chunks
    (floor ``pad_to``, see ``bucket_chunks``) so tier transitions re-enter
    already-compiled programs.

    ``transport`` (optional) is a serve/transport.py backend — either one
    Transport applied to every tier boundary or a per-hop sequence (None
    entries = same-host hops).  Only the compacted deferral payload (padded
    to its bucket cover) is sent, which is what makes the §5.2 scenario
    benches report MEASURED bytes-over-link.  ``hosts`` names the per-tier
    placement for hop metering (defaults to tier names).

    Cost accounting: spec.cost · examples evaluated (the chunk padding is
    charged too — that is the real serving cost).
    """
    n = len(tier_fns)
    cur = {k: jnp.asarray(v) for k, v in batch.items()}
    B = int(jax.tree.leaves(cur)[0].shape[0])
    hop_transports = (
        list(transport) if isinstance(transport, (list, tuple))
        else [transport] * (n - 1)
    )
    assert len(hop_transports) >= n - 1, (len(hop_transports), n)
    hop_names = list(hosts) if hosts is not None else [s.name for s in specs]

    pred = jnp.zeros((B,), jnp.int32)
    tier_of = jnp.full((B,), -1, jnp.int32)
    scores = jnp.zeros((B,), jnp.float32)
    tier_counts_dev: List[jax.Array] = []
    evaluated = np.zeros((n,), np.int64)
    cost = 0.0

    active_idx = jnp.arange(B, dtype=jnp.int32)  # local row -> original row
    m = B
    landed_tr = None  # transport whose placement `cur`'s rows currently honor
    for i, (fn, spec) in enumerate(zip(tier_fns, specs)):
        defer_c, p_c, s_c = [], [], []
        charged = 0
        off = 0
        for c in bucket_chunks(m, pad_to):
            take = min(c, m - off)
            if off == 0 and c == int(jax.tree.leaves(cur)[0].shape[0]):
                # the delivered payload IS this chunk (single-bucket cover):
                # feed it exactly as the transport landed it — no slice, no
                # re-layout, rows keep their data-sharded residency
                fed = cur
            else:
                fed = {
                    k: _pad_rows(jax.lax.slice_in_dim(v, off, off + take), c)
                    for k, v in cur.items()
                }
                if landed_tr is not None:
                    # slicing/padding re-laid the rows (XLA picks its own
                    # output sharding for eager slices); put each chunk back
                    # onto the transport's example sharding so a hand-off
                    # landed data-sharded is never silently re-replicated
                    fed = {
                        k: jax.device_put(v, landed_tr.example_sharding(v))
                        for k, v in fed.items()
                    }
            logits = fn(fed)
            out = deferral.apply_rule(spec.rule, logits, spec.theta)
            defer_c.append(out.defer[:take])
            p_c.append(out.pred[:take])
            s_c.append(out.score[:take])
            charged += c
            off += take
        defer = jnp.concatenate(defer_c) if len(defer_c) > 1 else defer_c[0]
        p = jnp.concatenate(p_c) if len(p_c) > 1 else p_c[0]
        s = jnp.concatenate(s_c) if len(s_c) > 1 else s_c[0]
        evaluated[i] = charged
        cost += spec.cost * charged

        last = i == n - 1
        take_m = jnp.logical_or(~defer, jnp.bool_(last))
        # scatter this tier's answers to their original rows (device-side;
        # answers produced on another host's pod slice hop back d2d first)
        take_l, p_l, s_l, idx_l = (
            _colocate(t, pred) for t in (take_m, p, s, active_idx)
        )
        pred = pred.at[idx_l].set(jnp.where(take_l, p_l, pred[idx_l]))
        tier_of = tier_of.at[idx_l].set(
            jnp.where(take_l, jnp.int32(i), tier_of[idx_l])
        )
        scores = scores.at[idx_l].set(
            jnp.where(take_l, s_l, scores[idx_l])
        )
        tier_counts_dev.append(jnp.sum(take_m))

        if last:
            break
        # on-device compaction of the defer path: dense payload + index map
        # straight from the mask — no host gather, no re-pad on host.
        # (cur may carry bucket-padding rows from the previous hop; the
        # mask covers only the m real rows)
        real = {
            k: v if v.shape[0] == m else jax.lax.slice_in_dim(v, 0, m)
            for k, v in cur.items()
        }
        # the compaction runs where the mask is (a placed tier's rule runs
        # on one device of its slice); rows landed elsewhere move there d2d
        ctree, index_map, count = compaction_ops.compact_tree(
            {k: _colocate(v, defer) for k, v in
             {**real, "__idx": active_idx}.items()},
            defer,
        )
        n_defer = int(_fetch(count))  # the ONLY per-tier host read: a scalar
        if n_defer == 0:
            break
        n_padded = sum(bucket_chunks(n_defer, pad_to))
        n_padded = min(n_padded, m)  # payload rows physically available
        payload = {
            k: jax.lax.slice_in_dim(v, 0, n_padded) for k, v in ctree.items()
        }
        tr = hop_transports[i]
        if tr is not None:
            # batch mode has no admission point to overlap with — tier i+1
            # needs the whole payload before its first chunk — so the hop
            # handle is drained immediately; the overlapped drain lives in
            # CascadeServer.serve_continuous (SlotStream in-flight admission)
            handle = tr.send_async(
                hop_names[i], hop_names[i + 1], payload, n_examples=n_defer
            )
            payload = {k: jnp.asarray(v) for k, v in handle.result().items()}
        # rows now live where THIS boundary's transport put them; the next
        # tier's chunking must preserve that residency (sharded hand-offs
        # expose example_sharding; others have no placement to honor)
        landed_tr = tr if hasattr(tr, "example_sharding") else None
        active_idx = payload.pop("__idx")[:n_defer]
        cur = payload
        m = n_defer

    while len(tier_counts_dev) < n:
        tier_counts_dev.append(jnp.zeros((), jnp.int32))
    # per-tier counts may live on different hosts' devices — fetch as-is
    pred_h, tier_h, scores_h, counts_h = _fetch(
        (pred, tier_of, scores, tier_counts_dev)
    )
    return CascadeResult(
        pred=pred_h,
        tier_of=tier_h,
        scores=scores_h,
        # abclint: disable=ABC203(counts_h is a host list of fetched per-tier scalars)
        tier_counts=np.asarray(counts_h, np.int64),
        evaluated=evaluated,
        cost=cost,
    )
