"""SlotStream: the single slot state machine behind all continuous batching.

One ``SlotStream`` owns the admit / refill / prompt-feed / force-complete
lifecycle of ``n_slots`` decode slots over stacked ``(E, n_slots, ...)``
caches; the single-model engine is just the E=1 case and a cascade tier the
E=k case, so ``ServingEngine.serve_continuous`` and
``CascadeServer.serve_continuous`` are both thin drivers over this module.

Slot-isolation contract (why mid-stream reuse is safe):

* prompts are left-aligned at position 0 of their slot; every slot advances
  at its OWN ``pos`` (the decode program takes a per-slot (B,) position
  vector).  Attention reads cache rows ``< pos+1`` only, so stale KV rows
  written by a slot's previous occupant are invisible — that is the
  pos-masking contract shared with ``attention_decode`` and
  ``attention_prefill_chunk``.
* constant-state families (SSM/RWKV, hybrid's mamba leaves) have no pos
  mask, so admission zeroes the slot's state leaves through the backend's
  jitted ``reset_slot`` program — this is what lifts the old
  attention-families-only restriction on cascade continuous batching.

Chunked-prefill admission: on admit, ``prompt[:-1]`` is consumed in exact
power-of-two chunks (``core.cascade.prompt_chunks``) through a per-bucket
jitted prefill-into-slot program (``models.api.prefill_into_slot``) that
writes KV rows / advances state at the slot's offset — a 400-token prompt
costs a handful of chunk calls instead of ~400 decode steps.  The final
prompt token always goes through the shared decode program (its logits
sample the first output token), which keeps chunked and decode-only
admission token-for-token identical.  Chunk shapes come from the O(log S)
bucket set, so trace counters stay flat across requests after warmup.

In-flight admission (the overlap half of DESIGN.md §8): work whose payload
is still crossing a ``Transport`` link enters through ``submit_inflight``
as a (``SendHandle``, finalize) pair instead of a ready ``Request``.  The
stream's ONLY legal drain points are its admission points — the top of
``refill()`` (polls, never blocks: decode keeps running while hops are in
flight) and ``drain()``/the driver's all-idle fallback (blocks on the
oldest handle only when no stream has runnable work, so waiting can never
starve compute).  Handles resolve strictly in submission (FIFO) order,
which keeps the admission order — and therefore the whole stream — equal
to what a blocking transport would produce.

Device work goes through a small backend protocol (duck-typed):

    E                        int, ensemble width
    supports_chunked_prefill bool
    decode(tok (E, n_slots, 1), pos (n_slots,)) -> next (E, n_slots)
    prefill_chunk(tokens (C,), slot, start)     -> None   (updates cache)
    reset_slot(slot)                            -> None   (zero state leaves)

plus three OPTIONAL hooks for backends whose slot memory is allocated
rather than dedicated (the block-paged KV pools — serve/paging.py):

    begin_slot(slot, tokens, share) -> Optional[int]
        claim slot memory for a prompt before any prefill; returns the
        number of leading prompt tokens already covered by shared prefix
        pages (0 for dense), or None when the pool cannot admit — the
        request stays queued and the slot stays free.  Subsumes
        ``reset_slot``.  ``share`` is the stream's chunked flag: prefix
        pages may only be published when chunked prefill writes them in
        full before any sharer can be admitted.
    release_slot(slot) -> None
        return the slot's memory (decref pages) on completion.
    prepare_step(pos, active) -> [slot, ...]
        make each active slot's next write position mapped (grow-by-page,
        copy-on-write); returns the slots the pool could NOT serve — the
        stream force-completes those with ``truncated=True`` (the paged
        analogue of the dense cache wall).

``EngineBackend`` (E=1, host-side sampling via the engine's rng) and
``TierBackend`` (ensemble programs with in-program sampling) are provided
here; both reuse the module-level compile-once program caches, and both
default to block-paged pools where ``api.supports_paging`` allows, keeping
the dense slot cache available behind ``paged=False`` as the bitwise
parity oracle.
"""
from __future__ import annotations

from collections import deque
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cascade import host_fetch, prompt_chunks
from repro.models import api
from repro.models.params import unbox
from repro.obs import Observability, StatsView
from repro.serve.batching import Request
from repro.serve.config import UNSET, ServeConfig, resolve_serve_config
from repro.serve.speculative import accepted_prefix, plan_draft


class SlotStream:
    """Slot-based continuous batching over a device backend.

    Construction takes a ``ServeConfig`` (``config=``) or the legacy
    kwargs (one deprecation pathway — ``serve/config.py``).  The stream
    reads the scheduling fields (``n_slots``/``max_seq``/
    ``chunked_prefill``/``max_chunk``/``obs``); the memory/sampling fields
    (``paged``/``page_size``/``n_pages``/``seed``) belong to the backend
    its caller already built."""

    def __init__(
        self,
        backend,
        config: Optional[ServeConfig] = None,
        *,
        n_slots=UNSET,
        max_seq=UNSET,
        chunked_prefill=UNSET,
        max_chunk=UNSET,
        obs=UNSET,
        name: str = "slot_stream",
    ):
        cfg = resolve_serve_config(
            config, "SlotStream", n_slots=n_slots, max_seq=max_seq,
            chunked_prefill=chunked_prefill, max_chunk=max_chunk, obs=obs,
        ).with_max_seq_default(256)
        n_slots, max_seq = cfg.n_slots, cfg.max_seq
        chunked_prefill, max_chunk, obs = (
            cfg.chunked_prefill, cfg.max_chunk, cfg.obs,
        )
        self.backend = backend
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.max_chunk = max_chunk
        self.chunked = bool(chunked_prefill) and backend.supports_chunked_prefill
        # admission-side slot cap (<= n_slots): the online controller's
        # slot-count actuation point.  Slots at index >= slot_limit stop
        # ADMITTING; occupants above a lowered limit drain naturally, so
        # actuation never aborts in-flight work.
        self.slot_limit = n_slots
        E = backend.E
        self.queue: deque = deque()
        # (SendHandle, finalize) pairs whose payload is still in flight on a
        # transport link; drained FIFO at the admission points (see module
        # docstring — this is where compute/communication overlap happens)
        self.inflight: deque = deque()
        self.slot_req: List[Optional[Request]] = [None] * n_slots
        self.slot_consumed = np.zeros(n_slots, np.int64)  # prompt tokens fed
        self.slot_emitted: List[List[np.ndarray]] = [[] for _ in range(n_slots)]
        self.pos = np.zeros(n_slots, np.int32)
        self.tok = np.zeros((E, n_slots, 1), np.int32)
        self.steps = 0
        # requests whose draft verification finished them AT ADMISSION
        # (full acceptance consumed the whole budget / hit the wall): they
        # never see a decode step, so ``step()`` hands them back from here
        self._admit_done: List[Tuple[Request, np.ndarray]] = []
        # cascade hook: called as (request, n_accepted, n_draft) after
        # every verify pass so the run can record per-tier accept rates
        self.on_draft_verified = None
        # telemetry (DESIGN.md §11): counters + histograms on the stream's
        # obs registry, named under ``name`` (cascade tiers pass
        # ``slot_stream.tier{i}`` so one registry serves every tier).
        # Timestamps go through the injectable ``obs.clock`` (ABC601), and
        # everything recorded is a host scalar the loop already owns.
        self.obs = obs if obs is not None else Observability.private()
        self.name = name
        self._clock = self.obs.clock
        self._tr = self.obs.tracer
        sc = self.obs.scope(name)
        self._c_admitted = sc.counter("admitted")
        self._c_admit_failures = sc.counter("admit_failures")
        self._c_forced = sc.counter("forced_completions")
        self._c_chunk_calls = sc.counter("chunk_calls")
        self._c_chunk_tokens = sc.counter("chunk_tokens")
        self._c_shared_tokens = sc.counter("shared_tokens")
        self._c_decode_tokens = sc.counter("decode_tokens")
        self._c_inflight_admitted = sc.counter("inflight_admitted")
        # speculative verify (serve/speculative.py): passes run, draft
        # tokens offered, draft tokens accepted
        self._c_spec_drafts = sc.counter("spec.drafts")
        self._c_spec_draft_tokens = sc.counter("spec.draft_tokens")
        self._c_spec_accepted = sc.counter("spec.accepted_tokens")
        # ready-queue depth after every enqueue/admit — the streaming
        # backlog signal the online controller reads from the registry
        self._g_queue = sc.gauge("queue_depth")
        # host wall time histograms.  jax dispatch is async, so the admit/
        # decode dispatch times measure enqueue overhead, not device
        # compute — block_until_ready on the backend's cache around
        # refill()/step() to measure true device latency
        # (benchmarks/bench_serving.py does).  The old conflated
        # ``admit_time`` accumulator is split three ways:
        #   admit.begin_slot_s        pool page claim / slot reset
        #   admit.prefill_dispatch_s  bucketed chunk-prefill dispatch
        #   admit.inflight_wait_s     BLOCKED time on unresolved transport
        #                             handles (0 when hops fully hid)
        self._h_begin_slot = sc.histogram("admit.begin_slot_s")
        self._h_prefill_dispatch = sc.histogram("admit.prefill_dispatch_s")
        self._h_decode_dispatch = sc.histogram("decode.dispatch_s")
        self._h_inflight_wait = sc.histogram("admit.inflight_wait_s")
        # the legacy ad-hoc stats dict survives as a read-only view over
        # the registry (same keys, same totals — ``admit_time`` is now the
        # sum of its two split histograms)
        self.stats = StatsView({
            "admitted": lambda: self._c_admitted.value,
            "admit_failures": lambda: self._c_admit_failures.value,
            "forced_completions": lambda: self._c_forced.value,
            "chunk_calls": lambda: self._c_chunk_calls.value,
            "chunk_tokens": lambda: self._c_chunk_tokens.value,
            "shared_tokens": lambda: self._c_shared_tokens.value,
            "decode_tokens": lambda: self._c_decode_tokens.value,
            "admit_time": lambda: (
                self._h_begin_slot.sum + self._h_prefill_dispatch.sum
            ),
            "decode_time": lambda: self._h_decode_dispatch.sum,
            "inflight_admitted": lambda: self._c_inflight_admitted.value,
            "inflight_wait": lambda: self._h_inflight_wait.sum,
            "spec_drafts": lambda: self._c_spec_drafts.value,
            "spec_draft_tokens": lambda: self._c_spec_draft_tokens.value,
            "spec_accepted_tokens": lambda: self._c_spec_accepted.value,
        })

    # -- admission ---------------------------------------------------------
    def _check_request(self, r: Request) -> Request:
        """The admission invariant, shared by BOTH entry paths (direct
        ``submit`` and in-flight ``poll_inflight`` finalizers): the prompt
        must fit the slot, 1 <= len(tokens) < max_seq."""
        assert len(r.tokens) >= 1, f"request {r.rid}: empty prompt"
        assert len(r.tokens) < self.max_seq, (
            f"request {r.rid}: prompt length {len(r.tokens)} does not fit "
            f"max_seq={self.max_seq}"
        )
        return r

    def submit(self, requests: Sequence[Request]):
        """Enqueue ready requests (payload already local — work arriving
        over a transport link enters via ``submit_inflight`` instead).
        Prompts must fit the slot: 1 <= len(tokens) < max_seq."""
        for r in requests:
            self.queue.append(self._check_request(r))
            if self._tr.enabled:
                self._tr.begin(r.rid, "queue_wait", stream=self.name)
        self._g_queue.set(len(self.queue))

    def submit_inflight(self, handle, finalize):
        """Enqueue work whose payload is still crossing a transport link.

        ``handle`` is a ``serve.transport.SendHandle``; ``finalize`` maps
        the delivered payload tree to the ``Request`` to admit (the caller
        owns the payload→request convention — e.g. the cascade re-queue
        rebuilds ``r.tokens`` from the delivered prompt).  The pair joins
        ``self.inflight`` and is drained FIFO at the admission points; the
        stream stays ``active`` (but not ``runnable``) while anything is in
        flight, so drivers never exit with payloads on the wire."""
        self.inflight.append((handle, finalize))

    def poll_inflight(self, *, block: bool = False) -> int:
        """Drain resolved in-flight sends (FIFO, stopping at the first
        unresolved handle so admission order matches a blocking transport)
        into ``self.queue``.  With ``block=True`` and nothing resolved,
        waits on the OLDEST handle — drivers only do this when no stream
        has runnable work left (the all-idle fallback), so blocking here
        never hides compute the loop could be doing.  Returns the number of
        requests that landed."""
        landed = 0
        while self.inflight and (
            self.inflight[0][0].done() or (block and landed == 0)
        ):
            handle, finalize = self.inflight.popleft()
            r = self._check_request(finalize(handle.result()))
            self.queue.append(r)
            self._h_inflight_wait.record(handle.wait_time)
            self._c_inflight_admitted.add(1)
            if self._tr.enabled:
                self._tr.begin(r.rid, "queue_wait", stream=self.name)
            landed += 1
        if landed:
            self._g_queue.set(len(self.queue))
        return landed

    def set_slot_limit(self, k: int) -> None:
        """Cap how many slots may hold occupants (clamped to
        ``[1, n_slots]``) — the controller's slot-count actuation.  A
        lowered limit takes effect as occupied slots free up; raising it
        re-opens admission immediately on the next ``refill``."""
        self.slot_limit = max(1, min(int(k), self.n_slots))

    def _release(self, s: int):
        """Hand the slot's memory back to the backend (paged pools decref
        their pages; dense backends have nothing to return)."""
        release = getattr(self.backend, "release_slot", None)
        if release is not None:
            release(s)
        self.slot_req[s] = None
        self.slot_emitted[s] = []

    def _admit(self, s: int):
        if not self.queue or s >= self.slot_limit:
            self.slot_req[s] = None
            return
        r = self.queue[0]  # peek: admission may be refused by the pool
        t0 = self._clock()
        begin = getattr(self.backend, "begin_slot", None)
        if begin is not None:
            # prefix pages are only shareable under chunked prefill (the
            # owner writes them in full before any sharer can be admitted)
            shared = begin(s, r.tokens, share=self.chunked)
            if shared is None:
                # pool exhausted: the request stays at the queue head and
                # the slot stays free; completions will release pages
                self._h_begin_slot.record(self._clock() - t0)
                self._c_admit_failures.add(1)
                self.slot_req[s] = None
                if not any(q is not None for q in self.slot_req):
                    raise RuntimeError(
                        f"request {r.rid}: prompt needs more pages than the "
                        "pool holds even with every slot free"
                    )
                return
        else:
            self.backend.reset_slot(s)
            shared = 0
        t1 = self._clock()
        self._h_begin_slot.record(t1 - t0)
        self.queue.popleft()
        self._g_queue.set(len(self.queue))
        tr = self._tr
        if tr.enabled:
            tr.end(r.rid, "queue_wait")
            tr.begin(
                r.rid, "admit", stream=self.name, slot=s,
                prompt_tokens=len(r.tokens), shared_tokens=shared,
            )
        consumed = 0
        if self.chunked and len(r.tokens) > 1:
            # consume prompt[:-1] in bucketed pow2 chunks; the last prompt
            # token rides the decode program (see module docstring).  A
            # shared-prefix span is already resident in the pool — chunks
            # start at its end (absolute positions, so the chunk split
            # never changes what any token computes)
            m = len(r.tokens) - 1
            chunks = prompt_chunks(m - shared, self.max_chunk)
            off = shared
            for c in chunks:
                if tr.enabled:
                    tr.begin(r.rid, "prefill_chunk", tokens=c, start=off)
                self.backend.prefill_chunk(r.tokens[off : off + c], s, off)
                if tr.enabled:
                    tr.end(r.rid, "prefill_chunk")
                off += c
            consumed = off
            self._c_chunk_calls.add(len(chunks))
            self._c_chunk_tokens.add(m - shared)
            self._c_shared_tokens.add(shared)
            self._h_prefill_dispatch.record(self._clock() - t1)
        # speculative verify (serve/speculative.py): a deferral arriving
        # with the previous tier's agreeing generation scores every draft
        # position in one chunked pass INSTEAD of the last-prompt-token
        # decode feed — it runs where the chunk loop left off (consumed ==
        # P-1 under chunked admission), and only on backends whose cache
        # can roll rejected rows back (attention families)
        plan = None
        if r.draft is not None:
            draft, r.draft = r.draft, None  # consumed at THIS admission
            if self.chunked and getattr(
                self.backend, "supports_draft_verify", False
            ):
                plan = plan_draft(
                    r.tokens, draft, r.max_new_tokens, self.max_seq
                )
        verified = None
        if plan is not None:
            P = len(r.tokens)
            T_use = len(plan.draft)
            ext = getattr(self.backend, "extend_slot", None)
            # paged: map private pages for the draft rows up front; a
            # refusal (pool pressure) falls back to plain admission
            if ext is None or ext(s, P + T_use):
                if tr.enabled:
                    tr.begin(r.rid, "verify_draft", draft_tokens=T_use)
                choices = self.backend.verify_draft(
                    plan.tokens, s, plan.start, self.max_chunk
                )  # (E, T_use + 1) host choices
                n_acc = accepted_prefix(choices, plan.draft)
                rb = getattr(self.backend, "rollback_slot", None)
                if rb is not None:
                    # unmap pages wholly past the accepted span (dense
                    # backends: the pos mask already hides rejected rows)
                    rb(s, P + n_acc)
                if tr.enabled:
                    tr.end(r.rid, "verify_draft", accepted=n_acc)
                self._c_spec_drafts.add(1)
                self._c_spec_draft_tokens.add(T_use)
                self._c_spec_accepted.add(n_acc)
                if self.on_draft_verified is not None:
                    self.on_draft_verified(r, n_acc, T_use)
                verified = (plan, choices, n_acc)
        self.slot_req[s] = r
        if verified is not None:
            plan, choices, n_acc = verified
            E = self.backend.E
            # accepted draft tokens are each member's own emission (their
            # choices matched the draft there); position n_acc emits each
            # member's OWN choice — together n_acc + 1 decode steps' worth
            # of output from one pass
            emitted = [
                np.full((E,), d, np.int32) for d in plan.draft[:n_acc]
            ]
            emitted.append(choices[:, n_acc].astype(np.int32).copy())
            self.slot_consumed[s] = len(r.tokens)
            self.slot_emitted[s] = emitted
            self.pos[s] = len(r.tokens) + n_acc
            self.tok[:, s, 0] = choices[:, n_acc]
        else:
            self.slot_consumed[s] = consumed + 1
            self.slot_emitted[s] = []
            self.pos[s] = consumed
            self.tok[:, s, 0] = r.tokens[consumed]
        self._c_admitted.add(1)
        if tr.enabled:
            tr.end(r.rid, "admit")
            tr.begin(r.rid, "decode", stream=self.name, slot=s)
        if verified is not None:
            # the verify pass may already satisfy the budget / hit the
            # wall: complete NOW (the slot never decodes) and hand the
            # result back through ``step()``'s _admit_done drain
            full = len(self.slot_emitted[s]) >= r.max_new_tokens
            wall = self.pos[s] >= self.max_seq - 1
            if full or wall:
                r.truncated = not full
                gen = np.stack(self.slot_emitted[s], axis=1)
                if tr.enabled:
                    tr.end(
                        r.rid, "decode",
                        new_tokens=gen.shape[1], truncated=r.truncated,
                    )
                self._admit_done.append((r, gen))
                self._release(s)
                self._admit(s)

    def refill(self):
        """Admit queued requests into every free slot.  This is the
        non-blocking admission point: resolved in-flight sends land first
        (a poll — decode never waits on the link here), then free slots
        admit from the queue."""
        if self.inflight:
            self.poll_inflight(block=False)
        for s in range(self.n_slots):
            if self.slot_req[s] is None and self.queue:
                self._admit(s)

    @property
    def runnable(self) -> bool:
        """True when the stream can make device progress RIGHT NOW: a slot
        is occupied, a ready request is queued, or an admission-time
        completion is waiting to be handed back.  In-flight sends do not
        count — a stream with only in-flight work has nothing to decode
        until a handle resolves (see ``active``)."""
        return (
            any(r is not None for r in self.slot_req)
            or bool(self.queue)
            or bool(self._admit_done)
        )

    @property
    def active(self) -> bool:
        """True while the stream still owes work: runnable, or a payload is
        in flight on a transport link (drivers must not exit on in-flight
        work — its requests have not completed anywhere yet)."""
        return self.runnable or bool(self.inflight)

    # -- stepping ----------------------------------------------------------
    def step(self) -> List[Tuple[Request, np.ndarray]]:
        """Advance every active slot by one token; returns the list of
        (request, member generations (E, T)) that completed this step.
        Freed slots immediately admit from ``self.queue``."""
        self.refill()
        # admission-time completions (fully-accepted drafts) exit first —
        # they were finished by the verify pass and own no slot
        completed = self._admit_done
        self._admit_done = []
        n_active = sum(r is not None for r in self.slot_req)
        if n_active == 0:
            return completed
        prepare = getattr(self.backend, "prepare_step", None)
        if prepare is not None:
            # paged pools: map every active slot's next write position
            # (grow-by-page / COW).  Slots the pool cannot serve force-
            # complete with what they have — the paged cache wall
            active = [s for s, r in enumerate(self.slot_req) if r is not None]
            for s in prepare(self.pos, active):
                r = self.slot_req[s]
                r.truncated = True
                gen = (
                    np.stack(self.slot_emitted[s], axis=1)
                    if self.slot_emitted[s]
                    else np.zeros((self.backend.E, 0), np.int32)
                )
                completed.append((r, gen))
                self._c_forced.add(1)
                if self._tr.enabled:
                    self._tr.end(r.rid, "decode", new_tokens=gen.shape[1])
                    self._tr.instant(r.rid, "forced_complete", slot=s)
                self._release(s)
                self._admit(s)
            n_active = sum(r is not None for r in self.slot_req)
            if n_active == 0:
                return completed
        t0 = self._clock()
        nxt = self.backend.decode(self.tok, self.pos)  # (E, n_slots)
        self._h_decode_dispatch.record(self._clock() - t0)
        self._c_decode_tokens.add(n_active)
        self.steps += 1
        for s, r in enumerate(self.slot_req):
            if r is None:
                continue
            self.pos[s] += 1
            if self.slot_consumed[s] < len(r.tokens):
                # prompt-feed: still consuming the prompt through decode
                self.tok[:, s, 0] = r.tokens[self.slot_consumed[s]]
                self.slot_consumed[s] += 1
            else:
                self.slot_emitted[s].append(nxt[:, s].copy())
                self.tok[:, s, 0] = nxt[:, s]
                full = len(self.slot_emitted[s]) >= r.max_new_tokens
                wall = self.pos[s] >= self.max_seq - 1  # out of cache rows
                if full or wall:
                    r.truncated = not full
                    gen = (
                        np.stack(self.slot_emitted[s], axis=1)
                        if self.slot_emitted[s]
                        else np.zeros((self.backend.E, 0), np.int32)
                    )
                    completed.append((r, gen))
                    if self._tr.enabled:
                        self._tr.end(
                            r.rid, "decode",
                            new_tokens=gen.shape[1], truncated=r.truncated,
                        )
                    self._release(s)
                    self._admit(s)
        return completed

    def drain(self) -> List[Tuple[Request, np.ndarray]]:
        """Step until every queued and in-flight request has completed.
        When only in-flight work remains (nothing runnable), blocks on the
        oldest handle — the single-stream all-idle fallback."""
        done = []
        while self.active:
            if not self.runnable:
                self.poll_inflight(block=True)
            done.extend(self.step())
        return done


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------


def _default_n_pages(n_slots: int, max_seq: int, page_size: int) -> int:
    """Dense-equivalent pool capacity plus the overflow sink: enough pages
    that no admission pattern the dense cache serves can ever fail."""
    return n_slots * (max_seq // page_size) + 1


class _PagedSlots:
    """The shared paged-backend half: host ``PagePool`` bookkeeping plus
    the begin/release/prepare hooks, parameterized over the device-side
    page-copy program (engine pools and E-stacked tier pools differ only
    in leading axes — ``api.copy_pool_page`` locates the page axis from
    the trailing layout)."""

    def _init_pool(self, n_slots, max_seq, page_size, n_pages,
                   obs=None, pool_name="paging"):
        from repro.serve.paging import PagePool

        if n_pages is None:
            n_pages = _default_n_pages(n_slots, max_seq, page_size)
        self.pool = PagePool(
            n_pages, page_size, n_slots=n_slots, max_seq=max_seq,
            obs=obs, name=pool_name,
        )

    def begin_slot(self, slot, tokens, *, share=True):
        """Claim pages for a new occupant (see ``PagePool.admit``); dense
        backends fall back to ``reset_slot`` + private rows."""
        if not self.paged:
            self.reset_slot(slot)
            return 0
        return self.pool.admit(slot, tokens, share=share)

    def release_slot(self, slot):
        if self.paged:
            self.pool.release(slot)

    def extend_slot(self, slot, n_rows):
        """Cover rows ``[0, n_rows)`` with pages before a speculative
        verify pass writes draft rows past the admission span (PRIVATE
        pages only — see ``PagePool.extend``).  Dense backends need
        nothing: their slot rows are dedicated.  Returns False when the
        pool cannot cover the span (caller falls back to plain
        admission)."""
        if not self.paged:
            return True
        return self.pool.extend(slot, n_rows)

    def rollback_slot(self, slot, keep_rows):
        """Speculative rollback: unmap pages wholly past rows
        ``[0, keep_rows)`` (``PagePool.truncate``).  Dense backends rely
        on the pos mask — rejected rows are invisible and the next decode
        overwrites its row before attending."""
        if self.paged:
            self.pool.truncate(slot, keep_rows)

    def prepare_step(self, pos, active):
        """Map each active slot's next write position; COW splits run the
        jitted page-copy program.  Returns slots the pool cannot serve."""
        if not self.paged:
            return []
        oom = []
        for s in active:
            # abclint: disable=ABC202(self.pos is host numpy maintained by the stream loop)
            ok, copies = self.pool.prepare(s, int(pos[s]))
            if not ok:
                oom.append(s)
                continue
            for src, dst in copies:
                self.pool_dev = self._copy_page(
                    self.pool_dev, jnp.int32(src), jnp.int32(dst)
                )
        return oom


class EngineBackend(_PagedSlots):
    """E=1 backend over a single model's compile-once programs.

    ``programs`` is the ``model_programs(cfg)`` namespace (decode /
    prefill_chunk / reset_slot); sampling stays on the host through
    ``sample`` (the engine's temperature + rng policy).  ``paged`` selects
    block-paged KV pools (default wherever the family supports them);
    ``paged=False`` keeps the dense slot cache as the parity oracle."""

    def __init__(self, cfg, params, programs, sample, *, n_slots, max_seq,
                 prefill_counter=None, paged=None, page_size: int = 16,
                 n_pages=None, obs=None, pool_name="paging"):
        assert not cfg.is_encoder
        self.cfg = cfg
        self.params = params
        self._decode = programs.decode
        self._chunk = getattr(programs, "prefill_chunk", None)
        self._reset = getattr(programs, "reset_slot", None)
        self._sample = sample
        # the owning engine's ``engine.prefill_tokens`` counter (legacy
        # engine.stats credit for chunked prefills); None outside an engine
        self._prefill_counter = prefill_counter
        self.E = 1
        self.paged = api.supports_paging(cfg) if paged is None else bool(paged)
        if self.paged:
            from repro.serve.engine import paged_model_programs

            self._init_pool(n_slots, max_seq, page_size, n_pages,
                            obs=obs, pool_name=pool_name)
            self.pool_dev, _ = unbox(
                api.init_paged_pool(cfg, self.pool.n_pages, page_size)
            )
            progs = paged_model_programs(cfg)
            self._decode_paged = progs.decode
            self._chunk_paged = progs.prefill_chunk
            self._copy_page = progs.copy_page
            self.cache = None
            self.supports_chunked_prefill = True
        else:
            # abclint: disable=ABC501(dense parity oracle: paged=False keeps the dense slot cache)
            self.cache, _ = unbox(api.init_cache(cfg, n_slots, max_seq))
            self.supports_chunked_prefill = self._chunk is not None

    def decode(self, tok, pos):
        """One decode step for every slot at its own ``pos``; returns the
        sampled next tokens (1, n_slots)."""
        if self.paged:
            logits, self.pool_dev = self._decode_paged(
                self.params, jnp.asarray(tok[0]), self.pool_dev,
                jnp.asarray(pos), jnp.asarray(self.pool.table),
            )
        else:
            logits, self.cache = self._decode(
                self.params, jnp.asarray(tok[0]), self.cache, jnp.asarray(pos)
            )
        return host_fetch(self._sample(logits))[None]  # (1, n_slots)

    def prefill_chunk(self, tokens, slot, start):
        """Write one pow2 prompt chunk into ``slot`` at offset ``start``."""
        if self.paged:
            self.pool_dev = self._chunk_paged(
                self.params, jnp.asarray(tokens), self.pool_dev,
                jnp.asarray(self.pool.table[slot]), jnp.int32(start),
            )
        else:
            self.cache = self._chunk(
                self.params, jnp.asarray(tokens), self.cache,
                jnp.int32(slot), jnp.int32(start),
            )
        if self._prefill_counter is not None:
            self._prefill_counter.add(len(tokens))

    def reset_slot(self, slot):
        """Zero the slot's constant-state leaves (no-op for pos-masked
        families — stale KV rows are invisible past the slot's pos)."""
        if self._reset is not None:
            self.cache = self._reset(self.cache, jnp.int32(slot))


class TierBackend(_PagedSlots):
    """E=k backend over a cascade tier's stacked-ensemble programs (one
    vmapped XLA program advances every member; sampling lives inside the
    programs).

    Sampling determinism: every slot owns an rng key ``fold_in(base,
    admit_seq)`` assigned at admission (admission order is FIFO and
    transport-timing-invariant), and each sampled token uses
    ``fold_in(fold_in(slot_key, pos), e)`` — a slot's sampled trajectory
    depends only on its own occupant and history, never on which OTHER
    slots happen to share its decode dispatches.  Temperature>0 voting is
    therefore bitwise identical under serial, blocking, or overlapped
    transport (the old shared rng thread made it interleaving-dependent).

    Paged tiers stack E pool planes but keep ONE page table: members score
    the same tokens at the same positions, so every shared prefix page is
    an E-fold HBM saving (the ABC-specific win — see DESIGN.md §10)."""

    def __init__(self, tier, *, n_slots, max_seq, seed: int = 0,
                 paged=None, page_size: int = 16, n_pages=None,
                 obs=None, pool_name="paging"):
        assert not tier.cfg.is_encoder
        self.tier = tier
        self.E = tier.k
        self._base_key = jax.random.PRNGKey(seed)
        self._admit_seq = 0
        self.slot_keys = jnp.tile(self._base_key[None], (n_slots, 1))
        self.paged = (
            api.supports_paging(tier.cfg) if paged is None else bool(paged)
        )
        if self.paged:
            from repro.serve.cascade_server import tier_paged_programs

            self._init_pool(n_slots, max_seq, page_size, n_pages,
                            obs=obs, pool_name=pool_name)
            pool0, _ = unbox(
                api.init_paged_pool(tier.cfg, self.pool.n_pages, page_size)
            )
            # E pool planes, ONE table: HBM scales with pages, not seqs
            self.pool_dev = jax.tree.map(
                # abclint: disable=ABC502(page-bounded pool planes scale with mapped pages, not sequence length)
                lambda v: jnp.zeros((self.E,) + v.shape, v.dtype), pool0
            )
            progs = tier_paged_programs(
                tier.cfg, float(tier.temperature), tier.mesh
            )
            self._decode_paged = progs.decode_slots
            self._chunk_paged = progs.prefill_chunk
            self._verify_paged = progs.verify_chunk
            self._copy_page = progs.copy_page
            self.caches = None
            self.supports_chunked_prefill = True
            # paged families are attention families: always verifiable
            self.supports_draft_verify = True
        else:
            # abclint: disable=ABC501(dense parity oracle: paged=False keeps the dense slot cache)
            values0, _ = unbox(api.init_cache(tier.cfg, n_slots, max_seq))
            self.caches = jax.tree.map(
                # abclint: disable=ABC502(dense parity oracle: paged=False keeps the E-stacked dense cache)
                lambda v: jnp.zeros((self.E,) + v.shape, v.dtype), values0
            )
            self.supports_chunked_prefill = (
                getattr(tier, "_prefill_chunk", None) is not None
            )
            self.supports_draft_verify = (
                getattr(tier, "_verify_chunk", None) is not None
            )

    def begin_slot(self, slot, tokens, *, share=True):
        """Assign the slot's admission rng key, then claim its memory."""
        shared = super().begin_slot(slot, tokens, share=share)
        if shared is None:
            return None  # pool refusal: the occupant (and its key) stays out
        self._admit_seq += 1
        self.slot_keys = self.slot_keys.at[slot].set(
            jax.random.fold_in(self._base_key, self._admit_seq)
        )
        return shared

    def decode(self, tok, pos):
        """One vmapped decode step for every member x slot; returns the
        sampled next tokens (E, n_slots)."""
        if self.paged:
            t, self.pool_dev = self._decode_paged(
                self.tier.values, jnp.asarray(tok), self.pool_dev,
                jnp.asarray(pos), jnp.asarray(self.pool.table),
                self.slot_keys,
            )
        else:
            t, self.caches = self.tier._decode_slots(
                self.tier.values, jnp.asarray(tok), self.caches,
                jnp.asarray(pos), self.slot_keys,
            )
        return host_fetch(t)[..., 0]  # (E, n_slots)

    def prefill_chunk(self, tokens, slot, start):
        """Write one pow2 prompt chunk into every member's ``slot``."""
        if self.paged:
            self.pool_dev = self._chunk_paged(
                self.tier.values, self.pool_dev, jnp.asarray(tokens),
                jnp.asarray(self.pool.table[slot]), jnp.int32(start),
            )
        else:
            self.caches = self.tier._prefill_chunk(
                self.tier.values, self.caches, jnp.asarray(tokens),
                jnp.int32(slot), jnp.int32(start),
            )

    def verify_draft(self, tokens, slot, start, max_chunk):
        """Score the verify chunk ``[prompt[-1], d_0..d_{T-1}]`` at
        absolute positions ``[start, start + len(tokens))`` and return
        every member's decode-equivalent choices, (E, len(tokens)) host
        int32.  Runs in the SAME pow2 buckets as chunked prefill
        (``prompt_chunks``), so no new program shapes trace per request;
        choices stay on device across chunks and come back in ONE metered
        fetch."""
        key = self.slot_keys[slot]
        outs = []
        off = 0
        for c in prompt_chunks(len(tokens), max_chunk):
            chunk = jnp.asarray(tokens[off : off + c])
            if self.paged:
                t, self.pool_dev = self._verify_paged(
                    self.tier.values, self.pool_dev, chunk,
                    jnp.asarray(self.pool.table[slot]),
                    jnp.int32(start + off), key,
                )
            else:
                t, self.caches = self.tier._verify_chunk(
                    self.tier.values, self.caches, chunk,
                    jnp.int32(slot), jnp.int32(start + off), key,
                )
            outs.append(t)
            off += c
        return np.concatenate(host_fetch(tuple(outs)), axis=1)

    def reset_slot(self, slot):
        """Zero the slot's constant-state leaves across all members."""
        if getattr(self.tier, "_reset_slot", None) is not None:
            self.caches = self.tier._reset_slot(self.caches, jnp.int32(slot))
