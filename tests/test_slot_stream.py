"""SlotStream equivalence harness.

The unified slot state machine (serve/slot_stream.py) must be *semantics-
free* infrastructure: for every model family and every ensemble width E,
a request served through a SlotStream — mid-stream admission, chunked
prefill, slot reuse and all — must emit exactly the tokens the same request
produces alone through the batch ``generate`` path (greedy).  Three
contracts are pinned here:

(a) stream == solo generate, per family x E in {1, 3};
(b) chunked-prefill admission == decode-only admission, token for token;
(c) back-to-back requests through a REUSED slot == fresh-engine runs for
    constant-state families (the slot state reset that lifts the
    attention-families-only restriction).
"""
import copy
import functools
import math

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import ensemble as ens
from repro.core.cascade import TierSpec, prompt_chunks
from repro.models import api
from repro.models.params import unbox
from repro.serve import (
    CascadeServer,
    CascadeTier,
    PagePool,
    Request,
    ServingEngine,
    SlotStream,
    TierBackend,
)

# every Observability these tests build gets a recording tracer; its
# stream is schema-validated at teardown (tests/conftest.py)
pytestmark = pytest.mark.usefixtures("trace_validation")

_BASE = dict(n_layers=2, d_model=64, d_ff=128, vocab_size=64, remat=False)
CONFIGS = {
    "dense": ModelConfig(
        name="ss-dense", family="dense", n_heads=4, n_kv_heads=2, **_BASE
    ),
    # capacity_factor >= n_experts -> no token ever drops, so MoE routing is
    # per-token independent and every admission path is exactly equivalent
    "moe": ModelConfig(
        name="ss-moe", family="moe", n_heads=4, n_kv_heads=2, n_experts=4,
        top_k=2, capacity_factor=4.0, **_BASE
    ),
    "moe_interleaved": ModelConfig(
        name="ss-moe-il", family="moe", n_heads=4, n_kv_heads=2, n_experts=4,
        top_k=2, moe_every=2, capacity_factor=4.0, **_BASE
    ),
    "ssm_mamba2": ModelConfig(
        name="ss-mamba", family="ssm_mamba2", ssm_state=16, ssm_head_dim=32,
        **_BASE
    ),
    "ssm_rwkv6": ModelConfig(
        name="ss-rwkv", family="ssm_rwkv6", ssm_head_dim=32, rwkv_lora_rank=8,
        **_BASE
    ),
    "hybrid": ModelConfig(
        name="ss-hybrid", family="hybrid", n_heads=4, n_kv_heads=2,
        ssm_state=16, ssm_head_dim=32, attn_every=2, **_BASE
    ),
}
FAMILIES = list(CONFIGS)
CONSTANT_STATE = ["ssm_mamba2", "ssm_rwkv6", "hybrid"]


@pytest.fixture(scope="module")
def stacks():
    return {
        f: unbox(ens.init_ensemble(cfg, 3, jax.random.PRNGKey(i)))[0]
        for i, (f, cfg) in enumerate(CONFIGS.items())
    }


def _requests(seed, n, *, lo=4, hi=20, max_new=(2, 5)):
    rng = np.random.default_rng(seed)
    return [
        Request(
            tokens=rng.integers(0, 64, int(rng.integers(lo, hi))).astype(np.int32),
            max_new_tokens=int(rng.integers(*max_new)),
        )
        for _ in range(n)
    ]


# ---------------------------------------------------------------------------
# (a) stream == solo generate, per family x E
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("E", [1, 3])
@pytest.mark.parametrize("family", FAMILIES)
def test_stream_matches_solo_generate(family, E, stacks):
    cfg = CONFIGS[family]
    reqs = _requests(seed=100 + E, n=5)
    if E == 1:
        member = ens.take_member(stacks[family], 0)
        eng = ServingEngine(cfg, member, max_seq=64)
        done = eng.serve_continuous(
            [copy.deepcopy(r) for r in reqs], n_slots=2
        )
        assert eng.last_stream_stats["chunk_calls"] > 0, (
            "chunked-prefill admission must be exercised"
        )
        ref_eng = ServingEngine(cfg, member)
        by_rid = {d.rid: d for d in done}
        assert sorted(by_rid) == sorted(r.rid for r in reqs)
        for r in reqs:
            ref = ref_eng.generate(r.tokens[None, :], r.max_new_tokens)[0]
            np.testing.assert_array_equal(ref, by_rid[r.rid].output)
    else:
        tier = CascadeTier(cfg, stacks[family], TierSpec("t", "vote", 0.67, k=3))
        stream = SlotStream(
            TierBackend(tier, n_slots=2, max_seq=64), n_slots=2, max_seq=64
        )
        stream.submit([copy.deepcopy(r) for r in reqs])
        got = {r.rid: gen for r, gen in stream.drain()}
        assert stream.stats["chunk_calls"] > 0
        assert sorted(got) == sorted(r.rid for r in reqs)
        for r in reqs:
            # every member's stream row == that member's vmapped generation
            ref = tier.generate(r.tokens[None, :], r.max_new_tokens)  # (E,1,T)
            np.testing.assert_array_equal(ref[:, 0, :], got[r.rid])


# ---------------------------------------------------------------------------
# (b) chunked-prefill admission == decode-only admission
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", FAMILIES)
def test_chunked_matches_decode_only_admission(family, stacks):
    cfg = CONFIGS[family]
    member = ens.take_member(stacks[family], 0)
    eng = ServingEngine(cfg, member, max_seq=64)
    # include a prompt long enough to need several pow2 buckets
    reqs = _requests(seed=7, n=4, lo=4, hi=16)
    reqs.append(
        Request(
            tokens=np.random.default_rng(8).integers(0, 64, 33).astype(np.int32),
            max_new_tokens=4,
        )
    )
    chunked = eng.serve_continuous(
        [copy.deepcopy(r) for r in reqs], n_slots=2, chunked_prefill=True
    )
    assert eng.last_stream_stats["chunk_tokens"] >= 32
    plain = eng.serve_continuous(
        [copy.deepcopy(r) for r in reqs], n_slots=2, chunked_prefill=False
    )
    assert eng.last_stream_stats["chunk_calls"] == 0
    a = {r.rid: r for r in chunked}
    b = {r.rid: r for r in plain}
    assert sorted(a) == sorted(b)
    for rid in a:
        np.testing.assert_array_equal(a[rid].output, b[rid].output)


# ---------------------------------------------------------------------------
# (c) slot reuse isolation for constant-state families
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunked", [True, False])
@pytest.mark.parametrize("family", CONSTANT_STATE)
def test_slot_reuse_matches_fresh_engine(family, chunked, stacks):
    """n_slots=1 forces every request back-to-back through the SAME slot;
    outputs must equal fresh-engine runs, proving the admitted slot's state
    leaves are zeroed (SSM/RWKV state is not pos-masked)."""
    cfg = CONFIGS[family]
    assert api.has_slot_state(cfg)
    member = ens.take_member(stacks[family], 0)
    eng = ServingEngine(cfg, member, max_seq=64)
    reqs = _requests(seed=21, n=3, max_new=(3, 5))
    done = eng.serve_continuous(
        [copy.deepcopy(r) for r in reqs], n_slots=1, chunked_prefill=chunked
    )
    by_rid = {d.rid: d for d in done}
    ref_eng = ServingEngine(cfg, member)
    for r in reqs:
        ref = ref_eng.generate(r.tokens[None, :], r.max_new_tokens)[0]
        np.testing.assert_array_equal(ref, by_rid[r.rid].output)


# ---------------------------------------------------------------------------
# chunk sizing: exact pow2 cover from the O(log S) bucket set
# ---------------------------------------------------------------------------


def test_prompt_chunks_exact_pow2_cover():
    for n in (1, 2, 3, 7, 8, 20, 255, 256, 257, 1000):
        sizes = prompt_chunks(n, max_chunk=256)
        assert sum(sizes) == n, "prompt chunks must tile exactly (no overshoot)"
        assert all(c & (c - 1) == 0 for c in sizes), "chunks must be pow2"
        assert all(c <= 256 for c in sizes)
    # a 256-token prompt admits in <= ceil(log2(256)) bucket calls
    assert len(prompt_chunks(255)) <= math.ceil(math.log2(256))


# ---------------------------------------------------------------------------
# force-complete: the cache wall sets the truncated flag
# ---------------------------------------------------------------------------


def test_truncated_flag_on_cache_wall(stacks):
    cfg = CONFIGS["dense"]
    member = ens.take_member(stacks["dense"], 0)
    eng = ServingEngine(cfg, member, max_seq=16)
    rng = np.random.default_rng(31)
    big = Request(tokens=rng.integers(0, 64, 8).astype(np.int32), max_new_tokens=32)
    small = Request(tokens=rng.integers(0, 64, 8).astype(np.int32), max_new_tokens=2)
    done = {r.rid: r for r in eng.serve_continuous([big, small], n_slots=2)}
    assert done[big.rid].truncated, "hitting pos >= max_seq-1 must flag truncation"
    assert len(done[big.rid].output) < 32
    assert not done[small.rid].truncated
    assert len(done[small.rid].output) == 2


# ---------------------------------------------------------------------------
# block-paged pools: paged serving == dense oracle, conservation, the wall
# ---------------------------------------------------------------------------

PAGED_FAMILIES = [f for f in FAMILIES if api.supports_paging(CONFIGS[f])]
FALLBACK_FAMILIES = [f for f in FAMILIES if not api.supports_paging(CONFIGS[f])]


def _prefix_requests(seed, n, prefix_len, *, tail_hi=12, max_new=(2, 5)):
    """Ragged prompts all sharing the same ``prefix_len``-token prefix."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, 64, prefix_len).astype(np.int32)
    reqs = []
    for _ in range(n):
        tail = rng.integers(0, 64, int(rng.integers(1, tail_hi))).astype(np.int32)
        reqs.append(
            Request(
                tokens=np.concatenate([prefix, tail]),
                max_new_tokens=int(rng.integers(*max_new)),
            )
        )
    return reqs


@pytest.mark.parametrize("E", [1, 3])
@pytest.mark.parametrize("family", PAGED_FAMILIES)
def test_paged_matches_dense_oracle(family, E, stacks):
    """Block-paged serving (page_size=8, prompts sharing a >=2-page prefix,
    ragged fillers) emits bitwise the dense-slot-cache oracle's tokens, the
    prefix index actually shares pages, and every page returns to the free
    list once the stream drains."""
    cfg = CONFIGS[family]
    reqs = _requests(seed=50 + E, n=3, lo=4, hi=20) + _prefix_requests(
        seed=60 + E, n=4, prefix_len=17
    )
    outs = {}
    for paged in (True, False):
        if E == 1:
            member = ens.take_member(stacks[family], 0)
            eng = ServingEngine(cfg, member, max_seq=64)
            stream = eng.slot_stream(n_slots=2, paged=paged, page_size=8)
        else:
            tier = CascadeTier(
                cfg, stacks[family], TierSpec("t", "vote", 0.67, k=3)
            )
            stream = SlotStream(
                TierBackend(
                    tier, n_slots=2, max_seq=64, paged=paged, page_size=8
                ),
                n_slots=2,
                max_seq=64,
            )
        assert stream.backend.paged is paged
        stream.submit([copy.deepcopy(r) for r in reqs])
        outs[paged] = {r.rid: gen for r, gen in stream.drain()}
        if paged:
            pool = stream.backend.pool
            # the 17-token shared prefix spans two full pages; later prefix
            # requests admit while an earlier holder is still resident
            assert pool.stats["shared_hits"] >= 2
            assert stream.stats["shared_tokens"] >= 16
            assert pool.pages_in_use == 0, "drained stream must free all pages"
            pool.assert_conserved()
    assert sorted(outs[True]) == sorted(outs[False])
    for rid in outs[True]:
        np.testing.assert_array_equal(outs[True][rid], outs[False][rid])


_solo_prefill = jax.jit(api.prefill_into_slot_paged, static_argnames="cfg")
_solo_verify = jax.jit(api.prefill_into_slot_paged_logits, static_argnames="cfg")
_solo_decode = jax.jit(api.decode_step_paged, static_argnames="cfg")


@functools.partial(jax.jit, static_argnames="cfg")
def _tier_decode(values, tok, pools, pos, pages, cfg):
    """The member vmap of ``tier_paged_programs.decode_slots``, logits kept."""
    return jax.vmap(
        lambda v, t, pl: api.decode_step_paged(v, t, pl, pos, pages, cfg)
    )(values, tok, pools)


@pytest.mark.parametrize("family", PAGED_FAMILIES)
def test_paged_tier_programs_match_each_member_alone(family):
    """A k=2 tier's paged programs (member vmap around the layer scan) give
    each member bitwise its own logits and pool planes, as that member alone
    through ``api.decode_step_paged`` / ``api.prefill_into_slot_paged``.  The
    members differ in every layer weight, so a program that ran one member's
    layers for both fails here."""
    from repro.serve.cascade_server import tier_paged_programs

    cfg = CONFIGS[family]
    values = unbox(ens.init_ensemble(cfg, 2, jax.random.PRNGKey(15)))[0]
    # norm scales start at one in every member: rescale every layer leaf
    leaves, treedef = jax.tree.flatten(values["layers"])
    keys = jax.random.split(jax.random.PRNGKey(16), len(leaves))
    values["layers"] = jax.tree.unflatten(treedef, [
        (t * jax.random.uniform(k, t.shape, minval=0.5, maxval=1.5)).astype(t.dtype)
        for t, k in zip(leaves, keys)
    ])
    for path, leaf in jax.tree_util.tree_leaves_with_path(values["layers"]):
        assert not np.array_equal(leaf[0], leaf[1]), path
    members = [ens.take_member(values, e) for e in range(2)]

    n_slots, page, n_pg = 2, 8, 4
    pool = unbox(api.init_paged_pool(cfg, n_slots * n_pg + 1, page))[0]
    pools = jax.tree.map(lambda t: np.stack([t, t]), pool)
    pages = np.arange(1, n_slots * n_pg + 1, dtype=np.int32).reshape(n_slots, n_pg)
    rng = np.random.default_rng(15)
    progs = tier_paged_programs(cfg, 0.0)

    def assert_planes(tier_pools, solo_pools):
        for e in range(2):
            for name in ("k", "v"):
                np.testing.assert_array_equal(
                    np.asarray(tier_pools[name][e]), np.asarray(solo_pools[e][name])
                )

    # two prefill chunks of slot 0, one of slot 1
    solo = [pool, pool]
    for slot, start in ((0, 0), (0, 6), (1, 0)):
        tokens = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
        pools = progs.prefill_chunk(values, pools, tokens, pages[slot], start)
        solo = [_solo_prefill(m, tokens, p, pages[slot], start, cfg=cfg)
                for m, p in zip(members, solo)]
        assert_planes(pools, solo)
    if api.supports_draft_verify(cfg):
        tokens = rng.integers(0, cfg.vocab_size, 4).astype(np.int32)
        drafted, _ = progs.verify_chunk(
            values, pools, tokens, pages[1], 6, jax.random.PRNGKey(0)
        )
        for e in range(2):
            logits, _ = _solo_verify(members[e], tokens, solo[e], pages[1], 6, cfg=cfg)
            np.testing.assert_array_equal(
                np.asarray(drafted[e]), np.asarray(logits.argmax(-1))
            )

    pos = np.array([12, 6], np.int32)
    tok = rng.integers(0, cfg.vocab_size, (2, n_slots, 1)).astype(np.int32)
    logits, _ = _tier_decode(values, tok, pools, pos, pages, cfg=cfg)
    nxt, pools = progs.decode_slots(
        values, tok, pools, pos, pages, np.zeros((n_slots, 2), np.uint32)
    )
    for e in range(2):
        solo_logits, solo[e] = _solo_decode(
            members[e], tok[e], solo[e], pos, pages, cfg=cfg
        )
        np.testing.assert_array_equal(np.asarray(logits[e]), np.asarray(solo_logits))
        np.testing.assert_array_equal(
            np.asarray(nxt[e, :, 0]), np.asarray(solo_logits.argmax(-1))
        )
    assert_planes(pools, solo)


def test_paged_pool_wall_forces_completion(stacks):
    """A pool too small for the offered load: admission fails while a slot
    is free (request re-queued), growth fails mid-decode (slot is force-
    completed with truncated=True), everything still completes exactly once
    and the free list ends conserved with zero pages mapped."""
    cfg = CONFIGS["dense"]
    member = ens.take_member(stacks["dense"], 0)
    eng = ServingEngine(cfg, member, max_seq=64)
    rng = np.random.default_rng(71)
    reqs = [
        Request(tokens=rng.integers(0, 64, 9).astype(np.int32), max_new_tokens=40)
        for _ in range(2)
    ]
    # 3 allocatable pages + sink; each prompt needs 2 pages at admission
    stream = eng.slot_stream(n_slots=2, paged=True, page_size=8, n_pages=4)
    stream.submit([copy.deepcopy(r) for r in reqs])
    done = {r.rid: r for r, _ in stream.drain()}
    pool = stream.backend.pool
    assert sorted(done) == sorted(r.rid for r in reqs)
    assert all(d.truncated for d in done.values()), "the wall must truncate"
    assert stream.stats["forced_completions"] == 2
    assert stream.stats["admit_failures"] >= 1
    assert pool.pages_in_use == 0
    pool.assert_conserved()


@pytest.mark.no_trace_validation  # aborts admission: queue_wait stays open
def test_paged_pool_too_small_for_prompt_raises(stacks):
    """A prompt needing more pages than the whole pool can never admit —
    with every slot free that is a configuration error, not a retry."""
    cfg = CONFIGS["dense"]
    member = ens.take_member(stacks["dense"], 0)
    eng = ServingEngine(cfg, member, max_seq=64)
    stream = eng.slot_stream(n_slots=1, paged=True, page_size=8, n_pages=3)
    stream.submit([
        Request(
            tokens=np.arange(17, dtype=np.int32) % 64, max_new_tokens=2
        )
    ])
    with pytest.raises(RuntimeError, match="pool"):
        list(stream.drain())


@pytest.mark.parametrize("family", FALLBACK_FAMILIES)
def test_state_families_fall_back_to_dense(family, stacks):
    """Constant-state families (SSM/RWKV/hybrid) have no paged path yet;
    paged=None must auto-select the dense slot cache and still serve."""
    cfg = CONFIGS[family]
    assert not api.supports_paging(cfg)
    member = ens.take_member(stacks[family], 0)
    eng = ServingEngine(cfg, member, max_seq=64)
    stream = eng.slot_stream(n_slots=2)
    assert stream.backend.paged is False
    reqs = _requests(seed=81, n=2)
    stream.submit([copy.deepcopy(r) for r in reqs])
    done = {r.rid: gen for r, gen in stream.drain()}
    assert sorted(done) == sorted(r.rid for r in reqs)


# ---------------------------------------------------------------------------
# PagePool mechanics (host-side, no model)
# ---------------------------------------------------------------------------


def test_page_pool_admit_share_release_conserves():
    pool = PagePool(8, 4, n_slots=3, max_seq=16)
    toks = list(range(11))  # m=10 -> 2 full pages, 3 pages mapped
    assert pool.admit(0, toks) == 0, "cold admission shares nothing"
    pool.assert_conserved()
    assert pool.admit(1, toks) == 8, "both full prefix pages hit"
    assert pool.stats["shared_hits"] == 2
    assert pool.shared_pages_saved() == 2
    pool.assert_conserved()
    # prompt diverging inside page 1: only page 0 is shareable
    toks2 = list(range(4)) + [63, 62, 61, 60, 59, 58, 57]
    assert pool.admit(2, toks2) == 4
    assert pool.stats["shared_hits"] == 3
    pool.assert_conserved()
    for s in range(3):
        pool.release(s)
    pool.assert_conserved()
    assert pool.pages_in_use == 0
    assert pool.free_pages == 7  # everything but the overflow sink


def test_page_pool_cow_and_unregister_guard_shared_pages():
    """Serving never writes into a registered page (writes start at or past
    the full-page prefix), but the pool still guards the case: a write into
    a multi-owner page COW-splits it, and a solo-owner write unregisters the
    page before it mutates so later admissions cannot share stale content."""
    pool = PagePool(8, 4, n_slots=2, max_seq=16)
    toks = list(range(9))  # m=8: pages 0,1 registered, page 2 private
    pool.admit(0, toks)
    pool.admit(1, toks)
    ok, copies = pool.prepare(1, 5)  # pos 5 -> page index 1, refcount 2
    assert ok and len(copies) == 1
    src, dst = copies[0]
    assert int(pool.table[0, 1]) == src != dst == int(pool.table[1, 1])
    assert pool.stats["cow_copies"] == 1
    pool.assert_conserved()
    pool.release(1)
    ok, copies = pool.prepare(0, 1)  # now solo-owned: no copy, unregister
    assert ok and copies == []
    pool.assert_conserved()
    # the mutated page no longer serves the prefix index: nothing shared
    assert pool.admit(1, toks) == 0
    pool.assert_conserved()
    pool.release(0)
    pool.release(1)
    assert pool.pages_in_use == 0
    pool.assert_conserved()


def test_page_pool_admission_rollback_frees_everything():
    pool = PagePool(4, 4, n_slots=2, max_seq=16)  # 3 allocatable pages
    toks = list(range(8))  # m=7: 1 full page, 2 pages mapped
    assert pool.admit(0, toks) == 0  # takes 2 pages, 1 free
    assert pool.admit(1, toks) == 4  # shares page 0 + allocs 1: 0 free
    pool.release(1)  # back to 1 free page
    # a non-sharing prompt needing 2 fresh pages cannot fit -> full rollback
    assert pool.admit(1, [63] * 8, share=False) is None
    assert pool.stats["admit_failures"] == 1
    assert np.all(pool.table[1] < 0), "failed admission must leave row empty"
    pool.assert_conserved()


# ---------------------------------------------------------------------------
# cascade end-to-end: deferrals admitted mid-stream by the next tier
# ---------------------------------------------------------------------------


def test_cascade_defer_completes_exactly_once(stacks):
    """Tier-0 members are independent (untrained -> essentially never
    agree), so every request is deferred and re-admitted mid-stream into
    tier-1 slots; each must complete exactly once with tier-1's answer.
    Tier-0 is a constant-state RWKV tier — the lifted family restriction
    in action."""
    rw_cfg = CONFIGS["ssm_rwkv6"]
    d_cfg = CONFIGS["dense"]
    tier1 = CascadeTier(
        d_cfg,
        jax.tree.map(lambda v: v[:1], stacks["dense"]),
        TierSpec("t1", "confidence", -1.0, k=1, cost=10.0),
    )
    server = CascadeServer([
        CascadeTier(rw_cfg, stacks["ssm_rwkv6"], TierSpec("t0", "vote", 0.67, k=3)),
        tier1,
    ])
    reqs = _requests(seed=41, n=5, lo=4, hi=10, max_new=(4, 5))
    done = server.serve_continuous(
        [copy.deepcopy(r) for r in reqs], n_slots=2, max_seq=32
    )
    assert sorted(r.rid for r in done) == sorted(r.rid for r in reqs)
    assert all(r.tier == 1 for r in done), "untrained members never agree"
    for r, d in zip(reqs, sorted(done, key=lambda x: x.rid)):
        # the k=1 top tier's answer is member 0's own generation
        ref = tier1.generate(r.tokens[None, :], r.max_new_tokens)[0, 0]
        np.testing.assert_array_equal(ref, d.output)


def test_cascade_agreement_answers_at_tier0(stacks):
    """Identical tier-0 members always agree: nothing reaches tier-1."""
    d_cfg = CONFIGS["dense"]
    one = ens.take_member(stacks["dense"], 0)
    same = jax.tree.map(lambda x: jax.numpy.stack([x, x, x]), one)
    server = CascadeServer([
        CascadeTier(d_cfg, same, TierSpec("t0", "vote", 0.9, k=3)),
        CascadeTier(
            d_cfg,
            jax.tree.map(lambda v: v[:1], stacks["dense"]),
            TierSpec("t1", "confidence", -1.0, k=1),
        ),
    ])
    reqs = _requests(seed=43, n=4, lo=4, hi=10, max_new=(3, 4))
    done = server.serve_continuous(
        [copy.deepcopy(r) for r in reqs], n_slots=2, max_seq=32
    )
    assert sorted(r.rid for r in done) == sorted(r.rid for r in reqs)
    assert all(r.tier == 0 for r in done)
    eng = ServingEngine(d_cfg, one)
    for r, d in zip(reqs, sorted(done, key=lambda x: x.rid)):
        ref = eng.generate(r.tokens[None, :], r.max_new_tokens)[0]
        np.testing.assert_array_equal(ref, d.output)
