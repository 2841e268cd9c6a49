"""ServeConfig consolidation tests (serve/config.py): the config-style and
legacy-kwarg spellings of every serving entrypoint are bitwise equivalent,
mixing them is a TypeError, the deprecation warning fires once per
process, and the engine.py obs-resolution fix lands stream counters in the
engine's own registry."""
import copy
import warnings

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import ensemble as ens
from repro.core.cascade import TierSpec
from repro.models.params import unbox
from repro.obs import Observability
from repro.serve import (
    CascadeServer,
    CascadeTier,
    Request,
    ServeConfig,
    ServingEngine,
)
from repro.serve.config import _reset_legacy_warning, resolve_serve_config

SMALL = ModelConfig(
    name="tiny-s", family="dense", n_layers=2, d_model=64, d_ff=128,
    vocab_size=64, n_heads=4, n_kv_heads=2, remat=False,
)
BIG = ModelConfig(
    name="tiny-b", family="dense", n_layers=3, d_model=96, d_ff=192,
    vocab_size=64, n_heads=4, n_kv_heads=4, remat=False,
)


@pytest.fixture(scope="module")
def stacks():
    v1, _ = unbox(ens.init_ensemble(SMALL, 3, jax.random.PRNGKey(0)))
    v2, _ = unbox(ens.init_ensemble(BIG, 1, jax.random.PRNGKey(1)))
    return v1, v2


def _requests(n=6, seed=0, vocab=64, max_new=(2, 5)):
    rng = np.random.default_rng(seed)
    return [
        Request(
            tokens=rng.integers(0, vocab, int(rng.integers(4, 12))).astype(np.int32),
            max_new_tokens=int(rng.integers(*max_new)),
        )
        for _ in range(n)
    ]


def _by_rid(done):
    return {r.rid: (r.tier, r.truncated, r.output.tolist()) for r in done}


# -- resolution mechanics ---------------------------------------------------


def test_mixing_config_and_legacy_is_typeerror(stacks):
    v1, _ = stacks
    eng = ServingEngine(SMALL, ens.take_member(v1, 0), max_seq=64)
    with pytest.raises(TypeError, match="not both"):
        eng.serve_continuous(_requests(1), ServeConfig(n_slots=2), n_slots=4)
    with pytest.raises(TypeError, match="not both"):
        eng.slot_stream(ServeConfig(), chunked_prefill=False)


def test_deprecation_warning_fires_once_per_process():
    _reset_legacy_warning()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        resolve_serve_config(None, "caller_a", n_slots=4)
        resolve_serve_config(None, "caller_b", n_slots=2)
    deps = [x for x in w if issubclass(x.category, DeprecationWarning)]
    assert len(deps) == 1 and "ServeConfig" in str(deps[0].message)
    # config-style resolution never warns
    _reset_legacy_warning()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        resolve_serve_config(ServeConfig(n_slots=4), "caller_c")
    assert not [x for x in w if issubclass(x.category, DeprecationWarning)]


def test_legacy_kwargs_map_onto_the_same_fields():
    _reset_legacy_warning()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        cfg = resolve_serve_config(
            None, "caller", n_slots=3, max_seq=128, chunked_prefill=False,
            page_size=8,
        )
    assert cfg == ServeConfig(
        n_slots=3, max_seq=128, chunked_prefill=False, page_size=8
    )
    # max_seq=None resolves to the caller's historical default, a set
    # max_seq survives untouched
    assert ServeConfig().with_max_seq_default(512).max_seq == 512
    assert cfg.with_max_seq_default(512).max_seq == 128


# -- bitwise equivalence: old spelling vs config spelling -------------------


def test_engine_serve_continuous_old_vs_config_bitwise(stacks):
    v1, _ = stacks
    member = ens.take_member(v1, 0)
    reqs = _requests(6, seed=5)
    eng_a = ServingEngine(SMALL, member, max_seq=64)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        done_a = eng_a.serve_continuous(
            [copy.deepcopy(r) for r in reqs], n_slots=3, chunked_prefill=True
        )
    stats_a = dict(eng_a.last_stream_stats)
    eng_b = ServingEngine(SMALL, member, max_seq=64)
    done_b = eng_b.serve_continuous(
        [copy.deepcopy(r) for r in reqs],
        ServeConfig(n_slots=3, chunked_prefill=True),
    )
    assert _by_rid(done_a) == _by_rid(done_b)
    assert stats_a == dict(eng_b.last_stream_stats)


def test_cascade_serve_continuous_old_vs_config_bitwise(stacks):
    v1, v2 = stacks

    def server():
        return CascadeServer([
            CascadeTier(SMALL, v1, TierSpec("t1", "vote", 0.67, k=3, cost=1.0)),
            CascadeTier(BIG, v2, TierSpec("t2", "confidence", -1.0, k=1,
                                          cost=50.0)),
        ])

    reqs = _requests(6, seed=6)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        done_a = server().serve_continuous(
            [copy.deepcopy(r) for r in reqs], n_slots=4, max_seq=64, seed=3
        )
    done_b = server().serve_continuous(
        [copy.deepcopy(r) for r in reqs],
        ServeConfig(n_slots=4, max_seq=64, seed=3),
    )
    assert _by_rid(done_a) == _by_rid(done_b)


def test_slot_stream_old_vs_config_bitwise(stacks):
    v1, _ = stacks
    member = ens.take_member(v1, 0)
    reqs = _requests(5, seed=7)
    eng = ServingEngine(SMALL, member, max_seq=64)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        st_a = eng.slot_stream(n_slots=2, max_seq=48)
    st_b = eng.slot_stream(ServeConfig(n_slots=2, max_seq=48))
    ra = [copy.deepcopy(r) for r in reqs]
    rb = [copy.deepcopy(r) for r in reqs]
    st_a.submit(ra)
    st_b.submit(rb)
    out_a = {r.rid: g.tolist() for r, g in st_a.drain()}
    out_b = {r.rid: g.tolist() for r, g in st_b.drain()}
    assert out_a == out_b
    assert dict(st_a.stats) == dict(st_b.stats)


# -- the engine.py obs-resolution fix ---------------------------------------


def test_engine_stream_obs_lands_in_engine_registry(stacks):
    """Regression (ISSUE 9 satellite): with no bundle passed,
    ``serve_continuous`` must wire the stream into the ENGINE's registry —
    the old code passed the raw ``obs=None`` through, so stream counters
    vanished into a private bundle nobody could read."""
    v1, _ = stacks
    eng = ServingEngine(SMALL, ens.take_member(v1, 0), max_seq=64)
    done = eng.serve_continuous(_requests(4, seed=8), ServeConfig(n_slots=2))
    assert len(done) == 4
    names = eng.obs.registry.names()
    assert "slot_stream.admitted" in names, names
    assert eng.obs.registry.value("slot_stream.admitted") == 4
    assert eng.obs.registry.value("slot_stream.decode_tokens") > 0
    # the run's latency histogram lands there too
    h = eng.obs.registry.get("serve.request_latency_s")
    assert h is not None and h.count == 4


def test_engine_explicit_obs_still_wins(stacks):
    """An explicitly-passed bundle keeps precedence over the engine's."""
    v1, _ = stacks
    eng = ServingEngine(SMALL, ens.take_member(v1, 0), max_seq=64)
    ob = Observability()
    eng.serve_continuous(_requests(3, seed=9), ServeConfig(n_slots=2, obs=ob))
    assert ob.registry.value("slot_stream.admitted") == 3
    assert eng.obs.registry.get("slot_stream.admitted") is None


def test_engine_last_stream_stats_stay_per_run(stacks):
    """Shared-registry counters are cumulative across serves on one
    engine; the legacy ``last_stream_stats`` contract is per-run deltas —
    a second serve must not inherit the first one's totals."""
    v1, _ = stacks
    eng = ServingEngine(SMALL, ens.take_member(v1, 0), max_seq=64)
    eng.serve_continuous(_requests(4, seed=10), ServeConfig(n_slots=2))
    first = dict(eng.last_stream_stats)
    eng.serve_continuous(_requests(2, seed=11), ServeConfig(n_slots=2))
    second = dict(eng.last_stream_stats)
    assert first["admitted"] == 4 and second["admitted"] == 2
    # but the registry keeps the running total
    assert eng.obs.registry.value("slot_stream.admitted") == 6
