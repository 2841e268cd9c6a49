"""Tiny stand-ins for the benchmark's cells, small enough for the CPU."""
import copy

import pytest


def tiny_config(config: dict) -> dict:
    """The configuration at toy widths: 2 layers, d_model 128, 4 slots."""
    c = copy.deepcopy(config)
    for t in c["tiers"]:
        m = t["model"]
        gqa = m["n_kv_heads"] < m["n_heads"]
        m.update(n_layers=2, d_model=128, d_ff=256, n_heads=4,
                 n_kv_heads=2 if gqa else 4, head_dim=32)
        m["vocab_size"] = 2048 if t.get("markers") else 2560
        if t.get("markers"):
            t["markers"] = dict(t["markers"], first=1792, count=256)
    c["serve"] = {"n_slots": 4, "max_seq": 128, "page_size": 16, "max_chunk": 32}
    c["limits"] = {k: 0.05 for k in c["limits"]}
    return c


def tiny_mix(mix: dict) -> dict:
    m = copy.deepcopy(mix)
    m["prompt"] = {"median": 24, "sigma": 0.8, "min": 4, "max": 96}
    m["output"] = {"median": 6, "sigma": 0.8, "min": 2, "max": 32}
    m["easy_ids"], m["hard_ids"] = [0, 1792], [1792, 2048]
    if m["arrival"] == "poisson":
        m["rate_per_s"] = 4.0
    return m


@pytest.fixture
def tiny_cell():
    """(config, mix) of a cell at toy size."""
    from bench import spec

    def make(name):
        _, config, mix = spec.cell(name, spec.benchmark())
        return tiny_config(config), tiny_mix(mix)

    return make


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    """Tests keep nothing in the checkout's compile cache."""
    from bench import run

    monkeypatch.setattr(run, "use_compile_cache", lambda: None)
