"""BENCHMARK.json against the contract's names and units, and every file
that it names found by name."""
import copy
import os

import pytest

from bench import spec


def test_names_and_units_validate():
    spec.check_names(spec.benchmark())


@pytest.mark.parametrize("field,bad", [
    ("name", "has space"), ("name", "a,b"), ("name", "a/b"), ("name", "x" * 65),
    ("unit", "tokens per second"), ("unit", "µs"),
])
def test_bad_names_and_units_are_refused(field, bad):
    s = copy.deepcopy(spec.benchmark())
    s["per_layer"][0][field] = bad
    with pytest.raises(ValueError):
        spec.check_names(s)


def test_every_cell_resolves_and_reports_what_it_must():
    s = spec.benchmark()
    e2e = {m["name"] for m in s["end_to_end"]}
    for wl in s["workloads"]:
        _, config, mix = spec.cell(wl["name"], s)
        assert config["name"] == wl["config"]
        names = {m["name"] for m in spec.metrics_of(wl["name"], s, "end_to_end")}
        assert "setup_s" in names and len(names) >= 2
        assert spec.metrics_of(wl["name"], s, "per_layer")
        for m in spec.metrics_of(wl["name"], s, "per_layer"):
            assert m["moves"] in e2e and m["moves"] in names, (wl["name"], m["name"])


def test_every_per_layer_metric_has_a_reader():
    for m in spec.benchmark()["per_layer"]:
        assert callable(spec.reader(m["name"]))


def test_config_files_hold_the_run_sizes():
    s = spec.benchmark()
    for c in s["configs"]:
        cfg = spec.load_json(os.path.join(spec.ROOT, c["file"]))
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        for t in cfg["tiers"]:
            assert set(t["model"]) >= {"n_layers", "d_model", "d_ff", "vocab_size"}
        for key in cfg["limits"]:
            assert key.startswith("gap.tier")
