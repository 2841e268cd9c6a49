"""The marker construction and the whole run at toy size on the CPU: easy
prompts are answered at tier 0 and hard prompts defer through the real
vote; a run with the timed path intact is correct, and each fault planted
under it makes ``correct`` false."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import faults
from bench import run as RUN
from bench import serving as D
from bench import spec
from bench import weights as W

CELL = "abc.mixed.backlog"
SEED = 2**31 + 1234


def test_members_share_all_but_the_marker_rows(tiny_cell):
    config, _ = tiny_cell(CELL)
    t = config["tiers"][0]
    model = t["model"]
    vals = W.make_tier(model, 2, SEED, 0, *D.markers(t))
    ids = np.array(D.markers(t)[0])
    emb = np.asarray(vals["embed"], np.float32)
    others = np.setdiff1d(np.arange(emb.shape[1]), ids)
    assert np.array_equal(emb[0][others], emb[1][others])
    assert not np.array_equal(emb[0][ids], emb[1][ids])
    for a in jax.tree.leaves(vals["layers"]):
        assert np.array_equal(np.asarray(a[0]), np.asarray(a[1]))
    # member 1 alone is member 1 of the pair
    one = W.make_tier(model, 2, SEED, 0, *D.markers(t), members=(1,))
    assert np.array_equal(np.asarray(one["embed"][0]), np.asarray(vals["embed"][1]))
    # the rows are small: a marker's row norm is a quarter of a normal row's
    assert np.linalg.norm(emb[0][ids], axis=1).mean() < 0.3 * np.linalg.norm(
        emb[0][others], axis=1).mean()


def test_weights_have_the_programs_layout(tiny_cell):
    from repro.core import ensemble as ens
    from repro.models.params import unbox

    config, _ = tiny_cell(CELL)
    for i, t in enumerate(config["tiers"]):
        cfg = D.model_config(t["name"], t["model"])
        want = jax.eval_shape(lambda: unbox(ens.init_ensemble(cfg, t["k"], jax.random.PRNGKey(0)))[0])
        got = jax.eval_shape(lambda: D.tier_weights(config, i, SEED))
        assert jax.tree.structure(want) == jax.tree.structure(got)
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_easy_answered_at_tier0_hard_deferred(tiny_cell):
    config, mix = tiny_cell(CELL)
    server = D.build_server(config, SEED)
    D.warm_up(server, config, mix)
    run = D.new_run(server, config)
    win = D.closed_loop(run, mix, SEED, 3.0, config["serve"]["n_slots"])
    done = [win.requests[r] for r in win.done_at]
    hard = [r for r in done if win.hard[r.rid]]
    easy = [r for r in done if not win.hard[r.rid]]
    assert len(hard) >= 3 and len(easy) >= 10
    assert all(r.tier == 1 for r in hard) and all(r.tier == 0 for r in easy)
    c = D.stream_counters(run)
    assert c["cascade.tier0.deferred"] == sum(
        1 for r in win.requests.values() if win.hard[r.rid] and r.tier == 1
    ) + sum(1 for st in run.streams[1:] for q in st.slot_req if q is not None) + len(run.streams[1].queue)


def run_cell(tiny_cell, capsys, cell=CELL, trace=0, seconds="3"):
    config, mix = tiny_cell(cell)
    rc = RUN.main(["--workload", cell, "--seed", str(SEED), "--seconds", seconds,
                   "--trace", str(trace)], require_tpu=False, config=config, mix=mix)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


@pytest.mark.parametrize("cell", ["abc.mixed.backlog", "solo.backlog"])
def test_sound_run_is_correct(tiny_cell, capsys, cell):
    res = run_cell(tiny_cell, capsys, cell)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"answered_tok_s", "setup_s"}
    assert res["device"]["count"] == 1 and res["attempted"] > 0 and res["failed"] == 0


def test_traced_open_run_reports_counter_metrics(tiny_cell, capsys, monkeypatch):
    """An open-loop mix, traced: the counter metrics are read, the device
    trace's are left out (no device plane on the CPU; the v5e's peaks
    stand in for the CPU's, which the table does not hold)."""
    from bench import counts

    v5e = counts.peaks("TPU v5 lite")
    monkeypatch.setattr(counts, "peaks", lambda kind: v5e)
    config, mix = tiny_cell(CELL)
    mix = dict(mix, arrival="poisson", rate_per_s=4.0)
    rc = RUN.main(["--workload", CELL, "--seed", str(SEED), "--seconds", "4",
                   "--trace", "1"], require_tpu=False, config=config, mix=mix)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"], res["checks"]
    assert {"compile_s", "decode_ms.tier0.tput", "defer_share.tier0.tput",
            "mfu.tput", "decoded_tok_s.tput"} <= set(res["metrics"])
    assert "idle_share.tput" not in res["metrics"]


def test_hard_requests_check_tier0_members(tiny_cell, capsys, monkeypatch):
    """Where tier 0 answers nothing, its members' generations are still
    checked: altering them makes the run not correct."""
    config, mix = tiny_cell(CELL)
    mix = dict(mix, hard_share=1.0)
    sound = RUN.main(["--workload", CELL, "--seed", str(SEED), "--seconds", "3"],
                     require_tpu=False, config=config, mix=mix)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert sound == 0 and res["correct"] and "gap.tier0" in res["checks"], res["checks"]
    from repro.serve.slot_stream import TierBackend

    orig = TierBackend.decode

    def decode(self, tok, pos):  # alter every token tier 0 produces
        out = orig(self, tok, pos)
        if self.tier.spec.k > 1:
            out = (out + 1) % self.tier.cfg.vocab_size
        return out

    monkeypatch.setattr(TierBackend, "decode", decode)
    RUN.main(["--workload", CELL, "--seed", str(SEED), "--seconds", "3"],
             require_tpu=False, config=config, mix=mix)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is False and res["checks"]["vote_mismatch"]["value"] == 0, res["checks"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_planted_fault_makes_run_incorrect(tiny_cell, capsys, monkeypatch, fault):
    faults.FAULTS[fault](monkeypatch.setattr)
    res = run_cell(tiny_cell, capsys)
    assert res["correct"] is False, res["checks"]


def test_exits_without_result_when_no_tpu(capsys):
    rc = RUN.main(["--workload", CELL, "--seed", "1", "--seconds", "1"])
    assert rc != 0 and capsys.readouterr().out == ""


def test_command_refuses_the_cpu_before_timing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.ROOT, "bench", "run.py"), "--workload",
         CELL, "--seed", "5", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr
