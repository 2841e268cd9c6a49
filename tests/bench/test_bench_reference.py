"""The plain reference computes what the served models compute: at toy
size in float32 its logits match the program's own full-sequence forward,
and its float8 control does not."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import serving as D
from bench import reference as R
from bench import weights as W


@pytest.mark.parametrize("tier", [0, 1])
def test_reference_matches_program_forward_in_float32(tiny_cell, tier):
    from repro.models import api

    config, _ = tiny_cell("abc.mixed.backlog")
    t = config["tiers"][tier]
    model = dict(t["model"], dtype="float32")
    w = W.member(W.make_tier(model, 1, 3, tier), 0)
    w32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    toks = np.random.default_rng(0).integers(0, 448, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        prog = api.forward_logits(w32, {"tokens": toks[None]},
                                  D.model_config("t", model))[0]
    ref = R.forward(w, jnp.asarray(toks), model, "f32")
    rel = float(jnp.linalg.norm(prog - ref) / jnp.linalg.norm(ref))
    assert rel < 1e-4, rel
    low = R.forward(w, jnp.asarray(toks), model, "fp8")
    assert float(jnp.linalg.norm(low - ref) / jnp.linalg.norm(ref)) > 1e-2


def test_served_gaps_read_the_served_tokens():
    config = {"tiers": [{"model": dict(
        n_layers=2, d_model=32, d_ff=64, vocab_size=96, n_heads=2, n_kv_heads=2,
        head_dim=16, norm_type="rmsnorm", norm_eps=1e-5, rope_theta=1e4,
        tie_embeddings=False)}]}
    m = config["tiers"][0]["model"]
    w = W.member(W.make_tier(m, 1, 5, 0), 0)
    prompt = np.arange(1, 9, dtype=np.int32)
    seq = list(prompt)
    for _ in range(5):  # the reference's own greedy continuation
        seq.append(int(jnp.argmax(R.forward(w, jnp.asarray(seq), m)[-1])))
    served = np.array(seq[8:], np.int32)
    g, c = R.served_gaps(w, prompt, served, m, pad_to=32, control="fp8")
    assert g == 0.0 and c >= 0.0
    worst = served.copy()
    logits = R.forward(w, jnp.asarray(seq), m)
    worst[2] = int(jnp.argmin(logits[8 + 1]))
    g2, _ = R.served_gaps(w, prompt, worst, m, pad_to=32)
    assert g2 == pytest.approx(float(logits[9].max() - logits[9].min()), rel=1e-5)


def test_float8_control_fails_the_limits_a_sound_run_meets(tiny_cell):
    """The control (the reference in float8 in the program's place) reads
    above each tier's limit on the same requests the program's run meets
    it on, and the harness's own judgement calls it not correct."""
    from bench import calibrate

    config, mix = tiny_cell("abc.mixed.backlog")
    r = calibrate.readings(config, mix, 2**31 + 99, 3.0)
    assert r["correct"] and not r["control_correct"], r
    assert r["checks"]["vote_mismatch"][0] == 0 and r["checks"]["forced"][0] == 0
    for tier, c in r["control_gap"].items():
        g, lim = r["checks"]["gap." + tier]
        assert g <= lim < c, (tier, g, c)
