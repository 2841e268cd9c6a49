"""Seeded weights of a tier, made on the device in one jitted call.

The layout is that of the served decoder (a dict per member, layer weights
stacked on a leading layer axis) with a leading member axis on every leaf.
Every member of a tier gets the same weights except the embedding rows of
the marker token ids: each member draws its own rows there, scaled down by
the tier's ``markers.scale``.  A prompt without markers therefore computes
identically in every member, so the members agree; a prompt with markers
gives each member a different input, so they disagree.  The first norm
removes the rows' scale, and a tied head gives a marker a logit near 0,
which never wins the argmax.

Scales: embedding rows ~ N(0, ``EMBED_STD``); projections ~ N(0, 1/fan_in).
These are the program's own initial scales (``models/params.py``); at
them a float32 and a bfloat16 forward of the full-width models agree to a
few hundredths of a logit (PERF.md), so the served tokens can be checked
against a reference.  What the device does per step does not depend on
the values.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

DTYPE = jnp.bfloat16
EMBED_STD = 0.02


def seed_key(seed: int, *salt: int):
    """A PRNG key from any non-negative integer seed (wider than 32 bits)."""
    key = jax.random.PRNGKey(seed % (2**31))
    for s in (seed // (2**31),) + salt:
        key = jax.random.fold_in(key, s)
    return key


def _shapes(m):
    """(name path, shape, fan_in) of every leaf of one member; the embedding
    has no fan_in."""
    L, D, F, V = m["n_layers"], m["d_model"], m["d_ff"], m["vocab_size"]
    H, K = m["n_heads"], m["n_kv_heads"]
    hd = m["head_dim"] or D // H
    out = [
        (("embed",), (V, D), None),
        (("layers", "attn", "wq"), (L, D, H, hd), D),
        (("layers", "attn", "wk"), (L, D, K, hd), D),
        (("layers", "attn", "wv"), (L, D, K, hd), D),
        (("layers", "attn", "wo"), (L, H, hd, D), H * hd),
        (("layers", "mlp", "w_gate"), (L, D, F), D),
        (("layers", "mlp", "w_up"), (L, D, F), D),
        (("layers", "mlp", "w_down"), (L, F, D), F),
    ]
    if not m["tie_embeddings"]:
        out.append((("lm_head",), (D, V), D))
    return out


def _set(tree, path, value):
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


@functools.partial(jax.jit,
                   static_argnames=("mkey", "members", "markers", "marker_scale"))
def _make(key, mkey, members, markers, marker_scale):
    m = dict(mkey)
    L, D = m["n_layers"], m["d_model"]
    w = {"layers": {"ln1": {}, "ln2": {}}, "final_norm": {}}
    leaves = _shapes(m)
    keys = jax.random.split(key, len(leaves) + 1)
    for kk, (path, shape, fan_in) in zip(keys, leaves):
        std = EMBED_STD if fan_in is None else 1.0 / math.sqrt(fan_in)
        _set(w, path, (jax.random.normal(kk, shape, jnp.float32) * std).astype(DTYPE))
    if m["norm_type"] == "rmsnorm":
        w["layers"]["ln1"]["scale"] = jnp.ones((L, D), jnp.float32)
        w["layers"]["ln2"]["scale"] = jnp.ones((L, D), jnp.float32)
        w["final_norm"]["scale"] = jnp.ones((D,), jnp.float32)
    k = len(members)
    stacked = jax.tree.map(lambda a: jnp.broadcast_to(a, (k,) + a.shape), w)
    if markers:
        ids = jnp.asarray(markers, jnp.int32)
        std = EMBED_STD * marker_scale
        rows = jnp.stack([
            jax.random.normal(jax.random.fold_in(keys[-1], e),
                              (len(markers), D), jnp.float32)
            for e in members
        ]) * std
        stacked["embed"] = stacked["embed"].at[:, ids, :].set(rows.astype(DTYPE))
    return stacked


def frozen(model: dict):
    """The static, hashable form of a model description."""
    return tuple(sorted(
        (k, tuple(sorted(v.items())) if isinstance(v, dict) else v)
        for k, v in model.items()
    ))


def make_tier(model: dict, k: int, seed: int, tier: int, markers=(),
              marker_scale=0.0, members=None):
    """(k, ...) stacked weights of tier ``tier``, on the default device.
    ``members`` picks which of the k members to make (all by default);
    member e is the same whichever others are made with it."""
    members = tuple(range(k)) if members is None else tuple(members)
    return _make(seed_key(seed, tier), frozen(model), members, tuple(markers),
                 float(marker_scale))


def member(values, e: int):
    """Member ``e``'s weights out of a stacked tier."""
    return jax.tree.map(lambda a: a[e], values)
