"""Plain reference of the served decoder models, written from the published
architectures (OLMo: non-parametric LayerNorm, tied head; InternLM2: RMSNorm,
grouped-query attention, separate head; both SwiGLU MLPs and rotary
positions on split halves).

It reads one member's weights as a nested dict of named arrays, in the
layout that ``bench/weights.py`` makes, and runs a whole sequence at once
with causal attention: no cache, no pages, no chunks, no kernels.  It
imports nothing of the program under test.

Precision modes:

* ``"f32"``: every operand in float32, matmuls at ``HIGHEST``.  This is the
  reference the served tokens are judged against.
* ``"fp8"``: every matmul operand rounded to float8 e4m3 with a per-tensor
  scale (the largest magnitude maps to 448), accumulation in float32.  This
  is the control: the step below bfloat16 that a later change might take.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.weights import frozen

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _fp8(a):
    """Round to float8 e4m3 under a per-tensor scale, back in float32."""
    a = a.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / F8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _operand(mode):
    if mode == "fp8":
        return _fp8
    return lambda a: a.astype(jnp.float32)


def _norm(x, kind, eps, scale=None):
    if kind == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return y * scale.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rope(x, pos, theta):
    """x (S, H, hd): rotate the two halves of each head by position."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def forward(w, tokens, model, mode="f32"):
    """Logits (S, V) in float32 for one member over ``tokens`` (S,)."""
    op = _operand(mode)
    kind, eps = model["norm_type"], float(model["norm_eps"])
    H, K = model["n_heads"], model["n_kv_heads"]
    hd = model["head_dim"] or model["d_model"] // H
    theta = float(model["rope_theta"])
    S = tokens.shape[0]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    x = w["embed"][tokens].astype(jnp.float32)

    def mm(spec, a, b):
        return jnp.einsum(spec, op(a), op(b), precision=HI)

    def layer(x, lw):
        at, ml = lw["attn"], lw["mlp"]
        h = _norm(x, kind, eps, lw["ln1"].get("scale"))
        q = _rope(mm("sd,dhk->shk", h, at["wq"]), pos, theta)
        k = _rope(mm("sd,dhk->shk", h, at["wk"]), pos, theta)
        v = mm("sd,dhk->shk", h, at["wv"])
        q = q.reshape(S, K, H // K, hd) / math.sqrt(hd)
        s = mm("skgd,tkd->kgst", q, k)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        ctx = mm("kgst,tkd->skgd", p, v).reshape(S, H, hd)
        x = x + mm("shk,hkd->sd", ctx, at["wo"])
        h = _norm(x, kind, eps, lw["ln2"].get("scale"))
        g = mm("sd,df->sf", h, ml["w_gate"])
        u = mm("sd,df->sf", h, ml["w_up"])
        x = x + mm("sf,fd->sd", jax.nn.silu(g) * u, ml["w_down"])
        return x, None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = _norm(x, kind, eps, w["final_norm"].get("scale"))
    head = w["lm_head"] if "lm_head" in w else w["embed"].T
    return mm("sd,dv->sv", x, head)


@functools.partial(jax.jit, static_argnames=("model_key", "mode"))
def _gaps(w, tokens, served_from, n_served, model_key, mode):
    """For each position j that produced served token tokens[j+1]:
    (gap of the served token below the f32 reference's best, and the same
    gap of the token that ``mode`` ranks first).  Positions outside the
    served span read 0."""
    model = dict(model_key)
    ref = forward(w, tokens, model, "f32")
    best = jnp.max(ref, -1)
    nxt = jnp.concatenate([tokens[1:], tokens[-1:]])
    served_gap = best - jnp.take_along_axis(ref, nxt[:, None], 1)[:, 0]
    if mode == "f32":
        ctrl_gap = jnp.zeros_like(served_gap)
    else:
        low = forward(w, tokens, model, mode)
        pick = jnp.argmax(low, -1)
        ctrl_gap = best - jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]
    j = jnp.arange(tokens.shape[0])
    keep = (j >= served_from) & (j < served_from + n_served)
    return jnp.where(keep, served_gap, 0.0), jnp.where(keep, ctrl_gap, 0.0)


def served_gaps(w, prompt, served, model, *, pad_to, control=None):
    """Widest gap of ``served`` tokens below the reference's best logit,
    teacher-forced over ``prompt + served`` padded to ``pad_to`` tokens
    (causal attention: padding after the sequence changes nothing before
    it).  With ``control`` ("fp8"), also the widest gap of the tokens the
    lower precision ranks first at the same positions.  Returns
    (served_gap, control_gap or None)."""
    import numpy as np

    seq = np.concatenate([np.asarray(prompt), np.asarray(served)]).astype(np.int32)
    assert len(seq) <= pad_to, (len(seq), pad_to)
    tokens = np.zeros(pad_to, np.int32)
    tokens[: len(seq)] = seq
    sg, cg = _gaps(
        w, jnp.asarray(tokens), len(prompt) - 1, len(served),
        frozen(model), control or "f32",
    )
    sg, cg = jax.device_get((jnp.max(sg), jnp.max(cg)))
    return float(sg), (float(cg) if control else None)
