"""ABC agreement reduce — Pallas TPU kernel (the paper's deferral hot path).

The expensive part of computing vote/score agreement over ensemble logits
(E, B, V) is the sweep over the vocabulary V (up to 256 K classes for the
assigned archs): per member we need max, argmax and log-sum-exp.  This
kernel streams V through VMEM in (block_b × block_v) tiles along the
sequential v-grid dimension, keeping running (m, idx, l) accumulators in
VMEM scratch — one HBM pass instead of the three separate passes XLA emits
for argmax + max + logsumexp.  The tiny O(E²·B) majority-vote epilogue and
the gather of each member's probability for the majority class happen in
ops.py (they are not V-sweeps).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _agree_kernel(
    x_ref,  # (1, block_b, block_v)
    m_ref,  # (1, block_b, 1)  out: max
    i_ref,  # (1, block_b, 1)  out: argmax (int32)
    l_ref,  # (1, block_b, 1)  out: sum exp(x - m)
    m_scr,  # (block_b, 1) f32
    i_scr,  # (block_b, 1) i32
    l_scr,  # (block_b, 1) f32
    *,
    block_v: int,
    num_v_blocks: int,
):
    iv = pl.program_id(2)

    @pl.when(iv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        i_scr[...] = jnp.zeros_like(i_scr)
        l_scr[...] = jnp.zeros_like(l_scr)

    x = x_ref[0].astype(jnp.float32)  # (block_b, block_v)
    bm = jnp.max(x, axis=1, keepdims=True)
    bidx = jnp.argmax(x, axis=1).astype(jnp.int32)[:, None] + iv * block_v

    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, bm)
    l_scr[...] = l_prev * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(x - m_new), axis=1, keepdims=True
    )
    i_scr[...] = jnp.where(bm > m_prev, bidx, i_scr[...])
    m_scr[...] = m_new

    @pl.when(iv == num_v_blocks - 1)
    def _fin():
        m_ref[0] = m_scr[...]
        i_ref[0] = i_scr[...]
        l_ref[0] = l_scr[...]


@functools.partial(jax.jit, static_argnames=("block_b", "block_v", "interpret"))
def member_stats_pallas(
    logits: jax.Array,  # (E, B, V)
    *,
    block_b: int = 128,
    block_v: int = 2048,
    interpret: bool = False,
):
    """Per-member (max, argmax, sumexp) over V.  Returns (m, idx, l): (E, B).

    Real vocabularies are multiples of 128 but rarely of ``block_v``
    (50304, 92544, 151936), so V is padded up to a whole number of lane-
    aligned blocks with -inf columns: they never win the max or argmax and
    add exactly 0 to the sum, so the stats are those of the unpadded V."""
    E, B, V = logits.shape
    block_b = min(block_b, B)
    block_v = min(block_v, -(-V // 128) * 128)
    Vp = -(-V // block_v) * block_v
    if B % block_b != 0:
        raise ValueError(
            f"agreement kernel BlockSpec tiling: B={B} is not divisible by "
            f"block_b={block_b} (logits {logits.shape})"
        )
    if Vp != V:
        logits = jnp.pad(
            logits, ((0, 0), (0, 0), (0, Vp - V)), constant_values=-jnp.inf
        )
    nb, nv = B // block_b, Vp // block_v
    kern = functools.partial(_agree_kernel, block_v=block_v, num_v_blocks=nv)
    m, idx, l = pl.pallas_call(
        kern,
        grid=(E, nb, nv),
        in_specs=[
            pl.BlockSpec((1, block_b, block_v), lambda e, ib, iv: (e, ib, iv)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_b, 1), lambda e, ib, iv: (e, ib, 0)),
            pl.BlockSpec((1, block_b, 1), lambda e, ib, iv: (e, ib, 0)),
            pl.BlockSpec((1, block_b, 1), lambda e, ib, iv: (e, ib, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((E, B, 1), jnp.float32),
            jax.ShapeDtypeStruct((E, B, 1), jnp.int32),
            jax.ShapeDtypeStruct((E, B, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_b, 1), jnp.float32),
            pltpu.VMEM((block_b, 1), jnp.int32),
            pltpu.VMEM((block_b, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(logits)
    return m[..., 0], idx[..., 0], l[..., 0]
