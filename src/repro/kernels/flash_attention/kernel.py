"""Flash attention (prefill) — Pallas TPU kernel.

Layout: q (B, H, Sq, hd), k/v (B, KVH, Sk, hd); GQA handled in the k/v
index_map (`h // group`) so grouped heads stream the same KV block from HBM
once per q-head — no expanded KV is ever materialized.

Tiling: (block_q × hd) query tile and (block_k × hd) KV tile live in VMEM;
the running max / denominator / accumulator live in VMEM scratch across the
sequential k-block grid dimension (online softmax).  block sizes default to
256×512 with hd in {64, 128} — MXU-aligned (multiples of 128 on the matmul
dims) and < 4 MiB of VMEM working set per core.

Supports: causal masking, sliding-window masking, logit soft-capping,
bidirectional (encoder) attention, and per-row ``starts`` (the serving
left-pad carve-out).  ``starts`` (B,) int32 rides scalar prefetch (SMEM),
so the per-request mask needs no recompilation per batch; row b never
attends a column < starts[b], and KV blocks wholly below starts[b] are
skipped together with the causal/window-irrelevant blocks via ``pl.when``
(structural work-skipping — left-padded prefill gets cheaper, not just
correct).  Rows that are pure padding (q row < starts[b]) produce zeros.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(
    starts_ref,  # scalar prefetch: (B,) int32 per-row prompt starts
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    softcap: Optional[float],
    block_q: int,
    block_k: int,
    num_k_blocks: int,
    has_starts: bool,
    skip_pad_blocks: bool,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    # read once at kernel top level (pl.when bodies must not touch
    # program_id / prefetch refs in interpret mode on older jax)
    start_b = starts_ref[pl.program_id(0)] if has_starts else None

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k

    # Structural block skipping: causal blocks strictly above the diagonal,
    # blocks entirely left of the sliding window, and blocks wholly below
    # the row's prompt start (left-pad carve-out) contribute nothing.
    relevant = jnp.bool_(True)
    if causal:
        relevant = jnp.logical_and(relevant, k_start <= q_start + block_q - 1)
    if window is not None:
        # newest k position needed for the oldest q row in this tile
        relevant = jnp.logical_and(
            relevant, k_start + block_k - 1 >= q_start - (window - 1)
        )
    if has_starts and skip_pad_blocks:
        relevant = jnp.logical_and(relevant, k_start + block_k - 1 >= start_b)

    @pl.when(relevant)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (block_q, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (block_k, hd)
        v = v_ref[0, 0].astype(jnp.float32)  # (block_k, hd)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (block_q, block_k)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)

        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.bool_(True)
        if causal:
            mask = jnp.logical_and(mask, cols <= rows)
        if window is not None:
            mask = jnp.logical_and(mask, rows - cols < window)
        if has_starts:
            mask = jnp.logical_and(mask, cols >= start_b)
        if causal or window is not None or has_starts:
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]  # (block_q, 1)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # (block_q, block_k)
        if has_starts:
            # fully-masked rows (pure left-padding) must stay at l == 0 so
            # _finalize emits zeros; without this, m_new == NEG_INF makes
            # exp(s - m_new) == 1 for every masked column
            p = jnp.where(mask, p, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)  # rows with no valid k (padding only)
        o_ref[0, 0, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)


def starts_block_counts(
    Sq: int,
    Sk: int,
    starts,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 256,
    block_k: int = 512,
):
    """(blocks_swept_with_skip, blocks_swept_without) per q/KV block pair,
    summed over the batch — a host-side mirror of ``_flash_kernel``'s exact
    ``relevant`` predicate, so the ratio is the kernel's structural
    block-skip win on a given starts pattern (deterministic, unlike
    interpret-mode wall clock on a shared CPU).  The skipped blocks are
    fully masked, so skip on/off is bitwise identical (tested)."""
    import numpy as np

    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    nq, nk = Sq // block_q, Sk // block_k
    q_start = np.arange(nq)[:, None] * block_q  # (nq, 1)
    k_start = np.arange(nk)[None, :] * block_k  # (1, nk)
    rel = np.ones((nq, nk), bool)
    if causal:
        rel &= k_start <= q_start + block_q - 1
    if window is not None:
        rel &= k_start + block_k - 1 >= q_start - (window - 1)
    starts = np.asarray(starts)
    with_skip = int(
        (rel[None] & (k_start[None] + block_k - 1 >= starts[:, None, None])).sum()
    )
    without = int(rel.sum()) * len(starts)
    return with_skip, without


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "softcap", "block_q", "block_k", "interpret",
        "skip_pad_blocks",
    ),
)
def flash_attention_bhsd(
    q: jax.Array,  # (B, H, Sq, hd)
    k: jax.Array,  # (B, KVH, Sk, hd)
    v: jax.Array,
    starts: Optional[jax.Array] = None,  # (B,) int32 per-row prompt starts
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool = False,
    skip_pad_blocks: bool = True,
) -> jax.Array:
    """``starts`` rides scalar prefetch: None keeps the starts-free program
    (zeros are prefetched but never read).  ``skip_pad_blocks=False`` keeps
    the per-row mask but disables the below-start block skipping — the
    no-skip baseline bench_kernels measures the structural win against."""
    B, H, Sq, hd = q.shape
    _, KVH, Sk, _ = k.shape
    group = H // KVH
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    if Sq % block_q != 0 or Sk % block_k != 0:
        raise ValueError(
            f"flash kernel BlockSpec tiling: Sq={Sq}/Sk={Sk} must divide "
            f"block_q={block_q}/block_k={block_k} (q {q.shape}, k {k.shape})"
        )
    nq, nk = Sq // block_q, Sk // block_k
    scale = 1.0 / math.sqrt(hd)

    has_starts = starts is not None
    starts_arr = (
        jnp.asarray(starts, jnp.int32)
        if has_starts
        else jnp.zeros((B,), jnp.int32)
    )

    kern = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        window=window,
        softcap=softcap,
        block_q=block_q,
        block_k=block_k,
        num_k_blocks=nk,
        has_starts=has_starts,
        skip_pad_blocks=skip_pad_blocks,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, nq, nk),
        # index_maps receive the scalar-prefetch ref as a trailing argument
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, hd), lambda b, h, iq, ik, starts: (b, h, iq, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, hd),
                lambda b, h, iq, ik, starts: (b, h // group, ik, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, hd),
                lambda b, h, iq, ik, starts: (b, h // group, ik, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, hd), lambda b, h, iq, ik, starts: (b, h, iq, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(starts_arr, q, k, v)
