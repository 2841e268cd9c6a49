"""GQA decode attention — Pallas TPU kernel.

One query token per sequence attends over a long KV cache.  The cache is
streamed through VMEM in (block_k × hd) tiles along the sequential grid
dimension; online-softmax accumulators live in VMEM scratch.  All G query
heads of a KV head are processed together, so the logits matmul is
(G × hd) @ (hd × block_k) — G·hd and block_k are the MXU dims (hd ∈ {64,128},
block_k a multiple of 512).

``cur_len`` and ``starts`` are runtime scalars delivered via scalar prefetch
(SMEM) so the masks need no recompilation per step.  Blocks entirely past
``cur_len``, before the sliding window, or wholly below a row's prompt start
(``starts`` — the serving left-pad carve-out) are skipped with ``pl.when``
— the sweep cost is O(cur_len - start), or O(window) with SWA.
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


def _decode_kernel(
    len_ref,  # scalar prefetch: (B,) int32  valid cache length per sequence
    starts_ref,  # scalar prefetch: (B,) int32  per-row prompt starts
    q_ref,  # (1, 1, G, hd)
    k_ref,  # (1, 1, block_k, hd)
    v_ref,  # (1, 1, block_k, hd)
    o_ref,  # (1, 1, G, hd)
    m_scr,  # (G, 1) f32
    l_scr,  # (G, 1) f32
    acc_scr,  # (G, hd) f32
    *,
    scale: float,
    window: Optional[int],
    softcap: Optional[float],
    block_k: int,
    num_k_blocks: int,
    has_starts: bool,
    skip_pad_blocks: bool,
):
    ik = pl.program_id(2)
    cur_len = len_ref[pl.program_id(0)]  # per-sequence (continuous batching)
    start_b = starts_ref[pl.program_id(0)] if has_starts else None

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = ik * block_k
    relevant = k_start < cur_len
    if window is not None:
        relevant = jnp.logical_and(relevant, k_start + block_k > cur_len - window)
    if has_starts and skip_pad_blocks:
        # left-pad carve-out: cache blocks wholly below the row's prompt
        # start hold only pad rows — skip them structurally
        relevant = jnp.logical_and(relevant, k_start + block_k > start_b)

    @pl.when(relevant)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (block_k, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (G, block_k)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = cols < cur_len
        if window is not None:
            mask = jnp.logical_and(mask, cols >= cur_len - window)
        if has_starts:
            mask = jnp.logical_and(mask, cols >= start_b)
        s = jnp.where(mask, s, NEG_INF)

        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if has_starts:
            # a row whose start swallows the whole valid cache must keep
            # l == 0 (zeros out), not exp(NEG_INF - NEG_INF) == 1 weights
            p = jnp.where(mask, p, 0.0)
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "softcap", "interpret"))
def decode_attention_paged_bkgd(
    q: jax.Array,  # (B, KVH, G, hd)
    k_pool: jax.Array,  # (P, KVH, page_size, hd) shared page pool
    v_pool: jax.Array,
    cur_len: jax.Array,  # (B,) int32
    pages: jax.Array,  # (B, n_pg) int32 page table, -1 = unmapped
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Block-paged decode attention: the SAME online-softmax body as
    ``decode_attention_bkgd`` with block_k = page_size and the page table
    riding the second scalar-prefetch slot.  Instead of streaming a dense
    per-slot cache, the K/V index maps dereference ``pages[b, ik]`` so each
    sequential grid step pulls the slot's ik-th page straight out of the
    shared pool — no gathered copy of the cache ever materializes.  Blocks
    past ``cur_len`` (or before the sliding window) are skipped exactly as
    in the dense kernel; an unmapped page with in-length columns can only
    belong to an inactive slot (allocation is a monotone prefix of the
    sequence), whose output the server discards, so the clamped page-0
    fetch is harmless."""
    B, KVH, G, hd = q.shape
    P, KVHp, page_size, hdp = k_pool.shape
    if k_pool.shape != v_pool.shape:
        raise ValueError(f"pool mismatch: k {k_pool.shape} v {v_pool.shape}")
    if (KVHp, hdp) != (KVH, hd):
        raise ValueError(f"pool {k_pool.shape} does not match q {q.shape}")
    if pages.shape[0] != B:
        raise ValueError(f"page table {pages.shape} does not match batch {B}")
    n_pg = pages.shape[1]
    if page_size % 8 != 0:
        raise ValueError(
            f"paged decode BlockSpec tiling: page_size={page_size} is not a "
            f"multiple of the f32 sublane (8); pool {k_pool.shape}"
        )
    scale = 1.0 / math.sqrt(hd)

    # pages_ref occupies the starts slot of the shared body; has_starts=False
    # means it is only ever read by the index maps below
    kern = functools.partial(
        _decode_kernel,
        scale=scale,
        window=window,
        softcap=softcap,
        block_k=page_size,
        num_k_blocks=n_pg,
        has_starts=False,
        skip_pad_blocks=False,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KVH, n_pg),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, ik, lens, pages: (b, h, 0, 0)),
            pl.BlockSpec(
                (1, 1, page_size, hd),
                lambda b, h, ik, lens, pages: (jnp.maximum(pages[b, ik], 0), h, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, page_size, hd),
                lambda b, h, ik, lens, pages: (jnp.maximum(pages[b, ik], 0), h, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, G, hd), lambda b, h, ik, lens, pages: (b, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    lens = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (B,))
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lens, jnp.asarray(pages, jnp.int32), q, k_pool, v_pool)


def starts_block_counts(
    S: int,
    cur_len,
    starts,
    *,
    window: Optional[int] = None,
    block_k: int = 512,
):
    """(blocks_swept_with_skip, blocks_swept_without) summed over the
    batch — a host-side mirror of ``_decode_kernel``'s exact ``relevant``
    predicate; the ratio is the structural block-skip win of the left-pad
    carve-out on a given (cur_len, starts) pattern (deterministic, unlike
    interpret-mode wall clock).  Skipped blocks are fully masked, so skip
    on/off is bitwise identical (tested)."""
    import numpy as np

    block_k = min(block_k, S)
    nk = S // block_k
    k_start = np.arange(nk)[None, :] * block_k  # (1, nk)
    cur = np.broadcast_to(np.asarray(cur_len), np.asarray(starts).shape)
    rel = k_start < cur[:, None]
    if window is not None:
        rel &= k_start + block_k > cur[:, None] - window
    with_skip = int((rel & (k_start + block_k > np.asarray(starts)[:, None])).sum())
    return with_skip, int(rel.sum())


@functools.partial(
    jax.jit,
    static_argnames=("window", "softcap", "block_k", "interpret", "skip_pad_blocks"),
)
def decode_attention_bkgd(
    q: jax.Array,  # (B, KVH, G, hd)
    k_cache: jax.Array,  # (B, KVH, S, hd)
    v_cache: jax.Array,
    cur_len: jax.Array,  # scalar or (B,) int32
    starts: Optional[jax.Array] = None,  # (B,) int32 per-row prompt starts
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_k: int = 512,
    interpret: bool = False,
    skip_pad_blocks: bool = True,
) -> jax.Array:
    """``starts`` rides a second scalar-prefetch ref: None keeps the
    starts-free program (zeros are prefetched but never read).
    ``skip_pad_blocks=False`` keeps the per-row mask but disables the
    below-start block skipping (bench_kernels' no-skip baseline)."""
    B, KVH, G, hd = q.shape
    S = k_cache.shape[2]
    block_k = min(block_k, S)
    if S % block_k != 0:
        raise ValueError(
            f"decode kernel BlockSpec tiling: cache S={S} is not divisible "
            f"by block_k={block_k} (k_cache {k_cache.shape})"
        )
    nk = S // block_k
    scale = 1.0 / math.sqrt(hd)

    has_starts = starts is not None
    kern = functools.partial(
        _decode_kernel,
        scale=scale,
        window=window,
        softcap=softcap,
        block_k=block_k,
        num_k_blocks=nk,
        has_starts=has_starts,
        skip_pad_blocks=skip_pad_blocks,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KVH, nk),
        # index_maps receive the scalar-prefetch refs as trailing arguments
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, ik, lens, starts: (b, h, 0, 0)),
            pl.BlockSpec(
                (1, 1, block_k, hd), lambda b, h, ik, lens, starts: (b, h, ik, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, hd), lambda b, h, ik, lens, starts: (b, h, ik, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, G, hd), lambda b, h, ik, lens, starts: (b, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    lens = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (B,))
    starts_arr = (
        jnp.broadcast_to(jnp.asarray(starts, jnp.int32), (B,))
        if has_starts
        else jnp.zeros((B,), jnp.int32)
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lens, starts_arr, q, k_cache, v_cache)
