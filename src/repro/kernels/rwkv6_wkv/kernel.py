"""RWKV6 WKV — Pallas TPU kernel (chunked, data-dependent per-channel decay).

Grid (B, H, num_chunks); the chunk dimension is sequential and carries the
(D × D) state in VMEM scratch.

Tiling note (TPU adaptation recorded in DESIGN.md): unlike Mamba2's scalar
per-head decay, RWKV6 decays **per key channel**, so the intra-chunk decay
cannot be folded into an (L × L) matrix — the exact pairwise form is an
(L, L, D) tensor.  We keep the chunk short (L=32) so that tensor is a
256 KiB VMEM tile computed on the VPU, while the three big contractions
(A@V, r·e^{ecum}@S, (k·w)ᵀ@V) stay on the MXU.  A production variant would
sub-chunk at 16 with an FLA-style secondary decomposition; L=32 keeps the
kernel readable and is already ~L× fewer HBM round trips than the step scan.
All exponentials are of non-positive numbers.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(
    r_ref,  # (1, 1, L, D)
    k_ref,
    v_ref,
    lw_ref,  # (1, 1, L, D) log decay
    u_ref,  # (1, 1, D)
    s0_ref,  # (1, 1, D, D)
    y_ref,  # (1, 1, L, D)
    sT_ref,  # (1, 1, D, D)
    s_scr,  # (D, D) f32
    *,
    num_chunks: int,
    chunk: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    L = chunk
    r = r_ref[0, 0].astype(jnp.float32)  # (L, D)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)  # (1, D)

    rows = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    # inclusive prefix sum as a lower-triangular ones matmul (the TPU
    # lowering has no cumsum); HIGHEST keeps the f32 log-decays unrounded
    cum = jax.lax.dot_general(
        (cols <= rows).astype(jnp.float32), lw, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # (L, D) inclusive
    ecum = cum - lw  # exclusive

    # pairwise decay (L, L, D) on the VPU; exponents <= 0
    diff = ecum[:, None, :] - cum[None, :, :]
    D = lw.shape[1]
    tri = jax.lax.broadcasted_iota(jnp.int32, (L, L, D), 1) < (
        jax.lax.broadcasted_iota(jnp.int32, (L, L, D), 0)
    )
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)
    # a VPU multiply-reduce: the TPU dot lowering takes no batch-only
    # three-operand contraction
    A = jnp.sum(r[:, None, :] * k[None, :, :] * decay, axis=-1)  # (L, L)
    diag = jnp.sum(r * u * k, axis=-1, keepdims=True)  # (L, 1)
    A = A + jnp.where(rows == cols, diag, 0.0)

    s = s_scr[...]
    y = jax.lax.dot_general(
        A, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    y = y + jax.lax.dot_general(
        r * jnp.exp(ecum), s, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y_ref[0, 0, :, :] = y.astype(y_ref.dtype)

    last = cum[L - 1 : L, :]  # (1, D) static slice: no dynamic_slice on TPU
    w_end = jnp.exp(last - cum)  # (L, D)
    s_scr[...] = s * jnp.exp(last).T + jax.lax.dot_general(
        k * w_end, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ic == num_chunks - 1)
    def _fin():
        sT_ref[0, 0, :, :] = s_scr[...]


@functools.partial(
    jax.jit, static_argnames=("chunk", "return_final_state", "interpret")
)
def wkv6_pallas(
    r: jax.Array,  # (B, S, H, D)
    k: jax.Array,
    v: jax.Array,
    logw: jax.Array,
    u: jax.Array,  # (H, D)
    *,
    chunk: int = 32,
    initial_state: Optional[jax.Array] = None,
    return_final_state: bool = False,
    interpret: bool = False,
):
    B, S, H, D = r.shape
    L = min(chunk, S)
    if S % L != 0:
        raise ValueError(
            f"wkv6 kernel chunking: S={S} is not divisible by chunk L={L} "
            f"(r shape {r.shape})"
        )
    nc = S // L
    tr = lambda a: a.astype(jnp.float32).transpose(0, 2, 1, 3)  # (B,H,S,D)
    s0 = (
        jnp.zeros((B, H, D, D), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )
    kern = functools.partial(_wkv_kernel, num_chunks=nc, chunk=L)
    y, sT = pl.pallas_call(
        kern,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, L, D), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, L, D), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, L, D), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, L, D), lambda b, h, ic: (b, h, ic, 0)),
            # (H, 1, D): a (1, D) block of an (H, D) array would not tile
            pl.BlockSpec((1, 1, D), lambda b, h, ic: (h, 0, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, D), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), r.dtype),
            jax.ShapeDtypeStruct((B, H, D, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(tr(r), tr(k), tr(v), tr(logw), u.astype(jnp.float32)[:, None, :], s0)
    y = y.transpose(0, 2, 1, 3).astype(r.dtype)
    if return_final_state:
        return y, sT
    return y
