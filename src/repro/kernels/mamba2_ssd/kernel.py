"""Mamba2 SSD — Pallas TPU kernel (chunked state-space dual).

Grid (B, H, num_chunks); the chunk dimension is sequential ('arbitrary') and
carries the (N × P) recurrent state in VMEM scratch.  Per chunk the work is
three MXU matmuls — C@Bᵀ (L×L), scores@X (L×P), Bwᵀ@X (N×P) — over an
(L × max(N,P)) VMEM tile, L=128/256, N,P ∈ {64,128}: all matmul dims are
multiples of the 128-lane MXU tile (P=64 uses half-tile packing).

Numerics: every exponential is of a non-positive cumulative log-decay, so
the dual form is stable at any chunk length.  Inputs arrive pre-scaled
(x~ = dt·x, l = A·dt) from ops.py so the kernel streams four operands.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref,  # (1, 1, L, P)   x~ = dt * x
    l_ref,  # (1, 1, L, 1)   l = A * dt  (<= 0)
    b_ref,  # (1, 1, L, N)
    c_ref,  # (1, 1, L, N)
    h0_ref,  # (1, 1, N, P)  initial state
    y_ref,  # (1, 1, L, P)
    hT_ref,  # (1, 1, N, P)  final state
    h_scr,  # (N, P) f32
    *,
    num_chunks: int,
    chunk: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)  # (L, P)
    l = l_ref[0, 0].astype(jnp.float32)  # (L, 1)
    b = b_ref[0, 0].astype(jnp.float32)  # (L, N)
    c = c_ref[0, 0].astype(jnp.float32)  # (L, N)

    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = cols <= rows
    # inclusive prefix sum as a lower-triangular ones matmul (the TPU
    # lowering has no cumsum); HIGHEST keeps the f32 log-decays unrounded
    cum = jax.lax.dot_general(
        tri.astype(jnp.float32), l, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # (L, 1)
    total = cum[-1:, :]  # (1, 1)

    # intra-chunk
    g = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (L, L)
    diff = cum - cum.T  # (L, L): cum_t - cum_s
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)
    y = jax.lax.dot_general(
        g * decay, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (L, P)

    # inter-chunk (contribution of the carried state)
    h = h_scr[...]
    y = y + jax.lax.dot_general(
        c * jnp.exp(cum), h, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y_ref[0, 0, :, :] = y.astype(y_ref.dtype)

    # state update
    w = jnp.exp(total - cum)  # (L, 1)
    h_scr[...] = h * jnp.exp(total[0, 0]) + jax.lax.dot_general(
        b * w, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (N, P)

    @pl.when(ic == num_chunks - 1)
    def _fin():
        hT_ref[0, 0, :, :] = h_scr[...]


@functools.partial(
    jax.jit, static_argnames=("chunk", "return_final_state", "interpret")
)
def ssd_pallas(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H)
    A: jax.Array,  # (H,)
    Bm: jax.Array,  # (B, S, G, N)
    Cm: jax.Array,  # (B, S, G, N)
    *,
    chunk: int = 128,
    initial_state: Optional[jax.Array] = None,
    return_final_state: bool = False,
    interpret: bool = False,
):
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    L = min(chunk, S)
    if S % L != 0:
        raise ValueError(
            f"ssd kernel chunking: S={S} is not divisible by chunk L={L} "
            f"(x shape {x.shape})"
        )
    nc = S // L

    xt = (x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]).transpose(
        0, 2, 1, 3
    )  # (B, H, S, P)
    lt = (A.astype(jnp.float32)[None, None, :] * dt.astype(jnp.float32)).transpose(
        0, 2, 1
    )[..., None]  # (B, H, S, 1)
    bt = Bm.astype(jnp.float32).transpose(0, 2, 1, 3)  # (B, G, S, N)
    ct = Cm.astype(jnp.float32).transpose(0, 2, 1, 3)
    h0 = (
        jnp.zeros((B, H, N, P), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )

    kern = functools.partial(_ssd_kernel, num_chunks=nc, chunk=L)
    y, hT = pl.pallas_call(
        kern,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, L, 1), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, L, N), lambda b, h, ic: (b, h // rep, ic, 0)),
            pl.BlockSpec((1, 1, L, N), lambda b, h, ic: (b, h // rep, ic, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xt, lt, bt, ct, h0)
    y = y.transpose(0, 2, 1, 3)  # (B, S, H, P)
    if return_final_state:
        return y, hT
    return y
