"""Deferral compaction — Pallas TPU kernel (ABC's tier-transition hot path).

Routing deferred examples to the next tier is a mask→prefix-sum→scatter:
row i of the payload moves to row ``cumsum(mask)[i]-mask[i]`` of a dense
output iff ``mask[i]``.  Doing this on host (np.flatnonzero + re-pad) drags
the whole activation payload across PCIe twice per tier transition; this
kernel keeps it in HBM.

A per-row dynamic scatter does not vectorize on the VPU, so the kernel
expresses the permutation as a one-hot selection matrix and rides the MXU:

  sel[d, i] = (prefix[i] == d) & mask[i]      # (B, B) one-hot rows
  out       = sel @ payload                   # (B, D) dense compaction

The feature axis D streams through VMEM in ``block_d`` tiles along the
grid; the (B, B) selection matrix is recomputed per tile from the (1, B)
mask — B is a serving batch (≤ a few thousand), so sel is tiny next to the
payload sweep and the payload itself is read exactly once from HBM.  The
first tile also emits the index map (original row index per output row,
-1 past the deferred count) and the scalar count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _compact_kernel(mask_ref, x_ref, out_ref, im_ref, cnt_ref):
    j = pl.program_id(0)
    m = mask_ref[...]  # (1, B) int32
    B = m.shape[1]
    d_iota = jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
    i_iota = jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)
    # exclusive prefix sum as a strictly-upper-triangular ones matmul (the
    # TPU lowering has no cumsum): 0/1 operands, integer sums <= B — exact
    upper = (d_iota < i_iota).astype(jnp.float32)
    prefix = jnp.dot(
        m.astype(jnp.float32), upper, preferred_element_type=jnp.float32
    ).astype(jnp.int32)  # (1, B)
    sel = jnp.logical_and(prefix == d_iota, m == 1)  # (B, B): dest d <- src i
    sel_f = sel.astype(jnp.float32)
    out_ref[...] = jnp.dot(
        sel_f, x_ref[...], preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )

    @pl.when(j == 0)
    def _emit_indices():
        # one-hot rows: sum(sel * (i+1)) - 1 is the source index, -1 if empty
        src = jnp.sum(sel_f * (i_iota + 1).astype(jnp.float32), axis=1, keepdims=True)
        im_ref[...] = src.astype(jnp.int32) - 1  # (B, 1)
        cnt_ref[0, 0] = jnp.sum(m)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def compact_pallas(
    x: jax.Array,  # (B, D) float32 payload
    mask: jax.Array,  # (B,) bool / int32 defer mask
    *,
    block_d: int = 512,
    interpret: bool = False,
):
    """Dense compaction of deferred rows.  Returns (out (B, D) f32,
    index_map (B,) i32, count () i32).  B should be sublane-friendly and
    D lane-friendly — ops.py pads both before calling."""
    B, D = x.shape
    block_d = min(block_d, D)
    if D % block_d != 0:
        raise ValueError(
            f"compaction kernel BlockSpec tiling: D={D} is not divisible "
            f"by block_d={block_d} (payload {x.shape})"
        )
    nd = D // block_d
    m_row = mask.astype(jnp.int32).reshape(1, B)
    out, im, cnt = pl.pallas_call(
        _compact_kernel,
        grid=(nd,),
        in_specs=[
            pl.BlockSpec((1, B), lambda j: (0, 0)),
            pl.BlockSpec((B, block_d), lambda j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((B, block_d), lambda j: (0, j)),
            pl.BlockSpec((B, 1), lambda j: (0, 0)),
            pl.BlockSpec((1, 1), lambda j: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, D), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(m_row, x.astype(jnp.float32))
    return out, im[:, 0], cnt[0, 0]
