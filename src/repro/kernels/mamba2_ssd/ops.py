"""Chunked SSD (state-space dual) wrapper.

impl='xla': the chunked dual form in pure jnp — intra-chunk attention-like
matmuls + an inter-chunk state scan.  O(S·L) work with chunk L, vectorized
over (batch, heads) so GSPMD shards it along 'data'/'model' like everything
else.  This is also exactly the math the Pallas kernel implements, with the
state scan living in VMEM scratch instead of a lax.scan carry.

All exponentials are of non-positive numbers (cumulative log-decays), so the
chunked form is numerically safe at any chunk length.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import config as kcfg


def _chunk_quantities(l_chunk):
    """l_chunk: (..., L) per-step log decays (<= 0).
    Returns (cum, total) where cum[t] = sum_{s<=t} l_s."""
    cum = jnp.cumsum(l_chunk, axis=-1)
    total = cum[..., -1:]
    return cum, total


def _xla_ssd(x, dt, A, Bm, Cm, *, chunk, initial_state, return_final_state):
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    L = min(chunk, S)
    if S % L:
        # zero-x / zero-dt padding is exact: decay exp(A·0)=1 and zero input
        # leave the state untouched; padded outputs are discarded
        pad = L - S % L
        p4 = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        p3 = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        out = _xla_ssd(
            p4(x), p3(dt), A, p4(Bm), p4(Cm),
            chunk=chunk, initial_state=initial_state,
            return_final_state=return_final_state,
        )
        if return_final_state:
            return out[0][:, :S], out[1]
        return out[:, :S]
    nc = S // L

    xf = (x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None])  # x~ = dt*x
    lf = A.astype(jnp.float32)[None, None, :] * dt.astype(jnp.float32)  # (B,S,H) <=0
    Bf = Bm.astype(jnp.float32)
    Cf = Cm.astype(jnp.float32)

    # chunked views: (nc, B, L, ...)
    def chunked(a):
        return a.reshape(B, nc, L, *a.shape[2:]).transpose(1, 0, 2, *range(3, a.ndim + 1))

    xc, lc = chunked(xf), chunked(lf)  # (nc,B,L,H,P), (nc,B,L,H)
    Bc, Cc = chunked(Bf), chunked(Cf)  # (nc,B,L,G,N)

    h0 = (
        jnp.zeros((B, H, N, P), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )

    def body(h, inp):
        xk, lk, bk, ck = inp
        cum, total = _chunk_quantities(lk.transpose(0, 2, 1))  # (B,H,L)
        # intra-chunk: scores[t,s] = (C_t . B_s) * exp(cum_t - cum_s) * [s<=t]
        gmat = jnp.einsum("blgn,bsgn->bgls", ck, bk)  # (B,G,L,L)
        gmat = jnp.repeat(gmat, rep, axis=1)  # (B,H,L,L)
        diff = cum[..., :, None] - cum[..., None, :]  # (B,H,L,L)
        tri = jnp.tril(jnp.ones((L, L), bool))
        decay = jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)
        scores = gmat * decay
        xh = xk.transpose(0, 2, 1, 3)  # (B,H,L,P)
        y_intra = jnp.einsum("bhls,bhsp->bhlp", scores, xh)
        # inter-chunk: y_t += exp(cum_t) * C_t . h
        crep = jnp.repeat(ck, rep, axis=2).transpose(0, 2, 1, 3)  # (B,H,L,N)
        y_inter = jnp.einsum("bhln,bhnp->bhlp", crep * jnp.exp(cum)[..., None], h)
        # state update: h = exp(total)*h + sum_s exp(total - cum_s) B_s x~_s^T
        w = jnp.exp(total - cum)  # (B,H,L)
        brep = jnp.repeat(bk, rep, axis=2).transpose(0, 2, 1, 3)  # (B,H,L,N)
        h = h * jnp.exp(total)[..., None] + jnp.einsum(
            "bhln,bhlp->bhnp", brep * w[..., None], xh
        )
        return h, (y_intra + y_inter).transpose(0, 2, 1, 3)  # (B,L,H,P)

    hT, yc = jax.lax.scan(body, h0, (xc, lc, Bc, Cc))
    y = yc.transpose(1, 0, 2, 3, 4).reshape(B, S, H, P).astype(x.dtype)
    if return_final_state:
        return y, hT
    return y


def ssd(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H) positive
    A: jax.Array,  # (H,) negative
    Bm: jax.Array,  # (B, S, G, N)
    Cm: jax.Array,  # (B, S, G, N)
    *,
    chunk: int = 128,
    initial_state: Optional[jax.Array] = None,
    return_final_state: bool = False,
):
    impl = kcfg.get_impl()
    if impl == "xla":
        return _xla_ssd(
            x, dt, A, Bm, Cm,
            chunk=chunk,
            initial_state=initial_state,
            return_final_state=return_final_state,
        )
    from repro.kernels.mamba2_ssd import kernel as _kernel

    opts = dict(chunk=chunk, return_final_state=return_final_state)

    def kernel(x, dt, A, Bm, Cm, h0):
        return _kernel.ssd_pallas(
            x, dt, A, Bm, Cm, initial_state=h0,
            interpret=(impl == "pallas_interpret"), **opts,
        )

    def jnp_path(x, dt, A, Bm, Cm, h0):
        return _xla_ssd(x, dt, A, Bm, Cm, initial_state=h0, **opts)

    # differentiable: the kernel forward, the jnp path's backward
    return kcfg.with_jnp_vjp(kernel, jnp_path)(x, dt, A, Bm, Cm, initial_state)


def ssd_step(x, dt, A, Bm, Cm, state):
    """Single decode step (always jnp: O(1) work)."""
    from repro.kernels.mamba2_ssd import ref as _ref

    return _ref.ssd_step_ref(x, dt, A, Bm, Cm, state)
