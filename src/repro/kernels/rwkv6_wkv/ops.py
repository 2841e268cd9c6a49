"""Chunked RWKV6 WKV wrapper.

impl='xla': chunked linear-attention-with-decay in pure jnp.  Intra-chunk
uses the exact pairwise decay tensor exp(ecum_t - cum_s) (all exponents
<= 0 — stable for any data-dependent decay), inter-chunk carries the
(D x D) state through a lax.scan.  Chunk length defaults to 32 to bound the
(L, L, D) pairwise tensor; see kernel.py for the TPU tiling discussion.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import config as kcfg


def _chunk_wkv_body(u):
    def body(s, inp):
        r, k, v, logw = inp  # (B, H, L, D)
        L = r.shape[2]
        cum = jnp.cumsum(logw, axis=2)  # inclusive
        ecum = cum - logw  # exclusive: sum_{s<t}
        diff = ecum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,H,L,L,D)
        tri = jnp.tril(jnp.ones((L, L), bool), k=-1)[None, None, :, :, None]
        decay = jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)
        A = jnp.einsum("bhtd,bhsd,bhtsd->bhts", r, k, decay)
        diag = jnp.einsum("bhtd,hd,bhtd->bht", r, u, k)  # bonus-u self term
        A = A + diag[..., None] * jnp.eye(L)[None, None]
        y = jnp.einsum("bhts,bhsd->bhtd", A, v)
        y = y + jnp.einsum("bhtd,bhde->bhte", r * jnp.exp(ecum), s)
        w_end = jnp.exp(cum[:, :, -1:, :] - cum)  # (B,H,L,D)
        s = s * jnp.exp(cum[:, :, -1, :])[..., None] + jnp.einsum(
            "bhsd,bhse->bhde", k * w_end, v
        )
        return s, y

    return body


def _pad_seq(a, pad):
    return jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))


def _xla_wkv6(r, k, v, logw, u, *, chunk, initial_state, return_final_state):
    B, S, H, D = r.shape
    L = min(chunk, S)
    if S % L:
        # zero k/v and zero log-decay padding is exact: contributes nothing
        # to outputs and leaves the final state untouched
        pad = L - S % L
        out = _xla_wkv6(
            _pad_seq(r, pad), _pad_seq(k, pad), _pad_seq(v, pad),
            _pad_seq(logw, pad), u,
            chunk=chunk, initial_state=initial_state,
            return_final_state=return_final_state,
        )
        if return_final_state:
            return out[0][:, :S], out[1]
        return out[:, :S]
    nc = S // L
    rf, kf, vf = (a.astype(jnp.float32) for a in (r, k, v))
    lf = logw.astype(jnp.float32)
    uf = u.astype(jnp.float32)

    def chunked(a):  # (B,S,H,D) -> (nc, B, H, L, D)
        return a.reshape(B, nc, L, H, D).transpose(1, 0, 3, 2, 4)

    s0 = (
        jnp.zeros((B, H, D, D), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )
    sT, yc = jax.lax.scan(
        _chunk_wkv_body(uf), s0, tuple(map(chunked, (rf, kf, vf, lf)))
    )
    y = yc.transpose(1, 0, 3, 2, 4).reshape(B, S, H, D).astype(r.dtype)
    if return_final_state:
        return y, sT
    return y


def wkv6(
    r: jax.Array,  # (B, S, H, D)
    k: jax.Array,
    v: jax.Array,
    logw: jax.Array,  # (B, S, H, D), <= 0
    u: jax.Array,  # (H, D)
    *,
    chunk: int = 32,
    initial_state: Optional[jax.Array] = None,
    return_final_state: bool = False,
):
    impl = kcfg.get_impl()
    if impl == "xla":
        return _xla_wkv6(
            r, k, v, logw, u,
            chunk=chunk,
            initial_state=initial_state,
            return_final_state=return_final_state,
        )
    from repro.kernels.rwkv6_wkv import kernel as _kernel

    opts = dict(chunk=chunk, return_final_state=return_final_state)

    def kernel(r, k, v, logw, u, s0):
        return _kernel.wkv6_pallas(
            r, k, v, logw, u, initial_state=s0,
            interpret=(impl == "pallas_interpret"), **opts,
        )

    def jnp_path(r, k, v, logw, u, s0):
        return _xla_wkv6(r, k, v, logw, u, initial_state=s0, **opts)

    # differentiable: the kernel forward, the jnp path's backward
    return kcfg.with_jnp_vjp(kernel, jnp_path)(r, k, v, logw, u, initial_state)


def wkv6_step(r, k, v, logw, u, state):
    from repro.kernels.rwkv6_wkv import ref as _ref

    return _ref.wkv6_step_ref(r, k, v, logw, u, state)
