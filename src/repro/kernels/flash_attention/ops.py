"""Dispatching wrapper for flash attention.

``flash_attention(q, k, v)`` with q (B, Sq, H, hd), kv (B, Sk, KVH, hd).

impl='xla' (default off a TPU): chunked-softmax pure-jnp path — scan over query blocks
so peak memory is O(block_q · Sk) not O(Sq · Sk), and with a sliding window
the KV is dynamically sliced to O(window + block_q) per block, making SWA
prefill genuinely sub-quadratic.  This is the path the 512-device dry-run
lowers; GSPMD shards it like any einsum.

impl='pallas[_interpret]': the TPU kernel in kernel.py on the forward pass;
its gradient is the xla path's (the kernel has no transpose rule).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import config as kcfg
from repro.kernels.flash_attention import kernel as _kernel


def _xla_flash(
    q, k, v, *, causal, window, softcap, q_offset=0, block_q: int = 512,
    return_lse: bool = False, starts=None,
):
    B, Sq, H, hd = q.shape
    _, Sk, KVH, _ = k.shape
    G = H // KVH
    scale = 1.0 / math.sqrt(hd)
    block_q = min(block_q, Sq)
    if Sq % block_q != 0:
        raise ValueError(
            f"flash_attention xla path: Sq={Sq} is not divisible by "
            f"block_q={block_q} (q shape {q.shape})"
        )
    nq = Sq // block_q

    if window is not None:
        kv_len = min(Sk, window + block_q)
    else:
        kv_len = Sk

    qb = q.reshape(B, nq, block_q, KVH, G, hd).transpose(1, 0, 2, 3, 4, 5)

    def body(_, inp):
        iq, qblk = inp  # qblk: (B, bq, KVH, G, hd)
        q_start = iq * block_q + q_offset
        if window is not None and kv_len < Sk:
            start = jnp.clip(q_start - (window - 1), 0, Sk - kv_len)
        else:
            start = jnp.int32(0)
        k_sl = jax.lax.dynamic_slice_in_dim(k, start, kv_len, axis=1)
        v_sl = jax.lax.dynamic_slice_in_dim(v, start, kv_len, axis=1)
        s = jnp.einsum(
            "bqkgd,bskd->bkgqs",
            qblk.astype(jnp.float32) * scale,
            k_sl.astype(jnp.float32),
        )
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        rows = q_start + jnp.arange(block_q)[:, None]
        cols = start + jnp.arange(kv_len)[None, :]
        mask = jnp.ones((block_q, kv_len), bool)
        if causal:
            mask &= cols <= rows
        if window is not None:
            mask &= (rows - cols) < window
        if starts is not None:
            # left-pad carve-out: row b's tokens never attend before its
            # prompt start — a per-batch (B, bq, kv) mask
            maskb = mask[None] & (cols[None] >= starts[:, None, None])
            s = jnp.where(maskb[:, None, None], s, -1e30)
        elif causal or window is not None:
            s = jnp.where(mask[None, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        if starts is not None:
            # fully-masked rows (pure left-padding) emit zeros, matching the
            # Pallas kernel's l == 0 carve-out and the ref oracle's NaN -> 0
            p = jnp.where(maskb[:, None, None], p, 0.0)
        o = jnp.einsum("bkgqs,bskd->bqkgd", p, v_sl.astype(jnp.float32))
        lse = jax.nn.logsumexp(s, axis=-1)  # (B, KVH, G, bq)
        return None, (o.astype(q.dtype), lse)

    _, (ob, lseb) = jax.lax.scan(body, None, (jnp.arange(nq), qb))
    out = ob.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, hd)
    if return_lse:
        # (nq, B, KVH, G, bq) -> (B, Sq, H)
        lse = lseb.transpose(1, 0, 4, 2, 3).reshape(B, Sq, H)
        return out, lse
    return out


# ---------------------------------------------------------------------------
# custom_vjp: flash-style chunked backward (O(block_q · Sk) memory — the
# (Sq × Sk) probability matrix is never materialized in either direction)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_diff(q, k, v, causal, window, softcap):
    return _xla_flash(q, k, v, causal=causal, window=window, softcap=softcap)


def _flash_diff_fwd(q, k, v, causal, window, softcap):
    out, lse = _xla_flash(
        q, k, v, causal=causal, window=window, softcap=softcap, return_lse=True
    )
    return out, (q, k, v, out, lse)


def _flash_diff_bwd(causal, window, softcap, res, do):
    """Chunked over q blocks; dk/dv accumulate in the scan carry.  Backward
    recomputes each block's logits from (q, k, lse) — the flash recipe."""
    q, k, v, out, lse = res
    B, Sq, H, hd = q.shape
    _, Sk, KVH, _ = k.shape
    G = H // KVH
    scale = 1.0 / math.sqrt(hd)
    block_q = min(512, Sq)
    nq = Sq // block_q

    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # D_i = dO_i · O_i  (B, Sq, H)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    def reshape_q(a, last):
        return a.reshape(B, nq, block_q, KVH, G, last).transpose(1, 0, 2, 3, 4, 5)

    qb = reshape_q(q.astype(jnp.float32), hd)
    dob = reshape_q(do.astype(jnp.float32), hd)
    deltab = delta.reshape(B, nq, block_q, KVH, G).transpose(1, 0, 2, 3, 4)
    lseb = lse.reshape(B, nq, block_q, KVH, G).transpose(1, 0, 2, 3, 4)

    def body(carry, inp):
        dk, dv = carry
        iq, qblk, doblk, dblk, lblk = inp
        q_start = iq * block_q
        s_raw = jnp.einsum("bqkgd,bskd->bkgqs", qblk * scale, kf)
        if softcap is not None:
            t = jnp.tanh(s_raw / softcap)
            s = softcap * t
            dcap = 1.0 - jnp.square(t)
        else:
            s = s_raw
            dcap = None
        rows = q_start + jnp.arange(block_q)[:, None]
        cols = jnp.arange(Sk)[None, :]
        mask = jnp.ones((block_q, Sk), bool)
        if causal:
            mask &= cols <= rows
        if window is not None:
            mask &= (rows - cols) < window
        if causal or window is not None:
            s = jnp.where(mask[None, None, None], s, -1e30)
        # P_ij = exp(s_ij - lse_i)
        p = jnp.exp(s - lblk.transpose(0, 2, 3, 1)[..., None])  # (B,K,G,bq,Sk)
        dvb = jnp.einsum("bkgqs,bqkgd->bskd", p, doblk)
        dp = jnp.einsum("bqkgd,bskd->bkgqs", doblk, vf)
        ds = p * (dp - dblk.transpose(0, 2, 3, 1)[..., None])
        if dcap is not None:
            ds = ds * dcap
        dqb = jnp.einsum("bkgqs,bskd->bqkgd", ds, kf) * scale
        dkb = jnp.einsum("bkgqs,bqkgd->bskd", ds, qblk) * scale
        return (dk + dkb, dv + dvb), dqb

    zeros = jnp.zeros((B, Sk, KVH, hd), jnp.float32)
    (dk, dv), dqb = jax.lax.scan(
        body, (zeros, zeros), (jnp.arange(nq), qb, dob, deltab, lseb)
    )
    dq = dqb.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, hd)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_offset: int = 0,
    starts: Optional[jax.Array] = None,
) -> jax.Array:
    """``starts`` (B,) int32, optional: per-request prompt starts for
    left-padded batches — row b attends no column < starts[b] (the serving
    engine's pad carve-out).  Inference-only (routes around the custom_vjp)
    and served on EVERY impl: the Pallas kernel carries starts via scalar
    prefetch and skips KV blocks wholly below a row's start, so left-padded
    continuous batching never falls back to XLA.  Without ``starts`` every
    impl is differentiable: under the kernels, ``jax.grad`` takes the jnp
    path's chunked backward (``kcfg.with_jnp_vjp``)."""
    impl = kcfg.get_impl()
    if starts is not None:
        starts = jnp.asarray(starts, jnp.int32)
    if impl == "xla":
        if starts is not None:
            return _xla_flash(
                q, k, v, causal=causal, window=window, softcap=softcap,
                q_offset=q_offset, starts=starts,
            )
        if q_offset == 0:
            return _flash_diff(q, k, v, causal, window, softcap)
        return _xla_flash(
            q, k, v, causal=causal, window=window, softcap=softcap, q_offset=q_offset
        )
    if q_offset != 0:
        raise ValueError(
            f"flash_attention: q_offset={q_offset} is unsupported on the "
            f"Pallas path (impl={impl!r}) — the kernel assumes q starts at "
            "position 0; use impl='xla' for offset prefill"
        )
    run = functools.partial(
        _kernel.flash_attention_bhsd,
        causal=causal,
        window=window,
        softcap=softcap,
        interpret=(impl == "pallas_interpret"),
    )

    def kernel(q, k, v, starts):
        t = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731  (B,S,H,hd) <-> (B,H,S,hd)
        return t(run(t(q), t(k), t(v), starts))

    if starts is not None:
        return kernel(q, k, v, starts)
    # differentiable: the kernel forward, the chunked custom_vjp backward
    return kcfg.with_jnp_vjp(
        lambda q, k, v: kernel(q, k, v, None),
        lambda q, k, v: _flash_diff(q, k, v, causal, window, softcap),
    )(q, k, v)
