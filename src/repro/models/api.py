"""Unified model API over the six architecture families.

    params = init_params(cfg, rng)                  # boxed (value + axes)
    values, axes = unbox(params)

    loss, metrics = loss_fn(values, batch, cfg)     # train step ingredient
    logits, cache = prefill(values, batch, cfg)     # inference prefill
    logits, cache = decode_step(values, token, cache_values, pos, cfg)

    input_specs(cfg, shape)   ShapeDtypeStruct stand-ins for the dry-run
    make_inputs(cfg, shape)   concrete random inputs for smoke tests

Layer stacks run under ``lax.scan`` over stacked parameters so HLO size is
depth-independent; remat applies to the scanned body for train shapes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import blocks_dense as BD
from repro.models import blocks_mamba2 as BM
from repro.models import blocks_rwkv6 as BR
from repro.models import layers as L
from repro.models.params import Box, Initializer, is_box, stack_layers, unbox
from repro.sharding.logical import constrain

# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_layer(ini_key, cfg: ModelConfig, *, moe_override=None):
    ini = Initializer(ini_key, cfg.dtype)
    fam = cfg.family
    if fam in ("dense", "vlm", "encoder"):
        return BD.init_dense_layer(ini, cfg, moe=False)
    if fam == "moe":
        moe = True if moe_override is None else moe_override
        return BD.init_dense_layer(ini, cfg, moe=moe)
    if fam == "ssm_mamba2" or fam == "hybrid":
        return BM.init_mamba2_block(ini, cfg)
    if fam == "ssm_rwkv6":
        return BR.init_rwkv6_block(ini, cfg)
    raise ValueError(fam)


def _interleaved_moe(cfg: ModelConfig) -> bool:
    """MoE every `moe_every`-th layer (llama4-style interleave)."""
    return cfg.family == "moe" and cfg.moe_every > 1


def init_params(cfg: ModelConfig, rng: jax.Array):
    k_embed, k_layers, k_shared, k_head, k_front = jax.random.split(rng, 5)
    ini = Initializer(k_embed, cfg.dtype)
    p = {}
    if not cfg.is_encoder:
        p["embed"] = ini.normal(
            (cfg.vocab_size, cfg.d_model), ("vocab", "embed"), std=0.02
        )
    if cfg.frontend_dim:
        fini = Initializer(k_front, cfg.dtype)
        p["frontend"] = {
            "proj": fini.normal((cfg.frontend_dim, cfg.d_model), (None, "embed"))
        }
    if _interleaved_moe(cfg):
        me = cfg.moe_every
        assert cfg.n_layers % me == 0, (cfg.n_layers, me)
        n_groups = cfg.n_layers // me
        kd, km = jax.random.split(k_layers)
        p["layers"] = {
            "dense": stack_layers(
                functools.partial(_init_layer, cfg=cfg, moe_override=False),
                n_groups * (me - 1), kd,
            ),
            "moe": stack_layers(
                functools.partial(_init_layer, cfg=cfg, moe_override=True),
                n_groups, km,
            ),
        }
    else:
        p["layers"] = stack_layers(
            functools.partial(_init_layer, cfg=cfg), cfg.n_layers, k_layers
        )
    if cfg.family == "hybrid" and cfg.attn_every:
        sini = Initializer(k_shared, cfg.dtype)
        p["shared_attn"] = BD.init_dense_layer(sini, cfg, moe=False)
    hini = Initializer(k_head, cfg.dtype)
    p["final_norm"] = L.init_norm(hini, cfg, cfg.d_model)
    if cfg.tie_embeddings:
        pass  # reuse embed.T at the head
    else:
        p["lm_head"] = hini.normal(
            (cfg.d_model, cfg.vocab_size), ("embed", "vocab"), std=0.02
        )
    return p


# ---------------------------------------------------------------------------
# embedding / frontend
# ---------------------------------------------------------------------------


def embed_inputs(params, batch, cfg: ModelConfig):
    """Returns hidden (B, S, D).  For VLM the (stubbed, precomputed) patch
    embeddings are projected and prepended; for the audio encoder the frame
    embeddings are projected directly (assignment carve-out)."""
    if cfg.is_encoder:
        x = batch["embeds"] @ params["frontend"]["proj"]
        return x.astype(cfg.dtype)
    tok = params["embed"][batch["tokens"]]  # (B, St, D)
    if cfg.n_vision_tokens and "embeds" in batch:
        vis = (batch["embeds"] @ params["frontend"]["proj"]).astype(tok.dtype)
        tok = jnp.concatenate([vis, tok], axis=1)
    return constrain(tok, ("act_batch", "act_seq", "act_embed"))


# ---------------------------------------------------------------------------
# backbone (full sequence)
# ---------------------------------------------------------------------------


def _maybe_remat(fn, cfg: ModelConfig, train: bool):
    if cfg.remat and train:
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return fn


def backbone_fwd(
    params,
    x,
    cfg: ModelConfig,
    *,
    train: bool,
    window_override: Optional[int] = None,
    collect_kv: bool = False,
    positions=None,
    starts=None,
):
    """Returns (x, aux_loss, kv_stack_or_None).

    ``positions``/``starts`` carry the per-request left-pad carve-out
    (serve/engine.py): attention-family layers offset RoPE per row and mask
    columns before each row's prompt start — on the Pallas kernel path as
    much as on XLA (starts ride scalar prefetch; below-start KV blocks are
    skipped).  Recurrent families sweep the sequence unconditionally, so
    the carve-out cannot apply there."""
    fam = cfg.family
    window = window_override if window_override is not None else cfg.sliding_window
    B, S, D = x.shape
    assert starts is None or fam in ("dense", "moe", "vlm"), (
        f"left-pad carve-out unsupported for family {fam}"
    )

    if fam in ("dense", "moe", "vlm", "encoder") and not _interleaved_moe(cfg):

        def body(carry, lp):
            h, aux = carry
            h, a, kv = BD.dense_layer_fwd(
                lp, h, cfg, causal=not cfg.is_encoder, sliding_window=window,
                positions=positions, starts=starts,
            )
            return (h, aux + a), (kv if collect_kv else None)

        body = _maybe_remat(body, cfg, train)
        (x, aux), kvs = jax.lax.scan(body, (x, jnp.float32(0.0)), params["layers"])
        return x, aux, kvs

    if _interleaved_moe(cfg):
        # llama4-style interleave: groups of (moe_every-1) dense layers
        # followed by one MoE layer; scan over groups
        me = cfg.moe_every
        n_groups = cfg.n_layers // me
        grp_dense = jax.tree.map(
            lambda t: t.reshape((n_groups, me - 1) + t.shape[1:]),
            params["layers"]["dense"],
        )

        def one(h, lp):
            h, a, kv = BD.dense_layer_fwd(
                lp, h, cfg, causal=True, sliding_window=window,
                positions=positions, starts=starts,
            )
            return h, (a, kv if collect_kv else None)

        def body(carry, lps):
            h, aux = carry
            lp_d, lp_m = lps
            h, (a_d, kv_d) = jax.lax.scan(one, h, lp_d)
            h, a_m, kv_m = BD.dense_layer_fwd(
                lp_m, h, cfg, causal=True, sliding_window=window,
                positions=positions, starts=starts,
            )
            ys = (kv_d, kv_m) if collect_kv else None
            return (h, aux + a_d.sum() + a_m), ys

        body = _maybe_remat(body, cfg, train)
        (x, aux), kvs = jax.lax.scan(
            body, (x, jnp.float32(0.0)), (grp_dense, params["layers"]["moe"])
        )
        if collect_kv:
            (kd, vd), (km, vm) = kvs  # kd: (g, me-1, B,S,K,hd); km: (g, B,S,K,hd)
            k_all = jnp.concatenate([kd, km[:, None]], axis=1).reshape(
                (cfg.n_layers,) + km.shape[1:]
            )
            v_all = jnp.concatenate([vd, vm[:, None]], axis=1).reshape(
                (cfg.n_layers,) + vm.shape[1:]
            )
            return x, aux, (k_all, v_all)
        return x, aux, None

    if fam == "ssm_mamba2":

        def body(carry, lp):
            h = carry
            if collect_kv:
                out, st = BM.mamba2_fwd(lp, h, cfg, return_state=True)
                return h + out, st
            return h + BM.mamba2_fwd(lp, h, cfg), None

        body = _maybe_remat(body, cfg, train)
        x, states = jax.lax.scan(body, x, params["layers"])
        return x, jnp.float32(0.0), states

    if fam == "ssm_rwkv6":

        def body(carry, lp):
            h = carry
            if collect_kv:
                h, st = BR.rwkv6_layer_fwd(lp, h, cfg, return_state=True)
                return h, st
            return BR.rwkv6_layer_fwd(lp, h, cfg), None

        body = _maybe_remat(body, cfg, train)
        x, states = jax.lax.scan(body, x, params["layers"])
        return x, jnp.float32(0.0), states

    if fam == "hybrid":
        shared = params["shared_attn"]
        every = cfg.attn_every
        n_inv = cfg.n_layers // every
        if collect_kv:
            ak0 = jnp.zeros((n_inv, B, S, cfg.n_kv_heads, cfg.head_dim), x.dtype)
            av0 = jnp.zeros_like(ak0)
        else:
            ak0 = av0 = jnp.zeros((1,), x.dtype)  # placeholder carry

        def body(carry, inp):
            h, ak, av = carry
            lp, idx = inp
            if collect_kv:
                out, st = BM.mamba2_fwd(lp, h, cfg, return_state=True)
                h = h + out
            else:
                h = h + BM.mamba2_fwd(lp, h, cfg)
                st = None

            def with_attn(args):
                h, ak, av = args
                hh, _, (k, v) = BD.dense_layer_fwd(
                    shared, h, cfg, causal=True, sliding_window=window
                )
                if collect_kv:
                    inv = idx // every
                    ak = jax.lax.dynamic_update_index_in_dim(ak, k.astype(ak.dtype), inv, 0)
                    av = jax.lax.dynamic_update_index_in_dim(av, v.astype(av.dtype), inv, 0)
                return hh, ak, av

            h, ak, av = jax.lax.cond(
                (idx + 1) % every == 0, with_attn, lambda a: a, (h, ak, av)
            )
            return (h, ak, av), st

        body = _maybe_remat(body, cfg, train)
        idxs = jnp.arange(cfg.n_layers)
        (x, ak, av), states = jax.lax.scan(
            body, (x, ak0, av0), (params["layers"], idxs)
        )
        if collect_kv:
            return x, jnp.float32(0.0), (states, (ak, av))
        return x, jnp.float32(0.0), states

    raise ValueError(fam)


# ---------------------------------------------------------------------------
# loss (chunked over sequence so (B, S, V) logits never materialize)
# ---------------------------------------------------------------------------


def _chunked_ce(params, hidden, targets, mask, cfg: ModelConfig, chunk: int = 512):
    B, S, D = hidden.shape
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    c = min(chunk, S)
    while S % c:  # e.g. VLM text length S - n_vision_tokens
        c //= 2
    c = max(c, 1)
    nc = S // c
    hs = hidden.reshape(B, nc, c, D).transpose(1, 0, 2, 3)
    ts = targets.reshape(B, nc, c).transpose(1, 0, 2)
    ms = mask.reshape(B, nc, c).transpose(1, 0, 2)

    def body(carry, inp):
        nll_sum, z_sum, n, correct = carry
        h, t, m = inp
        logits = (h @ head).astype(jnp.float32)  # (B, c, V)
        logits = constrain(logits, ("act_batch", "act_seq", "act_vocab"))
        logz = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
        nll = (logz - tgt) * m
        zl = jnp.square(logz) * m
        acc = (jnp.argmax(logits, -1) == t) * m
        return (
            nll_sum + nll.sum(),
            z_sum + zl.sum(),
            n + m.sum(),
            correct + acc.sum(),
        ), None

    init = (jnp.float32(0.0),) * 4
    (nll_sum, z_sum, n, correct), _ = jax.lax.scan(body, init, (hs, ts, ms))
    n = jnp.maximum(n, 1.0)
    return nll_sum / n, z_sum / n, correct / n


def loss_fn(params, batch, cfg: ModelConfig, *, window_override=None):
    x = embed_inputs(params, batch, cfg)
    x, aux, _ = backbone_fwd(
        params, x, cfg, train=True, window_override=window_override
    )
    x = L.apply_norm(params["final_norm"], x, cfg)
    if cfg.n_vision_tokens and "embeds" in batch:
        x = x[:, cfg.n_vision_tokens :, :]
    targets = batch["targets"]
    mask = batch.get("mask", jnp.ones_like(targets, jnp.float32))
    ce, zl, acc = _chunked_ce(params, x, targets, mask, cfg)
    loss = ce + 1e-4 * zl + aux
    return loss, {"ce": ce, "z_loss": zl, "acc": acc, "aux": aux}


def _pad_carveout(batch, S, cfg: ModelConfig):
    """(positions, starts) for a left-padded batch, or (None, None).
    ``batch['starts']`` (B,) marks each row's prompt start; positions are
    taken relative to it so RoPE matches the unpadded run.  Starts index
    the TOKEN grid, so a prepended vision prefix would shift every column
    the mask refers to — reject that combination instead of silently
    masking the wrong columns."""
    starts = batch.get("starts")
    if starts is None:
        return None, None
    assert not (cfg.n_vision_tokens and "embeds" in batch), (
        "left-pad carve-out indexes token columns; unsupported with a "
        "prepended vision prefix"
    )
    starts = jnp.asarray(starts, jnp.int32)
    return jnp.arange(S)[None, :] - starts[:, None], starts


def forward_logits(params, batch, cfg: ModelConfig, *, window_override=None):
    """Full logits (B, S, V) — small models / ABC ensembles only."""
    x = embed_inputs(params, batch, cfg)
    positions, starts = _pad_carveout(batch, x.shape[1], cfg)
    x, _, _ = backbone_fwd(
        params, x, cfg, train=False, window_override=window_override,
        positions=positions, starts=starts,
    )
    x = L.apply_norm(params["final_norm"], x, cfg)
    if cfg.n_vision_tokens and "embeds" in batch:
        x = x[:, cfg.n_vision_tokens :, :]
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    return (x @ head).astype(jnp.float32)


forward = forward_logits


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None):
    """Boxed cache tree (Box carries the logical axes for sharding)."""
    from repro.models.layers import LEGACY_DECODE

    dtype = dtype or jnp.dtype(cfg.dtype)
    Lyr = cfg.n_layers
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        if LEGACY_DECODE:
            shape = (Lyr, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
            axes = ("layers", "kv_batch", "kv_seq", "cache_kv_heads", "head_dim")
        else:
            # kernel-native layout: sequence innermost (§Perf iteration 1)
            shape = (Lyr, batch, cfg.n_kv_heads, max_seq, cfg.head_dim)
            axes = ("layers", "kv_batch", "cache_kv_heads", "kv_seq", "head_dim")
        return {
            "k": Box(jnp.zeros(shape, dtype), axes),
            "v": Box(jnp.zeros(shape, dtype), axes),
        }
    if fam == "ssm_mamba2":
        d_in, nh, G, N, conv_dim, _ = BM._dims(cfg)
        return {
            "conv": Box(
                jnp.zeros((Lyr, batch, cfg.ssm_conv - 1, conv_dim), dtype),
                ("layers", "kv_batch", None, "ssm_inner"),
            ),
            "ssm": Box(
                jnp.zeros((Lyr, batch, nh, N, cfg.ssm_head_dim), jnp.float32),
                ("layers", "kv_batch", "ssm_heads", None, None),
            ),
        }
    if fam == "ssm_rwkv6":
        D = cfg.d_model
        H, hd = D // cfg.ssm_head_dim, cfg.ssm_head_dim
        return {
            "tm_x": Box(jnp.zeros((Lyr, batch, D), dtype), ("layers", "kv_batch", None)),
            "cm_x": Box(jnp.zeros((Lyr, batch, D), dtype), ("layers", "kv_batch", None)),
            "wkv": Box(
                jnp.zeros((Lyr, batch, H, hd, hd), jnp.float32),
                ("layers", "kv_batch", "ssm_heads", None, None),
            ),
        }
    if fam == "hybrid":
        d_in, nh, G, N, conv_dim, _ = BM._dims(cfg)
        n_inv = cfg.n_layers // cfg.attn_every
        # per-invocation caches as SEPARATE leaves (§Perf iteration 3): the
        # decode path then never dynamic-slices a whole (B,K,S,hd) slab out
        # of a stacked buffer — XLA materializes such slices as full copies
        if LEGACY_DECODE:
            kv_shape = (batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
            kv_axes = ("kv_batch", "kv_seq", "cache_kv_heads", "head_dim")
        else:
            kv_shape = (batch, cfg.n_kv_heads, max_seq, cfg.head_dim)
            kv_axes = ("kv_batch", "cache_kv_heads", "kv_seq", "head_dim")
        return {
            "conv": Box(
                jnp.zeros((Lyr, batch, cfg.ssm_conv - 1, conv_dim), dtype),
                ("layers", "kv_batch", None, "ssm_inner"),
            ),
            "ssm": Box(
                jnp.zeros((Lyr, batch, nh, N, cfg.ssm_head_dim), jnp.float32),
                ("layers", "kv_batch", "ssm_heads", None, None),
            ),
            "attn_k": [Box(jnp.zeros(kv_shape, dtype), kv_axes) for _ in range(n_inv)],
            "attn_v": [Box(jnp.zeros(kv_shape, dtype), kv_axes) for _ in range(n_inv)],
        }
    raise ValueError(f"no cache for family {fam}")


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


def prefill(params, batch, cfg: ModelConfig, *, window_override=None):
    """Forward the prompt, return (last-token logits (B, V), cache values).
    ``batch['starts']`` (B,), optional, activates the left-pad carve-out
    for attention families (per-row RoPE offset + pad masking)."""
    x = embed_inputs(params, batch, cfg)
    B, S, _ = x.shape
    positions, starts = _pad_carveout(batch, S, cfg)
    x, _, states = backbone_fwd(
        params, x, cfg, train=False, window_override=window_override,
        collect_kv=True, positions=positions, starts=starts,
    )
    xl = L.apply_norm(params["final_norm"], x[:, -1:, :], cfg)
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    logits = (xl[:, 0] @ head).astype(jnp.float32)
    logits = constrain(logits, ("act_batch", "act_vocab"))

    fam = cfg.family
    if cfg.is_encoder:
        return logits, None
    cache_axes = ("layers", "kv_batch", "cache_kv_heads", "kv_seq", "head_dim")
    if fam in ("dense", "moe", "vlm"):
        k, v = states  # (L, B, S, KVH, hd) -> kernel-native (L, B, KVH, S, hd)
        return logits, {
            "k": constrain(k.transpose(0, 1, 3, 2, 4), cache_axes),
            "v": constrain(v.transpose(0, 1, 3, 2, 4), cache_axes),
        }
    if fam in ("ssm_mamba2", "ssm_rwkv6"):
        return logits, states
    if fam == "hybrid":
        mamba_st, (ak, av) = states
        n_inv = cfg.n_layers // cfg.attn_every
        akt = ak.transpose(0, 1, 3, 2, 4)  # (n_inv, B, K, S, hd)
        avt = av.transpose(0, 1, 3, 2, 4)
        inv_axes = cache_axes[1:]
        return logits, {
            "conv": mamba_st["conv"],
            "ssm": mamba_st["ssm"],
            "attn_k": [constrain(akt[i], inv_axes) for i in range(n_inv)],
            "attn_v": [constrain(avt[i], inv_axes) for i in range(n_inv)],
        }
    raise ValueError(fam)


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------


def decode_step(
    params,
    token,
    cache,
    pos,
    cfg: ModelConfig,
    *,
    window_override=None,
    embeds=None,
    starts=None,
):
    """One new token with a KV/SSM cache.

    token: (B, 1) int32; pos: scalar int32 position of the new token;
    cache: values tree from ``init_cache``/``prefill``; starts: (B,)
    optional per-request prompt starts (left-pad carve-out — attention
    families only).  Returns (logits (B, V), new_cache)."""
    window = window_override if window_override is not None else cfg.sliding_window
    fam = cfg.family
    assert starts is None or fam in ("dense", "moe", "vlm"), (
        f"left-pad carve-out unsupported for family {fam}"
    )
    x = params["embed"][token]  # (B, 1, D)
    x = constrain(x, ("act_batch", None, "act_embed"))

    if fam in ("dense", "moe", "vlm") and not _interleaved_moe(cfg):

        def body(h, inp):
            lp, kc, vc = inp
            h, (kc, vc) = BD.dense_layer_decode(
                lp, h, cfg, kc, vc, pos, sliding_window=window, starts=starts
            )
            return h, (kc, vc)

        x, (k_new, v_new) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"])
        )
        new_cache = {"k": k_new, "v": v_new}

    elif _interleaved_moe(cfg):
        me = cfg.moe_every
        n_groups = cfg.n_layers // me
        grp_dense = jax.tree.map(
            lambda t: t.reshape((n_groups, me - 1) + t.shape[1:]),
            params["layers"]["dense"],
        )
        # cache layer order is [d × (me-1), m] per group
        grp_cache = jax.tree.map(
            lambda t: t.reshape((n_groups, me) + t.shape[1:]),
            {"k": cache["k"], "v": cache["v"]},
        )

        def one(h, inp):
            lp, kc, vc = inp
            h, (kc, vc) = BD.dense_layer_decode(
                lp, h, cfg, kc, vc, pos, sliding_window=window, starts=starts
            )
            return h, (kc, vc)

        def body(h, inp):
            lp_d, lp_m, cg = inp
            h, (kd, vd) = jax.lax.scan(
                one, h, (lp_d, cg["k"][: me - 1], cg["v"][: me - 1])
            )
            h, (km, vm) = BD.dense_layer_decode(
                lp_m, h, cfg, cg["k"][me - 1], cg["v"][me - 1], pos,
                sliding_window=window, starts=starts,
            )
            k_new = jnp.concatenate([kd, km[None]], axis=0)
            v_new = jnp.concatenate([vd, vm[None]], axis=0)
            return h, (k_new, v_new)

        x, (k_new, v_new) = jax.lax.scan(
            body, x, (grp_dense, params["layers"]["moe"], grp_cache)
        )
        new_cache = {
            "k": k_new.reshape((cfg.n_layers,) + k_new.shape[2:]),
            "v": v_new.reshape((cfg.n_layers,) + v_new.shape[2:]),
        }

    elif fam == "ssm_mamba2":

        def body(h, inp):
            lp, st = inp
            out, st = BM.mamba2_step(lp, h, cfg, st)
            return h + out, st

        x, states = jax.lax.scan(
            body, x, (params["layers"], {"conv": cache["conv"], "ssm": cache["ssm"]})
        )
        new_cache = states

    elif fam == "ssm_rwkv6":

        def body(h, inp):
            lp, st = inp
            out, st = BR.rwkv6_step(lp, h, cfg, st)
            return out, st

        x, states = jax.lax.scan(
            body,
            x,
            (
                params["layers"],
                {"tm_x": cache["tm_x"], "cm_x": cache["cm_x"], "wkv": cache["wkv"]},
            ),
        )
        new_cache = states

    elif fam == "hybrid" and L.LEGACY_DECODE:
        # pre-iteration-3 baseline path: stacked per-invocation caches with
        # cond + dynamic slab slice/update inside the layer scan
        shared = params["shared_attn"]
        every = cfg.attn_every
        ak0 = jnp.stack(cache["attn_k"])
        av0 = jnp.stack(cache["attn_v"])

        def body(carry, inp):
            h, ak, av = carry
            lp, st, idx = inp
            out, st = BM.mamba2_step(lp, h, cfg, st)
            h = h + out

            def with_attn(args):
                h, ak, av = args
                inv = idx // every
                kc = jax.lax.dynamic_index_in_dim(ak, inv, 0, keepdims=False)
                vc = jax.lax.dynamic_index_in_dim(av, inv, 0, keepdims=False)
                h, (kc, vc) = BD.dense_layer_decode(
                    shared, h, cfg, kc, vc, pos, sliding_window=window
                )
                ak = jax.lax.dynamic_update_index_in_dim(ak, kc, inv, 0)
                av = jax.lax.dynamic_update_index_in_dim(av, vc, inv, 0)
                return h, ak, av

            h, ak, av = jax.lax.cond(
                (idx + 1) % every == 0, with_attn, lambda a: a, (h, ak, av)
            )
            return (h, ak, av), st

        idxs = jnp.arange(cfg.n_layers)
        (x, ak, av), states = jax.lax.scan(
            body,
            (x, ak0, av0),
            (params["layers"], {"conv": cache["conv"], "ssm": cache["ssm"]}, idxs),
        )
        n_inv = cfg.n_layers // every
        new_cache = {
            "conv": states["conv"],
            "ssm": states["ssm"],
            "attn_k": [ak[i] for i in range(n_inv)],
            "attn_v": [av[i] for i in range(n_inv)],
        }

    elif fam == "hybrid":
        # §Perf iteration 3: group the scan by shared-attention invocation.
        # Mamba layers still scan (HLO depth-independent within a group);
        # the 9 shared-attention calls are a static python loop over the
        # per-invocation cache leaves — no cond, no slab slice/update of a
        # stacked cache buffer.
        shared = params["shared_attn"]
        every = cfg.attn_every
        n_inv = cfg.n_layers // every
        n_grouped = n_inv * every
        grp_params = jax.tree.map(
            lambda t: t[:n_grouped].reshape((n_inv, every) + t.shape[1:]),
            params["layers"],
        )
        grp_cache = jax.tree.map(
            lambda t: t[:n_grouped].reshape((n_inv, every) + t.shape[1:]),
            {"conv": cache["conv"], "ssm": cache["ssm"]},
        )

        def mamba_body(h, inp):
            lp, st = inp
            out, st = BM.mamba2_step(lp, h, cfg, st)
            return h + out, st

        new_states = []
        new_ak, new_av = [], []
        for g in range(n_inv):
            lp_g = jax.tree.map(lambda t: t[g], grp_params)
            st_g = jax.tree.map(lambda t: t[g], grp_cache)
            x, st_out = jax.lax.scan(mamba_body, x, (lp_g, st_g))
            x, (kc, vc) = BD.dense_layer_decode(
                shared, x, cfg, cache["attn_k"][g], cache["attn_v"][g], pos,
                sliding_window=window,
            )
            new_states.append(st_out)
            new_ak.append(kc)
            new_av.append(vc)

        if n_grouped < cfg.n_layers:  # trailing mamba layers (no attn after)
            lp_t = jax.tree.map(lambda t: t[n_grouped:], params["layers"])
            st_t = jax.tree.map(
                lambda t: t[n_grouped:], {"conv": cache["conv"], "ssm": cache["ssm"]}
            )
            x, st_out = jax.lax.scan(mamba_body, x, (lp_t, st_t))
            new_states.append(st_out)

        merged = jax.tree.map(
            lambda *xs: jnp.concatenate([t for t in xs], axis=0), *new_states
        )
        new_cache = {
            "conv": merged["conv"],
            "ssm": merged["ssm"],
            "attn_k": new_ak,
            "attn_v": new_av,
        }
    else:
        raise ValueError(f"decode unsupported for family {fam}")

    x = L.apply_norm(params["final_norm"], x, cfg)
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    logits = (x[:, 0] @ head).astype(jnp.float32)
    return constrain(logits, ("act_batch", "act_vocab")), new_cache


# ---------------------------------------------------------------------------
# slot-stream support: chunked prefill into one slot + per-slot state reset
# ---------------------------------------------------------------------------

# Constant-size recurrent state leaves (everything that is NOT pos-masked).
# Attention KV rows are protected by the per-slot pos mask, so a reused slot
# only ever sees rows it wrote itself; these leaves have no such mask and
# must be zeroed when a slot admits a new request.
_SLOT_STATE_KEYS = ("conv", "ssm", "tm_x", "cm_x", "wkv")


def has_slot_state(cfg: ModelConfig) -> bool:
    """True for families whose slot cache carries non-pos-masked state."""
    return cfg.family in ("ssm_mamba2", "ssm_rwkv6", "hybrid")


def reset_slot(cache, slot, cfg: ModelConfig):
    """Zero one slot's constant-state leaves (slot admission for SSM/RWKV
    and hybrid families).  ``cache`` is the canonical ``init_cache`` values
    tree (batch axis = slots, axis 1 of every stacked leaf); attention KV
    leaves are left untouched — the pos mask already isolates them."""
    if not has_slot_state(cfg):
        return cache
    out = dict(cache)
    for name in _SLOT_STATE_KEYS:
        if name in out and not isinstance(out[name], list):
            out[name] = out[name].at[:, slot].set(0)
    return out


def supports_chunked_prefill(cfg: ModelConfig) -> bool:
    """Chunked-prefill admission is available for every decode-capable
    family on the kernel-native cache layout (the legacy layout keeps
    decode-only admission as its baseline)."""
    from repro.models.layers import LEGACY_DECODE

    return (
        not cfg.is_encoder
        and not LEGACY_DECODE
        and cfg.family in ("dense", "moe", "vlm", "ssm_mamba2", "ssm_rwkv6", "hybrid")
    )


def supports_draft_verify(cfg: ModelConfig) -> bool:
    """Speculative draft verification needs (a) chunked prefill to score
    all draft positions in one pass and (b) a pos-masked attention cache so
    rejected draft rows can be rolled back by position alone.  Constant-state
    families fail (b): their recurrent state has absorbed the rejected
    tokens and there is no mask to hide them behind."""
    return supports_chunked_prefill(cfg) and not has_slot_state(cfg)


def prefill_into_slot(
    params,
    tokens,
    cache,
    slot,
    start,
    cfg: ModelConfig,
    *,
    window_override=None,
    return_hidden: bool = False,
):
    """Consume a C-token chunk of one slot's prompt into the slot cache.

    tokens: (C,) int32 — prompt positions [start, start+C); cache: the full
    stacked slot cache (``init_cache`` values, batch axis = slots); slot and
    start are traced scalars, so one jitted program serves every slot and
    offset, tracing once per chunk length C (the O(log S) bucket warmup).

    Attention families write K/V rows at the slot's offset; constant-state
    families thread the slot's recurrent state through the full-sequence
    block forwards (``initial=``/``state=`` continuation).  No logits are
    produced: the LAST prompt token is never chunked — it is fed through
    the shared decode program, whose logits sample the first output token,
    which is what makes chunked and decode-only admission token-identical.

    MoE caveat: ``apply_moe``'s capacity depends on tokens-per-call, so in
    a capacity-LIMITED regime a C-token chunk can drop tokens that
    per-token decode admission would keep (exactly as the batch prefill
    path already differs from decode).  The token-for-token equivalence
    contract therefore holds whenever no capacity drops occur — e.g.
    ``capacity_factor >= n_experts`` guarantees it (tests/test_slot_stream
    pins this); serve capacity-tight MoE with ``chunked_prefill=False`` if
    bitwise admission parity matters more than admission latency.

    Returns the updated cache; with ``return_hidden=True`` (attention
    families only — the speculative verify pass, see
    ``supports_draft_verify``) returns ``(hidden (1, C, D), cache)`` so the
    caller can project per-position logits over the chunk."""
    window = window_override if window_override is not None else cfg.sliding_window
    fam = cfg.family
    slot = jnp.asarray(slot)
    start = jnp.asarray(start)
    x = params["embed"][tokens][None, :, :]  # (1, C, D)
    x = constrain(x, ("act_batch", None, "act_embed"))

    def take(t):  # slot row of a stacked (L, n_slots, ...) leaf, keepdims
        return jax.lax.dynamic_index_in_dim(t, slot, 1, keepdims=True)

    def put(full, part):
        return jax.lax.dynamic_update_index_in_dim(full, part, slot, 1)

    if fam in ("dense", "moe", "vlm") and not _interleaved_moe(cfg):

        def body(h, inp):
            lp, kc, vc = inp
            h, (kc, vc) = BD.dense_layer_prefill_chunk(
                lp, h, cfg, kc, vc, start, sliding_window=window
            )
            return h, (kc, vc)

        h, (k_new, v_new) = jax.lax.scan(
            body, x, (params["layers"], take(cache["k"]), take(cache["v"]))
        )
        new_cache = {"k": put(cache["k"], k_new), "v": put(cache["v"], v_new)}
        return (h, new_cache) if return_hidden else new_cache

    if _interleaved_moe(cfg):
        me = cfg.moe_every
        n_groups = cfg.n_layers // me
        grp_dense = jax.tree.map(
            lambda t: t.reshape((n_groups, me - 1) + t.shape[1:]),
            params["layers"]["dense"],
        )
        grp_cache = jax.tree.map(
            lambda t: t.reshape((n_groups, me) + t.shape[1:]),
            {"k": take(cache["k"]), "v": take(cache["v"])},
        )

        def one(h, inp):
            lp, kc, vc = inp
            h, (kc, vc) = BD.dense_layer_prefill_chunk(
                lp, h, cfg, kc, vc, start, sliding_window=window
            )
            return h, (kc, vc)

        def body(h, inp):
            lp_d, lp_m, cg = inp
            h, (kd, vd) = jax.lax.scan(
                one, h, (lp_d, cg["k"][: me - 1], cg["v"][: me - 1])
            )
            h, (km, vm) = BD.dense_layer_prefill_chunk(
                lp_m, h, cfg, cg["k"][me - 1], cg["v"][me - 1], start,
                sliding_window=window,
            )
            k_new = jnp.concatenate([kd, km[None]], axis=0)
            v_new = jnp.concatenate([vd, vm[None]], axis=0)
            return h, (k_new, v_new)

        h, (k_new, v_new) = jax.lax.scan(
            body, x, (grp_dense, params["layers"]["moe"], grp_cache)
        )
        new_cache = {
            "k": put(cache["k"], k_new.reshape((cfg.n_layers,) + k_new.shape[2:])),
            "v": put(cache["v"], v_new.reshape((cfg.n_layers,) + v_new.shape[2:])),
        }
        return (h, new_cache) if return_hidden else new_cache

    if return_hidden:  # constant-state families cannot roll a verify back
        raise ValueError(f"return_hidden unsupported for family {fam}")

    if fam == "ssm_mamba2":

        def body(h, inp):
            lp, st = inp
            out, st = BM.mamba2_fwd(lp, h, cfg, initial=st, return_state=True)
            return h + out, st

        _, states = jax.lax.scan(
            body, x, (params["layers"],
                      {"conv": take(cache["conv"]), "ssm": take(cache["ssm"])})
        )
        return {"conv": put(cache["conv"], states["conv"]),
                "ssm": put(cache["ssm"], states["ssm"])}

    if fam == "ssm_rwkv6":

        def body(h, inp):
            lp, st = inp
            h, st = BR.rwkv6_layer_fwd(lp, h, cfg, state=st, return_state=True)
            return h, st

        _, states = jax.lax.scan(
            body,
            x,
            (
                params["layers"],
                {"tm_x": take(cache["tm_x"]), "cm_x": take(cache["cm_x"]),
                 "wkv": take(cache["wkv"])},
            ),
        )
        return {k: put(cache[k], states[k]) for k in ("tm_x", "cm_x", "wkv")}

    if fam == "hybrid":
        # mirror the grouped decode path (§Perf iteration 3): scan the mamba
        # chunk-forward within each shared-attention group, then one chunk
        # attention over the group's per-invocation slot rows
        shared = params["shared_attn"]
        every = cfg.attn_every
        n_inv = cfg.n_layers // every
        n_grouped = n_inv * every
        grp_params = jax.tree.map(
            lambda t: t[:n_grouped].reshape((n_inv, every) + t.shape[1:]),
            params["layers"],
        )
        grp_state = jax.tree.map(
            lambda t: t[:n_grouped].reshape((n_inv, every) + t.shape[1:]),
            {"conv": take(cache["conv"]), "ssm": take(cache["ssm"])},
        )

        def mamba_body(h, inp):
            lp, st = inp
            out, st = BM.mamba2_fwd(lp, h, cfg, initial=st, return_state=True)
            return h + out, st

        def take0(t):  # per-invocation (n_slots, KVH, S, hd) leaves
            return jax.lax.dynamic_index_in_dim(t, slot, 0, keepdims=True)

        new_states = []
        new_ak, new_av = [], []
        for g in range(n_inv):
            lp_g = jax.tree.map(lambda t: t[g], grp_params)
            st_g = jax.tree.map(lambda t: t[g], grp_state)
            x, st_out = jax.lax.scan(mamba_body, x, (lp_g, st_g))
            x, (kc, vc) = BD.dense_layer_prefill_chunk(
                shared, x, cfg,
                take0(cache["attn_k"][g]), take0(cache["attn_v"][g]), start,
                sliding_window=window,
            )
            new_states.append(st_out)
            new_ak.append(kc)
            new_av.append(vc)

        if n_grouped < cfg.n_layers:  # trailing mamba layers (no attn after)
            lp_t = jax.tree.map(lambda t: t[n_grouped:], params["layers"])
            st_t = {"conv": take(cache["conv"])[n_grouped:],
                    "ssm": take(cache["ssm"])[n_grouped:]}
            x, st_out = jax.lax.scan(mamba_body, x, (lp_t, st_t))
            new_states.append(st_out)

        merged = jax.tree.map(
            lambda *xs: jnp.concatenate([t for t in xs], axis=0), *new_states
        )
        return {
            "conv": put(cache["conv"], merged["conv"]),
            "ssm": put(cache["ssm"], merged["ssm"]),
            "attn_k": [
                jax.lax.dynamic_update_index_in_dim(cache["attn_k"][g], new_ak[g], slot, 0)
                for g in range(n_inv)
            ],
            "attn_v": [
                jax.lax.dynamic_update_index_in_dim(cache["attn_v"][g], new_av[g], slot, 0)
                for g in range(n_inv)
            ],
        }

    raise ValueError(f"chunked prefill unsupported for family {fam}")


# ---------------------------------------------------------------------------
# block-paged serving: KV pool + page-table decode / chunked prefill
# ---------------------------------------------------------------------------


def supports_paging(cfg: ModelConfig) -> bool:
    """Block-paged KV pools serve the attention-cache families on the
    kernel-native layout.  Constant-state families (SSM/RWKV) have O(1)
    per-slot state — there is nothing to page — and hybrid's per-invocation
    KV leaves keep the dense slot layout for now."""
    from repro.models.layers import LEGACY_DECODE

    return (
        not cfg.is_encoder
        and not LEGACY_DECODE
        and cfg.family in ("dense", "moe", "vlm")
    )


def init_paged_pool(cfg: ModelConfig, n_pages: int, page_size: int, dtype=None):
    """Boxed paged KV pool: per leaf ``(L, n_pages, KVH, page_size, hd)``.

    The page axis replaces the dense cache's (batch, seq) product — HBM is
    bound by pages actually mapped, not slots x max_seq.  Page contents keep
    the kernel-native (KVH, seq, hd) tile layout, so a gathered slot view is
    bitwise the dense cache row."""
    assert supports_paging(cfg), cfg.family
    dtype = dtype or jnp.dtype(cfg.dtype)
    shape = (cfg.n_layers, n_pages, cfg.n_kv_heads, page_size, cfg.head_dim)
    axes = ("layers", None, "cache_kv_heads", "kv_seq", "head_dim")
    return {
        "k": Box(jnp.zeros(shape, dtype), axes),
        "v": Box(jnp.zeros(shape, dtype), axes),
    }


def copy_pool_page(pool, src, dst):
    """Device half of a copy-on-write split: copy page ``src`` to ``dst``
    on every leaf (and every layer / tier-member plane).  The page axis is
    located from the trailing (P, KVH, page_size, hd) layout, so the same
    program serves engine pools and E-stacked tier pools."""

    def cp(t):
        ax = t.ndim - 4
        row = jax.lax.dynamic_index_in_dim(t, src, ax, keepdims=True)
        return jax.lax.dynamic_update_index_in_dim(t, row, dst, ax)

    return jax.tree.map(cp, pool)


def _layer(layers, l):
    """Layer ``l`` of a stacked ``(L, ...)`` layer-weight tree.

    The paged programs scan over the layer index and read each layer from
    the stack the body closes over, instead of scanning the stack as
    ``xs``: under a tier's member ``vmap`` a batched ``xs`` operand has its
    member axis moved behind the scanned one, and XLA then writes a
    layer-major copy of every weight stack on each call.  A closed-over
    stack keeps its member axis first and the slice fuses into the
    matmuls."""
    return jax.tree.map(
        lambda t: jax.lax.dynamic_index_in_dim(t, l, 0, keepdims=False), layers
    )


def decode_step_paged(
    params,
    token,
    pool,
    pos,
    pages,
    cfg: ModelConfig,
    *,
    window_override=None,
):
    """One decode token per slot against the block-paged KV pool.

    token: (B, 1) int32; pos: (B,) per-slot positions; pages: (B, n_pg)
    int32 page table (-1 = unmapped); pool: values tree from
    ``init_paged_pool``.  Each layer scatters the new K/V row into the
    slot's current page and attends over the gathered page view — bitwise
    what the dense slot cache computes (see serve/paging.py).  Returns
    (logits (B, V), new_pool)."""
    window = window_override if window_override is not None else cfg.sliding_window
    assert supports_paging(cfg), cfg.family
    x = params["embed"][token]  # (B, 1, D)
    x = constrain(x, ("act_batch", None, "act_embed"))

    if not _interleaved_moe(cfg):

        def body(h, inp):
            l, kp, vp = inp
            h, (kp, vp) = BD.dense_layer_decode_paged(
                _layer(params["layers"], l), h, cfg, kp, vp, pos, pages,
                sliding_window=window,
            )
            return h, (kp, vp)

        x, (k_new, v_new) = jax.lax.scan(
            body, x, (jnp.arange(cfg.n_layers), pool["k"], pool["v"])
        )
        new_pool = {"k": k_new, "v": v_new}
    else:
        me = cfg.moe_every
        n_groups = cfg.n_layers // me
        grp_dense = jax.tree.map(
            lambda t: t.reshape((n_groups, me - 1) + t.shape[1:]),
            params["layers"]["dense"],
        )
        grp_pool = jax.tree.map(
            lambda t: t.reshape((n_groups, me) + t.shape[1:]),
            {"k": pool["k"], "v": pool["v"]},
        )

        def one(h, inp):
            lp, kp, vp = inp
            h, (kp, vp) = BD.dense_layer_decode_paged(
                lp, h, cfg, kp, vp, pos, pages, sliding_window=window
            )
            return h, (kp, vp)

        def body(h, inp):
            lp_d, lp_m, pg = inp
            h, (kd, vd) = jax.lax.scan(
                one, h, (lp_d, pg["k"][: me - 1], pg["v"][: me - 1])
            )
            h, (km, vm) = BD.dense_layer_decode_paged(
                lp_m, h, cfg, pg["k"][me - 1], pg["v"][me - 1], pos, pages,
                sliding_window=window,
            )
            k_new = jnp.concatenate([kd, km[None]], axis=0)
            v_new = jnp.concatenate([vd, vm[None]], axis=0)
            return h, (k_new, v_new)

        x, (k_new, v_new) = jax.lax.scan(
            body, x, (grp_dense, params["layers"]["moe"], grp_pool)
        )
        new_pool = {
            "k": k_new.reshape((cfg.n_layers,) + k_new.shape[2:]),
            "v": v_new.reshape((cfg.n_layers,) + v_new.shape[2:]),
        }

    x = L.apply_norm(params["final_norm"], x, cfg)
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    logits = (x[:, 0] @ head).astype(jnp.float32)
    return constrain(logits, ("act_batch", "act_vocab")), new_pool


def prefill_into_slot_paged(
    params,
    tokens,
    pool,
    pages_row,
    start,
    cfg: ModelConfig,
    *,
    window_override=None,
    return_hidden: bool = False,
):
    """Paged counterpart of ``prefill_into_slot``: consume a C-token chunk
    of one slot's prompt into the pool pages its table row maps.

    tokens: (C,) int32 for positions [start, start+C); pages_row: (n_pg,)
    the slot's page-table row; start is a traced scalar.  Shared-prefix
    admission skips chunks for the shared span, so ``start`` begins at the
    first unshared position.  Returns the updated pool; with
    ``return_hidden=True`` returns ``(hidden (1, C, D), pool)`` for the
    speculative verify pass."""
    window = window_override if window_override is not None else cfg.sliding_window
    assert supports_paging(cfg), cfg.family
    start = jnp.asarray(start)
    x = params["embed"][tokens][None, :, :]  # (1, C, D)
    x = constrain(x, ("act_batch", None, "act_embed"))

    if not _interleaved_moe(cfg):

        def body(h, inp):
            l, kp, vp = inp
            h, (kp, vp) = BD.dense_layer_prefill_chunk_paged(
                _layer(params["layers"], l), h, cfg, kp, vp, start, pages_row,
                sliding_window=window,
            )
            return h, (kp, vp)

        h, (k_new, v_new) = jax.lax.scan(
            body, x, (jnp.arange(cfg.n_layers), pool["k"], pool["v"])
        )
        new_pool = {"k": k_new, "v": v_new}
        return (h, new_pool) if return_hidden else new_pool

    me = cfg.moe_every
    n_groups = cfg.n_layers // me
    grp_dense = jax.tree.map(
        lambda t: t.reshape((n_groups, me - 1) + t.shape[1:]),
        params["layers"]["dense"],
    )
    grp_pool = jax.tree.map(
        lambda t: t.reshape((n_groups, me) + t.shape[1:]),
        {"k": pool["k"], "v": pool["v"]},
    )

    def one(h, inp):
        lp, kp, vp = inp
        h, (kp, vp) = BD.dense_layer_prefill_chunk_paged(
            lp, h, cfg, kp, vp, start, pages_row, sliding_window=window
        )
        return h, (kp, vp)

    def body(h, inp):
        lp_d, lp_m, pg = inp
        h, (kd, vd) = jax.lax.scan(
            one, h, (lp_d, pg["k"][: me - 1], pg["v"][: me - 1])
        )
        h, (km, vm) = BD.dense_layer_prefill_chunk_paged(
            lp_m, h, cfg, pg["k"][me - 1], pg["v"][me - 1], start, pages_row,
            sliding_window=window,
        )
        k_new = jnp.concatenate([kd, km[None]], axis=0)
        v_new = jnp.concatenate([vd, vm[None]], axis=0)
        return h, (k_new, v_new)

    h, (k_new, v_new) = jax.lax.scan(
        body, x, (grp_dense, params["layers"]["moe"], grp_pool)
    )
    new_pool = {
        "k": k_new.reshape((cfg.n_layers,) + k_new.shape[2:]),
        "v": v_new.reshape((cfg.n_layers,) + v_new.shape[2:]),
    }
    return (h, new_pool) if return_hidden else new_pool


def prefill_into_slot_logits(
    params, tokens, cache, slot, start, cfg: ModelConfig, *, window_override=None
):
    """Chunked prefill that ALSO scores every chunk position: returns
    ``(logits (C, V) f32, cache)`` where ``logits[j]`` is the next-token
    distribution after prompt position ``start + j``.  This is the
    speculative verify pass (serve/speculative.py): feeding the token
    before each draft position yields, in one chunk, the model's own
    choice at every draft position — numerically the decode head, since
    chunked prefill and decode share the attention math
    (``layers._chunk_attend``) and the head projection
    (``layers.project_logits``)."""
    assert supports_draft_verify(cfg), cfg.family
    h, cache = prefill_into_slot(
        params, tokens, cache, slot, start, cfg,
        window_override=window_override, return_hidden=True,
    )
    return L.project_logits(params, h, cfg)[0], cache


def prefill_into_slot_paged_logits(
    params, tokens, pool, pages_row, start, cfg: ModelConfig, *, window_override=None
):
    """Paged twin of ``prefill_into_slot_logits``: ``(logits (C, V), pool)``."""
    assert supports_draft_verify(cfg), cfg.family
    h, pool = prefill_into_slot_paged(
        params, tokens, pool, pages_row, start, cfg,
        window_override=window_override, return_hidden=True,
    )
    return L.project_logits(params, h, cfg)[0], pool


# ---------------------------------------------------------------------------
# inputs: ShapeDtypeStruct specs (dry-run) and concrete arrays (smoke)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeConfig):
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    B, S = shape.global_batch, shape.seq_len
    f32, i32 = jnp.float32, jnp.int32
    bf = jnp.dtype(cfg.dtype)
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        if cfg.is_encoder:
            return {
                "embeds": sds((B, S, cfg.frontend_dim), bf),
                "targets": sds((B, S), i32),
                "mask": sds((B, S), f32),
            }
        if cfg.n_vision_tokens:
            St = S - cfg.n_vision_tokens
            return {
                "tokens": sds((B, St), i32),
                "embeds": sds((B, cfg.n_vision_tokens, cfg.frontend_dim), bf),
                "targets": sds((B, St), i32),
                "mask": sds((B, St), f32),
            }
        return {
            "tokens": sds((B, S), i32),
            "targets": sds((B, S), i32),
            "mask": sds((B, S), f32),
        }
    if shape.kind == "prefill":
        if cfg.is_encoder:
            return {"embeds": sds((B, S, cfg.frontend_dim), bf)}
        if cfg.n_vision_tokens:
            St = S - cfg.n_vision_tokens
            return {
                "tokens": sds((B, St), i32),
                "embeds": sds((B, cfg.n_vision_tokens, cfg.frontend_dim), bf),
            }
        return {"tokens": sds((B, S), i32)}
    if shape.kind == "decode":
        return {"token": sds((B, 1), i32), "pos": sds((), i32)}
    raise ValueError(shape.kind)


def make_inputs(cfg: ModelConfig, shape: ShapeConfig, rng=None):
    """Concrete random inputs matching input_specs (smoke tests)."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    specs = input_specs(cfg, shape)
    out = {}
    for name, s in specs.items():
        rng, k = jax.random.split(rng)
        if s.dtype == jnp.int32 and name in ("tokens", "targets", "token"):
            out[name] = jax.random.randint(k, s.shape, 0, cfg.vocab_size, jnp.int32)
        elif s.dtype == jnp.int32:
            out[name] = jnp.zeros(s.shape, jnp.int32)
        elif name == "mask":
            out[name] = jnp.ones(s.shape, s.dtype)
        else:
            out[name] = jax.random.normal(k, s.shape, jnp.float32).astype(s.dtype)
    return out
