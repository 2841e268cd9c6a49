"""Per-kernel correctness sweeps: Pallas (interpret=True) and the XLA
fallback vs the pure-jnp ref.py oracle, across shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import config as kcfg
from repro.kernels.agreement import ops as agree_ops, ref as agree_ref
from repro.kernels.decode_attention import ops as dec_ops, ref as dec_ref
from repro.kernels.flash_attention import ops as flash_ops, ref as flash_ref
from repro.kernels.mamba2_ssd import ops as ssd_ops, ref as ssd_ref
from repro.kernels.rwkv6_wkv import ops as wkv_ops, ref as wkv_ref

# interpret-mode Pallas runs execute the kernel body in Python on CPU and
# take many minutes across the sweeps — marked slow, excluded from tier 1
# (pyproject.toml addopts); run them with `pytest -m slow` or `-m ""`.
IMPLS = ["xla", pytest.param("pallas_interpret", marks=pytest.mark.slow)]


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else dict(atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize(
    "B,S,H,KVH,hd,causal,window",
    [
        (1, 128, 4, 4, 64, True, None),
        (2, 256, 4, 2, 64, True, None),
        (2, 256, 8, 1, 32, True, 64),
        (1, 512, 4, 4, 64, False, None),  # encoder
        (2, 128, 4, 2, 128, True, 32),
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(impl, B, S, H, KVH, hd, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KVH, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KVH, hd), jnp.float32).astype(dtype)
    ref = flash_ref.attention_ref(q, k, v, causal=causal, window=window)
    with kcfg.use_impl(impl):
        out = flash_ops.flash_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize(
    "causal,window,softcap",
    [(True, None, None), (True, 32, None), (False, None, None), (True, None, 10.0)],
)
def test_flash_attention_custom_vjp_grads(causal, window, softcap):
    """The chunked flash backward (custom_vjp) matches AD through the naive
    oracle for q/k/v cotangents."""
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    B, S, H, KVH, hd = 2, 128, 4, 2, 32
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KVH, hd))
    v = jax.random.normal(ks[2], (B, S, KVH, hd))
    do = jax.random.normal(ks[3], (B, S, H, hd))
    f1 = lambda q, k, v: (
        flash_ops.flash_attention(q, k, v, causal=causal, window=window, softcap=softcap) * do
    ).sum()
    f2 = lambda q, k, v: (
        flash_ref.attention_ref(q, k, v, causal=causal, window=window, softcap=softcap) * do
    ).sum()
    g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("arch", ["olmo-1b", "rwkv6-7b", "zamba2-2.7b"])
def test_loss_grad_through_kernels(arch):
    """``jax.grad`` of the training loss traces and runs with the kernels
    selected (flash, wkv6, ssd): a pallas_call has no transpose rule, so
    each kernel's gradient must come from its jnp path.  The gradients
    match the all-jnp ones up to the bf16 forward's rounding."""
    from repro.configs import ShapeConfig, get_config
    from repro.models import api
    from repro.models.params import unbox

    cfg = get_config(arch).reduced()
    values, _ = unbox(api.init_params(cfg, jax.random.PRNGKey(0)))
    batch = api.make_inputs(cfg, ShapeConfig("grad", 64, 2, "train"))

    def grads(impl):
        # a fresh function per impl: the impl is read at trace time
        with kcfg.use_impl(impl):
            return jax.jit(
                jax.grad(lambda p: api.loss_fn(p, batch, cfg)[0])
            )(values)

    got, want = grads("pallas_interpret"), grads("xla")
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        assert bool(jnp.isfinite(g).all()), f"{arch}: non-finite grad {path}"
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(a - b) <= 0.05 * np.linalg.norm(b) + 1e-6


@pytest.mark.parametrize("impl", IMPLS)
def test_flash_attention_softcap(impl):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 64))
    k = jax.random.normal(ks[1], (1, 128, 2, 64))
    v = jax.random.normal(ks[2], (1, 128, 2, 64))
    ref = flash_ref.attention_ref(q, k, v, causal=True, softcap=20.0)
    with kcfg.use_impl(impl):
        out = flash_ops.flash_attention(q, k, v, causal=True, softcap=20.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# flash attention: per-row starts (left-pad carve-out on the kernel path)
# ---------------------------------------------------------------------------

# starts patterns over (B=4, S=128): all-zero (must equal the starts-free
# run), ragged left-padding, one fully-padded row (start == S -> zeros),
# and the extreme one-valid-column start == S-1
_STARTS_PATTERNS = {
    "all_zero": [0, 0, 0, 0],
    "ragged": [0, 37, 64, 101],
    "full_pad_row": [0, 37, 128, 64],
    "last_col": [127, 127, 127, 127],
}
_MASK_FAMILIES = {
    "causal": dict(causal=True, window=None, softcap=None),
    "window": dict(causal=True, window=32, softcap=None),
    "softcap": dict(causal=True, window=None, softcap=10.0),
}


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("pattern", sorted(_STARTS_PATTERNS))
@pytest.mark.parametrize("maskfam", sorted(_MASK_FAMILIES))
def test_flash_attention_starts_parity(impl, pattern, maskfam):
    """With ``starts`` supplied the dispatcher must keep the kernel path and
    agree with the XLA path and the ref oracle to 1e-5."""
    kw = _MASK_FAMILIES[maskfam]
    starts = jnp.asarray(_STARTS_PATTERNS[pattern], jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(31), 3)
    B, S, H, KVH, hd = 4, 128, 4, 2, 64
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KVH, hd))
    v = jax.random.normal(ks[2], (B, S, KVH, hd))
    ref = flash_ref.attention_ref(q, k, v, starts=starts, **kw)
    with kcfg.use_impl(impl):
        out = flash_ops.flash_attention(q, k, v, starts=starts, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)
    if pattern == "all_zero":
        with kcfg.use_impl(impl):
            plain = flash_ops.flash_attention(q, k, v, **kw)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(plain), atol=1e-5, rtol=1e-5
        )
    if pattern == "full_pad_row":  # row 2 is pure padding -> zeros
        np.testing.assert_array_equal(np.asarray(out)[2], 0.0)


@pytest.mark.slow
def test_flash_attention_starts_no_xla_fallback(monkeypatch):
    """starts used to force impl='xla'; the kernel path must now serve it
    without touching any XLA fallback."""

    def _boom(*a, **kw):
        raise AssertionError("starts fell back to the XLA path")

    monkeypatch.setattr(flash_ops, "_xla_flash", _boom)
    monkeypatch.setattr(flash_ops, "_flash_diff", _boom)
    ks = jax.random.split(jax.random.PRNGKey(32), 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 64))
    k = jax.random.normal(ks[1], (2, 128, 2, 64))
    v = jax.random.normal(ks[2], (2, 128, 2, 64))
    starts = jnp.asarray([0, 57], jnp.int32)
    ref = flash_ref.attention_ref(q, k, v, causal=True, starts=starts)
    with kcfg.use_impl("pallas_interpret"):
        out = flash_ops.flash_attention(q, k, v, causal=True, starts=starts)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


@pytest.mark.slow
def test_flash_attention_starts_multiblock_skip():
    """Small blocks force a multi-block KV sweep so below-start blocks are
    actually skipped; skip on/off must agree bitwise (the skipped blocks
    were fully masked) and match the oracle."""
    from repro.kernels.flash_attention import kernel as flash_kernel

    ks = jax.random.split(jax.random.PRNGKey(33), 3)
    B, S, H, KVH, hd = 4, 128, 2, 1, 64
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KVH, hd))
    v = jax.random.normal(ks[2], (B, S, KVH, hd))
    starts = jnp.asarray([0, 33, 96, 128], jnp.int32)
    ref = flash_ref.attention_ref(q, k, v, causal=True, starts=starts)
    qt, kt, vt = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    outs = {}
    for skip in (True, False):
        o = flash_kernel.flash_attention_bhsd(
            qt, kt, vt, starts, causal=True, block_q=32, block_k=32,
            interpret=True, skip_pad_blocks=skip,
        )
        outs[skip] = np.asarray(o.transpose(0, 2, 1, 3))
        np.testing.assert_allclose(outs[skip], np.asarray(ref), atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(outs[True], outs[False])


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize(
    "B,S,H,KVH,hd,cur,window",
    [
        (2, 256, 4, 2, 64, 100, None),
        (1, 512, 8, 8, 64, 512, None),
        (2, 256, 4, 1, 128, 200, 64),
        (3, 128, 6, 2, 32, 1, None),
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(impl, B, S, H, KVH, hd, cur, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KVH, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KVH, hd), jnp.float32).astype(dtype)
    ref = dec_ref.decode_attention_ref(q, k, v, cur, window=window)
    with kcfg.use_impl(impl):
        out = dec_ops.decode_attention(q, k, v, cur, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
    )


# ---------------------------------------------------------------------------
# decode attention: per-row starts (left-pad carve-out on the kernel path)
# ---------------------------------------------------------------------------

# (cur_len per row, starts per row) over (B=4, S=256): all-zero, ragged,
# one row whose start swallows its whole valid cache (pure padding ->
# zeros), and the one-valid-column extreme start == S-1
_DEC_STARTS_PATTERNS = {
    "all_zero": ([200, 100, 256, 64], [0, 0, 0, 0]),
    "ragged": ([200, 100, 256, 64], [0, 37, 128, 63]),
    "full_pad_row": ([200, 100, 50, 64], [0, 37, 50, 0]),
    "last_col": ([256, 256, 256, 256], [255, 255, 255, 255]),
}


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("pattern", sorted(_DEC_STARTS_PATTERNS))
@pytest.mark.parametrize("maskfam", sorted(_MASK_FAMILIES))
def test_decode_attention_starts_parity(impl, pattern, maskfam):
    kw = {k_: v_ for k_, v_ in _MASK_FAMILIES[maskfam].items() if k_ != "causal"}
    cur, starts = _DEC_STARTS_PATTERNS[pattern]
    cur = jnp.asarray(cur, jnp.int32)
    starts = jnp.asarray(starts, jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(41), 3)
    B, S, H, KVH, hd = 4, 256, 4, 2, 64
    q = jax.random.normal(ks[0], (B, 1, H, hd))
    k = jax.random.normal(ks[1], (B, S, KVH, hd))
    v = jax.random.normal(ks[2], (B, S, KVH, hd))
    kt, vt = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    ref = dec_ref.decode_attention_ref(q, k, v, cur, starts=starts, **kw)
    with kcfg.use_impl(impl):
        out = dec_ops.decode_attention_bksd(q, kt, vt, cur, starts=starts, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)
    if pattern == "all_zero":
        with kcfg.use_impl(impl):
            plain = dec_ops.decode_attention_bksd(q, kt, vt, cur, **kw)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(plain), atol=1e-5, rtol=1e-5
        )
    if pattern == "full_pad_row":  # row 2's start swallows its cache
        np.testing.assert_array_equal(np.asarray(out)[2], 0.0)


@pytest.mark.slow
def test_decode_attention_starts_no_xla_fallback(monkeypatch):
    def _boom(*a, **kw):
        raise AssertionError("starts fell back to the XLA path")

    monkeypatch.setattr(dec_ops, "_xla_decode_bksd", _boom)
    ks = jax.random.split(jax.random.PRNGKey(42), 3)
    q = jax.random.normal(ks[0], (2, 1, 4, 64))
    kt = jax.random.normal(ks[1], (2, 2, 256, 64))
    vt = jax.random.normal(ks[2], (2, 2, 256, 64))
    cur = jnp.asarray([100, 256], jnp.int32)
    starts = jnp.asarray([0, 57], jnp.int32)
    ref = dec_ref.decode_attention_ref(
        q, kt.transpose(0, 2, 1, 3), vt.transpose(0, 2, 1, 3), cur, starts=starts
    )
    with kcfg.use_impl("pallas_interpret"):
        out = dec_ops.decode_attention_bksd(q, kt, vt, cur, starts=starts)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


@pytest.mark.slow
def test_decode_attention_starts_multiblock_skip():
    """block_k=64 over S=256 -> 4 cache blocks; below-start blocks skip and
    skip on/off agree bitwise."""
    from repro.kernels.decode_attention import kernel as dec_kernel

    ks = jax.random.split(jax.random.PRNGKey(43), 3)
    B, S, KVH, G, hd = 4, 256, 2, 2, 64
    q = jax.random.normal(ks[0], (B, KVH, G, hd))
    kt = jax.random.normal(ks[1], (B, KVH, S, hd))
    vt = jax.random.normal(ks[2], (B, KVH, S, hd))
    cur = jnp.asarray([256, 200, 150, 256], jnp.int32)
    starts = jnp.asarray([0, 70, 140, 255], jnp.int32)
    ref = dec_ref.decode_attention_ref(
        q.reshape(B, 1, KVH * G, hd),
        kt.transpose(0, 2, 1, 3),
        vt.transpose(0, 2, 1, 3),
        cur,
        starts=starts,
    )
    outs = {}
    for skip in (True, False):
        o = dec_kernel.decode_attention_bkgd(
            q, kt, vt, cur, starts, block_k=64, interpret=True,
            skip_pad_blocks=skip,
        )
        outs[skip] = np.asarray(o.reshape(B, 1, KVH * G, hd))
        np.testing.assert_allclose(outs[skip], np.asarray(ref), atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(outs[True], outs[False])


# ---------------------------------------------------------------------------
# decode attention: block-paged pools (page table via scalar prefetch)
# ---------------------------------------------------------------------------


def _paged_case(seed, B, n_pg, ps, KVH, H, hd, cur, share_first=False):
    """Random pool + page table: each row maps exactly the pages its
    cur_len needs (rest -1 = unmapped), scattered through the pool in
    permuted order.  ``share_first`` points every row's first table entry
    at the SAME pool page — the shared-prefix layout."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    P = B * n_pg + 2  # head-room + the overflow sink (never mapped)
    q = jax.random.normal(ks[0], (B, 1, H, hd))
    k_pool = jax.random.normal(ks[1], (P, KVH, ps, hd))
    v_pool = jax.random.normal(ks[2], (P, KVH, ps, hd))
    perm = np.random.default_rng(seed).permutation(P - 1)
    pages = np.full((B, n_pg), -1, np.int32)
    t = 0
    for b in range(B):
        for i in range((int(cur[b]) + ps - 1) // ps):
            pages[b, i] = perm[t]
            t += 1
    if share_first:
        pages[:, 0] = pages[0, 0]
    return q, k_pool, v_pool, jnp.asarray(pages), jnp.asarray(cur, jnp.int32)


_PAGED_CASES = {
    "ragged": dict(B=4, n_pg=4, ps=8, cur=[32, 17, 8, 1]),
    "full": dict(B=2, n_pg=2, ps=16, cur=[32, 32]),
    "window": dict(B=3, n_pg=4, ps=8, cur=[25, 32, 9], window=16),
    "softcap": dict(B=2, n_pg=3, ps=8, cur=[24, 5], softcap=10.0),
    "shared_prefix": dict(B=4, n_pg=3, ps=8, cur=[24, 20, 10, 9], share=True),
}


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("case", sorted(_PAGED_CASES))
def test_decode_attention_paged(impl, case):
    c = dict(_PAGED_CASES[case])
    window = c.pop("window", None)
    softcap = c.pop("softcap", None)
    share = c.pop("share", False)
    H, KVH, hd = 4, 2, 64
    q, kp, vp, pages, cur = _paged_case(
        5, c["B"], c["n_pg"], c["ps"], KVH, H, hd, c["cur"], share_first=share
    )
    ref = dec_ref.decode_attention_paged_ref(
        q, kp, vp, pages, cur, window=window, softcap=softcap
    )
    with kcfg.use_impl(impl):
        out = dec_ops.decode_attention_paged(
            q, kp, vp, pages, cur, window=window, softcap=softcap
        )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4
    )


def test_decode_attention_paged_xla_bitwise_matches_dense():
    """The gathered-view XLA route is BITWISE the dense masked sweep over
    the equivalent contiguous cache in the layout the dense serving path
    holds, kernel-native (B, KVH, S, hd) — the foundation of the serving
    paged == dense parity contract (unmapped pages gather zero rows, which
    the length mask pins to softmax weight exactly 0.0).  The legacy
    (B, S, KVH, hd) layout contracts the same numbers in another order,
    so it agrees only to f32 rounding (DESIGN.md §10)."""
    B, n_pg, ps, KVH, H, hd = 4, 4, 8, 2, 4, 64
    cur = [32, 17, 8, 1]
    q, kp, vp, pages, curj = _paged_case(
        9, B, n_pg, ps, KVH, H, hd, cur, share_first=True
    )
    S = n_pg * ps
    kd = np.zeros((B, KVH, S, hd), np.float32)
    vd = np.zeros((B, KVH, S, hd), np.float32)
    pg = np.asarray(pages)
    for b in range(B):
        for i in range(n_pg):
            if pg[b, i] >= 0:  # pool pages are (KVH, ps, hd) tiles
                kd[b, :, i * ps:(i + 1) * ps] = np.asarray(kp[pg[b, i]])
                vd[b, :, i * ps:(i + 1) * ps] = np.asarray(vp[pg[b, i]])
    with kcfg.use_impl("xla"):
        paged = dec_ops.decode_attention_paged(q, kp, vp, pages, curj)
        dense = dec_ops.decode_attention_bksd(
            q, jnp.asarray(kd), jnp.asarray(vd), curj
        )
        legacy = dec_ops.decode_attention(
            q, jnp.asarray(kd.transpose(0, 2, 1, 3)),
            jnp.asarray(vd.transpose(0, 2, 1, 3)), curj,
        )
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(dense))
    np.testing.assert_allclose(
        np.asarray(paged), np.asarray(legacy), rtol=0, atol=1e-6
    )


def test_decode_attention_paged_rejects_bad_inputs():
    q, kp, vp, pages, cur = _paged_case(3, 2, 2, 8, 2, 4, 64, [9, 16])
    with pytest.raises(ValueError, match="pool mismatch"):
        dec_ops.decode_attention_paged(q, kp, vp[:-1], pages, cur)
    with pytest.raises(ValueError, match="page table"):
        dec_ops.decode_attention_paged(q, kp, vp, pages[:1], cur)
    from repro.kernels.decode_attention import kernel as dec_kernel

    # sublane guard: a 4-row page cannot tile the TPU block layout
    qk = q.reshape(2, 2, 2, 64)
    with pytest.raises(ValueError, match="sublane"):
        dec_kernel.decode_attention_paged_bkgd(
            qk, kp[:, :, :4], vp[:, :, :4], cur, pages, interpret=True
        )


# ---------------------------------------------------------------------------
# serving regression: the left-pad carve-out stays on the kernel path
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_serving_leftpad_kernel_path_matches_solo():
    """Left-padded generate AND slot-based serve_continuous, with
    impl='pallas_interpret' forced end to end, still reproduce solo runs
    token-for-token — serving never needs the XLA detour."""
    from repro.configs.base import ModelConfig
    from repro.models import api
    from repro.models.params import unbox
    from repro.serve.batching import Request
    from repro.serve.engine import ServingEngine

    cfg = ModelConfig(
        name="tiny-dense-kernelpath", family="dense", n_layers=2, d_model=32,
        d_ff=64, vocab_size=64, n_heads=2, n_kv_heads=2, remat=False,
    )
    values, _ = unbox(api.init_params(cfg, jax.random.PRNGKey(0)))
    rng = np.random.default_rng(5)
    lens, S = [3, 7, 12, 16], 16
    toks = np.zeros((4, S), np.int32)
    starts = np.zeros((4,), np.int32)
    prompts = []
    for i, L in enumerate(lens):
        p = rng.integers(0, 64, L).astype(np.int32)
        prompts.append(p)
        toks[i, S - L:] = p
        starts[i] = S - L

    with kcfg.use_impl("pallas_interpret"):
        eng = ServingEngine(cfg, values, max_batch=4)
        gen = eng.generate(toks, 5, starts=starts)
        solo = ServingEngine(cfg, values)
        for i, p in enumerate(prompts):
            np.testing.assert_array_equal(gen[i], solo.generate(p[None], 5)[0])

        reqs = [
            Request(
                tokens=rng.integers(0, 64, int(rng.integers(3, 10))).astype(np.int32),
                max_new_tokens=3,
            )
            for _ in range(5)
        ]
        done = eng.serve_continuous(reqs, n_slots=3, max_seq=32)
        assert len(done) == 5
        for r in done:
            ref = solo.generate(r.tokens[None], r.max_new_tokens)[0]
            np.testing.assert_array_equal(r.output, ref)


# ---------------------------------------------------------------------------
# mamba2 ssd
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize(
    "B,S,H,P,G,N,chunk",
    [
        (2, 128, 4, 32, 2, 16, 32),
        (1, 256, 2, 64, 1, 64, 64),
        (2, 96, 4, 32, 4, 16, 32),  # padded (96 % 32 == 0 but test chunk 64)
    ],
)
def test_mamba2_ssd(impl, B, S, H, P, G, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))) * 0.5
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.5
    ref, href = ssd_ref.ssd_ref(x, dt, A, Bm, Cm, return_final_state=True)
    with kcfg.use_impl(impl):
        if impl == "pallas_interpret" and S % chunk:
            pytest.skip("pallas path requires divisible chunks")
        out, h = ssd_ops.ssd(x, dt, A, Bm, Cm, chunk=chunk, return_final_state=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(href), atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("impl", IMPLS)
def test_mamba2_ssd_initial_state(impl):
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    B, S, H, P, G, N = 2, 64, 2, 16, 1, 8
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))) * 0.5
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.5
    h0 = jax.random.normal(ks[5], (B, H, N, P)) * 0.2
    ref = ssd_ref.ssd_ref(x, dt, A, Bm, Cm, initial_state=h0)
    with kcfg.use_impl(impl):
        out = ssd_ops.ssd(x, dt, A, Bm, Cm, chunk=32, initial_state=h0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-4, rtol=5e-4)


def test_mamba2_ssd_interpret_small():
    """Fast tier: the kernel body (triangular-matmul prefix sum) against
    the oracle at one small shape with a state carried across chunks."""
    ks = jax.random.split(jax.random.PRNGKey(12), 6)
    B, S, H, P, G, N = 1, 64, 2, 16, 1, 8
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))) * 0.5
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.5
    h0 = jax.random.normal(ks[5], (B, H, N, P)) * 0.2
    ref, href = ssd_ref.ssd_ref(
        x, dt, A, Bm, Cm, initial_state=h0, return_final_state=True
    )
    with kcfg.use_impl("pallas_interpret"):
        out, h = ssd_ops.ssd(
            x, dt, A, Bm, Cm, chunk=32, initial_state=h0,
            return_final_state=True,
        )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(href), atol=5e-4, rtol=5e-4)


def test_mamba2_step_matches_scan():
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    B, S, H, P, G, N = 2, 16, 2, 16, 1, 8
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))) * 0.5
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.5
    full = ssd_ref.ssd_ref(x, dt, A, Bm, Cm)
    st = jnp.zeros((B, H, N, P))
    for t in range(S):
        y, st = ssd_ops.ssd_step(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], st)
        np.testing.assert_allclose(np.asarray(y), np.asarray(full[:, t]), atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# rwkv6 wkv
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize(
    "B,S,H,D,chunk",
    [(2, 128, 3, 32, 32), (1, 64, 2, 64, 32), (2, 80, 2, 32, 32)],
)
def test_rwkv6_wkv(impl, B, S, H, D, chunk):
    ks = jax.random.split(jax.random.PRNGKey(6), 6)
    r = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, D)) * 0.5)
    u = jax.random.normal(ks[4], (H, D)) * 0.5
    s0 = jax.random.normal(ks[5], (B, H, D, D)) * 0.1
    ref, sref = wkv_ref.wkv6_ref(r, k, v, logw, u, initial_state=s0, return_final_state=True)
    with kcfg.use_impl(impl):
        if impl == "pallas_interpret" and S % chunk:
            pytest.skip("pallas path requires divisible chunks")
        out, s = wkv_ops.wkv6(r, k, v, logw, u, chunk=chunk, initial_state=s0, return_final_state=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sref), atol=2e-3, rtol=2e-3)


def test_rwkv6_wkv_interpret_small():
    """Fast tier: the kernel body ((H, 1, D) ``u`` blocks, triangular
    prefix sums, the static last-row read) against the oracle at one
    small shape, two chunks, with an initial state."""
    ks = jax.random.split(jax.random.PRNGKey(13), 6)
    B, S, H, D = 1, 64, 2, 32
    r = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, D)) * 0.5)
    u = jax.random.normal(ks[4], (H, D)) * 0.5
    s0 = jax.random.normal(ks[5], (B, H, D, D)) * 0.1
    ref, sref = wkv_ref.wkv6_ref(
        r, k, v, logw, u, initial_state=s0, return_final_state=True
    )
    with kcfg.use_impl("pallas_interpret"):
        out, s = wkv_ops.wkv6(
            r, k, v, logw, u, chunk=32, initial_state=s0,
            return_final_state=True,
        )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sref), atol=2e-3, rtol=2e-3)


def test_rwkv6_step_matches_scan():
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    B, S, H, D = 2, 12, 2, 16
    r = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, D)) * 0.5)
    u = jax.random.normal(ks[4], (H, D)) * 0.5
    full = wkv_ref.wkv6_ref(r, k, v, logw, u)
    st = jnp.zeros((B, H, D, D))
    for t in range(S):
        y, st = wkv_ops.wkv6_step(r[:, t], k[:, t], v[:, t], logw[:, t], u, st)
        np.testing.assert_allclose(np.asarray(y), np.asarray(full[:, t]), atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# agreement
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("E,B,V", [(2, 128, 2048), (3, 256, 4096), (5, 128, 512)])
def test_agreement(impl, E, B, V):
    logits = jax.random.normal(jax.random.PRNGKey(8), (E, B, V)) * 2
    ref = agree_ref.agreement_ref(logits)
    with kcfg.use_impl(impl):
        out = agree_ops.agreement(logits)
    np.testing.assert_array_equal(np.asarray(out["pred"]), np.asarray(ref["pred"]))
    np.testing.assert_allclose(np.asarray(out["vote_frac"]), np.asarray(ref["vote_frac"]))
    np.testing.assert_allclose(
        np.asarray(out["mean_score"]), np.asarray(ref["mean_score"]), atol=1e-5, rtol=1e-5
    )


@pytest.mark.parametrize("V", [640, 2176])
def test_agreement_interpret_unaligned_vocab(V):
    """Fast tier: vocabularies that are a multiple of 128 but not of the
    2048-lane block are padded with -inf; pred and vote_frac stay exact."""
    logits = jax.random.normal(jax.random.PRNGKey(14), (3, 16, V)) * 2
    # ties between members make the vote non-trivial
    logits = logits.at[1].set(logits[0])
    ref = agree_ref.agreement_ref(logits)
    with kcfg.use_impl("pallas_interpret"):
        out = agree_ops.agreement(logits)
    np.testing.assert_array_equal(np.asarray(out["pred"]), np.asarray(ref["pred"]))
    np.testing.assert_array_equal(
        np.asarray(out["vote_frac"]), np.asarray(ref["vote_frac"])
    )
    np.testing.assert_allclose(
        np.asarray(out["mean_score"]), np.asarray(ref["mean_score"]), atol=1e-5, rtol=1e-5
    )


@pytest.mark.parametrize("impl", IMPLS)
def test_agreement_identical_members(impl):
    logits = jnp.tile(jax.random.normal(jax.random.PRNGKey(9), (1, 64, 512)), (4, 1, 1))
    with kcfg.use_impl(impl):
        out = agree_ops.agreement(logits)
    assert float(out["vote_frac"].min()) == 1.0
