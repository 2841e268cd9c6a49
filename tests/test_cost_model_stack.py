"""Tests for the measurement stack: the scan-aware jaxpr cost walker and
the hierarchical HLO collective parser (EXPERIMENTS.md §Roofline
methodology — each test pins one of the corrections documented there)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import config as kcfg
from repro.launch.jaxpr_cost import estimate_fn_cost
from repro.launch.roofline import parse_collectives, roofline_terms


# ---------------------------------------------------------------------------
# jaxpr walker
# ---------------------------------------------------------------------------


def test_matmul_flops_exact():
    a = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    b = jax.ShapeDtypeStruct((512, 128), jnp.float32)
    c = estimate_fn_cost(lambda x, y: x @ y, a, b)
    assert c["flops"] == 2 * 256 * 512 * 128


def test_scan_multiplies_trip_count():
    a = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def f(x):
        def body(c, _):
            return c @ x, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    c1 = estimate_fn_cost(lambda x: x @ x, a)
    c10 = estimate_fn_cost(f, a)
    assert c10["flops"] >= 10 * c1["flops"]
    assert c10["flops"] < 11 * c1["flops"] + 64 * 64 * 20


def test_inner_jit_is_not_skipped():
    """Regression: this JAX names the pjit primitive 'jit'; kernel wrappers
    are jit-wrapped and must still be counted."""
    a = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    inner = jax.jit(lambda x: x @ x)
    c = estimate_fn_cost(lambda x: inner(x), a)
    assert c["flops"] >= 2 * 128**3


def test_dynamic_update_slice_charged_for_slice_only():
    buf = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    upd = jax.ShapeDtypeStruct((1, 1024), jnp.float32)
    c = estimate_fn_cost(
        lambda b, u: jax.lax.dynamic_update_slice(b, u, (5, 0)), buf, upd
    )
    # 2 * slice bytes, NOT the 4 MB buffer
    assert c["bytes"] <= 4 * 1024 * 2 + 1024
    assert c["bytes"] > 0


def test_pallas_kernel_block_traffic_counted():
    from repro.kernels.decode_attention import ops as dops

    B, KVH, S, hd, H = 2, 2, 2048, 64, 4
    q = jax.ShapeDtypeStruct((B, 1, H, hd), jnp.bfloat16)
    kc = jax.ShapeDtypeStruct((B, KVH, S, hd), jnp.bfloat16)
    with kcfg.use_impl("pallas_interpret"):
        c = estimate_fn_cost(
            lambda q, k, v: dops.decode_attention_bksd(q, k, v, 100), q, kc, kc
        )
    sweep = B * KVH * S * hd * 2 * 2  # k+v streamed once
    assert c["bytes"] >= sweep


def test_flash_kernel_flops_counted():
    from repro.kernels.flash_attention import ops as fops

    B, S, H, hd = 1, 512, 2, 64
    q = jax.ShapeDtypeStruct((B, S, H, hd), jnp.bfloat16)
    with kcfg.use_impl("pallas_interpret"):
        c = estimate_fn_cost(lambda q, k, v: fops.flash_attention(q, k, v), q, q, q)
    assert c["flops"] >= 2 * 2 * B * H * S * S * hd // 2  # at least causal half


# ---------------------------------------------------------------------------
# HLO collective parser
# ---------------------------------------------------------------------------

_FAKE_HLO = """HloModule test

%cond.1 (arg: (s32[], f32[8])) -> pred[] {
  %c = s32[] constant(7)
  ROOT %lt = pred[] compare(%iter, %c), direction=LT
}

%body.2 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %ar = f32[1024,32]{1,0} all-reduce(%x), replica_groups={}
  ROOT %t = (s32[], f32[8]) tuple(%i, %y)
}

ENTRY %main.3 (p0: f32[8]) -> f32[8] {
  %ag = bf16[64,128]{1,0} all-gather(%p0), dimensions={0}
  %w = (s32[], f32[8]) while(%init), condition=%cond.1, body=%body.2
  ROOT %out = f32[8] get-tuple-element(%w), index=1
}
"""


def test_parse_collectives_hierarchical():
    out = parse_collectives(_FAKE_HLO)
    assert out["all-gather"] == 64 * 128 * 2
    # the while body's all-reduce executes 7 times
    assert out["all-reduce"] == 7 * 1024 * 32 * 4


def test_parse_collectives_empty():
    out = parse_collectives("HloModule empty\n\nENTRY %m () -> f32[] {\n}\n")
    assert sum(out.values()) == 0


def test_roofline_terms_bottleneck():
    t = roofline_terms({"flops": 197e12, "bytes accessed": 1.0}, 0, 256)
    assert t["bottleneck"] == "compute" and abs(t["t_compute_s"] - 1.0) < 1e-9
    t2 = roofline_terms({"flops": 1.0, "bytes accessed": 819e9}, 0, 256)
    assert t2["bottleneck"] == "memory"
    t3 = roofline_terms({"flops": 0.0, "bytes accessed": 0.0}, 256 * 50e9, 256)
    assert t3["bottleneck"] == "collective" and abs(t3["t_collective_s"] - 1.0) < 1e-9
