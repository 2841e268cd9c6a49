"""Ahead-of-time compiles of the seven Pallas kernels for a TPU v5e, at the
widths of the models that use them.

Nothing runs: each kernel is lowered and compiled against a v5e:2x2
topology that the installed TPU compiler describes without a chip, which
refuses what the chip's compiler would refuse (unaligned blocks, ops the
TPU lowering lacks, too much VMEM).  Each test asserts the compiled program
holds the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.  Keep these tests in this one file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config


@pytest.fixture(scope="module")
def topology():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        from jax.experimental import topologies

        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except RuntimeError as e:
            # only an installation without the TPU compiler skips; a TPU
            # library that is there but broken fails every test here
            if "TPU support not installed" not in str(e):
                raise
            pytest.skip(f"no TPU compiler installed: {e}")
    # a persistent cache would store programs no CPU process can read back
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topology):
    return SingleDeviceSharding(topology.devices[0])


def _compiles_kernel(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _heads(arch):
    cfg = get_config(arch)
    return cfg.n_heads, cfg.n_kv_heads, cfg.head_dim


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
ATTN_ARCHS = ("olmo-1b", "internlm2-1.8b")


@pytest.mark.parametrize("arch", ATTN_ARCHS)
def test_flash_attention_compiles(one_chip, arch):
    from repro.kernels.flash_attention import kernel

    H, KVH, hd = _heads(arch)
    B, S = 2, 512
    _compiles_kernel(
        lambda q, k, v: kernel.flash_attention_bhsd(q, k, v), one_chip,
        ((B, H, S, hd), BF16), ((B, KVH, S, hd), BF16), ((B, KVH, S, hd), BF16),
    )


@pytest.mark.parametrize("arch", ATTN_ARCHS)
def test_decode_attention_compiles(one_chip, arch):
    from repro.kernels.decode_attention import kernel

    H, KVH, hd = _heads(arch)
    B, S = 8, 2048
    _compiles_kernel(
        lambda q, k, v, n: kernel.decode_attention_bkgd(q, k, v, n), one_chip,
        ((B, KVH, H // KVH, hd), BF16), ((B, KVH, S, hd), BF16),
        ((B, KVH, S, hd), BF16), ((B,), I32),
    )


@pytest.mark.parametrize("arch", ATTN_ARCHS)
def test_paged_decode_attention_compiles(one_chip, arch):
    from repro.kernels.decode_attention import kernel

    H, KVH, hd = _heads(arch)
    B, page, n_pg = 8, 16, 16  # the serving default: 256 rows per slot
    P = B * n_pg + 1
    _compiles_kernel(
        lambda q, k, v, n, t: kernel.decode_attention_paged_bkgd(q, k, v, n, t),
        one_chip,
        ((B, KVH, H // KVH, hd), BF16), ((P, KVH, page, hd), BF16),
        ((P, KVH, page, hd), BF16), ((B,), I32), ((B, n_pg), I32),
    )


@pytest.mark.parametrize("arch", ATTN_ARCHS)
def test_agreement_compiles(one_chip, arch):
    from repro.kernels.agreement import kernel

    V = get_config(arch).vocab_size  # 50304 / 92544: not multiples of 2048
    _compiles_kernel(
        lambda x: kernel.member_stats_pallas(x), one_chip, ((3, 16, V), BF16)
    )


def test_compaction_compiles(one_chip):
    from repro.kernels.compaction import kernel

    D = get_config("olmo-1b").d_model
    _compiles_kernel(
        lambda x, m: kernel.compact_pallas(x, m), one_chip,
        ((16, D), F32), ((16,), I32),
    )


def test_mamba2_ssd_compiles(one_chip):
    from repro.kernels.mamba2_ssd import kernel

    cfg = get_config("zamba2-2.7b")
    H, P, N, G = cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_ngroups
    assert (H, P, N) == (80, 64, 64)
    B, S = 1, 256
    _compiles_kernel(
        lambda x, dt, A, b, c: kernel.ssd_pallas(x, dt, A, b, c), one_chip,
        ((B, S, H, P), BF16), ((B, S, H), F32), ((H,), F32),
        ((B, S, G, N), BF16), ((B, S, G, N), BF16),
    )


def test_rwkv6_wkv_compiles(one_chip):
    from repro.kernels.rwkv6_wkv import kernel

    cfg = get_config("rwkv6-7b")
    D = cfg.ssm_head_dim
    H = cfg.d_model // D
    assert (H, D) == (64, 64)
    B, S = 1, 128
    _compiles_kernel(
        lambda r, k, v, w, u: kernel.wkv6_pallas(r, k, v, w, u), one_chip,
        ((B, S, H, D), BF16), ((B, S, H, D), BF16), ((B, S, H, D), BF16),
        ((B, S, H, D), F32), ((H, D), F32),
    )


@pytest.mark.parametrize("arch", ["olmo-1b", "rwkv6-7b", "zamba2-2.7b"])
def test_loss_grad_compiles_with_kernels(one_chip, arch, monkeypatch):
    """``jax.grad`` of the training loss compiles for the chip with the
    kernels selected: their forward is the kernel, their backward the jnp
    path (a pallas_call has no transpose rule)."""
    from repro.configs import ShapeConfig
    from repro.kernels import config as kcfg
    from repro.models import api
    from repro.models.params import unbox

    # 'pallas' is refused on this CPU backend; nothing runs here
    monkeypatch.setattr(kcfg._STATE, "impl", "pallas")
    cfg = get_config(arch).reduced()
    values = jax.eval_shape(
        lambda: unbox(api.init_params(cfg, jax.random.PRNGKey(0)))[0]
    )
    batch = api.make_inputs(cfg, ShapeConfig("grad", 256, 2, "train"))
    spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    grad = jax.grad(lambda p, b: api.loss_fn(p, b, cfg)[0])
    text = jax.jit(grad).lower(
        jax.tree.map(spec, values), jax.tree.map(spec, batch)
    ).compile().as_text()
    assert "tpu_custom_call" in text


def test_placed_tier_programs_compile_per_device(topology, monkeypatch):
    """A placed tier on a pod slice of two v5e chips compiles its classify
    and paged decode programs with the kernels: a jit over several devices
    cannot partition a kernel, so they must run per device (shard_map),
    with the examples split over the slice's 'data' axis."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import ensemble as ens
    from repro.kernels import config as kcfg
    from repro.launch.mesh import pod_submeshes
    from repro.models import api
    from repro.models.params import unbox
    from repro.serve.cascade_server import tier_paged_programs, tier_programs

    monkeypatch.setattr(kcfg._STATE, "impl", "pallas")
    mesh = Mesh(
        np.array(topology.devices).reshape(2, 2, 1), ("pod", "data", "model")
    )
    slice1 = pod_submeshes(mesh, 2)[1]  # tier 1's chips
    cfg = get_config("olmo-1b").reduced()
    E, B, S, n_slots, max_seq, page = 1, 8, 128, 4, 64, 16

    def on(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(slice1, P(*spec))
        )

    values = jax.tree.map(
        lambda a: on(a.shape, a.dtype),
        jax.eval_shape(
            lambda: unbox(ens.init_ensemble(cfg, E, jax.random.PRNGKey(0)))[0]
        ),
    )
    progs = tier_programs(cfg, 0.0, slice1)
    text = progs.last_logits.lower(
        values, {"tokens": on((B, S), I32, "data")}
    ).compile().as_text()
    assert "tpu_custom_call" in text

    n_pages = n_slots * (max_seq // page) + 1
    pool = jax.eval_shape(
        lambda: unbox(api.init_paged_pool(cfg, n_pages, page))[0]
    )
    pools = jax.tree.map(lambda a: on((E,) + a.shape, a.dtype), pool)
    text = tier_paged_programs(cfg, 0.0, slice1).decode_slots.lower(
        values, on((E, n_slots, 1), I32), pools, on((n_slots,), I32),
        on((n_slots, max_seq // page), I32), on((n_slots, 2), jnp.uint32),
    ).compile().as_text()
    assert "tpu_custom_call" in text


def _entry_layer_copies(text):
    """Copies in the entry computation of compiled HLO ``text`` (``copy``,
    ``copy-start``, ``copy_bitcast_fusion``) that read a ``layers`` weight
    argument: a relayout of a whole weight stack."""
    entry = text[text.index("\nENTRY"):]
    entry = entry[: entry.index("\n}")]
    layer_args = set(re.findall(
        r"^\s*(%\S+) = \S+ parameter\(\d+\).*op_name=\"[^\"]*layers", entry, re.M
    ))
    assert layer_args, "no layer-weight arguments found"
    copies = re.findall(
        r"^\s*(%copy[\w.-]*) = .*? (?:copy|copy-start|fusion)\(([^)]*)\)",
        entry, re.M,
    )
    return [
        name for name, operands in copies
        if layer_args & {o.strip() for o in operands.split(",")}
    ]


@pytest.mark.parametrize("program", ["decode_slots", "prefill_chunk"])
@pytest.mark.parametrize("arch, k, temp_limit", [
    pytest.param("olmo-1b", 2, 1.5e9, id="olmo-1b-k2"),
    pytest.param("internlm2-1.8b", 1, 0.5e9, id="internlm2-1.8b-k1"),
])
def test_paged_tier_program_reads_layer_weights_in_place(
    one_chip, arch, k, temp_limit, program, monkeypatch
):
    """A tier's paged decode and prefill programs at published widths (4
    slots, max_seq 896, page 128) read each layer from the member-major
    weight stack: no entry copy of a layer weight, and scratch far below
    the stacks (a layer-major copy of olmo-1b's k=2 stack is 4.3 GB)."""
    from repro.core import ensemble as ens
    from repro.kernels import config as kcfg
    from repro.models import api
    from repro.models.params import unbox
    from repro.serve.cascade_server import tier_paged_programs

    monkeypatch.setattr(kcfg._STATE, "impl", "pallas")
    cfg = get_config(arch)
    n_slots, max_seq, page, chunk = 4, 896, 128, 256
    n_pg = max_seq // page

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    values = jax.tree.map(
        lambda a: on(a.shape, a.dtype),
        jax.eval_shape(
            lambda: unbox(ens.init_ensemble(cfg, k, jax.random.PRNGKey(0)))[0]
        ),
    )
    pool = jax.eval_shape(
        lambda: unbox(api.init_paged_pool(cfg, n_slots * n_pg + 1, page))[0]
    )
    pools = jax.tree.map(lambda a: on((k,) + a.shape, a.dtype), pool)
    progs = tier_paged_programs(cfg, 0.0)
    if program == "decode_slots":
        lowered = progs.decode_slots.lower(
            values, on((k, n_slots, 1), I32), pools, on((n_slots,), I32),
            on((n_slots, n_pg), I32), on((n_slots, 2), jnp.uint32),
        )
    else:
        lowered = progs.prefill_chunk.lower(
            values, pools, on((chunk,), I32), on((n_pg,), I32), on((), I32)
        )
    compiled = lowered.compile()
    assert _entry_layer_copies(compiled.as_text()) == []
    assert compiled.memory_analysis().temp_size_in_bytes < temp_limit
