import os

# a CPU-only tool: pin the platform and append the fake host devices before
# jax is imported, so neither this process nor its children take a chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
).strip()

"""Multi-pod dry-run: lower + compile every (arch × input shape) on the
production meshes, prove memory fits, and extract roofline terms.

MUST be run as its own process (the environment lines above have to
execute before any jax initialization — hence the import-order heresy).
The kernels' jaxpr cost is traced under impl='pallas_interpret' (the
kernel calls are the same ones; 'pallas' is refused off a TPU).

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all --multi-pod both

Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json and feed
EXPERIMENTS.md §Dry-run / §Roofline.
"""
import argparse  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec  # noqa: E402

from repro.configs import INPUT_SHAPES, get_config, list_configs, shape_supported  # noqa: E402
from repro.kernels import config as kcfg  # noqa: E402
from repro.launch.jaxpr_cost import estimate_fn_cost  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.roofline import parse_collectives, roofline_terms  # noqa: E402
from repro.models import api  # noqa: E402
from repro.models.counting import count_params, model_flops_per_token  # noqa: E402
from repro.models.params import unbox  # noqa: E402
from repro.optim.adamw import OptimConfig, adamw_init  # noqa: E402
from repro.sharding.logical import axis_rules, logical_to_pspec, rules_for  # noqa: E402
from repro.train.step import TrainState, init_train_state, make_train_step  # noqa: E402

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")

LONG_WINDOW = 4096  # sliding window forced for long_500k on attention archs


def _sds_tree(shapes_tree, axes_tree, rules, mesh):
    """ShapeDtypeStructs with NamedShardings derived from logical axes."""
    leaves_s, treedef = jax.tree.flatten(shapes_tree)
    leaves_a = treedef.flatten_up_to(axes_tree)
    out = []
    for s, a in zip(leaves_s, leaves_a):
        pspec = logical_to_pspec(a, rules, shape=s.shape, mesh=mesh)
        out.append(jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=NamedSharding(mesh, pspec)))
    return treedef.unflatten(out)


_BATCH_AXES = {
    "tokens": ("act_batch", None),
    "targets": ("act_batch", None),
    "mask": ("act_batch", None),
    "embeds": ("act_batch", None, None),
    "token": ("act_batch", None),
    "pos": (),
}


def _batch_sds(specs, rules, mesh):
    out = {}
    for name, s in specs.items():
        pspec = logical_to_pspec(
            _BATCH_AXES[name], rules, shape=s.shape, mesh=mesh
        )
        out[name] = jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, pspec)
        )
    return out


def _moment_dtype(cfg) -> str:
    # >=80B params: bf16 AdamW moments (DESIGN.md §7) to fit 16 GB/chip
    return "bfloat16" if count_params(cfg) > 80e9 else "float32"


def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: str) -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "kind": shape.kind,
        "params": count_params(cfg),
        "active_params": count_params(cfg, active_only=True),
    }

    ok, reason = shape_supported(cfg, shape)
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return rec

    window = None
    if shape_name == "long_500k" and not cfg.attention_free:
        window = cfg.sliding_window or LONG_WINDOW
        rec["window_override"] = window

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(mesh.devices.shape))
    rules = rules_for(shape.kind, pod=multi_pod, batch=shape.global_batch)

    # abstract params (+ axes) — nothing is materialized
    boxed = jax.eval_shape(functools.partial(api.init_params, cfg), jax.random.PRNGKey(0))
    p_shapes, p_axes = unbox(boxed)
    params_sds = _sds_tree(p_shapes, p_axes, rules, mesh)
    specs = api.input_specs(cfg, shape)

    t0 = time.time()
    jcost = None
    with mesh, axis_rules(rules, mesh):
        if shape.kind == "train":
            ocfg = OptimConfig(moment_dtype=_moment_dtype(cfg))
            step = make_train_step(cfg, ocfg, window_override=window)
            opt_shapes = jax.eval_shape(functools.partial(adamw_init, cfg=ocfg), p_shapes)
            opt_sds = {
                "m": _sds_tree(opt_shapes["m"], p_axes, rules, mesh),
                "v": _sds_tree(opt_shapes["v"], p_axes, rules, mesh),
                "count": jax.ShapeDtypeStruct(
                    (), jnp.int32, sharding=NamedSharding(mesh, PartitionSpec())
                ),
            }
            state_sds = TrainState(
                params=params_sds,
                opt=opt_sds,
                step=jax.ShapeDtypeStruct(
                    (), jnp.int32, sharding=NamedSharding(mesh, PartitionSpec())
                ),
            )
            batch_sds = _batch_sds(specs, rules, mesh)
            # kernelized (TPU-target) cost: pallas forward trace scaled by
            # the XLA-path train/forward ratio (AD through pallas_call is
            # not defined; the ratio captures backward + remat + optimizer)
            fwd = lambda p, b: api.loss_fn(p, b, cfg, window_override=window)[0]
            jc_train_xla = estimate_fn_cost(step, state_sds, batch_sds)
            jc_fwd_xla = estimate_fn_cost(fwd, params_sds, batch_sds)
            with kcfg.use_impl("pallas_interpret"):
                jc_fwd_pal = estimate_fn_cost(fwd, params_sds, batch_sds)
            jcost = {
                "flops": jc_fwd_pal["flops"]
                * (jc_train_xla["flops"] / max(1, jc_fwd_xla["flops"])),
                "bytes": jc_fwd_pal["bytes"]
                * (jc_train_xla["bytes"] / max(1, jc_fwd_xla["bytes"])),
                "xla_train": jc_train_xla,
            }
            # §Perf iteration 4: pin the output state to the input shardings
            # (grads/optimizer update reduce-scatter instead of all-reduce)
            # and donate the state buffers
            state_shardings = jax.tree.map(lambda s: s.sharding, state_sds)
            # abclint: disable=ABC101(AOT lower-compile path — traces exactly once by construction)
            lowered = jax.jit(
                step, out_shardings=(state_shardings, None), donate_argnums=(0,)
            ).lower(state_sds, batch_sds)
        elif shape.kind == "prefill":
            fn = functools.partial(api.prefill, cfg=cfg, window_override=window)
            batch_sds = _batch_sds(specs, rules, mesh)
            with kcfg.use_impl("pallas_interpret"):
                jcost = estimate_fn_cost(fn, params_sds, batch_sds)
            # abclint: disable=ABC101(AOT lower-compile path — traces exactly once by construction)
            lowered = jax.jit(fn).lower(params_sds, batch_sds)
        else:  # decode
            fn = functools.partial(api.decode_step, cfg=cfg, window_override=window)
            cache_boxed = jax.eval_shape(
                lambda: api.init_cache(cfg, shape.global_batch, shape.seq_len)
            )
            c_shapes, c_axes = unbox(cache_boxed)
            cache_sds = _sds_tree(c_shapes, c_axes, rules, mesh)
            batch_sds = _batch_sds(specs, rules, mesh)
            with kcfg.use_impl("pallas_interpret"):
                jcost = estimate_fn_cost(
                    fn, params_sds, batch_sds["token"], cache_sds, batch_sds["pos"]
                )
            # abclint: disable=ABC101(AOT lower-compile path — traces exactly once by construction)
            lowered = jax.jit(fn).lower(
                params_sds, batch_sds["token"], cache_sds, batch_sds["pos"]
            )
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    xla_cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    # roofline terms from the scan-aware jaxpr cost (global -> per-chip);
    # XLA's cost_analysis counts scan bodies once, kept as a cross-check
    per_chip = {
        "flops": jcost["flops"] / n_chips,
        "bytes accessed": jcost["bytes"] / n_chips,
    }
    terms = roofline_terms(per_chip, sum(coll.values()), n_chips)

    # MODEL_FLOPS: 6·N·D for train, 2·N·D for inference, N = active non-embed
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    per_tok = model_flops_per_token(cfg) / 6.0
    model_flops = (6.0 if shape.kind == "train" else 2.0) * per_tok * tokens
    rec.update(
        status="ok",
        n_chips=n_chips,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        collectives=coll,
        roofline=terms,
        xla_cost={
            "flops_per_dev": float(xla_cost.get("flops", 0.0)),
            "bytes_per_dev": float(xla_cost.get("bytes accessed", 0.0)),
        },
        model_flops=model_flops,
        useful_ratio=(model_flops / jcost["flops"]) if jcost["flops"] else None,
        memory={
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "argument_size_in_bytes", 0),
        },
    )
    return rec


def run_cascade(multi_pod: bool, out_dir: str) -> dict:
    """The paper's technique on the production mesh: a 2-member tier-1
    ensemble stacked on the 'ensemble' logical axis (mapped to the 'pod'
    mesh axis on the 2×16×16 mesh — one member per pod), agreement reduce
    across pods, and the dense masked tier-2 pass.  Proves ABC's ensemble-
    parallel execution lowers + shards end to end."""
    import dataclasses

    from repro.core import deferral
    from repro.core import ensemble as ens_mod

    cfg1 = get_config("qwen2.5-3b")
    cfg2 = dataclasses.replace(
        cfg1, name="qwen2.5-14b-like", n_layers=48, d_model=5120,
        n_heads=40, n_kv_heads=8, d_ff=13824, head_dim=128,
    )
    B, S, E = 32, 8192, 2
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(mesh.devices.shape))
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rules = dict(rules_for("prefill", pod=multi_pod, batch=B))
    # the pod axis carries the ensemble, not the batch
    rules["act_batch"] = ("data",)
    rules["kv_batch"] = ("data",)
    rules["ensemble"] = "pod" if multi_pod else None

    b1 = jax.eval_shape(
        functools.partial(ens_mod.init_ensemble, cfg1, E), jax.random.PRNGKey(0)
    )
    s1, a1 = unbox(b1)
    b2 = jax.eval_shape(functools.partial(api.init_params, cfg2), jax.random.PRNGKey(1))
    s2, a2 = unbox(b2)
    v1_sds = _sds_tree(s1, a1, rules, mesh)
    v2_sds = _sds_tree(s2, a2, rules, mesh)
    batch_sds = _batch_sds(api.input_specs(cfg1, INPUT_SHAPES["prefill_32k"]), rules, mesh)
    batch_sds["tokens"] = jax.ShapeDtypeStruct(
        (B, S), jnp.int32, sharding=batch_sds["tokens"].sharding
    )

    def cascade_step(v1, v2, batch):
        logits1 = jax.vmap(lambda p: api.prefill(p, batch, cfg1)[0])(v1)  # (E,B,V)
        out = deferral.vote_rule(logits1, 0.67)
        logits2, _ = api.prefill(v2, batch, cfg2)
        pred = jnp.where(
            out.defer, jnp.argmax(logits2, -1).astype(jnp.int32), out.pred
        )
        return pred, out.defer, out.score

    rec = {"arch": "abc-cascade-2tier", "shape": f"prefill_{S}", "mesh": mesh_name,
           "kind": "cascade"}
    t0 = time.time()
    with mesh, axis_rules(rules, mesh):
        jcost = estimate_fn_cost(cascade_step, v1_sds, v2_sds, batch_sds)
        # abclint: disable=ABC101(AOT lower-compile path — traces exactly once by construction)
        lowered = jax.jit(cascade_step).lower(v1_sds, v2_sds, batch_sds)
        compiled = lowered.compile()
    coll = parse_collectives(compiled.as_text())
    per_chip = {"flops": jcost["flops"] / n_chips, "bytes accessed": jcost["bytes"] / n_chips}
    rec.update(
        status="ok",
        n_chips=n_chips,
        compile_s=round(time.time() - t0, 2),
        collectives=coll,
        roofline=roofline_terms(per_chip, sum(coll.values()), n_chips),
    )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", default="false", choices=["false", "true", "both"])
    ap.add_argument("--out", default=os.path.abspath(OUT_DIR))
    ap.add_argument("--cascade", action="store_true",
                    help="dry-run the ABC cascade step itself (ensemble on the pod axis)")
    ap.add_argument("--subprocess", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.cascade:
        os.makedirs(args.out, exist_ok=True)
        mp = args.multi_pod == "true"
        rec = run_cascade(mp, args.out)
        mesh_name = "pod2x16x16" if mp else "pod16x16"
        with open(os.path.join(args.out, f"abc-cascade__{mesh_name}.json"), "w") as f:
            json.dump(rec, f, indent=2)
        t = rec["roofline"]
        print(f"[ok] abc-cascade × {mesh_name}: compile={rec['compile_s']}s "
              f"coll={t['collective_bytes']:.3e} bottleneck={t['bottleneck']} "
              f"collectives={rec['collectives']}")
        return

    archs = list_configs() if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    pods = {"false": [False], "true": [True], "both": [False, True]}[args.multi_pod]
    os.makedirs(args.out, exist_ok=True)

    combos = [(a, s, mp) for a in archs for s in shapes for mp in pods]
    if len(combos) > 1:
        # one subprocess per combo: isolates XLA state and survives failures
        for a, s, mp in combos:
            mesh_name = "pod2x16x16" if mp else "pod16x16"
            out_file = os.path.join(args.out, f"{a}__{s}__{mesh_name}.json")
            if os.path.exists(out_file):
                print(f"[skip existing] {out_file}")
                continue
            cmd = [
                sys.executable, "-m", "repro.launch.dryrun",
                "--arch", a, "--shape", s,
                "--multi-pod", "true" if mp else "false",
                "--out", args.out,
            ]
            print(f"[dryrun] {a} × {s} × {mesh_name}")
            r = subprocess.run(cmd, env=dict(os.environ))
            if r.returncode != 0:
                print(f"  FAILED rc={r.returncode}")
        return

    arch, shape_name, mp = combos[0]
    mesh_name = "pod2x16x16" if mp else "pod16x16"
    out_file = os.path.join(args.out, f"{arch}__{shape_name}__{mesh_name}.json")
    try:
        rec = run_one(arch, shape_name, mp, args.out)
    except Exception as e:  # record the failure — these are bugs to fix
        rec = {
            "arch": arch, "shape": shape_name, "mesh": mesh_name,
            "status": "error", "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
    with open(out_file, "w") as f:
        json.dump(rec, f, indent=2)
    status = rec["status"]
    if status == "ok":
        t = rec["roofline"]
        print(
            f"[ok] {arch} × {shape_name} × {mesh_name}: "
            f"compile={rec['compile_s']}s flops={t['flops']:.3e} "
            f"bytes={t['bytes']:.3e} coll={t['collective_bytes']:.3e} "
            f"bottleneck={t['bottleneck']}"
        )
    else:
        print(f"[{status}] {arch} × {shape_name} × {mesh_name}: {rec.get('reason', rec.get('error'))}")
        if status == "error":
            sys.exit(1)


if __name__ == "__main__":
    main()
