import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) combo.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod both]
    PYTHONPATH=src python -m repro.launch.dryrun --gnn   # the paper's own pipeline

The XLA_FLAGS line above MUST precede any jax import: it materializes
512 host placeholder devices so ``jax.make_mesh`` can build the
production meshes (16x16 single pod / 2x16x16 two pods).

Each combo writes ``experiments/dryrun/<arch>__<shape>__<mesh>.json``
with memory analysis, cost analysis and roofline terms (§Roofline).
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ALL_ARCHS, get_config  # noqa: E402
from repro.launch import roofline as rl  # noqa: E402
from repro.launch.mesh import (  # noqa: E402
    PRODUCTION_DEVICE_KIND,
    make_production_mesh,
)
from repro.launch.shardings import (  # noqa: E402
    data_spec,
    decode_state_shardings,
    opt_shardings,
    param_shardings,
)
from repro.launch.specs import (  # noqa: E402
    SHAPES,
    batch_specs,
    decode_state_specs,
    opt_specs,
    params_specs,
    shape_applicable,
)
from repro.launch.steps import (  # noqa: E402
    make_prefill_step,
    make_serve_step,
    make_train_step,
)
from repro.models.transformer.config import active_param_count  # noqa: E402

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")


def _mesh_tag(multi_pod: bool) -> str:
    return "pod2x16x16" if multi_pod else "pod16x16"


def _parse_override(kv: str):
    k, v = kv.split("=", 1)
    if v.lower() in ("true", "false"):
        return k, v.lower() == "true"
    try:
        return k, int(v)
    except ValueError:
        try:
            return k, float(v)
        except ValueError:
            return k, v


def lower_combo(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    verbose: bool = True,
    overrides: dict | None = None,
    tag: str = "",
):
    n_batch_shards = 32 if multi_pod else 16
    overrides = dict(overrides or {})
    moe_fsdp = overrides.pop("moe_fsdp", False)  # sharding-rule switch
    cfg = dataclasses.replace(
        get_config(arch), dtype="bfloat16", moe_groups=n_batch_shards,
        **overrides,
    )
    spec = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": _mesh_tag(multi_pod),
                "status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    from repro.models.transformer.modules import set_logical_mesh

    set_logical_mesh(mesh)
    t0 = time.time()
    params_s = params_specs(cfg)
    p_sh = param_shardings(mesh, params_s, moe_fsdp=moe_fsdp)

    with mesh:
        if spec.kind == "train":
            step = make_train_step(cfg)
            opt_s = opt_specs(params_s)
            from repro.train.optim import AdamState

            opt_sh = AdamState(
                step=NamedSharding(mesh, P()),
                mu=opt_shardings(mesh, params_s),
                nu=opt_shardings(mesh, params_s),
            )
            b_specs = batch_specs(cfg, spec)
            b_sh = {
                k: NamedSharding(mesh, data_spec(mesh, v.shape))
                for k, v in b_specs.items()
            }
            jitted = jax.jit(
                step,
                in_shardings=(p_sh, opt_sh, b_sh),
                out_shardings=(p_sh, opt_sh, None),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(params_s, opt_s, b_specs)
        elif spec.kind == "prefill":
            step = make_prefill_step(cfg)
            b_specs = batch_specs(cfg, spec)
            b_sh = {
                k: NamedSharding(mesh, data_spec(mesh, v.shape))
                for k, v in b_specs.items()
            }
            jitted = jax.jit(step, in_shardings=(p_sh, b_sh))
            lowered = jitted.lower(params_s, b_specs)
        else:  # decode
            step = make_serve_step(cfg)
            state_s = decode_state_specs(cfg, spec)
            s_sh = decode_state_shardings(mesh, state_s)
            tok_s = batch_specs(cfg, spec)["token"]
            tok_sh = NamedSharding(mesh, data_spec(mesh, tok_s.shape))
            jitted = jax.jit(
                step,
                in_shardings=(p_sh, s_sh, tok_sh),
                out_shardings=(None, s_sh),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(params_s, state_s, tok_s)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    n_dev = mesh.size
    mf = rl.model_flops(cfg, spec, active_param_count(get_config(arch)))
    roof = rl.analyze(compiled, n_dev, mf, PRODUCTION_DEVICE_KIND)
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": _mesh_tag(multi_pod),
        "tag": tag,
        "overrides": {**overrides, **({"moe_fsdp": True} if moe_fsdp else {})},
        "status": "ok",
        "devices": n_dev,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            "peak_per_device_gb": roof.peak_mem_bytes / 2**30,
        },
        "roofline": roof.to_dict(),
    }
    if verbose:
        print(
            f"[{arch} | {shape_name} | {result['mesh']}] ok "
            f"lower {t_lower:.1f}s compile {t_compile:.1f}s "
            f"peak/dev {result['memory']['peak_per_device_gb']:.2f} GiB "
            f"bottleneck={roof.bottleneck} "
            f"(c={roof.compute_s*1e3:.2f}ms m={roof.memory_s*1e3:.2f}ms "
            f"coll={roof.collective_s*1e3:.2f}ms) useful={roof.useful_ratio:.2f}",
            flush=True,
        )
    return result


def run_gnn_dryrun(multi_pod: bool = False, verbose: bool = True,
                   overrides: dict | None = None, tag: str = ""):
    """Lower the paper's own cooperative GNN train step on the mesh.

    PEs = all mesh devices (the paper's cooperation domain); graph is
    block-partitioned so each PE's feature shard is a contiguous row
    block (production feature stores are owner-partitioned the same way).
    """
    from repro.launch.gnn_dryrun import lower_gnn_coop_step

    return lower_gnn_coop_step(
        multi_pod=multi_pod, verbose=verbose, tag=tag, **(overrides or {})
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--gnn", action="store_true")
    ap.add_argument(
        "--multi-pod", default="single", choices=["single", "multi", "both"]
    )
    ap.add_argument(
        "--set", action="append", default=[], dest="overrides",
        help="config override key=value (hillclimb experiments)",
    )
    ap.add_argument("--tag", default="", help="suffix for the result json")
    args = ap.parse_args()
    overrides = dict(_parse_override(kv) for kv in args.overrides)

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[
        args.multi_pod
    ]
    os.makedirs(OUT_DIR, exist_ok=True)
    results = []
    if args.gnn:
        for mp in meshes:
            results.append(
                run_gnn_dryrun(multi_pod=mp, overrides=overrides, tag=args.tag)
            )
    else:
        archs = ALL_ARCHS if (args.all or not args.arch) else [args.arch]
        shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
        for arch in archs:
            for shape in shapes:
                for mp in meshes:
                    try:
                        results.append(
                            lower_combo(arch, shape, mp, overrides=overrides,
                                        tag=args.tag)
                        )
                    except Exception as e:  # a failure here is a bug: record it
                        traceback.print_exc()
                        results.append(
                            {"arch": arch, "shape": shape,
                             "mesh": _mesh_tag(mp), "status": "error",
                             "error": repr(e)}
                        )
    for r in results:
        name = f"{r.get('arch','gnn')}__{r.get('shape','coop')}__{r['mesh']}"
        if args.tag:
            name += f"__{args.tag}"
        with open(os.path.join(OUT_DIR, name + ".json"), "w") as f:
            json.dump(r, f, indent=2)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\ndry-run complete: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
