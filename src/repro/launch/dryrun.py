"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Run as ``PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
[--mesh single|multi|both] [--out DIR]``.

The placeholder-device override MUST precede every other import (jax locks
the device count at first init).
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ..configs import SHAPES, get_config, list_configs  # noqa: E402
from ..models.model import abstract_params, input_specs  # noqa: E402
from ..models import transformer  # noqa: E402
from ..optim import adamw_init  # noqa: E402
from ..train import TrainConfig, make_serve_step, make_train_step  # noqa: E402
from ..train import make_prefill_step  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402
from .sharding import (  # noqa: E402
    batch_shardings,
    cache_shardings,
    param_shardings,
    state_shardings,
)

#: cells skipped per DESIGN.md §Arch-applicability: long_500k requires a
#: sub-quadratic architecture (SSM / hybrid).
def cell_skipped(cfg, shape) -> str | None:
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return "long_500k skipped: pure full-attention arch (DESIGN.md)"
    return None




def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               sequence_parallel: bool = False, microbatches: int = 1,
               remat: str = "full", strategy: str = "fsdp_tp"):
    """Lower + compile one cell; returns the result record."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    skip = cell_skipped(cfg, shape)
    if skip:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "SKIP", "reason": skip}

    mesh = make_production_mesh(multi_pod=multi_pod)
    from ..models.sharding_policy import set_policy_from_mesh

    set_policy_from_mesh(mesh, sequence_parallel=sequence_parallel,
                         strategy=strategy)
    transformer.set_remat_policy(remat)

    def p_shardings(tree):
        return param_shardings(tree, mesh, strategy=strategy)

    t0 = time.time()
    with jax.set_mesh(mesh):
        params_abs = abstract_params(cfg)
        if shape.kind == "train":
            state_abs = {
                "params": params_abs,
                "opt": jax.eval_shape(adamw_init, params_abs),
            }
            in_batch = input_specs(cfg, shape)
            from jax.sharding import NamedSharding, PartitionSpec as P

            state_sh = {
                "params": p_shardings(state_abs["params"]),
                "opt": {
                    "mu": p_shardings(state_abs["opt"]["mu"]),
                    "nu": p_shardings(state_abs["opt"]["nu"]),
                    "step": NamedSharding(mesh, P()),
                },
            }
            batch_sh = batch_shardings(in_batch, mesh)
            step = make_train_step(cfg, TrainConfig(microbatches=microbatches))
            jitted = jax.jit(step, in_shardings=(state_sh, batch_sh))
            lowered = jitted.lower(state_abs, in_batch)
        elif shape.kind == "prefill":
            in_batch = input_specs(cfg, shape)
            p_sh = p_shardings(params_abs)
            b_sh = batch_shardings(in_batch, mesh)
            step = make_prefill_step(cfg)
            jitted = jax.jit(step, in_shardings=(p_sh, b_sh))
            lowered = jitted.lower(params_abs, in_batch)
        else:  # decode
            specs = input_specs(cfg, shape)
            p_sh = p_shardings(params_abs)
            cache_sh = cache_shardings(specs["cache"], mesh, shape.global_batch)
            tok_sh = batch_shardings(
                {"token": specs["token"]}, mesh
            )["token"]
            from jax.sharding import NamedSharding, PartitionSpec as P

            len_sh = NamedSharding(mesh, P())
            step = make_serve_step(cfg)
            if cfg.family == "encdec":
                mem_sh = batch_shardings({"m": specs["memory"]}, mesh)["m"]
                jitted = jax.jit(
                    step, in_shardings=(p_sh, tok_sh, cache_sh, len_sh, mem_sh)
                )
                lowered = jitted.lower(
                    params_abs, specs["token"], specs["cache"],
                    specs["cache_len"], specs["memory"],
                )
            else:
                jitted = jax.jit(
                    step, in_shardings=(p_sh, tok_sh, cache_sh, len_sh)
                )
                lowered = jitted.lower(
                    params_abs, specs["token"], specs["cache"],
                    specs["cache_len"],
                )

        compiled = lowered.compile()
        t_compile = time.time() - t0

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        # loop-trip-count-corrected per-device costs from the partitioned
        # HLO (cost_analysis counts while bodies once — see roofline/)
        from ..roofline.hlo_cost import analyze_hlo

        hlo_cost = analyze_hlo(compiled.as_text())

    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "status": "OK",
        "n_devices": int(mesh.devices.size),
        "compile_s": round(t_compile, 1),
        # raw XLA numbers (per-device, while-bodies counted once):
        "xla_flops_body_once": float(cost.get("flops", -1.0)) if cost else -1.0,
        "xla_bytes_body_once": float(cost.get("bytes accessed", -1.0)) if cost else -1.0,
        # loop-corrected per-device numbers (roofline inputs):
        "flops_per_device": hlo_cost.flops,
        "hbm_bytes_per_device": hlo_cost.bytes_written,
        "collective_bytes_per_device": dict(hlo_cost.collective_bytes),
        "collective_total_per_device": hlo_cost.total_collective_bytes,
        "loop_trip_counts": hlo_cost.trip_counts,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None
            ),
        },
    }
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="single arch (default: all)")
    ap.add_argument("--shape", default=None, help="single shape (default: all)")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--sp", action="store_true", help="sequence parallelism")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="full", choices=["full", "dots"])
    ap.add_argument("--strategy", default="fsdp_tp",
                    choices=["fsdp_tp", "pure_fsdp", "fsdp_ep"])
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list_configs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    results = []
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                tag = f"{arch}_{shape}_{'multi' if multi else 'single'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    with open(path) as f:
                        rec = json.load(f)
                    print(f"[cached] {tag}: {rec['status']}")
                    results.append(rec)
                    continue
                try:
                    rec = lower_cell(arch, shape, multi,
                                     sequence_parallel=args.sp,
                                     microbatches=args.microbatches,
                                     remat=args.remat,
                                     strategy=args.strategy)
                except Exception as e:  # noqa: BLE001 — record and continue
                    rec = {
                        "arch": arch, "shape": shape,
                        "mesh": "multi" if multi else "single",
                        "status": "FAIL",
                        "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc()[-2000:],
                    }
                with open(path, "w") as f:
                    json.dump(rec, f, indent=2)
                status = rec["status"]
                extra = (
                    f"compile={rec.get('compile_s')}s"
                    if status == "OK"
                    else rec.get("reason", rec.get("error", ""))[:100]
                )
                print(f"[{status}] {tag}: {extra}", flush=True)
                results.append(rec)

    n_ok = sum(r["status"] == "OK" for r in results)
    n_skip = sum(r["status"] == "SKIP" for r in results)
    n_fail = sum(r["status"] == "FAIL" for r in results)
    print(f"\ndry-run complete: {n_ok} OK, {n_skip} SKIP, {n_fail} FAIL")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
