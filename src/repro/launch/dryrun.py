import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
mesh, print memory/cost analysis, and emit roofline terms.

MUST be executed as its own process (`python -m repro.launch.dryrun ...`)
because the device-count flag above has to land before jax initializes.

Usage:
  python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k
  python -m repro.launch.dryrun --all --mesh pod --out results.json
"""
import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.analysis import roofline as RL                     # noqa: E402
from repro.configs import INPUT_SHAPES, get_config, list_configs  # noqa: E402
from repro.launch.mesh import make_production_mesh            # noqa: E402
from repro.launch.specs import build_case                     # noqa: E402


def run_one(arch, shape_name, *, multi_pod=False, fsdp=True, moe_impl="einsum",
            attn_impl="flash", seq_parallel=False, verbose=True,
            capacity_factor=1.25, serve_profile="fsdp"):
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    case = build_case(cfg, shape_name, mesh, fsdp=fsdp,
                      moe_impl=moe_impl, attn_impl=attn_impl,
                      seq_parallel=seq_parallel, capacity_factor=capacity_factor,
                      serve_profile=serve_profile)
    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted = jax.jit(case.step_fn,
                         in_shardings=case.in_shardings)
        lowered = jitted.lower(*case.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    shape = INPUT_SHAPES[shape_name]
    rl = RL.analyze(case.name, compiled,
                    model_flops=RL.model_flops_per_step(cfg, shape), chips=chips)

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": case.kind,
        "notes": case.notes,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "per_device": {
            "flops": rl.flops,
            "hbm_bytes": rl.hbm_bytes,
            "collective_bytes": rl.coll_bytes,
            "collectives": {k: v for k, v in rl.coll_breakdown.items() if v},
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        },
        "roofline": {
            "t_compute_ms": rl.t_compute * 1e3,
            "t_memory_ms": rl.t_memory * 1e3,
            "t_collective_ms": rl.t_collective * 1e3,
            "bottleneck": rl.bottleneck,
            "model_flops": rl.model_flops,
            "useful_flops_ratio": rl.useful_flops_ratio,
        },
    }
    if verbose:
        print(f"== {case.name} on {rec['mesh']} ({case.kind}; {case.notes})")
        print(f"   lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(f"   memory_analysis: args={rec['per_device']['argument_bytes']} "
              f"out={rec['per_device']['output_bytes']} "
              f"temp={rec['per_device']['temp_bytes']}")
        print(f"   cost_analysis: flops/dev={rl.flops:.3e} hbm/dev={rl.hbm_bytes:.3e}")
        print(f"   collectives/dev: {rec['per_device']['collectives']}")
        print(f"   roofline ms: compute={rl.t_compute*1e3:.2f} "
              f"memory={rl.t_memory*1e3:.2f} collective={rl.t_collective*1e3:.2f} "
              f"-> {rl.bottleneck}  useful_flops_ratio={rl.useful_flops_ratio:.3f}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"], default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--moe-impl", default="einsum", choices=["einsum", "sort"])
    ap.add_argument("--attn-impl", default="flash", choices=["flash", "ref"])
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--seq-parallel", action="store_true")
    args = ap.parse_args()

    archs = list_configs() if args.all or not args.arch else [args.arch]
    archs = [a for a in archs if a != "feds3a-cnn"]
    shapes = list(INPUT_SHAPES) if args.all or not args.shape else [args.shape]
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]

    results, failures = [], []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    results.append(run_one(
                        arch, shape, multi_pod=mp, fsdp=not args.no_fsdp,
                        moe_impl=args.moe_impl, attn_impl=args.attn_impl,
                        seq_parallel=args.seq_parallel))
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    failures.append((arch, shape, mp, repr(e)))

    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    print(f"\n{len(results)} ok, {len(failures)} failed")
    for f_ in failures:
        print("FAIL:", f_)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
