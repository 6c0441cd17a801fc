import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512")
)

"""Multi-pod dry-run: lower + compile every (arch × shape) on the production
mesh, record memory/cost/collective analysis for §Dry-run and §Roofline.

The two lines above MUST precede any other import (jax locks the device
count on first init). Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --arch codeqwen1.5-7b \
        --shape train_4k [--multi-pod] [--out reports/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Exit code is non-zero if any requested combination fails — failures here are
sharding bugs by definition (see MULTI-POD DRY-RUN in the brief).
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_arch, get_shape, list_archs
from repro.launch.mesh import make_production_mesh
from repro.models.transformer.model import (
    Topology,
    make_prefill_step,
    make_serve_step,
    make_train_step,
)
from repro.roofline.analysis import model_flops, peaks_for, roofline_report
from repro.roofline.hlo_walk import analyze_hlo


def topology_for(cfg, shape, *, multi_pod: bool, moe_mode: str = "gathered",
                 num_micro: int | None = None, remat: bool = True,
                 zero3: bool = True) -> Topology:
    data = 16
    pods = 2 if multi_pod else 1
    b_local = max(shape.global_batch // (data * pods), 1)
    if num_micro is None:
        # more microbatches = smaller bubble AND smaller per-tick residuals;
        # capped by the local batch (see EXPERIMENTS.md §Perf)
        target = {"train": 16, "prefill": 4, "decode": 4}[shape.kind]
        num_micro = max(min(target, b_local), 1)
    seq_shard = shape.kind == "decode" and shape.global_batch == 1 and cfg.arch_type != "ssm"
    return Topology(
        num_stages=16,
        stage_axis="model",
        fsdp_axis="data",
        pod_axis="pod" if multi_pod else None,
        fsdp_size=data,
        num_micro=num_micro,
        moe_mode=moe_mode,
        zero3=zero3,
        remat=remat,
        seq_shard_decode=seq_shard,
        loss_chunks=8,
    )


def build_step(cfg, shape, topo, mesh):
    if shape.kind == "train":
        return make_train_step(cfg, topo, shape, mesh)
    if shape.kind == "prefill":
        return make_prefill_step(cfg, topo, shape, mesh)
    return make_serve_step(cfg, topo, shape, mesh)


def run_one(arch: str, shape_name: str, *, multi_pod: bool, out_dir: str | None,
            moe_mode: str = "gathered", zero3: bool = True,
            num_micro: int | None = None, remat: bool = True,
            verbose: bool = True, tag: str = "") -> dict:
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    topo = topology_for(cfg, shape, multi_pod=multi_pod, moe_mode=moe_mode,
                        zero3=zero3, num_micro=num_micro, remat=remat)
    art = build_step(cfg, shape, topo, mesh)

    t0 = time.time()
    jitted = jax.jit(art.fn, in_shardings=art.in_shardings, out_shardings=art.out_shardings)
    lowered = jitted.lower(*art.abstract_inputs)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    # loop-aware costs: XLA's cost_analysis counts while bodies once; the
    # walker multiplies through known_trip_counts (see roofline/hlo_walk.py)
    walk = analyze_hlo(compiled.as_text())
    mf = model_flops(cfg, shape, training=shape.kind == "train")
    report = roofline_report(
        device_flops=walk["flops"],
        device_bytes=walk["bytes"],
        device_collective=walk["collectives"],
        chips=chips,
        model_flops_global=mf,
        hw=peaks_for("TPU v5 lite"),  # the placeholder devices model v5e
    )
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "kind": shape.kind,
        "num_micro": topo.num_micro,
        "seq_shard_decode": topo.seq_shard_decode,
        "moe_mode": moe_mode,
        "zero3": zero3,
        "tag": tag,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes_per_device": mem.argument_size_in_bytes,
            "output_bytes_per_device": mem.output_size_in_bytes,
            "temp_bytes_per_device": mem.temp_size_in_bytes,
            "alias_bytes_per_device": mem.alias_size_in_bytes,
            "peak_estimate_gib": round(
                (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 - mem.alias_size_in_bytes + mem.temp_size_in_bytes) / 2**30, 3
            ),
        },
        "cost": {k: cost[k] for k in ("flops", "bytes accessed") if k in cost},
        "walk": {"flops": walk["flops"], "bytes": walk["bytes"]},
        "collective_bytes": walk["collectives"],
        "roofline": report,
        "ok": True,
    }
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × {result['mesh']}: "
              f"lower {t_lower:.0f}s compile {t_compile:.0f}s "
              f"peak/dev {result['memory']['peak_estimate_gib']} GiB "
              f"dominant {report['dominant']} ({report['bound_s']:.4f}s)")
        print("  memory_analysis:", mem)
        cost_str = {k: f"{v:.3e}" for k, v in result["cost"].items()}
        print("  cost_analysis:", cost_str, " walk:", {k: f"{v:.3e}" for k, v in result["walk"].items()})
        print("  collectives:", {k: v for k, v in walk["collectives"].items() if v})
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        fn = os.path.join(out_dir, f"{arch}__{shape_name}__{result['mesh']}{suffix}.json")
        with open(fn, "w") as f:
            json.dump(result, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--moe-mode", default="gathered", choices=["gathered", "a2a"])
    ap.add_argument("--no-zero3", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--num-micro", type=int, default=None)
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="reports/dryrun")
    args = ap.parse_args()

    combos: list[tuple[str, str]]
    if args.all:
        combos = [(a, s) for a in list_archs() for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        combos = [(args.arch, args.shape)]

    failures = []
    for arch, shape in combos:
        try:
            run_one(arch, shape, multi_pod=args.multi_pod, out_dir=args.out,
                    moe_mode=args.moe_mode, zero3=not args.no_zero3,
                    num_micro=args.num_micro, remat=not args.no_remat,
                    tag=args.tag)
        except Exception as e:  # noqa: BLE001 — report and continue
            failures.append((arch, shape, repr(e)))
            print(f"[dryrun] FAIL {arch} × {shape}: {e}")
            traceback.print_exc()
    if failures:
        print(f"[dryrun] {len(failures)} failures: {[(a, s) for a, s, _ in failures]}")
        raise SystemExit(1)
    print(f"[dryrun] all {len(combos)} combinations lowered + compiled OK")


if __name__ == "__main__":
    main()
