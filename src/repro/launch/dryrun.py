import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST be the first two lines — before ANY other import (jax locks the
# device count at first init).  Everything below may import jax.

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro import configs  # noqa: E402
from repro.launch import steps  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models.config import SHAPES  # noqa: E402
from repro.roofline.hlo_parse import analyze_hlo  # noqa: E402

# long_500k requires sub-quadratic serving; pure full-attention archs are
# skipped per the brief (documented in DESIGN.md §7)
LONG_OK = {"mamba2-2.7b", "recurrentgemma-9b", "gemma3-4b", "gemma2-27b"}


def cell_is_skipped(arch: str, shape_name: str):
    if shape_name == "long_500k" and arch not in LONG_OK:
        return ("pure full-attention arch: 500k-token decode is out of its "
                "design envelope (no sliding-window/SSM path)")
    return None


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             overrides=None, moe_impl: str = None) -> dict:
    cfg = configs.get_config(arch)
    if moe_impl and cfg.moe is not None:
        import dataclasses
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, impl=moe_impl))
    cell = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    lowered, info = steps.lower_step(cfg, mesh, cell,
                                     opts=None if not overrides else
                                     steps.pick_options(cfg, mesh, cell,
                                                        **overrides))
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo_stats = analyze_hlo(compiled.as_text())
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": 512 if multi_pod else 256,
        # the chip the production mesh stands for (launch/mesh.py); the
        # compile itself runs on host devices
        "device_kind": "TPU v5 lite",
        "info": info,
        "trace_s": round(t1 - t0, 1),
        "compile_s": round(t2 - t1, 1),
        "memory_analysis": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "xla_cost_analysis": {
            "flops": cost.get("flops"),
            "bytes_accessed": cost.get("bytes accessed"),
        },
        "hlo_stats": hlo_stats,
    }
    print(f"[dryrun] {arch} × {shape_name} × {result['mesh']}: "
          f"compile {result['compile_s']}s, "
          f"per-device flops {hlo_stats['flops']:.3e}, "
          f"hbm {hlo_stats['hbm_bytes']:.3e} B, "
          f"collectives {hlo_stats['collectives']}")
    print(f"[dryrun] memory_analysis: {mem}")      # proves it fits
    print(f"[dryrun] cost_analysis: flops={cost.get('flops')} "
          f"bytes={cost.get('bytes accessed')}")   # FLOPs/bytes for §Roofline
    return result


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["pod", "multipod",
                                                       "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--moe-impl", default=None,
                    choices=[None, "global", "sharded", "a2a"])
    ap.add_argument("--kv-mode", default=None,
                    choices=[None, "exact", "clustered", "int8"])
    args = ap.parse_args()

    archs = list(configs.ARCH_IDS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = ({"pod": [False], "multipod": [True],
               "both": [False, True]})[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"[dryrun] cached: {tag}")
                    continue
                skip = cell_is_skipped(arch, shape)
                if skip:
                    res = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "skipped": skip}
                    print(f"[dryrun] SKIP {tag}: {skip}")
                else:
                    try:
                        ov = ({"kv_mode": args.kv_mode}
                              if args.kv_mode else None)
                        res = run_cell(arch, shape, mp,
                                       overrides=ov,
                                       moe_impl=args.moe_impl)
                    except Exception as e:  # noqa: BLE001
                        traceback.print_exc()
                        failures.append(tag)
                        res = {"arch": arch, "shape": shape,
                               "mesh": "2x16x16" if mp else "16x16",
                               "error": f"{type(e).__name__}: {e}"}
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
    if failures:
        print("[dryrun] FAILURES:", failures)
        raise SystemExit(1)
    print("[dryrun] all requested cells done")


if __name__ == "__main__":
    main()
