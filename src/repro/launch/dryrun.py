import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: prove every (architecture × input-shape × mesh)
combination lowers, compiles, and fits — with 512 placeholder host devices
standing in for 2 TPU v5e pods (the XLA_FLAGS line above MUST precede any
jax import; jax locks the device count on first init).

Usage:
  python -m repro.launch.dryrun --arch mistral-nemo-12b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun]

Emits one JSON per pair with memory_analysis, cost_analysis, per-collective
byte counts, and the modelled roofline terms (``launch/analysis.py``).
"""

import argparse
import json
import pathlib
import time
import traceback

import jax  # noqa: E402  (after XLA_FLAGS on purpose)

from repro.configs import INPUT_SHAPES, shape_pairs
from repro.launch import analysis, hlo_parse, specs
from repro.launch.mesh import make_production_mesh


def run_pair(arch: str, shape_name: str, multi_pod: bool,
             out_dir: pathlib.Path, verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": mesh.size,
    }
    try:
        lowered, pair = specs.lower_pair(arch, shape_name, mesh)
        result["mode"] = pair.mode
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = analysis.memory_analysis_dict(compiled)
        cost = analysis.cost_analysis_dict(compiled)
        hlo = hlo_parse.hlo_costs(compiled.as_text())
        # trip-count-aware numbers (cost_analysis counts loop bodies once).
        # memory term: matmul-centric traffic model (dot operands+results);
        # touch_bytes (every op result ×2) is reported as the unfused upper
        # bound — the CPU backend does not fuse, so it wildly overcounts
        # what a TPU compilation would touch.
        flops = hlo["dot_flops"]
        hbm = hlo["dot_bytes"] + hlo["collective_bytes"]
        coll_bytes = hlo["collective_bytes"]
        terms = analysis.roofline_terms(flops, hbm, coll_bytes)
        shape = INPUT_SHAPES[shape_name]
        mflops_global = analysis.model_flops(pair.cfg, shape, pair.kind)
        mflops_per_dev = mflops_global / mesh.size
        result.update({
            "ok": True,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "memory": mem,
            "cost_analysis_raw": {"flops": cost.get("flops", 0.0),
                                  "bytes_accessed": cost.get("bytes accessed",
                                                             0.0)},
            "hlo_costs": hlo,
            "roofline": terms,
            "model_flops_per_device": mflops_per_dev,
            "useful_flops_ratio": (mflops_per_dev / flops) if flops else None,
        })
        if verbose:
            print(f"[OK] {arch} × {shape_name} × {result['mesh']} "
                  f"(mode={pair.mode})")
            print(f"     memory/device: args={mem.get('argument_bytes', 0)/2**30:.2f} GiB "
                  f"temp={mem.get('temp_bytes', 0)/2**30:.2f} GiB "
                  f"peak≈{mem.get('peak_bytes', 0)/2**30:.2f} GiB")
            print(f"     flops/device={flops:.3e} hbm/device={hbm:.3e} "
                  f"coll/device={coll_bytes:.3e} "
                  f"useful={result['useful_flops_ratio'] and round(result['useful_flops_ratio'], 3)}")
            print(f"     roofline: compute={terms['compute_s']*1e3:.2f}ms "
                  f"memory={terms['memory_s']*1e3:.2f}ms "
                  f"collective={terms['collective_s']*1e3:.2f}ms "
                  f"→ {terms['dominant']}-bound")
    except Exception as e:                                # noqa: BLE001
        result.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]})
        if verbose:
            print(f"[FAIL] {arch} × {shape_name} × {result['mesh']}: "
                  f"{result['error']}")
    out_dir.mkdir(parents=True, exist_ok=True)
    fname = f"{arch}__{shape_name}__{result['mesh']}.json"
    (out_dir / fname).write_text(json.dumps(result, indent=2, default=str))
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    out_dir = pathlib.Path(args.out)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    if args.all:
        pairs = shape_pairs()
    else:
        assert args.arch and args.shape, "--arch and --shape or --all"
        pairs = [(args.arch, args.shape)]

    failures = 0
    for multi_pod in meshes:
        for arch, shape_name in pairs:
            res = run_pair(arch, shape_name, multi_pod, out_dir)
            failures += 0 if res.get("ok") else 1
    print(f"\ndry-run complete: {len(pairs) * len(meshes) - failures} ok, "
          f"{failures} failed")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
