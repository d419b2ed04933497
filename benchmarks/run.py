"""Benchmark orchestrator — the only entry point for every registered
benchmark (paper tables/figures, kernel micro-benches, roofline, the
1024-agent fleet axis, the sharded 16384-agent mesh axis). Prints
``name,us_per_call,derived`` CSV to stdout and writes the
schema-versioned ``BENCH_topologies.json`` / ``BENCH_kernels.json`` /
``BENCH_fleet.json`` / ``BENCH_sharded.json`` artifacts to ``--out-dir``.

  python benchmarks/run.py --profile ci            # regression-gated set
  python benchmarks/run.py --profile quick         # everything, smoke scale
  python benchmarks/run.py --profile full          # paper-reduced scale
  python benchmarks/run.py --only table1,fig5      # by name, any profile

Gate a run against the committed baselines with
``python benchmarks/check_regression.py --candidate <out-dir>``.
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import time

# Make both `python benchmarks/run.py` and `python -m benchmarks.run`
# work without PYTHONPATH massaging: the repo root provides the
# `benchmarks` package, `src` provides `repro`.
_ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import importlib                                              # noqa: E402

from benchmarks import registry                               # noqa: E402
from repro.launch.compile_cache import enable_compile_cache   # noqa: E402

# Importing the suite modules populates the registry.
for _mod in ("fig2a_families", "fig2b_size_sweep", "fig3a_broadcast",
             "fig3b_controls", "fig3c_reach_homog", "fig4_approx",
             "fig5_density", "fleet16k_bench", "fleet_bench",
             "kernel_bench", "lm_netes", "resilience_bench", "roofline",
             "search_bench", "table1_er_vs_fc"):
    importlib.import_module(f"benchmarks.{_mod}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--profile", choices=registry.PROFILES, default="full")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names (overrides the "
                         "profile's selection; scales still follow "
                         "--profile)")
    ap.add_argument("--out-dir", default=_ROOT / "bench-out",
                    type=pathlib.Path,
                    help="where BENCH_*.json (and results/) are written "
                         "(default: <repo>/bench-out, gitignored — never "
                         "the CWD)")
    ap.add_argument("--list", action="store_true",
                    help="list registered benchmarks and exit")
    args = ap.parse_args(argv)

    if args.list:
        for b in registry.registered().values():
            print(f"{b.name:<10} group={b.group:<11} "
                  f"profiles={','.join(b.profiles)}")
        return 0

    enable_compile_cache()
    only = args.only.split(",") if args.only else None
    print("name,us_per_call,derived")
    t0 = time.time()
    _, failures = registry.run_profile(args.profile, args.out_dir, only=only)
    print(f"total,{(time.time() - t0) * 1e6:.0f},"
          f"profile={args.profile} failures={failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
