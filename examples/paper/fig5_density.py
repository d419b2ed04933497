"""Paper Fig 5: sparser Erdos-Renyi graphs learn better (reward improvement
vs fully-connected as density decreases). Paper: Roboschool Humanoid,
1000 agents. Here: cartpole swing-up.

  PYTHONPATH=src python examples/paper/fig5_density.py [--quick]
"""
from __future__ import annotations

import argparse

from common import compare, save_result


def run(quick: bool = False):
    n, iters, seeds = (16, 30, range(2)) if quick else (32, 60, range(2))
    densities = [0.2, 0.6, 1.0] if quick else [0.1, 0.5, 1.0]
    task = "cartpole_swingup"
    fc = compare(task, ["fully_connected"], n, iters, seeds)
    fc_mean = fc["fully_connected"]["mean"]
    rows = {"fully_connected": fc["fully_connected"]}
    for p in densities:
        r = compare(task, ["erdos_renyi"], n, iters, seeds,
                    density=p)["erdos_renyi"]
        r["improvement_vs_fc"] = r["mean"] - fc_mean
        rows[f"er_p={p}"] = r
    print(f"sparse={rows[f'er_p={densities[0]}']['mean']:.2f} "
          f"dense={rows[f'er_p={densities[-1]}']['mean']:.2f} "
          f"fc={fc_mean:.2f}")
    save_result("fig5_density", rows)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true", help="smoke scale")
    run(quick=ap.parse_args().quick)
