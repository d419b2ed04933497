"""Paper Fig 2B/C: an N-agent Erdos-Renyi population vs LARGER
fully-connected populations (paper: ER-1000 ≈ FC-3000 on Roboschool
Humanoid). Here: ER at N vs FC at {N, 3N} on cartpole swing-up.

  PYTHONPATH=src python examples/paper/fig2b_size_sweep.py [--quick]
"""
from __future__ import annotations

import argparse

from common import compare, save_result


def run(quick: bool = False):
    n, iters, seeds = (12, 30, range(2)) if quick else (24, 60, range(2))
    task = "cartpole_swingup"
    er = compare(task, ["erdos_renyi"], n, iters, seeds)
    rows = {"er": {"n": n, **er["erdos_renyi"]}, "fc": {}}
    for mult in (1, 3):
        fc = compare(task, ["fully_connected"], n * mult, iters, seeds)
        rows["fc"][f"n={n * mult}"] = fc["fully_connected"]
    print(f"er@{n}={rows['er']['mean']:.2f} "
          f"fc@{3 * n}={rows['fc'][f'n={3 * n}']['mean']:.2f}")
    save_result("fig2b_size_sweep", rows)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true", help="smoke scale")
    run(quick=ap.parse_args().quick)
