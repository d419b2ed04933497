"""Paper Fig 2A: learning performance across the four graph families
(Erdos-Renyi, scale-free, small-world, fully-connected), same density.
Paper setting: MuJoCo Ant, 100 agents. Here: cartpole swing-up, reduced
populations (see common.py's scale note).

  PYTHONPATH=src python examples/paper/fig2a_families.py [--quick]
"""
from __future__ import annotations

import argparse

from common import compare, save_result

FAMILIES = ["erdos_renyi", "scale_free", "small_world", "fully_connected"]


def run(quick: bool = False):
    n, iters, seeds = (16, 30, range(2)) if quick else (40, 60, range(2))
    task = "cartpole_swingup"
    res = compare(task, FAMILIES, n, iters, seeds)
    best = max(res, key=lambda f: res[f]["mean"])
    print(f"{task}: best={best} " + " ".join(
        f"{fam}={res[fam]['mean']:.2f}" for fam in FAMILIES))
    save_result("fig2a_families", {task: res})
    return res


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true", help="smoke scale")
    run(quick=ap.parse_args().quick)
