"""Paper Table 1: Erdos-Renyi vs fully-connected on the five benchmark
tasks (paper: 1000 agents on Mujoco Ant/HalfCheetah/Hopper/Humanoid +
Roboschool Humanoid). Here: five reduced tasks spanning the same kinds of
difficulty — three JAX control tasks + two rugged landscapes.

  PYTHONPATH=src python examples/paper/table1_er_vs_fc.py [--quick]
"""
from __future__ import annotations

import argparse

from common import compare, save_result

TASKS = ["pendulum", "cartpole_swingup", "acrobot",
         "landscape:rastrigin@2.5", "landscape:ackley@2.5"]


def run(quick: bool = False):
    n, iters, seeds = (16, 25, range(2)) if quick else (40, 60, range(2))
    tasks = TASKS[:2] + TASKS[3:4] if quick else TASKS
    rows = {}
    for task in tasks:
        res = compare(task, ["fully_connected", "erdos_renyi"], n, iters,
                      seeds)
        er, fc = res["erdos_renyi"]["mean"], res["fully_connected"]["mean"]
        # paper reports % improvement of ER over FC
        denom = abs(fc) if abs(fc) > 1e-9 else 1.0
        improv = 100.0 * (er - fc) / denom
        rows[task] = {"fully_connected": fc, "erdos_renyi": er,
                      "improvement_pct": improv,
                      "fc_ci": res["fully_connected"]["ci95"],
                      "er_ci": res["erdos_renyi"]["ci95"]}
        print(f"{task}: fc={fc:.2f} er={er:.2f} improv={improv:+.1f}%")
    save_result("table1_er_vs_fc", rows)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true", help="smoke scale")
    run(quick=ap.parse_args().quick)
