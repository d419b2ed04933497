"""Paper Fig 3A: broadcast-only control — disconnected agents (A = I) with
any broadcast probability do not learn; the topology is what matters.

  PYTHONPATH=src python examples/paper/fig3a_broadcast.py [--quick]
"""
from __future__ import annotations

import argparse

from common import compare, save_result


def run(quick: bool = False):
    n, iters, seeds = (16, 30, range(2)) if quick else (40, 60, range(2))
    task = "cartpole_swingup"
    rows = {}
    for p_b in [0.0, 0.8]:
        res = compare(task, ["disconnected"], n, iters, seeds,
                      p_broadcast=p_b)
        rows[f"disconnected_pb={p_b}"] = res["disconnected"]
    for fam in ["erdos_renyi", "fully_connected"]:
        rows[fam] = compare(task, [fam], n, iters, seeds,
                            p_broadcast=0.8)[fam]
    disc = max(v["mean"] for k, v in rows.items()
               if k.startswith("disconnected"))
    print(f"er={rows['erdos_renyi']['mean']:.2f} "
          f"best_disconnected={disc:.2f}")
    save_result("fig3a_broadcast", rows)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true", help="smoke scale")
    run(quick=ap.parse_args().quick)
