"""Paper Fig 3C: reachability/homogeneity scatter over random instances of
the four families — Erdos-Renyi maximizes reachability & minimizes
homogeneity; fully-connected is the extreme opposite.

  PYTHONPATH=src python examples/paper/fig3c_reach_homog.py [--quick]
"""
from __future__ import annotations

import argparse

import numpy as np
from common import save_result

from repro.core import topology

FAMILIES = ["erdos_renyi", "scale_free", "small_world", "fully_connected"]


def run(quick: bool = False):
    n, n_seeds = (100, 5) if quick else (300, 15)
    rows = {}
    for fam in FAMILIES:
        pts = []
        for s in range(n_seeds):
            kw = {} if fam == "fully_connected" else {"p": 0.5}
            adj = topology.make_topology(fam, n, seed=s, **kw)
            pts.append((topology.reachability(adj),
                        topology.homogeneity(adj)))
        arr = np.asarray(pts)
        rows[fam] = {"reachability_mean": float(arr[:, 0].mean()),
                     "homogeneity_mean": float(arr[:, 1].mean()),
                     "points": arr.tolist()}
    er, fc = rows["erdos_renyi"], rows["fully_connected"]
    ok = (er["reachability_mean"] > fc["reachability_mean"]
          and er["homogeneity_mean"] < fc["homogeneity_mean"])
    print(f"er_extremizes={ok} er_reach={er['reachability_mean']:.4f} "
          f"fc_reach={fc['reachability_mean']:.4f}")
    save_result("fig3c_reach_homog", rows)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true", help="smoke scale")
    run(quick=ap.parse_args().quick)
