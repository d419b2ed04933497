"""Paper Fig 4 / Appendix Fig 6: measured reachability & homogeneity vs the
Lemma 7.2 closed-form approximations across density p (n = 1000 as in the
paper; reduced seeds).

  PYTHONPATH=src python examples/paper/fig4_approx.py [--quick]
"""
from __future__ import annotations

import argparse

import numpy as np
from common import save_result

from repro.core import topology


def run(quick: bool = False):
    n, n_seeds = (200, 2) if quick else (1000, 3)
    ps = [0.2, 0.4, 0.6, 0.8] if quick else \
        [0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9]
    rows = []
    for p in ps:
        graphs = [topology.erdos_renyi(n, p=p, seed=s, connect=False)
                  for s in range(n_seeds)]
        rows.append({
            "p": p,
            "reachability": float(np.mean(
                [topology.reachability(g) for g in graphs])),
            "reachability_approx": topology.reachability_approx(n, p),
            "reachability_large_n": 1.0 / (p * np.sqrt(n)),
            "homogeneity": float(np.mean(
                [topology.homogeneity(g) for g in graphs])),
            "homogeneity_approx": topology.homogeneity_approx(n, p),
        })
    max_rel = max(abs(r["reachability"] - r["reachability_approx"])
                  / r["reachability"] for r in rows if r["p"] >= 0.3)
    print(f"n={n} max_rel_err(p>=0.3)={max_rel:.3f}")
    save_result("fig4_approx", {"n": n, "rows": rows,
                                "max_rel_err": max_rel})
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true", help="smoke scale")
    run(quick=ap.parse_args().quick)
