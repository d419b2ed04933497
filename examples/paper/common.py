"""Shared helpers for the paper-experiment scripts in this directory.

Scale note: the paper runs 100–1000 AWS workers on MuJoCo/Roboschool for
millions of timesteps. These scripts keep the paper's experimental
design (same-density topology comparisons, same update rule, same
evaluation protocol, multi-seed averages with CIs) at reduced scale:
agents, iterations and episodes shrink, the comparisons don't.
``--quick`` shrinks them further for a smoke run.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, Iterable, List

import numpy as np

from repro.core.netes import NetESConfig
from repro.core.topology import TopologySpec
from repro.train.loop import TrainConfig, train_rl_netes

# Results land under the repo root (git-ignored), wherever the script is
# started from.
RESULTS_DIR = pathlib.Path(__file__).resolve().parents[2] / "experiments" \
    / "paper"


def run_one(task: str, family: str, n_agents: int, iters: int, seed: int,
            density: float = 0.5, p_broadcast: float = 0.8) -> Dict:
    tc = TrainConfig(
        n_agents=n_agents, iters=iters,
        topology=TopologySpec(family=family, n_agents=n_agents, p=density,
                              seed=seed),
        seed=seed, eval_every=max(1, iters // 8), eval_episodes=8,
        netes=NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=p_broadcast))
    hist = train_rl_netes(task, tc)
    return {"task": task, "family": family, "n": n_agents, "seed": seed,
            "density": density, "p_broadcast": p_broadcast,
            "max_eval": hist["max_eval"], "final_eval": hist["final_eval"],
            "wall_s": hist["wall_s"]}


def compare(task: str, families: Iterable[str], n_agents: int, iters: int,
            seeds: Iterable[int], **kw) -> Dict[str, Dict]:
    """Mean ± 95% CI of the paper's evaluation metric per family."""
    out: Dict[str, Dict] = {}
    for fam in families:
        scores: List[float] = []
        for seed in seeds:
            r = run_one(task, fam, n_agents, iters, seed, **kw)
            scores.append(r["max_eval"])
        arr = np.asarray(scores, dtype=np.float64)
        ci = 1.96 * arr.std(ddof=1) / np.sqrt(len(arr)) if len(arr) > 1 \
            else 0.0
        out[fam] = {"mean": float(arr.mean()), "ci95": float(ci),
                    "scores": scores}
    return out


def save_result(name: str, payload: Dict) -> pathlib.Path:
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    path = RESULTS_DIR / f"{name}.json"
    path.write_text(json.dumps(payload, indent=2, default=str))
    return path
