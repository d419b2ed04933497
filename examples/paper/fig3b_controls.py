"""Paper Fig 3B ablation: fully-connected controls —
(1) same init, no broadcast; (2) same init + broadcast;
(3) different init + broadcast; (4) different init, no broadcast —
vs NetES on an Erdos-Renyi graph. Shows the gain comes from topology.
(Paper: MuJoCo Ant, 100 agents. Here: cartpole swing-up.)

  PYTHONPATH=src python examples/paper/fig3b_controls.py [--quick]
"""
from __future__ import annotations

import argparse

import jax
import numpy as np
from common import save_result

from repro.core import netes
from repro.core.netes import NetESConfig
from repro.envs import ENVS, MLPPolicy, make_env_reward_fn
from repro.envs.rollout import evaluate_best
from repro.train.loop import TrainConfig, build_adjacency

CONTROLS = [
    ("fc_same_init_no_bcast", "fully_connected", True, 0.0),
    ("fc_same_init_bcast", "fully_connected", True, 0.8),
    ("fc_diff_init_bcast", "fully_connected", False, 0.8),
    ("fc_diff_init_no_bcast", "fully_connected", False, 0.0),
    ("netes_erdos", "erdos_renyi", False, 0.8),
]


def _run_control(task, family, same_init, p_b, n, iters, seed):
    env = ENVS[task]()
    policy = MLPPolicy(obs_dim=env.obs_dim, act_dim=env.act_dim)
    rf = make_env_reward_fn(env, policy)
    tc = TrainConfig(n_agents=n, iters=iters, topology_family=family,
                     topo_seed=seed, seed=seed,
                     netes=NetESConfig(alpha=0.05, sigma=0.1,
                                       p_broadcast=p_b))
    adj = build_adjacency(tc)
    state = netes.init_state(jax.random.PRNGKey(seed), n, policy.num_params,
                             init_fn=policy.init, same_init=same_init)
    state, _ = netes.run(state, adj, rf, tc.netes, iters)
    return float(evaluate_best(env, policy, state.best_theta,
                               jax.random.PRNGKey(seed + 999), 8))


def run(quick: bool = False):
    n, iters, seeds = (16, 20, range(2)) if quick else (40, 60, range(2))
    task = "cartpole_swingup"
    rows = {}
    for name, fam, same, p_b in CONTROLS:
        scores = [_run_control(task, fam, same, p_b, n, iters, s)
                  for s in seeds]
        arr = np.asarray(scores)
        rows[name] = {"mean": float(arr.mean()),
                      "ci95": float(1.96 * arr.std(ddof=1)
                                    / np.sqrt(len(arr)))
                      if len(arr) > 1 else 0.0,
                      "scores": scores}
    best_control = max(v["mean"] for k, v in rows.items()
                       if k != "netes_erdos")
    print(f"netes_er={rows['netes_erdos']['mean']:.2f} "
          f"best_fc_control={best_control:.2f}")
    save_result("fig3b_controls", rows)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true", help="smoke scale")
    run(quick=ap.parse_args().quick)
