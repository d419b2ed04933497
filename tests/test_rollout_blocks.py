"""The env reward's agent blocks (envs.rollout.reward_blocks, DESIGN.md
§13): the byte rule, and that running the population's episodes in
blocks changes no row's return, bit for bit."""
import jax
import numpy as np
import pytest

from repro.envs import ENVS, MLPPolicy
from repro.envs.rollout import (REWARD_BLOCK_BYTES, make_env_reward_fn,
                                reward_blocks)

D = 4481          # the paper's 3->64->64->1 pendulum policy
ROWS = 32         # the block the tests' budget allows


def _reward_fns(task, discrete=False):
    """(unblocked, blocked at ROWS rows) reward functions for ``task``."""
    env = ENVS[task]()
    policy = MLPPolicy(obs_dim=env.obs_dim, act_dim=env.act_dim,
                       discrete=discrete)
    budget = ROWS * 4 * policy.num_params
    return (make_env_reward_fn(env, policy),
            make_env_reward_fn(env, policy, block_bytes=budget), policy)


def _population(policy, m, seed=0):
    k_init, k_eps = jax.random.split(jax.random.PRNGKey(seed))
    thetas = jax.vmap(policy.init)(jax.random.split(k_init, m))
    return thetas + 0.1 * jax.random.normal(k_eps, thetas.shape)


@pytest.mark.parametrize("m,d", [(1000, D), (3000, D), (4096, D),
                                 (96, D), (100, D), (3001, D)])
def test_rule_blocks_fit_the_budget_and_cover_the_rows(m, d):
    blocks, rows = reward_blocks(m, d)
    if m * 4 * d <= REWARD_BLOCK_BYTES:
        assert (blocks, rows) == (1, m)
        return
    assert blocks > 1 and rows % 8 == 0
    assert rows * 4 * d <= REWARD_BLOCK_BYTES
    assert (blocks - 1) * rows < m <= blocks * rows


def test_rule_on_the_benchmark_populations():
    """er1000's population runs as today's one block; fc3000's 3000 rows
    and a four-chip shard's 4096 rows run in blocks."""
    assert reward_blocks(1000, D) == (1, 1000)
    assert reward_blocks(3000, D)[0] > 1
    assert reward_blocks(4096, D)[0] > 1


def test_rule_keeps_eight_rows_under_a_tiny_budget():
    assert reward_blocks(20, D, budget=1) == (3, 8)


@pytest.mark.parametrize("task,discrete,m", [
    ("pendulum", False, 96),      # three full blocks
    ("pendulum", False, 100),     # three full blocks and a remainder of 4
    ("acrobot", False, 100),
    ("acrobot", True, 100),       # logits straight to the task
])
def test_blocked_rewards_equal_unblocked_bit_for_bit(task, discrete, m):
    whole, blocked, policy = _reward_fns(task, discrete)
    params = _population(policy, m)
    key = jax.random.PRNGKey(7)
    assert blocked.blocks(m) == (-(-m // ROWS), ROWS)
    assert whole.blocks(m) == (1, m)
    # jitted, as netes_step calls it: eager dispatch of the unblocked
    # vmap compiles the episode scan alone and can round differently
    np.testing.assert_array_equal(np.asarray(jax.jit(blocked)(params, key)),
                                  np.asarray(jax.jit(whole)(params, key)))


def test_blocked_rewards_run_as_a_scan_over_the_blocks():
    """The blocked program maps over M // ROWS full blocks; the unblocked
    one has only the episode scan."""
    whole, blocked, policy = _reward_fns("pendulum")
    params = _population(policy, 100)
    key = jax.random.PRNGKey(7)

    def top_scan_lengths(fn):
        jaxpr = jax.make_jaxpr(fn)(params, key).jaxpr
        return [e.params["length"] for e in jaxpr.eqns
                if e.primitive.name == "scan"]

    steps = ENVS["pendulum"]().episode_len
    assert sorted(top_scan_lengths(blocked)) == [100 // ROWS, steps]
    assert top_scan_lengths(whole) == [steps]


def test_blocked_rewards_under_an_outer_vmap_over_candidates():
    """``search.tournament`` vmaps the run over candidates: the rule sees
    each candidate's M, and the blocked rewards equal the unblocked."""
    whole, blocked, policy = _reward_fns("pendulum")
    params = np.stack([_population(policy, 40, seed=s) for s in (0, 1)])
    keys = jax.random.split(jax.random.PRNGKey(9), 2)
    got = jax.jit(jax.vmap(blocked))(params, keys)
    want = jax.jit(jax.vmap(whole))(params, keys)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


_SHARDED_SCRIPT = r"""
import json
import jax
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed import fleet_shard
from repro.envs import ENVS, MLPPolicy
from repro.envs.rollout import make_env_reward_fn

env = ENVS["pendulum"]()
policy = MLPPolicy(obs_dim=env.obs_dim, act_dim=env.act_dim)
whole = make_env_reward_fn(env, policy)
blocked = make_env_reward_fn(env, policy,
                             block_bytes=32 * 4 * policy.num_params)
n = 4 * 72                        # 72 rows a shard: 3 blocks of 24
params = jax.vmap(policy.init)(jax.random.split(jax.random.PRNGKey(0), n))
keys = jax.random.split(jax.random.PRNGKey(7), n)
rows = P(fleet_shard.AXIS)
per_shard = jax.jit(jax.shard_map(
    blocked.rowwise, mesh=fleet_shard.build_mesh(4), in_specs=(rows, rows),
    out_specs=rows, check_vma=False))   # as fleet_shard runs it
got = np.asarray(per_shard(params, keys))
want = np.asarray(jax.jit(whole.rowwise)(params, keys))
print("SHARDED " + json.dumps({
    "blocks": blocked.blocks(n // 4), "equal": bool((got == want).all())}))
"""


def test_blocked_rewards_per_shard_equal_the_unblocked_population():
    """``fleet_shard`` calls ``rowwise`` on each shard's rows: on 4 forced
    host devices, 72 rows a shard in 3 blocks of 24 give the rewards of the
    whole population's one block, bit for bit."""
    import json
    import os
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(PYTHONPATH="src", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT],
                         capture_output=True, text=True, timeout=600,
                         env=env)
    lines = [ln for ln in res.stdout.splitlines()
             if ln.startswith("SHARDED ")]
    assert lines, (res.stdout[-2000:], res.stderr[-4000:])
    out = json.loads(lines[-1][len("SHARDED "):])
    assert out == {"blocks": [3, 24], "equal": True}
