"""Episode rollouts as lax.scan, and reward_fn factories for NetES.

The paper evaluates each perturbed parameter set with one full episode per
iteration (§5.2 modification (1)). ``make_env_reward_fn`` returns a
``reward_fn(params (M, D), key) -> (M,)`` that vmaps episode returns over
the population — the exact interface ``core.netes`` consumes.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from .policy import MLPPolicy


def episode_return(env, policy: MLPPolicy, theta: jax.Array,
                   key: jax.Array) -> jax.Array:
    k_reset, k_steps = jax.random.split(key)
    state0 = env.reset(k_reset)

    def body(carry, k):
        state, total = carry
        obs = env.observe(state)
        action = policy.apply(theta, obs)
        state, reward = env.step(state, action, k)
        return (state, total + reward), None

    keys = jax.random.split(k_steps, env.episode_len)
    # strong-typed return accumulator: a weak 0.0 carry re-keys the jit
    # signature once the first scan hands back a strong f32 (PR 3 class)
    total0 = jnp.zeros((), jnp.float32)
    (final_state, total), _ = jax.lax.scan(body, (state0, total0), keys)
    del final_state
    return total


# The float32 parameter bytes of the rows whose episodes run together.
# Every step of an episode's scan reads each row's weights again. A block
# this small keeps its parameters in on-chip memory across the episode;
# a TPU v5e puts a whole population of 3000 or 4096 rows in HBM and
# re-reads its weights from there on every step (DESIGN.md §13). Set from
# a sweep on one v5e: the pendulum policy (D = 4481), both antithetic
# halves, device ms per call (blocks x rows):
#   N = 1000: 1 x 1000 3.02; 2 x 504 3.35; 3 x 336 3.09
#   N = 3000: 1 x 3000 22.65; 7 x 432 12.15; 5 x 600 9.43; 4 x 752 9.32;
#             3 x 1000 10.18; 2 x 1504 9.92
#   N = 4096: 1 x 4096 46.62; 6 x 688 13.82; 5 x 824 12.09;
#             4 x 1024 13.67; 3 x 1368 11.65; 2 x 2048 12.97
# 28 MiB keeps N = 1000 in one block (it needs 17.1 MiB) and gives the
# two larger populations 2 x 1504 and 3 x 1368, their least time summed.
REWARD_BLOCK_BYTES = 28 * 2 ** 20


def reward_blocks(m: int, dim: int,
                  budget: int = REWARD_BLOCK_BYTES) -> Tuple[int, int]:
    """``(blocks, rows)``: how ``make_env_reward_fn``'s reward runs M rows
    of D float32 parameters. Within ``budget`` bytes it is one block of M
    rows. Past it, the fewest equal blocks of at most ``budget`` bytes,
    each a multiple of 8 rows (a float32 tile's sublanes); the last block
    holds the remainder. At least 8 rows a block, whatever the budget."""
    row_bytes = 4 * dim
    if m * row_bytes <= budget:
        return 1, m
    cap = max(8, budget // row_bytes // 8 * 8)
    rows = -(-m // -(-m // cap))
    rows += -rows % 8
    return -(-m // rows), rows


def make_env_reward_fn(env, policy: MLPPolicy, episodes_per_eval: int = 1,
                       block_bytes: int = REWARD_BLOCK_BYTES) -> Callable:
    """reward_fn(params (M, D), key) -> (M,) mean episode return.

    The M rows run in ``reward_blocks(M, D, block_bytes)`` blocks, one
    after the other, each a vmap of the rows' episodes. Each row's
    episode, and so its return, is the same at any block size."""

    def single(theta: jax.Array, key: jax.Array) -> jax.Array:
        keys = jax.random.split(key, episodes_per_eval)
        rets = jax.vmap(partial(episode_return, env, policy, theta))(keys)
        return rets.mean()

    def blocks(m: int) -> Tuple[int, int]:
        return reward_blocks(m, policy.num_params, block_bytes)

    def rowwise(params: jax.Array, keys: jax.Array) -> jax.Array:
        n_blocks, rows = blocks(params.shape[0])
        if n_blocks == 1:
            return jax.vmap(single)(params, keys)
        return jax.lax.map(lambda a: single(*a), (params, keys),
                           batch_size=rows)

    def reward_fn(params: jax.Array, key: jax.Array) -> jax.Array:
        return rowwise(params, jax.random.split(key, params.shape[0]))

    # Row m's episode key is split(key, M)[m]. A caller that evaluates
    # a slice of the population (one shard of a mesh) hands ``rowwise``
    # the keys of its rows, so each agent sees the same episode at any
    # mesh size (distributed/fleet_shard.py).
    reward_fn.rowwise = rowwise
    reward_fn.blocks = blocks
    return reward_fn


def evaluate_best(env, policy: MLPPolicy, theta: jax.Array, key: jax.Array,
                  episodes: int = 32) -> jax.Array:
    """Paper's evaluation metric: run best params w/o noise for many
    episodes, return mean total reward (§5.2; 1000 episodes in the paper,
    reduced here). Its ops carry the ``eval`` named scope (DESIGN.md
    §15)."""
    with jax.named_scope("eval"):
        keys = jax.random.split(key, episodes)
        rets = jax.vmap(partial(episode_return, env, policy, theta))(keys)
        return rets.mean()


def make_evaluator(env, policy: Optional[MLPPolicy], episodes: int,
                   reward_fn: Optional[Callable] = None) -> Callable:
    """One jitted evaluation program for a whole training run:
    ``evaluate(theta, eval_key) -> (score, next_eval_key)``.

    It splits ``eval_key`` into the next key and this point's key, then
    scores ``theta`` on the latter: ``evaluate_best``'s mean return over
    ``episodes`` episodes, or, with ``env`` None (a landscape task),
    ``reward_fn`` on the one row. Built once per run and called at every
    evaluation point, it traces and compiles once; ``evaluate_best``
    called eagerly re-traces its episode scan at every call. Every op
    carries the ``eval`` named scope."""
    if env is not None:
        score = partial(evaluate_best, env, policy, episodes=episodes)
    else:
        def score(theta, key):
            return reward_fn(theta[None], key)[0]

    @jax.jit
    def evaluate(theta: jax.Array, eval_key: jax.Array):
        with jax.named_scope("eval"):
            eval_key, k_eval = jax.random.split(eval_key)
            return score(theta, k_eval), eval_key

    return evaluate
