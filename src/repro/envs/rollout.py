"""Episode rollouts as lax.scan, and reward_fn factories for NetES.

The paper evaluates each perturbed parameter set with one full episode per
iteration (§5.2 modification (1)). ``make_env_reward_fn`` returns a
``reward_fn(params (M, D), key) -> (M,)`` that vmaps episode returns over
the population — the exact interface ``core.netes`` consumes.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional

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


def make_env_reward_fn(env, policy: MLPPolicy,
                       episodes_per_eval: int = 1) -> Callable:
    """reward_fn(params (M, D), key) -> (M,) mean episode return."""

    def single(theta: jax.Array, key: jax.Array) -> jax.Array:
        keys = jax.random.split(key, episodes_per_eval)
        rets = jax.vmap(partial(episode_return, env, policy, theta))(keys)
        return rets.mean()

    def rowwise(params: jax.Array, keys: jax.Array) -> jax.Array:
        return jax.vmap(single)(params, keys)

    def reward_fn(params: jax.Array, key: jax.Array) -> jax.Array:
        return rowwise(params, jax.random.split(key, params.shape[0]))

    # Row m's episode key is split(key, M)[m]. A caller that evaluates
    # a slice of the population (one shard of a mesh) hands ``rowwise``
    # the keys of its rows, so each agent sees the same episode at any
    # mesh size (distributed/fleet_shard.py).
    reward_fn.rowwise = rowwise
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
