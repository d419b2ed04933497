"""Synthetic high-dimensional reward landscapes.

The networked-optimization literature the paper builds on (Lazer & Friedman
2007; Barkoczi & Galesic 2016) uses exactly these kinds of rugged synthetic
landscapes to study topology effects. They give fast, seeded, noise-free
comparisons between graph families — our primary statistical validation of
the paper's Fig 2A / Fig 5 claims on CPU.

Rewards are negated costs (higher is better); optimum value is 0 at x*=0
(or the standard optimum for rosenbrock).
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp


def sphere(x: jax.Array) -> jax.Array:
    return -jnp.sum(x ** 2, axis=-1)


def rastrigin(x: jax.Array) -> jax.Array:
    a = 10.0
    d = x.shape[-1]
    return -(a * d + jnp.sum(x ** 2 - a * jnp.cos(2 * jnp.pi * x), axis=-1))


def rosenbrock(x: jax.Array) -> jax.Array:
    x0 = x[..., :-1]
    x1 = x[..., 1:]
    return -jnp.sum(100.0 * (x1 - x0 ** 2) ** 2 + (1.0 - x0) ** 2, axis=-1)


def ackley(x: jax.Array) -> jax.Array:
    d = x.shape[-1]
    s1 = jnp.sqrt(jnp.sum(x ** 2, axis=-1) / d)
    s2 = jnp.sum(jnp.cos(2 * jnp.pi * x), axis=-1) / d
    return -(-20.0 * jnp.exp(-0.2 * s1) - jnp.exp(s2) + 20.0 + jnp.e)


def griewank(x: jax.Array) -> jax.Array:
    d = x.shape[-1]
    idx = jnp.sqrt(jnp.arange(1, d + 1, dtype=x.dtype))
    return -(jnp.sum(x ** 2, axis=-1) / 4000.0
             - jnp.prod(jnp.cos(x / idx), axis=-1) + 1.0)


LANDSCAPES: Dict[str, Callable] = {
    "sphere": sphere,
    "rastrigin": rastrigin,
    "rosenbrock": rosenbrock,
    "ackley": ackley,
    "griewank": griewank,
}


@functools.lru_cache(maxsize=None)
def make_landscape_reward_fn(name: str, noise_std: float = 0.0) -> Callable:
    """Returns reward_fn(params (M, D), key) -> (M,) for NetES.

    ``name`` may carry a shift suffix ``<fn>@<shift>`` (e.g.
    "rastrigin@2.5"): the optimum moves to x* = shift·1. Unshifted
    center-at-origin benchmarks are BIASED TOWARD FULLY-CONNECTED
    topologies — the consensus pull of the FC update points at the centroid
    of the population, which for a symmetric init IS the origin-optimum.
    Shifting (as in BBOB) removes that artifact; the paper's RL reward
    landscapes have no such centering.

    Memoized per (name, noise_std): the returned closure is a jit-static
    argument of ``netes_step``/``netes.run`` — a fresh closure per
    training run would miss every jit cache and recompile the fused scan
    on each ``train_rl_netes`` call (tests/test_train_loop.py's
    warm-rerun compile gate relies on this).
    """
    shift = 0.0
    if "@" in name:
        name, s = name.split("@", 1)
        shift = float(s)
    fn = LANDSCAPES[name]

    def reward_fn(params: jax.Array, key: jax.Array) -> jax.Array:
        r = fn(params - shift)
        if noise_std > 0.0:
            r = r + noise_std * jax.random.normal(key, r.shape)
        return r

    return reward_fn
