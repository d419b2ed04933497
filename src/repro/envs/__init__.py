"""Pure-JAX environments for NetES evaluation.

MuJoCo/Roboschool are unavailable offline; these JAX control tasks +
synthetic landscapes are the reduced-scale stand-ins (DESIGN.md §7.1).
"""
import functools

import jax

from .acrobot import Acrobot
from .cartpole import CartPoleSwingUp
from .landscapes import LANDSCAPES, make_landscape_reward_fn
from .pendulum import Pendulum
from .policy import MLPPolicy
from .rollout import make_env_reward_fn

ENVS = {
    "pendulum": Pendulum,
    "cartpole_swingup": CartPoleSwingUp,
    "acrobot": Acrobot,
}

# Parameter dimensionality of the synthetic landscape tasks (matches the
# paper-reduced scale of examples/paper).
LANDSCAPE_DIM = 64


def _landscape_init(key):
    return jax.random.normal(key, (LANDSCAPE_DIM,))


@functools.lru_cache(maxsize=None)
def resolve_task(task: str):
    """``"landscape:<name>"`` or an ``ENVS`` key →
    ``(reward_fn, dim, init_fn, env, policy)`` with
    ``reward_fn(params (M, D), key) -> (M,)``.

    The one task-resolution shared by the training loops
    (``train/loop.py``) and the topology-search tournaments
    (``repro/search``). Memoized per task string: the returned
    ``reward_fn`` closure is a jit-static argument of the fused training
    scans, so a fresh closure per call would miss every executable cache
    and recompile the scan each run (the warm-rerun compile gates of
    tests/test_train_loop.py and tests/test_search.py rely on this).
    ``env``/``policy`` are ``None`` for landscape tasks.
    """
    if task.startswith("landscape:"):
        name = task.split(":", 1)[1]
        return (make_landscape_reward_fn(name), LANDSCAPE_DIM,
                _landscape_init, None, None)
    env = ENVS[task]()
    policy = MLPPolicy(obs_dim=env.obs_dim, act_dim=env.act_dim)
    return (make_env_reward_fn(env, policy), policy.num_params, policy.init,
            env, policy)


__all__ = [
    "LANDSCAPES", "make_landscape_reward_fn", "Pendulum", "CartPoleSwingUp",
    "Acrobot", "MLPPolicy", "make_env_reward_fn", "ENVS", "LANDSCAPE_DIM",
    "resolve_task",
]
