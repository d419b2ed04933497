"""Device time of one iteration's reward evaluation: the program's own
reward function (``repro.envs.resolve_task``) called alone on both
antithetic halves at the cell's (N, D), parameters drawn on the device."""


def probes(ctx):
    import jax

    from repro.envs import resolve_task
    c = ctx.cell.config
    reward_fn, dim, init_fn = resolve_task(ctx.cell.traffic["task"])[:3]
    n, sigma = c["n_agents"], c["netes"]["sigma"]

    @jax.jit
    def make():
        k_init, k_eps = jax.random.split(jax.random.PRNGKey(0))
        thetas = jax.vmap(init_fn)(jax.random.split(k_init, n))
        return thetas, sigma * jax.random.normal(k_eps, (n, dim))

    thetas, s_eps = make()
    k_eval = jax.random.PRNGKey(1)

    @jax.jit
    def rollout(th, se):
        return reward_fn(th + se, k_eval), reward_fn(th - se, k_eval)

    return {"rollout": lambda: rollout(thetas, s_eps)}


def read(ctx):
    s = ctx.probe_seconds.get("rollout")
    return None if s is None else 1e3 * s
