"""Device time of one iteration's Eq. 3 mixing: the program's
``core.netes.mixing_update`` on the cell's own topology representation,
weight decay (``core.es_utils.apply_weight_decay``), the parameter add
and the broadcast-best select, called alone at the cell's (N, D)."""


def probes(ctx):
    import jax
    import jax.numpy as jnp

    from repro.core import es_utils, netes, topology_repr
    from repro.core.topology import TopologySpec
    c = ctx.cell.config
    if c["channel"]:
        return {}
    n, dim = c["n_agents"], c["policy"]["dim"]
    cfg = netes.NetESConfig(**c["netes"])
    spec = TopologySpec(family=c["topology"]["family"], n_agents=n,
                        p=c["topology"]["p"], seed=c["topology"]["seed"])
    topo = topology_repr.from_spec(spec, c["topology"]["representation"])

    @jax.jit
    def make():
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        th = 0.1 * jax.random.normal(k1, (n, dim))
        pert = th + cfg.sigma * jax.random.normal(k2, (n, dim))
        shaped = jax.random.uniform(k3, (n,), minval=-1.0, maxval=1.0)
        return th, pert, shaped

    th, pert, shaped = make()

    @jax.jit
    def mix(topo, th, pert, shaped):
        update = netes.mixing_update(topo, th, pert, shaped, cfg)
        update = es_utils.apply_weight_decay(th, update, cfg.weight_decay)
        new = th + update
        best = pert[jnp.argmax(shaped)]
        return jnp.where(shaped[0] < 0.6, jnp.broadcast_to(best, new.shape),
                         new)

    return {"mixing": lambda: mix(topo, th, pert, shaped)}


def read(ctx):
    s = ctx.probe_seconds.get("mixing")
    return None if s is None else 1e3 * s
