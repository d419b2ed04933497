"""The plain reference against the program's NetES step, at small sizes.

On the CPU both sides compute in float32, so the first iterations agree
to rounding: the graph, the parameters, the noise, the episodes, the
shaping, Eq. 3, the broadcast and the int8 codec all have to match.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import compare
from perfbench import reference as ref
from repro.comm.channel import compile_channel
from repro.core import netes, topology_repr
from repro.core.netes import NetESConfig
from repro.core.topology import TopologySpec
from repro.envs.pendulum import Pendulum
from repro.envs.policy import MLPPolicy
from repro.envs.rollout import make_env_reward_fn

N, SIZES, ITERS = 24, (3, 8, 8, 1), 3
CFG = NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.8)


def _program_metrics(family, p, representation, channel, seed):
    policy = MLPPolicy(obs_dim=3, act_dim=1, hidden=SIZES[1:-1])
    reward_fn = make_env_reward_fn(Pendulum(), policy)
    spec = TopologySpec(family=family, n_agents=N, p=p, seed=3)
    topo = topology_repr.from_spec(spec, representation=representation)
    chan = compile_channel(channel, N) if channel else None
    state = netes.init_state(jax.random.PRNGKey(seed), N, policy.num_params,
                             init_fn=policy.init)
    kw = {}
    if chan is not None:
        kw.update(channel=chan, chan_state=chan.init(state.thetas))
    out = jax.device_get(netes.run(state, topo, reward_fn, CFG, ITERS,
                                   **kw)[-1])
    after = netes.run(state, topo, reward_fn, CFG, 1, **kw)[0].thetas
    out["theta_mean"] = [np.asarray(t.mean(axis=0), np.float64)
                         for t in (state.thetas, after)]
    out["row"] = np.asarray(after[0], np.float64)
    return out


@pytest.mark.parametrize("family,p,representation,channel", [
    ("fully_connected", 1.0, "dense", None),
    ("erdos_renyi", 0.3, "sparse", None),
    ("erdos_renyi", 0.3, "sparse", "quantize(bits=8)"),
])
def test_reference_matches_program_step(family, p, representation, channel):
    seed = 2 ** 31 + 17
    got = _program_metrics(family, p, representation, channel, seed)
    setup = ref.Setup(n=N, family=family, p=p, topo_seed=3, sizes=SIZES,
                      quantize_bits=8 if channel else None)
    reference = ref.Reference(setup)
    flags = reference.broadcast_flags(seed, ITERS)
    first = compare.numbers(got, reference.first(seed), flags)
    assert first["broadcast_mismatch"] == 0
    assert first["reward_mean_gap"] < 1e-6, first
    assert first["update_var_gap"] < 1e-5, first
    if flags[0]:
        assert first["select_row_gap"] < 1e-6 and first["select_rank"] == 0
    else:
        assert first["mix_gap"] < 1e-5, first
    # later iterations: float32 on both sides, so rounding only (and an
    # int8 code that a division rounded the other way, now and then)
    later = compare.later_iterations(got, reference.run(seed, ITERS))
    assert later["reward_mean_gap"] < 1e-4, later


@pytest.mark.parametrize("n,p,seed", [(40, 0.05, 0), (300, 0.01, 7),
                                      (64, 0.5, 2)])
def test_reference_graph_is_the_configured_graph(n, p, seed):
    """Same G(n, p) draw and the same bridge edges as the program's
    generator, including graphs that need repair."""
    adj = TopologySpec(family="erdos_renyi", n_agents=n, p=p,
                       seed=seed).build()
    edges = ref.erdos_renyi_edges(n, p, seed)
    mine = np.zeros((n, n), np.float32)
    mine[edges[:, 0], edges[:, 1]] = 1.0
    np.testing.assert_array_equal(mine, adj)


def test_control_in_bfloat16_departs_from_reference():
    setup = ref.Setup(n=N, family="erdos_renyi", p=0.3, topo_seed=3,
                      sizes=SIZES)
    f32 = ref.Reference(setup).run(5, 2)
    bf16 = ref.Reference(setup, dtype=jnp.bfloat16).run(5, 2)
    gap = abs(bf16["reward_mean"][0] - f32["reward_mean"][0])
    assert gap / abs(f32["reward_mean"][0]) > 1e-4
