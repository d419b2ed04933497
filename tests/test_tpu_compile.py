"""Compiles for a TPU v5e chip that is described, not attached.

The TPU compiler is installed with JAX, so the main-path kernels and the
paper-scale training scan can be compiled for a ``v5e:2x2`` topology on
a CPU-only host. Nothing executes: these tests catch what interpret mode
cannot (Mosaic lowering refusals, scoped-VMEM overruns, programs that do
not fit the device) before any chip time is spent.

The topology is described inside a module fixture — never at import —
because only one process at a time may load the TPU library, and every
test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.comm import channel as comm_channel
from repro.core import netes, topology_repr
from repro.core.netes import NetESConfig
from repro.core.topology import TopologySpec
from repro.envs import resolve_task
from repro.kernels import netes_fused_mixing as nfm

PENDULUM_DIM = 4481         # paper MLP 3→64→64→1


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                       sharding=sharding), tree)


@pytest.mark.parametrize("n", [1000, 16384])
def test_fused_broadcast_select_compiles_for_v5e(one_chip, n):
    d = PENDULUM_DIM
    args = _abstract((jnp.zeros((d,), jnp.int8),
                      jnp.zeros((1,), jnp.float32),
                      jnp.zeros((), jnp.bool_),
                      jnp.zeros((n, d), jnp.float32)), one_chip)
    compiled = nfm.fused_broadcast_select.lower(
        *args, interpret=False, backend="pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_netes_run_compiles_for_v5e(one_chip, monkeypatch, request):
    """The jitted training scan at the paper's pendulum configuration
    (N=1000, ER p=0.1, sparse, quantize(bits=8)), with the kernel
    dispatch steered to the choice it makes on a TPU."""
    monkeypatch.setattr(nfm, "_on_tpu", lambda: True)
    # No trace made with the CPU's kernel choice is reused here, and none
    # made with the TPU's outlives this test on the worker.
    jax.clear_caches()
    request.addfinalizer(jax.clear_caches)
    n = 1000
    reward_fn, dim, init_fn, _, _ = resolve_task("pendulum")
    assert dim == PENDULUM_DIM
    spec = TopologySpec(family="erdos_renyi", n_agents=n, p=0.1, seed=0)
    chan = comm_channel.compile_channel("quantize(bits=8)", n)
    topo = topology_repr.from_spec(spec, representation="auto")
    assert topo.kind == "sparse"
    state = netes.init_state(jax.random.PRNGKey(0), n, dim,
                             init_fn=init_fn)
    cstate = chan.init(state.thetas)
    args = _abstract((state, topo, cstate), one_chip)
    compiled = jax.jit(
        lambda s, t, c: netes.run(s, t, reward_fn, NetESConfig(), 4,
                                  chan, c)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
