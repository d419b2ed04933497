"""Representation-dispatch parity: sparse and circulant mixing must match
the dense `mixing_update` reference for every registered topology family,
including non-power-of-2 populations (the refactor's correctness
contract — ISSUE 1 / DESIGN.md §3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import netes, topology, topology_repr
from repro.core.netes import NetESConfig
from repro.kernels import ref as ref_kernels

SIZES = [8, 64, 257]
FAMILIES = topology.available_families()
RNG = np.random.default_rng(7)


def _adj(family, n):
    kw = {}
    if family not in ("fully_connected", "disconnected", "star", "ring"):
        kw["p"] = 0.2
    return topology.make_topology(family, n, seed=3, **kw)


def _mixing_inputs(n, dim=6):
    th = jnp.asarray(RNG.normal(size=(n, dim)), jnp.float32)
    pe = jnp.asarray(RNG.normal(size=(n, dim)), jnp.float32)
    sh = jnp.asarray(RNG.normal(size=n), jnp.float32)
    return th, pe, sh


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("normalization", ["global", "degree"])
def test_sparse_matches_dense_mixing(family, n, normalization):
    adj = _adj(family, n)
    th, pe, sh = _mixing_inputs(n)
    cfg = NetESConfig(normalization=normalization)
    ref = netes.mixing_update(jnp.asarray(adj), th, pe, sh, cfg)
    topo = topology_repr.from_dense(adj, "sparse")
    out = netes.mixing_update(topo, th, pe, sh, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("family", ["circulant_erdos_renyi", "ring",
                                    "disconnected", "fully_connected"])
@pytest.mark.parametrize("n", SIZES)
def test_circulant_matches_dense_mixing(family, n):
    """Every circulant-representable family (incl. FC = all offsets and
    disconnected = no offsets) through the roll-chain backend."""
    adj = _adj(family, n)
    assert topology.circulant_offsets(adj) is not None
    th, pe, sh = _mixing_inputs(n)
    cfg = NetESConfig()
    ref = netes.mixing_update(jnp.asarray(adj), th, pe, sh, cfg)
    topo = topology_repr.from_dense(adj, "circulant")
    out = netes.mixing_update(topo, th, pe, sh, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("family", FAMILIES)
def test_auto_representation_is_parity_preserving(family):
    """`select_representation` may pick any backend — the update must not
    change."""
    n = 64
    adj = _adj(family, n)
    th, pe, sh = _mixing_inputs(n)
    cfg = NetESConfig()
    ref = netes.mixing_update(jnp.asarray(adj), th, pe, sh, cfg)
    topo = topology_repr.from_dense(adj, "auto")
    out = netes.mixing_update(topo, th, pe, sh, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Eq. 3 through mixing_update against the jnp oracles of kernels/ref.py
# ---------------------------------------------------------------------------

SIGMA = 0.1


def _oracle_inputs(n, dim):
    """θ, ε and shaped rewards, plus the perturbed θ + σε the live path
    takes."""
    th = jnp.asarray(RNG.normal(size=(n, dim)), jnp.float32)
    ep = jnp.asarray(RNG.normal(size=(n, dim)), jnp.float32)
    sh = jnp.asarray(RNG.normal(size=n), jnp.float32)
    return th, ep, sh, th + SIGMA * ep


def _unit_scale_update(topo, th, pert, sh):
    """mixing_update with α = Nσ², so its 1/(Nσ²) scale is 1 and the
    update is the bare Eq. 3 contraction the oracles compute."""
    n = th.shape[0]
    cfg = NetESConfig(alpha=n * SIGMA ** 2, sigma=SIGMA)
    return netes.mixing_update(topo, th, pert, sh, cfg)


def _oracle_graph(rep, n, p):
    """A symmetric graph with self-loops the representation can hold:
    a scattered one for dense/sparse, a circulant-ER ring otherwise."""
    if rep == "circulant":
        return topology.circulant_erdos_renyi(n, p=p, seed=n)
    adj = (RNG.random((n, n)) < p).astype(np.float32)
    adj = np.maximum(adj, adj.T)
    np.fill_diagonal(adj, 1.0)
    return adj


@pytest.mark.parametrize("n,dim", [(8, 64), (16, 700), (32, 1024), (5, 33)])
@pytest.mark.parametrize("rep", ["dense", "circulant"])
def test_dense_mixing_matches_oracle(n, dim, rep):
    adj = _oracle_graph(rep, n, 0.5)
    th, ep, sh, pert = _oracle_inputs(n, dim)
    out = _unit_scale_update(topology_repr.from_dense(adj, rep), th, pert,
                             sh)
    want = ref_kernels.netes_mixing_ref(jnp.asarray(adj), sh, sh, th, ep,
                                        sigma=SIGMA)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n,dim,p", [(8, 64, 0.3), (16, 700, 0.1),
                                     (32, 1024, 0.2), (5, 33, 0.5)])
@pytest.mark.parametrize("rep", ["sparse", "circulant"])
def test_sparse_mixing_matches_oracle(n, dim, p, rep):
    adj = _oracle_graph(rep, n, p)
    lists = topology_repr.from_dense(adj, "sparse")
    th, ep, sh, pert = _oracle_inputs(n, dim)
    out = _unit_scale_update(topology_repr.from_dense(adj, rep), th, pert,
                             sh)
    want = ref_kernels.sparse_mixing_ref(lists.neighbor_idx,
                                         lists.neighbor_mask, sh, sh, th,
                                         ep, sigma=SIGMA)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_sparse_mixing_equals_dense_at_width():
    """The neighbor-list path restricted to the graph's edges equals the
    dense path on the same graph, at a width of several lanes."""
    n, dim = 16, 384
    adj = _oracle_graph("sparse", n, 0.25)
    th, _, sh, pert = _oracle_inputs(n, dim)
    out_s = _unit_scale_update(topology_repr.from_dense(adj, "sparse"),
                               th, pert, sh)
    out_d = _unit_scale_update(topology_repr.from_dense(adj, "dense"),
                               th, pert, sh)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_d),
                               rtol=2e-5, atol=2e-5)


def test_sparse_preserves_edge_weights():
    """Non-binary adjacencies survive the neighbor-list representation
    (neighbor_mask carries a_ji, not a 0/1 mask) — incl. negative
    weights."""
    n = 16
    rng = np.random.default_rng(4)
    adj = topology.erdos_renyi(n, p=0.4, seed=4)
    weights = rng.uniform(0.5, 2.0, size=(n, n)).astype(np.float32)
    weights[rng.random((n, n)) < 0.2] *= -1.0
    weighted = (adj * np.maximum(weights, weights.T)).astype(np.float32)
    th, pe, sh = _mixing_inputs(n)
    cfg = NetESConfig()
    ref = netes.mixing_update(jnp.asarray(weighted), th, pe, sh, cfg)
    topo = topology_repr.from_dense(weighted, "sparse")
    out = netes.mixing_update(topo, th, pe, sh, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(topo.to_dense()), weighted,
                               rtol=1e-6, atol=1e-6)


def test_non_exact_circulants_are_rejected():
    """Directed or self-loop-free rings match circulant_offsets' row-
    rotation test but NOT the roll-chain backend's semantics — they must
    not be selected or buildable as circulant."""
    n = 8
    idx = np.arange(n)
    directed = np.zeros((n, n), np.float32)
    directed[idx, (idx + 1) % n] = 1.0           # directed ring
    no_self = np.zeros((n, n), np.float32)
    no_self[idx, (idx + 1) % n] = 1.0            # symmetric ring,
    no_self[(idx + 1) % n, idx] = 1.0            # zero diagonal
    for bad in (directed, no_self):
        assert topology_repr.select_representation(bad) != "circulant"
        with pytest.raises(ValueError):
            topology_repr.from_dense(bad, "circulant")
        # auto still produces a parity-preserving representation
        th, pe, sh = _mixing_inputs(n)
        cfg = NetESConfig()
        ref = netes.mixing_update(jnp.asarray(bad), th, pe, sh, cfg)
        out = netes.mixing_update(topology_repr.from_dense(bad, "auto"),
                                  th, pe, sh, cfg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_select_representation_heuristic():
    # sparse regime: ER at p ≪ 1 with max degree under the cutoff
    adj = topology.erdos_renyi(256, p=0.05, seed=0)
    assert topology_repr.select_representation(adj) == "sparse"
    # vertex-transitive ring family with few offsets → circulant
    adj = topology.circulant_erdos_renyi(256, p=0.05, seed=0)
    assert topology_repr.select_representation(adj) == "circulant"
    # dense regime: FC is circulant in form but gains nothing from it
    adj = topology.fully_connected(64)
    assert topology_repr.select_representation(adj) == "dense"
    adj = topology.erdos_renyi(64, p=0.8, seed=0)
    assert topology_repr.select_representation(adj) == "dense"


def test_topology_pytree_roundtrip_and_to_dense():
    adj = topology.erdos_renyi(33, p=0.2, seed=5)
    for representation in ("dense", "sparse"):
        topo = topology_repr.from_dense(adj, representation)
        leaves, treedef = jax.tree_util.tree_flatten(topo)
        rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
        assert rebuilt.kind == topo.kind and rebuilt.n == topo.n
        np.testing.assert_array_equal(np.asarray(topo.to_dense()), adj)
    circ = topology.circulant_erdos_renyi(32, p=0.3, seed=5)
    topo = topology_repr.from_dense(circ, "circulant")
    np.testing.assert_array_equal(np.asarray(topo.to_dense()), circ)


@pytest.mark.parametrize("n", SIZES)
def test_circulant_offsets_roundtrip_identity(n):
    """circulant_from_offsets ∘ circulant_offsets == id on circulant
    graphs (incl. non-power-of-2 N)."""
    adj = topology.circulant_erdos_renyi(n, p=0.3, seed=11)
    offs = topology.circulant_offsets(adj)
    assert offs is not None
    rebuilt = topology.circulant_from_offsets(n, offs)
    np.testing.assert_array_equal(rebuilt, adj)
    # and the offset list itself round-trips through the rebuilt graph
    assert topology.circulant_offsets(rebuilt) == offs


def test_netes_step_accepts_topology_and_matches_dense():
    """End-to-end: netes_step with a sparse Topology == raw dense adj."""
    from repro.envs import make_landscape_reward_fn
    n = 16
    adj = topology.erdos_renyi(n, p=0.3, seed=2)
    rf = make_landscape_reward_fn("sphere")
    cfg = NetESConfig(p_broadcast=0.0)
    s0 = netes.init_state(jax.random.PRNGKey(0), n, 5)
    ref, _ = netes.netes_step(s0, jnp.asarray(adj), rf, cfg)
    out, _ = netes.netes_step(
        s0, topology_repr.from_dense(adj, "sparse"), rf, cfg)
    np.testing.assert_allclose(np.asarray(out.thetas),
                               np.asarray(ref.thetas),
                               rtol=1e-5, atol=1e-6)


def test_replica_step_topology_dispatch_matches_dense():
    """Distributed replica step: sparse/circulant Topology produces the
    same update as the legacy dense-adjacency path."""
    import dataclasses as dc
    from repro.configs import get_config
    from repro.data import make_batch
    from repro.distributed import netes_dist
    from repro.models import transformer

    cfg = dc.replace(get_config("mistral-nemo-12b-smoke"),
                     name="nano-topo-repr", num_layers=1, d_model=64,
                     num_heads=2, num_kv_heads=2, head_dim=32, d_ff=128,
                     vocab_size=128)
    n = 8
    ncfg = NetESConfig(alpha=1e-3, sigma=0.01, p_broadcast=0.0,
                       weight_decay=0.0)
    key = jax.random.PRNGKey(0)
    p0 = transformer.init_params(key, cfg)
    params = jax.tree.map(
        lambda l: jnp.broadcast_to(l, (n,) + l.shape).copy(), p0)
    batch = make_batch(cfg, dict(seq_len=32, global_batch=n), key)
    batch = jax.tree.map(lambda x: x.reshape((n, 1) + x.shape[1:]), batch)

    adj = topology.circulant_erdos_renyi(n, p=0.3, seed=1)
    dense_step = jax.jit(netes_dist.make_replica_train_step(
        cfg, ncfg, n, microbatch=1))
    ref, _ = dense_step(params, jnp.asarray(adj), batch, key)
    for representation in ("sparse", "circulant"):
        topo = topology_repr.from_dense(adj, representation)
        step = jax.jit(netes_dist.make_replica_train_step(
            cfg, ncfg, n, microbatch=1, topology=topo))
        out, _ = step(params, jnp.asarray(adj), batch, key)
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(out), strict=True):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=2e-2, atol=2e-4, err_msg=representation)
