"""Topology generation invariants (unit + hypothesis property tests)."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import topology

FAMILIES = ["erdos_renyi", "scale_free", "small_world", "fully_connected",
            "circulant_erdos_renyi", "ring", "star"]


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("n", [8, 16, 33])
def test_adjacency_invariants(family, n):
    adj = topology.make_topology(family, n, seed=3)
    assert adj.shape == (n, n)
    assert np.array_equal(adj, adj.T), "paper assumes symmetric A"
    assert np.all(np.diag(adj) == 1.0), "self-loops required (Eq.1 reduction)"
    assert set(np.unique(adj)) <= {0.0, 1.0}
    assert topology.is_connected(adj), "paper: single connected component"


def test_disconnected_is_identity():
    adj = topology.make_topology("disconnected", 12)
    assert np.array_equal(adj, np.eye(12, dtype=np.float32))


def test_fully_connected_is_ones():
    adj = topology.make_topology("fully_connected", 9)
    assert np.array_equal(adj, np.ones((9, 9), dtype=np.float32))


@settings(max_examples=25, deadline=None)
@given(n=st.integers(8, 40), p=st.floats(0.2, 0.9),
       seed=st.integers(0, 10_000))
def test_erdos_renyi_density_tracks_p(n, p, seed):
    adj = topology.erdos_renyi(n, p=p, seed=seed, connect=False)
    d = topology.density(adj)
    # binomial concentration: |d − p| within ~4σ of edge-count std
    n_edges = n * (n - 1) / 2
    tol = 4.0 * np.sqrt(p * (1 - p) / n_edges) + 0.02
    assert abs(d - p) < tol


@settings(max_examples=20, deadline=None)
@given(n=st.integers(8, 48), seed=st.integers(0, 100))
def test_seed_determinism(n, seed):
    a = topology.erdos_renyi(n, p=0.5, seed=seed)
    b = topology.erdos_renyi(n, p=0.5, seed=seed)
    assert np.array_equal(a, b)


def test_circulant_offsets_roundtrip():
    adj = topology.circulant_erdos_renyi(24, p=0.4, seed=7)
    offs = topology.circulant_offsets(adj)
    assert offs is not None
    rebuilt = topology.circulant_from_offsets(24, offs)
    assert np.array_equal(adj, rebuilt)
    # a general ER graph is (almost surely) not circulant
    er = topology.erdos_renyi(24, p=0.4, seed=7)
    assert topology.circulant_offsets(er) is None


def test_circulant_same_expected_density_as_er():
    ns, p = 64, 0.5
    dens = [topology.density(topology.circulant_erdos_renyi(ns, p=p, seed=s))
            for s in range(30)]
    assert abs(np.mean(dens) - p) < 0.08


@pytest.mark.parametrize("n,p", [(200, 0.4), (500, 0.5), (500, 0.8)])
def test_reachability_homogeneity_approximations(n, p):
    """Paper Fig 4 / Lemma 7.2: closed forms track measured statistics
    (large-n approximations — the paper evaluates them at n=1000)."""
    reach = np.mean([topology.reachability(
        topology.erdos_renyi(n, p=p, seed=s, connect=False))
        for s in range(3)])
    hom = np.mean([topology.homogeneity(
        topology.erdos_renyi(n, p=p, seed=s, connect=False))
        for s in range(3)])
    assert abs(reach - topology.reachability_approx(n, p)) / reach < 0.25
    assert abs(hom - topology.homogeneity_approx(n, p)) < 0.15


def test_fully_connected_extremizes_reach_and_homog():
    """Paper §7: FC minimizes reachability and maximizes homogeneity."""
    n = 60
    fc = topology.fully_connected(n)
    er = topology.erdos_renyi(n, p=0.3, seed=0)
    assert topology.reachability(fc) < topology.reachability(er)
    assert topology.homogeneity(fc) >= topology.homogeneity(er)
    assert topology.homogeneity(fc) == 1.0


def test_sparser_er_has_higher_reachability():
    """Paper Fig 5 premise: lower density ⇒ higher reachability."""
    n = 100
    r = [np.mean([topology.reachability(topology.erdos_renyi(n, p=p, seed=s))
                  for s in range(3)]) for p in (0.2, 0.5, 0.9)]
    assert r[0] > r[1] > r[2]


# ---------------------------------------------------------------------------
# degenerate inputs: the search grid sweeps these corners — classify,
# don't raise
# ---------------------------------------------------------------------------

def test_degenerate_graph_statistics_do_not_raise():
    empty = np.zeros((0, 0), np.float32)
    one = np.ones((1, 1), np.float32)
    assert topology.is_connected(empty) is True
    assert topology.is_connected(one) is True
    assert topology.circulant_offsets(empty) == []
    assert topology.circulant_offsets(one) == []
    assert topology.density(empty) == 0.0
    assert topology.density(one) == 0.0
    assert topology.reachability(empty) == 0.0
    assert topology.homogeneity(empty) == 1.0
    # a degree-0 node (no self-loop) gives infinite reachability, not a
    # ZeroDivisionError; an edgeless graph is vacuously homogeneous
    isolated = np.zeros((3, 3), np.float32)
    isolated[0, 0] = isolated[0, 1] = isolated[1, 0] = 1.0
    assert topology.reachability(isolated) == float("inf")
    assert topology.homogeneity(np.zeros((3, 3), np.float32)) == 1.0
    assert not topology.is_connected(topology.disconnected(4))


@pytest.mark.parametrize("family", FAMILIES + ["disconnected"])
def test_families_build_at_trivial_sizes(family):
    for n in (1, 2, 3):
        adj = topology.make_topology(family, n, seed=0)
        assert adj.shape == (n, n)
        assert np.all(np.diag(adj) == 1.0)
        assert topology.is_connected(adj) or family == "disconnected"


# ---------------------------------------------------------------------------
# theory priors (jax) match the numpy Lemma 7.2 closed forms
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(n=st.integers(50, 2000), p=st.floats(0.1, 1.0))
def test_prior_matches_numpy_approximations(n, p):
    from repro.core import theory
    rho = float(theory.reachability_prior(n, p))
    gam = float(theory.homogeneity_prior(n, p))
    kmin = p * (n - 1) - 2.0 * np.sqrt(p * (n - 1) * (1 - p))
    # the jnp prior floors k_min at 1 (the self-loop); the numpy closed
    # form is the paper's, unfloored (e.g. n=50, p=0.109 gives k_min<1)
    want_rho = (topology.reachability_approx(n, p) if kmin >= 1.0
                else float(np.sqrt(p * p * n ** 3)))
    assert rho == pytest.approx(want_rho, rel=1e-4)
    assert gam == pytest.approx(topology.homogeneity_approx(n, p),
                                rel=1e-4, abs=1e-5)
    # prior_score uses the paper's large-n simplification ρ̂ = 1/(p√n)
    # (p ≥ ln n / n here, so the connectivity clip is inactive)
    assert float(theory.prior_score(n, p)) == pytest.approx(
        1.0 / (p * np.sqrt(n)) - gam, rel=1e-4, abs=1e-5)


def test_prior_score_total_and_orders_sparser_higher():
    from repro.core import theory
    import jax.numpy as jnp
    # batched + degenerate densities stay finite and BOUNDED (clipped at
    # the ER connectivity threshold — p → 0 must not rank a near-empty
    # graph above every real candidate)
    ps = jnp.asarray([0.0, 1e-9, 0.05, 0.5, 1.0])
    scores = np.asarray(theory.prior_score(257, ps))
    assert np.all(np.isfinite(scores))
    assert scores[0] == scores[1] == pytest.approx(
        float(theory.prior_score(257, np.log(257) / 257)))
    # monotone: sparser ⇒ higher prior (paper Fig 5 ordering)
    sweep = np.asarray(theory.prior_score(
        257, jnp.asarray([0.05, 0.1, 0.3, 0.6, 1.0])))
    assert np.all(np.diff(sweep) < 0)
    # ... including at small n, where the full closed form's k_min floor
    # would invert the order (ρ̂_full(24, 0.2) > ρ̂_full(24, 0.1))
    s24 = [float(theory.prior_score(24, p)) for p in (0.1, 0.2, 0.5)]
    assert s24[0] > s24[1] > s24[2]


# ---------------------------------------------------------------------------
# representation selection is total over the family zoo (property test)
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(family=st.sampled_from(FAMILIES + ["disconnected"]),
       n=st.integers(1, 40), p=st.floats(0.05, 1.0),
       seed=st.integers(0, 1000))
def test_select_representation_total_and_faithful(family, n, p, seed):
    """Any generated graph admits its selected representation, and the
    representation reproduces the exact adjacency (search sweeps rely on
    both)."""
    from repro.core import topology_repr
    kwargs = {} if family in ("fully_connected", "disconnected", "star",
                              "ring") else {"p": p}
    adj = topology.make_topology(family, n, seed=seed, **kwargs)
    rep = topology_repr.select_representation(adj)
    assert rep in ("dense", "sparse", "circulant")
    topo = topology_repr.from_dense(adj, rep)
    assert np.array_equal(np.asarray(topo.to_dense()), adj)
    assert np.allclose(np.asarray(topo.deg), adj.sum(axis=1))
