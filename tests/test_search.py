"""Topology-search subsystem (repro/search, DESIGN.md §10): batched
tournament parity, successive-halving determinism, stacking, resume."""
import dataclasses
import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import netes, topology, topology_repr
from repro.core.netes import NetESConfig
from repro.core.topology import TopologySpec
from repro.core.topology_sched import ScheduleSpec
from repro.envs import make_landscape_reward_fn
from repro.obs import count_backend_compiles
from repro.search import (CandidateSpec, SearchConfig, make_grid,
                          prior_scores, run_search, seed_pool)
from repro.search.tournament import (_eval_score, _make_plans,
                                     _round_scheduled, _round_static)
from repro.train.loop import TrainConfig, search_topology

CFG = NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.8)


def _tree_stack(items):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *items)


def _tree_index(tree, i):
    return jax.tree.map(lambda x: x[i], tree)


# ---------------------------------------------------------------------------
# topology_repr.stack / unstack
# ---------------------------------------------------------------------------

def test_stack_unstack_dense_roundtrip():
    topos = [topology_repr.from_dense(topology.erdos_renyi(12, p=0.4,
                                                           seed=s), "dense")
             for s in range(3)]
    stacked = topology_repr.stack(topos)
    assert stacked.adj.shape == (3, 12, 12)
    assert stacked.deg.shape == (3, 12)
    for orig, back in zip(topos, topology_repr.unstack(stacked), strict=True):
        assert np.array_equal(orig.adj, back.adj)
        assert np.array_equal(orig.deg, back.deg)


def test_stack_sparse_shared_kmax_preserves_graph():
    adjs = [topology.erdos_renyi(16, p=p, seed=s)
            for p, s in [(0.1, 0), (0.3, 1), (0.2, 2)]]
    topos = [topology_repr.from_dense(a, "sparse") for a in adjs]
    k_shared = max(t.k_max for t in topos)
    stacked = topology_repr.stack(topos)
    assert stacked.neighbor_idx.shape == (3, 16, k_shared)
    for adj, back in zip(adjs, topology_repr.unstack(stacked), strict=True):
        assert back.k_max == k_shared
        assert np.array_equal(np.asarray(back.to_dense()), adj)
    # explicit k_max floor widens further
    wider = topology_repr.stack(topos, k_max=k_shared + 3)
    assert wider.neighbor_idx.shape[-1] == k_shared + 3


def test_stack_rejects_mixed_kinds_and_sizes():
    d = topology_repr.from_dense(topology.erdos_renyi(8, p=0.5), "dense")
    s = topology_repr.from_dense(topology.erdos_renyi(8, p=0.2), "sparse")
    with pytest.raises(ValueError):
        topology_repr.stack([d, s])
    d2 = topology_repr.from_dense(topology.erdos_renyi(9, p=0.5), "dense")
    with pytest.raises(ValueError):
        topology_repr.stack([d, d2])
    with pytest.raises(ValueError):
        topology_repr.stack([])
    with pytest.raises(ValueError):
        topology_repr.widen_sparse(s, s.k_max - 1)


def test_stack_circulant_static_offsets_must_match():
    a = topology_repr.from_dense(
        topology.circulant_from_offsets(12, [1, 3]), "circulant")
    b = topology_repr.from_dense(
        topology.circulant_from_offsets(12, [1, 4]), "circulant")
    stacked = topology_repr.stack([a, a])
    assert stacked.offsets == (1, 3) and stacked.deg.shape == (2, 12)
    with pytest.raises(ValueError):
        topology_repr.stack([a, b])


# ---------------------------------------------------------------------------
# batched-tournament parity: vmapped S-candidate rounds are bit-identical
# to S independent netes.run calls (the tentpole's core invariant)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rep", ["dense", "sparse"])
def test_vmapped_round_parity(rep):
    n, dim, iters, episodes = 16, 8, 6, 2
    reward_fn = make_landscape_reward_fn("rastrigin@2.5")
    topos = [topology_repr.from_dense(
        topology.erdos_renyi(n, p=p, seed=s), rep)
        for p, s in [(0.15, 0), (0.3, 1), (0.5, 2)]]
    if rep == "sparse":
        k = max(t.k_max for t in topos)
        topos = [topology_repr.widen_sparse(t, k) for t in topos]
    keys = jax.random.split(jax.random.PRNGKey(7), len(topos))
    states = [netes.init_state(k, n, dim) for k in keys]
    ekeys = jax.random.split(jax.random.PRNGKey(99), len(topos))

    new_states, scores = _round_static(
        _tree_stack(states), topology_repr.stack(topos),
        jnp.stack(ekeys), reward_fn=reward_fn, cfg=CFG,
        num_iters=iters, eval_episodes=episodes)

    for i, (state, topo, ek) in enumerate(zip(states, topos, ekeys, strict=True)):
        ref_state, _m = netes.run(state, topo, reward_fn, CFG, iters)
        ref_score = _eval_score(ref_state, ek, reward_fn, episodes)
        got = _tree_index(new_states, i)
        for a, b in zip(jax.tree.leaves(ref_state), jax.tree.leaves(got), strict=True):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.array_equal(np.asarray(ref_score),
                              np.asarray(scores[i]))


def test_vmapped_scheduled_round_parity():
    """Scheduled cohorts share ONE static schedule object (base seed is
    init-only); the batched run must equal per-candidate run_scheduled
    with each candidate's own compiled schedule."""
    n, dim, iters = 12, 6, 5
    reward_fn = make_landscape_reward_fn("sphere")
    pool = [CandidateSpec(
        topo=TopologySpec(family="erdos_renyi", n_agents=n, p=0.25,
                          seed=s),
        sched=ScheduleSpec(kind="resample_er", period=2, seed=3))
        for s in (0, 1)]
    plans = _make_plans(pool, "auto")
    assert plans[0].cohort == plans[1].cohort
    assert plans[0].schedule.k_max == plans[1].schedule.k_max
    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    states = [netes.init_state(k, n, dim) for k in keys]
    sstates = [p.schedule.init() for p in plans]
    ekeys = jax.random.split(jax.random.PRNGKey(17), 2)

    new_states, new_ss, scores = _round_scheduled(
        _tree_stack(states), _tree_stack(sstates), jnp.stack(ekeys),
        reward_fn=reward_fn, cfg=CFG, schedule=plans[0].schedule,
        num_iters=iters, eval_episodes=1)

    for i in range(2):
        ref_state, ref_ss, _m = netes.run_scheduled(
            states[i], sstates[i], reward_fn, CFG, plans[i].schedule,
            iters)
        for a, b in zip(jax.tree.leaves((ref_state, ref_ss)),
                        jax.tree.leaves((_tree_index(new_states, i),
                                         _tree_index(new_ss, i))), strict=True):
            assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# candidates: grid, theory-prior seeding
# ---------------------------------------------------------------------------

def test_grid_controls_and_schedule_compat():
    grid = make_grid(16, ("erdos_renyi", "fully_connected", "ring"),
                     densities=(0.1, 0.3), seeds=(0, 1),
                     schedules=(None, "rotate_circulant(stride=1)"))
    labels = [c.label() for c in grid]
    assert labels.count("fully_connected") == 1      # controls collapse
    # rotate_circulant only pairs with circulant families (ring)
    assert "ring+rotate_circulant" in labels
    assert not any("erdos_renyi" in l and "rotate" in l for l in labels)


def test_seed_pool_prior_order_keeps_control():
    grid = make_grid(64, ("erdos_renyi", "fully_connected"),
                     densities=(0.05, 0.1, 0.3, 0.5), seeds=(0,))
    pool = seed_pool(grid, pool_size=3)
    fams = [c.topo.family for c in pool]
    assert "fully_connected" in fams                 # forced control
    ers = [c for c in pool if c.topo.family == "erdos_renyi"]
    # theory prior ranks sparser ER first (higher ρ̂, lower γ̂)
    assert ers and ers[0].topo.p == 0.05
    scores = prior_scores(grid)
    assert scores.shape == (len(grid),)
    assert np.all(np.isfinite(scores))


# ---------------------------------------------------------------------------
# the tournament driver: determinism, halving, resume, integration
# ---------------------------------------------------------------------------

_SC = SearchConfig(
    n_agents=16, families=("erdos_renyi", "fully_connected"),
    densities=(0.1, 0.4), seeds=(0,), pool_size=4, round_iters=4,
    eval_episodes=1, seed=0, netes=CFG)


# A mixed-family pool: ER, small-world and the fully-connected control
# over three densities and two graph seeds.
_MIXED_SC = dataclasses.replace(
    _SC, n_agents=32, families=("erdos_renyi", "small_world",
                                "fully_connected"),
    densities=(0.05, 0.1, 0.2), seeds=(0, 1), pool_size=5, round_iters=6)


@pytest.mark.parametrize("sc", [_SC, _MIXED_SC], ids=["er_fc", "mixed"])
def test_successive_halving_deterministic_and_shrinking(sc):
    """A warmed tournament replays its history exactly and compiles
    nothing: each round is one program per cohort shape."""
    r1 = run_search("landscape:rastrigin@2.5", sc)
    with count_backend_compiles() as compiles:
        r2 = run_search("landscape:rastrigin@2.5", sc)
    assert compiles == []
    assert r1.history == r2.history
    assert r1.winner == r2.winner and r1.score == r2.score
    sizes = [len(h["scores"]) for h in r1.history]
    assert sizes == sorted(sizes, reverse=True)
    assert len(r1.history[-1]["survivors"]) == 1
    # budget widening: each round doubles per-candidate iterations
    iters = [h["iters"] for h in r1.history]
    assert all(b == 2 * a for a, b in zip(iters, iters[1:], strict=False))
    # every candidate carries a label in round 0; winner is among pool
    assert r1.winner in r1.pool
    assert "fully_connected" in r1.control_scores


def test_search_includes_scheduled_candidates():
    sc = dataclasses.replace(
        _SC, schedules=(None, "resample_er(period=2)"), pool_size=5)
    r1 = run_search("landscape:sphere", sc)
    labels = [c.label() for c in r1.pool]
    assert any("resample_er" in l for l in labels)
    r2 = run_search("landscape:sphere", sc)
    assert r1.history == r2.history


def test_search_resume_matches_uninterrupted(tmp_path):
    full_dir = tmp_path / "full"
    sc = dataclasses.replace(_SC, checkpoint_dir=str(full_dir))
    full = run_search("landscape:rastrigin@2.5", sc)
    assert (full_dir / "latest.json").exists()

    # simulate a crash after round 0: point latest.json at round 0 and
    # rerun — the tournament must resume and reproduce the full result.
    resume_dir = tmp_path / "resume"
    shutil.copytree(full_dir, resume_dir)
    meta0 = json.loads((resume_dir / "step_00000000.json").read_text())
    (resume_dir / "latest.json").write_text(json.dumps(meta0))
    resumed = run_search(
        "landscape:rastrigin@2.5",
        dataclasses.replace(sc, checkpoint_dir=str(resume_dir)))
    assert resumed.history == full.history
    assert resumed.winner == full.winner and resumed.score == full.score


def test_search_resume_rejects_mismatched_config(tmp_path):
    sc = dataclasses.replace(_SC, checkpoint_dir=str(tmp_path))
    run_search("landscape:rastrigin@2.5", sc)
    with pytest.raises(ValueError, match="different search"):
        run_search("landscape:sphere", sc)          # different task
    with pytest.raises(ValueError, match="different search"):
        run_search("landscape:rastrigin@2.5",       # different config
                   dataclasses.replace(sc, round_iters=8))


def test_search_topology_and_from_search_result():
    spec = search_topology("landscape:rastrigin@2.5", _SC)
    assert isinstance(spec, TopologySpec)
    result = run_search("landscape:rastrigin@2.5", _SC)
    assert spec == result.topology
    tc = TrainConfig.from_search_result(result, iters=3, seed=1)
    assert tc.topology == result.topology
    assert tc.n_agents == _SC.n_agents and tc.iters == 3
    # the winning config trains end-to-end
    from repro.train.loop import train_rl_netes
    hist = train_rl_netes("landscape:rastrigin@2.5", tc)
    assert hist["final_eval"] is not None


def test_single_candidate_pool_still_scores():
    sc = dataclasses.replace(_SC, families=("erdos_renyi",),
                             densities=(0.2,), pool_size=1)
    r = run_search("landscape:sphere", sc)
    assert len(r.pool) == 1 and len(r.history) == 1
    assert r.winner == r.pool[0] and np.isfinite(r.score)
