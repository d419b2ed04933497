"""Scheduled-topology contract (ISSUE 3 / DESIGN.md §9): every schedule
kind matches the dense reference step-by-step, the whole schedule runs in
ONE compiled scan (no per-resample retrace), and a checkpointed run
resumes mid-schedule bit-for-bit."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import netes, topology, topology_repr, topology_sched
from repro.core.netes import NetESConfig
from repro.core.topology import TopologySpec
from repro.core.topology_sched import ScheduleSpec
from repro.envs import make_landscape_reward_fn
from repro.obs import count_backend_compiles


# ---------------------------------------------------------------------------
# spec parsing / validation
# ---------------------------------------------------------------------------

def test_schedule_spec_parse():
    assert ScheduleSpec.parse("static") == ScheduleSpec()
    assert ScheduleSpec.parse("resample_er(period=8)") == ScheduleSpec(
        kind="resample_er", period=8)
    assert ScheduleSpec.parse("rotate_circulant(stride=3)") == ScheduleSpec(
        kind="rotate_circulant", stride=3)
    spec = ScheduleSpec.parse("anneal_density(p_end=0.05, horizon=100)")
    assert spec.p_end == pytest.approx(0.05) and spec.horizon == 100
    with pytest.raises(ValueError):
        ScheduleSpec.parse("resample_er(8)")        # not key=value
    with pytest.raises(ValueError):
        ScheduleSpec.parse("warp_drive(period=2)")  # unknown kind
    with pytest.raises(ValueError):
        ScheduleSpec(kind="anneal_density")         # missing p_end/horizon
    with pytest.raises(ValueError):
        ScheduleSpec(kind="resample_er", period=0)


def test_compile_schedule_validation():
    base = TopologySpec(family="erdos_renyi", n_agents=16, p=0.3, seed=0)
    # rotating needs an exactly-circulant base
    with pytest.raises(ValueError):
        topology_sched.compile_schedule(
            ScheduleSpec(kind="rotate_circulant"), base)
    # ... and rejects offsets at n/2 (±d would collide under rotation)
    with pytest.raises(ValueError):
        topology_sched.compile_schedule(
            ScheduleSpec(kind="rotate_circulant"),
            TopologySpec(family="fully_connected", n_agents=8))
    # redraw schedules cannot keep a circulant payload
    with pytest.raises(ValueError):
        topology_sched.compile_schedule(
            ScheduleSpec(kind="resample_er", period=2), base, "circulant")
    # auto on a circulant base maps to sparse for redraw schedules
    circ = TopologySpec(family="circulant_erdos_renyi", n_agents=64,
                       p=0.05, seed=0)
    sched = topology_sched.compile_schedule(
        ScheduleSpec(kind="resample_er", period=2), circ, "auto")
    assert sched.representation == "sparse"


# ---------------------------------------------------------------------------
# per-step parity with the dense reference (the acceptance contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,stride", [(12, 1), (13, 2), (16, 5)])
def test_rotate_circulant_matches_dense_reference_every_step(n, stride):
    """rotate_circulant ≡ dense reference mixing at every step: advance
    the schedule T steps; at each t the traced-shift roll chain must
    reproduce the dense masked contraction of the host-rebuilt rotated
    graph, and to_dense() must equal that graph exactly."""
    base = TopologySpec(family="ring", n_agents=n, seed=0)
    sched = topology_sched.compile_schedule(
        ScheduleSpec(kind="rotate_circulant", stride=stride), base)
    state = sched.init()
    m = (n - 1) // 2
    offs0 = list(sched.base_offsets)
    rng = np.random.default_rng(5)
    advance = jax.jit(sched.advance)
    cfg = NetESConfig()
    for t in range(m + 3):          # cover > one full rotation cycle
        offs_t = [(d - 1 + t * stride) % m + 1 for d in offs0]
        dense = topology.circulant_from_offsets(n, offs_t)
        np.testing.assert_array_equal(np.asarray(state.topo.to_dense()),
                                      dense, err_msg=f"t={t}")
        th = jnp.asarray(rng.normal(size=(n, 5)), jnp.float32)
        pe = jnp.asarray(rng.normal(size=(n, 5)), jnp.float32)
        sh = jnp.asarray(rng.normal(size=n), jnp.float32)
        ref = netes.mixing_update(jnp.asarray(dense), th, pe, sh, cfg)
        out = netes.mixing_update(state.topo, th, pe, sh, cfg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5, err_msg=f"t={t}")
        assert int(state.t) == t
        state = advance(state)


@pytest.mark.parametrize("representation", ["dense", "sparse"])
def test_resample_er_redraws_on_period_and_stays_valid(representation):
    n, period = 32, 3
    base = TopologySpec(family="erdos_renyi", n_agents=n, p=0.2, seed=4)
    sched = topology_sched.compile_schedule(
        ScheduleSpec(kind="resample_er", period=period, seed=9), base,
        representation)
    state = sched.init()
    advance = jax.jit(sched.advance)
    prev = np.asarray(state.topo.to_dense())
    # t=0 is the host-built (connectivity-repaired) base graph
    np.testing.assert_array_equal(prev, np.asarray(base.build()))
    for t in range(1, 2 * period + 2):
        state = advance(state)
        cur = np.asarray(state.topo.to_dense())
        if t % period == 0:
            assert not np.array_equal(cur, prev), f"no redraw at t={t}"
        else:
            np.testing.assert_array_equal(cur, prev,
                                          err_msg=f"changed off-period t={t}")
        # every graph is symmetric with self-loops, degrees consistent
        np.testing.assert_array_equal(cur, cur.T)
        np.testing.assert_array_equal(np.diag(cur), np.ones(n))
        np.testing.assert_allclose(np.asarray(state.topo.deg),
                                   cur.sum(axis=1), rtol=1e-6)
        prev = cur


@pytest.mark.parametrize("representation", ["dense", "sparse"])
def test_anneal_density_is_nested_and_reaches_p_end(representation):
    n, horizon = 48, 6
    base = TopologySpec(family="erdos_renyi", n_agents=n, p=0.4, seed=1)
    sched = topology_sched.compile_schedule(
        ScheduleSpec(kind="anneal_density", p_end=0.02, horizon=horizon,
                     seed=3), base, representation)
    state = sched.init()
    advance = jax.jit(sched.advance)
    prev = np.asarray(state.topo.to_dense())
    for t in range(1, horizon + 2):
        state = advance(state)
        cur = np.asarray(state.topo.to_dense())
        # annealing DOWN re-thresholds one fixed uniform draw: edge sets
        # are nested (monotone non-increasing)
        assert ((prev - cur) >= -1e-6).all(), f"edge appeared at t={t}"
        prev = cur
    # past the horizon the graph is frozen at p_end
    state2 = advance(state)
    np.testing.assert_array_equal(np.asarray(state2.topo.to_dense()), prev)
    off_density = (prev.sum() - n) / (n * (n - 1))
    assert off_density < 0.1    # ≪ the 0.4 start, near p_end


def test_sparse_refresh_pad_and_truncation_semantics():
    """refresh_sparse re-pads to the EXISTING static k_max; deg counts the
    KEPT edges when a row overflows the pad (vanishing-probability event
    the schedule sizes against)."""
    n = 16
    adj = np.asarray(topology.erdos_renyi(n, p=0.3, seed=2))
    topo = topology_repr.from_dense(adj, "sparse")
    dense_star = np.asarray(topology.star(n))      # row 0 has degree n
    out = topology_repr.refresh_sparse(topo, jnp.asarray(dense_star))
    assert out.k_max == topo.k_max                 # shape is invariant
    np.testing.assert_allclose(np.asarray(out.deg),
                               np.asarray(out.neighbor_mask).sum(axis=1))
    # non-overflowing refresh is exact
    adj2 = np.asarray(topology.erdos_renyi(n, p=0.2, seed=7))
    out2 = topology_repr.refresh_sparse(topo, jnp.asarray(adj2))
    np.testing.assert_array_equal(np.asarray(out2.to_dense()), adj2)


# ---------------------------------------------------------------------------
# one-scan / no-retrace property + scan-vs-step equivalence
# ---------------------------------------------------------------------------

def test_scheduled_run_is_one_scan_no_retrace():
    """After a warm-up run, replaying the SAME-shape scheduled scan
    (spanning several resample events) triggers ZERO new XLA
    compilations — the on-device schedule never retraces per graph."""
    n = 16
    rf = make_landscape_reward_fn("sphere")
    cfg = NetESConfig(p_broadcast=0.5)
    base = TopologySpec(family="erdos_renyi", n_agents=n, p=0.2, seed=0)
    sched = topology_sched.compile_schedule(
        ScheduleSpec(kind="resample_er", period=2, seed=1), base, "sparse")
    s0 = netes.init_state(jax.random.PRNGKey(0), n, 6)
    state, sstate, _ = netes.run_scheduled(s0, sched.init(), rf, cfg,
                                           sched, num_iters=8)
    jax.block_until_ready(state.thetas)
    with count_backend_compiles() as counts:
        state, sstate, _ = netes.run_scheduled(s0, sched.init(), rf, cfg,
                                               sched, num_iters=8)
        jax.block_until_ready(state.thetas)
    assert counts == [], f"scheduled scan recompiled {len(counts)}×"


def test_run_scheduled_equals_stepwise_loop():
    """The fused scan and the per-step jitted path produce the same
    trajectory AND the same schedule state (resample draws included)."""
    n = 16
    rf = make_landscape_reward_fn("rastrigin")
    cfg = NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.5)
    base = TopologySpec(family="erdos_renyi", n_agents=n, p=0.25, seed=2)
    sched = topology_sched.compile_schedule(
        ScheduleSpec(kind="resample_er", period=3, seed=5), base, "dense")
    s0 = netes.init_state(jax.random.PRNGKey(1), n, 8)
    s_scan, ss_scan, _ = netes.run_scheduled(s0, sched.init(), rf, cfg,
                                             sched, num_iters=7)
    s_step, ss_step = s0, sched.init()
    for _ in range(7):
        s_step, ss_step, _ = netes.scheduled_step(s_step, ss_step, rf, cfg,
                                                  sched)
    np.testing.assert_allclose(np.asarray(s_scan.thetas),
                               np.asarray(s_step.thetas),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(ss_scan.topo.adj),
                                  np.asarray(ss_step.topo.adj))
    assert int(ss_scan.t) == int(ss_step.t) == 7


def test_scheduled_rl_run_matches_manual_static_rebuild():
    """End-to-end: a rotate_circulant scheduled netes run ≡ a manual loop
    that rebuilds the rotated DENSE graph host-side every iteration."""
    n, stride, iters = 12, 2, 6
    rf = make_landscape_reward_fn("sphere")
    cfg = NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.5)
    base = TopologySpec(family="ring", n_agents=n, seed=0)
    sched = topology_sched.compile_schedule(
        ScheduleSpec(kind="rotate_circulant", stride=stride), base)
    s0 = netes.init_state(jax.random.PRNGKey(3), n, 6)
    s_sched, _, _ = netes.run_scheduled(s0, sched.init(), rf, cfg, sched,
                                        num_iters=iters)
    m = (n - 1) // 2
    offs0 = list(sched.base_offsets)
    s_ref = s0
    for t in range(iters):
        offs_t = [(d - 1 + t * stride) % m + 1 for d in offs0]
        dense = jnp.asarray(topology.circulant_from_offsets(n, offs_t))
        s_ref, _ = netes.netes_step(s_ref, dense, rf, cfg)
    np.testing.assert_allclose(np.asarray(s_sched.thetas),
                               np.asarray(s_ref.thetas),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# checkpoint / resume mid-schedule
# ---------------------------------------------------------------------------

def test_resume_mid_schedule_reproduces_uninterrupted_eval_trace(tmp_path):
    """Interrupt a scheduled run at an eval point, resume from the
    checkpoint: the post-resume eval trace is bit-for-bit identical to
    the uninterrupted run's."""
    from repro.train.loop import TrainConfig, train_rl_netes
    tc = TrainConfig(
        n_agents=16, iters=16,
        topology=TopologySpec(family="erdos_renyi", n_agents=16, p=0.2,
                              seed=1),
        representation="sparse", schedule="resample_er(period=4)",
        seed=0, eval_every=4, eval_episodes=2,
        netes=NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.5))
    h_full = train_rl_netes("landscape:sphere", tc)
    ckpt = str(tmp_path / "ckpt")
    h_half = train_rl_netes("landscape:sphere", dataclasses.replace(
        tc, iters=8, checkpoint_dir=ckpt))
    h_res = train_rl_netes("landscape:sphere", dataclasses.replace(
        tc, checkpoint_dir=ckpt))
    assert h_half["eval"] == h_full["eval"][:2]
    assert h_res["eval_iter"] == h_full["eval_iter"][2:]
    assert h_res["eval"] == h_full["eval"][2:]       # bit-for-bit
