"""TrainConfig construction contract + train_rl_netes eval-protocol
bookkeeping, the evaluation program built once per run, and a warmed
run's replay building no training program for any representation,
schedule or quantized channel."""
import json

import jax
import numpy as np
import pytest
from _train_rerun import assert_one_scan_per_config, rerun_after_warmup

from repro.core.netes import NetESConfig
from repro.core.topology import TopologySpec
from repro.core.topology_sched import ScheduleSpec
from repro.train.loop import (TrainConfig, build_channel, build_schedule,
                              build_topology, train_rl_netes)


# ---------------------------------------------------------------------------
# TrainConfig.__post_init__: spec-vs-legacy precedence
# ---------------------------------------------------------------------------

def test_legacy_triplet_folds_into_spec():
    tc = TrainConfig(n_agents=24, topology_family="small_world",
                     density=0.3, topo_seed=5)
    assert tc.topology == TopologySpec(family="small_world", n_agents=24,
                                       p=0.3, seed=5)


def test_explicit_spec_wins_over_legacy_fields():
    spec = TopologySpec(family="ring", n_agents=12, p=0.7, seed=9)
    tc = TrainConfig(n_agents=999, topology_family="erdos_renyi",
                     density=0.123, topo_seed=42, topology=spec)
    # the spec is authoritative; the sugar fields are back-filled FROM it
    assert tc.topology is spec
    assert tc.n_agents == 12
    assert tc.topology_family == "ring"
    assert tc.density == pytest.approx(0.7)
    assert tc.topo_seed == 9


def test_schedule_string_sugar_parses():
    tc = TrainConfig(schedule="resample_er(period=8)")
    assert tc.schedule == ScheduleSpec(kind="resample_er", period=8)
    tc2 = TrainConfig(schedule=ScheduleSpec(kind="static"))
    assert tc2.schedule == ScheduleSpec(kind="static")
    assert TrainConfig().schedule is None


# ---------------------------------------------------------------------------
# eval-protocol tail bookkeeping
# ---------------------------------------------------------------------------

def _run(iters, eval_every, seed=0):
    tc = TrainConfig(
        n_agents=8, iters=iters,
        topology=TopologySpec(family="erdos_renyi", n_agents=8, p=0.4,
                              seed=0),
        seed=seed, eval_every=eval_every, eval_episodes=2,
        netes=NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.5))
    return train_rl_netes("landscape:sphere", tc)


@pytest.mark.parametrize("iters,eval_every", [(10, 3), (12, 4), (7, 10)])
def test_fixed_cadence_covers_every_iteration_once(iters, eval_every):
    h = _run(iters, eval_every)
    # every training iteration ran exactly once (chunks + tail, no
    # double-count, no drop)
    assert len(h["reward_mean"]) == iters
    assert len(h["reward_max"]) == iters
    # eval points: the cadence, plus a forced final-iteration eval
    expect = [it for it in range(eval_every - 1, iters, eval_every)]
    if iters - 1 not in expect:
        expect.append(iters - 1)
    assert h["eval_iter"] == expect
    assert len(h["eval"]) == len(expect)
    assert h["final_eval"] == h["eval"][-1]
    assert h["max_eval"] == max(h["eval"])


def test_paper_protocol_tail_bookkeeping():
    """eval_every=0 ⇒ random 8%-probability eval points; the last
    iteration is still always evaluated and the iteration count is
    exact."""
    h = _run(40, 0, seed=3)
    assert len(h["reward_mean"]) == 40
    assert h["eval_iter"] == sorted(set(h["eval_iter"]))
    assert h["eval_iter"][-1] == 39
    assert all(0 <= it < 40 for it in h["eval_iter"])


def test_zero_eval_history_fields():
    h = _run(0, 4)
    assert h["reward_mean"] == [] and h["eval"] == []
    assert h["final_eval"] is None and h["max_eval"] is None


def test_scheduled_run_counts_match_static():
    tc = TrainConfig(
        n_agents=8, iters=10,
        topology=TopologySpec(family="erdos_renyi", n_agents=8, p=0.4,
                              seed=0),
        schedule="resample_er(period=3)", seed=0, eval_every=4,
        eval_episodes=2,
        netes=NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.5))
    h = train_rl_netes("landscape:sphere", tc)
    assert len(h["reward_mean"]) == 10
    assert h["eval_iter"] == [3, 7, 9]
    assert np.isfinite(h["eval"]).all()


# ---------------------------------------------------------------------------
# the evaluation: one jitted program per run, the eager scores
# ---------------------------------------------------------------------------

def test_evaluation_compiles_once_and_matches_eager(tmp_path, monkeypatch):
    """Pendulum, 3 evaluation points: only the first point's ``eval``
    span compiles or loads a program (``xla_watch.Watch`` through the
    JSONL trace), and each score equals, bit for bit, eager
    ``evaluate_best`` on the best agent the loop evaluated and the key
    it derives from ``PRNGKey(seed + 999)``."""
    from repro.envs import resolve_task
    from repro.envs.rollout import evaluate_best
    from repro.train import loop

    thetas = []
    factory = loop.make_evaluator

    def recording_factory(*args):
        evaluate = factory(*args)

        def call(theta, eval_key):
            thetas.append(np.asarray(theta))
            return evaluate(theta, eval_key)
        return call

    monkeypatch.setattr(loop, "make_evaluator", recording_factory)
    seed, path = 5, tmp_path / "run.jsonl"
    tc = TrainConfig(
        n_agents=4, iters=6, eval_every=2, eval_episodes=2, seed=seed,
        topology=TopologySpec(family="erdos_renyi", n_agents=4, p=0.5,
                              seed=0),
        trace=str(path))
    h = train_rl_netes("pendulum", tc)
    assert h["eval_iter"] == [1, 3, 5] and len(thetas) == 3

    spans = [json.loads(line) for line in path.read_text().splitlines()]
    evals = [r for r in spans if r.get("name") == "eval"]
    assert len(evals) == 3
    first, *later = evals
    assert first["compiles"] + first["cache_loads"] >= 1
    assert all(r["compiles"] == 0 and r["cache_loads"] == 0 for r in later)

    _, _, _, env, policy = resolve_task("pendulum")
    key = jax.random.PRNGKey(seed + 999)
    eager = []
    for theta in thetas:
        key, k_eval = jax.random.split(key)
        eager.append(float(evaluate_best(env, policy, theta, k_eval, 2)))
    assert h["eval"] == eager


def test_build_span_records_the_reward_blocks(tmp_path):
    """A pendulum run's JSONL ``build`` span carries the agent blocks its
    reward runs in: one block of all N rows at a small N."""
    from repro.obs.trace import read_trace

    path = tmp_path / "run.jsonl"
    n = 6
    tc = TrainConfig(
        n_agents=n, iters=2, eval_every=2, eval_episodes=1, seed=0,
        topology=TopologySpec(family="erdos_renyi", n_agents=n, p=0.5,
                              seed=0),
        trace=str(path))
    train_rl_netes("pendulum", tc)
    builds = [r for r in read_trace(path)
              if r["kind"] == "span" and r["name"] == "build"]
    assert len(builds) == 1
    attrs = builds[0]["attrs"]
    assert (attrs["reward_blocks"], attrs["reward_block_rows"]) == (1, n)


# ---------------------------------------------------------------------------
# a warmed run replays without building a training program
# ---------------------------------------------------------------------------

N_FLEET, P_FLEET = 64, 0.1
TASK = "landscape:rastrigin"


def _fleet_tc(family, representation, iters=8, **extra):
    return TrainConfig(
        n_agents=N_FLEET, iters=iters,
        topology=TopologySpec(family=family, n_agents=N_FLEET, p=P_FLEET,
                              seed=0),
        representation=representation, seed=0, eval_every=iters // 2,
        eval_episodes=2,
        netes=NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.8), **extra)


@pytest.mark.parametrize("family,representation,extra", [
    ("erdos_renyi", "dense", {}),
    ("erdos_renyi", "sparse", {}),
    ("circulant_erdos_renyi", "circulant", {}),
    ("circulant_erdos_renyi", "circulant",
     {"schedule": "rotate_circulant(stride=1)"}),
    ("erdos_renyi", "sparse", {"schedule": "resample_er(period=2)"}),
    ("erdos_renyi", "dense",
     {"schedule": "anneal_density(p_end=0.02,horizon=8)"}),
    ("erdos_renyi", "sparse", {"channel": "quantize(bits=8)"}),
    ("erdos_renyi", "sparse", {"channel": "quantize(bits=4)"}),
    ("erdos_renyi", "sparse", {"channel": "quantize(bits=1)"}),
    ("erdos_renyi", "sparse", {"probes": "fitness|consensus|graph"}),
], ids=["dense", "sparse", "circulant", "rotate_circulant", "resample_er",
        "anneal_density", "q8", "q4", "q1", "probed"])
def test_warm_rerun_builds_no_training_program(family, representation,
                                               extra, tmp_path):
    """The run gets the representation it asked for (a schedule's first
    graph included, and the fused wire path for a quantized channel),
    and it builds one scan: no retrace per chunk, graph redraw, wire
    payload or probe ring, and none when the warmed config runs
    again."""
    tc = _fleet_tc(family, representation, **extra)
    sched = build_schedule(tc)
    topo = build_topology(tc) if sched is None else sched.init().topo
    assert topo.kind == representation
    if tc.channel is not None:
        assert build_channel(tc).wire_fused(topo)
    hist, warm, rerun = rerun_after_warmup(TASK, tc, tmp_path)
    assert_one_scan_per_config(warm, rerun)
    assert len(hist["eval"]) == 2 and np.isfinite(hist["eval"]).all()


def test_dense_and_sparse_training_agree():
    """The same ER graph and seeds through the dense and the neighbor-list
    representation follow the same training trajectory."""
    finals = [train_rl_netes(TASK, _fleet_tc("erdos_renyi", rep))
              for rep in ("dense", "sparse")]
    dense, sparse = (h["final_eval"] for h in finals)
    assert abs(dense - sparse) <= 1e-3 * max(1.0, abs(dense))
    np.testing.assert_allclose(finals[0]["reward_mean"],
                               finals[1]["reward_mean"], rtol=1e-4)
