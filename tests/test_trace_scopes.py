"""Named scopes on the device and host spans on the profiler's clock
(DESIGN.md §15).

The NetES programs carry the layer names of ``netes_step`` (noise,
reward, shaping, channel, mixing, broadcast, stats), the scheduled
step's ``schedule`` and the evaluation's ``eval`` in their compiled op
metadata, where a device trace reads them; the training loop's host
phases reach the profiler as ``repro/<span>`` annotations even with no
JSONL trace file.
"""
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import netes, topology_repr
from repro.core.netes import NetESConfig
from repro.core.topology import TopologySpec

SCOPES = {"noise", "reward", "shaping", "channel", "mixing", "broadcast",
          "stats", "schedule", "eval"}
STEP = {"noise", "reward", "shaping", "mixing", "broadcast", "stats"}
N, D = 8, 16


def _reward(params, key):
    return -jnp.sum(params * params, axis=-1)


def _scopes(compiled) -> set:
    """Scope names among the components of the compiled program's
    ``op_name`` metadata."""
    names = set()
    for path in re.findall(r'op_name="([^"]*)"', compiled.as_text()):
        names.update(p for p in path.split("/") if p in SCOPES)
    return names


def _state():
    return netes.init_state(jax.random.PRNGKey(0), N, D)


def _topo():
    return topology_repr.from_spec(TopologySpec(
        family="erdos_renyi", n_agents=N, p=0.5, seed=0))


@pytest.mark.parametrize("channel", [None, "quantize(bits=8)"])
def test_run_carries_the_step_scopes(channel):
    from repro.comm.channel import compile_channel
    cfg = NetESConfig()
    state = _state()
    if channel is None:
        low = netes._run_jit.lower(state, _topo(), _reward, cfg, 2)
        want = STEP
    else:
        chan = compile_channel(channel, N)
        low = netes._run_jit.lower(state, _topo(), _reward, cfg, 2, chan,
                                   chan.init(state.thetas))
        want = STEP | {"channel"}
    assert _scopes(low.compile()) == want


def test_scheduled_step_carries_the_schedule_scope():
    from repro.core.topology_sched import ScheduleSpec, compile_schedule
    schedule = compile_schedule(
        ScheduleSpec(kind="resample_er", period=2),
        TopologySpec(family="erdos_renyi", n_agents=N, p=0.5, seed=0))
    low = netes.scheduled_step.lower(_state(), schedule.init(), _reward,
                                     NetESConfig(), schedule)
    assert _scopes(low.compile()) == STEP | {"schedule"}


def test_sharded_step_carries_the_step_scopes():
    from repro.distributed import fleet_shard
    mesh = fleet_shard.build_mesh(1)
    topo, cfg = _topo(), NetESConfig()

    def run(state):
        return fleet_shard.run_sharded(state, topo, _reward, cfg, 2, mesh)

    assert _scopes(jax.jit(run).lower(_state()).compile()) == STEP


def test_evaluate_best_carries_the_eval_scope():
    from repro.envs import resolve_task
    from repro.envs.rollout import evaluate_best
    _, dim, init_fn, env, policy = resolve_task("pendulum")

    def ev(theta, key):
        return evaluate_best(env, policy, theta, key, 2)

    theta = init_fn(jax.random.PRNGKey(0))
    low = jax.jit(ev).lower(theta, jax.random.PRNGKey(1))
    assert _scopes(low.compile()) == {"eval"}


@pytest.mark.parametrize("task", ["pendulum", "landscape:sphere"])
def test_jitted_evaluator_carries_the_eval_scope(task):
    """The training loop's evaluation program (``make_evaluator``): the
    key split and the score, episodes or landscape row, all under
    ``eval``."""
    from repro.envs import resolve_task
    from repro.envs.rollout import make_evaluator
    reward_fn, dim, init_fn, env, policy = resolve_task(task)
    evaluate = make_evaluator(env, policy, 2, reward_fn)
    theta = init_fn(jax.random.PRNGKey(0))
    low = evaluate.lower(theta, jax.random.PRNGKey(1))
    assert _scopes(low.compile()) == {"eval"}


def test_scopes_change_no_numbers():
    """The scoped step against the same step traced with every
    ``jax.named_scope`` made a no-op: identical trajectories."""
    import contextlib
    cfg = NetESConfig()
    scoped = netes.run(_state(), _topo(), _reward, cfg, 3)
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        plain = netes.run(_state(), _topo(), _reward, cfg, 3)
    jax.clear_caches()
    for a, b in zip(jax.tree.leaves(scoped), jax.tree.leaves(plain)):
        assert jnp.array_equal(a, b)


def test_loop_spans_reach_the_profiler(tmp_path):
    """A profiler trace of a training run with no JSONL file holds the
    loop's spans on its host plane; each evaluation ends before the
    next chunk starts."""
    from jax.profiler import ProfileData

    from repro.train.loop import TrainConfig, train_rl_netes
    tc = TrainConfig(n_agents=N, iters=4, eval_every=2, seed=0,
                     topology=TopologySpec(family="erdos_renyi",
                                           n_agents=N, p=0.5, seed=0))
    assert tc.trace is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        train_rl_netes("landscape:sphere", tc)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro/"):
                    spans.setdefault(e.name, []).append((e.start_ns,
                                                         e.end_ns))
    assert {"repro/build", "repro/chunk", "repro/eval",
            "repro/drain"} <= set(spans)
    assert len(spans["repro/build"]) == 1
    assert len(spans["repro/eval"]) == 2 and len(spans["repro/chunk"]) == 2
    chunks = sorted(spans["repro/chunk"])
    for ev_start, ev_end in sorted(spans["repro/eval"]):
        later = [s for s, _ in chunks if s > ev_start]
        assert not later or ev_end <= later[0]
