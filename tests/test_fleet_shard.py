"""Sharded fleet engine (DESIGN.md §13): Eq. 3 exactness of the halo /
dense / full contraction paths on one device, host-side plan byte
accounting, and — in a subprocess with 8 forced host devices — the
shard-invariance contract: same seed ⇒ bit-identical trajectories and
identical realized traffic counters for mesh sizes {1, 2, 8}, plus a
checkpoint saved on an 8-way mesh restoring bit-for-bit against the
single-device oracle."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import channel as comm_channel
from repro.core import netes, topology, topology_repr
from repro.core.netes import NetESConfig
from repro.distributed import fleet_shard

N, D = 19, 4
CFG = NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.0)


def _reward(params, key):
    return -(params * params).sum(axis=-1)


def _sparse_topo(n=N, p=0.3, seed=2):
    return topology_repr.from_dense(
        topology.erdos_renyi(n, p=p, seed=seed), "sparse")


def _expected_one_step(topo, state, cfg):
    """Pure-numpy Eq. 3 oracle with ``core.netes``'s noise layout: ε is
    one ``normal(k_eps, (N, D))`` (p_broadcast=0 keeps the broadcast
    overwrite out of the picture)."""
    th = np.asarray(state.thetas)
    n, d = th.shape
    _, k_eps, k_eval, _ = jax.random.split(state.key, 4)
    eps = np.asarray(jax.random.normal(k_eps, (n, d), dtype=jnp.float32))
    pert_pos = th + cfg.sigma * eps
    pert_neg = th - cfg.sigma * eps
    r_pos = np.asarray(_reward(jnp.asarray(pert_pos), k_eval))
    r_neg = np.asarray(_reward(jnp.asarray(pert_neg), k_eval))
    raw = np.concatenate([r_pos, r_neg])
    shaped_all = np.asarray(netes.shape_fitness(jnp.asarray(raw),
                                                cfg.fitness_shaping))
    shaped = shaped_all[:n] - shaped_all[n:]
    adj = np.asarray(topo.to_dense()) if hasattr(topo, "to_dense") \
        else np.ones((n, n), np.float32)
    mixed = (adj * shaped[None, :]) @ pert_pos
    wsum = adj @ shaped
    update = cfg.alpha / (n * cfg.sigma ** 2) * \
        (mixed - wsum[:, None] * th)
    if cfg.weight_decay:
        update = update - cfg.weight_decay * th
    return th + update


@pytest.mark.parametrize("rep", ["sparse", "dense"])
def test_solo_step_matches_numpy_eq3(rep):
    topo = topology_repr.from_dense(
        topology.erdos_renyi(N, p=0.3, seed=2), rep)
    state0 = netes.init_state(jax.random.PRNGKey(0), N, D)
    eng = fleet_shard.ShardedNetES(topo, _reward, CFG)
    st, _ = eng.run(state0, 1)
    np.testing.assert_allclose(np.asarray(st.thetas),
                               _expected_one_step(topo, state0, CFG),
                               rtol=2e-5, atol=1e-6)


def test_full_marker_matches_dense_all_ones():
    """The FullyConnected rank-1 path == a dense all-ones adjacency
    (numerically; the contraction orders differ)."""
    state0 = netes.init_state(jax.random.PRNGKey(1), N, D)
    ones = topology_repr.Topology(
        kind="dense", n=N, deg=jnp.full((N,), float(N)),
        adj=jnp.ones((N, N), jnp.float32))
    st_fc, _ = fleet_shard.ShardedNetES(
        fleet_shard.FullyConnected(N), _reward, CFG).run(state0, 3)
    st_dn, _ = fleet_shard.ShardedNetES(ones, _reward, CFG).run(state0, 3)
    np.testing.assert_allclose(np.asarray(st_fc.thetas),
                               np.asarray(st_dn.thetas),
                               rtol=2e-5, atol=1e-6)


def test_plan_modes_and_byte_ordering():
    """Host-side plan accounting: circulant halo < ER halo < FC gather
    payload rows at 8 shards — the locality physics the paper's
    communication argument rests on."""
    n = 256
    er = topology_repr.from_dense(
        topology.erdos_renyi(n, p=0.05, seed=1), "sparse")
    circ = topology_repr.from_dense(
        topology.circulant_from_offsets(n, [1, 2, 3]), "circulant")
    p_er = fleet_shard.make_comm_plan(er, 8)
    p_circ = fleet_shard.make_comm_plan(circ, 8)
    p_fc = fleet_shard.make_comm_plan(fleet_shard.FullyConnected(n), 8)
    assert p_er.mode == "halo" and p_circ.mode == "halo"
    assert p_fc.mode == "full"
    assert 0 < p_circ.payload_rows < p_er.payload_rows < p_fc.payload_rows
    # stateful stages force the replicated fallback
    ev = comm_channel.compile_channel("event_triggered(threshold=0.01)", n)
    assert fleet_shard.make_comm_plan(er, 8, channel=ev).mode == \
        "replicated"


def test_collective_bytes_are_exact_ints():
    eng = fleet_shard.ShardedNetES(_sparse_topo(), _reward, CFG)
    b = eng.collective_bytes(D)
    assert all(isinstance(v, int) for v in b.values())
    assert b["total_bytes"] == (b["payload_bytes"] + b["reward_bytes"]
                                + b["broadcast_bytes"])
    # wire codec narrows payload rows from 4D to D+4 bytes
    q8 = comm_channel.compile_channel("quantize(bits=8)", N)
    eng_q = fleet_shard.ShardedNetES(_sparse_topo(), _reward, CFG,
                                     channel=q8)
    assert eng_q.collective_bytes(D)["payload_bytes"] <= \
        b["payload_bytes"]


def test_train_loop_shards_smoke():
    from repro.core.topology import TopologySpec
    from repro.train.loop import TrainConfig, train_rl_netes
    tc = TrainConfig(
        n_agents=8, iters=4,
        topology=TopologySpec(family="erdos_renyi", n_agents=8, p=0.4,
                              seed=0),
        seed=0, eval_every=2, eval_episodes=1, shards=1,
        netes=NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.5))
    h = train_rl_netes("landscape:sphere", tc)
    assert len(h["reward_mean"]) == 4


def test_build_span_records_the_collective_bytes(tmp_path):
    """With ``shards`` set, the JSONL trace's ``build`` span carries the
    engine's per-shard collective bytes; without it, none."""
    from repro.core.topology import TopologySpec
    from repro.envs import LANDSCAPE_DIM
    from repro.obs.trace import read_trace
    from repro.train.loop import (TrainConfig, build_channel,
                                  build_topology, train_rl_netes)

    def build_attrs(shards, name):
        tc = TrainConfig(
            n_agents=8, iters=2, topology=TopologySpec(
                family="erdos_renyi", n_agents=8, p=0.4, seed=0),
            representation="sparse", channel="quantize(bits=8)",
            shards=shards, seed=0, eval_every=2, eval_episodes=1,
            trace=str(tmp_path / name),
            netes=NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.5))
        train_rl_netes("landscape:sphere", tc)
        spans = [r for r in read_trace(tmp_path / name)
                 if r["kind"] == "span" and r["name"] == "build"]
        assert len(spans) == 1
        return tc, spans[0].get("attrs", {})

    tc, attrs = build_attrs(1, "sharded.jsonl")
    want = fleet_shard.ShardedNetES(
        build_topology(tc), _reward, tc.netes,
        channel=build_channel(tc)).collective_bytes(LANDSCAPE_DIM)
    assert attrs == want
    assert attrs["total_bytes"] == (attrs["payload_bytes"]
                                    + attrs["reward_bytes"]
                                    + attrs["broadcast_bytes"])
    assert build_attrs(None, "solo.jsonl")[1] == {}


def test_env_reward_rowwise_keys_follow_the_agent():
    """``rowwise`` on a slice of the population with that slice's keys
    gives the slice of the whole population's rewards — what lets a
    shard evaluate its agents without changing their episodes."""
    from repro.envs import resolve_task
    reward_fn, dim, init_fn, _, _ = resolve_task("pendulum")
    params = jax.vmap(init_fn)(jax.random.split(jax.random.PRNGKey(0), 6))
    key = jax.random.PRNGKey(3)
    whole = np.asarray(reward_fn(params, key))
    keys = jax.random.split(key, 8)[:6]   # split(k, n_pad)[:N] prefix
    part = np.asarray(reward_fn.rowwise(params[2:5], keys[2:5]))
    np.testing.assert_array_equal(part, whole[2:5])


def test_checkpoint_roundtrip_solo(tmp_path):
    from repro.checkpoint import io
    state0 = netes.init_state(jax.random.PRNGKey(3), N, D)
    eng = fleet_shard.ShardedNetES(_sparse_topo(), _reward, CFG)
    st, _ = eng.run(state0, 2)
    io.save_pytree(tmp_path / "st.npz", st)
    back = io.load_pytree(tmp_path / "st.npz", st)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(back), strict=True):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# the multi-device contract, in a subprocess (8 forced host devices)
# ---------------------------------------------------------------------------

_SUBPROCESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import tempfile

import numpy as np

import jax
import jax.numpy as jnp

from repro.checkpoint import io
from repro.comm import channel as comm_channel
from repro.core import netes, topology, topology_repr, topology_sched
from repro.core.netes import NetESConfig
from repro.core.topology import TopologySpec
from repro.distributed import fleet_shard

N, D, ITERS = 257, 16, 5
cfg = NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.5)
state0 = netes.init_state(jax.random.PRNGKey(0), N, D)


def reward_fn(params, key):
    return -(params * params - jnp.cos(2 * jnp.pi * params)).sum(axis=-1)


adj = topology.erdos_renyi(N, p=0.05, seed=3)
legs = {
    "dense": (topology_repr.from_dense(adj, "dense"), None),
    "sparse": (topology_repr.from_dense(adj, "sparse"), None),
    "circulant": (topology_repr.from_dense(
        topology.circulant_from_offsets(N, [1, 2, 5]), "circulant"),
        None),
    "fc": (fleet_shard.FullyConnected(N), None),
    "sparse_q8": (topology_repr.from_dense(adj, "sparse"),
                  comm_channel.compile_channel("quantize(bits=8)", N)),
    # event trigger + dropout are stateful -> replicated fallback mode
    "sparse_event": (topology_repr.from_dense(adj, "sparse"),
                     comm_channel.compile_channel(
                         "event_triggered(threshold=0.01)|"
                         "quantize(bits=8)|dropout(p=0.1,seed=0)", N)),
}

for name, (topo, chan) in legs.items():
    outs = {}
    for ndev in (None, 1, 2, 8):
        mesh = None if ndev is None else fleet_shard.build_mesh(ndev)
        eng = fleet_shard.ShardedNetES(topo, reward_fn, cfg, mesh=mesh,
                                       channel=chan)
        cs = chan.init(state0.thetas) if chan is not None else None
        res = eng.run(state0, ITERS, chan_state=cs)
        st, ms = res[0], res[-1]
        outs[ndev] = (jax.device_get((st.thetas, st.best_theta,
                                      st.best_reward, st.key)),
                      jax.device_get(ms.get("msgs")),
                      jax.device_get(ms["reward_mean"]))
    ref_arrs, ref_msgs, ref_rm = outs[None]
    for ndev in (1, 2, 8):
        arrs, msgs, rm = outs[ndev]
        for a, b in zip(arrs, ref_arrs):
            assert np.array_equal(np.asarray(a), np.asarray(b)), \
                (name, ndev, "state")
        assert np.array_equal(np.asarray(rm), np.asarray(ref_rm)), \
            (name, ndev, "reward_mean")
        if ref_msgs is not None:
            # realized traffic counters are placement-invariant
            assert np.array_equal(np.asarray(msgs),
                                  np.asarray(ref_msgs)), \
                (name, ndev, "msgs")

# an env reward draws each agent's episode from that agent's key, which
# must follow the agent to whichever shard holds it
from repro.envs import resolve_task
env_reward, env_dim, env_init, _, _ = resolve_task("pendulum")
env_state0 = netes.init_state(jax.random.PRNGKey(1), 24, env_dim,
                              init_fn=env_init)
env_topo = topology_repr.from_dense(
    topology.erdos_renyi(24, p=0.3, seed=1), "sparse")
env_ref = None
for ndev in (None, 8):
    mesh = None if ndev is None else fleet_shard.build_mesh(ndev)
    st = fleet_shard.ShardedNetES(env_topo, env_reward, cfg,
                                  mesh=mesh).run(env_state0, 2)[0]
    th = np.asarray(jax.device_get(st.thetas))
    if env_ref is None:
        env_ref = th
    else:
        assert np.array_equal(th, env_ref), ("env reward", ndev)

# scheduled topology (replicated mode): mesh sizes agree with solo
sched = topology_sched.compile_schedule(
    topology_sched.ScheduleSpec(kind="resample_er", period=2),
    TopologySpec(family="erdos_renyi", n_agents=N, p=0.05, seed=3),
    representation="sparse")
ref = None
for ndev in (None, 1, 8):
    mesh = None if ndev is None else fleet_shard.build_mesh(ndev)
    res = fleet_shard.run_sharded_scheduled(
        state0, sched.init(), reward_fn, cfg, sched, ITERS, mesh)
    th = np.asarray(jax.device_get(res[0].thetas))
    if ref is None:
        ref = th
    else:
        assert np.array_equal(th, ref), ("scheduled", ndev)

# checkpoint: saved from an 8-way mesh, restored on one device,
# bit-for-bit equal to the solo trajectory's state (and back again)
topo = legs["sparse"][0]
solo_st = fleet_shard.ShardedNetES(topo, reward_fn, cfg).run(
    state0, ITERS)[0]
mesh_st = fleet_shard.ShardedNetES(
    topo, reward_fn, cfg, mesh=fleet_shard.build_mesh(8)).run(
    state0, ITERS)[0]
with tempfile.TemporaryDirectory() as tmp:
    io.save_pytree(tmp + "/mesh.npz", mesh_st)
    restored = io.load_pytree(tmp + "/mesh.npz", solo_st)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(solo_st)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), "ckpt 8->1"
    io.save_pytree(tmp + "/solo.npz", solo_st)
    restored2 = io.load_pytree(tmp + "/solo.npz", mesh_st)
    for a, b in zip(jax.tree.leaves(restored2),
                    jax.tree.leaves(mesh_st)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), "ckpt 1->8"

print("FLEET_SHARD_MESH_OK")
"""


def test_shard_invariance_on_8_forced_devices():
    """Meshes {1, 2, 8} reproduce the solo oracle bit-for-bit — state,
    metrics, traffic counters — for every plan mode, and checkpoints
    round-trip across shard layouts."""
    res = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             **{k: v for k, v in __import__("os").environ.items()
                if k not in ("XLA_FLAGS",)}})
    assert "FLEET_SHARD_MESH_OK" in res.stdout, \
        (res.stdout[-2000:], res.stderr[-4000:])


# ---------------------------------------------------------------------------
# the sharded engine against the single-device engine, and its noise
# draw per shard (4 forced host devices, one subprocess)
# ---------------------------------------------------------------------------

_FOUR_DEVICE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import json
import re

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import channel as comm_channel
from repro.core import netes, topology, topology_repr
from repro.core.netes import NetESConfig
from repro.core.topology import TopologySpec
from repro.distributed import fleet_shard
from repro.obs import count_backend_compiles
from repro.train.loop import TrainConfig, train_rl_netes

out = {}

# train_rl_netes(shards=4) against shards=None (core.netes): the first
# chunk of 3 iterations, read where the loop calls netes.run
N = 64
LEGS = (("landscape:sphere", "quantize(bits=8)"), ("landscape:sphere", None),
        ("pendulum", "quantize(bits=8)"))
first = {}
orig = netes.run


def run(*args, **kwargs):
    res = orig(*args, **kwargs)
    first.setdefault(kwargs.get("mesh") is not None, res)
    return res


netes.run = run
for task, chan in LEGS:
    first.clear()
    for shards in (None, 4):
        tc = TrainConfig(
            n_agents=N, iters=3, topology=TopologySpec(
                family="erdos_renyi", n_agents=N, p=0.1, seed=0),
            representation="sparse", channel=chan, shards=shards, seed=5,
            eval_every=3, eval_episodes=1,
            netes=NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.8))
        train_rl_netes(task, tc)
    leg = {}
    for sharded, res in first.items():
        m = jax.device_get(res[-1])
        leg[str(sharded)] = {
            "reward_mean": np.asarray(m["reward_mean"]).tolist(),
            "update_var": np.asarray(m["update_var"]).tolist(),
            "broadcast": np.asarray(m["broadcast"]).tolist(),
            "thetas": np.asarray(jax.device_get(res[0].thetas)).tolist()}
    out[f"{task} {chan}"] = leg
netes.run = orig

# after the first evaluation point nothing is built again: the chunks
# and the evaluations keep their placements from then on
compiles = []
seen = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, duration, **kw: compiles.append(len(seen))
    if event == "/jax/core/compile/backend_compile_duration" else None)
tc = TrainConfig(
    n_agents=N, iters=12, topology=TopologySpec(
        family="erdos_renyi", n_agents=N, p=0.1, seed=0),
    representation="sparse", channel="quantize(bits=8)", shards=4, seed=6,
    eval_every=3, eval_episodes=1, netes=NetESConfig())
train_rl_netes("pendulum", tc, log=lambda entry: seen.append(entry))
out["compiles_after_first_eval"] = sum(1 for k in compiles if k > 0)
out["eval_points"] = len(seen)

# the compiled sharded run: the unsigned words of the threefry stream
# come in (n_loc, D) slabs, never at the population's (n_pad, D)
n, d = 64, 24
topo = topology_repr.from_dense(topology.erdos_renyi(n, p=0.1, seed=0),
                                "sparse")
eng = fleet_shard.ShardedNetES(
    topo, lambda p, k: -(p * p).sum(axis=-1), NetESConfig(),
    mesh=fleet_shard.build_mesh(4),
    channel=comm_channel.compile_channel("quantize(bits=8)", n))
state = netes.init_state(jax.random.PRNGKey(0), n, d)
cs = eng.channel.init(state.thetas)
text = eng._run_impl.lower(
    state.thetas, state.key, state.step, state.best_reward,
    state.best_theta, eng._operands, (cs,), (), (),
    num_iters=2).compile().as_text()
out["u32_shapes"] = sorted(set(re.findall(r"u32\[(\d+),(\d+)\]", text)))
out["n_loc"] = eng.plan.n_loc

# each exchange on the 4-device mesh: its payload bytes per shard and
# step, and what a second run of the built engine compiles
circ = topology_repr.from_dense(
    topology.circulant_from_offsets(n, [1, 2, 3, 4]), "circulant")
q8 = comm_channel.compile_channel("quantize(bits=8)", n)
out["payload_bytes"], out["replay_compiles"] = {}, {}
for name, t, ch in (("circulant", circ, None), ("er_sparse_q8", topo, q8),
                    ("er_sparse", topo, None),
                    ("fc", fleet_shard.FullyConnected(n), None)):
    eng = fleet_shard.ShardedNetES(
        t, lambda p, k: -(p * p).sum(axis=-1), NetESConfig(),
        mesh=fleet_shard.build_mesh(4), channel=ch)
    out["payload_bytes"][name] = eng.collective_bytes(d)["payload_bytes"]
    cs = ch.init(state.thetas) if ch is not None else None
    jax.block_until_ready(eng.run(state, 2, chan_state=cs))
    with count_backend_compiles() as replay:
        jax.block_until_ready(eng.run(state, 2, chan_state=cs))
    out["replay_compiles"][name] = len(replay)
print("FOUR_DEVICES " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_devices():
    res = subprocess.run(
        [sys.executable, "-c", _FOUR_DEVICE_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             **{k: v for k, v in __import__("os").environ.items()
                if k not in ("XLA_FLAGS",)}})
    lines = [ln for ln in res.stdout.splitlines()
             if ln.startswith("FOUR_DEVICES ")]
    assert lines, (res.stdout[-2000:], res.stderr[-4000:])
    import json
    return json.loads(lines[-1][len("FOUR_DEVICES "):])


@pytest.mark.parametrize("channel", ["quantize(bits=8)", None])
def test_sharded_training_matches_the_single_device_engine(four_devices,
                                                           channel):
    """``train_rl_netes(shards=4)`` draws the single-device engine's
    noise and broadcasts: after 3 iterations on a smooth landscape the
    mean return, the update variance and θ equal ``shards=None`` to
    float32 rounding, over the int8 channel (as the x4 cell runs) and
    without one."""
    leg = four_devices[f"landscape:sphere {channel}"]
    solo, sharded = leg["False"], leg["True"]
    assert sharded["broadcast"] == solo["broadcast"]
    for k in ("reward_mean", "update_var", "thetas"):
        np.testing.assert_allclose(np.asarray(sharded[k]),
                                   np.asarray(solo[k]), rtol=5e-6,
                                   atol=1e-7, err_msg=k)


def test_sharded_pendulum_episodes_match_the_single_device_engine(
        four_devices):
    """The pendulum over the int8 channel (the x4 cell's task): the
    first iteration's mean return and update variance equal
    ``shards=None`` to float32 rounding, and so do the broadcast draws
    of the chunk. Later iterations are not compared: an episode's
    return jumps with the last bit of the policy (perfbench/compare.py),
    so one ulp of a reduction order may move the second iteration."""
    leg = four_devices["pendulum quantize(bits=8)"]
    solo, sharded = leg["False"], leg["True"]
    assert sharded["broadcast"] == solo["broadcast"]
    for k in ("reward_mean", "update_var"):
        np.testing.assert_allclose(sharded[k][0], solo[k][0], rtol=5e-6,
                                   err_msg=k)


def test_sharded_training_compiles_nothing_after_its_first_eval(
        four_devices):
    """Chunks 2-4 and their evaluations reuse the programs the first
    chunk and evaluation built: the placements stay put."""
    assert four_devices["eval_points"] == 4
    assert four_devices["compiles_after_first_eval"] == 0


def test_each_shard_draws_only_its_own_noise_rows(four_devices):
    """The compiled 4-shard run generates the threefry words of its
    (n_pad, D) = (64, 24) noise as (16, 24) slabs, one per device, and
    never the whole draw."""
    shapes = {tuple(int(x) for x in s) for s in four_devices["u32_shapes"]}
    n_loc = four_devices["n_loc"]
    assert n_loc == 16
    assert (n_loc, 24) in shapes
    assert (64, 24) not in shapes, shapes


@pytest.mark.parametrize("cheaper,dearer", [("circulant", "er_sparse_q8"),
                                            ("er_sparse_q8", "er_sparse"),
                                            ("er_sparse", "fc")])
def test_sharded_payload_bytes_follow_the_graph(four_devices, cheaper,
                                                dearer):
    """On a 4-device mesh a ring of 4 offsets moves fewer payload bytes
    than the int8 ER halo, the int8 codec shrinks the f32 ER halo, and
    the ER halo undercuts the fully-connected gather."""
    b = four_devices["payload_bytes"]
    assert 0 < b[cheaper] < b[dearer], b


@pytest.mark.parametrize("exchange", ["circulant", "er_sparse_q8",
                                      "er_sparse", "fc"])
def test_sharded_replay_compiles_nothing(four_devices, exchange):
    """A built engine's second run on the 4-device mesh replays the
    program its first run compiled, for every exchange: halo rounds of
    a ring or of ER neighbors, int8 or f32, and the full gather."""
    assert four_devices["replay_compiles"][exchange] == 0
