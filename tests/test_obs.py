"""Observability layer (DESIGN.md §15): the non-negotiable invariant —
an instrumented run's trajectory is BIT-FOR-BIT identical to the
uninstrumented one's, across every representation × schedule × channel
combination — plus the ring-buffer mechanics, the zero-recompile replay
contract, checkpoint-resume of the metrics ring, the JSONL trace
schema, and (in an 8-forced-device subprocess) solo-vs-sharded probe
parity."""
import json
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import channel as comm_channel
from repro.core import netes, topology, topology_repr, topology_sched
from repro.core.netes import NetESConfig
from repro.core.topology import TopologySpec
from repro.obs import (Trace, count_backend_compiles, count_host_transfers,
                       validate_trace)
from repro.obs.probes import (MetricsState, ProbeSpec, Probes,
                              compile_probes)
from repro.obs.trace import SCHEMA, summarize

# N odd: rotate_circulant needs every offset of the circulant-ER base
# within [1, (N-1)//2], which N=13 guarantees (offsets max out at N//2).
N, D, ITERS = 13, 8, 6
CFG = NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.5)


def _reward(params, key):
    return -(params * params).sum(axis=-1)


def _state(seed=0):
    return netes.init_state(jax.random.PRNGKey(seed), N, D)


def _topo(rep):
    if rep == "circulant":
        return topology_repr.from_dense(
            topology.circulant_from_offsets(N, [1, 3]), "circulant")
    return topology_repr.from_dense(
        topology.erdos_renyi(N, p=0.4, seed=1), rep)


def _schedule(rep):
    if rep == "circulant":
        spec = topology_sched.ScheduleSpec(kind="rotate_circulant",
                                           stride=1)
        base = TopologySpec(family="circulant_erdos_renyi", n_agents=N,
                            p=0.4, seed=1)
    else:
        spec = topology_sched.ScheduleSpec(kind="resample_er", period=2)
        base = TopologySpec(family="erdos_renyi", n_agents=N, p=0.4,
                            seed=1)
    return topology_sched.compile_schedule(spec, base, representation=rep)


def _assert_states_equal(a, b, ctx=""):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(np.asarray(la), np.asarray(lb)), ctx


# ---------------------------------------------------------------------------
# spec / compile basics
# ---------------------------------------------------------------------------

def test_spec_parse_and_errors():
    assert ProbeSpec.parse("fitness|graph").stages == ("fitness", "graph")
    assert set(ProbeSpec.parse("all").stages) == {"fitness", "consensus",
                                                  "wire", "graph"}
    with pytest.raises(ValueError, match="unknown probe stage"):
        ProbeSpec.parse("fitness|bogus")
    with pytest.raises(ValueError, match="duplicate"):
        ProbeSpec(stages=("fitness", "fitness"))
    with pytest.raises(ValueError, match="at least one"):
        ProbeSpec(stages=())


def test_wire_stage_requires_channel():
    with pytest.raises(ValueError, match="needs a channel"):
        compile_probes("wire")
    chan = comm_channel.compile_channel("quantize(bits=8)", N)
    p = compile_probes("wire", channel=chan, dim=D)
    assert p.msg_bytes == float(chan.payload_bytes(D))


def test_probes_hashable_and_jit_static():
    p1 = compile_probes("fitness", capacity=8)
    p2 = compile_probes("fitness", capacity=8)
    assert hash(p1) == hash(p2) and p1 == p2  # engine-cache key material


# ---------------------------------------------------------------------------
# THE invariant: instrumented ≡ uninstrumented, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rep", ["dense", "sparse", "circulant"])
@pytest.mark.parametrize("scheduled", [False, True])
@pytest.mark.parametrize("chan_str", [None, "quantize(bits=8)"])
def test_probed_trajectory_bitwise_identical(rep, scheduled, chan_str):
    chan = comm_channel.compile_channel(chan_str, N) if chan_str else None
    stages = "fitness|consensus|graph" + ("|wire" if chan else "")
    probes = compile_probes(stages, capacity=ITERS, channel=chan, dim=D)

    def _run(with_probes):
        st = _state()
        kw = {}
        if chan is not None:
            kw.update(channel=chan, chan_state=chan.init(st.thetas))
        if with_probes:
            kw.update(probes=probes, metrics_state=probes.init())
        if scheduled:
            sched = _schedule(rep)
            out = netes.run_scheduled(st, sched.init(), _reward, CFG,
                                      sched, ITERS, **kw)
        else:
            out = netes.run(st, _topo(rep), _reward, CFG, ITERS, **kw)
        out = list(out)
        m = out.pop()
        ms = out.pop() if with_probes else None
        return out[0], m, ms

    st_plain, m_plain, _ = _run(False)
    st_probed, m_probed, ms = _run(True)
    _assert_states_equal(st_plain, st_probed, (rep, scheduled, chan_str))
    for k in m_plain:
        # the TRAJECTORY is gated bit-for-bit above; aggregate metrics
        # may move a final ULP because the probe column gives XLA an
        # extra consumer to fuse against (e.g. update_var), so they get
        # a tight allclose instead of exact equality.
        np.testing.assert_allclose(np.asarray(m_plain[k]),
                                   np.asarray(m_probed[k]),
                                   rtol=1e-6, atol=0, err_msg=k)
    # and the recorded series IS the metrics series, not a recompute
    series = probes.drain(ms)
    assert series["cursor"] == ITERS and series["dropped"] == 0
    np.testing.assert_array_equal(
        series["fitness_mean"], np.asarray(m_probed["reward_mean"],
                                           np.float32))
    if chan is not None:
        np.testing.assert_array_equal(
            series["msgs"], np.asarray(m_probed["msgs"], np.float32))
        np.testing.assert_array_equal(
            series["wire_bytes"],
            series["msgs"] * np.float32(probes.msg_bytes))


def test_probes_consume_no_rng():
    """Same key stream with and without probes: final NetESState.key is
    untouched by instrumentation (probes add no RNG edge)."""
    st0 = _state()
    topo = _topo("dense")
    p = compile_probes("fitness|consensus", capacity=ITERS)
    st_a, _ = netes.run(st0, topo, _reward, CFG, ITERS)
    st_b, _, _ = netes.run(st0, topo, _reward, CFG, ITERS,
                           probes=p, metrics_state=p.init())
    assert np.array_equal(np.asarray(st_a.key), np.asarray(st_b.key))


# ---------------------------------------------------------------------------
# zero-recompile replay
# ---------------------------------------------------------------------------

def test_probed_replay_compiles_nothing():
    """A warmed probed scan replays with ZERO XLA compilations — the
    MetricsState is ordinary carry, not a retrace source (and a fresh
    init() each run keeps shapes/dtypes identical)."""
    topo = _topo("sparse")
    p = compile_probes("fitness|consensus", capacity=ITERS)
    st0 = _state()
    netes.run(st0, topo, _reward, CFG, ITERS, probes=p,
              metrics_state=p.init())                      # warm-up
    with count_backend_compiles() as compiles:
        st, ms, m = netes.run(_state(7), topo, _reward, CFG, ITERS,
                              probes=p, metrics_state=p.init())
        jax.block_until_ready(st.thetas)
    assert len(compiles) == 0, f"probed replay recompiled {len(compiles)}×"
    assert int(jax.device_get(ms.cursor)) == ITERS


# ---------------------------------------------------------------------------
# ring mechanics
# ---------------------------------------------------------------------------

def test_ring_wraparound_keeps_last_capacity_samples():
    cap, iters = 4, 10
    topo = _topo("dense")
    p = compile_probes("fitness", capacity=cap)
    st, ms, m = netes.run(_state(), topo, _reward, CFG, iters,
                          probes=p, metrics_state=p.init())
    series = p.drain(ms)
    assert series["cursor"] == iters
    assert series["dropped"] == iters - cap
    # chronological order, LAST cap samples
    np.testing.assert_array_equal(
        series["fitness_mean"],
        np.asarray(m["reward_mean"], np.float32)[-cap:])
    np.testing.assert_array_equal(
        series["fitness_best"],
        np.asarray(m["reward_max"], np.float32)[-cap:])


def test_drain_is_one_host_transfer():
    p = compile_probes("fitness", capacity=8)
    _, ms, _ = netes.run(_state(), _topo("dense"), _reward, CFG, 3,
                         probes=p, metrics_state=p.init())
    with count_host_transfers() as transfers:
        p.drain(ms)
    assert len(transfers) == 1


def test_metrics_state_is_strong_typed_pytree():
    ms = compile_probes("fitness", capacity=4).init()
    assert isinstance(ms, MetricsState)
    assert ms.buf.dtype == jnp.float32 and not ms.buf.weak_type
    assert ms.cursor.dtype == jnp.int32 and not ms.cursor.weak_type


# ---------------------------------------------------------------------------
# checkpoint-resume reproduces the series bit-for-bit
# ---------------------------------------------------------------------------

def test_checkpoint_resume_reproduces_probe_series(tmp_path):
    from repro.train.loop import TrainConfig, train_rl_netes
    kw = dict(n_agents=8, iters=12, seed=5, probes="fitness|consensus",
              eval_every=4)
    full = train_rl_netes("landscape:sphere", TrainConfig(**kw))
    ck = str(tmp_path / "ck")
    import dataclasses
    t1 = dataclasses.replace(TrainConfig(checkpoint_dir=ck, **kw),
                             iters=8)
    train_rl_netes("landscape:sphere", t1)              # first leg
    res = train_rl_netes("landscape:sphere",
                         TrainConfig(checkpoint_dir=ck, **kw))
    assert res["probes"]["cursor"] == full["probes"]["cursor"] == 12
    for k in ("fitness_mean", "fitness_best", "consensus_dist",
              "update_var"):
        np.testing.assert_array_equal(full["probes"][k],
                                      res["probes"][k], err_msg=k)


# ---------------------------------------------------------------------------
# trace layer: schema, counters, no-op writer, CLI
# ---------------------------------------------------------------------------

def test_trace_schema_and_counters(tmp_path):
    path = tmp_path / "t.jsonl"
    with Trace(path, name="unit", extra_key=1) as tr:
        with tr.span("warmup"):
            jax.jit(lambda x: x * 2)(jnp.ones(3)).block_until_ready()
        with tr.span("drain"):
            jax.device_get(jnp.ones(3))
        tr.event("eval", score=1.5)
    assert validate_trace(path) == []
    recs = [json.loads(x) for x in path.read_text().splitlines()]
    assert recs[0]["kind"] == "meta" and recs[0]["schema"] == SCHEMA
    by_name = {r["name"]: r for r in recs[1:]}
    # a compile the persistent cache serves counts as a cache load
    assert by_name["warmup"]["compiles"] + by_name["warmup"][
        "cache_loads"] >= 1
    assert by_name["drain"]["transfers"] == 1
    assert by_name["eval"]["attrs"]["score"] == 1.5
    out = summarize(path)
    assert "warmup" in out and "drain" in out


@pytest.fixture
def warm_cache(tmp_path):
    """The persistent compilation cache in a fresh directory, caching
    every program; the worker's own settings come back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def test_watch_counts_cache_loads_apart_from_compiles(warm_cache):
    """A program the persistent cache serves is a cache load, not a
    compile, though jax fires its backend-compile event for both."""
    from repro.obs import Watch
    f = jax.jit(lambda x: x * 3.0 + 17.0)
    x = jnp.arange(5.0)
    w = Watch().start()
    try:
        f(x).block_until_ready()
        first = w.snapshot()
        jax.clear_caches()                  # in-memory only
        f(x).block_until_ready()
        second = w.snapshot()
    finally:
        w.stop()
    assert first == (1, 0, 0)               # (compiles, transfers, loads)
    assert second == (1, 0, 1)


def test_trace_spans_stamp_cache_loads(tmp_path, warm_cache):
    path = tmp_path / "t.jsonl"
    f = jax.jit(lambda x: x * 5.0 - 3.0)
    x = jnp.arange(4.0)
    with Trace(path, name="unit") as tr:
        with tr.span("compile"):
            f(x).block_until_ready()
        jax.clear_caches()
        with tr.span("load"):
            f(x).block_until_ready()
    assert validate_trace(path) == []
    by_name = {r["name"]: r for r in
               map(json.loads, path.read_text().splitlines()[1:])}
    assert (by_name["compile"]["compiles"],
            by_name["compile"]["cache_loads"]) == (1, 0)
    assert (by_name["load"]["compiles"],
            by_name["load"]["cache_loads"]) == (0, 1)


def test_train_drain_is_one_counted_transfer(tmp_path, warm_cache):
    """Each drain of the training loop (chunk metrics, evaluation
    scores) is one ``jax.device_get``, which the span's transfer count
    sees; a second run loads its programs from the warm cache."""
    from repro.train.loop import TrainConfig, train_rl_netes

    def spans(tag):
        path = tmp_path / f"{tag}.jsonl"
        train_rl_netes("landscape:sphere",
                       TrainConfig(n_agents=8, iters=6, seed=0,
                                   eval_every=2, trace=str(path)))
        assert validate_trace(path) == []
        return [json.loads(x) for x in path.read_text().splitlines()[1:]]

    cold = spans("cold")
    jax.clear_caches()
    warm = spans("warm")
    for recs in (cold, warm):
        drains = [r for r in recs if r["name"] == "drain"]
        assert len(drains) == 3 + 1     # three chunks, one eval drain
        assert all(r["transfers"] == 1 for r in drains)
    assert sum(r["compiles"] for r in warm) == 0
    assert sum(r["cache_loads"] for r in warm) >= 1
    assert sum(r["compiles"] for r in cold) >= 1


def test_trace_none_is_noop():
    tr = Trace(None)
    assert not tr.active
    with tr.span("anything"):
        pass
    tr.event("x")
    tr.close()          # all no-ops, no file anywhere


def test_validate_trace_catches_violations(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({"kind": "span", "name": "x"}) + "\n")
    errs = validate_trace(bad)
    assert any("meta" in e for e in errs)          # missing meta line
    bad.write_text(
        json.dumps({"kind": "meta", "schema": SCHEMA, "name": "x"}) + "\n"
        + json.dumps({"kind": "span", "name": "x", "t0": 0.0,
                      "dur_s": 0.1, "depth": 0, "compiles": -1,
                      "transfers": 0}) + "\n")
    assert any("non-negative" in e for e in validate_trace(bad))


def test_train_trace_end_to_end_and_cli(tmp_path):
    from repro.train.loop import TrainConfig, train_rl_netes
    path = tmp_path / "run.jsonl"
    train_rl_netes("landscape:sphere",
                   TrainConfig(n_agents=8, iters=6, seed=0, probes="all",
                               channel="quantize(bits=8)", eval_every=3,
                               trace=str(path)))
    assert validate_trace(path) == []
    names = {json.loads(x).get("name")
             for x in path.read_text().splitlines()}
    assert {"build", "chunk", "drain", "eval"} <= names
    res = subprocess.run(
        [sys.executable, "-m", "repro.obs", "summarize", str(path)],
        capture_output=True, text=True, env=_env())
    assert res.returncode == 0, res.stderr
    assert "chunk" in res.stdout
    res = subprocess.run(
        [sys.executable, "-m", "repro.obs", "validate", str(path)],
        capture_output=True, text=True, env=_env())
    assert res.returncode == 0, res.stderr


def _env():
    import os
    env = dict(os.environ)
    root = pathlib.Path(__file__).resolve().parent.parent
    env["PYTHONPATH"] = str(root / "src")
    return env


# ---------------------------------------------------------------------------
# solo vs sharded probe parity (8 forced host devices, subprocess)
# ---------------------------------------------------------------------------

_SUBPROCESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import channel as comm_channel
from repro.core import netes, topology, topology_repr, topology_sched
from repro.core.netes import NetESConfig
from repro.core.topology import TopologySpec
from repro.distributed import fleet_shard
from repro.obs.probes import compile_probes

N, D, ITERS = 257, 16, 5
cfg = NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.5)
state0 = netes.init_state(jax.random.PRNGKey(0), N, D)


def reward_fn(params, key):
    return -(params * params).sum(axis=-1)


topo = topology_repr.from_dense(
    topology.erdos_renyi(N, p=0.05, seed=3), "sparse")
chan = comm_channel.compile_channel("quantize(bits=8)", N)
probes = compile_probes("fitness|consensus|wire|graph", capacity=ITERS,
                        channel=chan, dim=D)

ref = None
for ndev in (None, 2, 8):
    mesh = None if ndev is None else fleet_shard.build_mesh(ndev)
    st, cs_out, ms, m = fleet_shard.run_sharded(
        state0, topo, reward_fn, cfg, ITERS, mesh, channel=chan,
        chan_state=chan.init(state0.thetas), probes=probes,
        metrics_state=probes.init())
    series = probes.drain(ms)
    if ref is None:
        ref = (jax.device_get(st.thetas), series)
    else:
        assert np.array_equal(np.asarray(jax.device_get(st.thetas)),
                              ref[0]), ("state", ndev)
        for k in series:
            a, b = np.asarray(series[k]), np.asarray(ref[1][k])
            if k in ("consensus_dist", "update_var"):
                # psum'd moment sums: reduction grouping differs per
                # mesh size, so these match to rounding, not bitwise
                np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7,
                                           err_msg=str((k, ndev)))
            else:
                assert np.array_equal(a, b), (k, ndev)

# scheduled engine: graph probes read the LIVE topology on every shard
sched = topology_sched.compile_schedule(
    topology_sched.ScheduleSpec(kind="resample_er", period=2),
    TopologySpec(family="erdos_renyi", n_agents=N, p=0.05, seed=3),
    representation="sparse")
probes_s = compile_probes("fitness|graph", capacity=ITERS)
ref = None
for ndev in (None, 8):
    mesh = None if ndev is None else fleet_shard.build_mesh(ndev)
    st, ss, ms, m = fleet_shard.run_sharded_scheduled(
        state0, sched.init(), reward_fn, cfg, sched, ITERS, mesh,
        probes=probes_s, metrics_state=probes_s.init())
    series = probes_s.drain(ms)
    if ref is None:
        ref = series
    else:
        for k in series:
            assert np.array_equal(np.asarray(series[k]),
                                  np.asarray(ref[k])), ("sched", k, ndev)

print("OBS_SHARD_PARITY_OK")
"""


def test_probe_parity_solo_vs_sharded_8dev():
    """The drained probe series is IDENTICAL for mesh sizes {solo, 2, 8}
    — probes sample the psum-reduced global metrics, so telemetry is
    placement-invariant like the trajectory itself (DESIGN.md §13)."""
    res = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             **{k: v for k, v in __import__("os").environ.items()
                if k not in ("XLA_FLAGS",)}})
    assert "OBS_SHARD_PARITY_OK" in res.stdout, \
        (res.stdout[-2000:], res.stderr[-4000:])
