"""Sharded-fleet scale axis: 16384 NetES agents with the agent axis
sharded over every device ``jax.devices()`` holds (DESIGN.md §13).

The paper's thesis is that sparse topologies buy their learning
performance *cheaply* — the communication cost argument only becomes
real once the agent axis is physically partitioned and cross-shard
edges cost actual collective traffic. This bench runs the
``distributed/fleet_shard`` engine at N = 16384 on a mesh of all local
devices (four chips on a v5e 2x2 host; on a CPU host, as many as
``XLA_FLAGS=--xla_force_host_platform_device_count=<n>`` makes) and
gates three things per leg:

* **zero steady-state recompiles** — a warmed engine must replay its
  scan chunk without a single XLA backend compile (counted via
  ``repro.obs.xla_watch``, same gate as ``fleet_bench``);
* **exact per-shard wire bytes** — ``ShardedNetES.collective_bytes``
  derives payload/reward/broadcast bytes from the static shapes of the
  ppermute/all-gather operands the compiled program executes, so they
  are Python ints and gate with ``wire_bytes`` exact-match semantics.
  On a mesh of more than one device the headline physics must hold: ER
  halo bytes < FC gather bytes at matched update semantics, and the
  int8 wire codec (quantize(bits=8)) must shrink the ER halo payload;
* **steady-state median step time** (advisory until a like-hardware
  baseline is armed — see check_regression.py).

A fourth entry, ``fleet.netes16384.shard_parity``, scores the
shard-invariance contract at small N: the SAME seed must produce
bit-identical trajectories on mesh sizes {1, n_dev} and the
single-device solo oracle, for sparse/circulant/FC modes and the
quantized channel.

Everything runs in the bench's own process: a child process could not
take a chip that this one already holds.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common, registry
from repro.comm import channel as comm_channel
from repro.core import netes, topology, topology_repr
from repro.core.netes import NetESConfig
from repro.distributed import fleet_shard
from repro.obs.xla_watch import count_backend_compiles

N_BIG = 16384
DIM = 32
# G(n, m) edge budget: m = 4n undirected edges → mean degree 8 (+ self
# loop), the sparse-regime operating point the paper's 1000-agent ER
# graphs sit in.
EDGES_PER_NODE = 4
CIRC_OFFSETS = (1, 2, 3, 4)


def reward_fn(params, key):
    # Row-decomposable rastrigin surface: per-agent O(D) so the bench
    # times the MIXING/collective layer, not the task.
    return -(params * params - jnp.cos(2 * jnp.pi * params)).sum(axis=-1)


def er_sparse_topology(n, edges_per_node, seed):
    # Direct G(n, m) neighbor-list construction — at n = 16384 a dense
    # (n, n) f32 adjacency is 1 GiB; the generators' from_dense path is
    # off the table. Semantics mirror topology_repr.sparse_neighbors:
    # self-loop edge present with weight 1, padded slots index the row
    # itself with weight 0, deg counts the self-loop.
    rng = np.random.default_rng(seed)
    m = edges_per_node * n
    a = rng.integers(0, n, size=3 * m)
    b = rng.integers(0, n, size=3 * m)
    keep = a != b
    pairs = np.unique(
        np.stack([np.minimum(a, b), np.maximum(a, b)], axis=1)[keep],
        axis=0)
    pairs = pairs[rng.permutation(len(pairs))[:m]]
    self_ix = np.arange(n, dtype=np.int64)
    src = np.concatenate([pairs[:, 0], pairs[:, 1], self_ix])
    dst = np.concatenate([pairs[:, 1], pairs[:, 0], self_ix])
    order = np.argsort(src, kind="stable")
    src, dst = src[order], dst[order]
    counts = np.bincount(src, minlength=n)
    k_max = int(counts.max())
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slot = np.arange(len(src)) - starts[src]
    idx = np.tile(np.arange(n, dtype=np.int32)[:, None], (1, k_max))
    mask = np.zeros((n, k_max), np.float32)
    idx[src, slot] = dst.astype(np.int32)
    mask[src, slot] = 1.0
    return topology_repr.Topology(
        kind="sparse", n=n, deg=jnp.asarray(counts, jnp.float32),
        neighbor_idx=jnp.asarray(idx), neighbor_mask=jnp.asarray(mask))


# ---- shard-invariance parity at small N (the tentpole contract) -------
def parity_check(n_dev):
    n_small, d_small, iters = 257, 16, 5
    cfg = NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.5)
    state0 = netes.init_state(jax.random.PRNGKey(0), n_small, d_small)
    adj = topology.erdos_renyi(n_small, p=0.05, seed=3)
    legs = {
        "sparse": (topology_repr.from_dense(adj, "sparse"), None),
        "circulant": (topology_repr.from_dense(
            topology.circulant_from_offsets(n_small, [1, 2, 5]),
            "circulant"), None),
        "fc": (fleet_shard.FullyConnected(n_small), None),
        "sparse_q8": (topology_repr.from_dense(adj, "sparse"),
                      comm_channel.compile_channel("quantize(bits=8)",
                                                   n_small)),
    }
    out = {}
    for name, (topo, chan) in legs.items():
        runs = {}
        for ndev in (None, 1, n_dev):
            mesh = None if ndev is None else fleet_shard.build_mesh(ndev)
            eng = fleet_shard.ShardedNetES(topo, reward_fn, cfg,
                                           mesh=mesh, channel=chan)
            cs = chan.init(state0.thetas) if chan is not None else None
            res = eng.run(state0, iters, chan_state=cs)
            st = res[0]
            runs[ndev] = jax.device_get(
                (st.thetas, st.best_theta, st.best_reward))
        ref = runs[None]
        ok = all(
            all(np.array_equal(np.asarray(x), np.asarray(y))
                for x, y in zip(runs[nd], ref, strict=True))
            for nd in (1, n_dev))
        out[name] = bool(ok)
    return out


# ---- the 16384-agent legs ---------------------------------------------
def timed_leg(topo, chan, n_dev, chunk, replays):
    cfg = NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.5)
    mesh = fleet_shard.build_mesh(n_dev)
    eng = fleet_shard.ShardedNetES(topo, reward_fn, cfg, mesh=mesh,
                                   channel=chan)
    state0 = netes.init_state(jax.random.PRNGKey(1), N_BIG, DIM)
    cs = chan.init(state0.thetas) if chan is not None else None

    jax.block_until_ready(eng.run(state0, chunk, chan_state=cs))  # warmup
    steps = []
    with count_backend_compiles() as compiles:
        for _ in range(replays):
            t0 = time.perf_counter()
            jax.block_until_ready(eng.run(state0, chunk, chan_state=cs))
            steps.append((time.perf_counter() - t0) / chunk)
    bytes_ = eng.collective_bytes(DIM)
    return {"step_s": float(np.median(steps)),
            "step_s_min": float(min(steps)),
            "step_s_max": float(max(steps)),
            "timed_compiles": len(compiles),
            "plan_mode": eng.plan.mode,
            **{k: int(v) for k, v in bytes_.items()}}


def run(quick: bool = False):
    n_dev = jax.device_count()
    chunk, replays = (2, 2) if quick else (4, 3)
    parity = parity_check(n_dev)
    assert all(parity.values()), \
        f"shard-invariance parity failed: {parity}"

    er_topo = er_sparse_topology(N_BIG, EDGES_PER_NODE, seed=7)
    q8 = comm_channel.compile_channel("quantize(bits=8)", N_BIG)
    circ = topology_repr.Topology(
        kind="circulant", n=N_BIG,
        deg=jnp.full((N_BIG,), 2 * len(CIRC_OFFSETS) + 1, jnp.float32),
        offsets=CIRC_OFFSETS)
    legs = {name: timed_leg(topo, chan, n_dev, chunk, replays)
            for name, topo, chan in (
                ("er_sparse", er_topo, None),
                ("er_sparse_q8", er_topo, q8),
                ("circulant", circ, None),
                ("fc", fleet_shard.FullyConnected(N_BIG), None))}

    for name, leg in legs.items():
        assert leg["timed_compiles"] == 0, \
            f"{name}: {leg['timed_compiles']} steady-state recompile(s)"
    if n_dev > 1:
        # The paper's communication argument, measured where bytes move:
        # sparse halo traffic must undercut the FC gather, and the int8
        # wire codec must undercut raw f32 halo rows.
        assert (legs["er_sparse"]["payload_bytes"]
                < legs["fc"]["payload_bytes"])
        assert (legs["er_sparse_q8"]["payload_bytes"]
                < legs["er_sparse"]["payload_bytes"])
        assert (legs["circulant"]["payload_bytes"]
                < legs["er_sparse"]["payload_bytes"])

    entries = []
    for name, leg in legs.items():
        ename = f"fleet.netes{N_BIG}.{name}"
        common.emit(ename, leg["step_s"],
                    f"bytes/shard/step={leg['total_bytes']} "
                    f"mode={leg['plan_mode']}")
        entries.append(registry.Entry(
            name=ename,
            wall_s=leg["step_s"],
            wire_bytes=leg["total_bytes"],
            extra={"n": N_BIG, "dim": DIM, "n_dev": n_dev,
                   "chunk": chunk, "replays": replays,
                   "plan_mode": leg["plan_mode"],
                   "payload_rows": leg["payload_rows"],
                   "payload_bytes": leg["payload_bytes"],
                   "reward_bytes": leg["reward_bytes"],
                   "broadcast_bytes": leg["broadcast_bytes"],
                   "step_s_min": leg["step_s_min"],
                   "step_s_max": leg["step_s_max"],
                   "timed_compiles": leg["timed_compiles"]}))
    entries.append(registry.Entry(
        name=f"fleet.netes{N_BIG}.shard_parity",
        eval_score=float(all(parity.values())),
        extra={"legs": parity, "n": 257,
               "mesh_sizes": sorted({1, n_dev}),
               "device_count": n_dev}))
    return entries


@registry.register("fleet16k", group="sharded")
def bench(ctx: registry.Context):
    return run(quick=ctx.quick)
