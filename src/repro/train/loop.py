"""Training loops.

* ``train_rl_netes`` — the paper's experiment: NetES over a population
  solving an RL task (or synthetic landscape), with the paper's evaluation
  protocol (periodic noise-free evaluation of the best agent, §5.2).
* ``train_lm_netes`` — NetES driving a transformer LM from the arch
  registry on the synthetic corpus (single-host, reduced scale), using the
  same distributed step builders the dry-run lowers.
"""
from __future__ import annotations

import dataclasses
import pathlib
import time
from typing import Callable, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import checkpoint
from repro.comm import channel as comm_channel
from repro.comm.channel import Channel, ChannelSpec
from repro.configs.base import ModelConfig
from repro.core import netes, topology_repr, topology_sched
from repro.core.netes import NetESConfig
from repro.core.topology import TopologySpec
from repro.core.topology_sched import ScheduleSpec, TopologySchedule
from repro.data import make_batch
from repro.distributed import netes_dist
from repro.envs import resolve_task
from repro.envs.rollout import make_evaluator
from repro.models import transformer
from repro.obs import Trace
from repro.obs.probes import (DEFAULT_CAPACITY, Probes, ProbeSpec,
                              compile_probes)

# How many iterations' device metrics accumulate before one host
# transfer drains them (the per-iteration float() conversions forced a
# device sync every step — the PR-1 bug, fixed in both loops).
METRIC_DRAIN_CHUNK = 8


@dataclasses.dataclass
class TrainConfig:
    n_agents: int = 32
    iters: int = 100
    # The topology travels as a serializable TopologySpec end-to-end; the
    # legacy (family, density, seed) triplet is kept as constructor sugar
    # and folded into ``topology`` in __post_init__.
    topology: Optional[TopologySpec] = None
    representation: str = "auto"    # auto | dense | sparse | circulant
    topology_family: str = "erdos_renyi"
    density: float = 0.5
    topo_seed: int = 0
    # Time-varying topology (DESIGN.md §9): a ScheduleSpec, or its string
    # form ("resample_er(period=8)", ...) as constructor sugar.
    schedule: Optional[Union[ScheduleSpec, str]] = None
    # Lossy communication channel (DESIGN.md §11): a ChannelSpec, or its
    # string form ("quantize(bits=8)|dropout(p=0.1)") as sugar. None ⇒
    # the idealized (channel-free) path, bit-identical to "lossless".
    channel: Optional[Union[ChannelSpec, str]] = None
    # Fused wire-form dispatch for quantizing channels (DESIGN.md §12).
    # False pins the legacy decode-then-contract path — the tests'
    # unfused control runs; semantics are identical either way.
    channel_fused: bool = True
    # Shard the agent axis over this many devices (DESIGN.md §13): the
    # fused scans route through distributed.fleet_shard with halo /
    # all-gather collectives between shards. None ⇒ single-device path.
    # The noise layout is the single-device engine's, so a sharded run
    # equals it up to reduction order; on the CPU, trajectories are
    # bitwise identical for ANY shard count (1 included).
    shards: Optional[int] = None
    seed: int = 0
    eval_every: int = 0             # 0 ⇒ paper protocol (prob 0.08)
    eval_episodes: int = 16
    # When set, train_rl_netes saves (NetES state, RNG, topology-schedule
    # state) at every eval point and resumes from ``latest.json`` if one
    # exists — crash-safe fleet runs.
    checkpoint_dir: Optional[str] = None
    # On-device telemetry (DESIGN.md §15): a ProbeSpec, or its string
    # form ("fitness|consensus|graph", or "all") as sugar. The probe
    # ring joins the scan carry; instrumented runs are bit-for-bit
    # identical to uninstrumented ones, and the drained series lands in
    # ``history["probes"]``. None ⇒ the exact status-quo program.
    probes: Optional[Union[ProbeSpec, str]] = None
    # Ring capacity; 0 ⇒ obs.probes.DEFAULT_CAPACITY. Iterations beyond
    # the capacity wrap (oldest samples drop); the drained series always
    # holds the LAST ``capacity`` iterations in order.
    probe_capacity: int = 0
    # Path for a structured JSONL trace (obs/trace.py): spans for every
    # build/chunk/eval/drain with wall times + XLA compile, cache-load and
    # host-transfer counts. None ⇒ no trace file; the spans still reach
    # a running profiler as ``repro/<name>`` annotations.
    trace: Optional[str] = None
    netes: NetESConfig = dataclasses.field(default_factory=NetESConfig)

    def __post_init__(self):
        if self.topology is None:
            self.topology = TopologySpec(
                family=self.topology_family, n_agents=self.n_agents,
                p=self.density, seed=self.topo_seed)
        else:
            self.n_agents = self.topology.n_agents
            self.topology_family = self.topology.family
            self.density = self.topology.p
            self.topo_seed = self.topology.seed
        if isinstance(self.schedule, str):
            self.schedule = ScheduleSpec.parse(self.schedule)
        if isinstance(self.channel, str):
            self.channel = ChannelSpec.parse(self.channel)
        if isinstance(self.probes, str):
            self.probes = ProbeSpec.parse(self.probes)

    @classmethod
    def from_search_result(cls, result, **overrides) -> "TrainConfig":
        """Build a TrainConfig from a ``repro.search.SearchResult``: the
        tournament's winning topology (and schedule/channel, if the
        winner was a time-varying or lossy-link candidate) becomes the
        run's communication graph. Any TrainConfig field can be
        overridden (``iters``, ``seed``, ``netes``, ...)."""
        kw = dict(topology=result.topology, schedule=result.schedule,
                  channel=result.channel)
        kw.update(overrides)
        return cls(**kw)


def build_topology(tc: TrainConfig) -> topology_repr.Topology:
    """TopologySpec → representation-selected Topology (DESIGN.md §3)."""
    return topology_repr.from_spec(tc.topology,
                                   representation=tc.representation)


def build_schedule(tc: TrainConfig) -> Optional[TopologySchedule]:
    """Compile ``tc.schedule`` against the topology spec (None if the
    config has no schedule — static runs keep the plain-Topology path)."""
    if tc.schedule is None:
        return None
    return topology_sched.compile_schedule(tc.schedule, tc.topology,
                                           tc.representation)


def build_channel(tc: TrainConfig) -> Optional[Channel]:
    """Compile ``tc.channel`` for the run's population (None if the
    config has no channel — channel-free runs keep the legacy path,
    which a ``lossless`` channel reproduces bit-for-bit)."""
    if tc.channel is None:
        return None
    return comm_channel.compile_channel(tc.channel, tc.n_agents,
                                        fused=tc.channel_fused)


def build_adjacency(tc: TrainConfig) -> jnp.ndarray:
    """Dense (N, N) adjacency — kept for graph-statistics consumers."""
    return jnp.asarray(tc.topology.build())


def build_probes(tc: TrainConfig, channel: Optional[Channel] = None,
                 dim: Optional[int] = None) -> Optional[Probes]:
    """Compile ``tc.probes`` for this run (None ⇒ no instrumentation and
    the exact status-quo compiled program). ``probe_capacity == 0`` means
    ``DEFAULT_CAPACITY`` — deliberately NOT a function of ``tc.iters``,
    so a checkpointed run resumed with a longer horizon restores its
    ring buffer shape-for-shape."""
    if tc.probes is None:
        return None
    capacity = tc.probe_capacity if tc.probe_capacity > 0 \
        else DEFAULT_CAPACITY
    return compile_probes(tc.probes, capacity=capacity, channel=channel,
                          dim=dim)


def train_rl_netes(task: str, tc: TrainConfig,
                   log: Optional[Callable[[Dict], None]] = None) -> Dict:
    """Paper experiment driver. ``task``: env name or 'landscape:<name>'.

    Returns history dict with train rewards and the paper's evaluation
    metric trace (best-agent noise-free episodes).

    With ``tc.schedule`` set, the topology anneals/resamples/rotates on
    device inside the same scans (DESIGN.md §9). With ``tc.channel``
    set, every inter-agent message rides the lossy channel (DESIGN.md
    §11) — the history gains per-iteration realized message counts plus
    ``realized_msgs``/``realized_wire_bytes`` totals. With
    ``tc.checkpoint_dir`` set, the full train state — NetES state
    (step + RNG), eval RNG, topology-schedule state, and channel
    state — is saved at every eval point and restored from
    ``latest.json`` on the next call, resuming mid-schedule (and
    mid-channel-stream) bit-for-bit; a resumed run's history covers
    only the post-resume iterations.
    """
    # Host phases run inside ``Trace`` spans (build, chunk, step, eval,
    # drain, checkpoint), which also reach the profiler as ``repro/<name>``
    # annotations whether or not ``tc.trace`` names a file.
    tr = Trace(tc.trace, name=f"rl:{task}", task=task,
               n_agents=tc.n_agents, iters=tc.iters,
               probes=tc.probes.label() if tc.probes is not None else None)
    with tr.span("build") as build_attrs:
        key = jax.random.PRNGKey(tc.seed)
        reward_fn, dim, init_fn, env, policy = resolve_task(task)

        mesh = None
        if tc.shards is not None:
            from repro.distributed import fleet_shard
            mesh = fleet_shard.build_mesh(tc.shards)
        schedule = build_schedule(tc)
        if schedule is not None:
            topo, sstate = None, schedule.init()
        else:
            topo, sstate = build_topology(tc), None
        state = netes.init_state(key, tc.n_agents, dim, init_fn=init_fn)
        channel = build_channel(tc)
        cstate = channel.init(state.thetas) if channel is not None else None
        probes = build_probes(tc, channel=channel, dim=dim)
        mstate = probes.init() if probes is not None else None
        evaluate = make_evaluator(env, policy, tc.eval_episodes, reward_fn)
        blocks = getattr(reward_fn, "blocks", None)
        if blocks is not None:
            # the agent blocks of one reward call: over all N rows, or
            # over one shard's rows on a mesh
            rows = tc.n_agents
            if mesh is not None:
                rows = -(-rows // mesh.size)
            n_blocks, block_rows = blocks(rows)
            build_attrs.update(reward_blocks=n_blocks,
                               reward_block_rows=block_rows)
        if mesh is not None:
            # per-shard bytes each iteration's collectives move, from the
            # engine the first chunk then runs
            build_attrs.update(fleet_shard.get_engine(
                topo, reward_fn, tc.netes, mesh, channel, schedule,
                probes).collective_bytes(dim))
    history: Dict[str, List] = {"reward_mean": [], "reward_max": [],
                                "eval": [], "eval_iter": []}
    if channel is not None:
        history["msgs"] = []
    t0 = time.time()

    # Paper §5.2 eval protocol, decided host-side UP FRONT (prob 0.08 per
    # iteration, or fixed cadence): the iterations between eval points run
    # as fused lax.scans (netes.run) and the per-iteration metrics are
    # drained in a single host transfer per chunk — the per-step float()
    # conversions forced a device sync every iteration. Scans use ONE
    # fixed length (gaps are split into ``scan_chunk``-sized scans + a
    # per-step jitted tail), so XLA compiles the scan once instead of once
    # per distinct gap length under the random-eval protocol.
    if tc.eval_every:
        eval_iters = list(range(tc.eval_every - 1, tc.iters, tc.eval_every))
        scan_chunk = tc.eval_every
    else:
        draw = np.random.default_rng(tc.seed + 999)
        eval_iters = [it for it in range(tc.iters) if draw.random() < 0.08]
        scan_chunk = 8
    if tc.iters > 0 and tc.iters - 1 not in eval_iters:
        eval_iters.append(tc.iters - 1)

    def drain(m):
        # one jax.device_get per chunk: the sync the trace's transfer
        # count sees
        with tr.span("drain"):
            names = [k for k in ("reward_mean", "reward_max", "msgs")
                     if k in m]
            host = jax.device_get({k: m[k] for k in names})
            for k in names:
                history[k].extend(
                    np.asarray(host[k], np.float64).reshape(-1).tolist())

    eval_key = jax.random.PRNGKey(tc.seed + 999)

    # ---- crash-safe resume (checkpoint/io): restore (NetES state, eval
    # RNG, schedule state) saved at the last completed eval point.
    def _blob():
        blob = {"netes": state, "eval_key": eval_key}
        if sstate is not None:
            blob["sched"] = sstate
        if cstate is not None:
            blob["chan"] = cstate
        if mstate is not None:
            blob["obs"] = mstate
        return blob

    ckpt_dir = pathlib.Path(tc.checkpoint_dir) if tc.checkpoint_dir \
        else None
    resume_iter = -1
    if ckpt_dir is not None and (ckpt_dir / "latest.json").exists():
        resume_iter, restored = checkpoint.restore_train_state(ckpt_dir,
                                                               _blob())
        state, eval_key = restored["netes"], restored["eval_key"]
        sstate = restored.get("sched", sstate)
        cstate = restored.get("chan", cstate)
        mstate = restored.get("obs", mstate)
    if mesh is not None:
        # the evaluation reads the replicated best row; its key lives on
        # the same devices from the first point on, or the second point
        # would build the evaluator again for the key's new placement
        eval_key = jax.device_put(eval_key, NamedSharding(mesh, P()))

    def _unpack(out):
        """Every step/run entry point returns ``(state[, sstate]
        [, cstate][, mstate], metrics)`` — peel the state axes this run
        actually carries off the tail and return the metrics."""
        nonlocal state, sstate, cstate, mstate
        out = list(out)
        m = out.pop()
        if probes is not None:
            mstate = out.pop()
        if channel is not None:
            cstate = out.pop()
        if schedule is not None:
            sstate = out.pop()
        (state,) = out
        return m

    # keyword axes shared by every probed entry point; empty when
    # probes are off so the call sites stay the status-quo programs.
    def _probe_kw():
        if probes is None:
            return {}
        return {"probes": probes, "metrics_state": mstate}

    def advance(n_iters: int):
        """n_iters fused training iterations with whatever state axes
        (schedule × channel × probes) this run carries joined into the
        scan."""
        with tr.span("chunk", iters=n_iters):
            if schedule is not None:
                kw = dict(num_iters=n_iters, mesh=mesh, **_probe_kw())
                if channel is not None:
                    kw.update(channel=channel, chan_state=cstate)
                out = netes.run_scheduled(state, sstate, reward_fn,
                                          tc.netes, schedule, **kw)
            else:
                kw = dict(num_iters=n_iters, mesh=mesh, **_probe_kw())
                if channel is not None:
                    kw.update(channel=channel, chan_state=cstate)
                out = netes.run(state, topo, reward_fn, tc.netes, **kw)
            m = _unpack(out)
        drain(m)

    def advance_one():
        nonlocal state, sstate, cstate, mstate
        if mesh is not None:
            # the sharded engine is a scan-only entry point; a length-1
            # scan is its single-step form (compiled once per run).
            advance(1)
            return
        with tr.span("step"):
            kw = _probe_kw()
            if channel is not None:
                kw.update(channel=channel, chan_state=cstate)
            if schedule is not None:
                out = netes.scheduled_step(state, sstate, reward_fn,
                                           tc.netes, schedule, **kw)
            else:
                out = netes.netes_step(state, topo, reward_fn, tc.netes,
                                       **kw)
            m = _unpack(out)
        drain(m)

    # Eval scores stay on device in a pending list and drain through ONE
    # jax.device_get per chunk — the old per-point float() coercion was
    # the same forced-sync bug the training metrics had in PR 1 (each
    # eval point stalled the pipeline for its own transfer).
    eval_pending: List = []

    def drain_evals():
        if not eval_pending:
            return
        with tr.span("drain", what="eval", points=len(eval_pending)):
            vals = jax.device_get([s for _, s in eval_pending])
        for (it, _), v in zip(eval_pending, vals, strict=True):
            history["eval"].append(float(v))
            history["eval_iter"].append(it)
        eval_pending.clear()

    start = resume_iter + 1
    for it in eval_iters:
        if it <= resume_iter:
            continue            # already trained + evaluated pre-crash
        todo = it - start + 1
        start = it + 1
        while todo >= scan_chunk:
            advance(scan_chunk)
            todo -= scan_chunk
        for _ in range(todo):   # tail < scan_chunk: jitted single steps
            advance_one()
        with tr.span("eval", iter=it):
            score, eval_key = evaluate(state.best_theta, eval_key)
        eval_pending.append((it, score))
        if len(eval_pending) >= METRIC_DRAIN_CHUNK or log is not None:
            # ``log`` wants the score now (interactive runs accept the
            # sync); otherwise scores accumulate and drain per chunk.
            drain_evals()
        if ckpt_dir is not None:
            with tr.span("checkpoint", iter=it):
                checkpoint.save_train_state(ckpt_dir, it, _blob(),
                                            extra={"task": task})
        if log:
            log({"iter": it, "eval": history["eval"][-1],
                 "reward_mean": history["reward_mean"][-1]})
    drain_evals()
    history["final_eval"] = history["eval"][-1] if history["eval"] else None
    history["max_eval"] = max(history["eval"]) if history["eval"] else None
    if channel is not None:
        # realized (not modeled) traffic: messages that actually moved ×
        # the pipeline's encoded bytes per message (DESIGN.md §11).
        total_msgs = float(np.sum(history["msgs"], dtype=np.float64))
        history["realized_msgs"] = total_msgs
        history["realized_wire_bytes"] = int(
            round(total_msgs * channel.payload_bytes(dim)))
    if probes is not None:
        # ONE host transfer for the whole probe ring (Probes.drain).
        with tr.span("drain", what="probes"):
            history["probes"] = probes.drain(mstate)
    history["wall_s"] = time.time() - t0
    tr.close()
    return history


def search_topology(task: str, sconfig=None,
                    log: Optional[Callable[[Dict], None]] = None
                    ) -> TopologySpec:
    """Optimize the communication graph for ``task`` and return the
    winning ``TopologySpec`` — the paper's closing claim, operational
    (DESIGN.md §10). ``sconfig`` is a ``repro.search.SearchConfig``
    (defaults if None). For the full tournament record (round history,
    control scores, a possible winning *schedule*), call
    ``repro.search.run_search`` directly and use
    ``TrainConfig.from_search_result``.
    """
    from repro.search import SearchConfig, run_search
    result = run_search(task, sconfig or SearchConfig(), log=log)
    return result.topology


def train_lm_netes(cfg: ModelConfig, tc: TrainConfig, seq_len: int = 128,
                   per_agent_batch: int = 1, same_init: bool = True,
                   log: Optional[Callable[[Dict], None]] = None) -> Dict:
    """NetES-trains a registry architecture on the synthetic corpus using
    the SAME replica step the dry-run lowers (single-host: agents live on
    one device; the mesh axes are virtual here).

    ``same_init=True`` (paper Eq. 1/2 regime): all agents start from one θ.
    At LM scale, independently-initialized agents make Eq. 3's θ-difference
    term O(weight-norm) × α/(Nσ²) — divergent for any useful α (the paper's
    own Fig 3B control shows diff-init FC populations failing too).
    """
    key = jax.random.PRNGKey(tc.seed)
    n = tc.n_agents
    schedule = build_schedule(tc)
    channel = build_channel(tc)
    # dedicated init subkey: init_params(key) / split(key, n) followed by
    # the loop's split(key, 3) reuses the SAME parent — threefry children
    # coincide (split(key, 3) == split(key, n)[:3]), correlating the
    # first iterations' batch/step draws with the init draws.
    key, k_init = jax.random.split(key)
    if same_init:
        p0 = transformer.init_params(k_init, cfg)
        params = jax.tree.map(
            lambda l: jnp.broadcast_to(l, (n,) + l.shape).copy(), p0)
    else:
        params = jax.vmap(lambda k: transformer.init_params(k, cfg))(
            jax.random.split(k_init, n))
    # wire-stage byte accounting sees the per-agent parameter count (the
    # leaves carry a leading agent axis).
    probes = build_probes(tc, channel=channel,
                          dim=sum(int(np.prod(l.shape[1:]))
                                  for l in jax.tree.leaves(params)))
    mstate = probes.init() if probes is not None else None
    if schedule is not None:
        sstate = schedule.init()
        step = netes_dist.make_replica_train_step(
            cfg, tc.netes, n, agent_axis_names=("data",), microbatch=1,
            schedule=schedule, channel=channel, probes=probes)
    else:
        sstate = None
        # The step dispatches on (and closes over) the Topology itself —
        # no dense (N, N) view is materialized anywhere (the old
        # ``adj = topo.to_dense()`` defeated the sparse representation's
        # O(N·K) footprint at fleet scale).
        step = netes_dist.make_replica_train_step(
            cfg, tc.netes, n, agent_axis_names=("data",), microbatch=1,
            topology=build_topology(tc), channel=channel, probes=probes)
    step = jax.jit(step)
    cstate = channel.init(params) if channel is not None else None
    tr = Trace(tc.trace, name=f"lm:{cfg.name}", n_agents=n, iters=tc.iters,
               probes=probes.spec.label() if probes is not None else None)
    history: Dict[str, List] = {"loss_mean": [], "reward_max": []}

    # Metrics stay on device and are drained once per chunk — the
    # per-iteration float() conversions forced a device sync every step
    # (the PR-1 train_rl_netes bug, same fix here).
    pending: List = []

    def drain():
        with tr.span("drain", points=len(pending)):
            for it, mv in zip([i for i, _ in pending],
                              jax.device_get([m for _, m in pending]),
                              strict=True):
                history["loss_mean"].append(float(mv["loss_mean"]))
                history["reward_max"].append(float(mv["reward_max"]))
                if log and it % 10 == 0:
                    log({"iter": it, "loss": history["loss_mean"][-1]})
            pending.clear()

    for it in range(tc.iters):
        key, k_batch, k_step = jax.random.split(key, 3)
        batch = make_batch(cfg, dict(seq_len=seq_len,
                                     global_batch=n * per_agent_batch),
                           k_batch)
        batch = jax.tree.map(
            lambda x: x.reshape((n, per_agent_batch) + x.shape[1:]), batch)
        with tr.span("step", iter=it):
            # the replica step returns its state axes after the metrics,
            # mstate LAST — peel them off the tail (mirrors _unpack in
            # train_rl_netes, modulo the flipped metrics position).
            args = [sstate] if schedule is not None else []
            if channel is not None:
                args.append(cstate)
            if probes is not None:
                args.append(mstate)
            out = list(step(params, None, batch, k_step, *args))
            if probes is not None:
                mstate = out.pop()
            if channel is not None:
                cstate = out.pop()
            if schedule is not None:
                sstate = out.pop()
            params, m = out
        pending.append((it, m))
        if len(pending) >= METRIC_DRAIN_CHUNK:
            drain()
    drain()
    if probes is not None:
        with tr.span("drain", what="probes"):
            history["probes"] = probes.drain(mstate)
    tr.close()
    return history
