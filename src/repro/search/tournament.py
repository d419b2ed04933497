"""On-device topology-search tournaments (DESIGN.md §10).

The paper closes on the claim that "distributed machine learning
algorithms could be made more effective if the communication topology
between learning agents was optimized" — this module does the
optimizing. S candidate topologies train **as one batched on-device
program**: candidate ``Topology`` pytrees are stacked to a shared static
``K_max`` (``topology_repr.stack``) and the fused training scan
(``netes.run`` / ``run_scheduled``) is vmapped over the candidate axis,
so S populations advance inside ONE jitted ``lax.scan`` with zero
per-candidate retraces (the vmapped trajectories are bit-identical to S
independent runs — tested in tests/test_search.py).

Successive halving drives the outer loop: every round trains all
surviving candidates ``round_iters`` iterations (doubling per round —
the compute freed by halving the pool is reallocated to survivors as a
wider eval budget), scores each candidate by noise-free evaluation of
its best parameters, and keeps the top half. Rounds are checkpointable
(``checkpoint/io``): the per-candidate states save after every round and
a re-run resumes from ``latest.json`` bit-for-bit.

Candidates that cannot share one compiled program are grouped into
*cohorts* — one vmapped program per cohort per round:

* static candidates cohort by physical representation (``dense`` vs
  ``sparse``; exactly-circulant graphs map to sparse, because static
  circulant offsets live in the pytree aux and cannot vary across a
  batch);
* scheduled candidates cohort by the jit-static part of their compiled
  ``TopologySchedule`` (schedule spec, representation, base density,
  base offsets). ``advance()`` never reads the base graph's *seed*, so
  same-family-different-seed candidates share one static schedule
  object; their per-candidate ``ScheduleState``s (graph + threefry key)
  carry everything that differs. Sparse schedule pads are harmonized to
  the cohort-max ``k_max`` so the stacked shapes agree.
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib
import time
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint
from repro.comm import channel as comm_channel
from repro.comm.channel import Channel
from repro.core import netes, topology_repr, topology_sched
from repro.core.netes import NetESConfig
from repro.core.topology_sched import TopologySchedule
from repro.envs import resolve_task

from .candidates import CandidateSpec, make_grid, seed_pool


@dataclasses.dataclass(frozen=True)
class SearchConfig:
    """Everything a tournament needs; serializable and deterministic —
    two searches with equal configs produce identical results (and the
    second one compiles nothing, every round shape being jit-cached)."""

    n_agents: int = 64
    families: Tuple[str, ...] = ("erdos_renyi", "small_world",
                                 "scale_free", "fully_connected")
    densities: Tuple[float, ...] = (0.05, 0.1, 0.2, 0.33)
    seeds: Tuple[int, ...] = (0, 1)
    schedules: Tuple[Optional[str], ...] = (None,)
    channels: Tuple[Optional[str], ...] = (None,)   # DESIGN.md §11
    pool_size: int = 12            # after theory-prior pruning
    round_iters: int = 16          # round-0 training iterations
    widen: bool = True             # double per-round budget (halving's
    #                                freed compute goes to survivors)
    eval_episodes: int = 1         # noise-free eval calls per score
    seed: int = 0
    representation: str = "auto"   # auto | dense | sparse (per candidate)
    keep_families: Tuple[str, ...] = ("fully_connected",)
    checkpoint_dir: Optional[str] = None
    netes: NetESConfig = dataclasses.field(default_factory=NetESConfig)


@dataclasses.dataclass
class SearchResult:
    """Tournament outcome, ready for ``TrainConfig.from_search_result``."""

    winner: CandidateSpec
    score: float                       # winner's final-round eval score
    control_scores: Dict[str, float]   # control family -> last eval score
    pool: List[CandidateSpec]          # post-pruning pool (prior order)
    history: List[dict]                # per-round scores + survivors
    wall_s: float
    n_agents: int

    @property
    def topology(self):
        return self.winner.topo

    @property
    def schedule(self):
        return self.winner.sched

    @property
    def channel(self):
        return self.winner.chan

    def to_json(self) -> dict:
        return {
            "winner": self.winner.label(),
            "topology": dataclasses.asdict(self.topology),
            "schedule": (dataclasses.asdict(self.schedule)
                         if self.schedule else None),
            "channel": (self.channel.label() if self.channel else None),
            "score": self.score,
            "control_scores": self.control_scores,
            "pool": [c.label() for c in self.pool],
            "history": self.history,
            "wall_s": self.wall_s,
            "n_agents": self.n_agents,
        }


# ---------------------------------------------------------------------------
# per-candidate plans and cohort signatures
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Plan:
    """How one candidate runs: its cohort signature plus either a static
    ``Topology`` or a compiled per-candidate ``TopologySchedule``, and
    an optional compiled ``Channel`` (jit-static — candidates sharing a
    channel share one vmapped program; DESIGN.md §11)."""

    cohort: tuple
    topo: Optional[topology_repr.Topology] = None
    schedule: Optional[TopologySchedule] = None
    channel: Optional[Channel] = None


def _plan_candidate(cand: CandidateSpec, representation: str) -> _Plan:
    channel = (comm_channel.compile_channel(cand.chan,
                                            cand.topo.n_agents)
               if cand.channeled else None)
    if not cand.scheduled:
        adj = cand.topo.build()
        rep = representation
        if rep == "auto":
            rep = topology_repr.select_representation(np.asarray(adj))
            if rep == "circulant":
                rep = "sparse"   # static offsets are aux — not batchable
        if rep not in ("dense", "sparse"):
            raise ValueError(
                f"tournaments batch dense or sparse candidates, not "
                f"{rep!r} (circulant offsets are jit-static aux)")
        return _Plan(cohort=("static", rep, channel),
                     topo=topology_repr.from_dense(adj, rep),
                     channel=channel)
    rep = representation
    if cand.sched.kind == "rotate_circulant":
        rep = "auto"             # compiles to traced-shift circulant
    schedule = topology_sched.compile_schedule(cand.sched, cand.topo, rep)
    # Everything ``TopologySchedule.advance`` reads must agree across a
    # cohort (it becomes the shared jit-static schedule); base.seed and
    # the base family are init-only and may differ.
    base_p = (round(float(schedule.base.p), 9)
              if schedule.spec.kind in ("anneal_density", "resample_er")
              else None)
    key = ("sched", schedule.spec, schedule.representation, schedule.n,
           schedule.base_offsets, base_p, channel)
    return _Plan(cohort=key, schedule=schedule, channel=channel)


def _make_plans(pool: Sequence[CandidateSpec], representation: str
                ) -> List[_Plan]:
    plans = [_plan_candidate(c, representation) for c in pool]
    # Harmonize sparse schedule pads per cohort: stacked ScheduleStates
    # need one static k_max. (Static sparse candidates re-pad inside
    # topology_repr.stack instead.)
    by_cohort: Dict[tuple, List[int]] = {}
    for i, p in enumerate(plans):
        if p.schedule is not None and p.schedule.k_max:
            by_cohort.setdefault(p.cohort, []).append(i)
    for idxs in by_cohort.values():
        k = max(plans[i].schedule.k_max for i in idxs)
        for i in idxs:
            plans[i].schedule = dataclasses.replace(plans[i].schedule,
                                                    k_max=k)
    return plans


# ---------------------------------------------------------------------------
# the batched round programs (module-level jits — cached across rounds,
# tournaments, and a warmed tournament's replay)
# ---------------------------------------------------------------------------

def _eval_score(state, key, reward_fn, episodes: int):
    keys = jax.random.split(key, episodes)
    scores = jax.vmap(lambda k: reward_fn(state.best_theta[None], k)[0])(
        keys)
    return scores.mean()


@partial(jax.jit, static_argnames=("reward_fn", "cfg", "num_iters",
                                   "eval_episodes", "channel"))
def _round_static(states, topos, eval_keys, reward_fn, cfg,
                  num_iters: int, eval_episodes: int, channel=None,
                  cstates=None):
    """One round for a stacked static cohort: S fused training scans +
    S noise-free evals, vmapped into one compiled program. With a
    (cohort-shared, jit-static) ``channel``, the per-candidate
    ``ChannelState``s vmap alongside and come back advanced."""

    if channel is not None:
        def one_chan(state, topo, ekey, cs):
            state, cs, _m = netes.run(state, topo, reward_fn, cfg,
                                      num_iters, channel=channel,
                                      chan_state=cs)
            return state, cs, _eval_score(state, ekey, reward_fn,
                                          eval_episodes)

        return jax.vmap(one_chan)(states, topos, eval_keys, cstates)

    def one(state, topo, ekey):
        state, _metrics = netes.run(state, topo, reward_fn, cfg, num_iters)
        return state, _eval_score(state, ekey, reward_fn, eval_episodes)

    return jax.vmap(one)(states, topos, eval_keys)


@partial(jax.jit, static_argnames=("reward_fn", "cfg", "schedule",
                                   "num_iters", "eval_episodes",
                                   "channel"))
def _round_scheduled(states, sstates, eval_keys, reward_fn, cfg,
                     schedule, num_iters: int, eval_episodes: int,
                     channel=None, cstates=None):
    """Scheduled-cohort round: the graph evolves on device inside each
    vmapped scan (one shared jit-static schedule for the whole cohort;
    likewise the channel, when the cohort carries one)."""

    if channel is not None:
        def one_chan(state, ss, ekey, cs):
            state, ss, cs, _m = netes.run_scheduled(
                state, ss, reward_fn, cfg, schedule, num_iters,
                channel=channel, chan_state=cs)
            return state, ss, cs, _eval_score(state, ekey, reward_fn,
                                              eval_episodes)

        return jax.vmap(one_chan)(states, sstates, eval_keys, cstates)

    def one(state, ss, ekey):
        state, ss, _m = netes.run_scheduled(state, ss, reward_fn, cfg,
                                            schedule, num_iters)
        return state, ss, _eval_score(state, ekey, reward_fn,
                                      eval_episodes)

    return jax.vmap(one)(states, sstates, eval_keys)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _tree_stack(items):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *items)


def _tree_index(tree, i):
    return jax.tree.map(lambda x: x[i], tree)


def _run_round(alive: List[int], plans: List[_Plan], states: dict,
               sstates: dict, cstates: dict, eval_root, rnd: int,
               sc: SearchConfig, reward_fn, iters: int,
               episodes: int) -> Dict[int, float]:
    """Train + score every surviving candidate (one vmapped program per
    cohort). Mutates ``states``/``sstates``/``cstates`` in place;
    returns scores."""
    groups: Dict[tuple, List[int]] = {}
    for cid in alive:
        groups.setdefault(plans[cid].cohort, []).append(cid)
    scores: Dict[int, float] = {}
    for key, cids in groups.items():
        stacked = _tree_stack([states[c] for c in cids])
        eval_keys = jnp.stack([
            jax.random.fold_in(jax.random.fold_in(eval_root, c), rnd)
            for c in cids])
        channel = plans[cids[0]].channel
        cstacked = (_tree_stack([cstates[c] for c in cids])
                    if channel is not None else None)
        if key[0] == "static":
            topos = topology_repr.stack([plans[c].topo for c in cids])
            out = _round_static(
                stacked, topos, eval_keys, reward_fn=reward_fn,
                cfg=sc.netes, num_iters=iters, eval_episodes=episodes,
                channel=channel, cstates=cstacked)
            if channel is not None:
                new_states, new_cs, vec = out
                for i, c in enumerate(cids):
                    cstates[c] = _tree_index(new_cs, i)
            else:
                new_states, vec = out
        else:
            schedule = plans[cids[0]].schedule
            sstacked = _tree_stack([sstates[c] for c in cids])
            out = _round_scheduled(
                stacked, sstacked, eval_keys, reward_fn=reward_fn,
                cfg=sc.netes, schedule=schedule, num_iters=iters,
                eval_episodes=episodes, channel=channel,
                cstates=cstacked)
            if channel is not None:
                new_states, new_ss, new_cs, vec = out
                for i, c in enumerate(cids):
                    cstates[c] = _tree_index(new_cs, i)
            else:
                new_states, new_ss, vec = out
            for i, c in enumerate(cids):
                sstates[c] = _tree_index(new_ss, i)
        vec = np.asarray(vec, np.float64)
        for i, c in enumerate(cids):
            states[c] = _tree_index(new_states, i)
            s = float(vec[i])
            scores[c] = s if math.isfinite(s) else -math.inf
    return scores


def run_search(task: str, sc: SearchConfig,
               log: Optional[Callable[[dict], None]] = None
               ) -> SearchResult:
    """Run the tournament on ``task`` ("landscape:<name>" or an env name)
    and return the winning candidate + full round history.

    Deterministic in ``sc`` (fixed-seed init, eval keys, and halving
    tie-breaks); with ``sc.checkpoint_dir`` set, every completed round is
    saved and a rerun resumes after the last one on disk.
    """
    t0 = time.time()
    reward_fn, dim, init_fn, _env, _policy = resolve_task(task)
    pool = seed_pool(
        make_grid(sc.n_agents, sc.families, sc.densities, sc.seeds,
                  sc.schedules, sc.channels),
        sc.pool_size, keep_families=sc.keep_families)
    if not pool:
        raise ValueError("empty candidate pool")
    plans = _make_plans(pool, sc.representation)

    root = jax.random.PRNGKey(sc.seed)
    eval_root = jax.random.PRNGKey(sc.seed + 999)
    states = {cid: netes.init_state(jax.random.fold_in(root, cid),
                                    sc.n_agents, dim, init_fn=init_fn)
              for cid in range(len(pool))}
    sstates = {cid: plans[cid].schedule.init()
               for cid in range(len(pool))
               if plans[cid].schedule is not None}
    cstates = {cid: plans[cid].channel.init(states[cid].thetas)
               for cid in range(len(pool))
               if plans[cid].channel is not None}

    alive = list(range(len(pool)))
    history: List[dict] = []
    last_scores: Dict[int, float] = {}
    total_rounds = max(1, math.ceil(math.log2(len(pool))))
    start_round = 0

    # ---- round-granular resume (checkpoint/io) --------------------------
    ckpt_dir = pathlib.Path(sc.checkpoint_dir) if sc.checkpoint_dir \
        else None
    fingerprint = _search_fingerprint(task, sc)
    if ckpt_dir is not None and (ckpt_dir / "latest.json").exists():
        meta = json.loads((ckpt_dir / "latest.json").read_text())
        if meta.get("fingerprint") != fingerprint:
            raise ValueError(
                f"checkpoint dir {ckpt_dir} holds a different search "
                f"(task/config mismatch: saved "
                f"{meta.get('fingerprint')!r}, current "
                f"{fingerprint!r}); resuming would silently mix states "
                "across searches — use a fresh --search-checkpoint-dir")
        alive = [int(c) for c in meta["alive"]]
        like = _ckpt_blob(alive, states, sstates, cstates)
        done_round, restored = checkpoint.restore_train_state(ckpt_dir,
                                                              like)
        for c in alive:
            states[c] = restored["netes"][str(c)]
        for c, v in restored.get("sched", {}).items():
            sstates[int(c)] = v
        for c, v in restored.get("chan", {}).items():
            cstates[int(c)] = v
        last_scores = {int(k): v for k, v in meta["scores"].items()}
        history = meta["history"]
        start_round = done_round + 1

    ranked = sorted(alive)
    for rnd in range(start_round, total_rounds):
        iters = sc.round_iters * (2 ** rnd if sc.widen else 1)
        episodes = sc.eval_episodes * (2 ** rnd if sc.widen else 1)
        scores = _run_round(alive, plans, states, sstates, cstates,
                            eval_root, rnd, sc, reward_fn, iters,
                            episodes)
        last_scores.update(scores)
        ranked = sorted(alive, key=lambda c: (-scores[c], c))
        survivors = sorted(ranked[:max(1, (len(alive) + 1) // 2)])
        history.append({
            "round": rnd, "iters": iters,
            "scores": {pool[c].label(): scores[c] for c in alive},
            "survivors": [pool[c].label() for c in survivors]})
        if log:
            log(history[-1])
        alive = survivors
        if ckpt_dir is not None:
            checkpoint.save_train_state(
                ckpt_dir, rnd, _ckpt_blob(alive, states, sstates,
                                          cstates),
                extra={"task": task,
                       "fingerprint": fingerprint,
                       "alive": alive,
                       "scores": {str(k): v
                                  for k, v in last_scores.items()},
                       "history": history})

    winner = ranked[0]
    controls = {pool[c].topo.family: last_scores[c]
                for c in range(len(pool))
                if pool[c].topo.family in sc.keep_families
                and c in last_scores}
    return SearchResult(
        winner=pool[winner], score=last_scores[winner],
        control_scores=controls, pool=pool, history=history,
        wall_s=time.time() - t0, n_agents=sc.n_agents)


def _search_fingerprint(task: str, sc: SearchConfig) -> str:
    """Identity of a search for resume validation: everything that
    shapes the pool, the candidate streams, or the round schedule —
    resuming a checkpoint written under a different (task, config)
    would silently mix states across searches. ``checkpoint_dir``
    itself is excluded (moving/copying a dir is a supported resume)."""
    d = dataclasses.asdict(sc)
    d.pop("checkpoint_dir")
    return json.dumps({"task": task, **d}, sort_keys=True, default=str)


def _ckpt_blob(alive: List[int], states: dict, sstates: dict,
               cstates: dict) -> dict:
    blob = {"netes": {str(c): states[c] for c in alive}}
    sched = {str(c): sstates[c] for c in alive if c in sstates}
    if sched:
        blob["sched"] = sched
    chan = {str(c): cstates[c] for c in alive if c in cstates}
    if chan:
        blob["chan"] = chan
    return blob
