"""NetES — Networked Evolution Strategies (paper Algorithm 1), single-host.

This module is the *algorithmic* core: a pure-JAX, fully-jittable
implementation of the NetES iteration over a stacked population
``thetas: (N, D)``. The distributed (shard_map over the mesh "data" axis)
version in ``repro/distributed`` reuses the same math with the population
axis carried by the mesh instead of by an array dimension.

Update rule (paper Eq. 3):

    θ_j ← θ_j + α/(Nσ²) Σ_i a_ij · R̃_i · ((θ_i + σ ε_i) − θ_j)

with R̃ the (optionally rank-shaped) returns. With a_ij ≡ 1 and identical
θ_i this reduces to standard ES (Eq. 1) — property-tested in
tests/test_netes_core.py.

Broadcast (paper Algorithm 1): with probability p_b per iteration, every
agent's θ is replaced by the best perturbed parameter argmax_j R_j
(θ_j + σ ε_j).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import es_utils, topology_repr


@dataclasses.dataclass(frozen=True)
class NetESConfig:
    alpha: float = 0.01            # learning rate α
    sigma: float = 0.02            # noise std σ
    p_broadcast: float = 0.8       # paper's global broadcast probability
    weight_decay: float = 0.005
    fitness_shaping: str = "centered_rank"   # centered_rank | normalize | none
    antithetic: bool = True
    # degree normalization: paper Eq. 3 divides by N for every agent. The
    # proof's intermediate steps use per-agent 1/|A_i| normalization
    # (Appendix Eq. 9). We default to the paper's main-text 1/N and expose
    # "degree" for the proof-faithful variant.
    normalization: str = "global"  # global (1/N) | degree (1/|A_i|)


class NetESState(NamedTuple):
    thetas: jax.Array        # (N, D) per-agent parameters
    key: jax.Array           # PRNG state
    step: jax.Array          # iteration counter
    best_reward: jax.Array   # running max raw reward (for eval protocol)
    best_theta: jax.Array    # (D,) argmax perturbed params seen so far


def init_state(key: jax.Array, n_agents: int, dim: int,
               init_fn: Optional[Callable[[jax.Array], jax.Array]] = None,
               same_init: bool = False) -> NetESState:
    """Initialize per-agent parameters.

    ``same_init=True`` reproduces the standard-ES setting (all agents share
    θ^(0)); False gives each agent its own draw (paper §2.1 generalization).
    """
    key, sub = jax.random.split(key)
    if init_fn is None:
        init_fn = lambda k: 0.1 * jax.random.normal(k, (dim,))
    if same_init:
        theta0 = init_fn(sub)
        thetas = jnp.broadcast_to(theta0, (n_agents,) + theta0.shape)
    else:
        thetas = jax.vmap(init_fn)(jax.random.split(sub, n_agents))
    return NetESState(
        thetas=thetas,
        key=key,
        step=jnp.zeros((), jnp.int32),
        # explicit dtype: a weak-typed scalar here would come back
        # strong-typed from the first fused scan, giving the second
        # same-shape chunk a NEW jit signature (one spurious recompile
        # mid-run — caught by the fleet bench's compile-count gate)
        best_reward=jnp.full((), -jnp.inf, jnp.float32),
        best_theta=thetas[0],
    )


def shape_fitness(returns: jax.Array, kind: str) -> jax.Array:
    if kind == "centered_rank":
        return es_utils.centered_rank(returns)
    if kind == "normalize":
        return es_utils.normalize_returns(returns)
    if kind == "none":
        return returns
    raise ValueError(f"unknown fitness shaping {kind!r}")


def mixing_update(adj, thetas: jax.Array, perturbed: jax.Array,
                  shaped: jax.Array, cfg: NetESConfig,
                  edge_mask=None) -> jax.Array:
    """Eq. 3, dispatched on the topology's physical representation.

    u_j = scale_j · Σ_i a_ji R̃_i (perturbed_i − θ_j)
        = scale_j · ( Σ_i a_ji R̃_i perturbed_i  −  (Σ_i a_ji R̃_i) θ_j )

    ``adj`` may be a raw (N, N) array (legacy call sites — treated as the
    dense representation) or a ``topology_repr.Topology``, in which case
    the contraction runs O(N²·D) dense, O(N·K·D) neighbor-gather, or
    O(N·|Δ|·D) roll-chain depending on ``topo.kind`` (DESIGN.md §3). All
    three paths are parity-tested against each other in
    tests/test_topology_repr.py. The dense hot loop is fused by
    kernels/netes_mixing; the sparse one by kernels/netes_sparse_mixing.

    ``edge_mask`` (DESIGN.md §11): a representation-matched live-link
    mask from a lossy channel — a dropped link removes source i's term
    from BOTH the neighbor sum and the self-correction weight (the
    receiver never saw the message at all).
    """
    topo = topology_repr.as_topology(adj)
    n = thetas.shape[0]
    mixed = topology_repr.weighted_neighbor_sum(topo, shaped, perturbed,
                                                edge_mask=edge_mask)
    wsum = topology_repr.weighted_row_sum(topo, shaped,
                                          edge_mask=edge_mask)[:, None]
    mixed = mixed - wsum * thetas                 # (N, D)
    if cfg.normalization == "degree":
        scale = cfg.alpha / (topo.deg[:, None] * cfg.sigma ** 2)
    else:
        scale = cfg.alpha / (n * cfg.sigma ** 2)
    return scale * mixed


@partial(jax.jit, static_argnames=("reward_fn", "cfg", "channel", "probes"))
def netes_step(state: NetESState, adj: jax.Array, reward_fn: Callable,
               cfg: NetESConfig, channel=None, chan_state=None,
               probes=None, metrics_state=None):
    """One NetES iteration (paper Algorithm 1).

    ``reward_fn(params: (M, D), key) -> (M,)`` evaluates a batch of
    parameter vectors (episode returns). M = N (or 2N antithetic).

    ``channel`` (optional): a ``comm.channel.Channel`` (jit-static) with
    its scan-carried ``chan_state`` (DESIGN.md §11). The per-source
    payloads entering the mixing — and the broadcast-best parameters —
    pass through the channel's encode pipeline; dropped links mask the
    contraction; trigger decisions and realized-traffic counters run on
    device. Returns ``(state', chan_state', metrics)`` instead of
    ``(state', metrics)``. A ``lossless`` channel is bit-identical to
    the channel-free path (parity-tested in tests/test_channel.py).

    ``probes`` (optional): a ``obs.probes.Probes`` (jit-static) with its
    scan-carried ``metrics_state`` ring buffer (DESIGN.md §15). Probes
    are pure reads of the metrics dict — no RNG, no trajectory dataflow
    — so the instrumented step is bit-for-bit identical to the plain
    one (tests/test_obs.py). The updated ``metrics_state`` joins the
    return value: ``(state'[, chan_state'], metrics_state', metrics)``.
    """
    n, dim = state.thetas.shape
    # Named scopes (DESIGN.md §15) put each part's name in its ops'
    # metadata, so a device trace reads layer time in place; they are
    # metadata only and change no numerics.
    # The perturbed parameters θ ± σε count as noise: XLA fuses the
    # threefry draw into the fusion that writes them.
    with jax.named_scope("noise"):
        key, k_eps, k_eval, k_beta = jax.random.split(state.key, 4)
        eps = jax.random.normal(k_eps, (n, dim), dtype=state.thetas.dtype)
    if cfg.antithetic:
        # evaluate ±ε; fold the pair back into a single effective sample by
        # using the return difference (standard mirrored-sampling estimator).
        with jax.named_scope("noise"):
            pert_pos = state.thetas + cfg.sigma * eps
            pert_neg = state.thetas - cfg.sigma * eps
        with jax.named_scope("reward"):
            r_pos = reward_fn(pert_pos, k_eval)
            r_neg = reward_fn(pert_neg, k_eval)
        with jax.named_scope("shaping"):
            raw = jnp.concatenate([r_pos, r_neg])
            shaped_all = shape_fitness(raw, cfg.fitness_shaping)
            shaped = shaped_all[:n] - shaped_all[n:]      # antithetic diff
        # broadcast/eval track the FULL population: both ±ε halves compete
        # for argmax (the −ε half is half the samples; dropping it biased
        # best_theta/best_reward toward +ε draws).
        rewards = raw
        with jax.named_scope("broadcast"):
            candidates = jnp.concatenate([pert_pos, pert_neg])
        perturbed = pert_pos
    else:
        with jax.named_scope("noise"):
            perturbed = state.thetas + cfg.sigma * eps
        with jax.named_scope("reward"):
            rewards = reward_fn(perturbed, k_eval)
        with jax.named_scope("shaping"):
            shaped = shape_fitness(rewards, cfg.fitness_shaping)
        candidates = perturbed

    # ---- lossy channel (DESIGN.md §11): encode the per-source payload,
    # draw this step's live-link mask, advance the channel state. Fused-
    # eligible quantizing channels on sparse graphs keep the payload in
    # WIRE FORM (apply_wire → WirePayload) so the mixing contraction
    # reads the int8 codes directly (DESIGN.md §12); the dispatch is
    # trace-time static (channel and topo.kind are jit-static), so the
    # compiled scan is branch-free either way.
    wire, edge_mask, chan_info = perturbed, None, None
    if channel is not None:
        with jax.named_scope("channel"):
            topo = topology_repr.as_topology(adj)
            chan_apply = (channel.apply_wire if channel.wire_fused(topo)
                          else channel.apply)
            wire, edge_mask, chan_state, chan_info = chan_apply(
                chan_state, topo, perturbed)

    with jax.named_scope("mixing"):
        update = mixing_update(adj, state.thetas, wire, shaped, cfg,
                               edge_mask=edge_mask)
        update = es_utils.apply_weight_decay(state.thetas, update,
                                             cfg.weight_decay)
        new_thetas = state.thetas + update

    # ---- broadcast event (exploit) ----
    with jax.named_scope("broadcast"):
        best_idx = jnp.argmax(rewards)
        iter_best_theta = candidates[best_idx]
        iter_best_reward = rewards[best_idx]
        beta = jax.random.uniform(k_beta)
        do_broadcast = beta < cfg.p_broadcast
        # the broadcast payload rides the same wire: lossy codecs apply
        # (the receivers adopt the DEGRADED best — what they actually
        # got); eval/best_theta bookkeeping keeps the true argmax
        # parameters.
        if (channel is not None and channel.fused
                and channel.wire_quantized):
            # fused variant: decode-where-flagged in one pass over θ — the
            # decoded (D,) + broadcast (N, D) round-trip never materializes
            from repro.kernels import netes_fused_mixing as _nfm
            wp = channel.encode_wire(iter_best_theta, batched=False)
            new_thetas = _nfm.fused_broadcast_select(
                wp.codes, wp.scale, do_broadcast, new_thetas)
        else:
            bcast_theta = (iter_best_theta if channel is None
                           else channel.codec(iter_best_theta,
                                              batched=False))
            new_thetas = jnp.where(do_broadcast,
                                   jnp.broadcast_to(bcast_theta,
                                                    new_thetas.shape),
                                   new_thetas)

        better = iter_best_reward > state.best_reward
        new_state = NetESState(
            thetas=new_thetas,
            key=key,
            step=state.step + 1,
            best_reward=jnp.where(better, iter_best_reward,
                                  state.best_reward),
            best_theta=jnp.where(better, iter_best_theta, state.best_theta),
        )
    with jax.named_scope("stats"):
        metrics = {
            "reward_mean": rewards.mean(),
            "reward_max": rewards.max(),
            "reward_min": rewards.min(),
            "reward_std": rewards.std(),                 # fitness dispersion
            "update_var": jnp.var(update, axis=0).sum(),  # Thm 7.1 LHS proxy
            "broadcast": do_broadcast.astype(jnp.float32),
            "theta_spread": jnp.var(new_thetas, axis=0).sum(),
        }
        if channel is not None:
            # broadcast is one message fanned out to the population
            bcast_msgs = do_broadcast.astype(jnp.float32) * n
            msgs = chan_info["msgs"] + bcast_msgs
            chan_state = chan_state._replace(
                msgs=chan_state.msgs + bcast_msgs)
            metrics["msgs"] = msgs
            metrics["trigger_frac"] = chan_info["trigger_frac"]
            metrics["drop_frac"] = chan_info["drop_frac"]
            if probes is not None:
                metrics_state = probes.record(
                    metrics_state, metrics, topology_repr.as_topology(adj))
                return new_state, chan_state, metrics_state, metrics
            return new_state, chan_state, metrics
        if probes is not None:
            metrics_state = probes.record(
                metrics_state, metrics, topology_repr.as_topology(adj))
            return new_state, metrics_state, metrics
        return new_state, metrics


@partial(jax.jit,
         static_argnames=("reward_fn", "cfg", "num_iters", "channel",
                          "probes"))
def _run_jit(state: NetESState, adj: jax.Array, reward_fn: Callable,
             cfg: NetESConfig, num_iters: int, channel=None,
             chan_state=None, probes=None, metrics_state=None):
    if channel is not None and probes is not None:
        def cpbody(carry, _):
            s, cs, ms = carry
            s, cs, ms, m = netes_step(s, adj, reward_fn, cfg, channel, cs,
                                      probes, ms)
            return (s, cs, ms), m

        (state, chan_state, metrics_state), metrics = jax.lax.scan(
            cpbody, (state, chan_state, metrics_state), None,
            length=num_iters)
        return state, chan_state, metrics_state, metrics

    if channel is not None:
        def cbody(carry, _):
            s, cs = carry
            s, cs, m = netes_step(s, adj, reward_fn, cfg, channel, cs)
            return (s, cs), m

        (state, chan_state), metrics = jax.lax.scan(
            cbody, (state, chan_state), None, length=num_iters)
        return state, chan_state, metrics

    if probes is not None:
        def pbody(carry, _):
            s, ms = carry
            s, ms, m = netes_step(s, adj, reward_fn, cfg,
                                  probes=probes, metrics_state=ms)
            return (s, ms), m

        (state, metrics_state), metrics = jax.lax.scan(
            pbody, (state, metrics_state), None, length=num_iters)
        return state, metrics_state, metrics

    def body(s, _):
        s, m = netes_step(s, adj, reward_fn, cfg)
        return s, m

    state, metrics = jax.lax.scan(body, state, None, length=num_iters)
    return state, metrics


def run(state: NetESState, adj: jax.Array, reward_fn: Callable,
        cfg: NetESConfig, num_iters: int, channel=None, chan_state=None,
        *, probes=None, metrics_state=None, mesh=None):
    """lax.scan driver over ``netes_step`` (fully on-device training loop).

    Jitted one level down (``_run_jit``) so repeat calls with the same
    shapes hit the executable cache: an EAGER ``lax.scan`` re-traces its
    body every call and its fresh jaxpr misses the primitive-dispatch
    cache, recompiling the scan shell once per eval chunk.

    With a ``channel`` (DESIGN.md §11) the ``ChannelState`` joins the
    scan carry — every encode, trigger decision, and edge drop runs
    inside the same compiled scan — and the return value becomes
    ``(state, chan_state, metrics)``.

    With a ``mesh`` (DESIGN.md §13) the fleet runs agent-sharded via
    ``distributed.fleet_shard`` — same return shapes, halo/all-gather
    collectives between shards. The sharded engine draws this
    module's single (N, D) noise, each shard its own rows, so it equals
    this path up to the order of its reductions (and, on the CPU, is
    bitwise identical across mesh sizes, including mesh size 1).

    With ``probes`` (DESIGN.md §15) the ``MetricsState`` ring joins the
    scan carry and the return value grows by one element before the
    stacked metrics: ``(state[, chan_state], metrics_state, metrics)``.
    Probes never touch the trajectory — instrumented ≡ uninstrumented
    bit-for-bit, and ``probes=None`` takes the pre-existing branch."""
    if mesh is not None:
        from repro.distributed import fleet_shard
        return fleet_shard.run_sharded(
            state, adj, reward_fn, cfg, num_iters, mesh,
            channel=channel, chan_state=chan_state,
            probes=probes, metrics_state=metrics_state)
    return _run_jit(state, adj, reward_fn, cfg, num_iters, channel,
                    chan_state, probes, metrics_state)


# ---------------------------------------------------------------------------
# scheduled (time-varying) topologies — DESIGN.md §9
# ---------------------------------------------------------------------------

def _advance(schedule, sched_state):
    """The topology schedule's on-device step, under its named scope."""
    with jax.named_scope("schedule"):
        return schedule.advance(sched_state)


@partial(jax.jit,
         static_argnames=("reward_fn", "cfg", "schedule", "channel",
                          "probes"))
def scheduled_step(state: NetESState, sched_state, reward_fn: Callable,
                   cfg: NetESConfig, schedule, channel=None,
                   chan_state=None, probes=None, metrics_state=None):
    """One NetES iteration under a ``topology_sched.TopologySchedule``:
    step on the topology in force, then advance the schedule on device.
    Returns ``(state', sched_state', metrics)`` — with a ``channel``,
    ``(state', sched_state', chan_state', metrics)``. With ``probes``
    the ring state is threaded through and returned before the metrics;
    the ``graph`` probe stage reads the LIVE (pre-advance) topology, so
    the recorded health series tracks the schedule step by step."""
    if channel is not None:
        if probes is not None:
            state, chan_state, metrics_state, metrics = netes_step(
                state, sched_state.topo, reward_fn, cfg, channel,
                chan_state, probes, metrics_state)
            return (state, _advance(schedule, sched_state), chan_state,
                    metrics_state, metrics)
        state, chan_state, metrics = netes_step(
            state, sched_state.topo, reward_fn, cfg, channel, chan_state)
        return state, _advance(schedule, sched_state), chan_state, metrics
    if probes is not None:
        state, metrics_state, metrics = netes_step(
            state, sched_state.topo, reward_fn, cfg,
            probes=probes, metrics_state=metrics_state)
        return (state, _advance(schedule, sched_state), metrics_state,
                metrics)
    state, metrics = netes_step(state, sched_state.topo, reward_fn, cfg)
    return state, _advance(schedule, sched_state), metrics


@partial(jax.jit,
         static_argnames=("reward_fn", "cfg", "schedule", "num_iters",
                          "channel", "probes"))
def _run_scheduled_jit(state: NetESState, sched_state,
                       reward_fn: Callable, cfg: NetESConfig, schedule,
                       num_iters: int, channel=None, chan_state=None,
                       probes=None, metrics_state=None):
    if channel is not None and probes is not None:
        def cpbody(carry, _):
            s, ss, cs, ms = carry
            s, cs, ms, m = netes_step(s, ss.topo, reward_fn, cfg,
                                      channel, cs, probes, ms)
            return (s, _advance(schedule, ss), cs, ms), m

        (state, sched_state, chan_state, metrics_state), metrics = \
            jax.lax.scan(cpbody,
                         (state, sched_state, chan_state, metrics_state),
                         None, length=num_iters)
        return state, sched_state, chan_state, metrics_state, metrics

    if channel is not None:
        def cbody(carry, _):
            s, ss, cs = carry
            s, cs, m = netes_step(s, ss.topo, reward_fn, cfg, channel, cs)
            return (s, _advance(schedule, ss), cs), m

        (state, sched_state, chan_state), metrics = jax.lax.scan(
            cbody, (state, sched_state, chan_state), None,
            length=num_iters)
        return state, sched_state, chan_state, metrics

    if probes is not None:
        def pbody(carry, _):
            s, ss, ms = carry
            s, ms, m = netes_step(s, ss.topo, reward_fn, cfg,
                                  probes=probes, metrics_state=ms)
            return (s, _advance(schedule, ss), ms), m

        (state, sched_state, metrics_state), metrics = jax.lax.scan(
            pbody, (state, sched_state, metrics_state), None,
            length=num_iters)
        return state, sched_state, metrics_state, metrics

    def body(carry, _):
        s, ss = carry
        s, m = netes_step(s, ss.topo, reward_fn, cfg)
        return (s, _advance(schedule, ss)), m

    (state, sched_state), metrics = jax.lax.scan(
        body, (state, sched_state), None, length=num_iters)
    return state, sched_state, metrics


def run_scheduled(state: NetESState, sched_state, reward_fn: Callable,
                  cfg: NetESConfig, schedule, num_iters: int,
                  channel=None, chan_state=None, *, probes=None,
                  metrics_state=None, mesh=None):
    """``run`` with the topology state joined into the scan carry: the
    graph anneals/resamples/rotates ON DEVICE inside one compiled scan
    (no per-resample re-trace, no host round-trips). Returns
    ``(state, sched_state, metrics)`` — with a ``channel``, the channel
    state joins the carry too and the return value becomes
    ``(state, sched_state, chan_state, metrics)``.

    With a ``mesh`` the fleet runs agent-sharded through
    ``distributed.fleet_shard`` (replicated-mixing mode: schedules
    mutate the live topology, so payloads are all-gathered and each
    shard keeps its own row slab — DESIGN.md §13).

    ``probes``/``metrics_state`` as in ``run``: the ring joins the carry
    and is returned right before the stacked metrics."""
    if mesh is not None:
        from repro.distributed import fleet_shard
        return fleet_shard.run_sharded_scheduled(
            state, sched_state, reward_fn, cfg, schedule, num_iters,
            mesh, channel=channel, chan_state=chan_state,
            probes=probes, metrics_state=metrics_state)
    return _run_scheduled_jit(state, sched_state, reward_fn, cfg,
                              schedule, num_iters, channel, chan_state,
                              probes, metrics_state)


# ---------------------------------------------------------------------------
# Standard ES (paper Eq. 1) — the fully-connected / shared-θ baseline.
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("reward_fn", "cfg", "n_agents"))
def es_step(theta: jax.Array, key: jax.Array, reward_fn: Callable,
            cfg: NetESConfig, n_agents: int) -> Tuple[jax.Array, jax.Array, dict]:
    """One standard-ES iteration on a single global θ (the paper's baseline)."""
    key, k_eps, k_eval = jax.random.split(key, 3)
    eps = jax.random.normal(k_eps, (n_agents,) + theta.shape, dtype=theta.dtype)
    if cfg.antithetic:
        r_pos = reward_fn(theta[None] + cfg.sigma * eps, k_eval)
        r_neg = reward_fn(theta[None] - cfg.sigma * eps, k_eval)
        raw = jnp.concatenate([r_pos, r_neg])
        shaped_all = shape_fitness(raw, cfg.fitness_shaping)
        shaped = shaped_all[:n_agents] - shaped_all[n_agents:]
        rewards = raw   # metrics over BOTH ±ε halves (same as netes_step)
    else:
        rewards = reward_fn(theta[None] + cfg.sigma * eps, k_eval)
        shaped = shape_fitness(rewards, cfg.fitness_shaping)
    grad = (shaped[:, None] * eps).sum(axis=0) / (n_agents * cfg.sigma)
    update = cfg.alpha * grad
    update = es_utils.apply_weight_decay(theta, update, cfg.weight_decay)
    metrics = {"reward_mean": rewards.mean(), "reward_max": rewards.max()}
    return theta + update, key, metrics


# ---------------------------------------------------------------------------
# static-analysis registry hook (repro.analysis — DESIGN.md §14)
# ---------------------------------------------------------------------------

def analysis_entry_points():
    """Contract-linter entry points: the compiled run drivers this module
    owns, traced at toy size (N=8, D=16). ``build`` closures construct
    fresh operands each call; nothing here executes — the linter only
    traces via ``jax.make_jaxpr``."""
    from repro.analysis.registry import EntryPoint

    def _reward(params, key):
        return -jnp.sum(params * params, axis=-1)

    def _toy_state(n=8, d=16):
        return init_state(jax.random.PRNGKey(0), n, d)

    def _toy_adj(n=8):
        from repro.core.topology import TopologySpec
        return jnp.asarray(TopologySpec(family="erdos_renyi", n_agents=n,
                                        p=0.5, seed=0).build())

    def build_run():
        cfg = NetESConfig()
        return (lambda s, a: _run_jit(s, a, _reward, cfg, 3),
                (_toy_state(), _toy_adj()), {})

    def build_run_q8():
        from repro.comm.channel import compile_channel
        cfg = NetESConfig()
        chan = compile_channel("quantize(bits=8)", 8)
        state = _toy_state()
        cs = chan.init(state.thetas)
        return (lambda s, a, c: _run_jit(s, a, _reward, cfg, 3, chan, c),
                (state, _toy_adj(), cs), {})

    def build_run_scheduled():
        from repro.core.topology import TopologySpec
        from repro.core.topology_sched import ScheduleSpec, compile_schedule
        cfg = NetESConfig()
        base = TopologySpec(family="erdos_renyi", n_agents=8, p=0.5, seed=0)
        schedule = compile_schedule(ScheduleSpec(kind="resample_er",
                                                 period=2), base)
        return (lambda s, t: _run_scheduled_jit(s, t, _reward, cfg,
                                                schedule, 3),
                (_toy_state(), schedule.init()), {})

    return (
        EntryPoint(name="netes.run", build=build_run),
        EntryPoint(name="netes.run.q8", build=build_run_q8),
        EntryPoint(name="netes.run_scheduled", build=build_run_scheduled),
    )
