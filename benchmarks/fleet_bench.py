"""Fleet-scale benchmark: the paper's N ≈ 1000 regime, measured.

The headline claim (Fig. 2B: 1000 Erdos-Renyi agents ≈ 3000
fully-connected agents) lives at a scale the paper-figure benches never
touch — they run N ≤ 40 so RL rollouts fit the CI budget. This bench
populates the scale axis: a lax.scan-chunked **1024-agent** NetES run
end-to-end through ``train_rl_netes`` (landscape task, so reward
evaluation is a cheap batched function and the measured cost is the
mixing/update path under test), once per physical representation:

* ``dense``     — (N, N) adjacency, masked-matmul backend;
* ``sparse``    — same ER graph, padded neighbor-list backend;
* ``circulant`` — same-density circulant-ER, roll-chain backend.

Per representation it reports the measured per-iteration step time and
the **modeled distributed wire bytes** per chip-step at production scale
(``benchmarks/perfmodel.py``) — the metric sparse topologies are judged
on (DESIGN.md §3/§8). Dense and sparse run the SAME graph and seeds, so
their eval traces must agree — an end-to-end representation parity check
at N = 1024.

Scheduled-topology entries (DESIGN.md §9) run the same 1024-agent loop
with the graph EVOLVING on device inside the fused scan —
``resample_er(period=8)`` over the sparse payload, ``rotate_circulant``
over traced ppermute/roll offsets (zero extra wire bytes), and a density
anneal over the dense mask. Every timed run (static AND scheduled) is
replayed after a same-shape warm-up under a compile counter and must
trigger ZERO XLA compilations: that is the "one scan, no per-resample
retrace" acceptance gate — a schedule that re-traced per graph would
show extra compiles here.

Quantized-channel entries (``chan_q8/q4/q1`` and their ``_unfused``
controls, DESIGN.md §12) run the same sparse 1024-agent loop under a
wire-quantizing channel twice — through the wire path and through the
fake-quant control — and gate that the wire path matches the control's
trajectory exactly while landing at or below its step time.

Every gated step time is the MEDIAN over ``TIMED_REPLAYS`` warmed
replays, with per-replay min/max recorded in the artifact, so a single
scheduler hiccup cannot trip the ±30% wall gate.

Two satellite legs make this the one path that exercises every layer the
topology travels through:

* ``fleet.replica_step`` — a nano-LM replica train step built through
  ``launch/specs.build_step`` (PairSpec.topo → ``topology_repr``-selected
  backend inside ``distributed/netes_dist.make_replica_train_step``);
* ``fleet.sparse_kernel`` — the Pallas sparse-mixing kernel
  (``kernels/netes_sparse_mixing``, interpret mode on CPU) against the
  jnp reference on an ER slice of the fleet's density.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import channel as comm_channel
from repro.core import topology, topology_repr
from repro.core.netes import NetESConfig
from repro.core.topology import TopologySpec
from repro.train.loop import (TrainConfig, build_schedule, build_topology,
                              train_rl_netes)

from . import common, perfmodel, registry

N_FLEET = 1024
P_FLEET = 0.1        # the paper's sparse regime (Fig. 2B / Fig. 5)

# (family, representation): dense and sparse share the ER graph so their
# runs are bit-comparable; circulant needs the vertex-transitive family.
REPRESENTATIONS = [
    ("erdos_renyi", "dense"),
    ("erdos_renyi", "sparse"),
    ("circulant_erdos_renyi", "circulant"),
]


def _fan_in(topo: topology_repr.Topology) -> int:
    """Per-agent distributed fetch count of the representation's wire
    format: K_max neighbor fetches (sparse), |±Δ| ppermute hops
    (circulant, static or traced), full all-gather (dense)."""
    if topo.kind == "sparse":
        return topo.k_max
    if topo.kind == "circulant":
        if topo.shifts is not None:
            return int(topo.shifts.shape[0])
        return len(topology_repr.signed_offsets(topo.offsets, topo.n))
    return topo.n


# Timed replays per leg: the gated step time is the MEDIAN over these,
# so one scheduler hiccup on a shared runner moves an extreme (recorded
# in the artifact), not the ±30%-gated number.
TIMED_REPLAYS = 3


def _run_fleet_tc(tc: TrainConfig, chunk: int):
    """Warm-up + compile-counted timed replays.
    Returns (hist, compiles, step_times).

    The warm-up at iters=chunk compiles the SAME lax.scan (one chunk,
    one eval) the timed runs replay, so the gated step time is
    steady-state — first-jit of the 1024-agent scan is tens of seconds
    and would otherwise dominate (and flap ±30%) at ci scale. The timed
    replays must then compile NOTHING: any recompile (e.g. a schedule
    that re-traced per resample) shows up in the returned count and
    fails the one-scan assertion in ``fleet_netes``.

    ``step_times`` holds one per-iteration time per replay — the first
    from the full-length run (whose ``hist`` carries the gated eval),
    the rest from chunk-length replays of the same warmed scan. Callers
    gate ``median(step_times)`` and record min/max in the entry extra.
    """
    train_rl_netes("landscape:rastrigin",
                   dataclasses.replace(tc, iters=chunk))
    step_times = []
    with common.count_backend_compiles() as counts:
        hist = train_rl_netes("landscape:rastrigin", tc)
        step_times.append(hist["wall_s"] / tc.iters)
        for _ in range(TIMED_REPLAYS - 1):
            h = train_rl_netes("landscape:rastrigin",
                               dataclasses.replace(tc, iters=chunk))
            step_times.append(h["wall_s"] / chunk)
    return hist, len(counts), step_times


def fleet_netes(quick: bool = False):
    """The 1024-agent end-to-end runs. Returns [Entry]."""
    iters = 6 if quick else 24
    chunk = max(1, iters // 2)
    entries = []
    finals = {}
    compile_counts = {}
    for family, rep in REPRESENTATIONS:
        tc = TrainConfig(
            n_agents=N_FLEET, iters=iters,
            topology=TopologySpec(family=family, n_agents=N_FLEET,
                                  p=P_FLEET, seed=0),
            representation=rep, seed=0,
            eval_every=chunk, eval_episodes=4,
            netes=NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.8))
        topo = build_topology(tc)
        assert topo.kind == rep, (topo.kind, rep)
        hist, compiles, samples = _run_fleet_tc(tc, chunk)
        step_s = float(np.median(samples))
        fan_in = _fan_in(topo)
        wire = perfmodel.wire_bytes(N_FLEET, fan_in, rep)
        finals[rep] = hist["final_eval"]
        compile_counts[rep] = compiles
        common.emit(
            f"fleet.netes{N_FLEET}.{rep}", step_s,
            f"fan_in={fan_in} wire_mb={wire / 2 ** 20:.0f} "
            f"final={hist['final_eval']:.2f}")
        entries.append(registry.Entry(
            name=f"fleet.netes{N_FLEET}.{rep}",
            wall_s=step_s,
            wire_bytes=wire,
            eval_score=hist["final_eval"],
            extra={"n": N_FLEET, "p": P_FLEET, "iters": iters,
                   "family": family, "fan_in": fan_in,
                   "total_wall_s": hist["wall_s"],
                   "step_s_min": float(min(samples)),
                   "step_s_max": float(max(samples)),
                   "step_s_replays": len(samples),
                   "max_eval": hist["max_eval"],
                   "timed_compiles": compiles,
                   "model_step_us": perfmodel.modeled_step_us(
                       N_FLEET, fan_in, rep)}))
    # representation parity at N=1024: same graph + seeds ⇒ same training
    # trajectory for the dense and sparse backends.
    assert abs(finals["dense"] - finals["sparse"]) <= \
        1e-3 * max(1.0, abs(finals["dense"])), finals
    # EVERY static representation must replay compile-free — not just
    # dense (a retrace in the sparse/circulant dispatch would otherwise
    # only show up in entry extras, never fail CI).
    assert all(c == 0 for c in compile_counts.values()), (
        f"static timed runs recompiled: {compile_counts}")
    entries += fleet_scheduled(quick=quick,
                               static_compiles=compile_counts["dense"])
    entries += fleet_channels(quick=quick)
    return entries


# (name_suffix, family, representation, schedule_str); the schedule
# string's horizon placeholder is filled per profile.
SCHEDULES = [
    ("sched_resample_er", "erdos_renyi", "sparse",
     "resample_er(period=8)"),
    ("sched_rotate_circulant", "circulant_erdos_renyi", "circulant",
     "rotate_circulant(stride=1)"),
    ("sched_anneal_density", "erdos_renyi", "dense",
     "anneal_density(p_end=0.02,horizon={iters})"),
]


def fleet_scheduled(quick: bool = False, static_compiles: int = 0):
    """Scheduled-topology runs at N=1024 (DESIGN.md §9): same fused-scan
    loop, graph evolving on device. Asserts the acceptance contract —
    each scheduled timed run shows the SAME compile count as the static
    run (both zero after warm-up: one scan, no per-resample retrace)."""
    # 16 quick iters (vs 6 static) so period=8 actually fires a redraw
    # inside the ci run; 24 full = three redraws.
    iters = 16 if quick else 24
    chunk = iters // 2
    entries = []
    for suffix, family, rep, sched_tpl in SCHEDULES:
        sched_str = sched_tpl.format(iters=iters)
        tc = TrainConfig(
            n_agents=N_FLEET, iters=iters,
            topology=TopologySpec(family=family, n_agents=N_FLEET,
                                  p=P_FLEET, seed=0),
            representation=rep, schedule=sched_str, seed=0,
            eval_every=chunk, eval_episodes=4,
            netes=NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.8))
        schedule = build_schedule(tc)
        topo0 = schedule.init().topo
        assert topo0.kind == rep, (topo0.kind, rep)
        hist, compiles, samples = _run_fleet_tc(tc, chunk)
        assert compiles == static_compiles == 0, (
            f"{suffix}: scheduled timed run compiled {compiles}× vs "
            f"static {static_compiles}× — the schedule left the fused "
            "scan (per-resample retrace?)")
        step_s = float(np.median(samples))
        fan_in = _fan_in(topo0)
        wire = perfmodel.wire_bytes(N_FLEET, fan_in, rep)
        common.emit(
            f"fleet.netes{N_FLEET}.{suffix}", step_s,
            f"fan_in={fan_in} wire_mb={wire / 2 ** 20:.0f} "
            f"final={hist['final_eval']:.2f} compiles={compiles}")
        entries.append(registry.Entry(
            name=f"fleet.netes{N_FLEET}.{suffix}",
            wall_s=step_s,
            wire_bytes=wire,
            eval_score=hist["final_eval"],
            extra={"n": N_FLEET, "p": P_FLEET, "iters": iters,
                   "family": family, "fan_in": fan_in,
                   "schedule": sched_str,
                   "representation": rep,
                   "k_max": schedule.k_max,
                   "total_wall_s": hist["wall_s"],
                   "step_s_min": float(min(samples)),
                   "step_s_max": float(max(samples)),
                   "step_s_replays": len(samples),
                   "max_eval": hist["max_eval"],
                   "timed_compiles": compiles,
                   "model_step_us": perfmodel.modeled_step_us(
                       N_FLEET, fan_in, rep)}))
    return entries


# The wire-quantized channels (DESIGN.md §12): (entry suffix, bits).
CHANNEL_BITS = [("q8", 8), ("q4", 4), ("q1", 1)]

# One-sided fused-vs-unfused step-time gate slack: the fused path must
# land at-or-below its unfused control modulo same-machine replay noise
# (both medians come from the same process, same warmed cache — this is
# NOT the cross-machine ±30% wall gate, which baselines apply per leg;
# measured jitter between two same-cost medians on a shared runner is
# up to ~10%).
FUSED_SLACK = 1.2


def fleet_channels(quick: bool = False):
    """Quantized-channel legs at N=1024 (the tentpole's measured gate):
    the sparse ER fleet run under q8/q4/q1 wire channels, once through
    the wire path (``channel_fused=True``, the default —
    ``weighted_neighbor_sum`` receives the WirePayload and decodes it
    once; the broadcast-best payload goes through
    ``kernels/netes_fused_mixing.fused_broadcast_select``) and once
    through the fake-quant control (``channel_fused=False``).

    Gates, per bit-width:

    * fused and unfused runs follow the SAME training trajectory (the
      wire path is exact w.r.t. the codec, not approximately so);
    * both replay compile-free (the WirePayload pytree lives in the
      scan like any other carry — no per-step retrace);
    * fused median step time ≤ unfused × ``FUSED_SLACK`` — the wire
      path costs no more than the control, end-to-end at fleet scale.

    Baselines additionally hold each leg's wire bytes (exact — fusion
    never changes what moves on the wire) and step time (±30%).
    """
    iters = 6 if quick else 24
    chunk = max(1, iters // 2)
    entries = []
    meds = {}
    finals = {}
    for suffix, bits in CHANNEL_BITS:
        chan_str = f"quantize(bits={bits})"
        for fused in (True, False):
            name = (f"fleet.netes{N_FLEET}.chan_{suffix}"
                    + ("" if fused else "_unfused"))
            tc = TrainConfig(
                n_agents=N_FLEET, iters=iters,
                topology=TopologySpec(family="erdos_renyi",
                                      n_agents=N_FLEET, p=P_FLEET,
                                      seed=0),
                representation="sparse", channel=chan_str,
                channel_fused=fused, seed=0,
                eval_every=chunk, eval_episodes=4,
                netes=NetESConfig(alpha=0.05, sigma=0.1,
                                  p_broadcast=0.8))
            topo = build_topology(tc)
            assert topo.kind == "sparse", topo.kind
            hist, compiles, samples = _run_fleet_tc(tc, chunk)
            assert compiles == 0, (
                f"{name}: timed replays recompiled {compiles}× — the "
                "wire payload left the fused scan")
            channel = comm_channel.compile_channel(chan_str, N_FLEET,
                                                   fused=fused)
            fan_in = _fan_in(topo)
            wire = perfmodel.wire_bytes(N_FLEET, fan_in, "sparse",
                                        elem_bytes=channel.elem_bytes)
            step_s = float(np.median(samples))
            meds[(suffix, fused)] = step_s
            finals[(suffix, fused)] = hist["final_eval"]
            common.emit(
                name, step_s,
                f"fan_in={fan_in} wire_mb={wire / 2 ** 20:.1f} "
                f"final={hist['final_eval']:.2f} fused={fused}")
            entries.append(registry.Entry(
                name=name,
                wall_s=step_s,
                wire_bytes=wire,
                eval_score=hist["final_eval"],
                extra={"n": N_FLEET, "p": P_FLEET, "iters": iters,
                       "channel": chan_str, "fused": fused,
                       "fan_in": fan_in,
                       "elem_bytes": channel.elem_bytes,
                       "total_wall_s": hist["wall_s"],
                       "step_s_min": float(min(samples)),
                       "step_s_max": float(max(samples)),
                       "step_s_replays": len(samples),
                       "max_eval": hist["max_eval"],
                       "timed_compiles": compiles,
                       "model_step_us": perfmodel.modeled_step_us(
                           N_FLEET, fan_in, "sparse",
                           elem_bytes=channel.elem_bytes,
                           codec_stages=1)}))
    for suffix, _bits in CHANNEL_BITS:
        f_eval, u_eval = finals[(suffix, True)], finals[(suffix, False)]
        assert abs(f_eval - u_eval) <= 1e-3 * max(1.0, abs(u_eval)), (
            f"chan_{suffix}: fused trajectory diverged from unfused "
            f"({f_eval} vs {u_eval}) — the wire path is not codec-exact")
        f_t, u_t = meds[(suffix, True)], meds[(suffix, False)]
        assert f_t <= u_t * FUSED_SLACK, (
            f"chan_{suffix}: fused median step {f_t * 1e3:.1f}ms above "
            f"unfused control {u_t * 1e3:.1f}ms × {FUSED_SLACK} — the "
            "wire path costs more than the fake-quant control")
    return entries


# Probe-overhead gate: the instrumented fleet run's median step time
# must land within 5% of the uninstrumented control's (DESIGN.md §15 —
# the ring-buffer carry is a handful of scalar reductions + one
# dynamic_update_slice per iteration, amortized over a 1024-agent
# mixing step). Both medians come from the same process and the same
# warmed cache, like the fused-vs-unfused gate above.
PROBE_SLACK = 1.05


def fleet_probed(quick: bool = False):
    """``fleet.netes1024.probed`` (DESIGN.md §15): the sparse ER fleet
    run with the full non-channel probe zoo (fitness dispersion,
    consensus distance, topology health) joined into the scan carry,
    against an uninstrumented control in the same process. Gates:

    * the probed trajectory is IDENTICAL to the control's — train
      rewards and eval scores match float-for-float (probes are pure
      reads; they consume no RNG and perturb no dataflow);
    * the probed timed replays compile NOTHING (the MetricsState is
      ordinary scan carry — no retrace, no host callback);
    * probed median step time ≤ control × ``PROBE_SLACK`` — telemetry
      that costs more than 5% is not "always-on" telemetry.

    The drained probe series lands in the entry extras (per-signal
    first/last/mean), so registry baselines version the telemetry
    itself alongside the step time.
    """
    iters = 6 if quick else 24
    chunk = max(1, iters // 2)
    tc = TrainConfig(
        n_agents=N_FLEET, iters=iters,
        topology=TopologySpec(family="erdos_renyi", n_agents=N_FLEET,
                              p=P_FLEET, seed=0),
        representation="sparse", seed=0,
        eval_every=chunk, eval_episodes=4,
        netes=NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.8))
    tc_probed = dataclasses.replace(tc,
                                    probes="fitness|consensus|graph")
    hist_u, _, samples_u = _run_fleet_tc(tc, chunk)
    hist_p, compiles_p, samples_p = _run_fleet_tc(tc_probed, chunk)

    assert hist_p["eval"] == hist_u["eval"], (
        "probed eval trajectory diverged from uninstrumented control: "
        f"{hist_p['eval']} vs {hist_u['eval']}")
    assert hist_p["reward_mean"] == hist_u["reward_mean"], (
        "probed train rewards diverged from uninstrumented control")
    assert compiles_p == 0, (
        f"probed timed replays recompiled {compiles_p}× — the metrics "
        "ring left the fused scan")
    med_u = float(np.median(samples_u))
    med_p = float(np.median(samples_p))
    assert med_p <= med_u * PROBE_SLACK, (
        f"probe overhead {med_p / med_u - 1:+.1%} above the "
        f"{PROBE_SLACK - 1:.0%} gate (probed {med_p * 1e3:.1f}ms vs "
        f"unprobed {med_u * 1e3:.1f}ms median step)")

    series = hist_p["probes"]
    summary = {k: {"first": float(v[0]), "last": float(v[-1]),
                   "mean": float(np.mean(v))}
               for k, v in series.items() if isinstance(v, np.ndarray)}
    common.emit(
        f"fleet.netes{N_FLEET}.probed", med_p,
        f"overhead={med_p / med_u - 1:+.1%} "
        f"consensus_last={summary['consensus_dist']['last']:.3g} "
        f"final={hist_p['final_eval']:.2f}")
    return [registry.Entry(
        name=f"fleet.netes{N_FLEET}.probed",
        wall_s=med_p,
        eval_score=hist_p["final_eval"],
        extra={"n": N_FLEET, "p": P_FLEET, "iters": iters,
               "probes": "fitness|consensus|graph",
               "unprobed_step_s": med_u,
               "overhead_frac": med_p / med_u - 1,
               "step_s_min": float(min(samples_p)),
               "step_s_max": float(max(samples_p)),
               "step_s_replays": len(samples_p),
               "timed_compiles": compiles_p,
               "probe_cursor": int(series["cursor"]),
               "probe_dropped": int(series["dropped"]),
               "probe_series": summary})]


def replica_step(quick: bool = False):
    """Nano-LM replica step built via launch/specs with a PairSpec.topo —
    the full launch-layer topology path at fleet-bench cost."""
    from repro.configs import get_config
    from repro.data import make_batch
    from repro.launch import specs
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer

    cfg = dataclasses.replace(
        get_config("mistral-nemo-12b-smoke"), name="fleet-nano",
        num_layers=1, d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=128)
    n = 16
    topo_spec = TopologySpec(family="erdos_renyi", n_agents=n, p=0.15,
                             seed=0)
    pair = specs.PairSpec(arch=cfg.name, shape_name="fleet_nano",
                          mode="replica", kind="train", cfg=cfg,
                          n_agents=n, topo=topo_spec)
    topo = topology_repr.from_spec(topo_spec)
    step, _order = specs.build_step(pair, make_host_mesh())
    step = jax.jit(step)

    key = jax.random.PRNGKey(0)
    p0 = transformer.init_params(key, cfg)
    params = jax.tree.map(
        lambda l: jnp.broadcast_to(l, (n,) + l.shape).copy(), p0)
    adj = topo.to_dense()    # step closes over topo; adj keeps the API
    batch = make_batch(cfg, dict(seq_len=64, global_batch=n), key)
    batch = jax.tree.map(lambda x: x.reshape((n, 1) + x.shape[1:]), batch)

    n_steps = 2 if quick else 4
    params, m = step(params, adj, batch, jax.random.fold_in(key, 0))
    jax.block_until_ready(m["loss_mean"])          # compile + first step
    t0 = time.time()
    for it in range(1, n_steps):
        params, m = step(params, adj, batch, jax.random.fold_in(key, it))
    loss = float(jax.block_until_ready(m["loss_mean"]))
    step_s = (time.time() - t0) / max(1, n_steps - 1)

    fan_in = _fan_in(topo)
    wire = perfmodel.wire_bytes(n, fan_in, topo.kind)
    common.emit(f"fleet.replica_step.{topo.kind}", step_s,
                f"n={n} loss={loss:.3f}")
    entries = [registry.Entry(
        name="fleet.replica_step",
        wall_s=step_s,
        wire_bytes=wire,
        eval_score=-loss,
        extra={"n": n, "representation": topo.kind, "fan_in": fan_in,
               "arch": "fleet-nano"})]

    # scheduled variant: PairSpec.sched → build_step compiles the
    # schedule, the step takes/returns the ScheduleState — the full
    # launch-layer path for time-varying topologies (DESIGN.md §9).
    from repro.core.topology_sched import ScheduleSpec
    pair_s = dataclasses.replace(
        pair, sched=ScheduleSpec(kind="resample_er", period=2, seed=0))
    step_fn, order = specs.build_step(pair_s, make_host_mesh())
    assert order[-1] == "sched", order
    schedule = specs._compile_pair_schedule(pair_s)
    sstate = schedule.init()
    step_fn = jax.jit(step_fn)
    params = jax.tree.map(
        lambda l: jnp.broadcast_to(l, (n,) + l.shape).copy(), p0)
    params, m, sstate = step_fn(params, None, batch,
                                jax.random.fold_in(key, 100), sstate)
    jax.block_until_ready(m["loss_mean"])          # compile + first step
    t0 = time.time()
    for it in range(1, n_steps):
        params, m, sstate = step_fn(params, None, batch,
                                    jax.random.fold_in(key, 100 + it),
                                    sstate)
    loss_s = float(jax.block_until_ready(m["loss_mean"]))
    sched_step_s = (time.time() - t0) / max(1, n_steps - 1)
    assert int(sstate.t) == n_steps
    rep_s = schedule.representation
    fan_s = schedule.k_max if rep_s == "sparse" else n
    common.emit(f"fleet.replica_step_sched.{rep_s}", sched_step_s,
                f"n={n} loss={loss_s:.3f}")
    entries.append(registry.Entry(
        name="fleet.replica_step_sched",
        wall_s=sched_step_s,
        wire_bytes=perfmodel.wire_bytes(n, fan_s, rep_s),
        eval_score=-loss_s,
        extra={"n": n, "representation": rep_s,
               "schedule": "resample_er(period=2)", "arch": "fleet-nano"}))
    return entries


def sparse_kernel(quick: bool = False):
    """Pallas sparse-mixing kernel (interpret mode) vs jnp ref on an ER
    slice at the fleet density; gated via eval_score (1 pass / 0 fail)."""
    from repro.kernels import ref
    from repro.kernels import netes_sparse_mixing as nsm

    n, d = 32, 128
    rng = np.random.default_rng(0)
    adj = np.asarray(topology.erdos_renyi(n, p=P_FLEET, seed=0))
    idx, mask = topology_repr.sparse_neighbors(adj)
    wt = jnp.asarray(rng.normal(size=n), jnp.float32)
    th = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    ep = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    t0 = time.time()
    out_k = jax.block_until_ready(
        nsm.netes_sparse_mixing(jnp.asarray(idx), jnp.asarray(mask),
                                wt, wt, th, ep, sigma=0.1))
    dt = time.time() - t0
    out_r = ref.netes_mixing_ref(jnp.asarray(adj), wt, wt, th, ep,
                                 sigma=0.1)
    ok = bool(jnp.allclose(out_k, out_r, rtol=1e-4, atol=1e-4))
    common.emit("fleet.sparse_kernel", dt, f"n={n} allclose={ok}")
    return [registry.Entry(
        name="fleet.sparse_kernel", eval_score=float(ok),
        extra={"n": n, "d": d, "k_max": int(idx.shape[1])})]


def run(quick: bool = False):
    return (fleet_netes(quick=quick) + fleet_probed(quick=quick)
            + replica_step(quick=quick) + sparse_kernel(quick=quick))


@registry.register("fleet", group="fleet")
def bench(ctx: registry.Context):
    return run(quick=ctx.quick)
