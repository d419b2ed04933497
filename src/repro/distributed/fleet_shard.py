"""Sharded mega-fleets: the NetES agent axis over a device mesh (DESIGN.md §13).

Every fleet so far ran as ONE array program on one device; the measured
ER-vs-FC wire-byte win was a model. This module partitions the agent
axis across a ``Mesh`` with ``shard_map`` so an N ≥ 16384 fleet runs
with per-shard parameter/perturbation slabs, and turns cross-shard
edges into real collectives:

* **halo exchange** (sparse / static-circulant graphs): a host-side
  ``CommPlan`` groups every cross-shard edge by ring distance r; round r
  is ONE batched ``lax.ppermute`` moving exactly the distinct boundary
  rows any shard needs from its r-th neighbor (padded to the fleet-wide
  max ``H_r`` so the collective is shape-static). Neighbor lists are
  remapped into local+halo buffer coordinates with slot order preserved,
  so the contraction is the same slot loop the single-device sparse
  kernel runs — bit-exact across mesh sizes.
* **codec at the collective layer**: with a wire-quantizing channel
  (``Channel.wire_quantized``) the ``WirePayload`` int8 codes + per-row
  scale are what the ppermute/all-gather moves; decode happens after the
  collective. Per-shard wire bytes are therefore *measured on the
  collective buffers themselves* (``collective_bytes``), not modeled.
* **fully-connected** fleets never materialize an (N, N) adjacency: the
  Eq. 3 sum collapses to one rank-1 term Σ_i R̃_i·wire_i computed from
  the all-gathered payload.
* **replicated fallback** (scheduled topologies, stateful channels —
  event triggers and dropout need global channel state): payloads are
  all-gathered raw and the mixing runs replicated through
  ``topology_repr``; each shard keeps its own row slab. Honest
  accounting: this mode moves FC-level bytes.

Shard-invariance contract: for a fixed seed the trajectory (thetas,
best_reward/theta, RNG carry) and the realized traffic counters are
IDENTICAL for any mesh size, including 1, and identical to the solo
(``mesh=None``) engine. Two ingredients make that hold bitwise: the
noise layout (agent g's ε is row g of ``normal(k_eps, (n_pad, D))``,
drawn under ``jit`` with its rows sharded like θ, so each shard computes
only its own rows; the threefry counters are partitionable, so row g
does not depend on n_pad or on the placement), and contraction shapes
pinned to N (row padding to ``n_pad = n_dev·ceil(N/n_dev)`` adds
phantom zero-weight rows, but every reduction — fitness shaping,
dense/full contractions, reward gathers — is sliced back to exactly N
first). ``reward_fn`` must be row-decomposable (each row's return
independent of the batch), which every env/landscape task satisfies.

The noise, the episode keys and the broadcast draws are those of
``core.netes.netes_step``, so a sharded run equals the single-device
engine up to the order of its reductions.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.comm import channel as comm_channel
from repro.core import netes, topology_repr, wire_format
from repro.core.netes import NetESConfig, NetESState
from repro.core.topology_repr import Topology

Array = jax.Array

AXIS = "agents"


def build_mesh(num_shards: Optional[int] = None, axis: str = AXIS) -> Mesh:
    """1-D mesh over the first ``num_shards`` local devices (all, if
    None). Simulated multi-device CPU runs set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` BEFORE
    importing jax (see benchmarks/README.md)."""
    devs = jax.devices()
    n = len(devs) if num_shards is None else int(num_shards)
    if not 1 <= n <= len(devs):
        raise ValueError(f"num_shards={n} but {len(devs)} devices visible")
    return Mesh(np.array(devs[:n]), (axis,))


@dataclasses.dataclass(frozen=True)
class FullyConnected:
    """Marker topology for an all-ones (self-loop included) graph whose
    (N, N) adjacency must never materialize: the engine's ``full`` mode
    contracts Eq. 3 as one rank-1 term from the gathered payload."""

    n: int


# ---------------------------------------------------------------------------
# host-side communication plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CommPlan:
    """Everything the shard_map body needs, precomputed in numpy.

    ``mode`` ∈ {halo, dense, full, replicated}; ``rounds`` is the static
    halo schedule — one ``(ring_distance, H_r)`` per NON-EMPTY round, so
    graphs with shard-local structure (small-offset circulants, banded
    sparse) skip most of the ring. ``operands`` hold the per-shard plan
    arrays laid out along axis 0 so ``shard_map`` splits them:

    * ``send{r}``      (n_dev, H_r) int32 — local row each shard sends
    * ``gid_buf``      (n_dev, B)   int32 — global id per buffer slot
    * ``remap_idx``    (n_pad, K)   int32 — neighbor slots in buffer coords
    * ``remap_mask``   (n_pad, K)   f32   — edge weights (0 on padding)
    * ``adj_block``    (n_pad, n)   f32   — dense mode row block
    * ``deg``          (n_pad,)     f32   — row degrees (1 on phantoms)

    ``payload_rows`` is the per-shard, per-step count of payload rows
    RECEIVED over collectives — the realized-wire-bytes base.
    """

    mode: str
    n: int
    n_dev: int
    n_loc: int
    n_pad: int
    rounds: Tuple[Tuple[int, int], ...]
    operands: Dict[str, np.ndarray]
    payload_rows: int


def _neighbor_lists(topo: Topology) -> Tuple[np.ndarray, np.ndarray]:
    """(idx, mask) global neighbor lists for the halo plan. Sparse
    topologies already carry them; a static circulant densifies its
    signed offsets into a (N, 1+|±Δ|) list — self first, then the
    sorted signed shifts, the exact slot order the solo contraction
    uses too (slot order is part of the bit-exactness contract)."""
    if topo.kind == "sparse":
        return (np.asarray(topo.neighbor_idx, np.int32),
                np.asarray(topo.neighbor_mask, np.float32))
    if topo.kind == "circulant" and topo.shifts is None:
        n = topo.n
        shifts = topology_repr.signed_offsets(topo.offsets, n)
        j = np.arange(n, dtype=np.int32)[:, None]
        cols = [j] + [((j + d) % n).astype(np.int32) for d in shifts]
        idx = np.concatenate(cols, axis=1)
        mask = np.ones_like(idx, np.float32)
        return idx, mask
    raise ValueError(f"no neighbor-list form for kind={topo.kind!r}")


def make_comm_plan(topo, n_dev: int, channel=None,
                   schedule=None) -> CommPlan:
    """Build the static communication plan for ``topo`` over ``n_dev``
    shards. Mode selection: schedules and stateful channels (event /
    dropout stages need globally-consistent state) force ``replicated``;
    ``FullyConnected`` gets the rank-1 ``full`` mode; sparse/static-
    circulant graphs get ``halo``; dense graphs get the row-block
    all-gather ``dense`` mode."""
    stateful = channel is not None and not channel.collective_eligible
    if schedule is not None or stateful:
        if isinstance(topo, FullyConnected):
            raise ValueError(
                "FullyConnected has no Topology for the replicated "
                "fallback; use a dense TopologySpec for stateful "
                "channels / schedules at FC density")
        n = topo.n if topo is not None else None
        if n is None:
            raise ValueError("replicated mode needs a template topology")
        n_loc = -(-n // n_dev)
        n_pad = n_loc * n_dev
        return CommPlan(mode="replicated", n=n, n_dev=n_dev, n_loc=n_loc,
                        n_pad=n_pad, rounds=(), operands={},
                        payload_rows=n_pad - n_loc)

    if isinstance(topo, FullyConnected):
        n = topo.n
        n_loc = -(-n // n_dev)
        n_pad = n_loc * n_dev
        return CommPlan(mode="full", n=n, n_dev=n_dev, n_loc=n_loc,
                        n_pad=n_pad, rounds=(), operands={},
                        payload_rows=n_pad - n_loc)

    n = topo.n
    n_loc = -(-n // n_dev)
    n_pad = n_loc * n_dev

    if topo.kind == "dense":
        adj_block = np.zeros((n_pad, n), np.float32)
        adj_block[:n] = np.asarray(topo.adj, np.float32)
        deg = np.ones((n_pad,), np.float32)
        deg[:n] = np.asarray(topo.deg, np.float32)
        return CommPlan(mode="dense", n=n, n_dev=n_dev, n_loc=n_loc,
                        n_pad=n_pad, rounds=(),
                        operands={"adj_block": adj_block, "deg": deg},
                        payload_rows=n_pad - n_loc)

    idx, mask = _neighbor_lists(topo)
    k = idx.shape[1]
    # phantom rows: self-indexed, zero-weight — they contribute nothing
    # and receive nothing.
    idx_pad = np.concatenate(
        [idx, np.tile(np.arange(n, n_pad, dtype=np.int32)[:, None],
                      (1, k))], axis=0)
    mask_pad = np.concatenate([mask, np.zeros((n_pad - n, k), np.float32)],
                              axis=0)
    deg = np.ones((n_pad,), np.float32)
    deg[:n] = np.asarray(topo.deg, np.float32)

    # ---- group cross-shard edges by ring distance -----------------------
    # needed[s][r]: sorted distinct global rows shard s must receive from
    # shard (s + r) % n_dev. Padding rows never appear (valid rows only
    # reference gids < n, and owners are gid // n_loc).
    needed = [[[] for _ in range(n_dev)] for _ in range(n_dev)]
    for s in range(n_dev):
        rows = slice(s * n_loc, (s + 1) * n_loc)
        gids = idx_pad[rows][mask_pad[rows] != 0]
        ext = np.unique(gids[gids // n_loc != s])
        for g in ext.tolist():
            r = (int(g) // n_loc - s) % n_dev
            needed[s][r].append(int(g))
    rounds = []
    for r in range(1, n_dev):
        h = max(len(needed[s][r]) for s in range(n_dev))
        if h:
            rounds.append((r, h))
    rounds = tuple(rounds)

    # ---- buffer layout: [local slab | round 1 halo | round 2 | ...] ----
    b = n_loc + sum(h for _, h in rounds)
    gid_buf = np.zeros((n_dev, b), np.int32)
    pos_maps = []
    for s in range(n_dev):
        gid_buf[s, :n_loc] = np.arange(s * n_loc, (s + 1) * n_loc)
        pos = {int(g): i for i, g in enumerate(gid_buf[s, :n_loc])}
        off = n_loc
        for r, h in rounds:
            lst = needed[s][r]
            gid_buf[s, off:off + len(lst)] = lst
            gid_buf[s, off + len(lst):off + h] = s * n_loc  # inert pad
            for i, g in enumerate(lst):
                pos[g] = off + i
            off += h
        pos_maps.append(pos)

    operands: Dict[str, np.ndarray] = {"gid_buf": gid_buf, "deg": deg}
    # shard u's send list for round r serves requester (u - r) % n_dev.
    for r, h in rounds:
        send = np.zeros((n_dev, h), np.int32)
        for u in range(n_dev):
            lst = needed[(u - r) % n_dev][r]
            send[u, :len(lst)] = np.asarray(lst, np.int64) - u * n_loc
        operands[f"send{r}"] = send

    remap_idx = np.zeros((n_pad, k), np.int32)
    remap_mask = mask_pad
    for j in range(n_pad):
        s = j // n_loc
        pm = pos_maps[s]
        for c in range(k):
            if mask_pad[j, c] != 0:
                remap_idx[j, c] = pm[int(idx_pad[j, c])]
    operands["remap_idx"] = remap_idx
    operands["remap_mask"] = remap_mask

    return CommPlan(mode="halo", n=n, n_dev=n_dev, n_loc=n_loc,
                    n_pad=n_pad, rounds=rounds, operands=operands,
                    payload_rows=sum(h for _, h in rounds))


# ---------------------------------------------------------------------------
# collective abstraction: the same step code runs sharded and solo
# ---------------------------------------------------------------------------

class _ShardOps:
    def __init__(self, axis: str, n_dev: int):
        self.axis, self.n_dev = axis, n_dev

    def axis_index(self):
        return jax.lax.axis_index(self.axis)

    def all_gather(self, x):
        return jax.lax.all_gather(x, self.axis, axis=0, tiled=True)

    def psum(self, x):
        return jax.lax.psum(x, self.axis)

    def ppermute_recv(self, x, r):
        # receiver s takes round-r data from source (s + r) % n_dev, so
        # source u sends to (u - r) % n_dev.
        perm = [(u, (u - r) % self.n_dev) for u in range(self.n_dev)]
        return jax.lax.ppermute(x, self.axis, perm)


class _SoloOps:
    """The unsharded oracle: one shard, every collective is the
    identity. Shares 100% of the step code with ``_ShardOps`` runs."""

    n_dev = 1

    def axis_index(self):
        return jnp.zeros((), jnp.int32)

    def all_gather(self, x):
        return x

    def psum(self, x):
        return x

    def ppermute_recv(self, x, r):  # pragma: no cover - no rounds solo
        raise AssertionError("solo engine has no halo rounds")


def _slot_contract(idx: Array, w: Array,
                   values: Array) -> Tuple[Array, Array]:
    """``(Σ_k w[j,k]·values[idx[j,k]], Σ_k w[j,k])`` with the same slot
    loop (×4 unroll + fori) as ``topology_repr.weighted_neighbor_sum``'s
    sparse path — per-row sequential accumulation in slot order, so
    results are independent of how rows are split across shards. Every
    product is pinned with ``optimization_barrier`` before its add: XLA
    contracts mul+add chains into FMAs per compiled program, and the
    (n_loc, D) and (N, D) programs may disagree in the last ulp without
    the explicit rounding points. The row sum rides the same loop so its
    accumulation order is slot order too (a ``w.sum(axis=1)`` reduce has
    implementation-defined order)."""
    k_max = idx.shape[1]

    def one(c, accs):
        m, ws = accs
        wc = w[:, c]
        prod = jax.lax.optimization_barrier(
            wc[:, None] * jnp.take(values, idx[:, c], axis=0))
        return (m + prod, ws + wc)

    accs = (jnp.zeros((idx.shape[0], values.shape[1]), values.dtype),
            jnp.zeros((idx.shape[0],), w.dtype))
    k4 = k_max - k_max % 4
    if k4:
        def body(kk, a):
            for u in range(4):
                a = one(kk * 4 + u, a)
            return a
        accs = jax.lax.fori_loop(0, k4 // 4, body, accs)
    for c in range(k4, k_max):
        accs = one(c, accs)
    return accs


def _dense_contract(adjb: Array, coeff: Array,
                    values: Array) -> Tuple[Array, Array]:
    """Dense Eq. 3 row block in FIXED source order: returns
    ``(Σ_i adjb[:,i]·coeff[i]·values[i], Σ_i adjb[:,i]·coeff[i])``.

    A gemm (``adjb @ ...``) would be the natural spelling, but gemm
    K-accumulation order depends on the M-tile blocking — splitting the
    row axis across shards perturbs the last ulp. The sequential ×4
    unroll makes the dense mode placement-invariant like the halo slot
    loop."""
    nsrc = values.shape[0]

    def one(c, accs):
        m, w = accs
        wc = adjb[:, c] * coeff[c]
        prod = jax.lax.optimization_barrier(
            wc[:, None] * values[c][None, :])
        return (m + prod, w + wc)

    accs = (jnp.zeros((adjb.shape[0], values.shape[1]), values.dtype),
            jnp.zeros((adjb.shape[0],), values.dtype))
    k4 = nsrc - nsrc % 4
    if k4:
        def body(kk, a):
            for u in range(4):
                a = one(kk * 4 + u, a)
            return a
        accs = jax.lax.fori_loop(0, k4 // 4, body, accs)
    for c in range(k4, nsrc):
        accs = one(c, accs)
    return accs


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class ShardedNetES:
    """A compiled NetES fleet over a device mesh (or solo, ``mesh=None``).

    Build once per (topology × config × mesh × channel/schedule) and call
    :meth:`run` repeatedly — the jitted program is cached per
    ``num_iters``, so steady-state replays compile nothing (gated by the
    fleet16k bench). ``topo`` may be a ``Topology``, a
    ``FullyConnected`` marker, or None with a ``schedule``.
    """

    def __init__(self, topo, reward_fn: Callable, cfg: NetESConfig,
                 mesh: Optional[Mesh] = None, channel=None, schedule=None,
                 probes=None):
        if topo is None and schedule is None:
            raise ValueError("need a topology or a schedule")
        self.mesh = mesh
        self.axis = mesh.axis_names[0] if mesh is not None else AXIS
        self.cfg = cfg
        self.reward_fn = reward_fn
        self.channel = channel
        self.schedule = schedule
        # DESIGN.md §15: probe samples read the psum-REDUCED metrics, so
        # every shard records identical values and the MetricsState ring
        # rides the carry replicated (same P() spec as channel/schedule
        # state) — probe series are mesh-invariant by construction.
        self.probes = probes
        self._sched_template = schedule.init() if schedule is not None \
            else None
        plan_topo = topo if topo is not None else self._sched_template.topo
        n_dev = mesh.shape[self.axis] if mesh is not None else 1
        self.topo = topo
        self.plan = make_comm_plan(plan_topo, n_dev, channel=channel,
                                   schedule=schedule)
        # static per-step mixing message count (stateless channels move
        # every live directed edge every step); replicated mode counts
        # inside the step from the live topology instead.
        self._static_msgs = None
        if channel is not None and self.plan.mode != "replicated":
            if self.plan.mode == "full":
                self._static_msgs = float(self.plan.n * (self.plan.n - 1))
            else:
                self._static_msgs = float(comm_channel.realized_messages(
                    topo, None, None))
        self._operands = self._place_operands()
        self._run_impl = jax.jit(self._make_run_impl(),
                                 static_argnames=("num_iters",))

    # -- operand placement -------------------------------------------------
    def _operand_spec(self, name: str, arr: np.ndarray) -> P:
        # every plan operand is laid out with shard axis 0 except none —
        # all current operands shard on axis 0.
        return P(self.axis, *([None] * (arr.ndim - 1)))

    def _place_operands(self):
        ops = {k: jnp.asarray(v) for k, v in self.plan.operands.items()}
        if self.mesh is not None:
            ops = {k: jax.device_put(
                v, NamedSharding(self.mesh,
                                 self._operand_spec(k, self.plan.operands[k])))
                for k, v in ops.items()}
        return ops

    # -- step body (shared by sharded and solo) ---------------------------
    def _encode_payload(self, payload):
        """Channel codec applied where the bytes move: wire-quantizing
        channels keep int8 codes + scale as the collective operands;
        other stateless codecs (topk) transform the f32 payload. Returns
        (parts tuple to move, decode fn)."""
        chan = self.channel
        if chan is None:
            return (payload,), lambda parts: parts[0]
        if chan.wire_quantized:
            wp = chan.encode_wire(payload, batched=True)
            return ((wp.codes, wp.scale),
                    lambda parts: wire_format.decode(parts[0], parts[1],
                                                     wp.dtype))
        return (chan.codec(payload, batched=True),), lambda parts: parts[0]

    def _mix(self, ops, operands, th, pert_pos, shaped, shaped_pad,
             carry):
        """Per-mode Eq. 3 contraction. Returns (mixed, wsum, deg,
        new_cs, chan_metrics) where mixed/wsum are the local neighbor
        sum and self-correction weight."""
        plan, cfg, chan = self.plan, self.cfg, self.channel
        n, n_loc, n_pad = plan.n, plan.n_loc, plan.n_pad
        cs = carry.get("cs")
        chan_metrics = None

        if plan.mode == "replicated":
            topo = carry["ss"].topo if self.schedule is not None \
                else self.topo
            with jax.named_scope("exchange"):
                pert_full = ops.all_gather(pert_pos)[:n]
            edge_mask = None
            wire = pert_full
            if chan is not None:
                with jax.named_scope("channel"):
                    chan_apply = (chan.apply_wire if chan.wire_fused(topo)
                                  else chan.apply)
                    wire, edge_mask, cs, info = chan_apply(cs, topo,
                                                           pert_full)
                chan_metrics = info
            wnb = topology_repr.weighted_neighbor_sum(
                topo, shaped, wire, edge_mask=edge_mask)
            wrs = topology_repr.weighted_row_sum(topo, shaped,
                                                 edge_mask=edge_mask)
            lo = ops.axis_index() * n_loc
            pad = n_pad - n
            wnb = jnp.pad(wnb, ((0, pad), (0, 0)))
            wrs = jnp.pad(wrs, (0, pad))
            deg = jnp.pad(topo.deg, (0, pad), constant_values=1.0)
            mixed = jax.lax.dynamic_slice_in_dim(wnb, lo, n_loc, 0)
            wsum = jax.lax.dynamic_slice_in_dim(wrs, lo, n_loc, 0)
            deg = jax.lax.dynamic_slice_in_dim(deg, lo, n_loc, 0)
            return mixed, wsum, deg, cs, chan_metrics

        with jax.named_scope("channel"):
            parts, decode = self._encode_payload(pert_pos)

        if plan.mode == "halo":
            bufs = [list(parts)]
            with jax.named_scope("exchange"):
                for r, _ in plan.rounds:
                    sidx = operands[f"send{r}"][0]
                    bufs.append([ops.ppermute_recv(
                        jnp.take(p, sidx, axis=0), r) for p in parts])
            joined = tuple(
                jnp.concatenate([b[i] for b in bufs], axis=0)
                for i in range(len(parts)))
            buf = decode(joined)
            coeff_buf = jnp.take(shaped_pad, operands["gid_buf"][0])
            ridx = operands["remap_idx"]
            w = (operands["remap_mask"]
                 * jnp.take(coeff_buf, ridx)).astype(buf.dtype)
            mixed, wsum = _slot_contract(ridx, w, buf)
            return mixed, wsum, operands["deg"], cs, chan_metrics

        # dense / full: all-gather the encoded payload, decode, contract
        # over EXACTLY n sources (contraction shapes pinned to N keeps
        # results identical across mesh sizes).
        with jax.named_scope("exchange"):
            joined = tuple(ops.all_gather(p)[:n] for p in parts)
        buf = decode(joined)
        if plan.mode == "dense":
            adjb = operands["adj_block"].astype(buf.dtype)
            mixed, wsum = _dense_contract(adjb,
                                          shaped.astype(buf.dtype), buf)
            return mixed, wsum, operands["deg"], cs, chan_metrics
        # full: rank-1 — Σ_i R̃_i·wire_i is one replicated (D,) vector.
        # Spelled as multiply + reduce, not a gemv: XLA:CPU lowers a gemv
        # with a different loop order when the [:n] slice fuses into it
        # (mesh sizes with n_pad > n), while the reduce keeps one order.
        # Both forms compile to one fused pass with the same temporaries
        # for v5e — no (N, D) product is materialized.
        svec = (shaped.astype(buf.dtype)[:, None] * buf).sum(axis=0)
        wsum_scalar = shaped.sum()
        mixed = jnp.broadcast_to(svec, th.shape)
        wsum = jnp.broadcast_to(wsum_scalar, (n_loc,))
        deg = jnp.full((n_loc,), float(n), jnp.float32)
        return mixed, wsum, deg, cs, chan_metrics

    def _step(self, ops, operands, carry, eps, k_eval, k_beta):
        """One iteration on this shard's rows. ``eps`` is the shard's
        slab of the iteration's (n_pad, D) noise; the key split that
        gave ``k_eval`` and ``k_beta`` runs outside, with the draw."""
        plan, cfg, chan = self.plan, self.cfg, self.channel
        n, n_loc, n_pad = plan.n, plan.n_loc, plan.n_pad
        th = carry["th"]
        lo = ops.axis_index() * n_loc
        gid = lo + jnp.arange(n_loc, dtype=jnp.int32)
        valid = (gid < n).astype(th.dtype)

        # The parts carry core.netes.netes_step's named scopes (DESIGN.md
        # §15): op metadata only, no change to numerics or fusion.
        with jax.named_scope("noise"):
            # Round σ·ε before the add: XLA is free to contract mul+add
            # chains into FMAs, and it decides per compiled program — the
            # (n_loc, D) and (N, D) programs can disagree in the last ulp.
            # optimization_barrier pins the rounding points so every mesh
            # size adds bit-identical values (shard-invariance contract).
            s_eps = jax.lax.optimization_barrier(cfg.sigma * eps)
            pert_pos = th + s_eps

        def rewards(params):
            # A reward with per-row keys (envs.rollout) gets agent g's
            # key split(k_eval, N)[g] — what the whole population's call
            # hands it — so episodes do not depend on the mesh size
            # (split(k, n_pad)[:N] == split(k, N)).
            rowwise = getattr(self.reward_fn, "rowwise", None)
            if rowwise is None:
                r = self.reward_fn(params, k_eval)
            else:
                keys = jnp.take(jax.random.split(k_eval, n_pad), gid,
                                axis=0)
                r = rowwise(params, keys)
            with jax.named_scope("exchange"):
                return ops.all_gather(r)[:n]

        if cfg.antithetic:
            with jax.named_scope("noise"):
                pert_neg = th - s_eps
            with jax.named_scope("reward"):
                r_pos = rewards(pert_pos)
                r_neg = rewards(pert_neg)
            with jax.named_scope("shaping"):
                raw = jnp.concatenate([r_pos, r_neg])
                shaped_all = netes.shape_fitness(raw, cfg.fitness_shaping)
                shaped = shaped_all[:n] - shaped_all[n:]
        else:
            with jax.named_scope("reward"):
                raw = rewards(pert_pos)
            with jax.named_scope("shaping"):
                shaped = netes.shape_fitness(raw, cfg.fitness_shaping)
        with jax.named_scope("shaping"):
            shaped_pad = jnp.pad(shaped, (0, n_pad - n))

        with jax.named_scope("mixing"):
            mixed, wsum, deg, cs, chan_metrics = self._mix(
                ops, operands, th, pert_pos, shaped, shaped_pad, carry)
            # Same FMA-seam pinning as σ·ε above: round every product
            # before it enters an add/sub so the update chain is bitwise
            # identical across program shapes (solo vs any mesh size).
            mixed, wsum = jax.lax.optimization_barrier((mixed, wsum))
            mixed = mixed - jax.lax.optimization_barrier(wsum[:, None] * th)
            if cfg.normalization == "degree":
                scale = cfg.alpha / (deg[:, None] * cfg.sigma ** 2)
            else:
                scale = cfg.alpha / (n * cfg.sigma ** 2)
            update = jax.lax.optimization_barrier(scale * mixed)
            if cfg.weight_decay:
                # es_utils.apply_weight_decay semantics (u ← u − wd·θ)
                # with the wd·θ product rounded before the subtract.
                update = jax.lax.optimization_barrier(
                    update - jax.lax.optimization_barrier(
                        cfg.weight_decay * th))
            new_th = th + update

        # ---- broadcast event: fetch the argmax row via a masked psum
        # (zeros + the owner's row — exact, order-free).
        with jax.named_scope("broadcast"):
            best_idx = jnp.argmax(raw)
            iter_best_reward = raw[best_idx]
            b0 = best_idx % n if cfg.antithetic else best_idx
            row_idx = jnp.clip(b0 - lo, 0, n_loc - 1)
            row = jax.lax.dynamic_index_in_dim(pert_pos, row_idx, 0,
                                               keepdims=False)
            if cfg.antithetic:
                row_neg = jax.lax.dynamic_index_in_dim(pert_neg, row_idx, 0,
                                                       keepdims=False)
                row = jnp.where(best_idx < n, row, row_neg)
            mine = ((b0 >= lo) & (b0 < lo + n_loc)).astype(th.dtype)
            iter_best_theta = ops.psum(row * mine)
            beta = jax.random.uniform(k_beta)
            do_b = beta < cfg.p_broadcast
            bcast = iter_best_theta if chan is None else chan.codec(
                iter_best_theta, batched=False)
            new_th = jnp.where(do_b, jnp.broadcast_to(bcast, new_th.shape),
                               new_th)

            better = iter_best_reward > carry["best_r"]
            out = dict(carry)
            out.update(
                th=new_th, step=carry["step"] + 1,
                best_r=jnp.where(better, iter_best_reward, carry["best_r"]),
                best_th=jnp.where(better, iter_best_theta,
                                  carry["best_th"]))

        def spread(x):
            # cross-shard population variance over the N valid rows via
            # psum'd moments (Σx, Σx²); phantom rows are masked out.
            s1 = ops.psum((valid[:, None] * x).sum(axis=0))
            s2 = ops.psum((valid[:, None] * x * x).sum(axis=0))
            return ((s2 / n) - (s1 / n) ** 2).sum()

        with jax.named_scope("stats"):
            # the gathered (N,) rewards are fused into the reductions
            # below differently by each program shape; materialize them
            # first so every mesh size (and solo) sums them in the same
            # order
            raw = jax.lax.optimization_barrier(raw)
            metrics = {
                "reward_mean": raw.mean(),
                "reward_max": raw.max(),
                "reward_min": raw.min(),
                "reward_std": raw.std(),   # fitness dispersion (global: raw
                "update_var": spread(update),       # is the gathered array)
                "broadcast": do_b.astype(jnp.float32),
                "theta_spread": spread(new_th),
            }
            if chan is not None:
                bcast_msgs = do_b.astype(jnp.float32) * n
                if chan_metrics is None:  # stateless codec modes
                    mix_msgs = jnp.float32(self._static_msgs)
                    metrics["trigger_frac"] = jnp.ones((), jnp.float32)
                    metrics["drop_frac"] = jnp.zeros((), jnp.float32)
                else:
                    mix_msgs = chan_metrics["msgs"]
                    metrics["trigger_frac"] = chan_metrics["trigger_frac"]
                    metrics["drop_frac"] = chan_metrics["drop_frac"]
                metrics["msgs"] = mix_msgs + bcast_msgs
                out["cs"] = cs._replace(
                    msgs=cs.msgs + mix_msgs + bcast_msgs)
        if self.schedule is not None:
            with jax.named_scope("schedule"):
                out["ss"] = self.schedule.advance(carry["ss"])
        if self.probes is not None:
            # graph probes read the LIVE topology (pre-advance, matching
            # core.netes.scheduled_step); FullyConnected has no Topology
            # to read — its graph stage fails at trace time by design.
            live = carry["ss"].topo if self.schedule is not None else (
                self.topo if isinstance(self.topo, Topology) else None)
            with jax.named_scope("stats"):
                out["ms"] = self.probes.record(carry["ms"], metrics, live)
        return out, metrics

    # -- jitted run --------------------------------------------------------
    def _make_run_impl(self):
        """The jitted run: one ``lax.scan`` whose body splits the key,
        draws the iteration's (n_pad, D) noise with its rows placed like
        θ's, then runs ``_step`` on each shard's rows (``shard_map``)."""
        have_chan = self.channel is not None
        have_sched = self.schedule is not None
        have_probes = self.probes is not None

        if self.mesh is None:
            def place(eps):
                return eps

            def step(carry, eps, k_eval, k_beta, operands):
                return self._step(_SoloOps(), operands, carry, eps, k_eval,
                                  k_beta)
        else:
            rows = P(self.axis, None)
            sharding = NamedSharding(self.mesh, rows)
            ops = _ShardOps(self.axis, self.plan.n_dev)
            opspec = {k: self._operand_spec(k, v)
                      for k, v in self.plan.operands.items()}

            def place(eps):
                # the draw's rows on the chips that hold them: each chip
                # computes n_loc rows of the threefry stream
                return jax.lax.with_sharding_constraint(eps, sharding)

            def step(carry, eps, k_eval, k_beta, operands):
                specs = jax.tree.map(lambda _: P(), carry)
                specs["th"] = rows
                return jax.shard_map(
                    lambda c, e, ke, kb, o: self._step(ops, o, c, e, ke, kb),
                    mesh=self.mesh,
                    in_specs=(specs, rows, P(), P(), opspec),
                    out_specs=(specs, P()),
                    check_vma=False)(carry, eps, k_eval, k_beta, operands)

        def run_impl(th, key, step_count, best_r, best_th, operands, cs, ss,
                     mst, num_iters):
            carry = {"th": th, "key": key, "step": step_count,
                     "best_r": best_r, "best_th": best_th}
            if have_chan:
                carry["cs"] = cs[0]
            if have_sched:
                carry["ss"] = ss[0]
            if have_probes:
                carry["ms"] = mst[0]

            def body(c, _):
                c = dict(c)
                th = c["th"]
                with jax.named_scope("noise"):
                    key, k_eps, k_eval, k_beta = jax.random.split(
                        c.pop("key"), 4)
                    eps = place(jax.random.normal(k_eps, th.shape,
                                                  dtype=th.dtype))
                c, m = step(c, eps, k_eval, k_beta, operands)
                return dict(c, key=key), m

            carry, ms = jax.lax.scan(body, carry, None, length=num_iters)
            cs_out = (carry["cs"],) if have_chan else ()
            ss_out = (carry["ss"],) if have_sched else ()
            mst_out = (carry["ms"],) if have_probes else ()
            return (carry["th"], carry["key"], carry["step"],
                    carry["best_r"], carry["best_th"], cs_out, ss_out,
                    mst_out, ms)

        return run_impl

    def run(self, state: NetESState, num_iters: int, chan_state=None,
            sched_state=None, metrics_state=None):
        """Mirror of ``core.netes.run`` / ``run_scheduled`` return
        shapes: ``(state, metrics)``, with a channel
        ``(state, chan_state, metrics)``, with a schedule the schedule
        state slots in before the channel state; with probes the
        ``MetricsState`` slots in right before the stacked metrics."""
        plan = self.plan
        n, d = state.thetas.shape
        if n != plan.n:
            raise ValueError(f"state has {n} agents, plan expects {plan.n}")
        th = state.thetas
        if plan.n_pad != n:
            th = jnp.pad(th, ((0, plan.n_pad - n), (0, 0)))
        cs = (chan_state,) if self.channel is not None else ()
        ss = (sched_state,) if self.schedule is not None else ()
        mst = (metrics_state,) if self.probes is not None else ()
        args = (state.key, state.step, state.best_reward, state.best_theta)
        if self.mesh is not None:
            # one placement for every call, the first chunk's included:
            # the compiled run is then looked up, never built again
            th = jax.device_put(th, NamedSharding(self.mesh,
                                                  P(self.axis, None)))
            args, cs, ss, mst = jax.device_put(
                (args, cs, ss, mst), NamedSharding(self.mesh, P()))
        (th, key, step, best_r, best_th, cs_out, ss_out, mst_out,
         metrics) = self._run_impl(th, *args, self._operands, cs, ss, mst,
                                   num_iters=num_iters)
        if plan.n_pad != n:
            th = th[:n]
        out_state = NetESState(thetas=th, key=key, step=step,
                               best_reward=best_r, best_theta=best_th)
        out = (out_state,)
        if self.schedule is not None:
            out = out + (ss_out[0],)
        if self.channel is not None:
            out = out + (cs_out[0],)
        if self.probes is not None:
            out = out + (mst_out[0],)
        return out + (metrics,)

    # -- realized traffic, measured on the collective buffers -------------
    def collective_bytes(self, dim: int) -> Dict[str, int]:
        """Per-shard, per-step bytes moved by this engine's collectives,
        derived from the exact static buffer shapes the compiled program
        executes (the ppermute/all-gather operands). Wire-quantized
        channels move int8 codes + one f32 scale per row; everything
        else moves f32 rows. ``reward_bytes`` covers the (±ε) reward
        gathers; ``broadcast_bytes`` the best-row psum."""
        plan, chan = self.plan, self.channel
        wired = (chan is not None and chan.wire_quantized
                 and plan.mode != "replicated")
        row = dim * 1 + 4 if wired else dim * 4
        payload = plan.payload_rows * row
        rewards = (plan.n_pad - plan.n_loc) * 4 * \
            (2 if self.cfg.antithetic else 1)
        broadcast = dim * 4
        return {
            "payload_rows": plan.payload_rows,
            "payload_bytes": payload,
            "reward_bytes": rewards,
            "broadcast_bytes": broadcast,
            "total_bytes": payload + rewards + broadcast,
        }


# ---------------------------------------------------------------------------
# engine cache + the core/netes mesh= entry points
# ---------------------------------------------------------------------------

# Keyed by object identity for the topology/schedule (mirroring jit's
# static-argument caching); the values hold strong references so ids
# stay valid. Pass a STABLE Topology object across calls (as the train
# loop does) — a fresh array-built Topology per call rebuilds+recompiles.
_ENGINE_CACHE: Dict[Any, ShardedNetES] = {}


def clear_engine_cache():
    _ENGINE_CACHE.clear()


def get_engine(topo, reward_fn, cfg, mesh, channel, schedule,
               probes=None):
    """The engine for these arguments, built once and then cached."""
    key = (id(topo), id(schedule), reward_fn, cfg, channel, mesh, probes)
    eng = _ENGINE_CACHE.get(key)
    if eng is None or eng.topo is not topo or eng.schedule is not schedule:
        eng = ShardedNetES(topo, reward_fn, cfg, mesh=mesh,
                           channel=channel, schedule=schedule,
                           probes=probes)
        _ENGINE_CACHE[key] = eng
    return eng


def run_sharded(state: NetESState, adj, reward_fn: Callable,
                cfg: NetESConfig, num_iters: int, mesh: Optional[Mesh],
                channel=None, chan_state=None, probes=None,
                metrics_state=None):
    """``core.netes.run``'s ``mesh=`` backend (also accepts mesh=None
    for the solo-oracle engine). ``adj`` should be a stable ``Topology``
    or ``FullyConnected`` instance for engine caching."""
    topo = adj if isinstance(adj, (Topology, FullyConnected)) \
        else topology_repr.as_topology(adj)
    eng = get_engine(topo, reward_fn, cfg, mesh, channel, None,
                     probes=probes)
    return eng.run(state, num_iters, chan_state=chan_state,
                   metrics_state=metrics_state)


def run_sharded_scheduled(state: NetESState, sched_state,
                          reward_fn: Callable, cfg: NetESConfig, schedule,
                          num_iters: int, mesh: Optional[Mesh],
                          channel=None, chan_state=None, probes=None,
                          metrics_state=None):
    """``core.netes.run_scheduled``'s ``mesh=`` backend (replicated
    mixing — schedules mutate the graph on device, so every shard keeps
    the full topology state; honest accounting: FC-level bytes)."""
    eng = get_engine(None, reward_fn, cfg, mesh, channel, schedule,
                     probes=probes)
    return eng.run(state, num_iters, chan_state=chan_state,
                   sched_state=sched_state, metrics_state=metrics_state)


# ---------------------------------------------------------------------------
# static-analysis registry hook (repro.analysis — DESIGN.md §14)
# ---------------------------------------------------------------------------

# Barrier ratchet for the engine step (per traced program, num_iters=2 →
# one scan body): σ·ε pin + (mixed, wsum) pair + wsum·θ + scale·mixed +
# the two weight-decay pins, plus the per-slot pins inside the
# _dense_contract loop (4-unrolled fori body). Measured by
# tests/test_analysis_contracts.py; raising the count is always fine,
# dropping below it is the PR 7 bit-parity regression.
_STEP_MIN_BARRIERS = 10


def analysis_entry_points():
    """Contract-linter entry points: the sharded engine's compiled step
    (solo + mesh variants, barrier-ratcheted) and the two seam leaf
    contractions under the PRECISE fma-seam contract — every product in
    them must be barrier-pinned before accumulation."""
    from repro.analysis.registry import EntryPoint

    def _reward(params, key):
        return -jnp.sum(params * params, axis=-1)

    def _toy_topo(n=8):
        from repro.core import topology
        return topology_repr.as_topology(
            jnp.asarray(topology.erdos_renyi(n, p=0.5, seed=0)))

    def _engine_args(eng, d=16):
        th = jnp.zeros((eng.plan.n_pad, d), jnp.float32)
        mst = (eng.probes.init(),) if eng.probes is not None else ()
        return (th, jax.random.PRNGKey(0), jnp.zeros((), jnp.int32),
                jnp.full((), -jnp.inf, jnp.float32), th[0],
                eng._operands, (), (), mst)

    def build_solo_step():
        eng = ShardedNetES(_toy_topo(), _reward, NetESConfig(), mesh=None)
        run_impl = eng._make_run_impl()
        return (lambda *a: run_impl(*a, 2), _engine_args(eng), {})

    def build_sharded_step():
        eng = ShardedNetES(_toy_topo(), _reward, NetESConfig(),
                           mesh=build_mesh())
        run_impl = eng._make_run_impl()
        return (lambda *a: run_impl(*a, 2), _engine_args(eng), {})

    def build_slot_contract():
        idx = jnp.zeros((4, 6), jnp.int32)
        w = jnp.ones((4, 6), jnp.float32)
        values = jnp.ones((8, 16), jnp.float32)
        return _slot_contract, (idx, w, values), {}

    def build_dense_contract():
        adjb = jnp.ones((4, 8), jnp.float32)
        coeff = jnp.ones((8,), jnp.float32)
        values = jnp.ones((8, 16), jnp.float32)
        return _dense_contract, (adjb, coeff, values), {}

    seam = ("no-host-callback", "fma-seam-barrier")
    return (
        EntryPoint(name="fleet_shard.solo_step", build=build_solo_step,
                   min_barriers=_STEP_MIN_BARRIERS),
        EntryPoint(name="fleet_shard.sharded_step",
                   build=build_sharded_step, min_devices=2,
                   min_barriers=_STEP_MIN_BARRIERS),
        # ratchets measured on the toy shapes above: slot loop = 4-unroll
        # fori body + 2 tail slots, dense loop = 4-unroll fori body
        EntryPoint(name="fleet_shard.slot_contract",
                   build=build_slot_contract, contracts=seam,
                   min_barriers=6),
        EntryPoint(name="fleet_shard.dense_contract",
                   build=build_dense_contract, contracts=seam,
                   min_barriers=4),
    )
