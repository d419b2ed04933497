"""First-class topology representations for the NetES mixing update.

The paper's headline result (1000 Erdos-Renyi agents matching 3000
fully-connected ones) lives in the sparse-density regime p ≪ 1, yet a raw
``(N, N)`` float32 adjacency pays the dense O(N²·D) contraction no matter
how empty it is. This module makes the *physical representation* of a
topology a first-class, dispatchable choice (DESIGN.md §3):

``dense``
    The seed behavior: the adjacency as an ``(N, N)`` float32 matrix; the
    mixing update is two masked matmuls. Optimal for high density (MXU /
    BLAS efficiency) and the only representation every graph admits.

``sparse``
    Padded neighbor-list (ELL/CSR-with-pad): ``neighbor_idx (N, K_max)``
    int32 + ``neighbor_mask (N, K_max)`` float32, built host-side from the
    generators. The mixing update becomes a gather + masked weighted-sum
    at O(N·K·D) flops and — in the distributed setting — K·D neighbor
    bytes instead of the N·D all-gather (the Chen et al. 2018 binding
    constraint).

``circulant``
    Offset list for vertex-transitive ring graphs
    (``topology.circulant_offsets``): the mixing update is a chain of
    rolls (single host) or ``lax.ppermute``s (distributed,
    ``distributed/permute_mixing.py``), moving exactly p·N·D bytes.
    Offsets are normally STATIC (a tuple in the pytree aux); a
    *scheduled* circulant (``core/topology_sched.rotate_circulant``)
    instead carries its signed offsets as a TRACED int32 ``shifts``
    array so the graph can rotate inside one ``lax.scan`` trace — the
    roll chain takes the shift values at runtime while the chain
    LENGTH stays static (DESIGN.md §9).

``Topology`` is a registered JAX pytree: array leaves (adjacency /
neighbor lists / degrees) trace through ``jit`` and ``lax.scan`` while the
representation kind and offsets stay static, so every consumer
(``core.netes.mixing_update``, the distributed step builders, the Pallas
kernels) can dispatch on ``topo.kind`` at trace time with zero runtime
branching.

Representation selection (``select_representation``) is a host-side
heuristic over the *structure* of the graph; builders are pure
numpy — topology construction happens once at launch.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import topology as topo_gen
from . import wire_format

Array = jax.Array

# Density at or below which the neighbor-list representation is preferred
# over dense. The flop ratio is N/K ≈ 1/(2p−p²); the measured CPU crossover
# (benchmarks/kernel_bench.py sparse_crossover) and the distributed
# communication model both favor sparse well below this cutoff, while at
# p ≳ 0.3 the padded K_max approaches N and sparse is strictly worse.
SPARSE_DENSITY_CUTOFF = 0.25

# A circulant offset chain costs one ppermute per signed offset; past this
# fraction of the ring the chain stops beating one optimized all-gather.
CIRCULANT_OFFSET_CUTOFF = 0.25


@dataclasses.dataclass(frozen=True)
class Topology:
    """A communication topology with an explicit physical representation.

    Exactly one representation's payload is populated:

    * dense:      ``adj (N, N)`` float32
    * sparse:     ``neighbor_idx (N, K_max)`` int32,
                  ``neighbor_mask (N, K_max)`` float32 — the edge WEIGHT
                  ``a_ji`` (1.0 on the generators' binary graphs), 0 on
                  padding; padded slots index row ``j`` itself so gathers
                  stay in bounds
    * circulant:  ``offsets`` — STATIC generator offsets d ∈ [1, n//2]
                  (edge set ∪_d {(i, i±d mod n)} plus self-loops) — OR
                  ``shifts``, a TRACED ``(2K,)`` int32 array of distinct
                  signed ring shifts, used by scheduled (rotating)
                  circulants whose offsets change inside a scan trace.
                  Exactly one of the two is set.

    ``deg (N,)`` float32 (row degrees, self-loop included) is always
    present — the ``normalization="degree"`` variant of Eq. 3 needs it
    regardless of representation.
    """

    kind: str                                   # dense | sparse | circulant
    n: int
    deg: Array
    adj: Optional[Array] = None                 # (N, N)      [dense]
    neighbor_idx: Optional[Array] = None        # (N, K_max)  [sparse]
    neighbor_mask: Optional[Array] = None       # (N, K_max)  [sparse]
    offsets: Optional[Tuple[int, ...]] = None   # [circulant, static]
    shifts: Optional[Array] = None              # (2K,) int32 [circulant,
    #                                             traced/scheduled]

    # -- pytree protocol (kind/n/offsets static, arrays traced) ----------
    def tree_flatten(self):
        children = (self.deg, self.adj, self.neighbor_idx,
                    self.neighbor_mask, self.shifts)
        aux = (self.kind, self.n, self.offsets)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        deg, adj, idx, mask, shifts = children
        kind, n, offsets = aux
        return cls(kind=kind, n=n, deg=deg, adj=adj, neighbor_idx=idx,
                   neighbor_mask=mask, offsets=offsets, shifts=shifts)

    @property
    def k_max(self) -> int:
        return 0 if self.neighbor_idx is None else self.neighbor_idx.shape[1]

    def to_dense(self) -> Array:
        """Materialize the (N, N) float32 adjacency (host/trace-side)."""
        if self.kind == "dense":
            return self.adj
        if self.kind == "circulant":
            if self.shifts is not None:
                # traced-shift (scheduled) circulant: rows of a rolled
                # identity. Shifts are distinct and nonzero by the
                # schedule contract, so 0/1 entries need no clipping.
                eye = jnp.eye(self.n, dtype=jnp.float32)
                acc = eye
                for k in range(self.shifts.shape[0]):
                    acc = acc + jnp.roll(eye, self.shifts[k], axis=1)
                return acc
            return jnp.asarray(
                topo_gen.circulant_from_offsets(self.n, list(self.offsets)))
        # sparse: scatter the edge weights through the neighbor list.
        # scatter-add is exact: each (j, i) edge appears once per row, and
        # padded slots contribute weight 0 at (j, j).
        n, k = self.neighbor_idx.shape
        rows = jnp.repeat(jnp.arange(n), k)
        cols = self.neighbor_idx.reshape(-1)
        vals = self.neighbor_mask.reshape(-1)
        return jnp.zeros((n, n), jnp.float32).at[rows, cols].add(vals)


jax.tree_util.register_pytree_node(
    Topology, Topology.tree_flatten, Topology.tree_unflatten)


# ---------------------------------------------------------------------------
# host-side builders
# ---------------------------------------------------------------------------

def sparse_neighbors(adj: np.ndarray,
                     k_max: Optional[int] = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Padded neighbor-list from a dense adjacency (host-side numpy).

    Returns ``(neighbor_idx (N, K_max) int32, neighbor_mask (N, K_max)
    float32)``. ``neighbor_mask`` carries the actual edge WEIGHT
    ``adj[j, i]`` (1.0 for the binary graphs the generators emit), so
    weighted adjacencies survive the representation; padded slots index
    the row itself (in-bounds gathers) with weight 0.

    ``k_max`` overrides the pad width (≥ the graph's max degree):
    topology SCHEDULES re-pad to a static K_max with headroom so that
    on-device resamples keep the scan carry's shapes fixed.
    """
    adj = np.asarray(adj)
    n = adj.shape[0]
    degs = (adj != 0).sum(axis=1)
    if k_max is None:
        k_max = max(int(degs.max()), 1)
    elif k_max < int(degs.max()):
        raise ValueError(f"k_max={k_max} < max degree {int(degs.max())}")
    idx = np.tile(np.arange(n, dtype=np.int32)[:, None], (1, k_max))
    mask = np.zeros((n, k_max), np.float32)
    for j in range(n):
        nbrs = np.nonzero(adj[j] != 0)[0]
        idx[j, :len(nbrs)] = nbrs
        mask[j, :len(nbrs)] = adj[j, nbrs]
    return idx, mask


def _exact_circulant_offsets(adj: np.ndarray):
    """Offsets iff the graph is EXACTLY the symmetric, self-looped
    circulant they generate. ``topo_gen.circulant_offsets`` only checks
    row-rotation structure, which also matches directed or zero-diagonal
    rings — graphs the roll-chain backend (unconditional self term, both
    ±d offsets, unit weights) would silently symmetrize and self-loop."""
    offs = topo_gen.circulant_offsets(adj)
    if offs is None:
        return None
    rebuilt = topo_gen.circulant_from_offsets(adj.shape[0], offs)
    return offs if np.array_equal(np.asarray(adj, np.float32),
                                  rebuilt) else None


def select_representation(adj: np.ndarray) -> str:
    """Pick the cheapest representation a graph admits (DESIGN.md §3, §12).

    1. circulant — the graph is exactly a symmetric self-looped circulant
       with a small enough offset set that the ppermute chain beats one
       all-gather;
    2. sparse — max degree ≤ ``SPARSE_DENSITY_CUTOFF``·N, so the padded
       gather does ≪ the dense contraction's work;
    3. dense — everything else (the always-correct fallback).
    """
    adj = np.asarray(adj)
    n = adj.shape[0]
    offs = _exact_circulant_offsets(adj)
    if offs is not None and n > 2:
        signed = len(offs) * 2 - (1 if n % 2 == 0 and (n // 2) in offs
                                  else 0)
        if signed <= CIRCULANT_OFFSET_CUTOFF * n:
            return "circulant"
    k_max = int((adj != 0).sum(axis=1).max())
    if k_max <= SPARSE_DENSITY_CUTOFF * n:
        return "sparse"
    return "dense"


def from_dense(adj, representation: str = "auto") -> Topology:
    """Build a ``Topology`` from a dense adjacency (host-side).

    ``representation`` ∈ {auto, dense, sparse, circulant}. ``auto`` runs
    ``select_representation``; asking for ``circulant`` on a non-circulant
    graph raises.
    """
    adj_np = np.asarray(adj, dtype=np.float32)
    n = adj_np.shape[0]
    deg = jnp.asarray(adj_np.sum(axis=1))
    if representation == "auto":
        representation = select_representation(adj_np)
    if representation == "dense":
        return Topology(kind="dense", n=n, deg=deg, adj=jnp.asarray(adj_np))
    if representation == "sparse":
        idx, mask = sparse_neighbors(adj_np)
        return Topology(kind="sparse", n=n, deg=deg,
                        neighbor_idx=jnp.asarray(idx),
                        neighbor_mask=jnp.asarray(mask))
    if representation == "circulant":
        offs = _exact_circulant_offsets(adj_np)
        if offs is None:
            raise ValueError(
                "adjacency is not a symmetric self-looped circulant")
        return Topology(kind="circulant", n=n, deg=deg,
                        offsets=tuple(offs))
    raise ValueError(f"unknown representation {representation!r}")


def from_spec(spec: "topo_gen.TopologySpec",
              representation: str = "auto") -> Topology:
    """TopologySpec → generated graph → representation-selected Topology."""
    return from_dense(spec.build(), representation=representation)


def as_topology(t: Union[Topology, Array, np.ndarray]) -> Topology:
    """Coerce raw adjacency arrays to a dense ``Topology`` (backwards
    compatibility: every legacy call site passes an (N, N) array)."""
    if isinstance(t, Topology):
        return t
    arr = jnp.asarray(t)
    return Topology(kind="dense", n=arr.shape[0], deg=arr.sum(axis=1),
                    adj=arr)


# ---------------------------------------------------------------------------
# batched (stacked) topologies — the tournament vmap axis (DESIGN.md §10)
# ---------------------------------------------------------------------------
#
# The topology-search tournaments run S candidate graphs as ONE compiled
# program by vmapping the training scan over a candidate axis. ``stack``
# builds the batched operand: same-kind, same-n topologies whose array
# leaves gain a leading S axis while the pytree aux (kind, n, offsets)
# stays shared/static — exactly what ``jax.vmap(..., in_axes=0)`` expects.
# A stacked Topology is ONLY for vmapped consumption (``to_dense`` etc.
# assume unbatched leaves); ``unstack`` recovers the per-candidate views.

def widen_sparse(topo: Topology, k_max: int) -> Topology:
    """Re-pad a sparse topology to a larger static ``k_max`` (padded
    slots index the row itself with weight 0 — the payload convention),
    so candidates of different max degree can share one batched shape."""
    if topo.kind != "sparse":
        raise ValueError(f"widen_sparse needs a sparse topology, "
                         f"got {topo.kind!r}")
    pad = k_max - topo.k_max
    if pad < 0:
        raise ValueError(f"cannot narrow k_max {topo.k_max} -> {k_max}")
    if pad == 0:
        return topo
    self_idx = jnp.tile(jnp.arange(topo.n, dtype=jnp.int32)[:, None],
                        (1, pad))
    return dataclasses.replace(
        topo,
        neighbor_idx=jnp.concatenate([topo.neighbor_idx, self_idx], axis=1),
        neighbor_mask=jnp.concatenate(
            [topo.neighbor_mask, jnp.zeros((topo.n, pad), jnp.float32)],
            axis=1))


def stack(topos: Sequence[Topology], k_max: Optional[int] = None
          ) -> Topology:
    """Batch S same-kind, same-n topologies along a new leading axis.

    * dense:     ``adj (S, N, N)``
    * sparse:    every candidate is re-padded (``widen_sparse``) to the
                 shared ``K_max = max(k_max arg, per-candidate K)`` —
                 the tournament's "shared static K_max" — then
                 ``neighbor_idx/mask (S, N, K_max)``
    * circulant: traced ``shifts`` of equal length stack to ``(S, 2K)``;
                 STATIC offsets live in the pytree aux and cannot vary
                 across the batch — all members must carry the identical
                 offset tuple (the search maps circulant candidates to
                 sparse instead, DESIGN.md §10)

    ``deg`` stacks to ``(S, N)`` in every case.
    """
    topos = list(topos)
    if not topos:
        raise ValueError("stack needs at least one topology")
    kind, n = topos[0].kind, topos[0].n
    for t in topos:
        if t.kind != kind or t.n != n:
            raise ValueError(
                f"cannot stack mixed topologies: ({t.kind}, n={t.n}) vs "
                f"({kind}, n={n})")
    if kind == "sparse":
        shared_k = max([k_max or 1] + [t.k_max for t in topos])
        topos = [widen_sparse(t, shared_k) for t in topos]
    if kind == "circulant":
        traced = [t.shifts is not None for t in topos]
        if any(traced) and not all(traced):
            raise ValueError("cannot stack static-offset and traced-shift "
                             "circulants together")
        if all(traced):
            lens = {int(t.shifts.shape[0]) for t in topos}
            if len(lens) > 1:
                raise ValueError(f"traced shift chains differ in length: "
                                 f"{sorted(lens)}")
        elif len({t.offsets for t in topos}) > 1:
            raise ValueError(
                "static circulant offsets are pytree aux (jit-static) and "
                "cannot vary across a stack; use traced shifts or the "
                "sparse representation for mixed-offset candidate pools")
    # tree.map also re-checks aux equality via treedef matching.
    return jax.tree.map(lambda *xs: jnp.stack(xs), *topos)


def unstack(stacked: Topology) -> list:
    """Invert ``stack``: split the leading candidate axis back into a
    list of per-candidate topologies (shared aux preserved)."""
    s = stacked.deg.shape[0]
    return [jax.tree.map(lambda x: x[i], stacked) for i in range(s)]


# ---------------------------------------------------------------------------
# signed-offset helper (shared with distributed/permute_mixing)
# ---------------------------------------------------------------------------

def signed_offsets(offsets: Sequence[int], n: int):
    """±Δ as distinct nonzero shifts mod n (offset n/2 is self-paired)."""
    out = []
    for d in offsets:
        out.append(d % n)
        if (-d) % n != d % n:
            out.append((-d) % n)
    return sorted(set(out) - {0})


def _circulant_shifts(topo: Topology):
    """Iterable of ring shifts for the roll-chain backend: static Python
    ints (``offsets``) or traced int32 scalars (``shifts`` — scheduled
    rotating circulants). Chain length is static either way."""
    if topo.shifts is not None:
        return [topo.shifts[k] for k in range(topo.shifts.shape[0])]
    return signed_offsets(topo.offsets, topo.n)


# ---------------------------------------------------------------------------
# representation-dispatched primitives (jittable)
# ---------------------------------------------------------------------------

def weighted_neighbor_sum(topo: Topology, coeff: Array,
                          values,
                          edge_mask: Optional[Array] = None) -> Array:
    """``out_j = Σ_i a_ji · coeff_i · values_i`` — the Eq. 3 contraction.

    ``coeff (N,)``, ``values (N, ...)`` → ``(N, ...)``. Dispatches on the
    physical representation at trace time:

    * dense:     one masked matmul — O(N²·D)
    * sparse:    K_max-step neighbor gather-accumulate — O(N·K·D)
    * circulant: |±Δ|+1 fused rolls of ``coeff ⊙ values`` — O(N·|Δ|·D)
    * wire:      ``values`` is a ``core.wire_format.WirePayload`` (a
      quantizing channel's ``apply_wire`` output), decoded once to the
      f32 messages and contracted by the representation's path above.

    ``edge_mask`` (optional, DESIGN.md §11) is a representation-matched
    live-link mask from ``comm.channel.dropout_mask`` — dense ``(N, N)``,
    sparse ``(N, K_max)``, circulant ``(|±Δ|, N)`` (per receiver, one
    row per ring shift; the d = 0 self term never drops). A masked edge
    contributes nothing, exactly as if ``a_ji`` were zero this step.
    """
    if isinstance(values, wire_format.WirePayload):
        values = wire_format.decode_payload(values)
    # Weights are formed in the coeff dtype (f32 for rank-shaped rewards)
    # and cast to the values dtype before contracting, at every call site.
    if topo.kind == "dense":
        # direct contraction: coeff scales the (N, D) operand, then one
        # adjacency matmul — the (N, N) `adj ⊙ coeff` weight temp of the
        # legacy form never materializes (only a masked step still
        # forms one (N, N) temp).
        adj = topo.adj if edge_mask is None else topo.adj * edge_mask
        src = coeff.astype(values.dtype).reshape(
            (-1,) + (1,) * (values.ndim - 1)) * values
        return jnp.einsum("ji,i...->j...", adj.astype(values.dtype), src)
    if topo.kind == "circulant":
        c = coeff.astype(values.dtype)
        src = c.reshape((-1,) + (1,) * (values.ndim - 1)) * values
        acc = src  # d = 0 (self-loop)
        for k, d in enumerate(_circulant_shifts(topo)):
            term = jnp.roll(src, -d, axis=0)
            if edge_mask is not None:
                term = term * edge_mask[k].astype(values.dtype).reshape(
                    (-1,) + (1,) * (values.ndim - 1))
            acc = acc + term
        return acc
    # sparse: loop over neighbor slots; each step is one row-gather + fma,
    # keeping transients at one (N, ...) slab (vs (N, K, ...) for a single
    # big gather). Unrolled ×4 so XLA fuses gather+fma chains.
    idx, mask = topo.neighbor_idx, topo.neighbor_mask
    if edge_mask is not None:
        mask = mask * edge_mask
    k_max = idx.shape[1]
    wnb = (mask * jnp.take(coeff, idx)).astype(values.dtype)    # (N, K)

    def one(c, acc):
        col = idx[:, c]
        w = wnb[:, c].reshape((-1,) + (1,) * (values.ndim - 1))
        return acc + w * jnp.take(values, col, axis=0)

    acc = jnp.zeros_like(values)
    k4 = k_max - k_max % 4
    if k4:
        def body(kk, a):
            for u in range(4):
                a = one(kk * 4 + u, a)
            return a
        acc = jax.lax.fori_loop(0, k4 // 4, body, acc)
    for c in range(k4, k_max):
        acc = one(c, acc)
    return acc


# ---------------------------------------------------------------------------
# in-place representation refresh (jittable — the topology-schedule paths)
# ---------------------------------------------------------------------------
#
# A scheduled topology (core/topology_sched.py) lives in a lax.scan carry,
# so its updates must keep every array shape and the pytree aux static:
# dense refreshes swap the (N, N) mask, sparse refreshes re-pad to the
# SAME K_max via top_k, rotating circulants swap the traced shift values.

def refresh_dense(topo: Topology, adj: Array) -> Topology:
    """New dense adjacency in place (degrees recomputed on device)."""
    return dataclasses.replace(topo, adj=adj, deg=adj.sum(axis=1))


def refresh_sparse(topo: Topology, adj: Array) -> Topology:
    """Re-derive the neighbor list from a fresh (N, N) adjacency, padded
    to the EXISTING static ``k_max`` (on device, via per-row top_k).

    Rows whose degree exceeds ``k_max`` are truncated to k_max edges
    (schedules size the pad with binomial-tail headroom so this is a
    vanishing-probability event — DESIGN.md §9); ``deg`` counts the KEPT
    edges so degree normalization stays consistent with what the gather
    actually sums. Assumes non-negative edge weights (the generators emit
    binary graphs) — top_k would misorder negative weights.
    """
    k_max = topo.k_max
    vals, idx = jax.lax.top_k(adj, k_max)          # (N, K), (N, K)
    return dataclasses.replace(
        topo, neighbor_idx=idx.astype(jnp.int32),
        neighbor_mask=vals.astype(jnp.float32),
        deg=vals.sum(axis=1).astype(jnp.float32))


def shift_circulant(topo: Topology, offsets: Array) -> Topology:
    """Swap the traced offset set of a scheduled circulant.

    ``offsets (K,)`` int32, values in [1, (n−1)//2] — the bound keeps
    +d and −d distinct so the signed chain ±Δ has exactly 2K distinct
    nonzero shifts and the degree (2K + 1) is invariant under rotation.
    """
    signed = jnp.concatenate([offsets, topo.n - offsets]).astype(jnp.int32)
    return dataclasses.replace(topo, shifts=signed)


def neighbor_column(topo: Topology, i: Array,
                    edge_mask: Optional[Array] = None) -> Array:
    """Dense column i of the adjacency — ``a_:,i`` as an (N,) vector.

    Used by the distributed seed-replay ε-scan, which consumes one
    per-SOURCE weight column per scan step: this derives the column from
    the live representation in O(N + K) instead of materializing the
    O(N²) dense adjacency up front. Relies on symmetry (column i ≡ row
    i), which every generator guarantees (core/topology.py conventions).

    ``edge_mask`` (DESIGN.md §11) masks dropped links; it must be
    link-symmetric (``comm.channel.dropout_mask`` draws per UNDIRECTED
    edge id, so it is) — the sparse/circulant paths read receiver-side
    entries through row i's symmetry.
    """
    if topo.kind == "dense":
        col = topo.adj[:, i]
        return col if edge_mask is None else col * edge_mask[:, i]
    if topo.kind == "circulant":
        col = jnp.zeros((topo.n,), jnp.float32).at[i].set(1.0)
        shifts = _circulant_shifts(topo)
        if not shifts:
            return col
        # receivers r = (i + d) mod n hear source i via the CONJUGATE
        # shifts −d; with link-symmetric masks the weight of edge {i, r}
        # is edge_mask[k, i] — row k holds the {j, j+d} links, and at
        # j = i that IS the undirected {i, r} link. One scatter-add
        # (shifts are distinct and nonzero, so targets never collide).
        rs = (i + jnp.stack([jnp.asarray(d) for d in shifts])) % topo.n
        w = (jnp.ones((len(shifts),), jnp.float32) if edge_mask is None
             else edge_mask[:, i])
        return col.at[rs].add(w)
    # sparse: scatter row i's neighbor list (padded slots add weight 0);
    # symmetric link masks let row i's mask stand in for column i's.
    mask_row = topo.neighbor_mask[i]
    if edge_mask is not None:
        mask_row = mask_row * edge_mask[i]
    return jnp.zeros((topo.n,), jnp.float32).at[topo.neighbor_idx[i]].add(
        mask_row)


def weighted_row_sum(topo: Topology, coeff: Array,
                     edge_mask: Optional[Array] = None) -> Array:
    """``Σ_i a_ji · coeff_i`` per row j — the self-correction weight.
    ``edge_mask`` drops links exactly as in ``weighted_neighbor_sum``
    (the two MUST see the same mask or Eq. 3's self term desyncs from
    the neighbor sum)."""
    if topo.kind == "dense":
        # matvec, not broadcast-then-reduce: `adj ⊙ coeff` is an (N, N)
        # temp the dot_general never needs (same micro-opt as the dense
        # weighted_neighbor_sum).
        adj = topo.adj if edge_mask is None else topo.adj * edge_mask
        return adj @ coeff
    if topo.kind == "circulant":
        acc = coeff
        for k, d in enumerate(_circulant_shifts(topo)):
            term = jnp.roll(coeff, -d)
            if edge_mask is not None:
                term = term * edge_mask[k]
            acc = acc + term
        return acc
    mask = topo.neighbor_mask
    if edge_mask is not None:
        mask = mask * edge_mask
    return (mask * jnp.take(coeff, topo.neighbor_idx)).sum(axis=1)
