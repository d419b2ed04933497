"""Beyond-paper: bandwidth-optimal θ-mixing for CIRCULANT topologies via a
collective-permute chain (DESIGN.md §2).

For a general Erdos-Renyi adjacency the θ-mixing einsum lowers to an
all-gather: every chip receives all N agents' shards (N·D bytes) even
though a density-p graph only USES p·N of them. A circulant graph with
offset set Δ (``topology.circulant_erdos_renyi`` — same density and degree
statistics as ER) makes the neighborhood structure uniform:

    mixed_j = Σ_{d ∈ ±Δ ∪ {0}} w_j,(j+d) · θ_{j+d}

so the mixing becomes |±Δ| ring rotations (``lax.ppermute``) of the local
θ shard with a weighted accumulation — exactly p·N·D bytes, a 1/p saving,
with perfect ring-schedule overlap on TPU ICI.

Implemented as a shard_map over the agent axis; the jnp reference
(`circulant_mixing_ref`) is the oracle for the multi-device equivalence
test (tests/test_permute_mixing.py runs it on 8 forced host devices in a
subprocess so the single-device test session stays clean).
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.topology_repr import Topology, signed_offsets  # noqa: F401
# signed_offsets moved to core.topology_repr (the circulant representation
# owns its offset algebra); re-exported here for existing importers.


def _wire_codec(channel):
    """Resolve a ``comm.channel.Channel`` into the per-shard payload
    encoder applied BEFORE the collective (DESIGN.md §11): each chip
    compresses its local θ rows once and every hop moves the narrow
    payload. Only stateless compression belongs at this layer — the
    collective schedule is static, so stateful stages (event triggers,
    edge dropout) live in the step builders, not the wire."""
    if channel is None or channel.lossless:
        return lambda x: x
    if not channel.collective_eligible:
        raise ValueError(
            "collective-layer channels carry only stateless payload "
            "codecs (quantize/topk); event_triggered and dropout stages "
            "thread through the train-step builders instead")
    return lambda x: channel.codec(x, batched=True)


def circulant_mixing_ref(weights: jax.Array, thetas: jax.Array,
                         offsets: Sequence[int]) -> jax.Array:
    """Oracle: mixed_j = Σ_d w[j, (j+d)%N]·θ_{(j+d)%N}, d ∈ ±Δ ∪ {0}.

    weights: (N, N) dense mixing weights (e.g. adj · R̃); thetas: (N, D).
    Only the circulant-neighborhood entries of ``weights`` are read.
    """
    n = thetas.shape[0]
    idx = jnp.arange(n)
    acc = weights[idx, idx][:, None] * thetas
    for d in signed_offsets(offsets, n):
        src = (idx + d) % n
        acc = acc + weights[idx, src][:, None] * thetas[src]
    return acc


def make_permute_mixing(mesh: Mesh, axis: str, offsets: Sequence[int],
                        channel=None):
    """Returns mix(weights (N,N), thetas (N,D)) -> (N,D), sharded over
    ``axis`` with agent-dim placement, moving p·N·D bytes via a ppermute
    chain instead of an N·D all-gather. ``channel`` (DESIGN.md §11)
    encodes each chip's θ shard ONCE before it enters the ring — a
    quantize(bits=8) channel moves p·N·D BYTES instead of p·N·D floats.
    The self term also reads the encoded value, matching the core
    engine (and the all-gather backends), where every consumer of the
    payload — agent j included — sees the wire encoding."""
    n = mesh.shape[axis]
    shifts = signed_offsets(offsets, n)
    encode = _wire_codec(channel)

    def local_mix(weights, theta):
        # theta: (1, D) local shard; weights: (N, N) replicated
        j = jax.lax.axis_index(axis)
        recv = encode(theta)
        acc = weights[j, j] * recv
        prev_shift = 0
        for d in shifts:
            # rotate the RING by (d − prev): chip j receives chip (j+d)'s θ
            step = (d - prev_shift) % n
            perm = [(src, (src - step) % n) for src in range(n)]
            recv = jax.lax.ppermute(recv, axis, perm)
            prev_shift = d
            src_idx = (j + d) % n
            acc = acc + weights[j, src_idx] * recv
        return acc

    mixed = jax.shard_map(
        local_mix, mesh=mesh,
        in_specs=(P(None, None), P(axis, None)),
        out_specs=P(axis, None))
    return mixed


# ---------------------------------------------------------------------------
# representation dispatch (DESIGN.md §3): one mixing signature, three wire
# formats. mix(weights (N, N), thetas (N, D)) -> (N, D), agent-sharded.
# ---------------------------------------------------------------------------

def make_allgather_mixing(mesh: Mesh, axis: str, channel=None):
    """Dense backend: one tiled all-gather of θ (N·D bytes) + local
    row-contraction — what the einsum in ``netes_dist`` lowers to, made
    explicit so the dispatch has a uniform shard_map shape. ``channel``
    encodes the shard before the gather; the local row j is re-read from
    the gathered buffer, so every chip (including j itself) contracts
    the SAME wire values — receivers never diverge."""
    encode = _wire_codec(channel)

    def local_mix(weights, theta):
        j = jax.lax.axis_index(axis)
        full = jax.lax.all_gather(encode(theta), axis, axis=0,
                                  tiled=True)                   # (N, D)
        return (weights[j] @ full)[None]

    return jax.shard_map(local_mix, mesh=mesh,
                         in_specs=(P(None, None), P(axis, None)),
                         out_specs=P(axis, None))


def make_sparse_gather_mixing(mesh: Mesh, axis: str, topo: Topology,
                              channel=None):
    """Sparse backend: all-gather θ, then contract ONLY the K_max listed
    neighbors — O(K·D) local flops instead of O(N·D).

    The collective is still the dense all-gather (an arbitrary neighbor
    set has no static ppermute schedule); the win over the dense backend
    is the local compute + the O(N·K) weight footprint. A
    neighborhood-routed exchange (per-edge ppermutes batched by offset)
    is the circulant case below; generalizing it to arbitrary sparse
    graphs is future work recorded in DESIGN.md §3. ``channel`` encodes
    the shard before the gather (quantized neighbor fetches).
    """
    idx, mask = topo.neighbor_idx, topo.neighbor_mask
    encode = _wire_codec(channel)

    def local_mix(weights, theta):
        j = jax.lax.axis_index(axis)
        full = jax.lax.all_gather(encode(theta), axis, axis=0,
                                  tiled=True)                   # (N, D)
        cols = idx[j]                                   # (K,)
        # ``weights`` is the full mixing matrix (adj ⊙ R̃) — the edge
        # weight is already in it, so only the PADDING indicator of
        # neighbor_mask applies here (the mask carries a_ji itself;
        # multiplying by it would square the weight on weighted graphs).
        valid = (mask[j] != 0).astype(weights.dtype)
        w = weights[j, cols] * valid                    # (K,)
        return (w @ jnp.take(full, cols, axis=0))[None]

    return jax.shard_map(local_mix, mesh=mesh,
                         in_specs=(P(None, None), P(axis, None)),
                         out_specs=P(axis, None))


def make_topology_mixing(mesh: Mesh, axis: str, topo: Topology,
                         channel=None):
    """Pick the distributed mixing backend from the topology's physical
    representation. The circulant ppermute chain (p·N·D bytes) is one case
    of the same dispatch; dense and sparse share the all-gather wire
    format and differ in local contraction cost. ``channel`` applies the
    same wire codec to whichever backend wins (DESIGN.md §11)."""
    if topo.kind == "circulant":
        return make_permute_mixing(mesh, axis, topo.offsets,
                                   channel=channel)
    if topo.kind == "sparse":
        return make_sparse_gather_mixing(mesh, axis, topo, channel=channel)
    return make_allgather_mixing(mesh, axis, channel=channel)


# ---------------------------------------------------------------------------
# scheduled (rotating) circulants — DESIGN.md §9
# ---------------------------------------------------------------------------

def make_rotating_permute_mixing(mesh: Mesh, axis: str,
                                 offsets: Sequence[int], stride: int,
                                 channel=None):
    """Rotating-circulant backend: ``mix(weights, thetas, t) -> (N, D)``.

    The ``rotate_circulant`` schedule maps offset d to
    ((d − 1 + t·stride) mod m) + 1 with m = (n−1)//2, so the offset sets
    cycle with period m / gcd(stride, m). ``lax.ppermute`` needs a STATIC
    permutation, so the schedule compiles every phase's chain once and
    ``lax.switch``es on ``t mod cycle`` — the branch index is replicated
    (same t on every chip), so all chips take the same chain and the
    collective stays deadlock-free. Every phase moves exactly |±Δ| hops
    of D floats: the rotation is wire-free (zero EXTRA bytes vs the
    static circulant), paying only compile time ∝ the cycle length —
    fine at mesh scale (cycle ≤ (n−1)//2 with n = device count).
    """
    n = mesh.shape[axis]
    m = max(1, (n - 1) // 2)
    if offsets and max(offsets) > m:
        raise ValueError(f"rotating offsets must lie in [1, {m}] (n={n})")
    cycle = m // math.gcd(stride % m or m, m)
    encode = _wire_codec(channel)

    def chain(offs):
        def local_chain(weights, theta):
            j = jax.lax.axis_index(axis)
            recv = encode(theta)
            acc = weights[j, j] * recv
            prev_shift = 0
            for d in signed_offsets(offs, n):
                step = (d - prev_shift) % n
                perm = [(src, (src - step) % n) for src in range(n)]
                recv = jax.lax.ppermute(recv, axis, perm)
                prev_shift = d
                src_idx = (j + d) % n
                acc = acc + weights[j, src_idx] * recv
            return acc

        return local_chain

    branches = [chain([(d - 1 + c * stride) % m + 1 for d in offsets])
                for c in range(cycle)]

    def local_mix(weights, theta, t):
        return jax.lax.switch(t % cycle, branches, weights, theta)

    return jax.shard_map(local_mix, mesh=mesh,
                         in_specs=(P(None, None), P(axis, None), P()),
                         out_specs=P(axis, None))


# ---------------------------------------------------------------------------
# static-analysis registry hook (repro.analysis — DESIGN.md §14)
# ---------------------------------------------------------------------------

def analysis_entry_points():
    """Contract-linter entry points for the collective-permute mixing
    backends. The rotating variant is the repo's only ``lax.switch`` over
    ppermute chains — the branch-collective-parity contract (deadlock
    freedom under the replicated phase index) is checked on a real
    multi-branch switch, which needs n ≥ 5 devices for cycle > 1 (the CI
    static-analysis job forces an 8-device host platform)."""
    from repro.analysis.registry import EntryPoint

    def _mesh():
        from repro.distributed.fleet_shard import build_mesh
        return build_mesh()

    def _mix_args(n, d=16):
        return (jnp.ones((n, n), jnp.float32), jnp.ones((n, d),
                                                        jnp.float32))

    def build_static_chain():
        mesh = _mesh()
        n = mesh.shape["agents"]
        fn = make_permute_mixing(mesh, "agents", (1,))
        return fn, _mix_args(n), {}

    def build_rotating_switch():
        mesh = _mesh()
        n = mesh.shape["agents"]
        fn = make_rotating_permute_mixing(mesh, "agents", (1, 2), stride=1)
        return fn, _mix_args(n) + (jnp.zeros((), jnp.int32),), {}

    return (
        EntryPoint(name="permute_mixing.static_chain",
                   build=build_static_chain, min_devices=2),
        EntryPoint(name="permute_mixing.rotating_switch",
                   build=build_rotating_switch, min_devices=5),
    )
