"""Lossy communication channels between learning agents (DESIGN.md §11).

Every link in the PR-1…4 stack is an idealized channel: lossless,
full-precision f32, always on. Real fleets pay for every wire byte and
lose messages — Chen et al. (arXiv:1812.03239) show event-triggered /
compressed updates preserve convergence at a fraction of the traffic,
and Adjodah et al. (arXiv:1711.11180) argue sparser *effective*
communication can even help learning. This module makes the channel a
first-class, serializable, scan-compatible object, mirroring the shape
of ``core/topology_sched.py``:

``ChannelSpec``
    A pipeline of ``StageSpec``s applied in order to every per-agent
    payload (and the broadcast-best payload):

    * ``lossless``                 — the identity (the PR-1…4 behavior);
    * ``quantize(bits∈{8,4,1})``   — per-message symmetric uniform
      quantization (absmax scale); ``bits=1`` is sign quantization
      (sign(x)·mean|x|, à la 1-bit SGD);
    * ``topk(frac)``               — keep the ``frac`` largest-magnitude
      entries of each message, zero the rest (wire format: value +
      index per kept entry);
    * ``event_triggered(threshold)`` — LAPG-style lazy links: a source
      re-sends only when the RMS change versus its *last transmitted*
      payload exceeds ``threshold``; receivers otherwise reuse the
      stale reference (carried in ``ChannelState.last_sent``);
    * ``dropout(p, seed)``         — fault injection: each undirected
      LINK fails independently with probability ``p`` per iteration
      (both directions at once — a down link drops both messages).
      Draws come from a stateless per-edge PRF (threefry fold-in of
      the canonical edge id), so the SAME edges fail regardless of the
      physical representation: dense and sparse runs of one graph stay
      bit-comparable under identical faults.

``Channel``
    The compiled form (``compile_channel``): hashable, so it rides
    through ``jax.jit`` as a static argument while every array lives in
    the ``ChannelState`` it initializes — threefry key (dropout draws),
    per-agent last-sent reference (event triggering), and the realized
    traffic counter. The state joins the ``lax.scan`` carry next to the
    NetES/schedule state: every encode, trigger decision, and edge drop
    happens ON DEVICE with zero steady-state recompiles (gated by
    ``count_backend_compiles`` exactly like schedules are).

Realized vs modeled traffic: ``benchmarks/perfmodel.wire_bytes`` models
the topology's *capacity*; the channel counts what actually moved —
per-step live directed edges × triggered sources (plus broadcast
events), accumulated in ``ChannelState.msgs`` and emitted per step in
the metrics. ``payload_bytes`` converts message counts to wire bytes
under the pipeline's encoding (bits/element × kept fraction + top-k
index overhead). The resilience bench gates the realized counter the
same way modeled wire bytes are gated (exact equality).
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import topology_repr, wire_format
from repro.core.topology_repr import Topology

Array = jax.Array

# The codec's decode as a Pallas-inlinable block function (DESIGN.md §12):
# pure jnp over aligned (codes, scale) slabs, uniform across q8/q4/q1 —
# `kernels/netes_fused_mixing` inlines it per tile, `topology_repr`'s
# dense/circulant fallbacks call it whole-array. Re-exported here so the
# channel module remains the single façade for codec semantics.
decode_block = wire_format.decode

STAGE_KINDS = ("lossless", "quantize", "topk", "event_triggered",
               "dropout")
QUANTIZE_BITS = (8, 4, 1)


@dataclasses.dataclass(frozen=True)
class StageSpec:
    """One pipeline stage (serializable, hashable)."""

    kind: str
    bits: int = 8             # quantize: 8 | 4 | 1 (sign)
    frac: float = 0.25        # topk: fraction of entries kept
    threshold: float = 0.0    # event_triggered: RMS re-send threshold
    p: float = 0.0            # dropout: per-link failure probability
    seed: int = 0             # dropout: threefry stream

    def __post_init__(self):
        if self.kind not in STAGE_KINDS:
            raise ValueError(f"unknown channel stage {self.kind!r}; "
                             f"available: {STAGE_KINDS}")
        if self.kind == "quantize" and self.bits not in QUANTIZE_BITS:
            raise ValueError(f"quantize needs bits in {QUANTIZE_BITS}, "
                             f"got {self.bits}")
        if self.kind == "topk" and not 0.0 < self.frac <= 1.0:
            raise ValueError(f"topk needs 0 < frac <= 1, got {self.frac}")
        if self.kind == "event_triggered" and self.threshold < 0:
            raise ValueError("event_triggered needs threshold >= 0")
        if self.kind == "dropout" and not 0.0 <= self.p < 1.0:
            raise ValueError(f"dropout needs 0 <= p < 1, got {self.p}")

    def label(self) -> str:
        return {
            "lossless": "id",
            "quantize": f"q{self.bits}",
            "topk": f"top{self.frac:g}",
            "event_triggered": f"evt{self.threshold:g}",
            "dropout": f"drop{self.p:g}",
        }[self.kind]


_FLOAT_KEYS = ("frac", "threshold", "p")
_STAGE_ARGS = ("bits", "frac", "threshold", "p", "seed")


@dataclasses.dataclass(frozen=True)
class ChannelSpec:
    """Serializable channel description (travels with ``TopologySpec``
    through ``TrainConfig.channel`` and ``launch/specs.PairSpec.chan``).

    ``stages`` apply in order; an empty tuple is the lossless channel.
    At most one ``event_triggered`` and one ``dropout`` stage (a second
    reference buffer / failure process has no physical reading).
    """

    stages: Tuple[StageSpec, ...] = ()

    def __post_init__(self):
        stages = tuple(s for s in self.stages if s.kind != "lossless")
        object.__setattr__(self, "stages", stages)
        for kind in ("event_triggered", "dropout"):
            if sum(s.kind == kind for s in stages) > 1:
                raise ValueError(f"at most one {kind} stage per channel")

    @property
    def lossless(self) -> bool:
        return not self.stages

    @classmethod
    def parse(cls, text: str) -> "ChannelSpec":
        """``"lossless" | "quantize(bits=8)" |
        "event_triggered(threshold=0.01)|quantize(bits=4)|dropout(p=0.1,
        seed=3)"`` — stages separated by ``|``, applied left to right."""
        stages = []
        for part in text.split("|"):
            m = re.fullmatch(r"\s*(\w+)\s*(?:\(([^)]*)\))?\s*", part)
            if not m:
                raise ValueError(f"unparseable channel stage {part!r}")
            kind, argstr = m.group(1), m.group(2) or ""
            kw = {}
            for item in filter(None,
                               (p.strip() for p in argstr.split(","))):
                k, sep, v = item.partition("=")
                if not sep:
                    raise ValueError(
                        f"channel arg {item!r} is not key=value")
                k = k.strip()
                if k not in _STAGE_ARGS:
                    raise ValueError(f"unknown channel stage arg {k!r}; "
                                     f"available: {sorted(_STAGE_ARGS)}")
                kw[k] = float(v) if k in _FLOAT_KEYS else int(v)
            stages.append(StageSpec(kind=kind, **kw))
        return cls(stages=tuple(stages))

    def label(self) -> str:
        if self.lossless:
            return "lossless"
        return "|".join(s.label() for s in self.stages)


class ChannelState(NamedTuple):
    """The scan-carry: threefry key for the dropout stream, the per-agent
    last-transmitted reference (event triggering; ``()`` when the
    pipeline has no event stage), and the cumulative realized message
    counter. A plain pytree — it checkpoints through
    ``checkpoint.save_pytree`` and joins the ``lax.scan`` carry next to
    the NetES (and schedule) state."""

    key: Array        # threefry carry (dropout consumes it)
    last_sent: Any    # payload-shaped pytree, or () without event stage
    msgs: Array       # float32 — cumulative realized directed messages


@dataclasses.dataclass(frozen=True)
class Channel:
    """Compiled (spec × population size) — hashable, so it rides through
    ``jax.jit`` as a static argument while every array lives in the
    ``ChannelState`` it initializes and advances.

    ``fused`` is a compile-level dispatch preference (hashable, so it is
    part of the jit-static identity): when True (the default) and the
    pipeline is ``wire_quantized``, channel-carrying steps hand the
    contraction the encoded ``WirePayload`` (``apply_wire``) instead of
    the fake-quant f32 payload, and ``topology_repr`` routes sparse
    graphs through ``kernels/netes_fused_mixing``. False forces the
    legacy decode-then-contract path — the benches' unfused control
    legs. Either way the channel's *semantics* (scale, rounding, masks,
    traffic accounting) are identical."""

    spec: ChannelSpec
    n: int
    fused: bool = True

    @property
    def lossless(self) -> bool:
        return self.spec.lossless

    @property
    def event_stage(self) -> Optional[StageSpec]:
        for s in self.spec.stages:
            if s.kind == "event_triggered":
                return s
        return None

    @property
    def dropout_stage(self) -> Optional[StageSpec]:
        for s in self.spec.stages:
            if s.kind == "dropout":
                return s
        return None

    @property
    def quantize_stage(self) -> Optional[StageSpec]:
        for s in self.spec.stages:
            if s.kind == "quantize":
                return s
        return None

    @property
    def wire_quantized(self) -> bool:
        """True iff the pipeline admits the wire-form encoding: exactly
        one quantize stage, with no payload-TRANSFORMING stage after it
        (a later quantize/topk/event would have to read decoded values,
        re-materializing what the fusion deletes). ``dropout`` after the
        quantize is fine — it only produces an edge mask."""
        kinds = [s.kind for s in self.spec.stages]
        if kinds.count("quantize") != 1:
            return False
        after = kinds[kinds.index("quantize") + 1:]
        return all(k == "dropout" for k in after)

    @property
    def collective_eligible(self) -> bool:
        """True iff every stage is a stateless payload codec (quantize /
        topk): the subset a collective-layer wire encoder can apply
        (DESIGN.md §13). Event triggers and dropout carry state / need
        globally-consistent draws, so they thread through the step
        builders — a sharded engine falls back to replicated mixing for
        them (``distributed/fleet_shard``)."""
        return self.event_stage is None and self.dropout_stage is None

    def wire_fused(self, topo: Topology) -> bool:
        """Trace-time dispatch decision for a channel-carrying step:
        route through ``apply_wire`` (the payload stays int8 until the
        contraction decodes it, and the broadcast-best payload goes
        through the fused broadcast select)? Sparse only; dense and
        circulant graphs keep the fake-quant path."""
        return self.fused and self.wire_quantized and topo.kind == "sparse"

    @property
    def elem_bytes(self) -> float:
        """Effective wire bytes per f32 payload element under the
        pipeline's encoding: quantization narrows each element, top-k
        sends ``frac`` of them (value + int32 index each)."""
        bits, frac, index_bits = 32, 1.0, 0
        for s in self.spec.stages:
            if s.kind == "quantize":
                bits = s.bits
            elif s.kind == "topk":
                frac = s.frac
                index_bits = 32
        return frac * (bits + index_bits) / 8.0

    def payload_bytes(self, d: int) -> float:
        """Wire bytes of one encoded d-element message."""
        return d * self.elem_bytes

    # -- state ------------------------------------------------------------
    def init(self, template: Any) -> ChannelState:
        """t = 0 state for payloads shaped like ``template`` (an (N, ...)
        array, or a pytree of (N, ...) leaves for the distributed
        replica step). Pure jnp — ``jax.eval_shape``-able."""
        seed = self.dropout_stage.seed if self.dropout_stage else 0
        last = (jax.tree.map(jnp.zeros_like, template)
                if self.event_stage else ())
        return ChannelState(key=jax.random.PRNGKey(seed), last_sent=last,
                            msgs=jnp.zeros((), jnp.float32))

    # -- traced -----------------------------------------------------------
    def apply(self, state: ChannelState, topo: Topology, payload: Any
              ) -> Tuple[Any, Optional[Any], ChannelState, dict]:
        """One channel step over per-source payloads.

        ``payload``: an (N, ...) array — or a pytree of (N, ...) leaves,
        in which case one message is an agent's whole tree slice (the
        event trigger fires per agent across all leaves). Returns
        ``(wire_payload, edge_mask, state', info)`` where ``edge_mask``
        is a representation-matched live-link mask (or None) for
        ``topology_repr``'s contraction primitives, and ``info`` carries
        the per-step realized ``msgs`` and ``trigger_frac``. Pure jax;
        shapes and pytree structure are invariant, so this composes with
        ``lax.scan`` (the whole pipeline lives inside ONE compiled
        scan)."""
        key = state.key
        x = payload
        new_last = state.last_sent
        triggered = None
        edge_mask = None
        for st in self.spec.stages:
            if st.kind == "quantize":
                x = jax.tree.map(lambda l, b=st.bits:
                                 _quantize(l, b, batched=True), x)
            elif st.kind == "topk":
                x = jax.tree.map(lambda l, f=st.frac:
                                 _keep_topk(l, f, batched=True), x)
            elif st.kind == "event_triggered":
                x, new_last, triggered = _event_select(
                    x, state.last_sent, st.threshold)
            else:  # dropout
                key, sub = jax.random.split(key)
                edge_mask = dropout_mask(sub, topo, st.p)
        msgs = realized_messages(topo, edge_mask, triggered)
        info = self._info(topo, edge_mask, triggered, msgs)
        new_state = ChannelState(key=key, last_sent=new_last,
                                 msgs=state.msgs + msgs)
        return x, edge_mask, new_state, info

    def _info(self, topo: Topology, edge_mask, triggered, msgs) -> dict:
        """Per-step traffic info (DESIGN.md §15 wire probes): realized
        ``msgs``, the event-stage ``trigger_frac``, and the dropout
        stage's realized ``drop_frac`` (dropped / would-have-moved;
        exactly 0.0 for drop-free channels — no extra pass is traced
        unless a dropout stage exists)."""
        info = {
            "msgs": msgs,
            "trigger_frac": (jnp.ones((), jnp.float32) if triggered is None
                             else triggered.astype(jnp.float32).mean()),
        }
        if self.dropout_stage is not None and edge_mask is not None:
            potential = realized_messages(topo, None, triggered)
            info["drop_frac"] = jnp.where(
                potential > 0, 1.0 - msgs / jnp.maximum(potential, 1.0),
                jnp.zeros((), jnp.float32))
        else:
            info["drop_frac"] = jnp.zeros((), jnp.float32)
        return info

    def codec(self, x: Any, batched: bool = False) -> Any:
        """The stateless payload compression alone (quantize/topk) —
        applied to payloads outside the per-edge mixing links, e.g. the
        broadcast-best parameters every agent adopts. ``batched=True``
        treats the leading axis as independent messages; ``False``
        treats each leaf as one message."""
        for st in self.spec.stages:
            if st.kind == "quantize":
                x = jax.tree.map(lambda l, b=st.bits:
                                 _quantize(l, b, batched), x)
            elif st.kind == "topk":
                x = jax.tree.map(lambda l, f=st.frac:
                                 _keep_topk(l, f, batched), x)
        return x

    def apply_wire(self, state: ChannelState, topo: Topology, payload: Any
                   ) -> Tuple[Any, Optional[Any], ChannelState, dict]:
        """``apply`` with the quantize stage left in WIRE FORM: identical
        stage order, trigger decisions, dropout draws, and traffic
        accounting, but the quantize stage ENCODES (``wire_format.encode``)
        instead of fake-quantizing, so the returned payload is a pytree of
        ``WirePayload`` leaves, decoded where they are contracted
        (``topology_repr.weighted_neighbor_sum``). Requires
        ``wire_quantized`` (checked at trace time): every stage that
        reads payload VALUES runs before the encode, and only mask-only
        stages (dropout) follow it."""
        if not self.wire_quantized:
            raise ValueError(
                f"channel {self.spec.label()!r} is not wire-encodable: "
                "apply_wire needs exactly one quantize stage with only "
                "dropout after it (see Channel.wire_quantized)")
        key = state.key
        x = payload
        new_last = state.last_sent
        triggered = None
        edge_mask = None
        for st in self.spec.stages:
            if st.kind == "quantize":
                x = jax.tree.map(lambda l, b=st.bits:
                                 wire_format.encode(l, b, batched=True), x)
            elif st.kind == "topk":
                x = jax.tree.map(lambda l, f=st.frac:
                                 _keep_topk(l, f, batched=True), x)
            elif st.kind == "event_triggered":
                x, new_last, triggered = _event_select(
                    x, state.last_sent, st.threshold)
            else:  # dropout
                key, sub = jax.random.split(key)
                edge_mask = dropout_mask(sub, topo, st.p)
        msgs = realized_messages(topo, edge_mask, triggered)
        info = self._info(topo, edge_mask, triggered, msgs)
        new_state = ChannelState(key=key, last_sent=new_last,
                                 msgs=state.msgs + msgs)
        return x, edge_mask, new_state, info

    def encode_wire(self, x: Any, batched: bool = False) -> Any:
        """``codec`` with the quantize stage left in wire form — the
        broadcast-best payload's twin of ``apply_wire``. Returns a pytree
        of ``WirePayload`` leaves for ``fused_broadcast_select``; requires
        ``wire_quantized`` like ``apply_wire`` does."""
        if not self.wire_quantized:
            raise ValueError(
                f"channel {self.spec.label()!r} is not wire-encodable "
                "(see Channel.wire_quantized)")
        for st in self.spec.stages:
            if st.kind == "quantize":
                x = jax.tree.map(lambda l, b=st.bits:
                                 wire_format.encode(l, b, batched), x)
            elif st.kind == "topk":
                x = jax.tree.map(lambda l, f=st.frac:
                                 _keep_topk(l, f, batched), x)
        return x


def compile_channel(spec: Optional[ChannelSpec | str], n: int,
                    fused: bool = True) -> Channel:
    """Resolve a ``ChannelSpec`` (or its string form; None compiles as
    lossless) for an n-agent population. ``fused=False`` pins the legacy
    fake-quant dispatch (the benches' unfused control legs)."""
    if spec is None:
        spec = ChannelSpec()
    elif isinstance(spec, str):
        spec = ChannelSpec.parse(spec)
    return Channel(spec=spec, n=n, fused=fused)


# ---------------------------------------------------------------------------
# payload codecs (pure jnp; rowwise when batched)
# ---------------------------------------------------------------------------

def _msg_axes(x: Array, batched: bool) -> Tuple[int, ...]:
    return tuple(range(1 if batched else 0, x.ndim))


def _quantize(x: Array, bits: int, batched: bool) -> Array:
    """Symmetric uniform quantization with per-message absmax scale;
    ``bits=1`` is sign quantization (sign(x) · mean|x|)."""
    axes = _msg_axes(x, batched)
    if bits == 1:
        scale = jnp.abs(x).mean(axis=axes, keepdims=True)
        return (jnp.sign(x) * scale).astype(x.dtype)
    levels = float(2 ** (bits - 1) - 1)
    amax = jnp.abs(x).max(axis=axes, keepdims=True)
    s = amax / levels
    q = jnp.round(x / jnp.where(s > 0, s, 1.0))
    return (q * s).astype(x.dtype)


def _keep_topk(x: Array, frac: float, batched: bool) -> Array:
    """Keep the ceil(frac·m) largest-|x| entries per message, zero the
    rest (static k — ``frac`` is spec-level, so shapes stay fixed)."""
    if frac >= 1.0:
        return x
    lead = x.shape[0] if batched else 1
    flat = x.reshape(lead, -1)
    m = flat.shape[1]
    k = max(1, int(math.ceil(frac * m)))
    if k >= m:
        return x
    _, idx = jax.lax.top_k(jnp.abs(flat), k)              # (lead, k)
    keep = jnp.zeros_like(flat).at[
        jnp.arange(lead)[:, None], idx].set(1.0)
    return (flat * keep).reshape(x.shape)


def _event_select(x: Any, last: Any, threshold: float):
    """LAPG-style trigger: source i re-sends iff the RMS change of its
    message (across ALL leaves) versus the last transmitted one exceeds
    ``threshold`` (strict — threshold 0 sends on any change). Returns
    (wire payload, new last-sent reference, triggered (N,) bool)."""
    leaves = jax.tree.leaves(x)
    n = leaves[0].shape[0]
    sq = jnp.zeros((n,), jnp.float32)
    dims = 0
    for l_new, l_old in zip(leaves, jax.tree.leaves(last), strict=True):
        d = l_new.astype(jnp.float32) - l_old.astype(jnp.float32)
        sq = sq + (d.reshape(n, -1) ** 2).sum(axis=1)
        dims += int(l_new.size // n)
    rms = jnp.sqrt(sq / max(dims, 1))
    triggered = rms > threshold

    def sel(l_new, l_old):
        t = triggered.reshape((n,) + (1,) * (l_new.ndim - 1))
        return jnp.where(t, l_new, l_old)
    wire = jax.tree.map(sel, x, last)
    return wire, wire, triggered


# ---------------------------------------------------------------------------
# fault injection: symmetric per-link dropout masks
# ---------------------------------------------------------------------------

def _edge_keep(key: Array, ids: Array, p: float) -> Array:
    """Per-edge-id Bernoulli(1−p) keep mask: a stateless PRF over the
    canonical undirected edge id, so the same link fails in every
    representation (and in both directions) given the same step key."""
    flat = ids.reshape(-1)

    def draw(eid):
        return jax.random.uniform(jax.random.fold_in(key, eid), ())

    u = jax.vmap(draw)(flat).reshape(ids.shape)
    return (u >= p).astype(jnp.float32)


def _edge_ids(a: Array, b: Array, n: int) -> Array:
    """Canonical undirected edge id: min·n + max (symmetric in (a, b))."""
    lo = jnp.minimum(a, b).astype(jnp.int32)
    hi = jnp.maximum(a, b).astype(jnp.int32)
    return lo * n + hi


def dropout_mask(key: Array, topo: Topology, p: float):
    """Representation-matched live-link mask for one step: dense
    ``(N, N)``, sparse ``(N, K_max)`` (slot-aligned), circulant
    ``(|±Δ|, N)`` (one row per ring shift, indexed by receiver).
    Self-loops (an agent's own value) never drop."""
    n = topo.n
    if topo.kind == "dense":
        idx = jnp.arange(n)
        ids = _edge_ids(idx[:, None], idx[None, :], n)
        keep = _edge_keep(key, ids, p)
        return jnp.where(jnp.eye(n, dtype=bool), 1.0, keep)
    if topo.kind == "sparse":
        rows = jnp.arange(n)[:, None]
        ids = _edge_ids(rows, topo.neighbor_idx, n)
        keep = _edge_keep(key, ids, p)
        return jnp.where(topo.neighbor_idx == rows, 1.0, keep)
    # circulant: one (N,) mask per signed shift; edge {j, (j+d) mod n}
    shifts = topology_repr._circulant_shifts(topo)
    if not shifts:
        return jnp.zeros((0, n), jnp.float32)
    j = jnp.arange(n)
    rows = [_edge_keep(key, _edge_ids(j, (j + d) % n, n), p)
            for d in shifts]
    return jnp.stack(rows)


def realized_messages(topo: Topology, edge_mask, triggered) -> Array:
    """Directed mixing messages that actually moved this step: live
    non-self edges whose SOURCE transmitted (all sources, without an
    event stage). A float32 scalar — per-step counts are far below the
    f32 integer range; accumulate sums host-side in float64."""
    n = topo.n
    trig = (jnp.ones((n,), jnp.float32) if triggered is None
            else triggered.astype(jnp.float32))
    if topo.kind == "dense":
        live = (topo.adj != 0).astype(jnp.float32)
        live = live * (1.0 - jnp.eye(n, dtype=jnp.float32))
        if edge_mask is not None:
            live = live * edge_mask
        # adj[j, i]: receiver j, source i — weight sources by trigger
        return (live * trig[None, :]).sum()
    if topo.kind == "sparse":
        rows = jnp.arange(n)[:, None]
        live = ((topo.neighbor_mask != 0)
                & (topo.neighbor_idx != rows)).astype(jnp.float32)
        if edge_mask is not None:
            live = live * edge_mask
        return (live * jnp.take(trig, topo.neighbor_idx)).sum()
    shifts = topology_repr._circulant_shifts(topo)
    total = jnp.zeros((), jnp.float32)
    for k, d in enumerate(shifts):
        src_trig = jnp.roll(trig, -d)             # trig[(j + d) mod n]
        live = (edge_mask[k] if edge_mask is not None
                else jnp.ones((n,), jnp.float32))
        total = total + (live * src_trig).sum()
    return total
