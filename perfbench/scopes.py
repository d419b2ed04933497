"""Device time by the program's layer and by the host phase that
dispatched it, and device idle time by host phase, from a profiler trace.

The program names its host phases with ``repro.obs.trace.Trace.span``,
which opens ``jax.profiler.TraceAnnotation("repro/<name>")`` on the
profiler's clock, and its layers on the device with ``jax.named_scope``
(``SCOPES``). Two readings need only what ``trace_reduce.load`` keeps
(device operations and host events, a ``trace_reduce.Reduced``):

* ``idle_under``: device idle time that host spans of one name cover;
* ``busy_begun_under``: device time of the operations that began while
  the host was inside such a span (the work that phase dispatched onto
  an idle device). The device and host planes share a clock only
  roughly: on a v5e a training scan's first op can carry a start time
  before the host span that dispatched it opens, so this reads a phase
  whose work starts well inside its span with idle device time around
  it.

Device time by named scope needs each operation's HLO ``op_name`` path,
which the profiler keeps in the ``tf_op`` stat of the operation's event
metadata. ``ProfileData`` does not expose metadata stats, so
``read_xspace`` parses the XSpace protobuf itself and ``load_scoped``
returns a ``ScopedReduced`` (``tests/data/scoped_chip.textproto`` is a
small trace in the chip's layout with op metadata).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from perfbench import trace_reduce
from perfbench.trace_reduce import (DEVICE_PLANE, OPS_LINE, clip, length,
                                    subtract, union)

# The program's ``jax.named_scope`` names (core.netes.netes_step,
# fleet_shard, envs.rollout.evaluate_best), and what an operation under
# none of them is called.
SCOPES = ("noise", "reward", "shaping", "channel", "mixing", "broadcast",
          "stats", "schedule", "eval")
UNSCOPED = "unscoped"
OP_PATH_STAT = "tf_op"
ScopedOp = Tuple[str, float, float]      # (scope, start_ns, end_ns)


# --------------------------------------------------------------------------
# host phases: readable from any Reduced
# --------------------------------------------------------------------------

def _cover(red: trace_reduce.Reduced, span: str):
    """The window's parts inside host spans named ``span`` (merged)."""
    lo, hi = red.window
    return union(clip(red.host_spans(span), lo, hi))


def idle_under(red: trace_reduce.Reduced, span: str) -> Optional[float]:
    """Device idle seconds in the window that host spans named ``span``
    cover, mean over chips; None when the window holds no such span (a
    program without it)."""
    if red.window is None:
        return None
    cover = _cover(red, span)
    if not cover:
        return None
    lo, hi = red.window
    idle = []
    for d in red.devices:
        gaps = subtract([(lo, hi)], red.busy_intervals(d, lo, hi))
        idle.append(length(gaps) - length(subtract(gaps, cover)))
    return sum(idle) / len(idle) * 1e-9


def busy_begun_under(red: trace_reduce.Reduced,
                     span: str) -> Optional[float]:
    """Device seconds in the window of the operations whose outermost
    enclosing operation began while the host was inside a span named
    ``span``, mean over chips; None when the window holds no such span."""
    if red.window is None:
        return None
    cover = _cover(red, span)
    if not cover:
        return None
    lo, hi = red.window
    tot = 0.0
    for ops in red.devices.values():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if e > lo and s < hi]
        own, roots = _nesting(inside)
        tot += sum(t for t, r in zip(own, roots)
                   if any(a <= r < b for a, b in cover))
    return tot / len(red.devices) * 1e-9


def _nesting(evs: Sequence[Tuple[str, float, float]]
             ) -> Tuple[List[float], List[float]]:
    """For each event, its own time (its length less what events nested
    inside it on the same line cover, as ``trace_reduce.self_times`` per
    name) and the start of the outermost event it is nested in (its own
    start when it is not nested)."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i][1], -evs[i][2]))
    own = [0.0] * len(evs)
    root = [0.0] * len(evs)
    stack: List[int] = []
    for i in order:
        _, s, e = evs[i]
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, evs[stack[-1]][2]) - s
        own[i] += e - s
        root[i] = root[stack[0]] if stack else s
        stack.append(i)
    return own, root


# --------------------------------------------------------------------------
# named scopes: need the op metadata of the XSpace
# --------------------------------------------------------------------------

def scope_of(path: Optional[str]) -> Optional[str]:
    """The innermost of ``SCOPES`` among the components of an HLO
    ``op_name`` path (the profiler writes it with a trailing ``:``);
    ``UNSCOPED`` for a path with none, None for no path at all."""
    if path is None:
        return None
    for part in reversed(path.rstrip(":").split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


@dataclasses.dataclass
class ScopedReduced(trace_reduce.Reduced):
    # device id -> every operation with its named scope
    scoped: Dict[int, List[ScopedOp]] = dataclasses.field(
        default_factory=dict)

    def scope_self_s(self, names: Sequence[str]) -> Optional[float]:
        """Device self time in the window of the operations whose scope
        is one of ``names``, seconds per chip (mean over the chips that
        ran operations); None when no operation in the trace carries any
        of ``names`` (a program without the scopes)."""
        if not any(sc in names for ops in self.scoped.values()
                   for sc, _, _ in ops):
            return None
        lo, hi = self.window
        tot = 0.0
        for ops in self.scoped.values():
            inside = [(sc, max(s, lo), min(e, hi)) for sc, s, e in ops
                      if e > lo and s < hi]
            own, _ = _nesting(inside)
            tot += sum(t for (sc, _, _), t in zip(inside, own)
                       if sc in names)
        return tot / len(self.scoped) * 1e-9


def _xspace_class():
    """A protobuf message class for ``XSpace``, built from a descriptor
    with the planes' fields of tsl/profiler/protobuf/xplane.proto (the
    same names and numbers; XSpace's own error and host lists are
    skipped)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    i64, u64, f64 = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE
    text, raw, sub = F.TYPE_STRING, F.TYPE_BYTES, F.TYPE_MESSAGE
    pkg = "perfbench_xplane"
    fdp = descriptor_pb2.FileDescriptorProto(
        name=f"{pkg}.proto", package=pkg, syntax="proto3")

    def message(parent, name, fields):
        m = parent.add(name=name)
        for fname, number, ftype, label, tname in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=label)
            if tname:
                f.type_name = f".{pkg}.{tname}"
        return m

    types = fdp.message_type
    message(types, "XStat", [
        ("metadata_id", 1, i64, one, None),
        ("double_value", 2, f64, one, None),
        ("uint64_value", 3, u64, one, None),
        ("int64_value", 4, i64, one, None),
        ("str_value", 5, text, one, None),
        ("bytes_value", 6, raw, one, None),
        ("ref_value", 7, u64, one, None)])
    message(types, "XEvent", [
        ("metadata_id", 1, i64, one, None),
        ("offset_ps", 2, i64, one, None),
        ("num_occurrences", 5, i64, one, None),
        ("duration_ps", 3, i64, one, None),
        ("stats", 4, sub, many, "XStat")])
    message(types, "XLine", [
        ("id", 1, i64, one, None),
        ("display_id", 10, i64, one, None),
        ("name", 2, text, one, None),
        ("display_name", 11, text, one, None),
        ("timestamp_ns", 3, i64, one, None),
        ("duration_ps", 9, i64, one, None),
        ("events", 4, sub, many, "XEvent")])
    message(types, "XEventMetadata", [
        ("id", 1, i64, one, None),
        ("name", 2, text, one, None),
        ("display_name", 4, text, one, None),
        ("metadata", 3, raw, one, None),
        ("stats", 5, sub, many, "XStat"),
        ("child_id", 6, i64, many, None)])
    message(types, "XStatMetadata", [
        ("id", 1, i64, one, None),
        ("name", 2, text, one, None),
        ("description", 3, text, one, None)])
    plane = message(types, "XPlane", [
        ("id", 1, i64, one, None),
        ("name", 2, text, one, None),
        ("lines", 3, sub, many, "XLine"),
        ("event_metadata", 4, sub, many, "XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, sub, many, "XPlane.StatMetadataEntry"),
        ("stats", 6, sub, many, "XStat")])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        e = message(plane.nested_type, entry, [
            ("key", 1, i64, one, None), ("value", 2, sub, one, value)])
        e.options.map_entry = True
    message(types, "XSpace", [("planes", 1, sub, many, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{pkg}.XSpace"))


def read_xspace(data: bytes = b"", text: Optional[str] = None):
    """An XSpace from its serialized bytes, or from its text form."""
    space = _xspace_class()()
    if text is not None:
        from google.protobuf import text_format
        text_format.Parse(text, space)
    else:
        space.ParseFromString(data)
    return space


def scoped_ops(space) -> Dict[int, List[ScopedOp]]:
    """Per device, every ``XLA Ops`` event as (scope, start_ns, end_ns),
    keyed per event: the scope comes from that event's own metadata
    (two programs may both hold a ``fusion.1``). A fusion's metadata is
    its root instruction's. An event with no ``op_name`` path (a loop,
    a copy the compiler added) takes the scope that every operation
    nested inside it shares, else ``UNSCOPED``."""
    out: Dict[int, List[ScopedOp]] = {}
    for plane in space.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        path_of = {}
        for key, meta in plane.event_metadata.items():
            path_of[key] = None
            for st in meta.stats:
                if names.get(st.metadata_id) == OP_PATH_STAT:
                    path_of[key] = (st.str_value if st.str_value else
                                    names.get(st.ref_value, ""))
        ops = out.setdefault(int(m.group(1)), [])
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                start = line.timestamp_ns + e.offset_ps / 1000
                ops.append((scope_of(path_of.get(e.metadata_id)), start,
                            start + e.duration_ps / 1000))
    return {d: _inherit_scopes(ops) for d, ops in out.items() if ops}


def _inherit_scopes(ops: Sequence[Tuple[Optional[str], float, float]]
                    ) -> List[ScopedOp]:
    """Name each pathless event by the one scope of the events nested in
    it (``UNSCOPED`` when they differ or there are none)."""
    evs = sorted(ops, key=lambda x: (x[1], -x[2]))
    inner: List[set] = [set() for _ in evs]
    stack: List[int] = []
    for i, (_, s, _e) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        for j in stack:
            inner[j].add(evs[i][0])
        stack.append(i)
    out = []
    for i, (scope, s, e) in enumerate(evs):
        if scope is None:
            named = inner[i] - {None}
            scope = named.pop() if len(named) == 1 else UNSCOPED
        out.append((scope, s, e))
    return out


def load_scoped(path: str) -> ScopedReduced:
    """``trace_reduce.load``, plus each device operation's named scope."""
    red = trace_reduce.load(path)
    with open(path, "rb") as fh:
        scoped = scoped_ops(read_xspace(fh.read()))
    return ScopedReduced(devices=red.devices, host=red.host, scoped=scoped)


# --------------------------------------------------------------------------
# per-layer readers' arithmetic (ctx: harness.Context of a traced run)
# --------------------------------------------------------------------------

def traced_chunks(ctx) -> Optional[int]:
    """Chunks in the traced window (each ends at an evaluation point),
    or None when the run holds no traced window."""
    red, stamps = ctx.trace, ctx.window.traced_stamps
    if red is None or red.window is None or not stamps:
        return None
    first, last = stamps
    return last - first


def per_chunk_ms(ctx, reading, span: str) -> Optional[float]:
    """``reading(red, span)`` seconds in ms per traced chunk (one
    evaluation point each)."""
    chunks = traced_chunks(ctx)
    if chunks is None:
        return None
    s = reading(ctx.trace, span)
    return None if s is None else 1e3 * s / chunks
