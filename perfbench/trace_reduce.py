"""From a profiler trace to the benchmark's device numbers.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``load`` reads it with ``jax.profiler.ProfileData`` and keeps two kinds of
events, each as (name, start_ns, end_ns) on the profiler's one clock:

* device operations: every event of the ``XLA Ops`` line of each device
  plane (``/device:TPU:<i>``);
* host events: every event of the host plane (``/host:CPU``), among them
  the harness's own annotations (``perfbench_window``, ``perfbench_probe:``).

All reductions work on plain lists of events, so they are checked on
hand-made events and on a small XSpace in the TPU layout
(``tests/data/two_chips.textproto``, read by ``from_profile``).
"""
from __future__ import annotations

import dataclasses
import glob
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]        # (name, start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "perfbench_window"
COLLECTIVE = re.compile(
    r"(all-gather|all-reduce|collective-permute|reduce-scatter|all-to-all"
    r"|send|recv)", re.IGNORECASE)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    return found[-1]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def subtract(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Parts of the merged intervals ``a`` that the merged ``b`` leaves
    uncovered."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Per-name time of events not covered by events nested inside them
    on the same line (a loop's own time excludes its body's operations)."""
    evs = sorted(events, key=lambda x: (x[1], -x[2]))
    out: Dict[str, float] = {}
    stack: List[List] = []           # [name, start, end, child_ns]

    def close(item):
        name, start, end, child = item
        out[name] = out.get(name, 0.0) + (end - start) - child

    for name, s, e in evs:
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return out


@dataclasses.dataclass
class Reduced:
    devices: Dict[int, List[Event]]      # device id -> operations
    host: List[Event]

    # -- windows ------------------------------------------------------------
    def host_spans(self, name: str) -> List[Tuple[float, float]]:
        return sorted((s, e) for n, s, e in self.host if n == name)

    @property
    def window(self) -> Optional[Tuple[float, float]]:
        spans = self.host_spans(WINDOW)
        return spans[0] if spans else None

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * 1e-9

    # -- busy and idle --------------------------------------------------------
    def busy_intervals(self, dev: int, lo: float, hi: float):
        return union(clip([(s, e) for _, s, e in self.devices[dev]], lo, hi))

    def device_busy(self, span: Tuple[float, float]) -> float:
        """Seconds in ``span`` in which some operation ran, averaged over
        the devices that ran any."""
        lo, hi = span
        busy = [length(self.busy_intervals(d, lo, hi)) for d in self.devices]
        busy = [b for b in busy if b > 0]
        return (sum(busy) / len(busy)) * 1e-9 if busy else 0.0

    @property
    def busy_s(self) -> float:
        """Busy seconds in the traced window, averaged over all devices."""
        lo, hi = self.window
        busy = [length(self.busy_intervals(d, lo, hi)) for d in self.devices]
        return sum(busy) / len(busy) * 1e-9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    # -- collectives ---------------------------------------------------------
    def collective_exposed_s(self) -> Optional[float]:
        """Seconds per device in the window in which a collective ran and
        no other operation did, averaged over devices; None when the
        trace holds no collective."""
        lo, hi = self.window
        per_dev, seen = [], False
        for ops in self.devices.values():
            coll = union(clip([(s, e) for n, s, e in ops
                               if COLLECTIVE.search(n)], lo, hi))
            other = union(clip([(s, e) for n, s, e in ops
                                if not COLLECTIVE.search(n)], lo, hi))
            seen = seen or bool(coll)
            per_dev.append(length(subtract(coll, other)))
        if not seen:
            return None
        return sum(per_dev) / len(per_dev) * 1e-9

    # -- breakdown -------------------------------------------------------------
    def top_ops(self, k: int = 10) -> List[List]:
        """Device operations by self time in the window, seconds per
        device."""
        lo, hi = self.window
        tot: Dict[str, float] = {}
        for ops in self.devices.values():
            inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                      if e > lo and s < hi]
            for n, t in self_times(inside).items():
                tot[n] = tot.get(n, 0.0) + t
        nd = len(self.devices)
        best = sorted(tot.items(), key=lambda x: -x[1])[:k]
        return [[n, t / nd * 1e-9] for n, t in best]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The longest stretches of the window in which the first device
        ran nothing, each named by the host event that covers most of
        it (the innermost when several tie)."""
        lo, hi = self.window
        dev = min(self.devices)
        gaps = subtract([(lo, hi)], self.busy_intervals(dev, lo, hi))
        gaps = sorted(gaps, key=lambda g: -(g[1] - g[0]))[:k]
        out = []
        for s, e in gaps:
            best, best_cover, best_len = "host: no event", 0.0, float("inf")
            for n, hs, he in self.host:
                if n == WINDOW:
                    continue
                cover = min(e, he) - max(s, hs)
                if cover <= 0:
                    continue
                if (cover > best_cover
                        or (cover == best_cover and he - hs < best_len)):
                    best, best_cover, best_len = n, cover, he - hs
            out.append([best, (e - s) * 1e-9])
        return out

    def breakdown(self) -> Dict[str, List[List]]:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def load(path: str) -> Reduced:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def from_profile(data) -> Reduced:
    """Keep the device operations and host events of a ``ProfileData``."""
    devices: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events)
    devices = {d: ops for d, ops in devices.items() if ops}
    return Reduced(devices=devices, host=host)
