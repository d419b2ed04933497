"""The collectives on a chip's ``XLA Ops`` line, told by opcode, for the
readers ``metrics/collective_ms.py`` and ``metrics/collective_exposed_ms.py``.

On a v5e an operation's event is named by its HLO text, operands
included: ``%fusion.9 = f32[935,4481]{...} fusion(s8[223,4481]{...}
%collective-permute-done.3, ...), kind=kLoop, ...``. Matching a
collective's name anywhere in that text would count the fusion that
consumes the halo as a collective, so an operation is classed by its
opcode alone: the word before the first ``(`` after ``=``, or, for a
short name (``all-gather.3``, hand-made and CPU traces), the name's
leading word. A generic ``async-start`` wrapper is not classed as a
collective; the sharded engine compiled for a v5e 2x2 has none.
"""
from __future__ import annotations

import re
from typing import List, Optional, Tuple

from perfbench import scopes
from perfbench.trace_reduce import clip, union

COLLECTIVE_OPCODES = frozenset({
    "all-gather", "all-gather-start", "all-gather-done",
    "all-reduce", "all-reduce-start", "all-reduce-done",
    "collective-permute", "collective-permute-start",
    "collective-permute-done", "reduce-scatter", "all-to-all",
    "send", "send-done", "recv", "recv-done"})
# ops that only hold others: the chunk's scan is one ``while`` op
# spanning every op of its body, collectives included
HOLDER_OPCODES = frozenset({"while", "conditional", "call"})

_HLO = re.compile(r"^%?\S+ = .*? ([a-z][a-z0-9-]*)\(")
_LEADING = re.compile(r"^%?([a-z][a-z-]*[a-z])")


def opcode(name: str) -> Optional[str]:
    m = _HLO.match(name) or _LEADING.match(name)
    return m.group(1) if m else None


def is_collective(name: str) -> bool:
    return opcode(name) in COLLECTIVE_OPCODES


def holds_others(name: str) -> bool:
    return opcode(name) in HOLDER_OPCODES


Intervals = List[Tuple[float, float]]


def per_chip(ctx) -> Optional[Tuple[List[Tuple[Intervals, Intervals]],
                                    int]]:
    """For each chip, the traced window's collective time and its work
    (every other op but the holders), each as merged intervals; and the
    traced iterations. None where the run has no traced window or its
    window holds no collective (a cell on one chip)."""
    chunks = scopes.traced_chunks(ctx)
    if chunks is None:
        return None
    lo, hi = ctx.trace.window
    chips = []
    for ops in ctx.trace.devices.values():
        coll = [(s, e) for n, s, e in ops if is_collective(n)]
        work = [(s, e) for n, s, e in ops
                if not is_collective(n) and not holds_others(n)]
        chips.append((union(clip(coll, lo, hi)), union(clip(work, lo, hi))))
    if not any(coll for coll, _ in chips):
        return None
    return chips, chunks * ctx.window.iters_per_chunk


def ms_per_iter(ns: List[float], iters: int) -> float:
    """The mean over chips of their nanoseconds, in ms an iteration."""
    return 1e-6 * sum(ns) / len(ns) / iters

