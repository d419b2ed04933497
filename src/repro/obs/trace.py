"""Structured JSONL run traces (DESIGN.md §15).

One trace = one append-only JSONL file. Line 1 is a schema-versioned
``meta`` record; every following line is a ``span`` or ``event`` record:

    {"kind": "meta", "schema": "repro.trace/v1", "name": ..., env...}
    {"kind": "span", "name": "chunk", "t0": ..., "dur_s": ...,
     "compiles": 0, "transfers": 1, "cache_loads": 0, "attrs": {...}}
    {"kind": "event", "name": "eval", "t": ..., "attrs": {...}}

Spans are wall-time intervals (build / chunk / step / eval / drain /
checkpoint in the training loop, prefill / decode in the server)
stamped with the XLA compiles, host transfers and persistent-cache
loads that occurred INSIDE the span (via ``xla_watch.Watch``) — so
"which chunk recompiled" and "which drain double-transferred" are
greppable facts, not printf archaeology. ``compiles`` counts backend
compilations the persistent cache did not serve; ``cache_loads`` (an
optional key of ``repro.trace/v1``, absent from older traces) the
programs it did. Spans may nest; each line is self-contained (``depth``
records nesting). The writer never touches device values itself: probes
drain through ``Probes.drain``, the trace only records host-side timing.

Every span also opens ``jax.profiler.TraceAnnotation("repro/<name>")``,
with or without a file (``Trace(None)`` included), so a profiler trace
holds the same spans on its host plane, on the device planes' clock.
With no profiler running that is one TraceMe check per span, well under
a microsecond; the training loop opens a few spans per chunk.

``validate_trace`` is the schema gate CI runs (``python -m repro.obs
validate <file>``); ``summarize`` renders a per-span table.
"""
from __future__ import annotations

import contextlib
import json
import pathlib
import time
from typing import Any, Dict, Iterator, List, Optional

import jax

SCHEMA = "repro.trace/v1"

_META_REQUIRED = ("kind", "schema", "name")
_SPAN_REQUIRED = ("kind", "name", "t0", "dur_s", "depth",
                  "compiles", "transfers")
_EVENT_REQUIRED = ("kind", "name", "t")


class Trace:
    """Append-only JSONL trace writer. Use as a context manager::

        with Trace(path, name="fleet-pulse") as tr:
            with tr.span("warmup"):
                ...
            tr.event("eval", score=1.2)

    Lines are flushed per record (a crashed run keeps its prefix; every
    prefix is a valid trace). ``Trace(None)`` writes no file, so call
    sites thread ``trace`` unconditionally without ``if`` forests; its
    spans still reach the profiler as ``repro/<name>`` annotations.
    """

    def __init__(self, path: Optional[str | pathlib.Path],
                 name: str = "run", **meta: Any) -> None:
        self.path = pathlib.Path(path) if path is not None else None
        self._fh = None
        self._depth = 0
        self._watch = None
        if self.path is None:
            return
        from . import xla_watch
        self.path.parent.mkdir(parents=True, exist_ok=True)
        self._fh = self.path.open("w")
        self._watch = xla_watch.Watch().start()
        self._write({"kind": "meta", "schema": SCHEMA, "name": name,
                     "t0": time.time(), "jax": jax.__version__,
                     "backend": jax.default_backend(),
                     "devices": jax.device_count(), **meta})

    # -- lifecycle --------------------------------------------------------
    @property
    def active(self) -> bool:
        return self._fh is not None

    def close(self) -> None:
        if self._fh is not None:
            self._watch.stop()
            self._fh.close()
            self._fh = None

    def __enter__(self) -> "Trace":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- records ----------------------------------------------------------
    def _write(self, rec: Dict[str, Any]) -> None:
        self._fh.write(json.dumps(rec, default=float) + "\n")
        self._fh.flush()

    @contextlib.contextmanager
    def span(self, name: str, **attrs: Any) -> Iterator[Dict[str, Any]]:
        """Wall-time span stamped with the XLA compiles, host transfers
        and cache loads that happened inside it, and the profiler
        annotation ``repro/<name>`` around it. Yields the span's
        attribute dict: what the body adds to it is written with the
        span."""
        with jax.profiler.TraceAnnotation(f"repro/{name}"):
            if self._fh is None:
                yield attrs
                return
            c0, x0, l0 = self._watch.snapshot()
            t0 = time.time()
            self._depth += 1
            try:
                yield attrs
            finally:
                self._depth -= 1
                c1, x1, l1 = self._watch.snapshot()
                rec = {"kind": "span", "name": name, "t0": t0,
                       "dur_s": time.time() - t0, "depth": self._depth,
                       "compiles": c1 - c0, "transfers": x1 - x0,
                       "cache_loads": l1 - l0}
                if attrs:
                    rec["attrs"] = attrs
                self._write(rec)

    def event(self, name: str, **attrs: Any) -> None:
        if self._fh is None:
            return
        rec: Dict[str, Any] = {"kind": "event", "name": name,
                               "t": time.time()}
        if attrs:
            rec["attrs"] = attrs
        self._write(rec)


# ---------------------------------------------------------------------------
# readers — schema validation + summary (the ``python -m repro.obs`` CLI)
# ---------------------------------------------------------------------------

def read_trace(path: str | pathlib.Path) -> List[Dict[str, Any]]:
    recs = []
    with open(path) as fh:
        for i, line in enumerate(fh):
            line = line.strip()
            if not line:
                continue
            try:
                recs.append(json.loads(line))
            except json.JSONDecodeError as e:
                raise ValueError(f"{path}:{i + 1}: not JSON: {e}") from e
    return recs


def validate_trace(path: str | pathlib.Path) -> List[str]:
    """Schema gate: returns a list of violations (empty = valid)."""
    errors: List[str] = []
    try:
        recs = read_trace(path)
    except ValueError as e:
        return [str(e)]
    if not recs:
        return [f"{path}: empty trace"]
    meta = recs[0]
    if meta.get("kind") != "meta":
        errors.append(f"line 1: first record must be kind=meta, "
                      f"got {meta.get('kind')!r}")
    elif meta.get("schema") != SCHEMA:
        errors.append(f"line 1: schema {meta.get('schema')!r} != {SCHEMA!r}")
    for missing in (k for k in _META_REQUIRED if k not in meta):
        errors.append(f"line 1: meta missing key {missing!r}")
    for i, rec in enumerate(recs[1:], start=2):
        kind = rec.get("kind")
        if kind == "span":
            req = _SPAN_REQUIRED
        elif kind == "event":
            req = _EVENT_REQUIRED
        elif kind == "meta":
            errors.append(f"line {i}: duplicate meta record")
            continue
        else:
            errors.append(f"line {i}: unknown kind {kind!r}")
            continue
        for k in req:
            if k not in rec:
                errors.append(f"line {i}: {kind} missing key {k!r}")
        for k in ("t0", "dur_s", "t"):
            if k in rec and not isinstance(rec[k], (int, float)):
                errors.append(f"line {i}: {k} must be a number")
        for k in ("compiles", "transfers", "depth", "cache_loads"):
            if k in rec and (not isinstance(rec[k], int) or rec[k] < 0):
                errors.append(f"line {i}: {k} must be a non-negative int")
    return errors


def summarize(path: str | pathlib.Path) -> str:
    """Per-span-name aggregate: count, total wall, compiles, transfers,
    cache loads."""
    recs = read_trace(path)
    meta = recs[0] if recs and recs[0].get("kind") == "meta" else {}
    spans: Dict[str, Dict[str, float]] = {}
    events = 0
    for rec in recs[1:]:
        if rec.get("kind") == "event":
            events += 1
            continue
        if rec.get("kind") != "span":
            continue
        agg = spans.setdefault(rec["name"], {"n": 0, "wall_s": 0.0,
                                             "compiles": 0, "transfers": 0,
                                             "cache_loads": 0})
        agg["n"] += 1
        agg["wall_s"] += rec.get("dur_s", 0.0)
        for k in ("compiles", "transfers", "cache_loads"):
            agg[k] += rec.get(k, 0)
    lines = [f"trace {meta.get('name', '?')} — schema "
             f"{meta.get('schema', '?')}, jax {meta.get('jax', '?')}, "
             f"{meta.get('devices', '?')} device(s)"]
    lines.append(f"{'span':<16}{'n':>6}{'wall_s':>10}{'compiles':>10}"
                 f"{'transfers':>11}{'cache_loads':>13}")
    for name in sorted(spans):
        a = spans[name]
        lines.append(f"{name:<16}{a['n']:>6}{a['wall_s']:>10.3f}"
                     f"{a['compiles']:>10}{a['transfers']:>11}"
                     f"{a['cache_loads']:>13}")
    lines.append(f"{events} event(s), {len(recs) - 1} record(s)")
    return "\n".join(lines)
