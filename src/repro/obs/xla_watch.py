"""XLA activity counters — compiles and host transfers (DESIGN.md §15).

The trace layer and the test suite share this one implementation.

Two kinds of hook:

* backend compiles — ``jax.monitoring`` fires a duration event
  (``/jax/core/compile/backend_compile_duration``) once per XLA backend
  compilation; listeners are cheap and composable.
* host transfers — JAX has NO monitoring event for d2h copies,
  so the counter wraps ``jax.device_get``, the repo's sanctioned drain
  path (DESIGN.md §7: metrics leave the device through chunked
  ``device_get`` calls, never through per-point ``float()`` coercion).
  The count is therefore "explicit drains", not raw DMA operations —
  exactly the quantity the single-transfer-per-drain contract gates.

Both are context managers yielding a list that grows by one per event,
so ``len(...)`` is the count and the list identity can be captured
before entering jitted code. ``Watch`` is the persistent variant the
JSONL trace writer uses to stamp per-span compile, cache-load and
transfer deltas; it counts compiles net of persistent-cache hits.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Iterator, List

import jax

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

# -- host-transfer hook ------------------------------------------------------
# One module-level wrapper around jax.device_get serves every active
# watcher: each registers a callback; install/uninstall is refcounted so
# nested watchers (a Watch inside count_host_transfers) compose.
_lock = threading.Lock()
_transfer_callbacks: List = []
_orig_device_get = None


def _counting_device_get(x):
    for cb in list(_transfer_callbacks):
        cb()
    return _orig_device_get(x)


def _add_transfer_callback(cb) -> None:
    global _orig_device_get
    with _lock:
        if not _transfer_callbacks:
            _orig_device_get = jax.device_get
            jax.device_get = _counting_device_get
        _transfer_callbacks.append(cb)


def _remove_transfer_callback(cb) -> None:
    global _orig_device_get
    with _lock:
        _transfer_callbacks.remove(cb)
        if not _transfer_callbacks:
            jax.device_get = _orig_device_get
            _orig_device_get = None


# -- public API --------------------------------------------------------------

@contextlib.contextmanager
def count_backend_compiles() -> Iterator[List[str]]:
    """Yields a list that grows by one per XLA backend compilation —
    the tests' steady-state gate (a warmed run must replay with ZERO
    compiles; scheduled topologies must match static runs)."""
    counts: List[str] = []

    def cb(event, *a, **kw):
        if event == _COMPILE_EVENT:
            counts.append(event)

    jax.monitoring.register_event_duration_secs_listener(cb)
    try:
        yield counts
    finally:
        jax.monitoring.unregister_event_duration_listener(cb)


@contextlib.contextmanager
def count_host_transfers() -> Iterator[List[str]]:
    """Yields a list that grows by one per ``jax.device_get`` call made
    while the context is active — the single-transfer-per-drain gate
    (DESIGN.md §7/§15). Counts explicit drains, not DMA ops: ``float()``
    coercion of a device array bypasses the hook, which is the point —
    code that syncs that way is the bug this counter exists to catch,
    and it shows up as a MISSING count against an expected one."""
    counts: List[str] = []

    def cb():
        counts.append("device_get")

    _add_transfer_callback(cb)
    try:
        yield counts
    finally:
        _remove_transfer_callback(cb)


class Watch:
    """Persistent compile, cache-load and transfer counter for
    span-structured tracing.

    ``start()`` installs the hooks; ``snapshot()`` returns monotonic
    ``(compiles, transfers, cache_loads)`` totals so a span records
    deltas around its body; ``stop()`` uninstalls. Used by
    ``repro.obs.trace.Trace`` — every span line carries the compiles,
    transfers and cache loads that happened inside it.

    On jax 0.9 the backend-compile event also fires for a request the
    persistent compilation cache serves, so ``compiles`` is compile
    requests minus cache hits (``/jax/compilation_cache/cache_hits``)
    and ``cache_loads`` the hits: a program loaded from the cache was
    traced and lowered, not compiled.
    """

    def __init__(self) -> None:
        self.requests = 0
        self.cache_loads = 0
        self.transfers = 0
        self._active = False

    @property
    def compiles(self) -> int:
        return self.requests - self.cache_loads

    def _on_compile(self, event, *a, **kw):
        if event == _COMPILE_EVENT:
            self.requests += 1

    def _on_event(self, event, *a, **kw):
        if event == _CACHE_HIT_EVENT:
            self.cache_loads += 1

    def _on_transfer(self):
        self.transfers += 1

    def start(self) -> "Watch":
        if self._active:
            return self
        jax.monitoring.register_event_duration_secs_listener(
            self._on_compile)
        jax.monitoring.register_event_listener(self._on_event)
        _add_transfer_callback(self._on_transfer)
        self._active = True
        return self

    def snapshot(self):
        return self.compiles, self.transfers, self.cache_loads

    def stop(self) -> None:
        if not self._active:
            return
        jax.monitoring.unregister_event_duration_listener(self._on_compile)
        jax.monitoring.unregister_event_listener(self._on_event)
        _remove_transfer_callback(self._on_transfer)
        self._active = False
