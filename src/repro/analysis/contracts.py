"""Layer 2: jaxpr contracts over registered entry points (DESIGN.md §14).

Each registered entry point (``registry.EntryPoint``) is abstractly
traced with ``jax.make_jaxpr`` — nothing executes — and the resulting
jaxpr is walked recursively (scan bodies, while bodies, cond/switch
branches, pjit/closed_call sub-jaxprs) checking structural invariants
the repo's shipped bugs motivated:

* ``no-host-callback`` — no ``*_callback``/``outside_call`` primitives
  anywhere: a host callback inside a per-step program serializes the
  fleet on the Python lock.
* ``strong-scan-carry`` — every ``scan``/``while`` carry aval is
  strong-typed. A weak carry is the PR 3 recompile class observed at
  the jaxpr level (the AST rule catches the literal at the source
  level; this catches whatever survives to the trace).
* ``branch-collective-parity`` — all branches of every ``cond``/
  ``switch`` issue the SAME ordered sequence of collective primitives
  (names + operand/result shapes; permutation tables may differ). With
  a replicated branch index this is exactly the deadlock-freedom
  contract the PR 3 rotating chains and PR 7 comm plans rely on: a
  branch-divergent collective deadlocks the mesh, it does not fail.
* ``fma-seam-barrier`` — no rank≥2 ``mul`` result feeds an ``add``/
  ``sub`` directly: on shard seams every product must be rounded
  (``optimization_barrier``) before accumulation, or XLA's per-program
  FMA contraction breaks bitwise mesh-size invariance (PR 7). Applied
  only to seam leaf functions — whole steps contain elementwise
  polynomial chains (erfinv in jax.random) where contraction is shape-
  uniform and harmless.
* ``min_barriers`` ratchet — the traced program keeps at least N
  ``optimization_barrier`` equations. Dropping a barrier from a step
  fails here, in tier-1, instead of as last-ulp drift on an 8-device
  mesh.
"""
from __future__ import annotations

from typing import Iterable, Iterator, List, Optional, Sequence, Tuple

import jax
from jax.extend import core as jax_core

from .findings import Finding
from .registry import EntryPoint, iter_entry_points

_CALLBACK_PRIMS = ("callback", "outside_call", "infeed", "outfeed")
_COLLECTIVE_PRIMS = {"psum", "pmax", "pmin", "ppermute", "pshuffle",
                     "all_gather", "all_to_all", "reduce_scatter",
                     "psum_scatter", "pgather"}


def _subjaxprs(eqn) -> Iterator[jax_core.Jaxpr]:
    for val in eqn.params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, jax_core.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jax_core.Jaxpr):
                yield v


def iter_jaxprs(jaxpr: jax_core.Jaxpr) -> Iterator[jax_core.Jaxpr]:
    """The jaxpr and every sub-jaxpr reachable through eqn params."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for sub in _subjaxprs(eqn):
            yield from iter_jaxprs(sub)


def _iter_eqns(jaxpr: jax_core.Jaxpr):
    for j in iter_jaxprs(jaxpr):
        yield from j.eqns


# --------------------------------------------------------------------------
# individual contracts — each returns a list of violation messages
# --------------------------------------------------------------------------

def check_no_host_callback(jaxpr: jax_core.Jaxpr) -> List[str]:
    out = []
    for eqn in _iter_eqns(jaxpr):
        name = eqn.primitive.name
        if any(tag in name for tag in _CALLBACK_PRIMS):
            out.append(f"host callback primitive {name!r} in the "
                       f"compiled program")
    return out


def _carry_avals(eqn) -> Sequence:
    if eqn.primitive.name == "scan":
        inner = eqn.params["jaxpr"]
        nc = eqn.params["num_consts"]
        return inner.in_avals[nc:nc + eqn.params["num_carry"]]
    if eqn.primitive.name == "while":
        inner = eqn.params["body_jaxpr"]
        return inner.in_avals[eqn.params["body_nconsts"]:]
    return ()


def check_strong_scan_carry(jaxpr: jax_core.Jaxpr) -> List[str]:
    out = []
    for eqn in _iter_eqns(jaxpr):
        for i, aval in enumerate(_carry_avals(eqn)):
            # only inexact carries: weak int32 counters are what jax's
            # own fori_loop lowering builds — unavoidable and benign.
            # The PR 3 recompile class is host floats (0.0) in the carry.
            if getattr(aval, "weak_type", False) \
                    and getattr(aval, "dtype", None) is not None \
                    and aval.dtype.kind in ("f", "c"):
                out.append(
                    f"{eqn.primitive.name} carry slot {i} is weak-typed "
                    f"({aval.str_short()}): a host-built initializer will "
                    f"recompile the steady state")
    return out


def _collective_signature(jaxpr: jax_core.Jaxpr) -> List[Tuple]:
    """Ordered (name, in-shapes, out-shapes) of every collective in the
    (sub)jaxpr. Permutation tables / axis names are excluded — branches
    may rotate the schedule, but the wire structure must match."""
    sig = []
    for eqn in _iter_eqns(jaxpr):
        if eqn.primitive.name in _COLLECTIVE_PRIMS:
            sig.append((
                eqn.primitive.name,
                tuple(str(v.aval) for v in eqn.invars),
                tuple(str(v.aval) for v in eqn.outvars),
            ))
    return sig


def check_branch_collective_parity(jaxpr: jax_core.Jaxpr) -> List[str]:
    out = []
    for eqn in _iter_eqns(jaxpr):
        if eqn.primitive.name != "cond" or "branches" not in eqn.params:
            continue
        sigs = [_collective_signature(b.jaxpr)
                for b in eqn.params["branches"]]
        ref = sigs[0]
        for i, sig in enumerate(sigs[1:], start=1):
            if sig != ref:
                out.append(
                    f"cond/switch branches 0 and {i} issue different "
                    f"collective sequences ({ref} vs {sig}): with a "
                    f"replicated branch index this deadlocks the mesh")
    return out


def check_fma_seam_barrier(jaxpr: jax_core.Jaxpr) -> List[str]:
    out = []
    for j in iter_jaxprs(jaxpr):
        producer = {}
        for eqn in j.eqns:
            for v in eqn.outvars:
                if isinstance(v, jax_core.Var):
                    producer[v] = eqn.primitive.name
        for eqn in j.eqns:
            if eqn.primitive.name not in ("add", "sub"):
                continue
            if getattr(eqn.outvars[0].aval, "ndim", 0) < 2:
                continue
            for v in eqn.invars:
                if isinstance(v, jax_core.Var) \
                        and producer.get(v) == "mul":
                    out.append(
                        f"rank-{eqn.outvars[0].aval.ndim} mul feeds "
                        f"{eqn.primitive.name} without an "
                        f"optimization_barrier: XLA's FMA contraction "
                        f"breaks bitwise mesh-size parity on this seam")
    return out


def count_barriers(jaxpr: jax_core.Jaxpr) -> int:
    return sum(1 for eqn in _iter_eqns(jaxpr)
               if eqn.primitive.name == "optimization_barrier")


_CONTRACT_FNS = {
    "no-host-callback": check_no_host_callback,
    "strong-scan-carry": check_strong_scan_carry,
    "branch-collective-parity": check_branch_collective_parity,
    "fma-seam-barrier": check_fma_seam_barrier,
}

CONTRACT_IDS = tuple(_CONTRACT_FNS) + ("barrier-ratchet",)


# --------------------------------------------------------------------------
# entry-point driver
# --------------------------------------------------------------------------

def check_entry_point(ep: EntryPoint) -> List[Finding]:
    """Trace one entry point and run its contracts. Returns findings
    (empty = clean). Entry points needing more devices than visible are
    skipped silently — the CI static-analysis job and the tier-1
    subprocess leg run under a forced 8-device host platform."""
    if len(jax.devices()) < ep.min_devices:
        return []
    path = f"<{ep.name}>"
    try:
        fn, args, kwargs = ep.build()
        closed = jax.make_jaxpr(fn)(*args, **kwargs)
    except Exception as e:  # a registered entry point must always trace
        return [Finding(
            rule="entry-point-trace", path=path, line=0,
            message=f"entry point failed to trace: {type(e).__name__}: {e}",
            hint="the registry contract is that build() returns a "
                 "traceable (fn, args, kwargs); fix the hook")]
    out: List[Finding] = []
    for name in ep.contracts:
        for msg in _CONTRACT_FNS[name](closed.jaxpr):
            out.append(Finding(rule=name, path=path, line=0, message=msg,
                               hint=_HINTS.get(name, "")))
    if ep.min_barriers:
        got = count_barriers(closed.jaxpr)
        if got < ep.min_barriers:
            out.append(Finding(
                rule="barrier-ratchet", path=path, line=0,
                message=f"{got} optimization_barrier eqns in the traced "
                        f"program, registered minimum is "
                        f"{ep.min_barriers}: a seam pin was dropped",
                hint="restore the barrier (see DESIGN.md §13), or if the "
                     "seam genuinely moved, update min_barriers in the "
                     "module's analysis_entry_points() with a comment"))
    return out


_HINTS = {
    "no-host-callback": "keep per-step code device-only; drain on the "
                        "host outside the scan",
    "strong-scan-carry": "build carry initializers with explicit dtypes "
                         "(jnp.zeros((), jnp.float32))",
    "branch-collective-parity": "pad every branch to the same collective "
                                "schedule (inert ppermute/psum) or hoist "
                                "the collective out of the cond",
    "fma-seam-barrier": "wrap the product: "
                        "jax.lax.optimization_barrier(w * x) + acc",
}


def run_contracts(names: Optional[Iterable[str]] = None) -> List[Finding]:
    """Check every registered entry point (or the named subset)."""
    eps = iter_entry_points()
    if names is not None:
        wanted = set(names)
        unknown = wanted - {ep.name for ep in eps}
        if unknown:
            raise ValueError(f"unknown entry points: {sorted(unknown)}")
        eps = [ep for ep in eps if ep.name in wanted]
    out: List[Finding] = []
    for ep in eps:
        out.extend(check_entry_point(ep))
    return out
