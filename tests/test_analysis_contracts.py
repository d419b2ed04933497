"""Layer-2 (jaxpr) contracts: every registered entry point passes its
contracts in-process, each contract detects a synthetic violation built
to trip exactly it, and the full CLI gate passes on a forced 8-device
host platform (tier-1)."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.analysis.contracts import (
    check_branch_collective_parity, check_entry_point,
    check_fma_seam_barrier, check_no_host_callback,
    check_strong_scan_carry, count_barriers, run_contracts)
from repro.analysis.registry import (
    DEFAULT_CONTRACTS, EntryPoint, iter_entry_points)

REPO = Path(__file__).resolve().parent.parent


def _jaxpr(fn, *args):
    return jax.make_jaxpr(fn)(*args).jaxpr


# -- the real registry --------------------------------------------------


def test_registry_collects_every_hooked_module():
    names = {ep.name for ep in iter_entry_points()}
    assert {"netes.run", "netes.run_scheduled", "netes_dist.replica_step",
            "netes_dist.consensus_step", "fleet_shard.solo_step",
            "fleet_shard.slot_contract", "fleet_shard.dense_contract",
            "kernels.fused_broadcast_select"} <= names


def test_registered_entry_points_pass_all_contracts():
    """The acceptance gate, in-process: every entry point traceable on
    this device count yields zero findings."""
    findings = run_contracts()
    assert findings == [], [f.render() for f in findings]


# -- synthetic violations, one per contract -----------------------------


def test_strong_scan_carry_detects_weak_float_carry():
    def bad(xs):
        return jax.lax.scan(lambda c, x: (c + x, None), 0.0, xs)

    msgs = check_strong_scan_carry(_jaxpr(bad, jnp.ones(3)))
    assert msgs and "weak-typed" in msgs[0]

    def good(xs):
        return jax.lax.scan(lambda c, x: (c + x, None),
                            jnp.zeros((), jnp.float32), xs)

    assert check_strong_scan_carry(_jaxpr(good, jnp.ones(3))) == []


def test_strong_scan_carry_ignores_fori_counter():
    """jax's own fori_loop counter is a weak int32 — unavoidable, benign,
    and must not fire the contract."""
    def loop(x):
        return jax.lax.fori_loop(0, 3, lambda i, a: a + 1.0, x)

    assert check_strong_scan_carry(
        _jaxpr(loop, jnp.zeros((), jnp.float32))) == []


def test_no_host_callback_detects_pure_callback():
    def bad(x):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2.0,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    msgs = check_no_host_callback(_jaxpr(bad, jnp.ones(3)))
    assert msgs and "callback" in msgs[0]
    assert check_no_host_callback(_jaxpr(jnp.sin, jnp.ones(3))) == []


def test_fma_seam_barrier_detects_unguarded_mul_add():
    def bad(w, x, acc):
        return acc + w * x

    msgs = check_fma_seam_barrier(
        _jaxpr(bad, jnp.ones((4, 8)), jnp.ones((4, 8)), jnp.ones((4, 8))))
    assert msgs and "optimization_barrier" in msgs[0]

    def good(w, x, acc):
        return acc + jax.lax.optimization_barrier(w * x)

    assert check_fma_seam_barrier(
        _jaxpr(good, jnp.ones((4, 8)), jnp.ones((4, 8)),
               jnp.ones((4, 8)))) == []


def test_fma_seam_barrier_skips_rank1_chains():
    """Rank-1 mul→add (scalar/elementwise polynomial chains) is outside
    the seam contract — erfinv in jax.random would false-positive."""
    def poly(x):
        return x + 2.0 * x * x

    assert check_fma_seam_barrier(_jaxpr(poly, jnp.ones(8))) == []


def test_branch_collective_parity_detects_divergent_switch():
    """One switch branch ppermutes, the other doesn't: with a replicated
    branch index that is a mesh deadlock. Structural — a 1-device mesh
    exhibits the same divergent jaxpr."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("agents",))
    perm = [(0, 0)]

    def diverge(idx, x):
        def local(i, v):
            return jax.lax.switch(i, [
                lambda u: jax.lax.ppermute(u, "agents", perm),
                lambda u: u * 2.0,
            ], v)

        return jax.shard_map(local, mesh=mesh, in_specs=(P(), P("agents")),
                             out_specs=P("agents"), check_vma=False)(idx, x)

    msgs = check_branch_collective_parity(
        _jaxpr(diverge, jnp.zeros((), jnp.int32), jnp.ones(4)))
    assert msgs and "deadlock" in msgs[0]

    def parity(idx, x):
        def local(i, v):
            return jax.lax.switch(i, [
                lambda u: jax.lax.ppermute(u, "agents", perm),
                lambda u: jax.lax.ppermute(u * 2.0, "agents", perm),
            ], v)

        return jax.shard_map(local, mesh=mesh, in_specs=(P(), P("agents")),
                             out_specs=P("agents"), check_vma=False)(idx, x)

    assert check_branch_collective_parity(
        _jaxpr(parity, jnp.zeros((), jnp.int32), jnp.ones(4))) == []


def test_barrier_ratchet_counts_and_gates():
    def pinned(x):
        return jax.lax.optimization_barrier(x * 2.0) + \
            jax.lax.optimization_barrier(x * 3.0)

    assert count_barriers(_jaxpr(pinned, jnp.ones(4))) == 2

    ep = EntryPoint(
        name="synthetic.ratchet",
        build=lambda: (pinned, (jnp.ones(4),), {}),
        contracts=(), min_barriers=3)
    findings = check_entry_point(ep)
    assert [f.rule for f in findings] == ["barrier-ratchet"]
    assert "registered minimum is 3" in findings[0].message


def test_untraceable_entry_point_is_a_finding():
    def broken():
        raise RuntimeError("hook is wrong")

    findings = check_entry_point(EntryPoint(name="synthetic.broken",
                                            build=broken))
    assert [f.rule for f in findings] == ["entry-point-trace"]
    assert "RuntimeError" in findings[0].message


def test_min_devices_gates_skipped_entry_points():
    calls = []

    def build():
        calls.append(1)
        return (lambda x: x, (jnp.ones(2),), {})

    ep = EntryPoint(name="synthetic.big", build=build,
                    min_devices=len(jax.devices()) + 1)
    assert check_entry_point(ep) == []
    assert calls == []


def test_default_contracts_cover_the_big_three():
    assert set(DEFAULT_CONTRACTS) == {
        "no-host-callback", "strong-scan-carry",
        "branch-collective-parity"}


# -- the CLI gate on a full 8-device mesh -------------------------------


def test_contract_cli_passes_on_8_forced_devices():
    """The CI static-analysis gate verbatim: every entry point — the
    mesh-only halo/rotating-switch ones included — passes under a forced
    8-device host platform."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=str(REPO / "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--layer", "contracts"],
        env=env, capture_output=True, text=True, timeout=900, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout
