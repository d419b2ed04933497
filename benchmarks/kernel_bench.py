"""Kernel micro-benchmarks: wall-times of the jnp reference paths (the
actual CPU execution) and a correctness pass of each Pallas kernel in
interpret mode. Interpret-mode timings are NOT hardware-representative
(Python interpretation) — the TPU perf story lives in the roofline report;
this harness proves the kernels run and the refs' CPU costs scale sanely.

``sparse_crossover`` is the representation-dispatch decision table
(DESIGN.md §3): per (N, p) it measures the dense vs sparse vs circulant
mixing backends on this host AND models the distributed step on the
production target, where the all-gather's N·D bytes — not flops — bind
(Chen et al. 2018). The winner column drives
``topology_repr.select_representation``'s cutoffs.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref

from . import common, perfmodel, registry


def _time(fn, *args, iters=5):
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters


# ---------------------------------------------------------------------------
# dense-vs-sparse crossover (ISSUE 1 acceptance table)
# ---------------------------------------------------------------------------
# The production-target model constants live in benchmarks/perfmodel.py
# (shared with fleet_bench); see that module and DESIGN.md §3/§8 for why
# the winner is judged on the modeled distributed step (wire bytes), not
# host wall-time.


def sparse_crossover(quick: bool = False):
    """Dense-vs-sparse mixing crossover over (N, p).

    Columns per cell: measured host ms for the dense matmul path and the
    sparse neighbor-gather path of ``core.netes.mixing_update`` (plus the
    circulant roll-chain on the same-density circulant-ER graph), the
    padded fan-in K_max, and the modeled production step time per backend.
    Host wall-times favor the dense path beyond its flop share — XLA's CPU
    row-gathers run ~50× below Eigen's sgemm throughput, so O(N·K·D) work
    loses to O(N²·D) matmuls until K/N ≪ measured-crossover — which is why
    the winner (and the representation heuristic) is judged on the modeled
    distributed step, where wire bytes bind.
    """
    from repro.core import netes, topology, topology_repr
    from repro.core.netes import NetESConfig

    rng = np.random.default_rng(0)
    cfg = NetESConfig()
    d = 64 if quick else 256
    iters = 3 if quick else 5

    def mix(topo_or_adj, th, pe, sh):
        return netes.mixing_update(topo_or_adj, th, pe, sh, cfg)

    mix_j = jax.jit(mix)
    print("# sparse_crossover: N, p, K_max, dense_ms, sparse_ms, "
          "circulant_ms, model_dense_us, model_sparse_us, winner")
    table = []
    for n in (256, 1024):
        for p in (0.05, 0.1, 0.5):
            adj = topology.erdos_renyi(n, p=p, seed=0)
            t_sparse = topology_repr.from_dense(adj, "sparse")
            th = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
            pe = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
            sh = jnp.asarray(rng.normal(size=n), jnp.float32)

            dt_dense = _time(mix_j, jnp.asarray(adj), th, pe, sh,
                             iters=iters)
            dt_sparse = _time(mix_j, t_sparse, th, pe, sh, iters=iters)
            # parity guard: the two backends must agree on the bench graph
            err = float(jnp.abs(mix_j(jnp.asarray(adj), th, pe, sh)
                                - mix_j(t_sparse, th, pe, sh)).max())
            assert err < 1e-4, err

            circ = topology.circulant_erdos_renyi(n, p=p, seed=0)
            t_circ = topology_repr.from_dense(circ, "circulant")
            dt_circ = _time(mix_j, t_circ, th, pe, sh, iters=iters)

            k_max = t_sparse.k_max
            m_dense = perfmodel.modeled_step_us(n, n, "dense")
            m_sparse = perfmodel.modeled_step_us(n, k_max, "sparse")
            winner = "sparse" if m_sparse < m_dense else "dense"
            table.append((n, p, k_max, dt_dense, dt_sparse, dt_circ,
                          m_dense, m_sparse, winner))
            common.emit(
                f"kernel.crossover.n{n}_p{p}", dt_dense,
                f"K={k_max} sparse_ms={dt_sparse * 1e3:.2f} "
                f"circ_ms={dt_circ * 1e3:.2f} "
                f"model_dense_us={m_dense:.0f} "
                f"model_sparse_us={m_sparse:.0f} winner={winner}")
    print("# N     p     K_max  dense_ms  sparse_ms  circ_ms  "
          "model_dense_us  model_sparse_us  winner")
    for row in table:
        print(f"# {row[0]:<5} {row[1]:<5} {row[2]:<6} {row[3]*1e3:<9.2f} "
              f"{row[4]*1e3:<10.2f} {row[5]*1e3:<8.2f} {row[6]:<15.0f} "
              f"{row[7]:<16.0f} {row[8]}")
    # acceptance guard: the sparse path must win the production model in
    # the paper's sparse regime (Fig. 2B: N ≈ 1000, p ≤ 0.1)
    for n_, p_, *_rest, winner_ in table:
        if n_ == 1024 and p_ <= 0.1:
            assert winner_ == "sparse", (n_, p_, winner_)
    return table


def run(quick: bool = False):
    entries = []
    rng = np.random.default_rng(0)
    s = 256 if quick else 1024

    # flash attention ref
    q = jnp.asarray(rng.normal(size=(1, s, 8, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, s, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, s, 2, 64)), jnp.float32)
    dt = _time(jax.jit(lambda a, b, c: ref.flash_attention_ref(a, b, c)),
               q, k, v)
    flops = 4 * s * s * 8 * 64 / 2  # causal half
    common.emit("kernel.attn_ref", dt, f"S={s} gflops/s={flops / dt / 1e9:.1f}")
    entries.append(registry.Entry(
        name="kernel.attn_ref", wall_s=dt,
        extra={"S": s, "gflops_per_s": flops / dt / 1e9}))

    # netes mixing ref
    n, p = 64, 1 << 16
    adj = jnp.asarray((rng.random((n, n)) < 0.5).astype(np.float32))
    wt = jnp.asarray(rng.normal(size=n), jnp.float32)
    th = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    ep = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    dt = _time(jax.jit(lambda *a: ref.netes_mixing_ref(*a, sigma=0.1)),
               adj, wt, wt, th, ep)
    common.emit("kernel.netes_mixing_ref", dt,
                f"N={n} P={p} gb/s={(3 * n * p * 4) / dt / 1e9:.1f}")
    entries.append(registry.Entry(
        name="kernel.netes_mixing_ref", wall_s=dt,
        extra={"N": n, "P": p, "gb_per_s": (3 * n * p * 4) / dt / 1e9}))

    # mamba scan ref
    dec = jnp.asarray(rng.uniform(0.9, 0.999, (1, s, 128, 16)), jnp.float32)
    drv = jnp.asarray(rng.normal(size=(1, s, 128, 16)), jnp.float32)
    dt = _time(jax.jit(ref.mamba_scan_ref), dec, drv)
    common.emit("kernel.mamba_scan_ref", dt, f"S={s} d=128 n=16")
    entries.append(registry.Entry(name="kernel.mamba_scan_ref", wall_s=dt,
                                  extra={"S": s}))

    # rwkv ref
    r = jnp.asarray(rng.normal(size=(1, s, 4, 64)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.9, 0.999, (1, s, 4, 64)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    dt = _time(jax.jit(lambda *a: ref.rwkv6_wkv_ref(*a)[0]), r, r, r, w, u)
    common.emit("kernel.rwkv6_wkv_ref", dt, f"S={s} H=4 n=64")
    entries.append(registry.Entry(name="kernel.rwkv6_wkv_ref", wall_s=dt,
                                  extra={"S": s}))

    # interpret-mode correctness pulse (tiny shapes); gated via eval_score
    # (1.0 pass / 0.0 fail — one-sided compare catches a parity break)
    from repro.core import topology_repr
    from repro.kernels import netes_mixing as nm
    from repro.kernels import netes_sparse_mixing as nsm
    out_k = nm.netes_mixing(adj[:8, :8], wt[:8], wt[:8], th[:8, :256],
                            ep[:8, :256], sigma=0.1)
    out_r = ref.netes_mixing_ref(adj[:8, :8], wt[:8], wt[:8], th[:8, :256],
                                 ep[:8, :256], sigma=0.1)
    ok = bool(jnp.allclose(out_k, out_r, rtol=1e-4, atol=1e-4))
    common.emit("kernel.pallas_interpret_check", 0.0, f"allclose={ok}")
    entries.append(registry.Entry(name="kernel.pallas_interpret_check",
                                  eval_score=float(ok)))

    idx8, mask8 = topology_repr.sparse_neighbors(np.asarray(adj[:8, :8]))
    out_sk = nsm.netes_sparse_mixing(jnp.asarray(idx8), jnp.asarray(mask8),
                                     wt[:8], wt[:8], th[:8, :256],
                                     ep[:8, :256], sigma=0.1)
    ok = bool(jnp.allclose(out_sk, out_r, rtol=1e-4, atol=1e-4))
    common.emit("kernel.pallas_sparse_interpret_check", 0.0,
                f"allclose={ok}")
    entries.append(registry.Entry(
        name="kernel.pallas_sparse_interpret_check", eval_score=float(ok)))

    # fused broadcast-best select (DESIGN.md §12) vs its jnp oracle: the
    # Pallas lowering, compiled on TPU and interpreted elsewhere
    from repro.core import wire_format
    from repro.kernels import netes_fused_mixing as nfm
    bw = wire_format.encode(th[0, :256], 8, batched=False)
    out_bk = nfm.fused_broadcast_select(
        bw.codes, bw.scale, jnp.asarray(True), th[:8, :256],
        backend="pallas")
    out_br = ref.broadcast_select_ref(bw.codes, bw.scale,
                                      jnp.asarray(True), th[:8, :256])
    ok = bool(jnp.allclose(out_bk, out_br, rtol=1e-4, atol=1e-4))
    common.emit("kernel.pallas_fused_broadcast_check", 0.0,
                f"allclose={ok}")
    entries.append(registry.Entry(
        name="kernel.pallas_fused_broadcast_check", eval_score=float(ok)))

    for (n_, p_, k_max, dt_dense, dt_sparse, dt_circ, m_dense, m_sparse,
         winner) in sparse_crossover(quick=quick):
        entries.append(registry.Entry(
            name=f"kernel.crossover.n{n_}_p{p_}",
            wall_s=dt_dense,
            # gated metric: modeled per-chip bytes of the SPARSE backend —
            # exact, machine-independent (DESIGN.md §8)
            wire_bytes=perfmodel.wire_bytes(n_, k_max, "sparse"),
            extra={"k_max": k_max, "sparse_ms": dt_sparse * 1e3,
                   "circulant_ms": dt_circ * 1e3,
                   "model_dense_us": m_dense, "model_sparse_us": m_sparse,
                   "winner": winner}))
    return entries


@registry.register("kernels", group="kernels")
def bench(ctx: registry.Context):
    return run(quick=ctx.quick)
