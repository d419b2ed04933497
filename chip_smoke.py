"""Smoke run of the NetES trainer on a TPU, through its normal entry point.

Drives ``repro.train.loop.train_rl_netes`` — the function
``python -m repro.launch.train rl`` calls — on the pendulum task with
the paper's policy (MLP 3→64→64→1, D=4481) and random initial weights
made from the seed:

  (a) N=1000 Erdős–Rényi p=0.1, representation auto (sparse)
  (b) (a) over a quantize(bits=8) channel: the int8 wire path and the
      compiled Pallas ``fused_broadcast_select``
  (c) N=1000 fully connected (dense)
  (d) N=16384 Erdős–Rényi p=0.0005 (mean degree ≈ 8), sparse, q8

Each phase trains twice with one config: the first call compiles, the
second replays warm and must reproduce the first bit for bit. Rewards
and the final evaluation must be finite, and ``fused_broadcast_select``
must equal ``ref.broadcast_select_ref`` exactly at phase (d)'s shape.

  python chip_smoke.py             # one chip: phases (a)-(d)
  python chip_smoke.py --chips 4   # phase (d) with shards=4 vs shards=1

``--chips 4`` runs only the sharded fleet (DESIGN.md §13): the same seed
on a 4-device mesh and on a 1-device mesh. It reports whether they agree
bit for bit (the contract the CPU tests hold; a v5e 2x2 has not met it)
and fails on a relative difference above 1e-4.

Each phase prints one JSON line. Times are host-clock seconds of one run
(not a benchmark): ``compile_s`` sums JAX's trace, lowering and backend
compile events of the first call, ``cold_s`` and ``warm_s`` are the
wall times of the two calls, and ``warm_train_s`` is the warm call's
``history["wall_s"]`` — the training loop alone, without building the
graph on the host (about 10 s at N=16384). The last line of stdout is
``{"ok": true, "device": {...}}``; any failure raises, exits non-zero
and prints no such line. Off a TPU the script exits non-zero at once.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

# Run from a checkout without PYTHONPATH: ``src`` provides ``repro``.
_SRC = str(pathlib.Path(__file__).resolve().parent / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

TASK = "pendulum"
PENDULUM_DIM = 4481
ITERS = 8
EVAL_EVERY = 4
N_FLEET = 16384
P_FLEET = 0.0005
REL_TOL_SHARDED = 1e-4

PHASES = (
    # name, n_agents, family, p, channel, expected representation
    ("a_er1000", 1000, "erdos_renyi", 0.1, None, "sparse"),
    ("b_er1000_q8", 1000, "erdos_renyi", 0.1, "quantize(bits=8)", "sparse"),
    ("c_fc1000", 1000, "fully_connected", 1.0, None, "dense"),
    ("d_er16384_q8", N_FLEET, "erdos_renyi", P_FLEET, "quantize(bits=8)",
     "sparse"),
)


class CompileClock:
    """Sums JAX's compile-path durations through the public
    ``jax.monitoring`` listener; compiles and persistent-cache loads are
    counted by ``repro.obs.xla_watch.Watch``."""

    def __init__(self):
        import jax

        from repro.obs.xla_watch import Watch
        self.seconds = 0.0
        self.watch = Watch().start()
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


def train_config(n, family, p, channel, shards=None):
    from repro.core.netes import NetESConfig
    from repro.core.topology import TopologySpec
    from repro.train.loop import TrainConfig
    # netes: the launcher's defaults (python -m repro.launch.train rl)
    return TrainConfig(
        n_agents=n, iters=ITERS, eval_every=EVAL_EVERY,
        topology=TopologySpec(family=family, n_agents=n, p=p, seed=0),
        channel=channel, shards=shards, seed=0,
        netes=NetESConfig(alpha=0.05, sigma=0.1, p_broadcast=0.8))


def check_history(name, hist):
    import numpy as np
    series = np.asarray(hist["reward_mean"] + hist["reward_max"]
                        + hist["eval"], np.float64)
    if len(hist["reward_mean"]) != ITERS or not np.all(np.isfinite(series)):
        raise AssertionError(f"{name}: non-finite or short history {hist}")


def timed_train(clock, tc):
    from repro.train.loop import train_rl_netes
    compile0 = clock.seconds
    t0 = time.perf_counter()
    hist = train_rl_netes(TASK, tc)
    return hist, time.perf_counter() - t0, clock.seconds - compile0


def run_phase(clock, kind, name, n, family, p, channel, expected_rep):
    from repro.train.loop import build_topology
    tc = train_config(n, family, p, channel)
    rep = build_topology(tc).kind
    if rep != expected_rep:
        raise AssertionError(f"{name}: representation {rep}, "
                             f"expected {expected_rep}")
    cold, cold_s, compile_s = timed_train(clock, tc)
    warm, warm_s, _ = timed_train(clock, tc)
    check_history(name, cold)
    check_history(name, warm)
    if (warm["reward_mean"] != cold["reward_mean"]
            or warm["eval"] != cold["eval"]):
        raise AssertionError(f"{name}: warm replay differs from cold run")
    print(json.dumps({
        "phase": name, "device_kind": kind, "n_agents": n, "dim":
        PENDULUM_DIM, "representation": rep, "channel": channel,
        "iters": ITERS, "compile_s": compile_s, "cold_s": cold_s,
        "warm_s": warm_s, "warm_train_s": warm["wall_s"],
        "final_eval": cold["final_eval"],
        "reward_mean_last": cold["reward_mean"][-1]}), flush=True)


def check_broadcast_select(kind):
    """The compiled Pallas kernel, exactly equal to its oracle at phase
    (d)'s shape, for both values of the broadcast flag."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import wire_format
    from repro.kernels import netes_fused_mixing as nfm
    from repro.kernels import ref

    k_theta, k_best = jax.random.split(jax.random.PRNGKey(0))
    thetas = jax.random.normal(k_theta, (N_FLEET, PENDULUM_DIM), jnp.float32)
    wp = wire_format.encode(jax.random.normal(k_best, (PENDULUM_DIM,)),
                            8, False)
    hlo = nfm.fused_broadcast_select.lower(
        wp.codes, wp.scale, jnp.asarray(True), thetas).compile().as_text()
    if "tpu_custom_call" not in hlo:
        raise AssertionError("fused_broadcast_select did not compile to "
                             "the Pallas kernel")
    for flag in (True, False):
        do = jnp.asarray(flag)
        got = nfm.fused_broadcast_select(wp.codes, wp.scale, do, thetas)
        want = ref.broadcast_select_ref(wp.codes, wp.scale, do, thetas)
        if not np.array_equal(np.asarray(got), np.asarray(want)):
            raise AssertionError(f"fused_broadcast_select != oracle "
                                 f"(flag={flag})")
    print(json.dumps({"phase": "broadcast_select_vs_ref",
                      "device_kind": kind, "shape": [N_FLEET, PENDULUM_DIM],
                      "exact": True}), flush=True)


def run_sharded(clock, kind):
    """Phase (d) with the agent axis on a 4-device mesh against a
    1-device mesh, same seed (DESIGN.md §13)."""
    import numpy as np
    runs = {}
    for shards in (1, 4):
        tc = train_config(N_FLEET, "erdos_renyi", P_FLEET,
                          "quantize(bits=8)", shards=shards)
        hist, cold_s, compile_s = timed_train(clock, tc)
        check_history(f"shards={shards}", hist)
        runs[shards] = hist
        print(json.dumps({
            "phase": f"d_er16384_q8_shards{shards}", "device_kind": kind,
            "compile_s": compile_s, "cold_s": cold_s,
            "train_s": hist["wall_s"], "final_eval": hist["final_eval"],
            "reward_mean_last": hist["reward_mean"][-1]}), flush=True)
    one, four = runs[1], runs[4]
    a = np.asarray(one["reward_mean"] + one["eval"], np.float64)
    b = np.asarray(four["reward_mean"] + four["eval"], np.float64)
    bitwise = bool(np.array_equal(a, b))
    d_reward = float(np.max(np.abs(np.asarray(one["reward_mean"])
                                   - np.asarray(four["reward_mean"]))))
    rel = float(np.max(np.abs(a - b) / np.maximum(np.abs(a), 1e-30)))
    print(json.dumps({"phase": "shards4_vs_shards1", "device_kind": kind,
                      "bitwise_equal": bitwise,
                      "max_abs_d_reward_mean": d_reward,
                      "max_rel_diff": rel}), flush=True)
    if rel > REL_TOL_SHARDED:
        raise AssertionError(f"shards=4 vs shards=1: relative difference "
                             f"{rel} > {REL_TOL_SHARDED}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-fleet comparison")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s)", file=sys.stderr)
        return 1

    clock = CompileClock()
    kind = devices[0].device_kind
    if args.chips == 4:
        run_sharded(clock, kind)
    else:
        for phase in PHASES:
            run_phase(clock, kind, *phase)
        check_broadcast_select(kind)
    print(json.dumps({"phase": "compile_cache", "dir": cache_dir,
                      "cache_loads": clock.watch.cache_loads,
                      "compiles": clock.watch.compiles}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
