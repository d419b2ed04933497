"""Readings that the limits of `correct` are set from (run on the chip).

For one cell, in one process: the program's first scan chunk through the
timed entry point on a dozen seeds or more (the lower readings), the
bfloat16 control (the reference computed one precision below the
configuration's float32) and the reference with each planted fault
(state frozen, half the batch, no Eq. 3 mixing, the worst row
broadcast: the upper readings), each compared with the float32
reference exactly as a benchmark run compares. On the first seeds also
the look that decided where the comparison starts: the gap of the best
return, the gaps over three iterations, and the largest per-episode gap
between the program's reward function and the reference's episodes.

  python perfbench/calibrate.py --workload er1000.pendulum \\
      --seeds 12 --upper-seeds 3 --out calibrate.jsonl

Not run by the benchmark's own runs. Prints one JSON line per reading.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path[:0] = [str(pathlib.Path(__file__).resolve().parents[1]),
                str(pathlib.Path(__file__).resolve().parents[1] / "src")]

from perfbench import compare, harness  # noqa: E402


def seeds(k: int):
    """Seeds no benchmark run of this PR used, above 2**31."""
    return [2 ** 31 + 101 + 7919 * i for i in range(k)]


class _FirstChunk(harness.FirstChunk):
    """Also keeps the best return, for the look at its spread."""
    KEEP = harness.FirstChunk.KEEP + ("reward_max",)


def program_first_chunk(cell, seed):
    """The first chunk's metrics through the timed entry point, and the
    parameters after its first iteration."""
    import jax

    from repro.train.loop import train_rl_netes
    tc = harness.train_config(cell, seed, iters=cell.traffic["eval_every"])
    with _FirstChunk() as first:
        train_rl_netes(cell.traffic["task"], tc)
    return dict(jax.device_get(first.metrics), **first.follow())


def episode_gap(cell, ref, seed):
    """Largest relative per-episode gap, program reward_fn vs reference,
    on the reference's own first-iteration perturbations."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.envs import resolve_task
    reward_fn = resolve_task(cell.traffic["task"])[0]
    key, thetas = ref.init_thetas(seed)
    _, k_eps, k_eval, _ = jax.random.split(key, 4)
    pos = thetas + cell.config["netes"]["sigma"] * ref.noise(k_eps)
    got = np.asarray(jax.jit(reward_fn)(pos, k_eval))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.rewards(pos, k_eval))
    del pos, thetas
    return float(np.max(np.abs(got - want) / np.abs(want))), \
        float(jnp.median(jnp.abs(got - want) / jnp.abs(want)))


UPPERS = (("control_bf16", dict(dtype="bfloat16")),
          ("fault_frozen", dict(fault="frozen")),
          ("fault_half_batch", dict(fault="half_batch")),
          ("fault_no_mixing", dict(fault="no_mixing")),
          ("fault_wrong_row", dict(fault="wrong_row")))


def upper(setup, ref, kw, seed, chunk):
    """What the reference with a control or fault in it gives in the
    program's place."""
    from perfbench import reference
    other = reference.Reference(setup, edges=ref.edges, **kw)
    got = other.first(seed)
    if kw.get("fault") == "frozen":
        # a state that does not advance replays its first iteration
        got["broadcast"] = [float(got["broadcast"])] * chunk
        got["reward_mean"] = [float(got["reward_mean"])] * chunk
    else:
        got["broadcast"] = other.broadcast_flags(seed, chunk)
        got["reward_mean"] = [float(got["reward_mean"])]
    got["update_var"] = [float(got["update_var"])]
    return got


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--upper-seeds", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    from perfbench import reference
    cell = harness.load_cell(args.workload)
    harness.configure_jax()
    dev = harness.check_devices(cell.chips)
    chunk = cell.traffic["eval_every"]
    out = open(args.out, "a")

    def emit(rec):
        rec.update(cell=cell.name, device=dev["kind"])
        line = json.dumps(rec)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    t0 = time.perf_counter()
    setup = harness.reference_setup(cell)
    ref = reference.Reference(setup)
    emit({"kind": "graph", "nnz": ref.nnz,
          "seconds": time.perf_counter() - t0})
    for i, seed in enumerate(seeds(args.seeds)):
        flags = ref.broadcast_flags(seed, chunk)
        t = time.perf_counter()
        prog = program_first_chunk(cell, seed)
        t_prog = time.perf_counter() - t
        t = time.perf_counter()
        want = ref.first(seed)
        t_ref = time.perf_counter() - t
        emit({"kind": "program", "seed": seed, "broadcast_0": flags[0],
              "numbers": compare.numbers(prog, want, flags),
              "reward_max_gap": abs(float(prog["reward_max"][0])
                                    - float(want["returns"].max()))
              / abs(float(want["returns"].max())),
              "program_s": t_prog, "reference_s": t_ref})
        if i >= args.upper_seeds:
            continue
        later = ref.run(seed, compare.ITERS)
        emit({"kind": "three_iterations", "seed": seed,
              "gaps": compare.later_iterations(prog, later),
              "program": {k: [float(x) for x in prog[k][:compare.ITERS]]
                          for k in _FirstChunk.KEEP},
              "reference": later})
        emit({"kind": "episode_gap", "seed": seed,
              "max_median": episode_gap(cell, ref, seed)})
        for kind, kw in UPPERS:
            got = upper(setup, ref, kw, seed, chunk)
            emit({"kind": kind, "seed": seed,
                  "numbers": compare.numbers(got, want, flags)})
    out.close()


if __name__ == "__main__":
    main()
