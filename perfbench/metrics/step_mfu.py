"""The whole step's share of the chips' peak: the algorithm's operations
per iteration (rollout, Eq. 3 contraction, evaluation amortized; see
perfbench/flops.py) times iterations per second, over chips × the bf16
peak (no float32 peak is published). The rate is the host clock's over
the chunks after the one in which the profiler stopped."""

from perfbench import flops


def read(ctx):
    win = ctx.window
    first = win.traced_stamps[1] + 1 if win.traced_stamps else 0
    if len(win.stamps) - 1 - first < 1:
        return None
    c, t = ctx.cell.config, ctx.cell.traffic
    iters_per_s = win.rate(first) / c["n_agents"]
    per_iter = flops.iteration_flops(
        c["n_agents"], c["policy"]["sizes"], t["episode_steps"], ctx.nnz,
        t["eval_episodes"], t["eval_every"])
    peak = ctx.cell.chips * ctx.peak["bf16_flop_per_s"]
    return 100.0 * per_iter * iters_per_s / peak
