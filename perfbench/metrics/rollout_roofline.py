"""Reward evaluation's share of its roofline: the least time at the
chip's peaks (operations: 2N episodes × steps × the policy's
multiply-adds; bytes: each perturbed parameter vector read once) over the
measured device time of ``rollout_ms``'s probe. At these shapes the
bytes bound it."""

from perfbench import flops


def read(ctx):
    s = ctx.probe_seconds.get("rollout")
    if s is None:
        return None
    c, t = ctx.cell.config, ctx.cell.traffic
    sizes, n = c["policy"]["sizes"], c["n_agents"]
    share = flops.roofline_share(
        flops.rollout_flops(n, sizes, t["episode_steps"]),
        flops.rollout_bytes(n, flops.policy_dim(sizes)), s, ctx.peak)
    return share["percent"]
