"""Eq. 3 mixing's share of its roofline: the least time at the chip's
peaks (operations 2·nnz·D; bytes 3·N·D floats plus an int32 index per
edge, none for a fully connected graph) over ``mixing_ms``'s measured
device time."""

from perfbench import flops


def read(ctx):
    s = ctx.probe_seconds.get("mixing")
    if s is None:
        return None
    c = ctx.cell.config
    n, dim = c["n_agents"], c["policy"]["dim"]
    fc = c["topology"]["family"] == "fully_connected"
    share = flops.roofline_share(
        flops.mixing_flops(ctx.nnz, dim),
        flops.mixing_bytes(n, dim, ctx.nnz, fc), s, ctx.peak)
    return share["percent"]
