"""Device time of the collectives per training iteration: the union of
the traced window's collective operations (by opcode,
``perfbench/collective_ops.py``: the sharded engine's halo
``collective-permute``s, payload and reward all-gathers, best-row and
moment all-reduces), mean over the chips, over the traced iterations.
None where the window holds no collective (a cell on one chip)."""

from perfbench import collective_ops
from perfbench.trace_reduce import length


def read(ctx):
    found = collective_ops.per_chip(ctx)
    if found is None:
        return None
    chips, iters = found
    return collective_ops.ms_per_iter([length(c) for c, _ in chips], iters)
