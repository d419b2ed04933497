"""Exposed collective time per training iteration: the time of the
traced window in which a collective operation ran on a chip and no work
did, mean over the chips, over the traced iterations. None where the
window holds no collective.

Operations are classed by opcode (``perfbench/collective_ops.py``). As
``trace_reduce.Reduced.collective_exposed_s``, except that an operation
that only holds others (``while``, ``conditional``, ``call``) is no
work of its own: on a chip's ``XLA Ops`` line the training scan's loop
spans every operation of its body, collectives included, and would hide
them all."""

from perfbench import collective_ops
from perfbench.trace_reduce import length, subtract


def read(ctx):
    found = collective_ops.per_chip(ctx)
    if found is None:
        return None
    chips, iters = found
    return collective_ops.ms_per_iter(
        [length(subtract(c, w)) for c, w in chips], iters)
