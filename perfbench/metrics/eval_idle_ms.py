"""Device idle time per evaluation point that the host spends in the
training loop's ``eval`` phase: the idle time of the traced window that
``repro/eval`` host spans (``repro.obs.trace.Trace.span``) cover, over
the traced evaluation points."""

from perfbench import scopes


def read(ctx):
    return scopes.per_chunk_ms(ctx, scopes.idle_under, "repro/eval")
