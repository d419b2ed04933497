"""Device idle time per chunk that the host spends draining: the idle
time of the traced window that ``repro/drain`` host spans (the chunk's
metrics and the evaluation score, each one ``jax.device_get``) cover,
over the traced chunks."""

from perfbench import scopes


def read(ctx):
    return scopes.per_chunk_ms(ctx, scopes.idle_under, "repro/drain")
