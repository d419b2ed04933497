"""Device time of one evaluation point (``envs.rollout.evaluate_best``:
the best agent's noise-free episodes), read in place from the traced
window: the device time of the operations that began while the host was
inside a ``repro/eval`` span (``repro.obs.trace.Trace.span``). The loop
drains each chunk before it evaluates and dispatches the evaluation op
by op, so the device is idle around the span and what begins inside it
is the evaluation's work."""

from perfbench import scopes


def read(ctx):
    return scopes.per_chunk_ms(ctx, scopes.busy_begun_under, "repro/eval")
