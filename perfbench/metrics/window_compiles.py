"""XLA compilations inside the timed window: compile requests that the
persistent cache did not serve (public ``jax.monitoring`` events).
Nothing should compile once set-up has run every shape."""


def read(ctx):
    return float(ctx.window.counters["compiles"])
