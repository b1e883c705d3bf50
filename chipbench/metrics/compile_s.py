"""Seconds inside XLA's backend compile over the whole run, summed from
``jax.monitoring``'s backend_compile_duration events (layer: launcher)."""


def read(ctx):
    return ctx["report"]["compile_s"]
