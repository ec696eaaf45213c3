"""Executables the backend produced (compiled or read from the persistent
cache) inside the window, counted through ``jax.monitoring``; warm-up ends
only after a round that needed none, so this should read 0."""


def read(ctx):
    return ctx["compiles"]
