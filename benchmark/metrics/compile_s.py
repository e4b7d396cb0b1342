"""Seconds the backend spent building programs during set-up (compiles and
persistent-cache loads), from jax.monitoring's compile events."""


def read(run):
    return run["setup_compile"]["seconds"]
