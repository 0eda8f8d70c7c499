"""Launcher layer: seconds of XLA compilation during set-up (a hit in the
persistent cache counts its retrieval), from ``jax.monitoring``."""


def read(run):
    return run["setup_compile"]["seconds"]
