"""Seconds JAX spent in set-up tracing, lowering and compiling programs or
loading them from the persistent cache (``jax.monitoring`` durations)."""


def read(run: dict):
    return run["compile"]["seconds"]
