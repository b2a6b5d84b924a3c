"""Seconds of XLA backend compile (persistent-cache loads included)
inside the window, from jax.monitoring. Set-up warms every shape, so
this should read 0."""


def read(record):
    return record["compile_s"]
