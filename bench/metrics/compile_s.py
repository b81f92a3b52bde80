"""Seconds of XLA compiles and persistent-cache loads in set-up, from
``jax.monitoring``'s backend-compile events."""


def read(r):
    return r.setup.get("compile_s")
