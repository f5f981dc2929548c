"""Executables JAX prepared inside the measured window (built, or read
back from the persistent cache), counted through ``jax.monitoring``.
Must read 0: everything belongs to set-up."""


def read(ctx):
    return float(ctx["compiles_in_window"])
