"""Validity: programs the engine's process built inside the window (compiled
or fetched from the persistent cache — a new shape either way), heard by the
launcher's `jax.monitoring` listener. Must read 0."""


def read(collected: dict):
    return float(collected["compiles_in_window"])
