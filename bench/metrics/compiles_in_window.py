"""Compile: executables built inside the window (compiled, or read back
from the persistent cache), counted by a `jax.monitoring` listener.
Expected 0. Moves read_p50_ms."""

UNIT = "count"


def read(obs):
    return obs.compiles_in_window
