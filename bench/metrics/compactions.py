"""Writes: delta compactions inside the window (the window's delta of
`MutableGraph.compactions`). Moves read_p50_ms. Nothing to read where the
graph takes no writes."""

UNIT = "count"


def read(obs):
    return obs.compactions
