"""Writes: 99th percentile of the window's CREATE / DELETE acknowledgement
time through `Database.query`, the AOF fsync included. The loop runs
writes on its own thread, so this time delays reads. Moves read_p50_ms.
Nothing to read in a cell without writes."""
import numpy as np

UNIT = "ms"


def read(obs):
    acks = [w.ack_s for w in obs.writes]
    return float(np.percentile(acks, 99)) * 1e3 if acks else None
