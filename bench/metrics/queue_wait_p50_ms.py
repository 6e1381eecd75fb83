"""Front end: median queue wait of the window's reads, from submit to the
launch of their batch (`Submitted.wait_s`, the server's own record).
Moves read_p50_ms."""
import numpy as np

UNIT = "ms"


def read(obs):
    waits = [r.wait_s for r in obs.reads if r.done is not None]
    return float(np.median(waits)) * 1e3 if waits else None
