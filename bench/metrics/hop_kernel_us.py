"""Kernels: mean device time per call of the hop's Pallas kernel in the
traced window. Moves read_p50_ms."""
from bench import trace as tr

UNIT = "us"


def read(obs):
    if obs.trace is None:
        return None
    calls, secs = tr.kernel_calls(obs.trace)
    return secs / calls * 1e6 if calls else None
