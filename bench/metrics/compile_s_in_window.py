"""Compile: seconds the program spent tracing, lowering and compiling
(or reading back) executables inside the window, the window's delta of
its `compile.s` counter in `QueryServer.stats`. Expected 0. Moves
read_p50_ms. Nothing to read where the program keeps no such counter."""

UNIT = "s"


def read(obs):
    if "compile.s" not in obs.stats1:
        return None
    return obs.stats1["compile.s"] - obs.stats0.get("compile.s", 0)
