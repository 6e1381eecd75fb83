"""Front end: frontier columns per launched sweep over the window, the
window's delta of `QueryServer.stats` (batched_width_total / batches).
Moves read_qps."""

UNIT = "lanes"


def read(obs):
    batches = obs.stats1["batches"] - obs.stats0["batches"]
    width = (obs.stats1["batched_width_total"]
             - obs.stats0["batched_width_total"])
    return width / batches if batches else None
