"""Writes: mean host time of the program's `repro.refresh` span, the
snapshot freeze before each launch (the delta catch-up of the writes
acknowledged since the last one, and any compaction), from the window's
delta of `QueryServer.stats`. Moves read_p50_ms. Nothing to read in a
cell without writes."""

UNIT = "ms"


def read(obs):
    def d(key):
        return obs.stats1.get(key, 0) - obs.stats0.get(key, 0)

    calls = d("repro.refresh.calls")
    if not obs.writes or not calls:
        return None
    return d("repro.refresh.s") / calls * 1e3
