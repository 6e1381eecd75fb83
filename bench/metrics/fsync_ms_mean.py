"""Writes: mean time of the program's `repro.aof.fsync` span, the flush
and fsync of the append-only file before a write is acknowledged, from
the window's delta of `QueryServer.stats`. Moves read_p50_ms. Nothing to
read in a cell without writes."""

UNIT = "ms"


def read(obs):
    def d(key):
        return obs.stats1.get(key, 0) - obs.stats0.get(key, 0)

    calls = d("repro.aof.fsync.calls")
    return d("repro.aof.fsync.s") / calls * 1e3 if calls else None
