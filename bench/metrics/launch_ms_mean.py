"""Execute: mean host time of the program's `repro.launch` span (source
mask, seed resolution, the dispatch of the sweep, the delta patch it
builds, and any compile these trigger), from the window's delta of
`QueryServer.stats`. Moves read_p50_ms."""

UNIT = "ms"


def read(obs):
    def d(key):
        return obs.stats1.get(key, 0) - obs.stats0.get(key, 0)

    calls = d("repro.launch.calls")
    return d("repro.launch.s") / calls * 1e3 if calls else None
