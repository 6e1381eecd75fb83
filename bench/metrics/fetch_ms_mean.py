"""Transfer: mean host time of the program's `repro.fetch` span, the copy
of a batch's frontier from the device to the host, from the window's
delta of `QueryServer.stats`. Host clock, not device time. Moves
read_p50_ms."""

UNIT = "ms"


def read(obs):
    def d(key):
        return obs.stats1.get(key, 0) - obs.stats0.get(key, 0)

    calls = d("repro.fetch.calls")
    return d("repro.fetch.s") / calls * 1e3 if calls else None
