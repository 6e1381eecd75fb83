"""Parse / plan: mean host time of the program's `repro.submit` span
(plan-cache lookup, seed binding, enqueue), from the window's delta of
`QueryServer.stats`. Moves read_qps. Nothing to read where the program
has no such span."""

UNIT = "us"


def read(obs):
    def d(key):
        return obs.stats1.get(key, 0) - obs.stats0.get(key, 0)

    calls = d("repro.submit.calls")
    return d("repro.submit.s") / calls * 1e6 if calls else None
