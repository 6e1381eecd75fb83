"""Parse / plan: mean host time inside `QueryServer.submit` (plan-cache
lookup, seed binding, enqueue), timed by the harness around the call.
Moves read_qps."""

UNIT = "us"


def read(obs):
    t = [r.submit_s for r in obs.reads]
    return sum(t) / len(t) * 1e6 if t else None
