"""Host: share of the traced window the serving thread spends on the
program's own work, from the window's delta of `QueryServer.stats`: the
thread's CPU seconds in `repro.pump` (where it sleeps there, it waits on
the device: a result read back, or a dispatch held while the device's
queue is full), plus the wall seconds of `repro.submit` and `repro.write`
(host work and the AOF fsync, which never wait on the device). Near 100
the host holds the device back; well under it, it does not. Moves
read_qps."""

UNIT = "%"


def read(obs):
    def d(key):
        return obs.stats1.get(key, 0) - obs.stats0.get(key, 0)

    if obs.trace is None or obs.trace["window_s"] <= 0:
        return None
    if not d("repro.pump.calls"):
        return None
    busy = d("repro.pump.cpu_s") + d("repro.submit.s") + d("repro.write.s")
    return 100.0 * busy / obs.trace["window_s"]
