"""Execute: bytes of frontier copied to the host (`repro.fetch.bytes`)
per read projected (`repro.project.reads`), from the window's delta of
`QueryServer.stats`. Moves read_qps."""

UNIT = "bytes/read"


def read(obs):
    def d(key):
        return obs.stats1.get(key, 0) - obs.stats0.get(key, 0)

    reads = d("repro.project.reads")
    return d("repro.fetch.bytes") / reads if reads else None
