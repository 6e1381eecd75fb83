"""Execute: host time of the program's `repro.project` span (each
member's answer cut from the batch's frontier on the host) per read
projected (`repro.project.reads`), from the window's delta of
`QueryServer.stats`. Moves read_qps."""

UNIT = "us"


def read(obs):
    def d(key):
        return obs.stats1.get(key, 0) - obs.stats0.get(key, 0)

    reads = d("repro.project.reads")
    return d("repro.project.s") / reads * 1e6 if reads else None
