"""Kernels: the hop kernel's share of its roofline. The least time of the
window's hops is the least bytes they must move over the chip's HBM
bandwidth (`peaks.json`); the share is that over the kernel's traced time.
Moves read_p50_ms.

The least bytes of one hop over a sweep of W packed words are counted
from stored edges, not padded slots, so the count is the same whatever
storage implements the hop: each edge's column id (4 B) and the frontier
words it gathers (4 W B), once for each way the pattern follows it (out
or in: once; both: twice), and the n output rows of W words. A sweep is
the reads one pump launched, each read one seed, so W is ceil(reads / 32),
and it makes k hops. The hop moves no operations worth counting (bitwise
OR), so bytes bound it."""
from collections import Counter

from bench import trace as tr

UNIT = "%"
WAYS = {"out": 1, "in": 1, "both": 2}


def hop_least_bytes(edges: int, n: int, words: int, ways: int) -> int:
    return ways * (edges * 4 + edges * words * 4) + n * words * 4


def read(obs):
    if obs.trace is None:
        return None
    calls, secs = tr.kernel_calls(obs.trace)
    if not calls or secs <= 0:
        return None
    sweeps = Counter((r.launch_pump, r.k, r.direction) for r in obs.reads
                     if r.done is not None)
    least = sum(k * hop_least_bytes(obs.edges, obs.n, -(-lanes // 32),
                                    WAYS[way])
                for (_, k, way), lanes in sweeps.items())
    if not least:
        return None
    return 100.0 * least / obs.peaks["hbm_bytes_per_s"] / secs
