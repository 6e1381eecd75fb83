"""The one traffic generator: every stream a loop consumes, drawn from the
seed and a mix file's parameters (`bench/traffic/<mix>.json`).

Each stream is its own generator keyed by (seed, stream name, phase), so a
stream's i-th draw never depends on how far another stream got, and the
window's draws never depend on how long warm-up ran. The write stream is
the exception by nature: a write is drawn against the edge set the writes
before it left (`Writes`), so every phase of a run shares one.
"""
from __future__ import annotations

import zlib

import numpy as np

FNV_OFFSET_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)
CHUNK = 4096


def rng_for(seed: int, stream: str, phase: str = "") -> np.random.Generator:
    """An independent generator per (seed, stream, phase)."""
    tag = zlib.crc32(f"{stream}/{phase}".encode())
    return np.random.default_rng([int(seed) & (2**63 - 1), tag])


def fnv64(x: np.ndarray) -> np.ndarray:
    """FNV-1a over the 8 little-endian bytes of each value (YCSB's
    `Utils.fnvhash64`, unsigned)."""
    h = np.full(x.shape, FNV_OFFSET_64, np.uint64)
    v = x.astype(np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (v & np.uint64(0xFF))) * FNV_PRIME_64
            v = v >> np.uint64(8)
    return h


class Chooser:
    """Draws items from a fixed population: `uniform`, or YCSB's
    scrambled Zipfian (rank r with weight 1 / (r + 1)^theta, item
    fnv64(r) mod N, so the popular items are spread over the ids)."""

    def __init__(self, items: np.ndarray, spec: dict, rng):
        self.items = np.asarray(items)
        self.rng = rng
        self.kind = spec["dist"]
        if self.kind == "zipf":
            n = len(self.items)
            w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** spec["theta"]
            self.cdf = np.cumsum(w) / w.sum()
            self.scramble = (fnv64(np.arange(n)) % np.uint64(n)).astype(
                np.int64)
        elif self.kind != "uniform":
            raise ValueError(f"unknown distribution {self.kind!r}")

    def draw(self, size: int) -> np.ndarray:
        n = len(self.items)
        if self.kind == "uniform":
            return self.items[self.rng.integers(0, n, size)]
        r = np.minimum(np.searchsorted(self.cdf, self.rng.random(size)),
                       n - 1)
        return self.items[self.scramble[r]]

    def one(self) -> int:
        return int(self.draw(1)[0])


def unit_exponential(m: int) -> np.ndarray:
    """m gaps at the midpoint quantiles of the unit exponential: a Poisson
    process's gaps with every chunk holding the same set, so every seed
    offers the same arrivals in another order."""
    return -np.log1p(-(np.arange(m) + 0.5) / m)


class Stream:
    """An endless stream drawn in chunks: `next()` gives one value."""

    def __init__(self, draw):
        self._draw = draw
        self._buf = np.zeros(0)
        self._i = 0

    def next(self):
        if self._i == len(self._buf):
            self._buf, self._i = self._draw(CHUNK), 0
        self._i += 1
        return self._buf[self._i - 1]


class Read:
    """One kind of read a mix sends: its query text, its k, the way its
    pattern follows edges ("out", "in" or "both"), for the reference."""

    def __init__(self, spec: dict):
        self.k = int(spec["k"])
        self.direction = spec["direction"]
        self.text = spec["query"].format(k=self.k)
        self.share = float(spec["share"])


def shares(weights, m: int) -> np.ndarray:
    """m indices into `weights`, each i about weights[i] / sum(weights)
    of m times (largest remainders first)."""
    w = np.asarray(weights, np.float64) * m / np.sum(weights)
    n = np.floor(w).astype(np.int64)
    n[np.argsort(n - w, kind="stable")[:m - n.sum()]] += 1
    return np.repeat(np.arange(len(n)), n)


class Traffic:
    """The streams of one phase of a run: read seeds over `sources` (the
    vertices with an edge), operation kinds (a read of each of the mix's
    `reads`, or a write), and Poisson gaps, and the run's one write stream,
    `writes` (None for a read-only mix). Kinds and gaps come in chunks
    that each hold the same multiset, permuted by the seed, so the seed
    changes the order of the work and not its amount."""

    def __init__(self, mix: dict, seed: int, sources, phase: str,
                 writes: "Writes" = None):
        self.reads = [Read(r) for r in mix["reads"]]
        self.seeds = Stream(Chooser(sources, mix["seeds"],
                                    rng_for(seed, "seeds", phase)).draw)
        share = float(mix.get("read_share", 1.0))
        # -1 is a write, i >= 0 a read of self.reads[i]
        mixed = [1.0 - share] + [share * r.share / sum(
            x.share for x in self.reads) for r in self.reads]
        kinds = rng_for(seed, "kinds", phase)
        self.kind = Stream(lambda m: kinds.permutation(shares(mixed, m) - 1))
        gaps = rng_for(seed, "gaps", phase)
        self.gap = Stream(lambda m: gaps.permutation(unit_exponential(m)))
        self.writes = writes

    def next_read(self, write):
        """The next read as (Read, seed). The writes that come before it in
        the stream of kinds are drawn and sent first, through
        `write(kind, src, dst)`."""
        while True:
            i = int(self.kind.next())
            if i >= 0:
                return self.reads[i], int(self.seeds.next())
            write(*self.writes.next())


class Writes:
    """Single-edge writes drawn against the current edge set: CREATE of an
    absent pair and DELETE of a present edge, in turn, so the edge count
    holds. Sources and CREATE targets come from the mix's choosers over
    `sources`; a DELETE takes a uniform present out-edge of its source."""

    def __init__(self, spec: dict, seed: int, live, sources):
        self.live = live
        self.rng = rng_for(seed, "writes")
        self.choose = Chooser(sources, spec["sources"], self.rng)
        self.targets = Chooser(sources, spec["targets"], self.rng)
        self.kinds = spec["kinds"]
        self.drawn = 0

    def next(self):
        """The next write as (kind, src, dst); applied to the edge set."""
        return self.draw(self.kinds[self.drawn % len(self.kinds)])

    def draw(self, kind: str):
        """One write of the given kind, applied to the edge set."""
        for _ in range(1000):
            s = self.choose.one()
            if kind == "create":
                t = self.targets.one()
                if t != s and not self.live.has(s, t):
                    break
            else:
                out = self.live.out(s)
                if len(out):
                    t = int(out[self.rng.integers(0, len(out))])
                    break
        else:
            raise RuntimeError(f"no {kind} found in 1000 draws")
        self.live.apply(kind, s, t)
        self.drawn += 1
        return kind, s, t
