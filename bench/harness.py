"""The benchmark harness: one cell from set-up to its result line.

A cell is found by name: `workloads/<cell>.json` names its configuration
(`configs/<config>.json`), its traffic mix (`traffic/<mix>.json`, read by
`streams.Traffic`) and the load the mix is offered at; the mix names its
loop (`loops/<loop>.py`). Per-layer metrics are the readers in `metrics/`,
each `read(obs)` returning a number or None. Adding any of these is adding
a file: nothing here names a cell, a mix or a metric.

A run: build the graph from the seed (bulk load, or CREATE lines through
the fsynced AOF), warm up on the cell's own traffic until a warm-up pass
builds no executable, measure for `seconds`, read the device's peak
memory, free the program's state, then compare a seeded sample of the
window's answers with the plain reference (`graph500`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import graph500, streams

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
# Warm-up ends at the first pass (the loop's `warm`) that builds no
# executable; a cell still building after WARM_MAX_PASSES fails the run.
# With an empty compile cache each compile stalls the ramp past some batch
# widths, so a cold run takes many passes.
# A workload file may name `measure_unsettled_after`: the passes after
# which a cell whose program builds in every pass is measured as it stands.
WARM_MAX_PASSES = 20
DRAIN_S = 60.0
CREATE_LINE_EDGES = 1024


# -- the registry ---------------------------------------------------------------
def load_json(root: Path, kind: str, name: str) -> dict:
    path = root / kind / f"{name}.json"
    if not path.is_file():
        have = sorted(p.stem for p in (root / kind).glob("*.json"))
        raise SystemExit(f"unknown {kind[:-1]} {name!r} (have: {have})")
    return json.loads(path.read_text())


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    mix: dict
    loop: object


def load_cell(name: str, root: Path = BENCH) -> Cell:
    w = load_json(root, "workloads", name)
    cfg = load_json(root, "configs", w["config"])
    mix = load_json(root, "traffic", w["traffic"])
    loop = root / "loops" / f"{mix['loop']}.py"
    if not loop.is_file():
        raise SystemExit(f"mix {w['traffic']!r}: no loop {mix['loop']!r}")
    return Cell(name, w, cfg, mix, load_module(loop))


def metric_readers(root: Path = BENCH) -> Dict[str, object]:
    return {p.stem: load_module(p)
            for p in sorted((root / "metrics").glob("*.py"))}


def peaks_for(kind: str, root: Path = BENCH) -> dict:
    table = json.loads((root / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(have: {sorted(table['devices'])})")
    return table["devices"][kind]


# -- the chip ------------------------------------------------------------------
def open_chip(cell: Cell, what: str):
    """(device, log) for a run on the chip, or SystemExit where JAX finds
    no TPU or fewer chips than the cell asks for. Turns on the persistent
    compile cache (the program's own directory) for every program, however
    quick to compile, and the compile counter. `log` prefixes each line
    with the device."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    tag = f"[{dev.platform} {dev.device_kind} x{len(devices)}]"
    if dev.platform != "tpu":
        raise SystemExit(f"{tag} {what}: needs a TPU, JAX found "
                         f"{dev.platform}")
    chips = int(cell.workload["chips"])
    if len(devices) < chips:
        raise SystemExit(f"{tag} {what}: {cell.name} needs {chips} chips")
    if chips != 1:
        raise SystemExit(f"{tag} {what}: {cell.name} asks for {chips} "
                         f"chips; the harness builds no mesh and runs "
                         f"one-chip cells only")
    peaks_for(dev.device_kind)           # an unknown kind is an error
    from repro import compile_cache
    cache = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _Compiles.listen(jax)

    def log(msg: str) -> None:
        print(f"{tag} {msg}", file=sys.stderr, flush=True)

    log(f"setup {what} cell={cell.name} compile_cache={cache}")
    return dev, log


# -- compile counting (a copy of chip_smoke.Clock's listener) --------------------
class _Compiles:
    """Executables built in this process: the backend-compile event fires
    for each, whether compiled or read from the persistent cache."""
    count = 0
    _on = False

    @classmethod
    def listen(cls, jax) -> None:
        if cls._on:
            return

        def on_event(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                cls.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)
        cls._on = True


# -- the system under test, as the loops see it ---------------------------------
@dataclasses.dataclass
class ReadRec:
    qid: int
    seed: int
    k: int
    direction: str             # the way the pattern follows edges
    due: float                 # latency runs from here (scheduled or sent)
    sent: float                # when the loop called submit
    submit_s: float            # host time inside QueryServer.submit
    phase: str
    done: Optional[float] = None
    launch_pump: int = -1
    wait_s: float = 0.0        # Submitted.wait_s: queue wait to launch
    count: Optional[int] = None
    error: Optional[str] = None


@dataclasses.dataclass
class WriteRec:
    kind: str
    src: int
    dst: int
    ack_s: float
    ok: bool
    phase: str


class Target:
    """Reads go through `QueryServer.submit` / `pump`, writes through
    `Database.query` on the caller's thread. Records every operation."""

    def __init__(self, db, name: str, srv, span, state_dir: Path,
                 lines: List[str]):
        self.db, self.name, self.srv = db, name, srv
        self.span = span
        self.state_dir = state_dir
        self.lines = lines              # mutating lines acknowledged, in order
        self.phase = "warm"
        self.reads: Dict[int, ReadRec] = {}
        self.writes: List[WriteRec] = []  # single edges, in ack order
        self.writes_at_pump: List[int] = []   # writes acked as pump p began
        self.queued: List[tuple] = []         # (time, reads pending), per pump
        self._log_at = 0

    @property
    def pending(self) -> int:
        return self.srv.pending

    def submit(self, read: streams.Read, seed: int, due: float) -> int:
        t0 = time.perf_counter()
        with self.span("bench.submit"):
            qid = self.srv.submit(read.text, seeds=[int(seed)],
                                  arrival_s=due)
        self.reads[qid] = ReadRec(qid, int(seed), read.k, read.direction,
                                  due, t0, time.perf_counter() - t0,
                                  self.phase)
        return qid

    def pump(self) -> List[ReadRec]:
        p = len(self.writes_at_pump)
        self.writes_at_pump.append(len(self.writes))
        self.queued.append((time.perf_counter(), self.srv.pending))
        with self.span("bench.pump"):
            out = self.srv.pump()
        t = time.perf_counter()
        log = self.srv.log
        for m in log[self._log_at:]:
            self.reads[m.qid].wait_s = m.wait_s
        self._log_at = len(log)
        done = []
        for qid, res in out.items():
            r = self.reads[qid]
            r.done, r.launch_pump = t, p - 1
            if res.error is not None:
                r.error = res.error
            elif len(res.rows) != 1 or len(res.rows[0]) != 1:
                r.error = f"rows {res.rows!r}"
            else:
                r.count = int(res.rows[0][0])
            done.append(r)
        return done

    def write(self, kind: str, s: int, t: int, rel: str) -> None:
        """One single-edge CREATE or DELETE, acknowledged before it returns."""
        text = mutation(kind, [s], [t], rel)
        t0 = time.perf_counter()
        with self.span("bench.write"):
            res = self.db.query(self.name, text)
        self.lines.append(text)
        self.writes.append(WriteRec(
            kind, int(s), int(t), time.perf_counter() - t0,
            res.error is None and res.rows == [(0, 1)], self.phase))

    def drain(self) -> None:
        end = time.perf_counter() + DRAIN_S
        while self.srv.pending and time.perf_counter() < end:
            self.pump()


# -- set-up ---------------------------------------------------------------------
def build(cell: Cell, seed: int, state_dir: Path):
    """(db, server, graph name, edge list, mutating lines acknowledged) for
    the cell's configuration."""
    from repro.engine import Database
    from repro.graph.graph import GraphBuilder
    cfg = cell.config
    src, dst, n = graph500.edge_list(cfg, seed)
    name, rel = cfg["name"], cfg["relation"]
    lines: List[str] = []
    if cfg["load"] == "bulk":
        g = (GraphBuilder(n).add_edges(rel, src, dst)
             .build(fmt=cfg["storage_fmt"], block=cfg["block"]))
        db = Database()
        db.load_graph(name, g)
    elif cfg["load"] == "create":
        shutil.rmtree(state_dir, ignore_errors=True)
        db = Database(data_dir=str(state_dir))
        for i in range(0, len(src), CREATE_LINE_EDGES):
            lines.append(mutation("create", src[i:i + CREATE_LINE_EDGES],
                                  dst[i:i + CREATE_LINE_EDGES], rel))
            db.query(name, lines[-1])
    else:
        raise SystemExit(f"config {cfg['name']}: unknown load "
                         f"{cfg['load']!r}")
    srv = db.server(name)         # the first freeze builds the base here
    return db, srv, name, (src, dst, n), lines


def mutation(kind: str, src, dst, rel: str) -> str:
    verb = "CREATE" if kind == "create" else "DELETE"
    return f"{verb} " + ", ".join(f"({a})-[:{rel}]->({b})"
                                  for a, b in zip(src, dst))


def aof_lines(state_dir: Path, name: str) -> List[str]:
    path = state_dir / f"{name}.aof"
    return path.read_text().splitlines() if path.is_file() else []


# -- the correctness comparison -------------------------------------------------
def compare(cell: Cell, target: Target, edges, seed: int,
            control: Optional[str] = None) -> Dict[str, dict]:
    """Each number compared, with its limit. A seeded sample of the
    window's reads is answered by the plain reference over the edge set
    as the writes acknowledged before the read's batch launched left it.
    `control` puts a broken reference in the program's place:
    "one_hop_short" answers k - 1 hops, "stale_snapshot" answers from the
    edge set as it stood before the last writes ahead of the batch."""
    src, dst, n = edges
    reads = [r for r in target.reads.values() if r.phase == "window"]
    lost = [r for r in reads if r.done is None or r.error is not None]
    ok = [r for r in reads if r.done is not None and r.error is None]
    rng = streams.rng_for(seed, "check")
    size = min(int(cell.mix["check_sample"]), len(ok))
    sample = [ok[i] for i in rng.choice(len(ok), size, replace=False)]

    def state(r: ReadRec, stale: bool = False) -> int:
        """Writes acknowledged before the read's batch launched; `stale`:
        before the last of those writes that came in one run between
        pumps."""
        at = target.writes_at_pump
        j = r.launch_pump
        while stale and j > 0 and at[j - 1] == at[r.launch_pump]:
            j -= 1
        return at[j - 1] if stale and j > 0 else at[j]

    def answers(stale: bool, short: int) -> Dict[int, int]:
        """The reference's count for each sampled read, replaying the
        acknowledged writes in order up to each read's state."""
        live = graph500.LiveEdges(src, dst, n)
        applied, out = 0, {}
        for r in sorted(sample, key=lambda r: (state(r, stale), r.qid)):
            while applied < state(r, stale):
                w = target.writes[applied]
                live.apply(w.kind, w.src, w.dst)
                applied += 1
            out[r.qid] = live.khop(r.seed, r.k - short, r.direction)
        return out

    want = answers(False, 0)
    if control == "one_hop_short":
        got = answers(False, 1)
    elif control == "stale_snapshot":
        got = answers(True, 0)
    else:
        got = {r.qid: r.count for r in sample}
    mismatched = sum(got[q] != want[q] for q in want)
    checks = {"mismatched_counts": mismatched, "reads_failed": len(lost)}
    if cell.mix.get("read_share", 1.0) < 1.0:
        checks["writes_failed"] = sum(not w.ok for w in target.writes)
        aof = aof_lines(target.state_dir, target.name)
        checks["acked_lines_missing_from_aof"] = sum(
            a != b for a, b in zip(aof, target.lines)) + max(
            len(target.lines) - len(aof), 0)
    return {key: {"value": v, "limit": 0} for key, v in checks.items()}


# -- trace ------------------------------------------------------------------------
@contextlib.contextmanager
def traced(on: bool, out_dir: Path):
    """Profile the block when `on`; yields the span factory to use."""
    import jax
    if not on:
        yield lambda name: contextlib.nullcontext()
        return
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        yield jax.profiler.TraceAnnotation
    finally:
        jax.profiler.stop_trace()


# -- one run ----------------------------------------------------------------------
@dataclasses.dataclass
class Observation:
    """What the window left for the per-layer readers."""
    cell: Cell
    reads: List[ReadRec]
    writes: List[WriteRec]
    stats0: dict
    stats1: dict
    compactions: Optional[int]     # None where the graph takes no writes
    compiles_in_window: int
    storage_bytes: int
    edges: int
    n: int
    peaks: dict
    trace: Optional[dict]          # bench.trace.reduce() of the window


def percentile(x, q: float) -> float:
    return float(np.percentile(np.asarray(x, np.float64), q))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, device, root: Path = BENCH,
             control: Optional[str] = None, log=print) -> dict:
    """Set up, warm up, measure, check. Returns the result object."""
    import jax
    from bench import trace as tr
    _Compiles.listen(jax)
    state_dir = root.parent / ".bench_state" / cell.name
    tmp = root.parent / ".bench_state" / f"{cell.name}.trace"
    shutil.rmtree(tmp, ignore_errors=True)
    try:
        return _run(cell, seed, seconds, trace, t_start, device, root,
                    control, log, jax, tr, state_dir, tmp)
    finally:
        shutil.rmtree(state_dir, ignore_errors=True)
        shutil.rmtree(tmp, ignore_errors=True)


@dataclasses.dataclass
class Session:
    """A built, warmed-up cell: the program's objects and the run's
    streams. `traffic(phase)` gives a phase its own read streams, sharing
    the one write stream."""
    cell: Cell
    seed: int
    db: object
    srv: object
    name: str
    edges: tuple
    target: Target
    warm: streams.Traffic

    @property
    def rel(self) -> str:
        return self.cell.config["relation"]

    def traffic(self, phase: str) -> streams.Traffic:
        src, dst, _ = self.edges
        return streams.Traffic(self.cell.mix, self.seed,
                               np.unique(np.concatenate([src, dst])), phase,
                               self.warm.writes)


def set_up(cell: Cell, seed: int, state_dir: Path, t_start: float,
           log=print) -> Session:
    """Build the graph, then warm up on the cell's own traffic until a
    pass builds no executable. A cell still building after
    WARM_MAX_PASSES passes, or after the workload's
    `measure_unsettled_after`, fails the run or is measured as it stands."""
    db, srv, name, edges, lines = build(cell, seed, state_dir)
    src, dst, n = edges
    log(f"setup built n={n} edges={len(src)} "
        f"compiles={_Compiles.count} t={time.perf_counter() - t_start:.3f}")
    target = Target(db, name, srv, null_span, state_dir, lines)
    sources = np.unique(np.concatenate([src, dst]))
    writes = None
    if "writes" in cell.mix:
        writes = streams.Writes(cell.mix["writes"], seed,
                                graph500.LiveEdges(src, dst, n), sources)
    warm = streams.Traffic(cell.mix, seed, sources, "warm", writes)
    ses = Session(cell, seed, db, srv, name, edges, target, warm)
    unsettled = cell.workload.get("measure_unsettled_after")
    for i in range(unsettled or WARM_MAX_PASSES):
        c0 = _Compiles.count
        cell.loop.warm(target, warm, cell.workload["load"], ses.rel)
        built = _Compiles.count - c0
        log(f"setup warm pass={i} compiles={built} "
            f"reads={len(target.reads)} writes={len(target.writes)} "
            f"t={time.perf_counter() - t_start:.3f}")
        if not built:
            return ses
    if unsettled:
        log(f"setup warm unsettled after {unsettled} passes: measured as "
            f"it stands")
        return ses
    log(f"setup warm still built executables after {WARM_MAX_PASSES} "
        f"passes: not measured")
    raise SystemExit(1)


def null_span(name: str):
    return contextlib.nullcontext()


def _run(cell, seed, seconds, trace, t_start, device, root, control, log,
         jax, tr, state_dir, tmp) -> dict:
    ses = set_up(cell, seed, state_dir, t_start, log)
    target, srv, db, rel = ses.target, ses.srv, ses.db, ses.rel
    src, dst, n = edges = ses.edges
    mg = db.graphs[ses.name]
    win = ses.traffic("window")
    # the set-up's objects are never freed in the window: a full collection
    # there scans only what the window makes
    gc.collect()
    gc.freeze()
    stats0 = dict(srv.stats)
    comp0 = mg.compactions
    p0 = len(target.writes_at_pump)
    target.phase = "window"
    with traced(trace, tmp) as span:
        target.span = span
        c0 = _Compiles.count
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        with span("bench.window"):
            cell.loop.drive(target, win, cell.workload["load"], seconds,
                            rel)
        t1 = time.perf_counter()
        compiles = _Compiles.count - c0
    target.span = null_span
    stats1 = dict(srv.stats)
    peak = int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))
    reads = [r for r in target.reads.values() if r.phase == "window"]
    writes = [w for w in target.writes if w.phase == "window"]
    lat = [(r.done - r.due) if r.done is not None else DRAIN_S
           for r in reads]
    in_time = sum(r.done is not None and r.done <= t0 + seconds
                  for r in reads)
    late = [r.sent - r.due for r in reads]
    log(f"window s={t1 - t0:.3f} reads={len(reads)} writes={len(writes)} "
        f"completed_in_window={in_time} compiles={compiles} "
        f"pumps={len(target.writes_at_pump) - p0} peak_bytes={peak}")
    storage = graph500.storage_bytes(db.context(ses.name).graph)
    obs = Observation(
        cell, reads, writes, stats0, stats1,
        (mg.compactions - comp0) if cell.config["load"] == "create" else None,
        compiles, storage, len(src), n, peaks_for(device.device_kind, root),
        None)
    result_trace = None
    if trace:
        events = tr.load(tmp)
        obs.trace = tr.reduce(events)
        result_trace = obs.trace
    e2e = {
        "read_qps": (in_time / seconds, "queries/s"),
        "read_p50_ms": (percentile(lat, 50) * 1e3, "ms"),
        "read_p99_ms": (percentile(lat, 99) * 1e3, "ms"),
        "device_bytes_per_edge": (peak / len(src), "bytes/edge"),
        "setup_s": (setup_s, "s"),
    }
    per_layer = {}
    if trace:
        for key, mod in metric_readers(root).items():
            v = mod.read(obs)
            if v is not None:
                per_layer[key] = (float(v), mod.UNIT)
    # free the program's state before the reference runs
    target.srv = target.db = ses.srv = ses.db = None
    del srv, db, mg
    gc.unfreeze()
    gc.collect()
    t2 = time.perf_counter()
    checks = compare(cell, target, edges, seed, control)
    log(f"check reference_s={time.perf_counter() - t2:.3f} "
        f"sampled={min(int(cell.mix['check_sample']), len(reads))} "
        f"generator_late_p99_ms={percentile(late, 99) * 1e3:.3f}")
    failed = checks["reads_failed"]["value"] + checks.get(
        "writes_failed", {"value": 0})["value"]
    metrics = per_layer if trace else e2e
    out = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(reads) + len(writes),
        "failed": failed,
        "metrics": {k: {"value": v, "unit": u}
                    for k, (v, u) in metrics.items()},
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak},
    }
    if result_trace is not None:
        out["device"]["busy_s"] = result_trace["busy_s"]
        out["device"]["window_s"] = result_trace["window_s"]
        out["breakdown"] = {"device_ops": result_trace["top_ops"],
                            "idle_gaps": result_trace["idle_gaps"]}
    out["checks"] = checks
    return out

