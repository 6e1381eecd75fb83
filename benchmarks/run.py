"""Benchmark harness: one function per paper table/claim.

  khop        — paper Fig. 1 (k-hop response time, RedisGraph protocol)
  khop-dist   — sharded-vs-single-device k-hop crossover per device count
                (REPRO_FORCE_DEVICES=8 sweeps 1/2/4/8 fake CPU devices),
                plus the packed-vs-unpacked all-gather payload comparison
  khop-packed — bitmap-packed vs float boolean frontiers per frontier
                width (the measured AUTO_PACK_MIN_WIDTH crossover)
  throughput  — paper §II (threadpool/read-scaling claim): Poisson
                open-loop serving, continuous batching vs one-query-at-a-
                time (qps, p50/p99 latency, plan-cache hit rate)
  kernels     — format-selection crossover (BSR/ELL/dense)
  ewise       — mesh/device-resident element-wise: BSR Pallas vs XLA vs
                the pre-refactor host round-trip; shard-local vs gather
  triangles   — GraphChallenge (paper future-work item)
  ktruss      — Graphulo k-truss, sparse (masked SpGEMM) vs dense
  bitadj      — bit-packed adjacency (BitELL): resident bytes + triangle
                and BFS speed vs the float ELL route, validated
                bit-identical first (AUTO_BITADJ_* provenance)
  algos       — algorithm breadth (CALL algo.* tentpole): batched multi-
                source Brandes vs one-BFS-per-source, packed vs unpacked
                closeness widths, BitELL vs ELL cells — each validated
                against the reference before timing
                (AUTO_CENTRALITY_BATCH provenance)
  mutations   — query latency under a live Poisson insert/delete stream
                (delta serving vs rebuild-on-freeze) + the delta-vs-rebuild
                crossover sweep calibrating AUTO_DELTA_COMPACT

Prints ``name,us_per_call,derived`` CSV. ``--json out.json`` additionally
writes the rows as machine-readable records
(``{"suite", "metric", "value", "derived"}``) — what `make bench-smoke`
archives as ``BENCH_*.json`` and CI diffs run-over-run. Roofline terms come
from the dry-run artifacts: ``python -m benchmarks.roofline``.
"""
from __future__ import annotations

import json
import os
import sys

# Must run before anything imports jax: a fake multi-device CPU topology
# (the khop-dist sweep) can only be forced through XLA_FLAGS at backend
# init — same env guard as tests/conftest.py.
if os.environ.get("REPRO_FORCE_DEVICES"):
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count="
            + os.environ["REPRO_FORCE_DEVICES"]).strip()


def main(argv=None) -> None:
    from repro import compile_cache
    compile_cache.enable()
    from benchmarks import bench_algos, bench_bitadj, bench_ewise, \
        bench_khop, bench_kernels, bench_ktruss, bench_mutations, \
        bench_throughput, bench_triangles
    argv = list(sys.argv[1:] if argv is None else argv)
    json_path = None
    if "--json" in argv:
        i = argv.index("--json")
        try:
            json_path = argv[i + 1]
        except IndexError:
            raise SystemExit("--json needs an output path")
        del argv[i:i + 2]
    only = argv[0] if argv else None
    suites = {
        "khop": bench_khop.run,
        "khop-dist": bench_khop.run_dist,
        "khop-packed": bench_khop.run_packed,
        "throughput": bench_throughput.run,
        "kernels": bench_kernels.run,
        "ewise": bench_ewise.run,
        "triangles": bench_triangles.run,
        "ktruss": bench_ktruss.run,
        "mutations": bench_mutations.run,
        "bitadj": bench_bitadj.run,
        "algos": bench_algos.run,
    }
    if only and only not in suites:
        raise SystemExit(f"unknown suite {only!r}; one of "
                         f"{', '.join(suites)}")
    rows: list = []
    records: list = []
    print("name,us_per_call,derived")
    for name, fn in suites.items():
        if only and name != only:
            continue
        start = len(rows)
        fn(rows)
        for r in rows[start:]:
            print(f"{r[0]},{r[1]:.1f},{r[2]}")
            records.append({"suite": name, "metric": r[0],
                            "value": float(r[1]), "derived": str(r[2])})
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(records, fh, indent=1)
        print(f"# wrote {len(records)} records to {json_path}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
