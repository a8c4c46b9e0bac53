"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (see benchmarks.common.emit).

  accuracy    Fig 2/3   precision/recall per profiler per sample
  query_perf  Fig 4/5   software query time + throughput
  memory      Fig 6     working-structure bytes + reduction ratios
  build_time  Fig 11    reference build time
  acc_perf    Fig 12/13 accelerated (TPU-model) query time/throughput
  energy      Table 3   energy breakdown + Mbp/J
  accel_sim   §5/Table 3 PCM-substrate noise sweep + analytical cost model
  serve_perf  §1 system   ProfilingService reads/s + p50/p99 request latency
  tenant_serve §1 system  registry+router fleet reads/s + delta hot-swap
                          publish/drain latency
  fleet_serve  §1 system  multi-host aggregate reads/s vs host count +
                          fleet-swap flip/retire + host-kill failover
  shard_scaling  §scale   sharded-AM reads/s + RefDB bytes/device vs shards
                          (grow the sweep with
                          XLA_FLAGS=--xla_force_host_platform_device_count=N)

``--smoke`` switches to the CI benchmark smoke instead: a tiny sample
through every hot-path backend, emitting machine-readable
``BENCH_smoke.json`` for the regression gate
(``python -m benchmarks.check_regression``); see benchmarks/smoke.py.
"""

from __future__ import annotations

import sys

from benchmarks import (accel_sim, accuracy, acc_perf, build_time, common,
                        energy, fleet_serve, memory, query_perf, serve_perf,
                        shard_scaling, tenant_serve)


def main() -> None:
    if "--smoke" in sys.argv[1:]:
        from benchmarks import smoke
        smoke.main([a for a in sys.argv[1:] if a != "--smoke"])
        return
    only = sys.argv[1] if len(sys.argv) > 1 else None
    community = common.afs_small()
    print("name,us_per_call,derived")

    def want(name):
        return only is None or only == name

    if want("accuracy"):
        accuracy.run(community)
    sw = None
    if want("query_perf"):
        sw = query_perf.run(community)
    if want("memory"):
        memory.run(community)
    if want("build_time"):
        build_time.run(community)
    if want("acc_perf"):
        acc_perf.run(community, software_query=sw)
    if want("energy"):
        energy.run(community)
    if want("accel_sim"):
        accel_sim.run(community)
    if want("serve_perf"):
        serve_perf.run(community)
    if want("tenant_serve"):
        tenant_serve.run(community)
    if want("fleet_serve"):
        fleet_serve.run(community)
    if want("shard_scaling"):
        shard_scaling.run(community)


if __name__ == "__main__":
    main()
