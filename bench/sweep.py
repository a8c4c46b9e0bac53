"""Find the highest open-loop rate an open-loop cell sustains.

    python3 bench/sweep.py --workload afs20-short-open --seed 5 \\
        --seconds 30 --rates 6 7 8 9 10 11

Runs the cell once per rate in one process (the cell's traffic file with
``rate_rps`` replaced), and prints per rate the latency median and 95th
percentile, the reads served per second, how long after the window's
close the last due request was answered (``drain_s``), and the median
latency of the last quarter of requests over that of the first
(``trend``).  A rate is sustained when neither grows: the backlog then
does not build up over the window.  The cell's fixed rate is set once,
by hand, at about four fifths of the highest sustained rate.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("sweep: needs a TPU")
    jax.config.update("jax_compilation_cache_dir", str(harness.COMPILE_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    man = harness.manifest()
    cfg, traffic = harness.cell_files(man, args.workload)
    if traffic["loop"] != "open":
        sys.exit("sweep: the cell's traffic is not an open loop")
    for rate in args.rates:
        result, run = harness.run_cell(
            cell=args.workload, cfg=cfg, traffic={**traffic, "rate_rps": rate},
            seed=args.seed, seconds=args.seconds, trace=False,
            t_start=time.perf_counter(), metrics=[], log=lambda s: None)
        lat = run.latencies_s
        q = max(1, len(lat) // 4)
        print(json.dumps({
            "rate_rps": rate, "correct": result["correct"],
            "attempted": result["attempted"], "failed": result["failed"],
            "p50_s": harness.nearest_rank(lat, 50),
            "p95_s": harness.nearest_rank(lat, 95),
            "served_reads_per_s": run.window_reads / run.window_s,
            "drain_s": run.drain_s,
            "trend": statistics.median(lat[-q:]) / statistics.median(lat[:q]),
        }), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
