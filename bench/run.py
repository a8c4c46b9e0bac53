"""Run one cell of the Demeter benchmark on the chip.

    python3 bench/run.py --workload afs20-short-open --seed 7 \\
        --seconds 30 --trace 0

Prints the platform, ``device_kind`` and device count first, and as its
last line of standard output one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1``
a ``breakdown``, and last ``compared``: each number the correctness check
compared, beside its limit.  The same numbers close standard error.

It exits non-zero, printing no result, unless JAX's devices are TPUs
and there are as many as the cell asks for; and with one line on
standard error where set-up cannot go on: the configuration has no
usable reference file, or the service refuses one of the cell's reads.
JAX's persistent compile cache lives in ``bench/.jax_cache`` of the
checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    man = harness.manifest()
    cell = {w["name"]: w for w in man["workloads"]}.get(args.workload)
    if cell is None:
        sys.exit(f"bench: no cell {args.workload!r} in BENCHMARK.json")

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        sys.exit(f"bench: cell {args.workload} needs {cell['chips']} TPU "
                 f"chip(s); JAX reports {len(devices)} "
                 f"{devices[0].platform!r} device(s)")
    print(f"device: platform {devices[0].platform} | device_kind "
          f"{devices[0].device_kind} | count {len(devices)} | jax "
          f"{jax.__version__}", flush=True)
    jax.config.update("jax_compilation_cache_dir",
                      str(harness.COMPILE_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    cfg, traffic = harness.cell_files(man, args.workload)
    try:
        result, _ = harness.run_cell(
            cell=args.workload, cfg=cfg, traffic=traffic, seed=args.seed,
            seconds=args.seconds, trace=bool(args.trace), t_start=T_START,
            metrics=harness.cell_metrics(man, args.workload,
                                         bool(args.trace)),
            log=lambda s: print(s, flush=True))
    except harness.SetupError as e:
        sys.exit(str(e))
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
