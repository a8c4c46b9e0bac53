"""Read the correctness check's control: the reference in bfloat16.

    python3 bench/control.py --workload afs20-short-open --seeds 1 2 3

For each seed it makes the cell's genomes and requests, builds the
configuration's plain reference, and puts the reference computed with its
agreements held in bfloat16 in the program's place: cohorts of the cell's
batch, padded to the lengths the program's service gives them (one
service is built for that alone, on the first seed, and asked for every
seed), and whole requests, sampled as a run samples them, profiled both
ways, compared by the same numbers a run compares and judged by the same
limits (``correct``).  The check is
sound only where this control reads ``correct`` false on every seed.
The benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import pathlib
import sys
import types

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


def padder(cfg: dict, traffic: dict, genomes: np.ndarray):
    """``pad(length)``: the cohort length the program's service pads a read
    of ``length`` bases to.  The service (with its RefDB of ``genomes``,
    through the program's store) is built once, for its buckets alone."""
    from bench import harness

    _, service, _ = harness.build_service(cfg, traffic, genomes,
                                          log=lambda s: None)
    return functools.partial(harness.padded_length, service)


def readings(cfg: dict, traffic: dict, seed: int, seconds: float,
             pad) -> dict:
    """The control's compared numbers on one seed; ``pad`` is
    :func:`padder`'s."""
    from bench import harness, loadgen

    wl = loadgen.make(cfg, traffic, seed, seconds)
    sent = wl.requests[:wl.clients] if wl.loop == "closed" else wl.requests
    ref = harness.reference(cfg)(cfg, wl.genomes)

    # Cohorts as the service forms them: batch_size rows in arrival order,
    # padded to the length the service gives the longest read.
    b = cfg["batch_size"]
    lengths = np.concatenate([r.lengths for r in sent])
    width = max(r.tokens.shape[1] for r in sent)
    tokens = np.concatenate([np.pad(r.tokens, ((0, 0), (0, width
                                                        - r.tokens.shape[1])))
                             for r in sent])
    cohorts = []
    for i in range(0, len(lengths), b):
        idx = np.arange(i, min(i + b, len(lengths)))
        padded = pad(lengths[idx].max())
        ln = np.zeros(b, np.int32)
        ln[:len(idx)] = lengths[idx]
        part = tokens[idx, :padded]
        tk = np.zeros((b, padded), np.int8)
        tk[:part.shape[0], :part.shape[1]] = part
        cohorts.append((tk, ln, None))
    cohorts = harness.sample_cohorts(cohorts, seed)
    for k, (tk, ln, _) in enumerate(cohorts):
        sc = np.zeros((b, ref.bounds.size - 1), np.float32)
        sc[ln > 0] = ref.scores(tk[ln > 0], ln[ln > 0], control=True)
        cohorts[k] = (tk, ln, sc)

    requests = harness.sample_requests(
        [{"req": r, "report": types.SimpleNamespace(total_reads=r.reads)}
         for r in sent], seed)
    for rec in requests:
        rep = ref.report(*ref.classify(rec["req"].tokens, rec["req"].lengths,
                                       control=True))
        rec["report"] = types.SimpleNamespace(
            total_reads=rep["total"], unmapped_reads=rep["unmapped"],
            multi_reads=rep["multi"], unique_counts=rep["unique_counts"],
            abundance=rep["abundance"])
    out = {"failed": 0, **harness.compare(ref, ref.prototypes, requests,
                                          cohorts)}
    out["correct"] = harness.is_correct(out)
    out["cohort_reads"] = int(sum((c[1] > 0).sum() for c in cohorts))
    out["request_reads"] = sum(r["report"].total_reads for r in requests)
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    import jax
    jax.config.update("jax_compilation_cache_dir", str(harness.COMPILE_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import loadgen
    cfg, traffic = harness.cell_files(harness.manifest(), args.workload)
    pad = padder(cfg, traffic, loadgen.make(cfg, traffic, args.seeds[0],
                                            args.seconds).genomes)
    for seed in args.seeds:
        r = readings(cfg, traffic, seed, args.seconds, pad)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "device": jax.devices()[0].device_kind,
                          **r}), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
