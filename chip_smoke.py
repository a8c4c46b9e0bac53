"""Smoke run of the Demeter profiler's main path on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chip    # 4-way sharded RefDB vs one chip

One process drives the path a user drives: ``ProfilingSession`` builds
an AFS20-scale RefDB (20 synthetic species of 600 kbp, made from
``--seed``) with the ``pallas_fused`` backend at the production widths
(D=40960, n=16, window 8192, batch 4096), profiles 16k 150-bp reads, and
a ``ProfilingService`` answers 4 concurrent requests.  The script checks
that the ``pallas_fused`` prototypes and first-batch species scores are
bit-identical to the ``reference`` backend's, and that every service
report equals a sequential ``profile()`` of the same reads.

``--four-chip`` runs only the sharded phase: ``sharded`` over
``pallas_fused`` on 4 devices against single-device ``pallas_fused``,
bit-exact, with each shard's device printed.

Timings are smoke figures from one cold run, not benchmark results.
The script exits non-zero before any work unless JAX's first device is
a TPU; its last line is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

SPECIES = 20                 # AFS20: 20 animal references, ~12 Mbp in all
GENOME_LEN = 600_000
SAMPLE_READS = 16_384        # 4 batches of 4096
PRESENT = (0, 5, 11, 17)     # skewed sample with a ~1% trace species
ABUNDANCE = (0.70, 0.20, 0.09, 0.01)
REQUESTS = 4
READS_PER_REQUEST = 3_000


def _require_devices(count: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < count:
        sys.exit(f"chip_smoke: needs {count} TPU device(s); JAX reports "
                 f"{len(devices)} {devices[0].platform!r} device(s)")
    print(f"jax {jax.__version__} | device_kind {devices[0].device_kind} "
          f"| devices {len(devices)}", flush=True)
    return devices


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")
    print(f"check OK: {what}", flush=True)


def _community(seed: int):
    """AFS20-scale genomes plus a skewed sample, all from ``seed``."""
    import numpy as np
    from repro.genomics import synth

    spec = synth.CommunitySpec(num_species=SPECIES, genome_len=GENOME_LEN,
                               seed=seed)
    genomes = synth.make_reference_genomes(spec)
    abundance = np.zeros(SPECIES)
    abundance[list(PRESENT)] = ABUNDANCE
    rng = np.random.default_rng(seed + 1)
    tokens, lengths, _ = synth.sample_reads(genomes, abundance, SAMPLE_READS,
                                            spec, rng)
    return genomes, tokens, lengths, abundance


def _config(backend: str, **options):
    from benchmarks.common import PROD_CONFIG
    return dataclasses.replace(PROD_CONFIG, backend=backend,
                               backend_options=options)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _report_line(label: str, report, seconds: float, truth) -> None:
    from repro.eval import score_profile
    m = score_profile(report.abundance, truth)
    print(f"smoke {label}: {report.total_reads} reads in {seconds} s "
          f"({report.total_reads / seconds} reads/s) | mapped "
          f"{report.mapped_reads} unmapped {report.unmapped_reads} | "
          f"precision {m.precision} recall {m.recall} L1 {m.l1_error}",
          flush=True)


def one_chip(seed: int) -> None:
    import numpy as np
    from repro.pipeline import ArraySource, ProfilingSession
    from repro.serve import ProfilingService

    genomes, tokens, lengths, truth = _community(seed)
    session = ProfilingSession(_config("pallas_fused"))
    db, t_build = _timed(
        lambda: session.build_or_load_refdb(genomes, cache_dir=None))
    print(f"smoke build (pallas_fused, compile included): {t_build} s | "
          f"{db.num_prototypes} prototypes, {db.memory_bytes()} bytes",
          flush=True)

    ref = ProfilingSession(_config("reference"))
    ref_db, t_ref = _timed(lambda: ref.build_refdb(genomes))
    print(f"smoke build (reference): {t_ref} s", flush=True)
    _check(np.array_equal(np.asarray(db.prototypes),
                          np.asarray(ref_db.prototypes)),
           "pallas_fused RefDB prototypes bit-identical to reference")

    batch = session.config.batch_size
    first, t_first = _timed(lambda: np.asarray(session.classify_batch(
        tokens[:batch], lengths[:batch]).classification.scores))
    print(f"smoke first batch (compile included): {t_first} s", flush=True)
    want = np.asarray(ref.classify_batch(
        tokens[:batch], lengths[:batch]).classification.scores)
    _check(np.array_equal(first, want),
           "first-batch species scores bit-identical to reference")

    report, t_query = _timed(
        lambda: session.profile(ArraySource(tokens, lengths)))
    _report_line("sequential profile", report, t_query, truth)

    sources = [ArraySource(tokens[i * READS_PER_REQUEST:
                                  (i + 1) * READS_PER_REQUEST],
                           lengths[i * READS_PER_REQUEST:
                                   (i + 1) * READS_PER_REQUEST])
               for i in range(REQUESTS)]
    service = ProfilingService(session, max_active=REQUESTS)
    t0 = time.perf_counter()
    with service:
        handles = [service.submit(src) for src in sources]
        served = [h.result(timeout=900) for h in handles]
    t_serve = time.perf_counter() - t0
    print(f"smoke service: {REQUESTS} requests x {READS_PER_REQUEST} reads "
          f"in {t_serve} s (compile included), {service.cohorts_run} "
          f"cohorts", flush=True)
    for i, (src, rep) in enumerate(zip(sources, served)):
        _check(rep.to_json() == session.profile(src).to_json(),
               f"service request {i} bit-identical to sequential profile")


def four_chip(seed: int) -> None:
    import numpy as np
    from repro.pipeline import ArraySource, ProfilingSession, per_device_bytes

    genomes, tokens, lengths, truth = _community(seed)
    single = ProfilingSession(_config("pallas_fused"))
    db = single.build_or_load_refdb(genomes, cache_dir=None)
    sharded = ProfilingSession(_config("sharded", base="pallas_fused",
                                       shards=4))
    sdb, t_build = _timed(
        lambda: sharded.build_or_load_refdb(genomes, cache_dir=None))
    print(f"smoke sharded build (compile included): {t_build} s", flush=True)
    _check(np.array_equal(np.asarray(sdb.prototypes)[:db.num_prototypes],
                          np.asarray(db.prototypes)),
           "sharded RefDB rows bit-identical to the single-device build")

    shards = sdb.prototypes.addressable_shards
    for sh in shards:
        print(f"shard {sh.index}: device {sh.device} | "
              f"{sh.data.nbytes} prototype bytes", flush=True)
    print(f"per-device RefDB bytes (4 shards): {per_device_bytes(db, 4)} "
          f"of {db.memory_bytes()}", flush=True)
    _check(len({sh.device.id for sh in shards}) == 4,
           "prototype shards on 4 distinct devices")

    batch = single.config.batch_size
    want = np.asarray(single.classify_batch(
        tokens[:batch], lengths[:batch]).classification.scores)
    got = np.asarray(sharded.classify_batch(
        tokens[:batch], lengths[:batch]).classification.scores)
    _check(np.array_equal(got, want),
           "sharded first-batch species scores bit-identical to one chip")

    source = ArraySource(tokens, lengths)
    want_rep, t_one = _timed(lambda: single.profile(source))
    got_rep, t_four = _timed(lambda: sharded.profile(source))
    _report_line("one-chip profile", want_rep, t_one, truth)
    _report_line("4-way sharded profile", got_rep, t_four, truth)
    _check(got_rep.to_json() == want_rep.to_json(),
           "sharded report bit-identical to one chip")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the 4-way sharded phase (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=11,
                    help="seed of the synthetic genomes and reads")
    args = ap.parse_args(argv)

    count = 4 if args.four_chip else 1
    devices = _require_devices(count)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    (four_chip if args.four_chip else one_chip)(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
