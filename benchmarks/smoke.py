"""CI benchmark smoke: reads/s + intermediate HBM bytes/read per backend.

``python -m benchmarks.run --smoke`` (or ``python -m benchmarks.smoke``)
profiles one tiny synthetic sample through each hot-path backend and
writes a machine-readable ``BENCH_smoke.json``:

    {"schema": 1, "jax": ..., "platform": ...,
     "config": {...}, "num_reads": ...,
     "bit_exact": true,
     "backends": {
        "pallas_fused": {"reads_per_s": ..., "us_per_read": ...,
                         "relative_throughput": ...,
                         "intermediate_bytes_per_read": 0,
                         "prototype_bytes_per_read": ...}, ...}}

``relative_throughput`` is each backend's reads/s divided by the same
run's *family anchor* (jnp backends vs ``reference``, Pallas backends vs
``pallas_matmul`` — see ``ANCHORS``).  The regression gate
(:mod:`benchmarks.check_regression`) compares THIS ratio against
``benchmarks/baseline.json``, so absolute runner speed cancels and a >20%
relative slowdown of any backend fails CI no matter the machine.  The
anchors themselves are gated by the ``bit_exact`` check plus their
family partners' ratios (an anchor can't silently regress without every
partner's ratio moving).

``intermediate_bytes_per_read`` is the analytical HBM traffic of the
query path's *intermediates* — everything between raw tokens in and
agreement scores out (see :func:`intermediate_bytes_per_read`).  It is
deterministic, so the gate allows no increase at all: the fused
megakernel's 0 bytes/read is pinned forever.  ``prototype_bytes_per_read``
does the same for the prototype stream (the query path's only remaining
HBM traffic — see :func:`prototype_bytes_per_read`): any analytic growth
of any backend's prototype traffic fails CI, pinning the fused kernel's
chunk-reuse amortization the way fusion pinned the intermediates.

The payload also carries ``observability.enabled_over_disabled``: the
``reference`` backend's throughput with the metrics layer fully enabled
over the same session with it disabled (interleaved best-of rounds).
The gate requires this ratio to stay within 2% of 1.0 — the
instrumentation's zero-cost-when-disabled contract, measured, with the
enabled mode held to the same bar.

``fleet`` routes the same sample through a 1-host and a 3-host
:class:`~repro.serve.fleet.FleetController` over one in-memory source
registry.  ``relative_aggregate`` is the 3-host aggregate reads/s over
the 1-host figure (runner speed cancels); the gate flags a drop beyond
``--fleet-tolerance`` (coordination overhead regression), and
``fleet.bit_exact`` — every fleet-routed report bit-identical to a
sequential run — failing is a hard error at any tolerance.

Refresh the baseline after an intentional perf change with:

    PYTHONPATH=src python -m benchmarks.run --smoke
    PYTHONPATH=src python -m benchmarks.check_regression --update
"""

from __future__ import annotations

import argparse
import json
import pathlib
import time

import dataclasses

import jax

from benchmarks import common
from repro import obs
from repro.core import HDSpace
from repro.pipeline import ArraySource, ProfilerConfig, ProfilingSession
from repro.serve import FleetController, RefDBRegistry

SCHEMA = 1

#: The hot-path lineup the gate tracks (pcm_sim is covered by accel-smoke;
#: sharded by shard-smoke — both are wrappers around these primitives).
BACKENDS = ("reference", "reference_packed", "pallas_matmul",
            "pallas_packed", "pallas_fused")

#: Normalization anchor per backend (its own execution family's
#: two-kernel baseline); see the comment at the normalization site.
ANCHORS = {
    "reference": "reference",
    "reference_packed": "reference",
    "pallas_matmul": "pallas_matmul",
    "pallas_packed": "pallas_matmul",
    "pallas_fused": "pallas_matmul",
}

# Small enough that interpret-mode Pallas stays in CI seconds, big enough
# that per-read timing dominates dispatch overhead.
SMOKE_SPACE = HDSpace(dim=512, ngram=8, z_threshold=3.0)
SMOKE_CONFIG = ProfilerConfig(space=SMOKE_SPACE, window=1024,
                              batch_size=64, backend="reference")


def intermediate_bytes_per_read(backend: str, space: HDSpace) -> int:
    """Analytical HBM bytes of query-path *intermediates*, per read.

    Counts only traffic the kernel organization itself creates between
    "tokens in" and "scores out" (what fusion can eliminate) — not the
    token read or score write every backend shares, and not the
    prototype stream (modelled separately by
    :func:`prototype_bytes_per_read`, since PR 9 it differs per backend):

      two-kernel ±1 matmul   packed query write+read (4B/word each) plus
                             the ±1 bf16 expansion write+read (2B/bit);
      two-kernel packed      packed query write+read only;
      pallas_fused           0 — the encoded tile never leaves VMEM.
    """
    w_bytes = space.num_words * 4
    if backend in ("reference", "pallas_matmul"):
        return 2 * w_bytes + 2 * space.dim * 2
    if backend in ("reference_packed", "pallas_packed"):
        return 2 * w_bytes
    if backend == "pallas_fused":
        return 0
    raise ValueError(f"no traffic model for backend {backend!r}")


def prototype_bytes_per_read(backend: str, space: HDSpace,
                             num_prototypes: int, batch: int) -> float:
    """Analytical HBM bytes of the *prototype stream*, per read.

    How many bytes of reference-DB prototypes the kernel organization
    pulls from HBM to score one batch, divided by the batch size — the
    traffic Acc-Demeter eliminates by keeping the AM inside the
    memristor array (PAPER.md §5), and what the fused kernel's
    chunk-axis grid amortizes in software.  Uses the backends' real
    padded shapes (what the DMA engine actually moves, not the logical
    prototype count):

      reference            ±1 bf16 expansion streamed once per batch;
      pallas_matmul        ±1 bf16 tiles, rows padded to 128, re-fetched
                           per 128-row batch tile;
      reference_packed     packed uint32, once per batch (32x packing);
      pallas_packed        packed tiles, rows padded to 128, re-fetched
                           per 8-row batch tile;
      pallas_fused         packed ``(bs, W)`` slabs fetched once per
                           chunk and reused across every batch tile —
                           once per batch total (``fused_tile_plan``).
    """
    from repro.kernels.ops import fused_tile_plan
    w_bytes = space.num_words * 4
    pad128 = -(-num_prototypes // 128) * 128
    if backend == "reference":
        return num_prototypes * space.dim * 2 / batch
    if backend == "pallas_matmul":
        return pad128 * space.dim * 2 * (-(-batch // 128)) / batch
    if backend == "reference_packed":
        return num_prototypes * w_bytes / batch
    if backend == "pallas_packed":
        return pad128 * w_bytes * (-(-batch // 8)) / batch
    if backend == "pallas_fused":
        plan = fused_tile_plan(batch, num_prototypes, space.num_words,
                               read_len=common.READ_LEN, n=space.ngram)
        return plan["proto_bytes_per_call"] / batch
    raise ValueError(f"no prototype-stream model for backend {backend!r}")


def run_smoke(out_path: str | pathlib.Path = "BENCH_smoke.json",
              num_reads: int = 256, rounds: int = 5,
              emit=common.emit) -> dict:
    """Time every backend on one shared sample; write ``out_path``."""
    community = common.make_community(
        "SMOKE", num_species=4, genome_len=12_000,
        reads_per_sample=num_reads, seed=7)
    toks, lens, *_ = community.samples["kylo"]
    source = ArraySource(toks, lens)

    sessions: dict[str, ProfilingSession] = {}
    reports: dict[str, str] = {}
    db = None
    for name in BACKENDS:
        session = ProfilingSession(
            dataclasses.replace(SMOKE_CONFIG, backend=name))
        if db is None:
            db = session.build_refdb(community.genomes)
        session.refdb = db            # bit-exact twins: one shared build
        reports[name] = session.profile(source).to_json()  # warmup+check
        sessions[name] = session

    # Timing rounds are INTERLEAVED across backends (round-robin, best
    # pass per backend): the gate compares throughput *ratios*, and with
    # per-backend timing windows any machine-speed drift between windows
    # lands straight in the ratio.  Interleaving puts every backend in
    # every window, so drift cancels and best-of-R converges per backend.
    # Fast (jnp) backends additionally repeat within each round until
    # ~0.25s has elapsed: a lone ~ms pass is granularity-and-GC noise.
    best = {name: float("inf") for name in BACKENDS}
    for _ in range(rounds):
        for name, session in sessions.items():
            spent = 0.0
            while spent < 0.25:
                secs, _ = common.timeit(lambda: session.profile(source))
                best[name] = min(best[name], secs)
                spent += secs

    results: dict[str, dict] = {}
    num_protos = int(db.prototypes.shape[0])
    for name, secs in best.items():
        us = secs / num_reads * 1e6
        results[name] = {
            "reads_per_s": num_reads / secs,
            "us_per_read": us,
            "intermediate_bytes_per_read":
                intermediate_bytes_per_read(name, SMOKE_SPACE),
            "prototype_bytes_per_read":
                prototype_bytes_per_read(name, SMOKE_SPACE, num_protos,
                                         SMOKE_CONFIG.batch_size),
        }
        emit(f"smoke.{name}.us_per_read", us,
             f"{num_reads / secs:.1f}reads/s")

    # Normalize each backend inside its own execution family: jnp
    # backends against `reference`, Pallas (interpret-mode on CPU)
    # against `pallas_matmul`.  Cross-family ratios mix two runtimes
    # that respond differently to runner load (BLAS threading vs the
    # Pallas interpreter) and are too volatile to gate at 20%;
    # within-family ratios are what a kernel regression actually moves.
    for name, r in results.items():
        anchor = ANCHORS[name]
        r["anchor"] = anchor
        r["relative_throughput"] = (r["reads_per_s"]
                                    / results[anchor]["reads_per_s"])

    observability = observability_overhead(db, source, num_reads,
                                           rounds=rounds, emit=emit)
    fleet = fleet_smoke(community, emit=emit)

    bit_exact = all(r == reports["reference"] for r in reports.values())
    payload = {
        "schema": SCHEMA,
        "jax": jax.__version__,
        "platform": jax.default_backend(),
        "config": SMOKE_CONFIG.to_dict(),
        "num_reads": num_reads,
        "bit_exact": bit_exact,
        "observability": observability,
        "fleet": fleet,
        "backends": results,
    }
    out = pathlib.Path(out_path)
    out.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    emit("smoke.bit_exact", 0.0, str(bit_exact))
    emit("smoke.json", 0.0, str(out))
    if not bit_exact:
        raise SystemExit(
            "smoke FAILED: backend reports are not bit-identical")
    return payload


def observability_overhead(db, source, num_reads: int, *, rounds: int = 5,
                           emit=common.emit) -> dict:
    """Measure the metrics layer's cost on the ``reference`` hot path.

    Two twin sessions over the same RefDB — one recording into a live
    :class:`~repro.obs.metrics.MetricsRegistry`, one with observability
    disabled — timed with the same interleaved best-of discipline as the
    backend lineup, so machine drift cancels out of the ratio.  The
    twins' reports are also compared: enabling metrics must not move a
    single bit of output.
    """
    off = ProfilingSession(SMOKE_CONFIG)
    on = ProfilingSession(SMOKE_CONFIG, metrics=obs.MetricsRegistry())
    off.refdb = on.refdb = db
    rep_off = off.profile(source).to_json()     # warmup + parity check
    rep_on = on.profile(source).to_json()
    # Strict call-by-call alternation, best-of, over independent blocks;
    # the reported ratio is the best block's.  A real >2% overhead is
    # systematic and shows in every block; a lucky low sample on one
    # side is random and doesn't repeat — so a 2% gate on the best
    # block is stable where a single-window measurement flakes.
    ratios = []
    best = {"disabled": float("inf"), "enabled": float("inf")}
    for _ in range(3):
        block = {"disabled": float("inf"), "enabled": float("inf")}
        for _ in range(rounds * 5):
            for mode, session in (("disabled", off), ("enabled", on)):
                secs, _ = common.timeit(lambda: session.profile(source))
                block[mode] = min(block[mode], secs)
        ratios.append(block["disabled"] / block["enabled"])
        for mode in best:
            best[mode] = min(best[mode], block[mode])
    ratio = max(ratios)
    emit("smoke.observability.enabled_over_disabled", 0.0, f"{ratio:.4f}")
    return {
        "reads_per_s_disabled": num_reads / best["disabled"],
        "reads_per_s_enabled": num_reads / best["enabled"],
        "enabled_over_disabled": ratio,
        "bit_exact": rep_on == rep_off,
    }


def fleet_smoke(community, *, num_requests: int = 8,
                emit=common.emit) -> dict:
    """Route the smoke sample through a 1-host and a 3-host fleet.

    One in-memory source registry, two tenants, ``num_requests`` request
    slices.  Reports the 3-host aggregate throughput relative to the
    1-host cell (coordination overhead, runner speed cancelled) and
    whether every fleet-routed report came back bit-identical to a
    sequential profile of the same slice — the determinism contract
    that makes replicated serving and failover safe.
    """
    toks, lens, *_ = community.samples["kylo"]
    sources = [ArraySource(toks[i::num_requests], lens[i::num_requests])
               for i in range(num_requests)]
    registry = RefDBRegistry(root=None)
    snap = registry.create("smoke", community.genomes, SMOKE_CONFIG)
    seq = ProfilingSession(SMOKE_CONFIG)
    seq.adopt_refdb(snap.db)
    expected = [seq.profile(s).to_json() for s in sources]

    out: dict = {"bit_exact": True}
    for hosts in (1, 3):
        fleet = FleetController(registry, hosts=hosts)
        for t in range(2):
            fleet.add_tenant(f"t{t}", "smoke", max_active=8,
                             max_queue=num_requests)
        with fleet:
            for replica in fleet.hosts():      # warmup: compile per host
                replica.router.submit(sources[0], tenant="t0").result(
                    timeout=600)
            t0 = time.perf_counter()
            handles = [fleet.submit(s, tenant=f"t{i % 2}")
                       for i, s in enumerate(sources)]
            fleet_reports = [h.result(timeout=600) for h in handles]
            wall = time.perf_counter() - t0
        fleet.close()
        out["bit_exact"] &= all(
            r.to_json() == e for r, e in zip(fleet_reports, expected))
        reads = sum(r.total_reads for r in fleet_reports)
        out[f"h{hosts}"] = {"reads_per_s": reads / max(wall, 1e-9)}
    out["relative_aggregate"] = (out["h3"]["reads_per_s"]
                                 / out["h1"]["reads_per_s"])
    emit("smoke.fleet.relative_aggregate", 0.0,
         f"{out['relative_aggregate']:.3f}")
    emit("smoke.fleet.bit_exact", 0.0, str(out["bit_exact"]))
    return out


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="BENCH_smoke.json",
                    help="where to write the benchmark JSON")
    ap.add_argument("--reads", type=int, default=256)
    ap.add_argument("--rounds", type=int, default=5,
                    help="interleaved timing rounds (best pass counts)")
    args = ap.parse_args(argv)
    print("name,us_per_call,derived")
    run_smoke(args.out, num_reads=args.reads, rounds=args.rounds)


if __name__ == "__main__":
    main()
