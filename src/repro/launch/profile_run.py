"""End-to-end Demeter profiling driver (the paper's production entry point).

    python -m repro.launch.profile_run --ref ref.fasta --sample reads.fastq
    python -m repro.launch.profile_run --synthetic --backend pallas_matmul

Runs the five-step pipeline through the unified API: one
:class:`~repro.pipeline.config.ProfilerConfig` (step 1 from flags) drives
a :class:`~repro.pipeline.session.ProfilingSession` — RefDB build or load
(step 2, cached by the config's content fingerprint plus a genome digest,
so neither a changed space/window/stride nor a swapped reference FASTA
can reuse a stale database), streamed
read conversion + classification (steps 3-4), abundance (step 5).
"""

from __future__ import annotations

import argparse
import pathlib
import time

from repro.core import HDSpace
from repro.eval import score_profile
from repro.genomics import fasta, synth
from repro.launch.compile_cache import enable_compile_cache
from repro.pipeline import (ArraySource, FastqSource, ProfilerConfig,
                            ProfilingSession, ReadSource, available_backends,
                            resolve_backend)


def profile(genomes: dict, source: ReadSource | tuple, *,
            config: ProfilerConfig, cache_dir: str | None = None,
            json_path: str | None = None):
    """Build-or-load the RefDB for ``config`` and profile ``source``."""
    session = ProfilingSession(config)

    t0 = time.perf_counter()
    db = session.build_or_load_refdb(genomes, cache_dir=cache_dir)
    t_build = time.perf_counter() - t0
    if session.refdb_loaded_from_cache:
        print(f"loaded HD-RefDB from {session.refdb_cache_file}")

    t0 = time.perf_counter()
    rep = session.profile(source)
    t_query = time.perf_counter() - t0

    print(f"\nbackend {config.backend} | build {t_build:.2f}s | "
          f"query {t_query:.2f}s "
          f"({rep.total_reads / max(t_query, 1e-9):.0f} reads/s) | "
          f"AM {db.memory_bytes() / 1e6:.2f} MB "
          f"({db.num_prototypes} prototypes)")
    shards = getattr(session.backend, "num_shards", 1)
    if shards > 1:
        from repro.pipeline import per_device_bytes
        print(f"sharded {shards} ways ({session.backend.base.name} base): "
              f"{per_device_bytes(db, shards) / 1e6:.2f} MB per device")
    print(f"reads: {rep.total_reads}  unmapped: {rep.unmapped_reads}  "
          f"multi: {rep.multi_reads}")
    print("\nspecies-level abundance (step 5):")
    for name, ab in rep.top(12):
        if ab > 0.001:
            print(f"  {name:24s} {100 * ab:6.2f}%")
    if json_path is not None:
        # The same machine-readable artifact ProfilingService snapshots
        # emit: one ProfileReport JSON (round-trips via from_json).
        p = pathlib.Path(json_path)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(rep.to_json(indent=2))
        print(f"\nwrote report JSON to {p}")
    return rep


def _parse_spec(spec: str) -> tuple[str, str]:
    """Split ``KEY=VALUE`` (values stay raw; the schema types them)."""
    key, sep, raw = spec.partition("=")
    if not sep or not key:
        raise SystemExit(f"--backend-option expects KEY=VALUE, got {spec!r}")
    return key, raw


def _typed_options(ap, backend: str,
                   pairs: list[tuple[str, str]]) -> dict:
    """Coerce raw ``--backend-option`` values through ``backend``'s
    declared schema (`repro.pipeline.options`): unknown keys and values
    that don't parse as the declared kind are CLI errors naming the
    option, identical across every backend.  For a passthrough backend
    (``sharded``) unknown keys fall through to the wrapped base's schema.
    """
    from repro.pipeline.backend import options_schema
    from repro.pipeline.options import OptionError

    schema = options_schema(backend)
    base_schema = None
    if schema.passthrough:
        base = dict(pairs).get("base", "reference")
        if base in available_backends():
            base_schema = options_schema(base)
    out = {}
    for key, raw in pairs:
        use = schema
        if (schema.option(key) is None and schema.passthrough
                and base_schema is not None):
            use = base_schema
        try:
            out[key] = use.parse_cli(key, raw)
        except OptionError as e:
            ap.error(f"--backend-option: {e}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ref", help="reference FASTA")
    ap.add_argument("--sample", help="sample FASTQ")
    ap.add_argument("--synthetic", action="store_true")
    ap.add_argument("--dim", type=int, default=8192)
    ap.add_argument("--ngram", type=int, default=16)
    ap.add_argument("--z-threshold", type=float, default=5.0)
    ap.add_argument("--window", type=int, default=4096)
    ap.add_argument("--stride", type=int, default=None,
                    help="window stride (default: non-overlapping)")
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--read-len", type=int, default=150)
    ap.add_argument("--cache-dir", default=None)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the ProfileReport as JSON (the same "
                         "artifact ProfilingService snapshots emit)")
    ap.add_argument("--backend", default="reference",
                    help="execution backend, one of the registered names "
                         "(see --list-backends; Pallas backends run in "
                         "interpret mode on CPU)")
    ap.add_argument("--backend-option", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="backend-specific option, repeatable (e.g. "
                         "--backend pcm_sim --backend-option preset=pcm "
                         "--backend-option read_sigma=0.05)")
    ap.add_argument("--mesh", type=int, default=None, metavar="N",
                    help="size of the 1-D ('shard',) profiling mesh. One "
                         "shard lives on each mesh device, so this and "
                         "--shards are the same knob (given both, they "
                         "must agree); grow the host device count with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    ap.add_argument("--shards", type=int, default=None, metavar="N",
                    help="shard the RefDB prototype axis N ways: wraps the "
                         "chosen backend in the 'sharded' backend (reports "
                         "stay bit-identical; each device holds 1/N of the "
                         "database)")
    ap.add_argument("--list-backends", action="store_true",
                    help="print the registered backend names with their "
                         "declared options and exit")
    ap.add_argument("--noise-aware-refdb", action="store_true",
                    help="retrain the RefDB prototypes on simulated noisy "
                         "readout through the chosen backend (the "
                         "margin-maximizing co-design pass; joins the "
                         "RefDB cache key)")
    ap.add_argument("--noise-aware-iters", type=int, default=2,
                    help="retraining passes for --noise-aware-refdb")
    args = ap.parse_args()
    enable_compile_cache()

    if args.list_backends:
        from repro.pipeline.backend import options_schema
        for name in available_backends():
            print(name)
            schema = options_schema(name)
            for row in schema.describe():
                print(f"  {row}")
            if schema.passthrough:
                print("  (+ the wrapped base backend's options, validated "
                      "by its own schema)")
        return
    if args.backend not in available_backends():
        ap.error(f"unknown backend {args.backend!r}; available: "
                 f"{', '.join(available_backends())}")

    pairs = [_parse_spec(s) for s in args.backend_option]
    # The schema that types the values is the *effective* backend's: with
    # --shards/--mesh the options ride into 'sharded' (whose passthrough
    # forwards base-backend knobs), otherwise the named backend's own.
    wrapping = ((args.shards is not None or args.mesh is not None)
                and args.backend != "sharded")
    base_hint = ([("base", args.backend)]
                 if wrapping and "base" not in dict(pairs) else [])
    options = _typed_options(
        ap, "sharded" if wrapping else args.backend, pairs + base_hint)
    if base_hint:       # parse-time hint only; the wrap logic re-adds it
        del options["base"]
    backend = args.backend
    if args.mesh is not None and args.shards is not None \
            and args.mesh != args.shards:
        ap.error(f"--mesh {args.mesh} conflicts with --shards "
                 f"{args.shards}: the mesh holds one shard per device, "
                 f"so the two must agree (or give just one)")
    shards = args.shards if args.shards is not None else args.mesh
    if shards is not None:
        # An explicit flag must never be silently overridden by a
        # conflicting backend-option (same contract as --mesh vs --shards
        # above: disagreement is an error, not a quiet winner).
        if "shards" in options and options["shards"] != shards:
            ap.error(f"--shards {shards} conflicts with "
                     f"--backend-option shards={options['shards']}")
        if backend != "sharded":
            if "base" in options and options["base"] != backend:
                ap.error(f"--backend {backend} conflicts with "
                         f"--backend-option base={options['base']}")
            # --shards N means "this backend, N ways": the sharded backend
            # wraps it as its base, same reports, 1/N database per device.
            options = {**options, "base": backend, "shards": shards}
            backend = "sharded"
        else:
            options["shards"] = shards

    config = ProfilerConfig(
        space=HDSpace(dim=args.dim, ngram=args.ngram,
                      z_threshold=args.z_threshold),
        window=args.window, stride=args.stride,
        batch_size=args.batch_size, backend=backend,
        backend_options=options,
        noise_aware_refdb=args.noise_aware_refdb,
        noise_aware_iters=args.noise_aware_iters)
    try:                      # surface bad --backend-option values as CLI
        resolve_backend(config.backend, config)  # errors, not tracebacks
    except ValueError as e:
        ap.error(str(e))

    if args.synthetic or not args.ref:
        spec = synth.CommunitySpec(num_species=10, genome_len=60_000)
        genomes, toks, lens, truth, true_ab = synth.make_sample(
            spec, num_reads=2_000)
        rep = profile(genomes, ArraySource(toks, lens), config=config,
                      cache_dir=args.cache_dir, json_path=args.json)
        m = score_profile(rep.abundance, true_ab)
        print(f"\nvs ground truth: {m.row()}")
        return
    genomes = fasta.read_fasta(args.ref)
    profile(genomes, FastqSource(args.sample, args.read_len),
            config=config, cache_dir=args.cache_dir, json_path=args.json)


if __name__ == "__main__":
    main()
