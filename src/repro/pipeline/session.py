"""`ProfilingSession`: the facade over the five-step Demeter pipeline.

One session binds a :class:`~repro.pipeline.config.ProfilerConfig` to a
resolved :class:`~repro.pipeline.backend.Backend` and drives the whole
pipeline::

    config = ProfilerConfig(space=HDSpace(dim=8192), window=4096,
                            backend="pallas_matmul")
    session = ProfilingSession(config)
    session.build_or_load_refdb(genomes, cache_dir="cache/")
    report = session.profile(FastqSource("sample.fastq"))

The query path streams batch-by-batch (the paper pipelines steps 3 and 4
in hardware; here host prefetch plus XLA async dispatch overlap the
encode of batch i+1 with the classification of batch i).  A per-batch
callback hook exposes the raw classifications for serving integration
(incremental responses, monitoring) without buffering the stream.
"""

from __future__ import annotations

import dataclasses
import hashlib
import pathlib
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import assoc_memory, classifier
from repro.core.assoc_memory import RefDB, RefDBBuilder
from repro.pipeline import refdb_store
from repro.pipeline.backend import Backend, resolve_backend
from repro.pipeline.config import ProfilerConfig
from repro.pipeline.report import ProfileAccumulator, ProfileReport
from repro.pipeline.source import as_source, prefetch


@dataclasses.dataclass(frozen=True)
class BatchResult:
    """What the per-batch callback sees: one classified read batch.

    ``queries`` is ``None`` when the backend fused encode into the AM
    search (``tokens_agreement`` capability): the whole point of that
    path is that the encoded ``(B, W)`` matrix is never materialized.
    """
    index: int
    queries: jax.Array | None                           # (B, W) packed
    classification: classifier.ReadClassification      # over all B rows
    num_valid: int                                      # real rows (<= B)


BatchCallback = Callable[[BatchResult], None]


class ProfilingSession:
    """Facade binding a config + backend + (optionally cached) RefDB."""

    def __init__(self, config: ProfilerConfig, *,
                 backend: Backend | None = None,
                 metrics: obs.MetricsRegistry | None = None):
        """Args:
          backend: pre-resolved backend to use instead of resolving
            ``config.backend``.  Sessions sharing one backend share its
            jit caches and any one-time state (programmed pcm_sim
            conductances, the sharded mesh) — the serving router runs one
            session per RefDB version on a single shared backend so a
            hot-swap never recompiles the query path.
          metrics: observability registry; None resolves the process
            global (:func:`repro.obs.metrics`, the no-op registry unless
            observability was enabled).  Recording is host-side only and
            never enters a jax trace — metrics cannot perturb results.
        """
        self.config = config
        self.space = config.space
        self.backend: Backend = (backend if backend is not None
                                 else resolve_backend(config.backend, config))
        self._obs = obs.resolve_metrics(metrics)
        self._m_batches = self._obs.counter(
            "session_classify_batches_total",
            "classify_batch calls per backend and dispatch path")
        self.refdb: RefDB | None = None
        self.refdb_loaded_from_cache = False
        self.refdb_cache_file: pathlib.Path | None = None
        # Only the substrate-independent tail is jitted here; the
        # backend's own primitives are already jitted per backend.
        # Calling `agreement` outside any outer trace lets stateful
        # backends (pcm_sim) amortize one-time work — programming the
        # crossbar conductances — across the whole batch stream.
        self._from_agreement = jax.jit(
            classifier.from_agreement,
            static_argnames=("num_species", "threshold_bits"))
        self._from_scores = jax.jit(
            classifier.from_scores, static_argnames=("threshold_bits",))

    # -- Step 2 ------------------------------------------------------------
    def build_refdb(self, genomes: dict[str, np.ndarray]) -> RefDB:
        """Encode the reference genomes into the AM through the backend.

        With ``config.noise_aware_refdb`` the naive build is followed by
        the margin-maximizing retraining pass of
        :mod:`repro.accel.codesign`: the prototypes are tuned on
        simulated readout through this session's own backend + options,
        so the database the device serves is the one trained against its
        non-idealities.
        """
        db = assoc_memory.build_refdb(
            genomes, self.space, window=self.config.window,
            stride=self.config.effective_stride,
            batch_size=self.config.batch_size,
            encode_fn=self.backend.encode)
        db = self._maybe_refine(db, genomes)
        self.refdb = self._place(db)
        self.refdb_loaded_from_cache = False
        return self.refdb

    def _maybe_refine(self, db: RefDB,
                      genomes: dict[str, np.ndarray]) -> RefDB:
        """Noise-aware co-design pass, when the config asks for it."""
        if not self.config.noise_aware_refdb:
            return db
        from repro.accel.codesign import noise_aware_refdb
        return noise_aware_refdb(db, genomes, self.config,
                                 iterations=self.config.noise_aware_iters)

    def adopt_refdb(self, db: RefDB) -> RefDB:
        """Make an externally built/loaded RefDB this session's database.

        Runs the backend's device-placement step, exactly like a build or
        cache load would — the serving registry hands out plain host
        databases, and every hot-swap re-places the new version here (the
        ``sharded`` backend re-pads and re-distributes it across its
        mesh).
        """
        self.refdb = self._place(db)
        self.refdb_loaded_from_cache = False
        return self.refdb

    def refdb_cache_path(self, cache_dir: str | pathlib.Path,
                         genomes: dict[str, np.ndarray]) -> pathlib.Path:
        """Cache location keyed by every input that determines RefDB
        content: the config's RefDB fingerprint (space/window/stride) plus
        an order-insensitive digest of the reference genomes themselves."""
        key = f"{self.config.refdb_fingerprint()}_{_genomes_digest(genomes)}"
        return pathlib.Path(cache_dir) / f"refdb_{key}.npz"

    def build_or_load_refdb(self, genomes: dict[str, np.ndarray], *,
                            cache_dir: str | pathlib.Path | None = None
                            ) -> RefDB:
        """Load the RefDB from the content-keyed cache, or build and cache it.

        The key covers every input that can change the built prototypes —
        space, window, stride, and the reference genomes (names + token
        content, insertion-order-insensitive) — so neither a config change
        nor a swapped reference database can silently reuse a stale cache
        entry (the paper's step-1 config check).  ``batch_size``/``backend``
        are excluded: they cannot affect the prototypes (backends are
        bit-exact twins), so tuning them reuses the cache instead of
        rebuilding.

        Entries are persisted through :mod:`repro.pipeline.refdb_store`
        (versioned npz + JSON manifest, written atomically): a truncated
        file, a legacy pickle cache from an older checkout, or a
        format-version mismatch all read as a miss and trigger a clean
        rebuild — never a crash or a silently wrong database.  The build
        itself streams genome-by-genome through
        :class:`~repro.core.assoc_memory.RefDBBuilder`.
        """
        if cache_dir is None:
            return self.build_refdb(genomes)
        cache = self.refdb_cache_path(cache_dir, genomes)
        self.refdb_cache_file = cache
        db = refdb_store.load(cache)
        if db is not None:
            self.refdb = self._place(db)
            self.refdb_loaded_from_cache = True
            return self.refdb
        builder = RefDBBuilder(
            self.space, window=self.config.window,
            stride=self.config.effective_stride,
            batch_size=self.config.batch_size,
            encode_fn=self.backend.encode)
        refine = self.config.noise_aware_refdb
        db = refdb_store.build_streaming(
            genomes, builder, path=None if refine else cache,
            refdb_fingerprint=self.config.refdb_fingerprint(),
            genomes_digest=_genomes_digest(genomes),
            config_fields=self._refdb_config_fields())
        if refine:
            # Cache the *refined* database under the noise-aware key (the
            # fingerprint already folds in backend + options + iters), so
            # a later load gets the retrained prototypes, not the naive
            # intermediate.
            db = self._maybe_refine(db, genomes)
            refdb_store.save(
                cache, db, refdb_fingerprint=self.config.refdb_fingerprint(),
                genomes_digest=_genomes_digest(genomes),
                config_fields=self._refdb_config_fields())
        self.refdb = self._place(db)
        self.refdb_loaded_from_cache = False
        return self.refdb

    def _refdb_config_fields(self) -> dict:
        """Provenance recorded in the store manifest."""
        fields = {"space": dataclasses.asdict(self.space),
                  "window": self.config.window,
                  "stride": self.config.effective_stride}
        if self.config.noise_aware_refdb:
            fields["noise_aware"] = {
                "backend": self.config.backend,
                "backend_options": list(self.config.backend_options),
                "iters": self.config.noise_aware_iters}
        return fields

    # -- Step 3 ------------------------------------------------------------
    def encode_reads(self, tokens, lengths) -> jax.Array:
        """Convert a read batch ``(B, L)`` into query HD vectors ``(B, W)``."""
        return self.backend.encode(jnp.asarray(tokens), jnp.asarray(lengths))

    # -- Step 4 ------------------------------------------------------------
    def classify_queries(self, queries: jax.Array, refdb: RefDB | None = None
                         ) -> classifier.ReadClassification:
        """AM search + threshold over pre-encoded ``(B, W)`` query vectors.

        Backends exposing the fused ``species_scores`` capability (the
        ``sharded`` backend: agreement + per-species reduction inside one
        ``shard_map``, merged with a pmax) skip the per-prototype
        agreement round-trip; everyone else routes through ``agreement``
        and the shared :func:`~repro.core.classifier.from_agreement` tail.
        Both paths are bit-identical.
        """
        db = self._require_refdb(refdb)
        fused = getattr(self.backend, "species_scores", None)
        if fused is not None:
            scores = fused(queries, db.prototypes, db.proto_species,
                           db.num_species)
            return self._from_scores(
                scores, threshold_bits=self.space.threshold_bits)
        agree = self.backend.agreement(queries, db.prototypes)
        return self._from_agreement(
            agree, db.proto_species, num_species=db.num_species,
            threshold_bits=self.space.threshold_bits)

    # -- Steps 3+4: the step-level serving primitive -----------------------
    def classify_batch(self, tokens, lengths, *, refdb: RefDB | None = None,
                       num_valid: int | None = None, index: int = 0
                       ) -> BatchResult:
        """Encode + classify one read batch: the shared hot-path step.

        This is the single place steps 3 and 4 are glued together; both
        :meth:`profile` and the serving layer
        (:class:`repro.serve.profiler_service.ProfilingService`) drive it,
        so any backend, kernel, or dispatch change lands in both paths at
        once.

        Capability dispatch (most-fused first, all bit-identical):

        1. ``tokens_species_scores`` — encode + search + species
           reduction in one backend call (``sharded`` over a fused base).
        2. ``tokens_agreement`` — fused encode->search (``pallas_fused``):
           the encoded queries never touch HBM; ``queries`` is ``None``
           on the returned :class:`BatchResult`.
        3. fallback — separate ``encode`` then :meth:`classify_queries`
           (which itself prefers a ``species_scores`` capability).

        Args:
          tokens: ``(B, L)`` int32 padded read tokens.
          lengths: ``(B,)`` int32 true read lengths (0 for padding rows).
          refdb: database to query; defaults to the session's own.
          num_valid: how many leading rows are real reads (default: all).
          index: stream position recorded on the :class:`BatchResult`.
        """
        db = self._require_refdb(refdb)
        fused_full = getattr(self.backend, "tokens_species_scores", None)
        fused = getattr(self.backend, "tokens_agreement", None)
        path = ("tokens_species_scores" if fused_full is not None
                else "tokens_agreement" if fused is not None
                else "encode_classify")
        kernel_plan = getattr(self.backend, "kernel_plan", None)
        # The gram-step count reads the lengths: only for a capture.
        plan = {} if kernel_plan is None else kernel_plan(
            *np.shape(tokens), db.prototypes.shape[0],
            lengths if obs.capturing() else None)
        # The span ends once the work is handed to the device: copies in,
        # backend call and tail launched, no result waited for.
        with obs.span("session.dispatch", path=path, **plan):
            toks, lens = jnp.asarray(tokens), jnp.asarray(lengths)
            if fused_full is not None:
                scores = fused_full(toks, lens, db.prototypes,
                                    db.proto_species, db.num_species)
                res = self._from_scores(
                    scores, threshold_bits=self.space.threshold_bits)
                q = None
            elif fused is not None:
                agree = fused(toks, lens, db.prototypes)
                res = self._from_agreement(
                    agree, db.proto_species, num_species=db.num_species,
                    threshold_bits=self.space.threshold_bits)
                q = None
            else:
                q = self.encode_reads(toks, lens)
                res = self.classify_queries(q, db)
        if self._obs.enabled:
            self._m_batches.inc(1, backend=self.config.backend, path=path)
        n = len(toks) if num_valid is None else num_valid
        return BatchResult(index=index, queries=q, classification=res,
                           num_valid=n)

    # -- Steps 3+4+5 streamed ----------------------------------------------
    def profile(self, source, *, refdb: RefDB | None = None,
                on_batch: BatchCallback | None = None,
                prefetch_depth: int = 2) -> ProfileReport:
        """Profile a sample: stream, encode, classify, estimate abundance.

        Args:
          source: a :class:`~repro.pipeline.source.ReadSource`, a
            ``(tokens, lengths)`` array pair, or an iterable of pre-batched
            pairs (legacy contract).
          refdb: database to query; defaults to the session's own.
          on_batch: optional hook called with a :class:`BatchResult` per
            batch — the serving integration point.
          prefetch_depth: host-side read-batch prefetch depth (0 disables).
        """
        db = self._require_refdb(refdb)
        acc = ProfileAccumulator(db.num_species)
        stream = prefetch(as_source(source).batches(self.config.batch_size),
                          prefetch_depth)
        for i, batch in enumerate(stream):
            res = self.classify_batch(batch.tokens, batch.lengths, refdb=db,
                                      num_valid=batch.num_valid, index=i)
            n = res.num_valid
            acc.add(np.asarray(res.classification.hits)[:n],
                    np.asarray(res.classification.category)[:n])
            if on_batch is not None:
                on_batch(res)
        return acc.finalize(np.asarray(db.genome_lengths), db.species_names)

    # ----------------------------------------------------------------------
    def _place(self, db: RefDB) -> RefDB:
        """Run the backend's device-placement step, if it has one.

        The ``sharded`` backend pads the prototype axis to its mesh and
        distributes the database across devices (one shard per device);
        single-device backends have no hook and the db passes through.
        Placement happens here — on build *and* on cache load — so every
        way a session acquires a RefDB ends device-resident the same way.
        """
        place = getattr(self.backend, "place_refdb", None)
        return db if place is None else place(db)

    def _require_refdb(self, refdb: RefDB | None) -> RefDB:
        db = refdb if refdb is not None else self.refdb
        if db is None:
            raise RuntimeError(
                "no RefDB: call build_or_load_refdb()/build_refdb() first "
                "or pass refdb= explicitly")
        return db


def _genomes_digest(genomes: dict[str, np.ndarray]) -> str:
    """Stable, order-insensitive hash of the reference content.

    Each genome hashes as its (name, tokens) pair; the per-genome digests
    are *sorted* before the final hash, so the same reference set built
    from a dict in a different insertion order hits the same cache entry.
    (The cached RefDB is self-describing — ``species_names`` records the
    species order of the build that wrote it — so a load under a
    different insertion order still reports every species correctly.)
    """
    parts = []
    for name, toks in genomes.items():
        h = hashlib.sha256(name.encode())
        h.update(b"\x00")
        h.update(np.ascontiguousarray(toks, dtype=np.int32).tobytes())
        parts.append(h.digest())
    return hashlib.sha256(b"".join(sorted(parts))).hexdigest()[:16]
