"""Shared benchmark substrate: the synthetic AFS-analogue community, the
profiler lineup, timing helpers, and CSV emission.

The paper evaluates on AFS20/AFS31 (20/31 animal genomes, 12 MB-14 GB) with
calibrator-sausage Illumina reads.  Offline we reproduce the *structure*:
two reference databases (AFS-S: 12 species, AFS-L: 20 species — sized for
CPU), two read samples ("kylo", "kal") with disjoint present-species sets,
strain divergence and sequencing error.  All headline comparisons
(accuracy, memory, build/query time) use the same community for every
profiler, so ratios are apples-to-apples even though absolute scale is
laptop-bound.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import numpy as np

from repro import obs
from repro.baselines import ClarkLike, Kraken2Like, MetaCacheLike
from repro.core import HDSpace
from repro.genomics import synth
from repro.pipeline import ProfilerConfig, ProfilingSession

# Demeter production HD space (paper: D=40,000; ours is 128-lane aligned).
PROD_SPACE = HDSpace(dim=40960, ngram=16, z_threshold=5.0)
# CPU-sized space used by the software benchmarks (keeps run.py < minutes).
BENCH_SPACE = HDSpace(dim=8192, ngram=16, z_threshold=5.0)

# Illumina read length of every synthetic benchmark sample.
READ_LEN = synth.CommunitySpec.read_len

# The same two setups as full profiling configs (window/batch/backend named).
PROD_CONFIG = ProfilerConfig(space=PROD_SPACE, window=8192, batch_size=4096,
                             backend="pallas_matmul")
BENCH_CONFIG = ProfilerConfig(space=BENCH_SPACE, window=4096, batch_size=256,
                              backend="reference")


@dataclasses.dataclass(frozen=True)
class BenchCommunity:
    name: str
    genomes: dict
    samples: dict          # sample name -> (tokens, lengths, truth, true_ab)

    @property
    def genome_lengths(self) -> np.ndarray:
        return np.array([len(g) for g in self.genomes.values()])


def make_community(name: str, *, num_species: int, genome_len: int,
                   reads_per_sample: int, seed: int) -> BenchCommunity:
    spec = synth.CommunitySpec(num_species=num_species, genome_len=genome_len,
                               homology_fraction=0.06, strain_snp_rate=0.002,
                               read_error_rate=0.002, seed=seed)
    genomes = synth.make_reference_genomes(spec)
    rng = np.random.default_rng(seed + 100)
    samples = {}
    for sname, present in (("kylo", list(range(0, num_species, 2))),
                           ("kal", list(range(1, num_species, 2)))):
        ab = np.zeros(num_species)
        ab[present] = rng.dirichlet(np.ones(len(present))) + 0.05
        ab = ab / ab.sum()
        toks, lens, truth = synth.sample_reads(
            genomes, ab, reads_per_sample, spec, rng)
        samples[sname] = (toks, lens, truth, ab)
    return BenchCommunity(name=name, genomes=genomes, samples=samples)


def afs_small() -> BenchCommunity:
    """AFS20-analogue sized for CPU benchmarking."""
    return make_community("AFS-S", num_species=12, genome_len=50_000,
                          reads_per_sample=2_000, seed=21)


def afs_large() -> BenchCommunity:
    """AFS31-analogue (more species, longer genomes)."""
    return make_community("AFS-L", num_species=20, genome_len=80_000,
                          reads_per_sample=2_000, seed=31)


def make_profilers(backend: str | None = None) -> dict:
    """The paper's lineup: Demeter (a ProfilingSession) vs 4 SOTA baselines."""
    config = (BENCH_CONFIG if backend is None
              else dataclasses.replace(BENCH_CONFIG, backend=backend))
    return {
        "demeter": ProfilingSession(config),
        "kraken2": Kraken2Like(k=21),
        "kraken2+bracken": Kraken2Like(k=21),   # + bracken redistribution
        "metacache": MetaCacheLike(),
        "clark": ClarkLike(k=21),
    }


def latency_percentiles_ms(latencies_s: "list[float]") -> tuple[float, float]:
    """``(p50_ms, p99_ms)`` via the serving stack's shared histogram.

    Folds per-request latencies into an
    :class:`~repro.obs.metrics.HistogramState` over the same
    ``TIME_BUCKETS_S`` the live ``serve_*`` metrics use, so benchmark
    percentiles and production-snapshot percentiles come from one
    estimator (bucketed linear interpolation) instead of two competing
    definitions of "p99".
    """
    state = obs.HistogramState(obs.TIME_BUCKETS_S)
    for s in latencies_s:
        state.observe(s)
    return state.percentile(50) * 1e3, state.percentile(99) * 1e3


def timeit(fn: Callable, *, repeats: int = 1) -> tuple[float, object]:
    """(best seconds, last result)."""
    best, out = float("inf"), None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def emit(name: str, us_per_call: float, derived: str) -> None:
    """CSV contract for benchmarks.run: name,us_per_call,derived."""
    print(f"{name},{us_per_call:.3f},{derived}", flush=True)
