"""The one traffic generator: requests and their reads from a seed.

It reads a configuration file (``bench/configs/<name>.json``: reference
set, read profile, sample composition) and a traffic file
(``bench/traffic/<name>.json``: loop, rate or clients, request sizes) and
makes everything a run sends.  Every seed gets the same request sizes
and arrival gaps, drawn as evenly spaced quantiles of the stated
distributions and put in one fixed order, and the same multiset of read
lengths in a seeded order, so seeds change which genomes and reads are
sent but not how much work they are or when it arrives.

Distributions (``{"dist": ...}``): ``fixed`` (``value``) and
``lognormal`` (``median``, ``sigma``, optional ``min``/``max`` clip).
Open-loop arrivals are Poisson: gaps are exponential quantiles scaled so
that the ``round(rate * seconds)`` requests fall due inside the window.
"""

from __future__ import annotations

import dataclasses
import statistics

import numpy as np

from bench import synth


@dataclasses.dataclass
class Request:
    index: int
    due_s: float | None          # offset from the window's start (open loop)
    tokens: np.ndarray           # (R, L) int8
    lengths: np.ndarray          # (R,) int32
    species: np.ndarray          # (R,) int32 true species of each read

    @property
    def reads(self) -> int:
        return len(self.lengths)


@dataclasses.dataclass
class Workload:
    genomes: np.ndarray          # (species, genome_len) int8 references
    requests: list[Request]
    loop: str                    # "open" or "closed"
    clients: int                 # closed loop: requests kept in flight


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per purpose; any integer seed."""
    return np.random.default_rng(
        np.random.SeedSequence([seed & 0xFFFFFFFFFFFFFFFF, stream]))


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of ``dist`` (midpoints), ascending."""
    if dist["dist"] == "fixed":
        return np.full(n, float(dist["value"]))
    if dist["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf((i + 0.5) / n)
                      for i in range(n)])
        v = dist["median"] * np.exp(dist["sigma"] * z)
        return np.clip(v, dist.get("min", 0), dist.get("max", np.inf))
    raise ValueError(f"unknown distribution {dist['dist']!r}")


def arrival_offsets(rate: float, seconds: float, r: np.random.Generator
                    ) -> np.ndarray:
    """Poisson arrival times in ``[0, seconds)``, the first at 0."""
    n = max(1, round(rate * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = r.permutation(gaps) * (seconds / gaps.sum())
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def make(cfg: dict, traffic: dict, seed: int, seconds: float) -> Workload:
    """Reference genomes plus every request a run of ``seconds`` sends."""
    genomes = synth.reference_genomes(rng(seed, 0), cfg["species"],
                                      cfg["genome_len"],
                                      cfg["homology_fraction"])
    strains = synth.mutate(genomes, cfg["strain_snp_rate"], rng(seed, 1))
    # The schedule (which size arrives after which gap) is one fixed draw,
    # the same for every seed: on the chip, seeded orders moved the
    # latency tail by 15-19% from seed to seed, while the seed's reads
    # moved it by a few percent.
    order = rng(0, 2)
    if traffic["loop"] == "open":
        due = arrival_offsets(traffic["rate_rps"], seconds, order)
        clients = 0
    elif traffic["loop"] == "closed":
        clients = traffic["clients"]
        due = [None] * clients
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    sizes = order.permutation(
        np.round(quantiles(traffic["request_reads"], len(due)))).astype(int)
    ab = np.asarray(cfg["sample_abundance"], np.float64)
    requests = []
    for i, (d, n) in enumerate(zip(due, sizes)):
        r = rng(seed, 3 + i)
        lengths = r.permutation(np.round(quantiles(cfg["read_length"], n))
                                ).astype(np.int32)
        present = r.choice(cfg["species"], len(ab), replace=False)
        counts = np.floor(ab * n).astype(int)
        counts[0] += n - counts.sum()
        species = r.permutation(np.repeat(present, counts)).astype(np.int32)
        tokens = synth.reads(strains, species, lengths,
                             cfg["read_error_rate"], r)
        requests.append(Request(i, d, tokens, lengths, species))
    return Workload(genomes, requests, traffic["loop"], clients)
