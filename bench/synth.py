"""Synthetic reference genomes and sequencing reads, made from a seed.

A vectorised copy of the program's own generator
(``src/repro/genomics/synth.py``: ``make_reference_genomes``, ``mutate``,
``sample_reads``), kept here so that the yardstick does not move when the
program's copy changes.  The properties it reproduces are the ones that
make profiling hard: homologous blocks shared between related species,
strain SNPs between the sampled organism and its reference, and per-base
sequencing errors.  Tokens are ``int8`` in ``[0, 4)`` (A, C, G, T).
"""

from __future__ import annotations

import numpy as np


def reference_genomes(rng: np.random.Generator, num_species: int,
                      genome_len: int, homology_fraction: float
                      ) -> np.ndarray:
    """``(num_species, genome_len)`` int8 genomes.

    Each species after the first carries a block of ``homology_fraction``
    of its length copied from the previous species, as in the program's
    generator, so reads from that block hit two species.
    """
    genomes = rng.integers(0, 4, (num_species, genome_len), dtype=np.int8)
    h = int(genome_len * homology_fraction)
    for s in range(1, num_species):
        if h > 0:
            src, dst = rng.integers(0, genome_len - h + 1, 2)
            genomes[s, dst:dst + h] = genomes[s - 1, src:src + h]
    return genomes


def mutate(tokens: np.ndarray, rate: float, rng: np.random.Generator
           ) -> np.ndarray:
    """I.i.d. substitutions at ``rate`` per base (SNPs or read errors)."""
    out = np.array(tokens, np.int8)
    if rate <= 0:
        return out
    flat = out.reshape(-1)
    pos = np.flatnonzero(rng.random(flat.size, dtype=np.float32) < rate)
    flat[pos] = (flat[pos] + rng.integers(1, 4, pos.size, dtype=np.int8)) % 4
    return out


def reads(strains: np.ndarray, species: np.ndarray, lengths: np.ndarray,
          error_rate: float, rng: np.random.Generator) -> np.ndarray:
    """Reads drawn uniformly from ``strains[species[i]]``, with errors.

    Returns ``(R, max(lengths))`` int8 tokens, zero past each read's
    length.
    """
    width = int(lengths.max())
    genome_len = strains.shape[1]
    starts = (rng.random(len(lengths)) * (genome_len - lengths + 1)
              ).astype(np.int64) + species.astype(np.int64) * genome_len
    toks = np.take(strains.reshape(-1),
                   starts[:, None] + np.arange(width)[None, :], mode="clip")
    toks = mutate(toks, error_rate, rng)
    toks[np.arange(width)[None, :] >= lengths[:, None]] = 0
    return toks
