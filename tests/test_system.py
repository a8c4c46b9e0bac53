"""End-to-end system tests: the five-step Demeter pipeline on a synthetic
food sample, FASTA/FASTQ IO, and the write-once RefDB."""

import numpy as np
import pytest

from repro.core import HDSpace
from repro.eval import score_profile
from repro.genomics import alphabet, fasta, synth
from repro.pipeline import ArraySource, ProfilerConfig, ProfilingSession


def test_end_to_end_food_profile(tmp_path):
    """Build HD-RefDB -> stream reads -> classify -> abundance (all 5 steps),
    including FASTA/FASTQ round-trips of the inputs."""
    spec = synth.CommunitySpec(num_species=8, genome_len=30_000,
                               homology_fraction=0.05, seed=5)
    genomes, toks, lens, truth, true_ab = synth.make_sample(
        spec, num_reads=600, present=[1, 3, 5])

    # IO round-trip (the real pipeline reads files)
    fa = tmp_path / "ref.fasta"
    fq = tmp_path / "sample.fastq"
    fasta.write_fasta(fa, genomes)
    fasta.write_fastq(fq, toks, lens)
    genomes2 = fasta.read_fasta(fa)
    toks2, lens2 = fasta.read_fastq(fq, spec.read_len)
    assert set(genomes2) == set(genomes)
    np.testing.assert_array_equal(toks2, toks)

    dm = ProfilingSession(ProfilerConfig(
        space=HDSpace(dim=8192, ngram=16, z_threshold=5.0), window=4096,
        batch_size=128))
    db = dm.build_refdb(genomes2)
    rep = dm.profile(ArraySource(toks2, lens2), refdb=db)
    m = score_profile(rep.abundance, true_ab)
    assert m.recall == 1.0, m.row()
    assert m.precision >= 0.75, m.row()
    assert m.l1_error < 0.3, m.row()
    # absent species get (almost) nothing
    absent = [i for i in range(8) if true_ab[i] == 0]
    assert rep.abundance[absent].sum() < 0.1


def test_alphabet_roundtrip():
    seq = "ACGTACGTNNGT"
    toks = alphabet.seq_to_tokens(seq)
    assert alphabet.tokens_to_seq(toks) == seq.replace("N", "A")
    rc = alphabet.reverse_complement(alphabet.seq_to_tokens("AACG"))
    assert alphabet.tokens_to_seq(rc) == "CGTT"


def test_refdb_is_write_once():
    """RefDB is frozen (PCM write-once discipline)."""
    import dataclasses
    dm = ProfilingSession(ProfilerConfig(
        space=HDSpace(dim=512, ngram=4), window=512))
    rng = np.random.default_rng(0)
    db = dm.build_refdb({"a": rng.integers(0, 4, 2000).astype(np.int32)})
    with pytest.raises(dataclasses.FrozenInstanceError):
        db.prototypes = None
