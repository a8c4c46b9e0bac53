"""Unified profiling API: backend registry parity, ProfilerConfig identity
and serialization, ReadSource streaming, the ProfilingSession facade, and
the legacy Demeter shim."""

import dataclasses
import pickle

import numpy as np
import pytest

from repro.core.hd_space import HDSpace
from repro.genomics import fasta, synth
from repro.pipeline import (ArraySource, FastqSource, IterableSource,
                            ProfilerConfig, ProfilingSession, SyntheticSource,
                            as_source, available_backends, prefetch,
                            resolve_backend)

SP = HDSpace(dim=512, ngram=5, z_threshold=3.0)
SPEC = synth.CommunitySpec(num_species=4, genome_len=6_000, seed=11)
BACKENDS = available_backends()


def _config(**kw):
    kw.setdefault("space", SP)
    kw.setdefault("window", 1024)
    kw.setdefault("batch_size", 16)
    return ProfilerConfig(**kw)


@pytest.fixture(scope="module")
def sample():
    return SyntheticSource(SPEC, num_reads=96, present=[0, 2])


@pytest.fixture(scope="module")
def reference_report(sample):
    s = ProfilingSession(_config())
    s.build_refdb(sample.genomes)
    return s.profile(sample)


# -- backend registry ------------------------------------------------------

def test_registry_names():
    assert {"reference", "reference_packed", "pallas_matmul",
            "pallas_packed", "pallas_fused", "pcm_sim",
            "sharded"} <= set(available_backends())


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="unknown backend"):
        ProfilingSession(_config(backend="no_such_backend"))


@pytest.mark.parametrize("name", BACKENDS)
def test_backend_parity_encode_and_agreement(name):
    """Every registered backend matches the reference bit-exactly."""
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 4, (16, 60)).astype(np.int32)
    lens = np.full(16, 60, np.int32)
    ref = resolve_backend("reference", _config())
    q_ref = np.asarray(ref.encode(toks, lens))
    protos = q_ref[:7]  # any packed (S, W) array works as prototypes
    a_ref = np.asarray(ref.agreement(q_ref, protos))
    be = resolve_backend(name, _config(backend=name))
    np.testing.assert_array_equal(np.asarray(be.encode(toks, lens)), q_ref)
    np.testing.assert_array_equal(
        np.asarray(be.agreement(q_ref, protos)), a_ref)


@pytest.mark.parametrize("name", BACKENDS)
def test_profile_report_is_backend_invariant(sample, reference_report, name):
    """Swapping the backend changes no ProfileReport field (acceptance);
    a fresh ``reference`` session reproduces its own report."""
    s = ProfilingSession(_config(backend=name))
    s.build_refdb(sample.genomes)
    rep = s.profile(sample)
    for f in dataclasses.fields(rep):
        np.testing.assert_array_equal(
            np.asarray(getattr(rep, f.name)),
            np.asarray(getattr(reference_report, f.name)), err_msg=f.name)


# -- ProfilerConfig --------------------------------------------------------

def test_config_json_roundtrip():
    cfg = _config(stride=512, backend="pallas_packed")
    back = ProfilerConfig.from_json(cfg.to_json())
    assert back == cfg
    assert hash(back) == hash(cfg)          # frozen => jit-static usable
    assert back.fingerprint() == cfg.fingerprint()


def test_fingerprint_covers_every_field():
    base = _config()
    assert _config(stride=512).fingerprint() != base.fingerprint()
    assert _config(window=2048).fingerprint() != base.fingerprint()
    assert _config(batch_size=8).fingerprint() != base.fingerprint()
    assert _config(backend="pallas_matmul").fingerprint() != base.fingerprint()
    assert _config(space=HDSpace(dim=1024, ngram=5)).fingerprint() \
        != base.fingerprint()
    # stride=None is canonically stride=window: same database, same key
    assert _config(stride=1024).fingerprint() == base.fingerprint()


def test_refdb_fingerprint_covers_content_fields_only():
    """Cache key part: content fields change it, host/backend knobs don't."""
    base = _config()
    assert _config(stride=512).refdb_fingerprint() != base.refdb_fingerprint()
    assert _config(window=2048).refdb_fingerprint() != base.refdb_fingerprint()
    assert _config(space=HDSpace(dim=1024, ngram=5)).refdb_fingerprint() \
        != base.refdb_fingerprint()
    # batch_size and backend cannot change the prototypes (bit-exact twins)
    assert _config(batch_size=8).refdb_fingerprint() == base.refdb_fingerprint()
    assert _config(backend="pallas_matmul").refdb_fingerprint() \
        == base.refdb_fingerprint()


def test_cache_reused_across_backends(tmp_path, sample):
    """Switching to a bit-exact backend must hit, not rebuild, the cache."""
    s1 = ProfilingSession(_config())
    s1.build_or_load_refdb(sample.genomes, cache_dir=tmp_path)
    s2 = ProfilingSession(_config(backend="pallas_matmul", batch_size=32))
    db = s2.build_or_load_refdb(sample.genomes, cache_dir=tmp_path)
    assert s2.refdb_loaded_from_cache
    assert len(list(tmp_path.glob("refdb_*.npz"))) == 1
    np.testing.assert_array_equal(np.asarray(db.prototypes),
                                  np.asarray(s1.refdb.prototypes))


def test_accumulator_categories_match_classifier():
    """ProfileAccumulator rebinds the category encoding (import cycle keeps
    it from importing classifier); this pins the two definitions together."""
    from repro.core import classifier
    from repro.pipeline import ProfileAccumulator
    assert (ProfileAccumulator.UNMAPPED, ProfileAccumulator.UNIQUE,
            ProfileAccumulator.MULTI) == (classifier.UNMAPPED,
                                          classifier.UNIQUE, classifier.MULTI)


def test_config_validation():
    with pytest.raises(ValueError):
        _config(window=0)
    with pytest.raises(ValueError):
        _config(stride=0)
    with pytest.raises(ValueError):
        _config(batch_size=0)
    with pytest.raises(ValueError):
        _config(backend="")


def test_stride_gets_distinct_cache_entries(tmp_path, sample):
    """The stale-cache bug: configs differing only in stride must not
    share a RefDB cache entry."""
    s1 = ProfilingSession(_config())
    s2 = ProfilingSession(_config(stride=512))
    db1 = s1.build_or_load_refdb(sample.genomes, cache_dir=tmp_path)
    db2 = s2.build_or_load_refdb(sample.genomes, cache_dir=tmp_path)
    assert s1.refdb_cache_path(tmp_path, sample.genomes) \
        != s2.refdb_cache_path(tmp_path, sample.genomes)
    assert len(list(tmp_path.glob("refdb_*.npz"))) == 2
    # overlapping stride really does build a different database
    assert db2.num_prototypes > db1.num_prototypes
    # and the second call with an equal config loads from cache, bit-exact
    s3 = ProfilingSession(_config(stride=512))
    db3 = s3.build_or_load_refdb(sample.genomes, cache_dir=tmp_path)
    assert s3.refdb_loaded_from_cache
    np.testing.assert_array_equal(np.asarray(db3.prototypes),
                                  np.asarray(db2.prototypes))


def test_cache_key_ignores_genome_insertion_order(tmp_path, sample):
    """Regression: the same reference set in a different dict order must
    hit the same cache entry (the digest used to hash in iteration
    order, so a reordered FASTA rebuilt an identical database)."""
    s1 = ProfilingSession(_config())
    s1.build_or_load_refdb(sample.genomes, cache_dir=tmp_path)
    reordered = dict(reversed(list(sample.genomes.items())))
    assert list(reordered) != list(sample.genomes)
    s2 = ProfilingSession(_config())
    assert s2.refdb_cache_path(tmp_path, reordered) \
        == s1.refdb_cache_path(tmp_path, sample.genomes)
    db = s2.build_or_load_refdb(reordered, cache_dir=tmp_path)
    assert s2.refdb_loaded_from_cache
    assert len(list(tmp_path.glob("refdb_*.npz"))) == 1
    # the cached entry is self-describing: species order is the original
    # build's, recorded in species_names, so reports stay name-correct
    assert db.species_names == tuple(sample.genomes.keys())
    np.testing.assert_array_equal(np.asarray(db.prototypes),
                                  np.asarray(s1.refdb.prototypes))


def test_cache_key_covers_genome_content(tmp_path, sample):
    """Same config + different reference genomes must not share a cache
    entry (the config fingerprint alone cannot see the genomes)."""
    s = ProfilingSession(_config())
    s.build_or_load_refdb(sample.genomes, cache_dir=tmp_path)
    other = {k: v.copy() for k, v in sample.genomes.items()}
    next(iter(other.values()))[0] += 1  # one mutated base
    assert s.refdb_cache_path(tmp_path, sample.genomes) \
        != s.refdb_cache_path(tmp_path, other)
    s2 = ProfilingSession(_config())
    s2.build_or_load_refdb(other, cache_dir=tmp_path)
    assert not s2.refdb_loaded_from_cache
    assert len(list(tmp_path.glob("refdb_*.npz"))) == 2


# -- ReadSource ------------------------------------------------------------

def test_array_source_pads_tail():
    toks = np.arange(10 * 4, dtype=np.int32).reshape(10, 4)
    lens = np.full(10, 4, np.int32)
    batches = list(ArraySource(toks, lens).batches(4))
    assert [b.num_valid for b in batches] == [4, 4, 2]
    assert all(b.tokens.shape == (4, 4) for b in batches)
    assert batches[-1].lengths[2:].sum() == 0
    np.testing.assert_array_equal(
        np.concatenate([b.tokens[:b.num_valid] for b in batches]), toks)


def test_fastq_source_streams_file(tmp_path, sample):
    path = tmp_path / "reads.fastq"
    fasta.write_fastq(path, sample.tokens, sample.lengths)
    got = list(FastqSource(path, SPEC.read_len).batches(20))
    want = list(ArraySource(sample.tokens, sample.lengths).batches(20))
    assert [b.num_valid for b in got] == [b.num_valid for b in want]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.tokens, w.tokens)
        np.testing.assert_array_equal(g.lengths, w.lengths)


def test_as_source_coercions(sample):
    assert as_source(sample) is sample
    toks, lens = sample.tokens, sample.lengths
    assert isinstance(as_source((toks, lens)), ArraySource)
    it = as_source(iter([(toks[:8], lens[:8])]))
    assert isinstance(it, IterableSource)
    (batch,) = list(it.batches(999))
    assert batch.num_valid == 8          # pre-batched: size passes through
    with pytest.raises(TypeError):
        as_source(42)


def test_as_source_accepts_jax_and_list_pairs(sample):
    import jax.numpy as jnp
    toks, lens = sample.tokens[:8], sample.lengths[:8]
    src = as_source((jnp.asarray(toks), jnp.asarray(lens)))
    assert isinstance(src, ArraySource)
    np.testing.assert_array_equal(src.tokens, toks)
    src2 = as_source((toks.tolist(), lens.tolist()))
    assert isinstance(src2, ArraySource)
    with pytest.raises(TypeError, match="pre-batched"):
        as_source((toks, toks))          # (R, L) lengths: not a valid pair


def test_prefetch_preserves_order_and_errors():
    assert list(prefetch(iter(range(50)), depth=4)) == list(range(50))
    assert list(prefetch(iter(range(5)), depth=0)) == list(range(5))

    def boom():
        yield 1
        raise RuntimeError("producer failed")

    out = prefetch(boom(), depth=2)
    assert next(out) == 1
    with pytest.raises(RuntimeError, match="producer failed"):
        list(out)


def test_prefetch_releases_producer_when_abandoned():
    """Abandoning the stream mid-profile must not leave the producer
    thread blocked on the full queue (or its file handle open)."""
    import threading
    import time

    closed = []

    def endless():
        try:
            i = 0
            while True:
                yield i
                i += 1
        finally:
            closed.append(True)

    before = threading.active_count()
    out = prefetch(endless(), depth=1)
    assert next(out) == 0
    out.close()                          # consumer walks away
    deadline = time.monotonic() + 5.0
    while (threading.active_count() > before or not closed) \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= before
    assert closed == [True]              # source iterator was closed too


# -- ReadSource edge cases (the serving layer hits these) ------------------

def test_empty_fastq_yields_zero_read_report(tmp_path):
    """An empty sample file is a valid (empty) profiling request."""
    path = tmp_path / "empty.fastq"
    path.write_text("")
    genomes = synth.make_reference_genomes(SPEC)
    s = ProfilingSession(_config())
    s.build_refdb(genomes)
    rep = s.profile(FastqSource(path, SPEC.read_len))
    assert rep.total_reads == rep.unmapped_reads == rep.multi_reads == 0
    assert float(np.sum(rep.abundance)) == 0.0
    assert len(rep.top(3)) == 3                  # still well-formed


def test_fastq_trailing_blank_lines_add_no_phantom_reads(tmp_path, sample):
    """A trailing newline must not parse as a zero-length read."""
    path = tmp_path / "trail.fastq"
    fasta.write_fastq(path, sample.tokens[:5], sample.lengths[:5])
    with open(path, "a") as f:
        f.write("\n\n")
    toks, lens = fasta.read_fastq(path, SPEC.read_len)
    assert len(toks) == 5
    batches = list(FastqSource(path, SPEC.read_len).batches(4))
    assert sum(b.num_valid for b in batches) == 5


def test_final_partial_batch_profiles_cleanly(sample):
    """A read count not divisible by batch_size pads, never crashes, and
    padding rows never leak into the report."""
    s = ProfilingSession(_config())             # batch_size=16
    s.build_refdb(sample.genomes)
    n = 21                                      # 16 + 5-row partial tail
    rep = s.profile(ArraySource(sample.tokens[:n], sample.lengths[:n]))
    assert rep.total_reads == n
    full = s.profile(sample)
    assert full.total_reads == 96


# -- ProfileReport serialization -------------------------------------------

def test_profile_report_json_roundtrip(sample):
    s = ProfilingSession(_config())
    s.build_refdb(sample.genomes)
    rep = s.profile(sample)
    back = type(rep).from_json(rep.to_json(indent=2))
    for f in dataclasses.fields(rep):
        np.testing.assert_array_equal(np.asarray(getattr(back, f.name)),
                                      np.asarray(getattr(rep, f.name)),
                                      err_msg=f.name)
    assert back.species_names == rep.species_names
    assert back.to_json() == rep.to_json()


# -- ProfilingSession ------------------------------------------------------

@pytest.mark.parametrize("name", BACKENDS)
def test_classify_batch_matches_profile_on_every_backend(sample, name):
    """The step-level primitive IS the profile() hot path: driving it by
    hand reproduces profile()'s accumulator inputs bit-exactly, for every
    registered backend."""
    from repro.pipeline import ProfileAccumulator
    s = ProfilingSession(_config(backend=name))
    db = s.build_refdb(sample.genomes)
    acc = ProfileAccumulator(db.num_species)
    for i, b in enumerate(sample.batches(s.config.batch_size)):
        res = s.classify_batch(b.tokens, b.lengths,
                               num_valid=b.num_valid, index=i)
        assert res.index == i and res.num_valid == b.num_valid
        n = res.num_valid
        acc.add(np.asarray(res.classification.hits)[:n],
                np.asarray(res.classification.category)[:n])
    manual = acc.finalize(np.asarray(db.genome_lengths), db.species_names)
    assert manual.to_json() == s.profile(sample).to_json()


def test_session_requires_refdb(sample):
    s = ProfilingSession(_config())
    with pytest.raises(RuntimeError, match="no RefDB"):
        s.profile(sample)


def test_on_batch_callback_sees_every_batch(sample):
    s = ProfilingSession(_config())
    s.build_refdb(sample.genomes)
    seen = []
    rep = s.profile(sample, on_batch=lambda b: seen.append(b))
    assert [b.index for b in seen] == list(range(6))
    assert [b.num_valid for b in seen] == [16] * 6
    assert seen[0].queries.shape[0] == 16
    assert sum(b.num_valid for b in seen) == rep.total_reads == 96


def test_refdb_pickle_roundtrip_queries_identically(sample, tmp_path):
    s = ProfilingSession(_config())
    db = s.build_refdb(sample.genomes)
    db2 = pickle.loads(pickle.dumps(db))
    r1 = s.profile(sample, refdb=db)
    r2 = s.profile(sample, refdb=db2)
    np.testing.assert_array_equal(r1.abundance, r2.abundance)


# -- legacy shim (retired) ---------------------------------------------------

def test_retired_demeter_shim_raises_with_migration_pointer():
    from repro.core import Demeter, batch_reads
    with pytest.raises(RuntimeError, match="ProfilingSession"):
        Demeter(SP, window=1024, batch_size=16)
    with pytest.raises(RuntimeError, match="ReadSource"):
        batch_reads(np.zeros((4, 8), np.int32), np.full(4, 8, np.int32), 2)
    # the old import path for reports still resolves to the real class
    from repro.core.profiler import ProfileReport
    from repro.pipeline.report import ProfileReport as Canonical
    assert ProfileReport is Canonical
