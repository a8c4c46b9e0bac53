"""The ``sharded`` backend and the shard-composable species reduction.

Acceptance contract: sharded reports are bit-identical to ``reference``
for the same config on a 1-device mesh (in-process here) AND on an 8-way
``--xla_force_host_platform_device_count`` mesh (subprocess tests below,
own process so the device count doesn't leak into other tests).
"""

import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.core import classifier
from repro.core.hd_space import HDSpace
from repro.genomics import synth
from repro.pipeline import (ProfilerConfig, ProfilingSession, SyntheticSource,
                            available_backends, pad_refdb, per_device_bytes,
                            place_refdb, resolve_backend)
from repro.pipeline.sharded import make_profile_mesh

SP = HDSpace(dim=512, ngram=5, z_threshold=3.0)
SPEC = synth.CommunitySpec(num_species=4, genome_len=6_000, seed=11)
REPO = pathlib.Path(__file__).resolve().parent.parent


def _config(**kw):
    kw.setdefault("space", SP)
    kw.setdefault("window", 1024)
    kw.setdefault("batch_size", 16)
    return ProfilerConfig(**kw)


@pytest.fixture(scope="module")
def sample():
    return SyntheticSource(SPEC, num_reads=64, present=[0, 2])


@pytest.fixture(scope="module")
def reference(sample):
    s = ProfilingSession(_config())
    s.build_refdb(sample.genomes)
    return s, s.profile(sample)


# -- 1-device-mesh parity (half of the acceptance contract) ----------------

def test_registered():
    assert "sharded" in available_backends()


@pytest.mark.parametrize("base", ["reference", "reference_packed", "pcm_sim"])
def test_report_bit_identical_on_one_device_mesh(sample, reference, base):
    ref_session, ref_report = reference
    s = ProfilingSession(_config(backend="sharded",
                                 backend_options={"base": base}))
    s.build_refdb(sample.genomes)
    assert s.profile(sample).to_json() == ref_report.to_json()


def test_agreement_protocol_surface_matches(sample, reference):
    """The Backend-protocol primitive (per-prototype counts) is exact,
    including when S doesn't divide the mesh (padding sliced off)."""
    ref_session, _ = reference
    db = ref_session.refdb
    q = ref_session.encode_reads(sample.tokens[:8], sample.lengths[:8])
    sharded = resolve_backend("sharded", _config(backend="sharded"))
    for s_take in (db.prototypes.shape[0], 7):       # even and ragged
        protos = db.prototypes[:s_take]
        np.testing.assert_array_equal(
            np.asarray(sharded.agreement(q, protos)),
            np.asarray(ref_session.backend.agreement(q, protos)))


def test_fused_species_scores_matches_tail(sample, reference):
    ref_session, _ = reference
    db = ref_session.refdb
    q = ref_session.encode_reads(sample.tokens[:8], sample.lengths[:8])
    sharded = resolve_backend("sharded", _config(backend="sharded"))
    got = np.asarray(sharded.species_scores(
        q, db.prototypes, db.proto_species, db.num_species))
    agree = ref_session.backend.agreement(q, db.prototypes)
    want = np.asarray(classifier.partial_scores(
        agree, db.proto_species, db.num_species))
    np.testing.assert_array_equal(got, want)


def test_sharded_shares_refdb_cache_with_reference(tmp_path, sample):
    """backend/backend_options are excluded from the cache key: the
    sharded backend loads the database reference built, then places it."""
    s1 = ProfilingSession(_config())
    s1.build_or_load_refdb(sample.genomes, cache_dir=tmp_path)
    s2 = ProfilingSession(_config(backend="sharded"))
    s2.build_or_load_refdb(sample.genomes, cache_dir=tmp_path)
    assert s2.refdb_loaded_from_cache
    assert len(list(tmp_path.glob("refdb_*.npz"))) == 1


# -- placement + padding ----------------------------------------------------

def test_pad_refdb_tags_padding_out_of_range(reference):
    db = reference[0].refdb
    padded = pad_refdb(db, 8)
    s = db.prototypes.shape[0]
    assert padded.prototypes.shape[0] % 8 == 0
    tail = np.asarray(padded.proto_species[s:])
    assert (tail == db.num_species).all()            # dropped by segment_max
    np.testing.assert_array_equal(np.asarray(padded.prototypes[s:]), 0)
    # idempotent once divisible
    assert pad_refdb(padded, 8) is padded


def test_place_refdb_preserves_values(reference):
    db = reference[0].refdb
    mesh = make_profile_mesh(1)
    placed = place_refdb(db, mesh)
    np.testing.assert_array_equal(np.asarray(placed.prototypes),
                                  np.asarray(db.prototypes))
    assert placed.species_names == db.species_names


def test_per_device_bytes():
    import jax.numpy as jnp
    from repro.core.assoc_memory import RefDB
    db = RefDB(prototypes=jnp.zeros((10, 16), jnp.uint32),
               proto_species=jnp.zeros(10, jnp.int32),
               genome_lengths=jnp.zeros(3, jnp.int32),
               num_species=3, species_names=("a", "b", "c"))
    assert per_device_bytes(db, 1) == db.memory_bytes()
    # 10 rows over 4 shards pads to 12 -> 3 rows/device
    assert per_device_bytes(db, 4) == 3 * 16 * 4 + 3 * 4 + 3 * 4


def test_option_validation():
    with pytest.raises(ValueError, match="base"):
        resolve_backend("sharded", _config(
            backend="sharded", backend_options={"base": "sharded"}))
    with pytest.raises(ValueError, match="shards"):
        resolve_backend("sharded", _config(
            backend="sharded", backend_options={"shards": -1}))
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("sharded", _config(
            backend="sharded", backend_options={"base": "no_such"}))
    with pytest.raises(ValueError, match="num_shards"):
        resolve_backend("sharded", _config(
            backend="sharded", backend_options={"shards": 10_000}))


# -- the associative per-shard merge (property-tested) ----------------------

def _check_merge_case(rng, num_species, n_protos, b, n_pad, cuts):
    """One instance of the property: shard-then-merge == reduce-global."""
    import jax.numpy as jnp
    ps = np.sort(rng.integers(0, num_species, n_protos)).astype(np.int32)
    agree = rng.integers(0, 513, (b, n_protos)).astype(np.int32)
    ps_p = np.concatenate([ps, np.full(n_pad, num_species, np.int32)])
    agree_p = np.concatenate(
        [agree, rng.integers(0, 513, (b, n_pad)).astype(np.int32)], axis=1)
    want = np.asarray(classifier.partial_scores(
        jnp.asarray(agree), jnp.asarray(ps), num_species))
    bounds = [0, *sorted(cuts), n_protos + n_pad]
    partials = [classifier.partial_scores(
        jnp.asarray(agree_p[:, lo:hi]), jnp.asarray(ps_p[lo:hi]), num_species)
        for lo, hi in zip(bounds[:-1], bounds[1:]) if lo < hi]
    if not partials:
        return
    got = np.asarray(classifier.merge_scores(*partials))
    np.testing.assert_array_equal(got, want)


def test_merge_property_deterministic():
    """Seeded sweep of the same property (runs even without hypothesis):
    uneven shards, empty shards, absent species, mesh-padding rows."""
    rng = np.random.default_rng(0)
    for _ in range(40):
        num_species = int(rng.integers(1, 7))
        n_protos = int(rng.integers(1, 41))
        b = int(rng.integers(1, 6))
        n_pad = int(rng.integers(0, 8))
        n_cuts = int(rng.integers(0, 5))
        cuts = rng.integers(0, n_protos + n_pad + 1, n_cuts).tolist()
        _check_merge_case(rng, num_species, n_protos, b, n_pad, cuts)


def test_merge_property_hypothesis():
    """Concatenating prototype shards then reducing == merging per-shard
    partial reductions — for uneven shard sizes and padded rows."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(deadline=None, max_examples=60)
    @given(st.data())
    def check(data):
        rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
        num_species = data.draw(st.integers(1, 6))
        n_protos = data.draw(st.integers(1, 40))
        n_pad = data.draw(st.integers(0, 7))
        cuts = data.draw(st.lists(
            st.integers(0, n_protos + n_pad), max_size=4))
        _check_merge_case(rng, num_species, n_protos,
                          data.draw(st.integers(1, 5)), n_pad, cuts)

    check()


def test_no_score_is_the_reduction_fill_and_merge_identity():
    """partial_scores fills species absent from a shard with NO_SCORE
    (what segment_max actually emits), and NO_SCORE never wins a merge —
    pinning the constant to the implementation so they cannot drift."""
    import jax.numpy as jnp
    agree = jnp.asarray([[7], [3]], jnp.int32)       # 1 prototype, species 0
    sc = np.asarray(classifier.partial_scores(
        agree, jnp.asarray([0], jnp.int32), 3))
    assert (sc[:, 1:] == classifier.NO_SCORE).all()  # absent species
    np.testing.assert_array_equal(sc[:, 0], [7, 3])
    merged = classifier.merge_scores(
        jnp.asarray(sc), jnp.full_like(jnp.asarray(sc), classifier.NO_SCORE))
    np.testing.assert_array_equal(np.asarray(merged), sc)  # identity


def test_merge_is_order_invariant():
    import jax.numpy as jnp
    a = jnp.asarray([[1, -5], [3, 2]], jnp.int32)
    b = jnp.asarray([[0, 7], [-1, 2]], jnp.int32)
    c = jnp.asarray([[2, 2], [2, 2]], jnp.int32)
    lhs = classifier.merge_scores(classifier.merge_scores(a, b), c)
    rhs = classifier.merge_scores(a, classifier.merge_scores(c, b))
    np.testing.assert_array_equal(np.asarray(lhs), np.asarray(rhs))


# -- serving over one sharded RefDB ----------------------------------------

def test_service_shares_sharded_refdb(sample, reference):
    """Many concurrent requests over one sharded, device-placed database
    come back bit-identical to sequential reference runs."""
    from repro.serve import ProfilingService
    _, ref_report = reference
    s = ProfilingSession(_config(backend="sharded"))
    s.build_refdb(sample.genomes)
    service = ProfilingService(s, max_active=4)
    handles = [service.submit((sample.tokens, sample.lengths))
               for _ in range(3)]
    service.run_until_idle()
    for h in handles:
        assert h.result(timeout=5).to_json() == ref_report.to_json()


# -- 8-way host-platform mesh (the other half of the acceptance) ------------

def _run8(snippet: str, devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run([sys.executable, "-c", snippet], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_eight_way_mesh_report_parity():
    """Reports bit-identical to reference on an 8-device mesh, for an
    S that does NOT divide the mesh (padding in play), plus cache
    build/load through the store under sharding."""
    _run8("""
import tempfile
import numpy as np
from repro.core.hd_space import HDSpace
from repro.genomics import synth
from repro.pipeline import ProfilerConfig, ProfilingSession, SyntheticSource

SP = HDSpace(dim=512, ngram=5, z_threshold=3.0)
SPEC = synth.CommunitySpec(num_species=5, genome_len=6_000, seed=11)
sample = SyntheticSource(SPEC, num_reads=64, present=[0, 2])

ref = ProfilingSession(ProfilerConfig(space=SP, window=1024, batch_size=16))
ref.build_refdb(sample.genomes)
want = ref.profile(sample).to_json()
# 5 genomes x 6 windows = 30 prototypes: not a multiple of 8 -> padded
assert ref.refdb.prototypes.shape[0] % 8 != 0

for base in ("reference", "reference_packed", "pallas_matmul"):
    for shards in (3, 8):
        cfg = ProfilerConfig(space=SP, window=1024, batch_size=16,
                             backend="sharded",
                             backend_options={"base": base, "shards": shards})
        s = ProfilingSession(cfg)
        s.build_refdb(sample.genomes)
        assert s.backend.num_shards == shards
        got = s.profile(sample).to_json()
        assert got == want, (base, shards)

with tempfile.TemporaryDirectory() as d:
    s1 = ProfilingSession(ProfilerConfig(space=SP, window=1024, batch_size=16))
    s1.build_or_load_refdb(sample.genomes, cache_dir=d)
    s2 = ProfilingSession(ProfilerConfig(space=SP, window=1024, batch_size=16,
                                         backend="sharded"))
    db = s2.build_or_load_refdb(sample.genomes, cache_dir=d)
    assert s2.refdb_loaded_from_cache
    assert db.prototypes.shape[0] % 8 == 0         # placed = padded to mesh
    assert s2.profile(sample).to_json() == want
print('8-way parity OK')
""")


def test_eight_way_mesh_actually_distributes():
    """Placement puts distinct prototype rows on distinct devices (the
    capacity claim, not just numerical parity)."""
    _run8("""
import jax
import numpy as np
from repro.core.hd_space import HDSpace
from repro.genomics import synth
from repro.pipeline import ProfilerConfig, ProfilingSession, SyntheticSource

assert len(jax.devices()) == 8
SP = HDSpace(dim=512, ngram=5, z_threshold=3.0)
SPEC = synth.CommunitySpec(num_species=4, genome_len=6_000, seed=11)
sample = SyntheticSource(SPEC, num_reads=8, present=[0, 2])
s = ProfilingSession(ProfilerConfig(space=SP, window=1024, batch_size=8,
                                    backend="sharded"))
s.build_refdb(sample.genomes)
db = s.refdb
shards = {sh.device.id for sh in db.prototypes.addressable_shards}
assert len(shards) == 8, shards
rows = db.prototypes.shape[0]
for sh in db.prototypes.addressable_shards:
    assert sh.data.shape[0] == rows // 8
print('8-way placement OK')
""")
