"""Tile autotuner: cache round-trip, determinism (cache hits never
re-measure), VMEM feasibility filtering, and tuned-config bit-exactness
through session / sharded / ProfilingService."""

import json
import os

import pytest

from repro.core.hd_space import HDSpace
from repro.genomics import synth
from repro.kernels import autotune, ops
from repro.pipeline import ProfilerConfig, ProfilingSession, SyntheticSource

SP = HDSpace(dim=256, ngram=4, z_threshold=3.0)


def _tune(path, **kw):
    kw.setdefault("batch", 8)
    kw.setdefault("num_prototypes", 20)
    kw.setdefault("read_len", 64)
    kw.setdefault("trials", 1)
    return autotune.tune(SP, path=path, **kw)


# -- cache behaviour --------------------------------------------------------

def test_cache_round_trip(tmp_path):
    p = tmp_path / "cache.json"
    tiles, cached = _tune(p)
    assert not cached and set(tiles) == {"bb", "bw", "bs"}
    data = json.loads(p.read_text())
    key = autotune.cache_key(8, SP.num_words, 20, SP.dim)
    assert data[key]["tiles"] == tiles
    assert data[key]["swept"] >= 1


def test_same_key_reuses_without_remeasuring(tmp_path, monkeypatch):
    p = tmp_path / "cache.json"
    tiles, _ = _tune(p)

    def boom(*a, **k):
        raise AssertionError("cache hit must not re-measure")

    monkeypatch.setattr(autotune, "_time_plan", boom)
    tiles2, cached = _tune(p)
    assert cached and tiles2 == tiles


def test_force_remeasures_and_updates_cache(tmp_path):
    """Determinism lives in the cache: without --force a key never
    re-measures; with it, the sweep reruns and the cache is replaced."""
    p = tmp_path / "cache.json"
    _tune(p)
    tiles2, cached = _tune(p, force=True)
    assert not cached and set(tiles2) == {"bb", "bw", "bs"}
    key = autotune.cache_key(8, SP.num_words, 20, SP.dim)
    assert json.loads(p.read_text())[key]["tiles"] == tiles2


def test_corrupt_cache_is_an_empty_cache(tmp_path):
    p = tmp_path / "cache.json"
    p.write_text("{not json")
    assert autotune.load_cache(p) == {}
    tiles, cached = _tune(p)                  # tunes + rewrites atomically
    assert not cached and json.loads(p.read_text())


def test_env_var_overrides_cache_location(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "env.json"))
    assert autotune.cache_path() == tmp_path / "env.json"
    assert autotune.cache_path(tmp_path / "x.json") == tmp_path / "x.json"


def test_distinct_shapes_get_distinct_keys():
    keys = {autotune.cache_key(*a) for a in
            [(8, 8, 20, 256), (16, 8, 20, 256), (8, 16, 20, 512),
             (8, 8, 40, 256)]}
    assert len(keys) == 4


# -- feasibility filter -----------------------------------------------------

def test_vmem_filter_drops_oversized_plans(tmp_path):
    cost = dict(read_len=1024, n=8)
    plans = autotune.candidate_plans(64, 5000, 512, **cost)
    budget = 2 ** 20
    feasible = [p for p in plans if ops.vmem_bytes(p, **cost) <= budget]
    dropped = [p for p in plans if ops.vmem_bytes(p, **cost) > budget]
    assert dropped, "sweep must contain plans a 1 MiB budget rejects"
    assert all(ops.vmem_bytes(p, **cost) <= budget for p in feasible)


def test_degenerate_budget_still_tunes(tmp_path):
    # budget=1 rejects everything; tune falls back to the leanest plan
    tiles, cached = _tune(tmp_path / "c.json", budget=1)
    assert not cached and tiles["bs"] >= 128


# -- tuned-config parity through the pipeline -------------------------------

@pytest.fixture(scope="module")
def pipeline_setup(tmp_path_factory):
    space = HDSpace(dim=512, ngram=5, z_threshold=3.0)
    spec = synth.CommunitySpec(num_species=3, genome_len=4_000, seed=5)
    sample = SyntheticSource(spec, num_reads=24, present=[0, 1])
    cache = str(tmp_path_factory.mktemp("tuner") / "tuner.json")

    def cfg(backend, **kw):
        return ProfilerConfig(space=space, window=256, batch_size=8,
                              backend=backend, **kw)

    ref = ProfilingSession(cfg("reference"))
    ref.build_refdb(sample.genomes)
    expected = ref.profile(sample).to_json()
    return cfg, sample, cache, expected


def test_tuned_session_parity_and_cache_reuse(pipeline_setup):
    cfg, sample, cache, expected = pipeline_setup
    opts = {"autotune": True, "autotune_cache": cache}
    s = ProfilingSession(cfg("pallas_fused", backend_options=opts))
    s.build_refdb(sample.genomes)
    assert s.profile(sample).to_json() == expected
    assert os.path.exists(cache), "first profiled batch persists the sweep"
    tuned = s.backend.tiles
    # a second session reuses the cached choice (deterministic, no sweep)
    s2 = ProfilingSession(cfg("pallas_fused", backend_options=opts))
    s2.build_refdb(sample.genomes)
    assert s2.profile(sample).to_json() == expected
    assert {k: s2.backend.tiles[k] for k in ("bb", "bw", "bs")} == \
        {k: tuned[k] for k in ("bb", "bw", "bs")}


def test_tuned_sharded_parity(pipeline_setup):
    """`sharded` forwards non-own options to its base, so autotune flows
    through to the fused shards untouched."""
    cfg, sample, cache, expected = pipeline_setup
    s = ProfilingSession(cfg("sharded", backend_options={
        "base": "pallas_fused", "autotune": True, "autotune_cache": cache}))
    s.build_refdb(sample.genomes)
    assert s.profile(sample).to_json() == expected


def test_tuned_service_parity(pipeline_setup):
    from repro.serve.profiler_service import ProfilingService
    cfg, sample, cache, expected = pipeline_setup
    s = ProfilingSession(cfg("pallas_fused", backend_options={
        "autotune": True, "autotune_cache": cache}))
    s.build_refdb(sample.genomes)
    service = ProfilingService(s, max_active=2)
    h = service.submit(sample)
    service.run_until_idle()
    assert h.result(timeout=60).to_json() == expected
