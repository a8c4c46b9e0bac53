"""`pallas_fused` megakernel: bit-exactness against `reference` on every
entry point (kernel, backend, session, sharded wrapping, ProfilingService
interleaving), odd-shape coverage, and the friendly tile-size validation."""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import assoc_memory, encoder, item_memory
from repro.core.hd_space import HDSpace
from repro.genomics import synth
from repro.kernels import ops
from repro.pipeline import (ArraySource, ProfilerConfig, ProfilingSession,
                            SyntheticSource, available_backends,
                            resolve_backend)

SP = HDSpace(dim=512, ngram=5, z_threshold=3.0)
SPEC = synth.CommunitySpec(num_species=4, genome_len=6_000, seed=11)


def _config(**kw):
    kw.setdefault("space", SP)
    kw.setdefault("window", 1024)
    kw.setdefault("batch_size", 16)
    kw.setdefault("backend", "pallas_fused")
    return ProfilerConfig(**kw)


@pytest.fixture(scope="module")
def sample():
    return SyntheticSource(SPEC, num_reads=64, present=[0, 2])


def _reference_agreement(space, toks, lens, protos):
    import jax.numpy as jnp
    im = item_memory.make_item_memory(space)
    tie = item_memory.make_tie_break(space)
    q = encoder.encode(jnp.asarray(toks), jnp.asarray(lens), im, tie, space)
    return np.asarray(assoc_memory.agreement_matmul(
        q, jnp.asarray(protos), space.dim))


def _fused_agreement(space, toks, lens, protos, **tiles):
    import jax.numpy as jnp
    im = item_memory.make_item_memory(space)
    tie = item_memory.make_tie_break(space)
    return np.asarray(ops.fused_agreement(
        jnp.asarray(toks), jnp.asarray(lens), im, tie,
        jnp.asarray(protos), space, **tiles))


# -- kernel-level parity on odd shapes --------------------------------------

@pytest.mark.parametrize("dim,ngram,b,length,s,tiles", [
    (512, 5, 16, 60, 7, {}),                      # plain
    (1056, 8, 4, 50, 5, {"bw": 8}),               # W=33: dim not a multiple
                                                  # of the word tile
    (512, 8, 1, 40, 3, {}),                       # batch of 1
    (512, 8, 5, 6, 9, {}),                        # reads shorter than ngram
    (2048, 16, 12, 150, 300, {"bs": 128}),        # prototype-axis chunking
    (512, 5, 16, 60, 7, {"bb": 4, "bw": 4}),      # tiny tiles
    (512, 5, 8, 40, 387, {"bs": 128}),            # odd S, multi-chunk grid:
                                                  # S % bs != 0, pad-once
    (512, 5, 8, 40, 129, {"bs": 256}),            # bs re-balanced below ask
    (1056, 8, 4, 50, 260, {"bw": 8, "bs": 128}),  # odd S x odd word tile
])
def test_fused_kernel_matches_reference(dim, ngram, b, length, s, tiles):
    space = HDSpace(dim=dim, ngram=ngram, z_threshold=3.0)
    rng = np.random.default_rng(7)
    toks = rng.integers(0, 4, (b, length)).astype(np.int32)
    lens = rng.integers(0, length + 1, b).astype(np.int32)
    protos = np.asarray(item_memory.make_item_memory(space))  # any packed
    protos = np.tile(protos, (s // len(protos) + 1, 1))[:s]
    np.testing.assert_array_equal(
        _fused_agreement(space, toks, lens, protos, **tiles),
        _reference_agreement(space, toks, lens, protos))


def test_fused_double_buffer_path_matches_reference():
    """The manual-DMA double-buffered prototype stream is bit-exact too
    (interpret mode executes the async copies synchronously)."""
    space = HDSpace(dim=512, ngram=5, z_threshold=3.0)
    rng = np.random.default_rng(3)
    toks = rng.integers(0, 4, (8, 50)).astype(np.int32)
    lens = rng.integers(0, 51, 8).astype(np.int32)
    protos = np.asarray(item_memory.make_item_memory(space))
    protos = np.tile(protos, (80, 1))[:300]
    np.testing.assert_array_equal(
        _fused_agreement(space, toks, lens, protos, bs=128,
                         double_buffer=True),
        _reference_agreement(space, toks, lens, protos))


def test_fused_tile_plan_pads_once():
    """Regression for the old per-chunk 128-row pad: an odd S is padded
    once to the chunk grid, wasting less than one chunk in total."""
    plan = ops.fused_tile_plan(16, 387, 16, read_len=40, n=5, bs=129)
    assert plan["bs"] % 128 == 0
    assert plan["s_pad"] == plan["n_chunks"] * plan["bs"]
    assert plan["s_pad"] - 387 < plan["bs"]
    # tiny bs requests are clamped, not allowed to explode the pad
    plan = ops.fused_tile_plan(16, 300, 16, read_len=40, n=5, bs=8)
    assert plan["bs"] >= 128 and plan["s_pad"] - 300 < plan["bs"]


# -- encoded-batch cache (calls of two chunks or more) ----------------------

@pytest.mark.parametrize("double_buffer", [False, True],
                         ids=["pipeline", "dma"])
@pytest.mark.parametrize("dim,ngram,b,length,s,tiles,chunks", [
    (512, 5, 16, 60, 256, {"bs": 128}, 2),             # two chunks
    (512, 5, 24, 40, 387, {"bs": 128, "bw": 8}, 4),    # odd S, 3 batch x
                                                       # 2 word tiles
    (1056, 8, 16, 50, 520, {"bs": 128, "bw": 8}, 5),   # W=33, odd word tile
    (512, 5, 16, 60, 100, {}, 1),                      # one chunk: no cache
])
def test_fused_encode_cache_matches_reference(dim, ngram, b, length, s,
                                              tiles, chunks, double_buffer):
    """Chunks after the first search with the tiles chunk 0 encoded;
    rows with no valid gram (length 0, or under the n-gram) included."""
    space = HDSpace(dim=dim, ngram=ngram, z_threshold=3.0)
    plan = ops.fused_tile_plan(b, s, space.num_words, read_len=length,
                               n=ngram, **tiles)
    assert plan["n_chunks"] == chunks and plan["encodes"] == 1
    rng = np.random.default_rng(chunks)
    toks = rng.integers(0, 4, (b, length)).astype(np.int32)
    lens = rng.integers(0, length + 1, b).astype(np.int32)
    lens[:3] = [0, 1, ngram - 1]                       # zero valid grams
    protos = rng.integers(0, 2 ** 32, (s, space.num_words), dtype=np.uint32)
    np.testing.assert_array_equal(
        _fused_agreement(space, toks, lens, protos,
                         double_buffer=double_buffer, **tiles),
        _reference_agreement(space, toks, lens, protos))


def _tile_lengths(mix, ngram, length, rng):
    """Lengths of four 8-row batch tiles, by mix."""
    def live(k=8):
        return rng.integers(ngram, length + 1, k)

    pad = np.zeros(8, int)
    short = rng.integers(1, ngram, 8)                    # under the n-gram
    if mix == "padding_around_live":      # padding before and after live
        tiles = [pad, live(), pad, np.r_[live(5), 0, 0, 0]]
    elif mix == "under_ngram":            # live rows with no valid gram
        tiles = [short, np.r_[short[:4], 0, 0, 0, 0], live(), pad]
    elif mix == "one_long":               # one long read among short ones
        tiles = [np.r_[rng.integers(ngram, ngram + 4, 7), length]
                 for _ in range(4)]
    else:                                 # every row at the full length
        tiles = [np.full(8, length)] * 4
    return np.concatenate(tiles).astype(np.int32)


@pytest.mark.parametrize("double_buffer", [False, True],
                         ids=["pipeline", "dma"])
@pytest.mark.parametrize("s,tiles", [(100, {}), (256, {"bs": 128})],
                         ids=["one_chunk", "cached"])
@pytest.mark.parametrize("mix", ["padding_around_live", "under_ngram",
                                 "one_long", "full"])
def test_fused_length_bounded_tiles_match_reference(mix, s, tiles,
                                                    double_buffer):
    """Each batch tile runs its longest read's grams, and a tile with
    none takes the tie-break row: bit-exact on every row, padding rows
    included, on a one-chunk call and a cached multi-chunk call."""
    space = HDSpace(dim=512, ngram=5, z_threshold=3.0)
    rng = np.random.default_rng(17)
    lens = _tile_lengths(mix, 5, 60, rng)
    toks = rng.integers(0, 4, (32, 60)).astype(np.int32)
    protos = rng.integers(0, 2 ** 32, (s, space.num_words), dtype=np.uint32)
    np.testing.assert_array_equal(
        _fused_agreement(space, toks, lens, protos,
                         double_buffer=double_buffer, **tiles),
        _reference_agreement(space, toks, lens, protos))


def test_tile_trips_and_gram_steps():
    """A tile's trip count is ``max(0, max(len) - n + 1)``, on the device
    as on the host, and the host's gram steps sum ``bb`` rows of it."""
    import jax.numpy as jnp

    from repro.kernels import fused_profile

    n, length, bb = 5, 60, 8
    rng = np.random.default_rng(3)
    lens = np.concatenate([_tile_lengths(mix, n, length, rng) for mix in (
        "padding_around_live", "under_ngram", "one_long", "full")])
    want = [max(0, int(lens[t:t + bb].max()) - n + 1)
            for t in range(0, len(lens), bb)]
    assert want[:4] == [0, want[1], 0, want[3]] and want[4:6] == [0, 0]
    assert want[-4:] == [length - n + 1] * 4
    g = length - n + 1
    np.testing.assert_array_equal(
        fused_profile.tile_trips(lens, bb=bb, n=n, g=g), want)
    np.testing.assert_array_equal(fused_profile.tile_trips(
        jnp.asarray(lens)[:, None], bb=bb, n=n, g=g), want)
    plan = ops.fused_tile_plan(len(lens) - 3, 100, 16, read_len=length, n=n)
    assert plan["b_pad"] == len(lens)
    # The last three rows (full length) are left off: the padded
    # batch's last tile still runs the full count for its five rows.
    assert ops.gram_steps(lens[:-3], plan, read_len=length, n=n) == {
        "gram_steps": bb * sum(want), "gram_steps_shape": len(lens) * g}


def test_fused_tile_plan_sizes_the_encode_cache():
    """The cache holds the padded batch's encoded words when the call has
    two chunks or more, and nothing with one; the tile buffers (and so
    ``bs`` and the chunk count) are what they were without it."""
    w, n, read_len = 1280, 16, 256                    # D=40960, AFS20 short
    cost = dict(read_len=read_len, n=n)
    for b, s, chunks in [(4096, 1480, 2), (256, 1480, 2), (4093, 700, 1)]:
        plan = ops.fused_tile_plan(b, s, w, **cost)
        assert plan["n_chunks"] == chunks
        assert plan["bs"] == 768
        tiles = ops.vmem_bytes(plan, **cost)
        assert tiles <= ops.VMEM_BUDGET
        assert plan["n_calls"] == 1 and plan["b_call"] == plan["b_pad"]
        assert plan["encodes"] == 1
        if chunks == 1:
            assert plan["cache_bytes"] == 0
            assert plan["vmem_limit_bytes"] is None
        else:
            assert plan["cache_bytes"] == plan["b_pad"] * plan["w_pad"] * 4
    short = ops.fused_tile_plan(4096, 1480, w, **cost)
    held = ops.vmem_bytes(short, **cost) + short["cache_bytes"]
    assert held > ops.VMEM_BUDGET                     # 20 MiB of cache
    assert held + ops.VMEM_HEADROOM <= short["vmem_limit_bytes"] \
        <= ops.vmem_capacity() - ops.VMEM_HEADROOM
    ont = ops.fused_tile_plan(256, 1480, w, read_len=4096, n=n)
    assert ops.vmem_bytes(ont, read_len=4096, n=n) + ont["cache_bytes"] \
        <= ops.VMEM_BUDGET and ont["vmem_limit_bytes"] is None


def test_fused_batch_split_when_cache_exceeds_vmem(monkeypatch):
    """A batch whose cache cannot fit the chip's VMEM runs as several
    kernel calls, each streaming the prototypes once: bit-exact."""
    space = HDSpace(dim=512, ngram=5, z_threshold=3.0)
    b, length, s = 40, 44, 260
    tiles = {"bs": 128}
    whole = ops.fused_tile_plan(b, s, space.num_words, read_len=length,
                                n=5, **tiles)
    assert whole["n_calls"] == 1 and whole["n_chunks"] == 3
    # room for 16 rows of cache beside the tile buffers
    room = (ops.vmem_bytes(whole, read_len=length, n=5)
            + 2 * ops.VMEM_HEADROOM + 16 * whole["w_pad"] * 4)
    monkeypatch.setattr(ops, "vmem_capacity", lambda: room)
    plan = ops.fused_tile_plan(b, s, space.num_words, read_len=length,
                               n=5, **tiles)
    assert (plan["n_calls"], plan["b_call"], plan["b_pad"]) == (3, 16, 48)
    assert plan["cache_bytes"] == 16 * plan["w_pad"] * 4
    rng = np.random.default_rng(5)
    toks = rng.integers(0, 4, (b, length)).astype(np.int32)
    lens = rng.integers(0, length + 1, b).astype(np.int32)
    protos = rng.integers(0, 2 ** 32, (s, space.num_words), dtype=np.uint32)
    ops.fused_agreement.clear_cache()        # trace under the small VMEM
    try:
        got = _fused_agreement(space, toks, lens, protos, **tiles)
    finally:
        ops.fused_agreement.clear_cache()
    np.testing.assert_array_equal(
        got, _reference_agreement(space, toks, lens, protos))


def test_dispatch_span_names_chunks_and_encodes(monkeypatch, sample):
    """``session.dispatch`` carries the kernel plan of the call: one
    encode per read, and, while a capture runs, the gram steps its tiles
    run against the padded shape's, on one chunk and on several; under
    ``sharded``, one shard's."""
    import jax.numpy as jnp

    from repro import obs

    seen = []
    real_span = obs.span

    def recording_span(name, **args):
        seen.append((name, args))
        return real_span(name, **args)

    monkeypatch.setattr(obs, "span", recording_span)
    # Tile 0: eight 100-bp reads, 96 grams each; tile 1: padding.
    lens = np.r_[[100] * 8, [0] * 8].astype(np.int32)
    steps = {"gram_steps": 8 * 96, "gram_steps_shape": 16 * (150 - 4)}
    for window, bs, chunks in [(1024, 4096, 1), (128, 128, 2)]:
        s = ProfilingSession(_config(window=window,
                                     backend_options={"bs": bs}))
        s.build_refdb(sample.genomes)
        for capture in (False, True):
            monkeypatch.setattr(obs, "capturing", lambda c=capture: c)
            seen.clear()
            s.classify_batch(sample.tokens[:16], lens)
            (args,) = [a for n, a in seen if n == "session.dispatch"]
            # With no capture the span records nothing: no steps counted.
            assert args == {"path": "tokens_agreement", "chunks": chunks,
                            "encodes": 1, **(steps if capture else {})}
        num_prototypes = s.refdb.prototypes.shape[0]
        assert s.backend.kernel_plan(
            16, sample.tokens.shape[1],
            num_prototypes) == {"chunks": chunks, "encodes": 1}
        # Lengths still on the device are not waited for.
        assert s.backend.kernel_plan(
            16, sample.tokens.shape[1], num_prototypes,
            jnp.asarray(lens)) == {"chunks": chunks, "encodes": 1}
    # sharded over the fused kernel: the plan of one shard's slice, which
    # encodes the whole batch
    sharded = ProfilingSession(_config(
        backend="sharded", backend_options={"base": "pallas_fused",
                                            "bs": 128})).backend
    per_shard = 129 * sharded.num_shards                # 2 chunks a shard
    lens = np.r_[[40] * 3, [0] * 13].astype(np.int32)
    assert sharded.kernel_plan(16, 40, per_shard) \
        == sharded.base.kernel_plan(16, 40, 129) == {"chunks": 2,
                                                     "encodes": 1}
    assert sharded.kernel_plan(16, 40, per_shard, lens) \
        == sharded.base.kernel_plan(16, 40, 129, lens) == {
            "chunks": 2, "encodes": 1, "gram_steps": 8 * 36,
            "gram_steps_shape": 16 * 36}
    unfused = ProfilingSession(_config(
        backend="sharded", backend_options={"base": "reference"})).backend
    assert getattr(unfused, "kernel_plan", None) is None


# -- backend + session ------------------------------------------------------

def test_fused_backend_registered():
    assert "pallas_fused" in available_backends()


def test_fused_profile_matches_reference(sample):
    ref = ProfilingSession(_config(backend="reference"))
    ref.build_refdb(sample.genomes)
    fused = ProfilingSession(_config())
    fused.build_refdb(sample.genomes)
    assert fused.profile(sample).to_json() == ref.profile(sample).to_json()


def test_fused_batchresult_has_no_queries(sample):
    """The fusion's whole point: the encoded matrix is never materialized,
    so the per-batch callback sees ``queries=None``."""
    s = ProfilingSession(_config())
    s.build_refdb(sample.genomes)
    seen = []
    s.profile(sample, on_batch=seen.append)
    assert seen and all(b.queries is None for b in seen)
    assert sum(b.num_valid for b in seen) == 64


def test_fused_partial_tail_batch(sample):
    """A read count not divisible by batch_size (nor the batch tile)."""
    ref = ProfilingSession(_config(backend="reference"))
    ref.build_refdb(sample.genomes)
    s = ProfilingSession(_config())
    s.build_refdb(sample.genomes)
    src = ArraySource(sample.tokens[:21], sample.lengths[:21])
    assert s.profile(src).to_json() == ref.profile(src).to_json()


def test_fused_tile_options_through_config(sample):
    """Non-default tiles change nothing but the schedule."""
    ref = ProfilingSession(_config(backend="reference"))
    ref.build_refdb(sample.genomes)
    s = ProfilingSession(_config(backend_options={"bb": 4, "bw": 4,
                                                  "bs": 128}))
    s.build_refdb(sample.genomes)
    assert s.profile(sample).to_json() == ref.profile(sample).to_json()


# -- sharded wrapping -------------------------------------------------------

def test_fused_under_sharded_wrapping(sample):
    ref = ProfilingSession(_config(backend="reference"))
    ref.build_refdb(sample.genomes)
    s = ProfilingSession(_config(backend="sharded",
                                 backend_options={"base": "pallas_fused"}))
    be = s.backend
    assert getattr(be, "tokens_agreement", None) is not None
    assert getattr(be, "tokens_species_scores", None) is not None
    s.build_refdb(sample.genomes)
    assert s.profile(sample).to_json() == ref.profile(sample).to_json()


def test_sharded_over_unfused_base_exposes_no_tokens_capability():
    s = ProfilingSession(_config(backend="sharded",
                                 backend_options={"base": "reference"}))
    assert getattr(s.backend, "tokens_agreement", None) is None
    assert getattr(s.backend, "tokens_species_scores", None) is None


# -- ProfilingService interleaving ------------------------------------------

def test_fused_through_profiling_service(sample):
    """Two interleaved requests over the fused backend produce reports
    bit-identical to sequential ``session.profile`` runs."""
    from repro.serve.profiler_service import ProfilingService

    s = ProfilingSession(_config(batch_size=8))
    s.build_refdb(sample.genomes)
    a = ArraySource(sample.tokens[:40], sample.lengths[:40])
    b = ArraySource(sample.tokens[40:], sample.lengths[40:])
    service = ProfilingService(s, max_active=2)
    ha, hb = service.submit(a), service.submit(b)
    service.run_until_idle()
    assert ha.result(timeout=60).to_json() == s.profile(a).to_json()
    assert hb.result(timeout=60).to_json() == s.profile(b).to_json()


# -- option validation (bugfix satellite) -----------------------------------

@pytest.mark.parametrize("options,match", [
    ({"bb": 3}, "power of two"),
    ({"bb": 0}, "positive int"),
    ({"bw": -1}, "positive int"),
    ({"bs": 0}, "positive int"),
    ({"bb": True}, "must be an integer"),
    ({"bw": "wide"}, "must be an integer"),
    ({"block": 64}, "unknown option"),
    ({"bs": 100}, "multiple of 128"),
    ({"bb": 64}, "padded batch"),          # config batch_size=16 pads to 16
    ({"autotune": 1}, "must be a bool"),
    ({"autotune_cache": ""}, "non-empty path"),
])
def test_fused_tile_validation_is_friendly(options, match):
    """Bad tile sizes fail at session construction with a ValueError —
    never a Pallas shape crash mid-profile."""
    with pytest.raises(ValueError, match=match):
        ProfilingSession(_config(backend_options=options))


def test_fused_explicit_tiles_override_autotune(sample):
    """autotune=true plus explicit tiles: explicit wins, warned once."""
    from repro.pipeline import fused as fused_mod

    fused_mod._warned_autotune_override = False
    with pytest.warns(UserWarning, match="override autotune"):
        s = ProfilingSession(_config(
            backend_options={"autotune": True, "bb": 4}))
    assert s.backend._autotune is False
    assert s.backend.tiles["bb"] == 4
    # second construction: same override, no second warning
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        ProfilingSession(_config(backend_options={"autotune": True,
                                                  "bb": 4}))


# -- registry completeness (bugfix satellite) --------------------------------

def test_backends_visible_without_package_import():
    """`--list-backends` and the unknown-backend error must include every
    backend even when only `repro.pipeline.backend` was imported (the
    lazily-registered entry points)."""
    out = subprocess.run(
        [sys.executable, "-c",
         "from repro.pipeline.backend import available_backends\n"
         "print(','.join(available_backends()))"],
        env={**os.environ,
             "PYTHONPATH": os.pathsep.join(
                 filter(None, ["src", os.environ.get("PYTHONPATH")]))},
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    names = set(out.stdout.strip().split(","))
    assert {"pallas_fused", "pcm_sim", "sharded"} <= names


def test_unknown_backend_error_lists_lazy_backends():
    with pytest.raises(ValueError, match="pallas_fused"):
        resolve_backend("no_such_backend", _config(backend="reference"))
