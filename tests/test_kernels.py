"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, bit-exact."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import item_memory
from repro.core.hd_space import HDSpace
from repro.kernels import ops, ref
from repro.kernels.am_matmul import am_matmul
from repro.kernels.hamming_am import hamming_am

RNG = np.random.default_rng(42)


def _rand_packed(b, w):
    return jnp.asarray(RNG.integers(0, 2**32, (b, w), dtype=np.uint32))


@pytest.mark.parametrize("b,s,w", [(8, 16, 64), (16, 128, 128),
                                   (8, 128, 40), (4, 300, 64), (128, 8, 8)])
def test_am_agreement_sweep(b, s, w):
    q, p = _rand_packed(b, w), _rand_packed(s, w)
    want = np.asarray(ref.hamming_am_ref(q, p))
    got_m = np.asarray(ops.am_agreement(q, p, 32 * w, "matmul"))
    got_p = np.asarray(ops.am_agreement(q, p, 32 * w, "packed"))
    np.testing.assert_array_equal(got_m, want)
    np.testing.assert_array_equal(got_p, want)


@pytest.mark.parametrize("platform,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None)])
def test_kernels_interpret_only_on_cpu(monkeypatch, platform, interpret):
    """Native on TPU, interpreted on CPU, refused anywhere else — never
    a silent interpreter run on an accelerator that failed to start."""
    from repro.kernels import interpret as mode
    monkeypatch.setattr(mode.jax, "default_backend", lambda: platform)
    assert mode.interpret_default(True) is True
    if interpret is None:
        with pytest.raises(RuntimeError, match="natively on TPU"):
            mode.interpret_default(None)
    else:
        assert mode.interpret_default(None) is interpret


@pytest.mark.parametrize("bm,bn,bk", [(8, 8, 128), (4, 16, 256)])
def test_am_matmul_blockings(bm, bn, bk):
    q, p = _rand_packed(8, 16), _rand_packed(16, 16)
    qpm, ppm = ops.to_pm1(q), ops.to_pm1(p)
    got = np.asarray(am_matmul(qpm, ppm, bm=bm, bn=bn, bk=bk))
    want = np.asarray(ref.am_matmul_ref(qpm, ppm))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("bm,bn,bw", [(4, 8, 8), (8, 16, 16)])
def test_hamming_am_blockings(bm, bn, bw):
    q, p = _rand_packed(8, 32), _rand_packed(16, 32)
    got = np.asarray(hamming_am(q, p, bm=bm, bn=bn, bw=bw))
    want = np.asarray(ref.hamming_am_ref(q, p))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("dim,n,length", [(1024, 4, 24), (2048, 8, 40),
                                          (512, 2, 9), (512, 6, 5)])
def test_encoder_kernel_sweep(dim, n, length):
    sp = HDSpace(dim=dim, ngram=n)
    im = item_memory.make_item_memory(sp)
    tie = item_memory.make_tie_break(sp)
    imr = item_memory.rolled(im, n)
    toks = jnp.asarray(RNG.integers(0, 4, (8, length), dtype=np.int32))
    lens = jnp.asarray(RNG.integers(0, length + 1, 8, dtype=np.int32))
    want = np.asarray(ref.hdc_encode_ref(toks, lens, imr, tie))
    got = np.asarray(ops.hdc_encode(toks, lens, im, tie, sp))
    np.testing.assert_array_equal(got, want)


def test_encoder_kernel_batch_padding():
    """Non-multiple-of-8 batch is padded and sliced back."""
    sp = HDSpace(dim=512, ngram=3)
    im = item_memory.make_item_memory(sp)
    tie = item_memory.make_tie_break(sp)
    toks = jnp.asarray(RNG.integers(0, 4, (5, 12), dtype=np.int32))
    lens = jnp.full((5,), 12, jnp.int32)
    got = ops.hdc_encode(toks, lens, im, tie, sp)
    want = ref.hdc_encode_ref(toks, lens, item_memory.rolled(im, 3), tie)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernel_profiler_integration():
    """The pallas_matmul backend == the reference backend end-to-end."""
    from repro.pipeline import ProfilerConfig, ProfilingSession
    sp = HDSpace(dim=512, ngram=5, z_threshold=3.0)
    rng = np.random.default_rng(0)
    genomes = {f"s{i}": rng.integers(0, 4, 3000).astype(np.int32)
               for i in range(3)}
    s0 = ProfilingSession(ProfilerConfig(
        space=sp, window=1024, batch_size=16, backend="reference"))
    s1 = ProfilingSession(ProfilerConfig(
        space=sp, window=1024, batch_size=16, backend="pallas_matmul"))
    db0, db1 = s0.build_refdb(genomes), s1.build_refdb(genomes)
    np.testing.assert_array_equal(np.asarray(db0.prototypes),
                                  np.asarray(db1.prototypes))
    toks = rng.integers(0, 4, (16, 60)).astype(np.int32)
    lens = np.full(16, 60, np.int32)
    q0 = s0.encode_reads(toks, lens)
    q1 = s1.encode_reads(toks, lens)
    np.testing.assert_array_equal(np.asarray(q0), np.asarray(q1))
    r0 = s0.classify_queries(q0, db0)
    r1 = s1.classify_queries(q1, db1)
    np.testing.assert_array_equal(np.asarray(r0.scores), np.asarray(r1.scores))
