"""The traffic generator and the roofline counts of the benchmark."""

from __future__ import annotations

import json
import math
import pathlib
import statistics
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import loadgen, roofline, synth  # noqa: E402

SEED = 2**31 + 17


def _files(config, traffic):
    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                     .read_text())
    tr = json.loads((ROOT / "bench" / "traffic" / f"{traffic}.json")
                    .read_text())
    cfg.update(species=6, genome_len=30_000)
    return cfg, tr


@pytest.mark.parametrize("config,traffic", [("afs20", "short_open"),
                                            ("afs20-ont", "ont_backlog")])
def test_same_seed_same_workload(config, traffic):
    cfg, tr = _files(config, traffic)
    if tr["loop"] == "closed":
        tr["request_reads"] = {"dist": "fixed", "value": 40}
    a = loadgen.make(cfg, tr, SEED, 3.0)
    b = loadgen.make(cfg, tr, SEED, 3.0)
    assert np.array_equal(a.genomes, b.genomes)
    assert len(a.requests) == len(b.requests)
    for x, y in zip(a.requests, b.requests):
        assert x.due_s == y.due_s
        assert np.array_equal(x.tokens, y.tokens)
        assert np.array_equal(x.lengths, y.lengths)
    c = loadgen.make(cfg, tr, SEED + 1, 3.0)
    assert not np.array_equal(a.genomes, c.genomes)
    # Another seed sends other reads, but the same amount of work.
    assert sorted(r.reads for r in a.requests) == sorted(
        r.reads for r in c.requests)
    assert sorted(np.concatenate([r.lengths for r in a.requests])) == sorted(
        np.concatenate([r.lengths for r in c.requests]))


def test_open_loop_arrivals_match_the_rate():
    r = loadgen.rng(SEED, 2)
    due = loadgen.arrival_offsets(6.4, 35.0, r)
    assert len(due) == round(6.4 * 35)
    assert due[0] == 0 and np.all(np.diff(due) > 0) and due[-1] < 35.0
    gaps = np.diff(due)
    # Exponential gaps: mean 1/rate, standard deviation about the mean.
    assert np.mean(gaps) == pytest.approx(1 / 6.4, rel=0.02)
    assert np.std(gaps) == pytest.approx(1 / 6.4, rel=0.1)


def test_request_sizes_follow_the_stated_lognormal():
    dist = {"dist": "lognormal", "median": 300, "sigma": 1.0, "min": 32,
            "max": 8192}
    q = loadgen.quantiles(dist, 1001)
    assert q[500] == pytest.approx(300)
    assert q.min() >= 32 and q.max() <= 8192
    logs = np.log(q[(q > 32) & (q < 8192)])
    assert np.std(np.log(loadgen.quantiles(
        {"dist": "lognormal", "median": 300, "sigma": 1.0}, 1001))) == \
        pytest.approx(1.0, rel=0.02)
    assert logs.min() > math.log(32)
    assert np.all(loadgen.quantiles({"dist": "fixed", "value": 150}, 7)
                  == 150)


def test_reads_have_their_lengths_species_and_error_rate():
    cfg, tr = _files("afs20-ont", "ont_backlog")
    tr["request_reads"] = {"dist": "fixed", "value": 400}
    wl = loadgen.make(cfg, tr, SEED, 3.0)
    assert len(wl.requests) == tr["clients"] and wl.loop == "closed"
    req = wl.requests[0]
    assert req.tokens.dtype == np.int8 and req.tokens.max() <= 3
    assert statistics.median(req.lengths) == pytest.approx(2500, rel=0.02)
    assert req.lengths.max() <= 4096 and req.lengths.min() >= 256
    for row, n in zip(req.tokens, req.lengths):
        assert not row[n:].any()
    counts = np.bincount(req.species, minlength=cfg["species"])
    assert sorted(counts[counts > 0]) == [4, 36, 80, 280]


def test_mutate_rate_and_homology():
    rng = np.random.default_rng(3)
    g = synth.reference_genomes(rng, 3, 100_000, 0.06)

    def kmers(x, k=20):
        w = np.lib.stride_tricks.sliding_window_view(x.astype(np.int64), k)
        return np.unique(w @ (4 ** np.arange(k, dtype=np.int64)))

    # A 6,000-base block of each species comes from the previous one.
    for s in (1, 2):
        shared = np.intersect1d(kmers(g[s]), kmers(g[s - 1])).size
        assert 6000 - 19 <= shared < 6000 + 50
    m = synth.mutate(g, 0.02, rng)
    assert np.mean(m != g) == pytest.approx(0.02, rel=0.05)
    assert m.max() <= 3


def test_roofline_counts_on_known_shapes():
    # Padding rows (length 0) are not work.
    lengths = np.array([150] * 3000 + [0] * 1096)
    ops, nbytes = roofline.call_work(lengths, dim=40960, ngram=16,
                                     prototypes=1480, species=20)
    assert ops == 3 * 40960 * 135 * 3000 + 2 * 40960 * 1480 * 3000
    assert nbytes == 150 * 3000 + 1480 * 40960 / 8 + 4 * 20 * 3000
    t, bound = roofline.least_seconds(ops, nbytes, "TPU v5 lite")
    assert bound == "compute" and t == pytest.approx(ops / 393e12)
    # Two reads against the whole database: the prototype bytes bind.
    ops, nbytes = roofline.call_work(np.array([150, 150]), dim=40960,
                                     ngram=16, prototypes=1480, species=20)
    t, bound = roofline.least_seconds(ops, nbytes, "TPU v5 lite")
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks("TPU v9 imaginary")
