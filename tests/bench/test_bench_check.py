"""The correctness check: a sound run passes, the control and planted
faults fail.  Tiny sizes on the CPU, with the chip look skipped."""

from __future__ import annotations

import dataclasses
import json
import pathlib
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import control, harness, loadgen  # noqa: E402

SEED = 3_000_000_777


def _tiny():
    cfg = json.loads((ROOT / "bench/configs/afs20.json").read_text())
    cfg.update(species=4, genome_len=20_000, dim=1024, window=2048,
               batch_size=64)
    traffic = json.loads((ROOT / "bench/traffic/short_open.json").read_text())
    traffic.update(rate_rps=3.0, request_reads={
        "dist": "lognormal", "median": 20, "sigma": 1.0, "min": 4,
        "max": 100})
    return cfg, traffic


def _run(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "STORE", tmp_path / "store")
    cfg, traffic = _tiny()
    result, _ = harness.run_cell(
        cell="tiny", cfg=cfg, traffic=traffic, seed=SEED, seconds=2.0,
        trace=False, t_start=0.0, log=lambda s: None,
        metrics=harness.cell_metrics(harness.manifest(), "afs20-short-open",
                                     False))
    return result


def _half_left_out(classify):
    """Only the first half of a cohort's reads is classified."""
    def broken(self, tokens, lengths, **kw):
        tokens, lengths = np.array(tokens), np.array(lengths)
        live = int((lengths > 0).sum())
        tokens[live // 2:live] = 0
        lengths[live // 2:live] = 0
        return classify(self, tokens, lengths, **kw)
    return broken


def _answer_altered(classify):
    """The first read of every cohort reports one species hit flipped."""
    def broken(self, tokens, lengths, **kw):
        res = classify(self, tokens, lengths, **kw)
        c = res.classification
        hits = c.hits.at[0, 0].set(~c.hits[0, 0])
        n = hits.sum(axis=-1)
        cat = np.where(n == 0, 0, np.where(n == 1, 1, 2)).astype(np.int32)
        return dataclasses.replace(res, classification=dataclasses.replace(
            c, hits=hits, category=cat))
    return broken


def test_sound_run_is_correct(tmp_path, monkeypatch):
    result = _run(tmp_path, monkeypatch)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] == 6
    assert list(result)[-1] == "compared"
    assert set(result["metrics"]) == {"latency_p50_s", "latency_p95_s",
                                      "setup_s"}


@pytest.mark.parametrize("fault,fails", [
    (_half_left_out, "score_diff"),
    (_answer_altered, "count_diff"),
])
def test_planted_fault_is_not_correct(tmp_path, monkeypatch, fault, fails):
    from repro.pipeline.session import ProfilingSession
    monkeypatch.setattr(ProfilingSession, "classify_batch",
                        fault(ProfilingSession.classify_batch))
    result = _run(tmp_path, monkeypatch)
    assert not result["correct"]
    assert result["compared"][fails]["value"] > 0


def test_refused_request_is_not_correct(tmp_path, monkeypatch):
    """A request the service refuses counts in ``failed``, which has the
    limit 0, though every answer that came is right."""
    from repro.serve import ProfilingService, ServiceOverloaded
    submit, calls = ProfilingService.submit, []

    def refuse_second(self, *a, **kw):
        calls.append(1)
        if len(calls) == 2:
            raise ServiceOverloaded("admission queue full")
        return submit(self, *a, **kw)
    monkeypatch.setattr(ProfilingService, "submit", refuse_second)
    result = _run(tmp_path, monkeypatch)
    assert result["failed"] == 1
    assert result["compared"]["failed"]["value"] == 1
    assert result["compared"]["score_diff"]["value"] == 0
    assert not result["correct"]


def test_control_in_bfloat16_is_not_correct(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "STORE", tmp_path / "store")
    cfg, traffic = _tiny()
    pad = control.padder(cfg, traffic, loadgen.make(cfg, traffic, 1,
                                                    2.0).genomes)
    for seed in (1, 2, 3):
        got = control.readings(cfg, traffic, seed, 2.0, pad)
        assert got["score_diff"] > 0
        assert got["prototype_diff"] == 0
        assert not got["correct"]


@pytest.fixture(scope="module")
def reference_and_request():
    """The real reference at the tiny sizes, the largest tiny request, and
    its reads' classification by that reference."""
    cfg, traffic = _tiny()
    wl = loadgen.make(cfg, traffic, SEED, 2.0)
    req = max(wl.requests, key=lambda r: r.reads)
    ref = harness.reference(cfg)(cfg, wl.genomes)
    return ref, req, *ref.classify(req.tokens, req.lengths)


def _covered(hits, cat, which: str) -> np.ndarray:
    """Indices of ``k`` = half of the request's reads: its first ``k``
    (``prefix``), or the first ``k - 1`` and then, in place of read
    ``k - 1``, the first later read whose category or unique species
    differs from it (``other``), so the two sets' species counts differ."""
    k = len(cat) // 2
    if which == "prefix":
        return np.arange(k)

    def key(i):
        return int(cat[i]), tuple(hits[i]) if cat[i] == 1 else ()
    swap = next(j for j in range(k, len(cat)) if key(j) != key(k - 1))
    return np.append(np.arange(k - 1), swap)


@pytest.mark.parametrize("which,count_diff_is_0", [("prefix", True),
                                                   ("other", False)])
def test_report_is_checked_against_its_requests_first_reads(
        reference_and_request, which, count_diff_is_0):
    """A report of ``k`` reads stands for the request's first ``k``: one
    that covers other reads, of the same number, fails ``count_diff``."""
    ref, req, hits, cat = reference_and_request
    idx = _covered(hits, cat, which)
    rep = ref.report(hits[idx], cat[idx])
    first = ref.report(hits[:len(idx)], cat[:len(idx)])
    assert (np.array_equal(rep["unique_counts"], first["unique_counts"])
            and rep["unmapped"] == first["unmapped"]) == count_diff_is_0
    report = types.SimpleNamespace(
        total_reads=rep["total"], unmapped_reads=rep["unmapped"],
        multi_reads=rep["multi"], unique_counts=rep["unique_counts"],
        abundance=rep["abundance"])
    got = harness.compare(ref, ref.prototypes,
                          [{"req": req, "report": report}], [])
    assert (got["count_diff"] == 0) == count_diff_is_0
    assert got["score_diff"] == 0 and got["prototype_diff"] == 0
