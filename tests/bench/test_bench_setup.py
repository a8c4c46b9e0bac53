"""Set-up takes its cohort lengths from the service under test and its
reference from the configuration: a long-read configuration with a
reference of its own is added by adding files only.  Tiny sizes on the
CPU, with the chip look skipped."""

from __future__ import annotations

import functools
import json
import pathlib
import shutil
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness, loadgen  # noqa: E402

SEED = 3_000_000_778

STUB_REFERENCE = '''"""A stand-in reference: three species, one prototype
each, every read scoring 7 against each."""

import numpy as np


class Reference:
    def __init__(self, cfg, genomes):
        self.prototypes = np.zeros((3, 4), np.uint32)
        self.bounds = np.arange(4)

    def scores(self, tokens, lengths, **kw):
        return np.full((len(lengths), 3), 7, np.int32)
'''


class _WindowOpened(Exception):
    pass


def _long_copy(tmp_path: pathlib.Path) -> dict:
    """A copy of ``bench/`` with a long-read configuration (reads up to
    16,384 bp) that names a reference file of its own; no file already
    there is touched."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("store", "traces",
                                                  ".jax_cache"))
    (tmp_path / "bench/reference_long.py").write_text(STUB_REFERENCE)
    cfg = json.loads((ROOT / "bench/configs/afs20-ont.json").read_text())
    cfg.update(name="afs20-ont-long", reference="reference_long.py",
               read_length={"dist": "lognormal", "median": 8000,
                            "sigma": 0.5, "min": 256, "max": 16384})
    (tmp_path / "bench/configs/afs20-ont-long.json").write_text(
        json.dumps(cfg))
    man = harness.manifest()
    man["configs"].append({"name": "afs20-ont-long", "source": "x",
                           "file": "bench/configs/afs20-ont-long.json",
                           "reduced": ["genome_len", "batch_size"],
                           "why": "x"})
    man["workloads"].append({"name": "afs20-ont-long-backlog",
                             "config": "afs20-ont-long",
                             "traffic": "ont_backlog", "chips": 1,
                             "why": "x"})
    for m in man["end_to_end"]:
        if m["name"] == "reads_per_s":
            m["workloads"].append("afs20-ont-long-backlog")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    return cfg


def _with_buckets(monkeypatch, buckets) -> None:
    """Every service ``harness.build_service`` builds gets ``buckets``
    through its public constructor, whatever the program's default."""
    from repro import serve
    monkeypatch.setattr(serve, "ProfilingService", functools.partial(
        serve.ProfilingService, buckets=buckets))


def _tiny(cfg: dict, traffic: dict) -> tuple[dict, dict]:
    return ({**cfg, "species": 4, "genome_len": 20_000, "dim": 1024,
             "window": 2048, "batch_size": 8},
            {**traffic, "clients": 2,
             "request_reads": {"dist": "fixed", "value": 20}})


def _run_until_window(tmp_path, monkeypatch, cfg, traffic):
    """Run a cell's set-up; the window is never opened."""
    monkeypatch.setattr(harness, "STORE", tmp_path / "store")

    def no_window(*a, **kw):
        raise _WindowOpened
    monkeypatch.setattr(harness, "_Recorder", no_window)
    return harness.run_cell(
        cell="tiny", cfg=cfg, traffic=traffic, seed=SEED, seconds=2.0,
        trace=False, t_start=0.0, log=lambda s: None, metrics=[],
        root=tmp_path)


def test_long_read_config_is_found_and_checked_by_its_own_reference(
        tmp_path):
    cfg = _long_copy(tmp_path)
    man = harness.manifest(tmp_path)
    got, traffic = harness.cell_files(man, "afs20-ont-long-backlog", tmp_path)
    assert got == cfg and traffic["loop"] == "closed"
    assert "reads_per_s" in [m["name"] for m in harness.cell_metrics(
        man, "afs20-ont-long-backlog", False)]
    ref = harness.reference(cfg, tmp_path)
    assert ref.__init__.__code__.co_filename == str(
        tmp_path / "bench/reference_long.py")
    lengths = np.array([5, 9, 0, 0], np.int32)
    tokens = np.zeros((4, 16), np.int8)
    protos = np.zeros((3, 4), np.uint32)
    scores = np.full((4, 3), 7, np.int32)
    same = harness.compare(ref(cfg, None), protos, [],
                           [(tokens, lengths, scores)])
    assert same == {"score_diff": 0, "count_diff": 0, "abundance_diff": 0.0,
                    "prototype_diff": 0}
    scores[0, 1], scores[3, 0] = 6, 6        # a live read, a padding row
    protos[2, 3] = 1
    off = harness.compare(ref(cfg, None), protos, [],
                          [(tokens, lengths, scores)])
    assert off["score_diff"] == 1 and off["prototype_diff"] == 1


def test_service_with_longer_buckets_is_warmed_at_its_own_lengths(
        tmp_path, monkeypatch):
    """A service whose buckets reach 16,384 is asked for that length."""
    from repro.pipeline.session import ProfilingSession
    from repro.serve import pow2_buckets
    cfg, traffic = _tiny(_long_copy(tmp_path), json.loads(
        (ROOT / "bench/traffic/ont_backlog.json").read_text()))
    _with_buckets(monkeypatch, pow2_buckets(16, 16384))
    warmed = []

    def record(self, tokens, lengths, **kw):
        warmed.append(np.shape(tokens))
        hits = np.zeros((len(lengths), cfg["species"]), bool)
        return types.SimpleNamespace(classification=types.SimpleNamespace(
            hits=hits, category=np.zeros(len(lengths), np.int32)))
    monkeypatch.setattr(ProfilingSession, "classify_batch", record)
    with pytest.raises(_WindowOpened):
        _run_until_window(tmp_path, monkeypatch, cfg, traffic)
    lengths = np.concatenate([r.lengths for r in loadgen.make(
        cfg, traffic, SEED, 2.0).requests])
    assert lengths.min() <= 4096 and lengths.max() == 16384
    assert [w[1] for w in warmed] == [4096, 8192, 16384]
    assert all(w[0] == cfg["batch_size"] for w in warmed)


def test_default_service_stops_set_up_on_a_read_it_refuses(
        tmp_path, monkeypatch):
    """A service that refuses a read stops set-up: built with buckets up
    to 4,096 (set here, not the program's default), it refuses the
    16,384-bp reads, and set-up ends with one line naming the longest read
    and the largest bucket, before any warm-up or window."""
    from repro.pipeline.session import ProfilingSession
    from repro.serve import pow2_buckets
    cfg, traffic = _tiny(_long_copy(tmp_path), json.loads(
        (ROOT / "bench/traffic/ont_backlog.json").read_text()))
    _with_buckets(monkeypatch, pow2_buckets(16, 4096))

    def no_warm_up(*a, **kw):
        raise AssertionError("warm-up ran")
    monkeypatch.setattr(ProfilingSession, "classify_batch", no_warm_up)
    with pytest.raises(harness.SetupError) as e:
        _run_until_window(tmp_path, monkeypatch, cfg, traffic)
    assert str(e.value) == ("bench: the service refuses reads of 16384 bp: "
                            "its largest bucket is 4096")


@pytest.mark.parametrize("name,body", [
    ("missing.py", None),
    ("broken.py", "def Reference(:\n"),
    ("no_class.py", "REFERENCE = None\n"),
])
def test_unusable_reference_stops_set_up_first(tmp_path, monkeypatch, name,
                                               body):
    (tmp_path / "bench").mkdir()
    if body is not None:
        (tmp_path / "bench" / name).write_text(body)
    cfg = json.loads((ROOT / "bench/configs/afs20.json").read_text())
    cfg["reference"] = name

    def no_data(*a, **kw):
        raise AssertionError("data was made")
    monkeypatch.setattr(loadgen, "make", no_data)
    with pytest.raises(harness.SetupError, match=name):
        _run_until_window(tmp_path, monkeypatch, cfg, {})


def _served_lengths(service, session, lengths) -> dict[int, int]:
    """The cohort length ``service`` runs a read of each of ``lengths``
    at: one single-read request at a time, pumped on this thread, with the
    session's ``classify_batch`` recording the width it is called with."""
    from repro.pipeline import ArraySource
    species = session.refdb.num_species
    widths = []

    def record(tokens, lens, **kw):
        widths.append(np.shape(tokens)[1])
        n = len(lens)
        return types.SimpleNamespace(classification=types.SimpleNamespace(
            hits=np.zeros((n, species), bool),
            category=np.zeros(n, np.int32)))
    session.classify_batch = record
    served = {}
    for x in lengths:
        service.submit(ArraySource(np.zeros((1, x), np.int8),
                                   np.array([x], np.int32)))
        service.run_until_idle()
        served[int(x)] = widths[-1]
    return served


@pytest.mark.parametrize("cell", ["afs20-short-open", "afs20-ont-backlog",
                                  "afs20-short-backlog"])
def test_cells_compare_through_reference_py_and_warm_as_before(
        tmp_path, monkeypatch, cell):
    """The cells already there keep ``bench/reference.py`` and warm
    exactly the lengths the built service runs their reads at, as the
    service shows them (not a list written here: the buckets are the
    program's to choose); ``padded_length`` agrees read length by read
    length."""
    cfg, traffic = harness.cell_files(harness.manifest(), cell)
    assert harness.reference(cfg).__init__.__code__.co_filename == str(
        ROOT / "bench/reference.py")
    tiny = {**cfg, "species": 4, "genome_len": 20_000, "dim": 1024,
            "window": 2048, "batch_size": 8}
    monkeypatch.setattr(harness, "STORE", tmp_path / "store")
    wl = loadgen.make(tiny, traffic, SEED, 35.0)
    session, service, _ = harness.build_service(tiny, traffic, wl.genomes,
                                                log=lambda s: None)
    warm = harness.warm_lengths(service, wl.requests)
    lengths = np.unique(np.concatenate([r.lengths for r in wl.requests]))
    served = _served_lengths(service, session, lengths)
    assert warm == sorted(set(served.values()))
    assert {x: harness.padded_length(service, x) for x in served} == served


@pytest.mark.parametrize("service", [
    types.SimpleNamespace(),
    types.SimpleNamespace(_sched=types.SimpleNamespace(buckets=(256,))),
    types.SimpleNamespace(bucket_for=lambda size: 256),
])
def test_service_without_its_bucket_lookup_stops_set_up(service):
    """A service that offers ``bucket_for`` and ``buckets`` neither
    itself nor through its scheduler ends set-up with one line, not an
    ``AttributeError``."""
    with pytest.raises(harness.SetupError, match="bucket_for/buckets"):
        harness.padded_length(service, 150)


class _Buckets:
    """A bucket lookup: the smallest of ``buckets`` that holds a read."""

    def __init__(self, *buckets: int):
        self.buckets = buckets

    def bucket_for(self, size: int) -> int:
        for b in self.buckets:
            if size <= b:
                return b
        raise ValueError(f"{size} exceeds {self.buckets[-1]}")


def _public_over_private() -> types.SimpleNamespace:
    """A service with public buckets and a scheduler whose buckets
    differ, so each answer shows which of the two was asked."""
    public = _Buckets(512, 1024, 8192)
    return types.SimpleNamespace(bucket_for=public.bucket_for,
                                 buckets=public.buckets,
                                 _sched=_Buckets(256, 4096))


@pytest.mark.parametrize("length,want", [(150, 512), (700, 1024),
                                         (5000, 8192)])
def test_padded_length_prefers_the_services_public_buckets(length, want):
    assert harness.padded_length(_public_over_private(), length) == want


def test_read_refused_by_the_public_buckets_stops_set_up():
    with pytest.raises(harness.SetupError) as e:
        harness.padded_length(_public_over_private(), 9000)
    assert str(e.value) == ("bench: the service refuses reads of 9000 bp: "
                            "its largest bucket is 8192")
