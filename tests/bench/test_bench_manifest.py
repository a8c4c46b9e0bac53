"""The manifest, and how the harness finds cells, configs, traffic and
metrics by name."""

from __future__ import annotations

import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def man():
    return harness.manifest()


def test_manifest_keys_and_names(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert all(p in ("bench", "tests/bench") for p in man["paths"])
    assert 1 <= man["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in man[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names


def test_every_cell_reports_what_it_must(man):
    e2e = {m["name"] for m in man["end_to_end"]}
    for cell in man["workloads"]:
        name = cell["name"]
        assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
        got = [m["name"] for m in harness.cell_metrics(man, name, False)]
        assert "setup_s" in got and len(got) >= 2
        layer = harness.cell_metrics(man, name, True)
        assert layer and all(m["moves"] in got for m in layer)
        cfg, traffic = harness.cell_files(man, name)
        assert cfg["name"] == cell["config"]
        assert traffic["loop"] in ("open", "closed")
    for m in man["per_layer"]:
        assert m["moves"] in e2e and m["workloads"]
    for m in man["end_to_end"]:
        assert 0 < m["bound"] <= 0.25 and m["source"] == "host_clock"


def test_every_metric_has_a_reader(man):
    for m in man["end_to_end"] + man["per_layer"]:
        assert callable(harness.reader(m["name"]))


def test_short_backlog_cell_reports_throughput(man):
    """Eight 50,000-read short-read samples in a closed loop, on AFS20 as
    the open cell runs it, read as throughput and its four layers."""
    cell = {w["name"]: w for w in man["workloads"]}["afs20-short-backlog"]
    assert (cell["config"], cell["chips"]) == ("afs20", 1)
    cfg, traffic = harness.cell_files(man, "afs20-short-backlog")
    assert cfg == harness.cell_files(man, "afs20-short-open")[0]
    assert {k: traffic[k] for k in ("loop", "clients", "request_reads",
                                    "max_active", "max_queue")} == {
        "loop": "closed", "clients": 8,
        "request_reads": {"dist": "fixed", "value": 50000},
        "max_active": 8, "max_queue": 64}
    assert {m["name"] for m in harness.cell_metrics(
        man, "afs20-short-backlog", False)} == {"reads_per_s", "setup_s"}
    assert {m["name"] for m in harness.cell_metrics(
        man, "afs20-short-backlog", True)} == {
        "fused_ms_per_kread.backlog", "fused_roofline_pct.backlog",
        "device_idle_pct.backlog", "token_fill_pct.backlog"}


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path, man):
    """A later change adds a cell by adding files and manifest entries."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("store", "traces",
                                                  ".jax_cache"))
    cfg = json.loads((ROOT / "bench/configs/afs20.json").read_text())
    cfg.update(name="afs20-pe250", read_length={"dist": "fixed",
                                                "value": 250})
    (tmp_path / "bench/configs/afs20-pe250.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/short_burst.json").write_text(json.dumps(
        {"loop": "open", "rate_rps": 3.0, "max_active": 8, "max_queue": 64,
         "request_reads": {"dist": "fixed", "value": 100}}))
    (tmp_path / "bench/metrics/reads_per_cohort.burst.py").write_text(
        "def read(run):\n"
        "    return run.window_reads / run.window_cohorts\n")
    new = json.loads(json.dumps(man))
    new["configs"].append({"name": "afs20-pe250", "source": "x",
                           "file": "bench/configs/afs20-pe250.json",
                           "reduced": [], "why": "x"})
    new["workloads"].append({"name": "afs20-pe250-burst",
                             "config": "afs20-pe250",
                             "traffic": "short_burst", "chips": 1,
                             "why": "x"})
    new["per_layer"].append({"name": "reads_per_cohort.burst", "unit": "1",
                             "better": "higher", "source": "program_counter",
                             "layer": "service", "moves": "latency_p95_s",
                             "workloads": ["afs20-pe250-burst"]})
    for m in new["end_to_end"]:
        if m["name"].startswith("latency"):
            m["workloads"].append("afs20-pe250-burst")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))

    got = harness.manifest(tmp_path)
    cfg2, traffic = harness.cell_files(got, "afs20-pe250-burst", tmp_path)
    assert cfg2["read_length"]["value"] == 250
    assert traffic["rate_rps"] == 3.0
    assert [m["name"] for m in harness.cell_metrics(
        got, "afs20-pe250-burst", True)] == ["reads_per_cohort.burst"]
    assert {m["name"] for m in harness.cell_metrics(
        got, "afs20-pe250-burst", False)} == {
        "latency_p50_s", "latency_p95_s", "setup_s"}
    read = harness.reader("reads_per_cohort.burst", tmp_path)
    run = harness.Run(cfg=cfg2, traffic=traffic, device_kind="x",
                      window_reads=300, window_cohorts=4)
    assert read(run) == 75
    # The cells already there are untouched.
    assert harness.cell_files(got, "afs20-short-open", tmp_path) == \
        harness.cell_files(man, "afs20-short-open")
    with pytest.raises(KeyError, match="no cell"):
        harness.cell_files(got, "no-such-cell", tmp_path)


def _run_py(cwd: pathlib.Path) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "afs20-short-open",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert "needs 1 TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("store", "traces",
                                                  ".jax_cache"))
    shutil.copytree(ROOT / "tests" / "bench", tmp_path / "tests" / "bench")
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
