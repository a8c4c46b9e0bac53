"""The program's spans in a benchmark trace, and the six metrics read
from them, on a small trace the test writes."""

from __future__ import annotations

import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import harness, program_spans, readers, trace_reduce  # noqa: E402

MS = 1_000_000          # ns
FUSED = "%fused_profile.1 = s32[8,128]{1,0} custom-call(s32[8,256] %p)"
TAIL = "%fusion = s32[20,8]{1,0} fusion(s32[8,128] %a)"

# The device and the benchmark's spans of the reduction's own test trace:
# a 100 ms window, fused kernel 10-40 and 60-90 ms, tail 40-42 and
# 90-92 ms; service steps 5-45 and 55-95 ms with calls 8-10 and 58-60 ms,
# an idle poll 50-51 ms.
DEVICE = [
    ("XLA Ops", [(FUSED, 10, 30), (FUSED, 60, 30), (TAIL, 40, 2),
                 (TAIL, 90, 2),
                 ("%copy.1 = s32[8]{0} copy(s32[8] %x)", 30, 5)]),
    ("XLA Modules", [("jit_fused_agreement(1)", 10, 30),
                     ("jit_fused_agreement(1)", 60, 30),
                     ("jit_from_agreement(2)", 40, 2),
                     ("jit_from_agreement(2)", 90, 2)]),
]
BENCH = [
    ("python3", [("bench.window", 0, 100)]),
    ("worker", [("bench.step", 5, 40), ("bench.classify_batch", 8, 2),
                ("bench.step", 50, 1), ("bench.step", 55, 40),
                ("bench.classify_batch", 58, 2), ("other.span", 1, 1)]),
]
STEP1 = {"cohort": 0, "rows": 8, "slots": 8, "bucket": 256, "tokens": 1200,
         "requests": "req-0 req-1", "compiles": 2}
STEP2 = {"cohort": 1, "rows": 4, "slots": 8, "bucket": 256, "tokens": 600,
         "requests": "req-1", "compiles": 0}
# The program's spans on the same thread (name, start ms, length ms,
# arguments): two cohorts' steps, a step that ran no cohort inside the
# idle poll, and an admission left without its step at the window's close.
PROGRAM = [
    ("serve.step", 5.5, 39.0, STEP1),
    ("serve.admit", 5.5, 1.5, {}), ("serve.pull", 6.0, 0.5, {}),
    ("serve.assemble", 7.0, 1.0, {}),
    ("session.dispatch", 8.2, 1.6, {"path": "tokens_agreement"}),
    ("serve.wait", 10.0, 32.5, {}), ("serve.demux", 42.5, 2.0, {}),
    ("serve.step", 50.2, 0.6, {}), ("serve.admit", 50.2, 0.6, {}),
    ("serve.step", 55.5, 39.0, STEP2),
    ("serve.admit", 55.5, 0.5, {}), ("serve.pull", 55.6, 0.3, {}),
    ("serve.assemble", 56.0, 2.0, {}),
    ("session.dispatch", 58.2, 1.6, {"path": "tokens_agreement"}),
    ("serve.wait", 60.0, 32.5, {}), ("serve.demux", 92.5, 1.5, {}),
    ("serve.admit", 96.0, 1.0, {}),
]


def _value(v):
    return f"int64_value: {v}" if isinstance(v, int) else f'str_value: "{v}"'


def _plane(pid, name, lines):
    events = [e for _, evs in lines for e in evs]
    names = sorted({e[0] for e in events})
    stats = sorted({k for e in events if len(e) > 3 for k in e[3]})
    body = ""
    for lid, (ln, evs) in enumerate(lines):
        ev = ""
        for e in evs:
            st = "".join(f"stats {{ metadata_id: {stats.index(k) + 1} "
                         f"{_value(v)} }} "
                         for k, v in (e[3] if len(e) > 3 else {}).items())
            ev += (f"events {{ metadata_id: {names.index(e[0]) + 1} "
                   f"offset_ps: {round(e[1] * MS * 1000)} "
                   f"duration_ps: {round(e[2] * MS * 1000)} {st}}}\n")
        body += (f'lines {{ id: {lid + 1} name: "{ln}" timestamp_ns: 0\n'
                 f"{ev}}}\n")
    meta = "".join(
        f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
        f'name: "{n}" }} }}\n' for i, n in enumerate(names))
    meta += "".join(
        f'stat_metadata {{ key: {i + 1} value {{ id: {i + 1} '
        f'name: "{n}" }} }}\n' for i, n in enumerate(stats))
    return f'planes {{ id: {pid} name: "{name}"\n{body}{meta}}}\n'


def _write(d: pathlib.Path, program=True, shift=0.0) -> pathlib.Path:
    from jax.profiler import ProfileData
    host = [(ln, [(n, s + shift, dur) for n, s, dur in evs])
            for ln, evs in BENCH]
    if program:
        host[1] = ("worker", host[1][1] + [(n, s + shift, dur, a)
                                            for n, s, dur, a in PROGRAM])
    txt = _plane(1, "/device:TPU:0", DEVICE) + _plane(2, "/host:CPU", host)
    out = d / "plugins" / "profile" / "t"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(txt))
    return d


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    d = _write(tmp_path_factory.mktemp("program"))
    return trace_reduce.load(d), program_spans.load(d)


@pytest.fixture(scope="module")
def plain(tmp_path_factory):
    return trace_reduce.load(_write(tmp_path_factory.mktemp("plain"),
                                    program=False))


def test_program_spans_and_their_arguments_are_read(traced):
    _, prog = traced
    assert prog.window == (0, 100 * MS)
    assert len(prog.spans) == len(PROGRAM)
    assert {s.name for s in prog.spans} == {n for n, *_ in PROGRAM}
    steps = program_spans.steps(prog, 0, 100 * MS)
    assert [s.args for s in steps] == [STEP1, STEP2]
    (d,) = program_spans.inside(prog, steps[0], "session.dispatch")
    assert d.args == {"path": "tokens_agreement"}
    assert (d.start, d.end) == pytest.approx((8.2 * MS, 9.8 * MS))


def test_phase_means_and_token_fill(traced):
    _, prog = traced
    w = (0, 100 * MS)

    def ms(name):
        return program_spans.phase_ms_per_cohort(prog, name, *w)

    # The bare step and the admission outside any step are no cohort's.
    assert ms("serve.admit") == pytest.approx((1.5 + 0.5) / 2)
    assert ms("serve.assemble") == pytest.approx((1.0 + 2.0) / 2)
    assert ms("session.dispatch") == pytest.approx((1.6 + 1.6) / 2)
    assert ms("serve.demux") == pytest.approx((2.0 + 1.5) / 2)
    assert ms("serve.wait") == pytest.approx(32.5)
    assert program_spans.token_fill_pct(prog, *w) == pytest.approx(
        100 * (1200 + 600) / (2 * 8 * 256))
    # A window that holds only the first step.
    assert program_spans.token_fill_pct(prog, 0, 50 * MS) == pytest.approx(
        100 * 1200 / (8 * 256))
    assert program_spans.phase_ms_per_cohort(
        prog, "serve.admit", 96 * MS, 100 * MS) is None


def test_idle_inside_steps_is_split_by_span(traced):
    trace, prog = traced
    split = program_spans.idle_split(trace, prog, 0, 0, 100 * MS)
    # Idle inside the working steps: 5-10, 42-45, 55-60, 92-95 ms.
    assert split["idle_in_steps"] == pytest.approx(16 * MS)
    assert split["under_program_spans"] == pytest.approx(14 * MS)
    want = {"serve.admit": 2.0, "serve.pull": 0.8, "serve.assemble": 3.0,
            "session.dispatch": 3.2, "serve.wait": 1.0, "serve.demux": 3.5,
            "serve.step": 14.0}
    for name, v in want.items():
        assert split[name] == pytest.approx(v * MS), name
    got = program_spans.summary(trace, prog)
    assert got["cohorts"] == 2 and got["compiles"] == 2
    assert got["idle_covered_pct"] == pytest.approx(87.5)


def _run(trace):
    """The reduction test's run: 12 live reads in two cohorts of 8."""
    cfg = {"dim": 1024, "ngram": 16, "batch_size": 8}
    run = harness.Run(cfg=cfg, traffic={}, device_kind="TPU v5 lite",
                      window_s=0.1, window_reads=12, window_cohorts=2,
                      prototypes=128, species=20, trace=trace)
    run.calls = [np.array([150] * 8), np.array([150] * 4 + [0] * 4)]
    return run


EXISTING = (readers.fused_ms_per_kread, readers.fused_roofline_pct,
            readers.tail_ms_per_kread, readers.host_gap_ms_per_cohort,
            readers.device_idle_pct, readers.cohort_fill_pct)


def test_existing_readers_read_what_they_read_without_program_spans(
        traced, plain):
    trace, _ = traced
    assert trace.host == plain.host
    for read in EXISTING:
        assert read(_run(trace)) == read(_run(plain)) is not None
    assert readers.host_gap_ms_per_cohort(_run(trace)) == pytest.approx(8.0)
    assert readers.device_idle_pct(_run(trace)) == pytest.approx(36.0)


NEW = {"admit_ms_per_cohort.open": 1.0, "assemble_ms_per_cohort.open": 1.5,
       "dispatch_ms_per_cohort.open": 1.6, "demux_ms_per_cohort.open": 1.75,
       "token_fill_pct.open": 100 * 1800 / 4096}


def _cell_run(trace, cell):
    """A run of ``cell`` as the harness records it, with ``trace``."""
    cfg, traffic = harness.cell_files(harness.manifest(), cell)
    return harness.Run(cfg=cfg, traffic=traffic, device_kind="TPU v5 lite",
                       trace=trace)


def _cell_traces(tmp_path, monkeypatch, cell, **kw):
    monkeypatch.setattr(harness, "TRACES", tmp_path / "traces")
    return trace_reduce.load(_write(tmp_path / "traces" / cell, **kw))


def test_metric_files_read_the_runs_trace(tmp_path, monkeypatch):
    runs = {cell: _cell_run(_cell_traces(tmp_path, monkeypatch, cell), cell)
            for cell in ("afs20-short-open", "afs20-ont-backlog")}
    for name, want in NEW.items():
        assert harness.reader(name)(runs["afs20-short-open"]) \
            == pytest.approx(want), name
    assert harness.reader("token_fill_pct.backlog")(
        runs["afs20-ont-backlog"]) == pytest.approx(100 * 1800 / 4096)


@pytest.mark.parametrize("case", ["no_program_spans", "other_window",
                                  "no_trace", "no_file"])
def test_new_readers_give_nothing_to_read(tmp_path, monkeypatch, case):
    """A program without the spans (the parent's), a trace that is not the
    run's, no trace, or no trace file left: no value, no error."""
    cell = "afs20-short-open"
    trace = _cell_traces(tmp_path, monkeypatch, cell,
                         program=case != "no_program_spans",
                         shift=5.0 if case == "other_window" else 0.0)
    if case == "other_window":
        trace = trace_reduce.load(_write(tmp_path / "elsewhere"))
    run = _cell_run(None if case == "no_trace" else trace, cell)
    if case == "no_file":
        monkeypatch.setattr(harness, "TRACES", tmp_path / "none")
    for name in NEW:
        assert harness.reader(name)(run) is None, name
