"""``gram_step_pct``: the fused kernel's gram steps over the padded
shape's, read from the ``session.dispatch`` spans of a small trace the
test writes (the program-spans test's trace, with the two arguments)."""

from __future__ import annotations

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

import test_bench_program_spans as sp  # noqa: E402

from bench import gram_steps, harness, program_spans, trace_reduce  # noqa: E402

MS = sp.MS
# Cohort 0: one 8-row tile of 150-bp reads in the 256 bucket (135 of 241
# grams); cohort 1: four such reads and four padding rows, still one tile.
STEPS = [{"gram_steps": 8 * 135, "gram_steps_shape": 8 * 241},
         {"gram_steps": 8 * 135, "gram_steps_shape": 8 * 241}]
WANT = 100 * (2 * 8 * 135) / (2 * 8 * 241)


def _program(with_steps: bool):
    """The program-spans test's spans; its dispatches carry the steps."""
    steps = iter(STEPS)
    return [(n, s, d, {**a, **next(steps)} if with_steps
             and n == "session.dispatch" else a)
            for n, s, d, a in sp.PROGRAM]


def _write(d: pathlib.Path, with_steps=True) -> pathlib.Path:
    from jax.profiler import ProfileData
    host = [sp.BENCH[0], ("worker", sp.BENCH[1][1] + _program(with_steps))]
    txt = (sp._plane(1, "/device:TPU:0", sp.DEVICE)
           + sp._plane(2, "/host:CPU", host))
    out = d / "plugins" / "profile" / "t"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(txt))
    return d


def test_gram_step_pct_of_the_window(tmp_path):
    prog = program_spans.load(_write(tmp_path))
    assert gram_steps.gram_step_pct(prog, 0, 100 * MS) == pytest.approx(WANT)
    # A window that holds only the first cohort's dispatch.
    assert gram_steps.gram_step_pct(prog, 0, 50 * MS) == pytest.approx(
        100 * 135 / 241)
    assert gram_steps.gram_step_pct(prog, 96 * MS, 100 * MS) is None


@pytest.mark.parametrize("cell,name", [
    ("afs20-short-open", "gram_step_pct.open"),
    ("afs20-ont-backlog", "gram_step_pct.backlog")])
@pytest.mark.parametrize("case", ["steps", "no_steps", "no_program_spans",
                                  "no_trace"])
def test_metric_files_read_gram_steps(tmp_path, monkeypatch, cell, name,
                                      case):
    """A known ratio from the run's trace; nothing, and no error, from a
    program whose dispatches lack the arguments (the parent's), one with
    no program spans, or a run with no trace."""
    monkeypatch.setattr(harness, "TRACES", tmp_path / "traces")
    d = tmp_path / "traces" / cell
    if case == "no_program_spans":
        sp._write(d, program=False)
    else:
        _write(d, with_steps=case == "steps")
    trace = None if case == "no_trace" else trace_reduce.load(d)
    got = harness.reader(name)(sp._cell_run(trace, cell))
    if case == "steps":
        assert got == pytest.approx(WANT)
    else:
        assert got is None
