"""The benchmark's trace reduction, on a small trace the test writes."""

from __future__ import annotations

import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import harness, readers, trace_reduce  # noqa: E402

FUSED = "%fused_profile.1 = s32[8,128]{1,0} custom-call(s32[8,256] %p)"
TAIL = "%fusion = s32[20,8]{1,0} fusion(s32[8,128] %a)"
MS = 1_000_000          # ns


def _line(lid, name, events, names):
    ev = "".join(
        f"events {{ metadata_id: {names.index(n) + 1} "
        f"offset_ps: {int(s * 1000)} duration_ps: {int(d * 1000)} }}\n"
        for n, s, d in events)
    return f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0\n{ev}}}\n'


def _plane(pid, name, lines):
    names = sorted({n for _, evs in lines for n, _, _ in evs})
    body = "".join(_line(i + 1, ln, evs, names)
                   for i, (ln, evs) in enumerate(lines))
    meta = "".join(
        f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
        f'name: "{n.replace(chr(34), chr(92) + chr(34))}" }} }}\n'
        for i, n in enumerate(names))
    return f'planes {{ id: {pid} name: "{name}"\n{body}{meta}}}\n'


# A 100 ms window, two cohorts.  Device: fused kernel 10-40 and 60-90 ms,
# tail 40-42 and 90-92 ms, plus an op overlapping the first kernel.
# Host: service steps 5-45 and 55-95 ms with calls dispatched 8-10 and
# 58-60 ms, an idle poll step 50-51 ms.
DEVICE = [
    ("XLA Ops", [(FUSED, 10 * MS, 30 * MS), (FUSED, 60 * MS, 30 * MS),
                 (TAIL, 40 * MS, 2 * MS), (TAIL, 90 * MS, 2 * MS),
                 ("%copy.1 = s32[8]{0} copy(s32[8] %x)", 30 * MS, 5 * MS)]),
    ("XLA Modules", [("jit_fused_agreement(1)", 10 * MS, 30 * MS),
                     ("jit_fused_agreement(1)", 60 * MS, 30 * MS),
                     ("jit_from_agreement(2)", 40 * MS, 2 * MS),
                     ("jit_from_agreement(2)", 90 * MS, 2 * MS)]),
]
HOST = [
    ("python3", [("bench.window", 0, 100 * MS)]),
    ("worker", [("bench.step", 5 * MS, 40 * MS),
                ("bench.classify_batch", 8 * MS, 2 * MS),
                ("bench.step", 50 * MS, 1 * MS),
                ("bench.step", 55 * MS, 40 * MS),
                ("bench.classify_batch", 58 * MS, 2 * MS),
                ("other.span", 1 * MS, 1 * MS)]),
]


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    from jax.profiler import ProfileData
    txt = (_plane(1, "/device:TPU:0", DEVICE)
           + _plane(2, "/host:CPU", HOST))
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(txt))
    return trace_reduce.load(d.parents[2])


def test_events_are_read_from_the_file(trace):
    assert len(trace.ops[0]) == 5 and len(trace.modules[0]) == 4
    assert {e.name for e in trace.host} == {
        "bench.window", "bench.step", "bench.classify_batch"}
    assert (trace.window.start, trace.window.end) == (0, 100 * MS)


def test_busy_union_merges_overlapping_ops(trace):
    busy = trace_reduce.busy(trace, 0, 0, 100 * MS)
    assert busy == [(10 * MS, 42 * MS), (60 * MS, 92 * MS)]
    assert trace_reduce.length(busy) == 64 * MS
    assert trace_reduce.busy(trace, 0, 20 * MS, 70 * MS) == [
        (20 * MS, 42 * MS), (60 * MS, 70 * MS)]


def test_kernel_sums_and_gaps_between_calls(trace):
    ev = trace_reduce.kernel_events(trace, 0, readers.is_fused, 0, 100 * MS)
    assert [e.start for e in ev] == [10 * MS, 60 * MS]
    assert sum(e.dur for e in ev) == 60 * MS
    tail = trace_reduce.module_events(trace, 0, readers.TAIL_MODULE, 0,
                                      100 * MS)
    assert sum(e.dur for e in tail) == 4 * MS
    # The device is idle from 42 to 60 ms between the two calls.
    gap = trace_reduce.complement(trace_reduce.busy(trace, 0, 0, 100 * MS),
                                  ev[0].end, ev[1].start)
    assert trace_reduce.length(gap) == 18 * MS


def test_idle_time_is_attributed_to_the_host(trace):
    idle = trace_reduce.idle_attribution(trace, 0, 0, 100 * MS)
    # Idle 0-10, 42-60, 92-100 ms.  Working steps 5-45 (call ends at 10)
    # and 55-95 (call ends at 60): before-call idle 5-10 and 55-60,
    # after-call idle 42-45 and 92-95.
    assert idle[trace_reduce.IDLE_BEFORE] == 10 * MS
    assert idle[trace_reduce.IDLE_AFTER] == 6 * MS
    assert idle[trace_reduce.IDLE_NO_WORK] == 20 * MS
    assert len(trace_reduce.working_steps(trace, 0, 100 * MS)) == 2


def _run(trace):
    cfg = {"dim": 1024, "ngram": 16, "batch_size": 8}
    run = harness.Run(cfg=cfg, traffic={}, device_kind="TPU v5 lite",
                      window_s=0.1, window_reads=12, window_cohorts=2,
                      prototypes=128, species=20, trace=trace)
    run.calls = [np.array([150] * 8), np.array([150] * 4 + [0] * 4)]
    return run


def test_readers_on_the_trace(trace):
    run = _run(trace)
    assert readers.live_reads(run) == 12
    assert readers.fused_ms_per_kread(run) == pytest.approx(60 / 12 * 1e3)
    assert readers.tail_ms_per_kread(run) == pytest.approx(4 / 12 * 1e3)
    assert readers.host_gap_ms_per_cohort(run) == pytest.approx(8.0)
    assert readers.device_idle_pct(run) == pytest.approx(36.0)
    assert readers.cohort_fill_pct(run) == pytest.approx(75.0)
    ops = 3 * 1024 * 135 * 12 + 2 * 1024 * 128 * 12
    nbytes = 150 * 12 + 2 * 128 * 1024 / 8 + 4 * 20 * 12
    least = max(ops / 393e12, nbytes / 819e9)
    assert readers.fused_roofline_pct(run) == pytest.approx(
        100 * least / 0.060)


def test_readers_return_nothing_without_device_events(trace):
    run = _run(trace_reduce.Trace(ops={}, modules={}, host=trace.host))
    for read in (readers.fused_ms_per_kread, readers.fused_roofline_pct,
                 readers.tail_ms_per_kread, readers.host_gap_ms_per_cohort,
                 readers.device_idle_pct):
        assert read(run) is None


def test_rate_ends_at_the_last_cohort_finished_in_the_window():
    read = harness.reader("reads_per_s")
    run = harness.Run(cfg={}, traffic={}, device_kind="x", window_s=2.0,
                      window_reads=768,
                      finished=[(0.9, 256), (1.8, 512)])
    assert read(run) == pytest.approx(512 / 1.8)
    assert read(harness.Run(cfg={}, traffic={}, device_kind="x")) is None


def test_op_names_from_hlo_text():
    assert trace_reduce.op_name(FUSED) == "fused_profile.1"
    assert trace_reduce.short_name(FUSED) == "fused_profile.1 s32[8,128]{1,0}"
    assert readers.is_fused(FUSED) and not readers.is_fused(TAIL)
