"""Shared arithmetic of the per-layer metric readers.

Each file ``bench/metrics/<metric>.py`` is a small reader of one metric;
the ones that read the same quantity for different cells call into this
module.  A reader returns ``None`` when the run gave it nothing to read,
and the harness then leaves the metric out of the result line.

The fused kernel is the ``pallas_call`` of ``kernels/fused_profile.py``.
On a TPU v5e trace (JAX 0.9.0) its event on the ``XLA Ops`` line is the
custom call ``%fused_profile.<k> = s32[B,S_pad] custom-call(...)``, named
after the jitted ``fused_profile`` (``FUSED_OP``).  The classifier tail is
the module ``jit_from_agreement(<hash>)`` of the session's jitted
``from_agreement`` on the ``XLA Modules`` line (``TAIL_MODULE``).
"""

from __future__ import annotations

from bench import roofline, trace_reduce

FUSED_OP = "fused_profile"
TAIL_MODULE = "jit_from_agreement"


def is_fused(name: str) -> bool:
    return trace_reduce.op_name(name).split(".")[0] == FUSED_OP


def _window(run):
    span = run.span
    if span is None or not run.trace.ops.get(trace_reduce.DEVICE):
        return None
    return (run.trace, trace_reduce.DEVICE) + span


def live_reads(run) -> int:
    return int(sum(int((c > 0).sum()) for c in run.calls))


def fused_seconds(run) -> float | None:
    w = _window(run)
    if w is None:
        return None
    ev = trace_reduce.kernel_events(*w[:2], is_fused, *w[2:])
    return sum(e.dur for e in ev) / 1e9 if ev else None


def fused_ms_per_kread(run) -> float | None:
    t, n = fused_seconds(run), live_reads(run)
    if t is None or n == 0:
        return None
    return t * 1e3 / (n / 1e3)


def fused_roofline_pct(run) -> float | None:
    t = fused_seconds(run)
    if t is None or not run.calls:
        return None
    ops = nbytes = 0.0
    for lengths in run.calls:
        o, b = roofline.call_work(
            lengths, dim=run.cfg["dim"], ngram=run.cfg["ngram"],
            prototypes=run.prototypes, species=run.species)
        ops, nbytes = ops + o, nbytes + b
    least, _ = roofline.least_seconds(ops, nbytes, run.device_kind)
    return 100.0 * least / t


def tail_ms_per_kread(run) -> float | None:
    w = _window(run)
    n = live_reads(run)
    if w is None or n == 0:
        return None
    ev = trace_reduce.module_events(*w[:2], TAIL_MODULE, *w[2:])
    if not ev:
        return None
    return sum(e.dur for e in ev) / 1e6 / (n / 1e3)


def host_gap_ms_per_cohort(run) -> float | None:
    """Device-idle ms per cohort while a service step was running it."""
    w = _window(run)
    if w is None:
        return None
    steps = trace_reduce.working_steps(w[0], *w[2:])
    if not steps:
        return None
    idle = trace_reduce.idle_attribution(*w)
    host = idle[trace_reduce.IDLE_BEFORE] + idle[trace_reduce.IDLE_AFTER]
    return host / 1e6 / len(steps)


def cohort_fill_pct(run) -> float | None:
    if run.window_cohorts == 0:
        return None
    return 100.0 * run.window_reads / (run.window_cohorts
                                       * run.cfg["batch_size"])


def device_idle_pct(run) -> float | None:
    w = _window(run)
    if w is None or w[3] <= w[2]:
        return None
    busy = trace_reduce.length(trace_reduce.busy(*w))
    if busy == 0:
        return None
    return 100.0 * (1.0 - busy / (w[3] - w[2]))
