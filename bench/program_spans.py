"""The program's own spans in a benchmark trace, and the metrics they give.

The service and the session open host spans on the profiler's clock
(``repro.obs.span``).  Each service step that ran a cohort is one
``serve.step`` span whose arguments count the cohort: ``cohort`` (its
index), ``rows`` (live reads), ``slots`` (B), ``bucket`` (L), ``tokens``
(the live reads' lengths summed), ``requests`` (space-separated ids) and
``compiles`` (backend compiles during the step).  Its children tile it
in order on the pump thread: ``serve.admit`` (around one ``serve.pull``
per pull), ``serve.assemble``, ``session.dispatch`` (copies in, backend
call and tail launched), ``serve.wait`` (device time left and the copy
to the host) and ``serve.demux``.  A step whose streams all ended
without a read leaves a ``serve.step`` with no arguments, and is not a
cohort's step.

The harness reduces its trace to the benchmark's own ``bench.*`` spans
(``trace_reduce``); the readers here load the same ``.xplane.pb`` again,
the one under ``harness.TRACES`` with the run's window, and keep the
``serve.*`` and ``session.*`` events with their arguments.  A trace of a
program that opens no such spans gives every reader nothing.

    python3 bench/program_spans.py --workload afs20-short-open

prints, for the cell's last traced run, each phase's mean ms per cohort,
the device-idle time inside the benchmark's working steps and the share
of it under the program's spans, split by phase, and the compiles.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys

if __package__ in (None, ""):
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import trace_reduce  # noqa: E402

PREFIXES = ("serve.", "session.")
STEP = "serve.step"
#: The children of a step, in the order they tile it.
PHASES = ("serve.admit", "serve.assemble", "session.dispatch", "serve.wait",
          "serve.demux")
PULL = "serve.pull"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    line: int               # host thread, as the plane's line index
    start: float            # ns, on the device trace's clock
    end: float
    args: dict

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Program:
    spans: list[Span]
    window: tuple[float, float] | None      # the trace's bench.window


def from_profile(profile) -> Program:
    """The program spans of a ``ProfileData``, with their arguments."""
    spans: list[Span] = []
    window = None
    for plane in profile.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                start = float(e.start_ns)
                end = start + float(e.duration_ns)
                if e.name.startswith(PREFIXES):
                    spans.append(Span(e.name, k, start, end, dict(e.stats)))
                elif e.name == trace_reduce.WINDOW_SPAN and window is None:
                    window = (start, end)
    spans.sort(key=lambda s: s.start)
    return Program(spans=spans, window=window)


def load(log_dir: str | pathlib.Path) -> Program:
    """The program spans of the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_profile(ProfileData.from_file(str(files[-1])))


def steps(prog: Program, lo: float, hi: float) -> list[Span]:
    """``serve.step`` spans of cohorts, starting in ``[lo, hi)``."""
    return [s for s in prog.spans if s.name == STEP and "cohort" in s.args
            and lo <= s.start < hi]


def inside(prog: Program, outer: Span, name: str) -> list[Span]:
    """Spans named ``name`` nested in ``outer`` on its thread."""
    return [s for s in prog.spans if s.name == name and s.line == outer.line
            and outer.start <= s.start and s.end <= outer.end]


def phase_ms_per_cohort(prog: Program, name: str, lo: float, hi: float
                        ) -> float | None:
    """Mean ms of ``name`` spans per cohort's step in the window."""
    st = steps(prog, lo, hi)
    if not st:
        return None
    return sum(c.dur for s in st for c in inside(prog, s, name)) / 1e6 \
        / len(st)


def token_fill_pct(prog: Program, lo: float, hi: float) -> float | None:
    """Live tokens over slots x bucket, across the window's cohorts."""
    st = steps(prog, lo, hi)
    cap = sum(s.args["slots"] * s.args["bucket"] for s in st)
    if cap == 0:
        return None
    return 100.0 * sum(s.args["tokens"] for s in st) / cap


def _intersect(a, b) -> list[tuple[float, float]]:
    """Where two merged interval lists overlap, as intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_split(trace: trace_reduce.Trace, prog: Program, device: int,
               lo: float, hi: float) -> dict[str, float]:
    """Device-idle ns inside the benchmark's working steps, and how much
    of it lies under the program's spans, in all and per span name."""
    idle = trace_reduce.complement(trace_reduce.busy(trace, device, lo, hi),
                                   lo, hi)
    work = trace_reduce.union([(s.start, s.end) for s, _ in
                               trace_reduce.working_steps(trace, lo, hi)])
    idle = _intersect(idle, work)

    def under(names) -> float:
        return trace_reduce.overlap(idle, trace_reduce.union(
            [(s.start, s.end) for s in prog.spans if s.name in names]))

    out = {"idle_in_steps": trace_reduce.length(idle),
           "under_program_spans": under({s.name for s in prog.spans})}
    for name in (STEP,) + PHASES + (PULL,):
        out[name] = under({name})
    return out


# -- what the metric readers read ------------------------------------------------

_last: tuple[object, Program | None] | None = None     # (trace, spans)


def of_run(run) -> Program | None:
    """The program spans of the trace the run reduced, read once: the
    newest trace under ``harness.TRACES`` with the run's window."""
    global _last
    from jax.profiler import ProfileData

    from bench import harness
    if run.span is None:
        return None
    if _last is None or _last[0] is not run.trace:
        prog = None
        for f in sorted(pathlib.Path(harness.TRACES).rglob("*.xplane.pb"),
                        key=lambda p: -p.stat().st_mtime):
            prog = from_profile(ProfileData.from_file(str(f)))
            if prog.window == run.span:
                break
            prog = None
        _last = (run.trace, prog)
    return _last[1]


def _phase_reader(name: str):
    def read(run) -> float | None:
        prog = of_run(run)
        return None if prog is None else phase_ms_per_cohort(
            prog, name, *run.span)
    read.__doc__ = f"Mean ms of ``{name}`` per cohort's service step."
    return read


admit_ms_per_cohort = _phase_reader("serve.admit")
assemble_ms_per_cohort = _phase_reader("serve.assemble")
dispatch_ms_per_cohort = _phase_reader("session.dispatch")
demux_ms_per_cohort = _phase_reader("serve.demux")


def token_fill(run) -> float | None:
    prog = of_run(run)
    return None if prog is None else token_fill_pct(prog, *run.span)


# -- the command line -------------------------------------------------------------

def summary(trace: trace_reduce.Trace, prog: Program) -> dict:
    """Per-cohort phase times, idle split and compiles of one trace."""
    w = trace.window
    lo, hi = w.start, w.end
    st = steps(prog, lo, hi)
    split = idle_split(trace, prog, trace_reduce.DEVICE, lo, hi)
    idle = split["idle_in_steps"]
    return {
        "cohorts": len(st),
        "ms_per_cohort": {n: phase_ms_per_cohort(prog, n, lo, hi)
                          for n in (STEP,) + PHASES + (PULL,)},
        "token_fill_pct": token_fill_pct(prog, lo, hi),
        "compiles": sum(s.args.get("compiles", 0) for s in st),
        "idle_in_steps_ms": idle / 1e6,
        "idle_covered_pct": 100.0 * split["under_program_spans"] / idle
        if idle else None,
        "idle_ms_by_span": {n: split[n] / 1e6
                            for n in (STEP,) + PHASES + (PULL,)},
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    args = ap.parse_args(argv)
    from bench import harness
    d = harness.TRACES / args.workload
    print(json.dumps(summary(trace_reduce.load(d), load(d))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
