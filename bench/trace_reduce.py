"""Reduce a ``jax.profiler`` trace to device busy time, kernel time and
idle gaps attributed to what the host was doing.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes under
``<dir>/plugins/profile/<time>/``, read with ``jax.profiler.ProfileData``.
Device planes are named ``/device:TPU:<k>``; each has an ``XLA Ops`` line
(one event per operation the chip ran) and an ``XLA Modules`` line (one
event per jitted program).  The host plane ``/host:CPU`` holds the
``TraceAnnotation`` spans that the benchmark opens around its calls into
the service (``bench.window``, ``bench.step``, ``bench.classify_batch``).
All times are nanoseconds on one clock.
"""

from __future__ import annotations

import dataclasses
import pathlib

DEVICE_PREFIX = "/device:TPU:"
DEVICE = 0              # the chip of a one-chip cell, /device:TPU:0
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

WINDOW_SPAN = "bench.window"
STEP_SPAN = "bench.step"
CALL_SPAN = "bench.classify_batch"

IDLE_BEFORE = "host: admit, assemble, dispatch (service step before the call)"
IDLE_AFTER = "host: results to host, demux (service step after the call)"
IDLE_NO_WORK = "service idle: no reads queued"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float        # ns
    end: float          # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    """The events a reduction needs, clipped to nothing yet."""
    ops: dict[int, list[Event]]          # device id -> XLA op events
    modules: dict[int, list[Event]]      # device id -> XLA module events
    host: list[Event]                    # bench.* spans, all threads

    def spans(self, name: str) -> list[Event]:
        return sorted((e for e in self.host if e.name == name),
                      key=lambda e: e.start)

    @property
    def window(self) -> Event | None:
        w = self.spans(WINDOW_SPAN)
        return w[0] if w else None


def _events(line) -> list[Event]:
    return [Event(e.name, float(e.start_ns),
                  float(e.start_ns) + float(e.duration_ns))
            for e in line.events]


def from_profile(profile) -> Trace:
    """Collect the device and host events of a ``ProfileData``."""
    ops: dict[int, list[Event]] = {}
    modules: dict[int, list[Event]] = {}
    host: list[Event] = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = int(plane.name[len(DEVICE_PREFIX):])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(dev, []).extend(_events(line))
                elif line.name == MODULES_LINE:
                    modules.setdefault(dev, []).extend(_events(line))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(e for e in _events(line)
                            if e.name.startswith("bench."))
    return Trace(ops=ops, modules=modules, host=host)


def load(log_dir: str | pathlib.Path) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_profile(ProfileData.from_file(str(files[-1])))


def op_name(event_name: str) -> str:
    """``fused_profile.1`` from ``%fused_profile.1 = s32[..] custom-call(..)``.

    TPU traces name an op event by its whole HLO instruction; the name is
    the part before `` = ``, without the ``%``.
    """
    return event_name.split(" = ", 1)[0].lstrip("%")


def short_name(event_name: str) -> str:
    """The op's name and result type, for a breakdown."""
    head, _, rest = event_name.partition(" = ")
    return " ".join([head.lstrip("%"), rest.split(" ", 1)[0]]).strip()


# -- interval arithmetic -----------------------------------------------------

def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge overlapping ``(start, end)`` intervals, sorted."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def overlap(a, b) -> float:
    """Total length where two merged interval lists overlap."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def complement(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The gaps of merged ``busy`` intervals inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in clip(busy, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


# -- reductions ----------------------------------------------------------------

def busy(trace: Trace, device: int, lo: float, hi: float):
    """Merged intervals in which any operation ran on ``device``."""
    return clip(union([(e.start, e.end) for e in trace.ops.get(device, [])]),
                lo, hi)


def kernel_events(trace: Trace, device: int, match, lo: float, hi: float
                  ) -> list[Event]:
    """Op events whose name satisfies ``match``, starting in the window."""
    return sorted((e for e in trace.ops.get(device, [])
                   if match(e.name) and lo <= e.start < hi),
                  key=lambda e: e.start)


def module_events(trace: Trace, device: int, prefix: str, lo: float,
                  hi: float) -> list[Event]:
    return [e for e in trace.modules.get(device, [])
            if e.name.startswith(prefix) and lo <= e.start < hi]


def working_steps(trace: Trace, lo: float, hi: float
                  ) -> list[tuple[Event, Event]]:
    """``(step, call)`` pairs: service steps that dispatched a cohort."""
    calls = trace.spans(CALL_SPAN)
    out = []
    for st in trace.spans(STEP_SPAN):
        if st.end <= lo or st.start >= hi:
            continue
        inner = [c for c in calls if st.start <= c.start and c.end <= st.end]
        if inner:
            out.append((st, inner[0]))
    return out


def idle_attribution(trace: Trace, device: int, lo: float, hi: float
                     ) -> dict[str, float]:
    """Device-idle ns in ``[lo, hi]`` by what the service host was doing.

    Idle time inside a working service step is the host path: before the
    call returns (admission, row assembly, dispatch) or after it (waiting
    for the results to reach the host, demultiplexing).  Idle time outside
    every working step is time in which no cohort was ready.
    """
    idle = complement(busy(trace, device, lo, hi), lo, hi)
    steps = working_steps(trace, lo, hi)
    before = union([(s.start, c.end) for s, c in steps])
    after = union([(c.end, s.end) for s, c in steps])
    b, a = overlap(idle, before), overlap(idle, after)
    return {IDLE_BEFORE: b, IDLE_AFTER: a,
            IDLE_NO_WORK: max(length(idle) - b - a, 0.0)}


def top_ops(trace: Trace, device: int, lo: float, hi: float, k: int = 10
            ) -> list[tuple[str, float]]:
    """Device ops by total seconds inside the window, most first."""
    tot: dict[str, float] = {}
    for e in trace.ops.get(device, []):
        d = min(e.end, hi) - max(e.start, lo)
        if d > 0:
            n = short_name(e.name)
            tot[n] = tot.get(n, 0.0) + d
    return sorted(((n, v / 1e9) for n, v in tot.items()),
                  key=lambda x: -x[1])[:k]
