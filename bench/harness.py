"""Runs one benchmark cell against the profiler's served path.

``run.py`` parses the command line, checks for the chip and calls
:func:`run_cell`.  Everything that belongs to one cell is found by name:
the manifest (``BENCHMARK.json``) names the cell's configuration and
traffic; ``bench/configs/<file>.json`` and ``bench/traffic/<traffic>.json``
hold their parameters; ``bench/metrics/<metric>.py`` reads each metric;
and the configuration's ``reference`` key names its plain reference,
``bench/<reference>``, whose ``Reference`` class the check uses.

A run: load the configuration's reference (a missing or broken file ends
set-up), make the genomes and requests from the seed, load the RefDB
through the program's content-keyed store (a seed's first run builds it
there first, timed apart from set-up), build the ``ProfilingService``,
warm each cohort length the service pads the cell's reads to (a
read it refuses ends set-up with :class:`SetupError`), start its
background worker, and drive it for ``seconds`` (open loop: requests sent
at their due times; closed loop: a fixed number of requests kept in
flight).  Then the program's state is freed and the reference checks a
seeded sample of what the service answered.  With ``trace`` on, the
window runs under ``jax.profiler`` with spans around the benchmark's
calls into the service, and the per-layer metrics are read from that
trace.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import itertools
import json
import math
import pathlib
import shutil
import threading
import time

import numpy as np

from bench import loadgen, trace_reduce

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
STORE = BENCH / "store"           # the program's RefDB store, one per seed
TRACES = BENCH / "traces"
COMPILE_CACHE = BENCH / ".jax_cache"

#: Reads of the sampled requests that the reference re-profiles, in bases.
CHECK_BASES = 3_000_000
#: How long past the window's close a due request is awaited.
GRACE_S = 60.0
#: Limits of the numbers that decide ``correct`` (PERF.md section 2 gives
#: the readings each was set from).  ``failed`` counts requests refused,
#: failed, or not answered within :data:`GRACE_S` of the close.
LIMITS = {"failed": 0, "score_diff": 0, "count_diff": 0,
          "abundance_diff": 1e-9, "prototype_diff": 0}


def is_correct(checked: dict[str, float]) -> bool:
    return all(checked[k] <= LIMITS[k] for k in LIMITS)


class SetupError(Exception):
    """Set-up cannot go on; ``run.py`` prints the message as one line and
    exits non-zero before any window opens."""


# -- what the manifest names -------------------------------------------------

def manifest(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_files(man: dict, name: str, root: pathlib.Path = ROOT
               ) -> tuple[dict, dict]:
    """The configuration and traffic dicts of cell ``name``."""
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in man["configs"]}
    cfg = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return cfg, traffic


def cell_metrics(man: dict, name: str, trace: bool) -> list[dict]:
    """End-to-end (``trace`` off) or per-layer metrics this cell reports."""
    group = man["per_layer"] if trace else man["end_to_end"]
    return [m for m in group if name in m.get("workloads", [name])]


def _module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, root: pathlib.Path = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    return _module(root / "bench" / "metrics" / f"{name}.py",
                   "bench_metric_" + name).read


def reference(cfg: dict, root: pathlib.Path = ROOT):
    """The ``Reference`` class of ``bench/<cfg["reference"]>``.

    ``Reference(cfg, genomes)`` holds the RefDB's ``prototypes`` and the
    species' prototype ``bounds``, and gives ``scores``, ``classify`` and
    ``report`` as ``bench/reference.py`` does.  A file that is missing,
    does not load or has no such class raises :class:`SetupError`.
    """
    path = root / "bench" / cfg["reference"]
    try:
        return _module(path, "bench_reference_" + path.stem).Reference
    except Exception as e:
        raise SetupError(
            f"bench: configuration {cfg['name']!r} has no usable reference "
            f"{cfg['reference']!r}: {e!r}") from e


# -- what one run records ----------------------------------------------------

@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    cfg: dict
    traffic: dict
    device_kind: str
    setup_s: float = 0.0
    build_s: float | None = None      # a seed's first run: the RefDB build
    window_s: float = 0.0
    window_reads: int = 0             # reads demuxed into requests
    window_cohorts: int = 0
    # (seconds from the window's start, reads demuxed since) at the end of
    # each cohort that finished inside the window
    finished: list[tuple[float, int]] = dataclasses.field(
        default_factory=list)
    latencies_s: list[float] = dataclasses.field(default_factory=list)
    drain_s: float = 0.0              # last answer after the window closed
    lateness_s: list[float] = dataclasses.field(default_factory=list)
    prototypes: int = 0
    species: int = 0
    calls: list[np.ndarray] = dataclasses.field(default_factory=list)
    trace: trace_reduce.Trace | None = None

    @property
    def span(self) -> tuple[float, float] | None:
        w = self.trace.window if self.trace is not None else None
        return None if w is None else (w.start, w.end)


def nearest_rank(values: list[float], pct: float) -> float:
    """Exact order statistic: the smallest value with ``pct``% at or below."""
    v = sorted(values)
    return v[max(0, math.ceil(pct / 100 * len(v)) - 1)]


def padded_length(service, length: int) -> int:
    """The cohort length ``service`` pads a read of ``length`` bases to;
    :class:`SetupError` where it refuses the read.

    It asks the service's public ``bucket_for`` and ``buckets`` where it
    has both, else its scheduler's (``_sched.bucket_for``,
    ``_sched.buckets``); where neither has them, :class:`SetupError` too.
    """
    for owner in (service, getattr(service, "_sched", None)):
        try:
            bucket_for, buckets = owner.bucket_for, owner.buckets
            break
        except AttributeError:
            continue
    else:
        raise SetupError(
            "bench: neither the service nor its scheduler has "
            "bucket_for/buckets to pad reads with")
    try:
        return bucket_for(max(int(length), 1))
    except ValueError:
        raise SetupError(
            f"bench: the service refuses reads of {int(length)} bp: its "
            f"largest bucket is {buckets[-1]}") from None


def warm_lengths(service, requests) -> list[int]:
    """Every cohort length the service can pad this cell's reads to; a
    refused read raises :class:`SetupError` naming the longest."""
    lengths = np.unique(np.concatenate([r.lengths for r in requests]))
    return sorted({padded_length(service, x) for x in lengths[::-1]})


def build_service(cfg: dict, traffic: dict, genomes: np.ndarray,
                  log=print):
    """The program's session, with its RefDB of ``genomes``, and its
    service, not started; with the RefDB build's seconds, ``None`` where
    the store held it already."""
    from repro.core import HDSpace
    from repro.pipeline import ProfilerConfig, ProfilingSession
    from repro.serve import ProfilingService

    names = [f"species_{s:02d}" for s in range(len(genomes))]
    config = ProfilerConfig(
        space=HDSpace(dim=cfg["dim"], ngram=cfg["ngram"],
                      alphabet_size=cfg["alphabet"],
                      z_threshold=cfg["z_threshold"], seed=cfg["space_seed"]),
        window=cfg["window"], batch_size=cfg["batch_size"],
        backend=cfg["backend"])
    session = ProfilingSession(config)
    # A deployment builds its RefDB once, offline, and loads it at start:
    # on a seed's first run the build is timed apart and left out of
    # set-up, and every run then loads the RefDB from the store.
    named = {n: g.astype(np.int32) for n, g in zip(names, genomes)}
    build_s, t_build = None, time.perf_counter()
    db = session.build_or_load_refdb(named, cache_dir=STORE)
    if not session.refdb_loaded_from_cache:
        build_s = time.perf_counter() - t_build
        db = session.build_or_load_refdb(named, cache_dir=STORE)
        log(f"refdb built in {build_s} s (not in setup_s)")
    log(f"refdb loaded: {db.num_prototypes} prototypes, "
        f"{db.memory_bytes()} bytes")
    service = ProfilingService(session, max_active=traffic["max_active"],
                               max_queue=traffic["max_queue"])
    return session, service, build_s


class _Recorder:
    """Wraps the service's step and the session's ``classify_batch``.

    Every run keeps, for each cohort, the token and length arrays the
    service assembled and the device array of species scores the call
    returned (no copy, no transfer), so the check can compare the scores
    of sampled cohorts, and in ``done`` the host clock and the service's
    ``reads_classified`` at the end of each step that ran a cohort.  With
    ``trace`` on it also opens a span around
    each step and call, and after the window closes, ``close`` holds new
    cohorts back until the profiler has stopped: the trace then holds
    exactly the cohorts recorded in ``traced``, each complete.
    """

    def __init__(self, service, session, trace: bool):
        import contextlib
        import jax
        ann = jax.profiler.TraceAnnotation if trace else (
            lambda name: contextlib.nullcontext())
        self._cond = threading.Condition()
        self._closing = False
        self.cohorts: list[tuple[np.ndarray, np.ndarray, object]] = []
        self.traced: list[np.ndarray] | None = [] if trace else None
        self.done: list[tuple[float, int]] = []
        self._service = service
        step, classify = service.step, session.classify_batch

        def recorded_step():
            with ann(trace_reduce.STEP_SPAN):
                ran = step()
            if ran:
                self.done.append((time.perf_counter(),
                                  service.reads_classified))
            return ran

        def recorded_classify(tokens, lengths, **kw):
            with self._cond:
                while self._closing:
                    self._cond.wait()
                if self.traced is not None:
                    self.traced.append(np.asarray(lengths))
            with ann(trace_reduce.CALL_SPAN):
                res = classify(tokens, lengths, **kw)
            self.cohorts.append((tokens, lengths, res.classification.scores))
            return res

        service.step = recorded_step
        session.classify_batch = recorded_classify

    def close(self) -> None:
        with self._cond:
            self._closing = True
            n = len(self.traced)
        while self._service.cohorts_run < n and self._service.error is None:
            time.sleep(0.001)

    def release(self) -> None:
        with self._cond:
            self._closing = False
            self._cond.notify_all()


# -- the two loops -------------------------------------------------------------

def _close_window(service, run: Run, t0: float, base: tuple[int, int],
                  done: list[tuple[float, int]]) -> None:
    t_end = time.perf_counter()
    run.window_s = t_end - t0
    run.window_reads = service.reads_classified - base[0]
    run.window_cohorts = service.cohorts_run - base[1]
    run.finished = [(t - t0, n - base[0]) for t, n in list(done)
                    if t0 < t <= t_end]


def _open_loop(service, requests, seconds, run: Run, done):
    """Send each request at its due time; await all of them."""
    from repro.pipeline import ArraySource
    from repro.serve import ServiceOverloaded

    recs = [{"req": r} for r in requests]
    threads = []
    t0 = time.perf_counter()
    base = service.reads_classified, service.cohorts_run

    def wait(rec, handle):
        left = t0 + seconds + GRACE_S - time.perf_counter()
        try:
            rec["report"] = handle.result(timeout=max(left, 0.0))
            rec["done"] = time.perf_counter()
        except TimeoutError:
            rec["missing"] = True
        except Exception as e:                    # a failed request
            rec["error"] = repr(e)

    for rec in recs:
        rec["due"] = t0 + rec["req"].due_s
        pause = rec["due"] - time.perf_counter()
        if pause > 0:
            time.sleep(pause)
        rec["sent"] = time.perf_counter()
        try:
            h = service.submit(ArraySource(rec["req"].tokens,
                                           rec["req"].lengths))
        except ServiceOverloaded as e:
            rec["error"] = repr(e)
            continue
        finally:
            rec["submit_s"] = time.perf_counter() - rec["sent"]
        th = threading.Thread(target=wait, args=(rec, h), daemon=True)
        th.start()
        threads.append(th)
    pause = t0 + seconds - time.perf_counter()
    if pause > 0:
        time.sleep(pause)
    _close_window(service, run, t0, base, done)
    run.lateness_s = [r["sent"] - r["due"] for r in recs]
    return recs, threads


def _await_open(recs, threads, run: Run):
    for th in threads:
        th.join(GRACE_S + 5)
    run.latencies_s = [r["done"] - r["due"] for r in recs if "done" in r]
    if run.latencies_s:
        run.drain_s = max(r["done"] for r in recs if "done" in r) - (
            recs[0]["due"] + run.window_s)


def _closed_loop(service, requests, clients, seconds, run: Run, done):
    """Keep ``clients`` requests in flight, replacing each one that ends
    by a new request for the next sample, in turn."""
    from repro.pipeline import ArraySource

    pool = itertools.cycle(requests)
    live: list[tuple[dict, object]] = []
    recs = []

    def send():
        req = next(pool)
        rec = {"req": req}
        recs.append(rec)
        live.append((rec, service.submit(ArraySource(req.tokens,
                                                     req.lengths))))

    t0 = time.perf_counter()
    base = service.reads_classified, service.cohorts_run
    for _ in range(clients):
        send()
    t_end = t0 + seconds
    while time.perf_counter() < t_end:
        for item in list(live):
            rec, h = item
            if h.done:
                live.remove(item)
                rec["final"] = h
                send()
        time.sleep(min(0.01, max(t_end - time.perf_counter(), 0)))
    _close_window(service, run, t0, base, done)
    for rec, h in live:
        rec["snapshot"] = h.snapshot()
    for rec in recs:
        h = rec.pop("final", None)
        if h is not None:
            try:
                rec["report"] = h.result(timeout=0)
            except Exception as e:                # a failed request
                rec["error"] = repr(e)
        elif "snapshot" in rec:
            rec["report"] = rec.pop("snapshot")
    return recs


# -- the check -------------------------------------------------------------------

def _sample(items, size, seed: int, stream: int, first=None) -> list:
    """Seeded sample of ``items`` until their ``size`` reaches
    :data:`CHECK_BASES`, with ``first`` always in it."""
    out = [] if first is None else [first]
    bases = 0 if first is None else size(first)
    for i in loadgen.rng(seed, stream).permutation(len(items)):
        if bases >= CHECK_BASES:
            break
        if items[i] is not first:
            out.append(items[i])
            bases += size(items[i])
    return out


def sample_requests(recs, seed: int) -> list[dict]:
    """Answered requests to re-profile, the largest always among them."""
    answered = [r for r in recs if "report" in r]
    if not answered:
        return []
    return _sample(
        answered,
        lambda r: int(r["req"].lengths[:r["report"].total_reads].sum()),
        seed, 99, max(answered, key=lambda r: r["report"].total_reads))


def sample_cohorts(cohorts, seed: int) -> list:
    """Cohorts whose per-read species scores the reference recomputes."""
    return _sample(cohorts, lambda c: int(np.asarray(c[1]).sum()), seed, 98)


def compare(ref, protos: np.ndarray, requests, cohorts) -> dict[str, float]:
    """Re-profile sampled cohorts and requests with the plain reference.

    ``ref`` is the configuration's :func:`reference`, built for the run's
    genomes; ``protos`` the program's RefDB.  ``cohorts`` are ``(tokens,
    lengths, scores)`` as the service ran them: every live read's score
    for every species must equal the reference's.  ``requests`` carry the
    service's report, which must equal the reference's report of the same
    reads.

    A report covers a prefix of its request's reads: one of ``k`` reads
    (``total_reads``), a final report or a window-end ``snapshot()``, is
    compared with the reference's report of the request's first ``k``
    reads in the order they were sent, so a service that classifies reads
    out of order must still commit each request's results in read order.
    """
    out = {"score_diff": 0, "count_diff": 0, "abundance_diff": 0.0,
           "prototype_diff": int((ref.prototypes != protos).sum())}
    for tokens, lengths, scores in cohorts:
        lengths = np.asarray(lengths)
        live = lengths > 0
        want = ref.scores(np.asarray(tokens)[live], lengths[live])
        out["score_diff"] += int((np.asarray(scores)[live] != want).sum())
    for rec in requests:
        got = rec["report"]
        k = got.total_reads
        want = ref.report(*ref.classify(rec["req"].tokens[:k],
                                        rec["req"].lengths[:k]))
        out["count_diff"] += (abs(got.total_reads - want["total"])
                              + abs(got.unmapped_reads - want["unmapped"])
                              + abs(got.multi_reads - want["multi"])
                              + int(np.abs(np.asarray(got.unique_counts)
                                           - want["unique_counts"]).sum()))
        out["abundance_diff"] = max(out["abundance_diff"], float(
            np.abs(np.asarray(got.abundance) - want["abundance"]).max()))
    return out


# -- one run -----------------------------------------------------------------------

def run_cell(*, cell: str, cfg: dict, traffic: dict, seed: int,
             seconds: float, trace: bool, t_start: float, metrics: list[dict],
             log=print, root: pathlib.Path = ROOT) -> tuple[dict, Run]:
    """Set up, drive the window, check; the result line and the run.

    The reference and the metric readers are found under ``root``.
    Raises :class:`SetupError`, before any window, where the configuration
    has no usable reference or the service refuses one of the cell's
    read lengths.
    """
    import jax

    device = jax.devices()[0]
    run = Run(cfg=cfg, traffic=traffic, device_kind=device.device_kind)
    phases = {"start": time.perf_counter() - t_start}
    Reference = reference(cfg, root)
    wl = loadgen.make(cfg, traffic, seed, seconds)
    phases["data"] = time.perf_counter() - t_start
    session, service, run.build_s = build_service(cfg, traffic, wl.genomes,
                                                  log)
    db = session.refdb
    run.prototypes, run.species = db.num_prototypes, db.num_species
    phases["refdb"] = time.perf_counter() - t_start
    b = cfg["batch_size"]
    for length in warm_lengths(service, wl.requests):
        res = session.classify_batch(np.zeros((b, length), np.int32),
                                     np.full(b, length, np.int32))
        np.asarray(res.classification.hits)
        np.asarray(res.classification.category)
    recorder = _Recorder(service, session, trace)
    trace_dir = TRACES / cell
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
    service.start()
    phases["warm-up"] = time.perf_counter() - t_start
    run.setup_s = phases["warm-up"] - (run.build_s or 0.0)
    log("setup: " + ", ".join(f"{k} done at {v} s" for k, v in
                              phases.items())
        + f"; setup_s {run.setup_s} s")
    if trace:
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        window = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        window.__enter__()
    try:
        if wl.loop == "open":
            recs, threads = _open_loop(service, wl.requests, seconds, run,
                                       recorder.done)
        else:
            recs = _closed_loop(service, wl.requests, wl.clients, seconds,
                                run, recorder.done)
    finally:
        if trace:
            recorder.close()
            window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            run.calls = recorder.traced
            recorder.release()
    if wl.loop == "open":
        _await_open(recs, threads, run)
        service.stop(drain=True, timeout=GRACE_S)
    else:
        service.stop(drain=False, timeout=GRACE_S)
    if run.lateness_s:
        late = sorted(run.lateness_s)
        worst = max(recs, key=lambda r: r["sent"] - r["due"])
        log(f"generator lateness: max {late[-1]} s (request due at "
            f"{worst['req'].due_s} s), p95 {nearest_rank(late, 95)} s, "
            f"median {nearest_rank(late, 50)} s over {len(late)} requests; "
            f"longest submit() {max(r['submit_s'] for r in recs)} s")
    if len(run.finished) > 1:
        ends = np.array([t for t, _ in run.finished])
        gaps = np.diff(ends)
        log(f"window: {len(ends)} cohorts finished by {ends[-1]} s; between "
            f"cohort ends median {np.median(gaps)} s, longest {gaps.max()} s "
            f"(ending at {ends[1:][gaps.argmax()]} s)")
    stats = device.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    protos = np.asarray(db.prototypes)
    cohorts = [(t, ln, np.asarray(sc)) for t, ln, sc in
               sample_cohorts(recorder.cohorts, seed)]
    del service, session, db, recorder
    gc.collect()

    failed = sum(1 for r in recs if "error" in r or r.get("missing"))
    checked = {"failed": failed}
    t_check = time.perf_counter()
    sample = sample_requests(recs, seed)
    checked.update(compare(Reference(cfg, wl.genomes), protos, sample,
                           cohorts))
    log(f"check: reference re-profiled {len(cohorts)} cohorts and "
        f"{len(sample)} requests ({sum(r['report'].total_reads for r in sample)}"
        f" reads) in {time.perf_counter() - t_check} s")
    correct = is_correct(checked)

    if trace:
        run.trace = trace_reduce.load(trace_dir)
    values = {}
    for m in metrics:
        v = reader(m["name"], root)(run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": correct, "attempted": len(recs), "failed": failed,
              "metrics": values,
              "device": {"platform": device.platform, "kind": run.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": peak}}
    if trace and run.span is not None:
        lo, hi = run.span
        dev = trace_reduce.DEVICE
        result["device"]["busy_s"] = trace_reduce.length(
            trace_reduce.busy(run.trace, dev, lo, hi)) / 1e9
        result["device"]["window_s"] = (hi - lo) / 1e9
        idle = trace_reduce.idle_attribution(run.trace, dev, lo, hi)
        result["breakdown"] = {
            "device_ops": [list(x) for x in
                           trace_reduce.top_ops(run.trace, dev, lo, hi)],
            "idle_gaps": sorted(([k, v / 1e9] for k, v in idle.items()),
                                key=lambda x: -x[1])}
    result["compared"] = {k: {"value": checked[k], "limit": LIMITS[k]}
                          for k in LIMITS}
    return result, run
