"""Observability layer: metrics math, trace assembly, and the two
contracts the serving stack stakes on it.

Acceptance contract (ISSUE 7): enabling metrics must not move a single
bit of profiler output on any backend (``reference``, ``pallas_fused``,
``sharded`` — and ``pcm_sim`` with device noise, whose stats read is a
separate compiled graph); and an assembled request trace's child spans
must tile the root span exactly, cancelled and failed requests
included.  Plus: histogram bucket/percentile/merge math, registry GC
(pinned refusal, ``keep_last``, ``max_age_s``, reclaimed bytes), and
the router/registry metric touchpoints.
"""

import math

import numpy as np
import pytest

from repro import obs
from repro.core.assoc_memory import build_refdb
from repro.core.hd_space import HDSpace
from repro.genomics import synth
from repro.pipeline import (ArraySource, ProfilerConfig, ProfilingSession,
                            SyntheticSource)
from repro.serve import (ProfilingService, RefDBRegistry, ServiceOverloaded,
                         TenantRouter)

SP = HDSpace(dim=512, ngram=5, z_threshold=3.0)
SPEC = synth.CommunitySpec(num_species=4, genome_len=6_000, seed=11)


def _config(**kw):
    kw.setdefault("space", SP)
    kw.setdefault("window", 1024)
    kw.setdefault("batch_size", 16)
    return ProfilerConfig(**kw)


@pytest.fixture(scope="module")
def sample():
    return SyntheticSource(SPEC, num_reads=96, present=[0, 2])


@pytest.fixture(scope="module")
def refdb(sample):
    return build_refdb(sample.genomes, SP, window=1024)


@pytest.fixture(scope="module")
def extra():
    rng = np.random.default_rng(99)
    return {"sp_new": rng.integers(0, 4, 6_000, dtype=np.int32)}


def _slices(sample, n):
    return [ArraySource(sample.tokens[i::n], sample.lengths[i::n])
            for i in range(n)]


# -- histogram bucket + percentile math --------------------------------------

def test_histogram_boundaries_and_overflow():
    state = obs.HistogramState((1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 2.0, 4.0, 5.0):     # bounds inclusive (le)
        state.observe(v)
    assert state.counts == [2, 1, 1, 1]     # last slot = overflow
    assert state.count == 5
    assert state.sum == pytest.approx(12.5)
    # ranks landing in the overflow bucket clamp to the last bound
    assert state.percentile(100) == 4.0


def test_histogram_percentile_interpolates_within_bucket():
    state = obs.HistogramState((10.0,))
    state.observe(3.0)                      # one sample, bucket [0, 10]
    assert state.percentile(50) == pytest.approx(5.0)
    state = obs.HistogramState((1.0, 2.0))
    for _ in range(2):
        state.observe(1.5)
    for _ in range(2):
        state.observe(0.5)
    assert state.percentile(50) == pytest.approx(1.0)
    assert state.percentile(100) == pytest.approx(2.0)


def test_histogram_empty_and_bad_args():
    state = obs.HistogramState((1.0,))
    assert math.isnan(state.percentile(50))
    assert math.isnan(state.mean)
    with pytest.raises(ValueError):
        state.percentile(101)
    with pytest.raises(ValueError):
        obs.HistogramState(())
    with pytest.raises(ValueError):
        obs.HistogramState((2.0, 1.0))      # not ascending


def test_histogram_merge():
    a = obs.HistogramState((1.0, 2.0))
    b = obs.HistogramState((1.0, 2.0))
    a.observe(0.5)
    b.observe(1.5)
    b.observe(9.0)
    a.merge(b)
    assert a.counts == [1, 1, 1]
    assert a.count == 3
    assert a.sum == pytest.approx(11.0)
    with pytest.raises(ValueError):
        a.merge(obs.HistogramState((1.0,)))


def test_registry_merge_from_and_merged():
    """The cross-host aggregation seam: merged() folds per-host
    registries into one snapshot with a ``host`` label on every series;
    an unlabelled merge_from accumulates same-label series."""
    a = obs.MetricsRegistry()
    b = obs.MetricsRegistry()
    a.counter("reads_total").inc(3, tenant="acme")
    b.counter("reads_total").inc(2, tenant="acme")
    a.histogram("lat_seconds", buckets=(0.1, 1.0)).observe(0.05)
    b.histogram("lat_seconds", buckets=(0.1, 1.0)).observe(5.0)
    b.gauge("queue_depth").set(7)

    fleet = obs.MetricsRegistry.merged({"h0": a, "h1": b})
    snap = fleet.snapshot()
    reads = {s["labels"]["host"]: s["value"]
             for s in snap["counters"]["reads_total"]["series"]}
    assert reads == {"h0": 3.0, "h1": 2.0}
    assert all(s["labels"]["tenant"] == "acme"
               for s in snap["counters"]["reads_total"]["series"])
    hosts = {s["labels"]["host"]
             for s in snap["histograms"]["lat_seconds"]["series"]}
    assert hosts == {"h0", "h1"}
    [g] = snap["gauges"]["queue_depth"]["series"]
    assert g["labels"] == {"host": "h1"} and g["value"] == 7.0

    total = obs.MetricsRegistry()       # no label: same series accumulate
    total.merge_from(a)
    total.merge_from(b)
    snap2 = total.snapshot()
    assert snap2["counters"]["reads_total"]["series"][0]["value"] == 5.0
    [h] = snap2["histograms"]["lat_seconds"]["series"]
    assert h["counts"] == [1, 0, 1]     # bucket-wise HistogramState.merge


def test_registry_get_or_create_and_kind_conflicts():
    reg = obs.MetricsRegistry()
    h = reg.histogram("x_seconds", buckets=(1.0, 2.0))
    assert reg.histogram("x_seconds", buckets=(1.0, 2.0)) is h
    with pytest.raises(ValueError, match="different buckets"):
        reg.histogram("x_seconds", buckets=(1.0,))
    reg.counter("x_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")
    with pytest.raises(ValueError):
        reg.counter("x_total").inc(-1)      # counters only go up


def test_snapshot_and_prometheus_exposition():
    reg = obs.MetricsRegistry()
    reg.counter("reads_total").inc(3, tenant="acme")
    lat = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
    lat.observe(0.05, backend="reference")
    lat.observe(5.0, backend="reference")
    snap = reg.snapshot()
    assert snap["counters"]["reads_total"]["series"][0] == {
        "labels": {"tenant": "acme"}, "value": 3.0}
    [series] = snap["histograms"]["lat_seconds"]["series"]
    assert series["labels"] == {"backend": "reference"}
    assert series["counts"] == [1, 0, 1]
    assert series["p50"] is not None
    text = reg.to_prometheus()
    assert 'reads_total{tenant="acme"} 3' in text
    assert 'lat_seconds_bucket{backend="reference",le="+Inf"} 2' in text
    assert 'lat_seconds_count{backend="reference"} 2' in text


def test_null_registry_is_inert():
    null = obs.NULL_METRICS
    assert not null.enabled
    c = null.counter("whatever_total")
    c.inc(5)
    assert c.value() == 0.0 and not c.enabled
    null.histogram("h").observe(1.0)
    assert math.isnan(null.histogram("h").percentile(50))
    assert null.instruments() == ()


# -- trace assembly -----------------------------------------------------------

def _timeline(*marks):
    tl = obs.RequestTimeline()
    for name, t in marks:
        tl.mark(name, at=t)
    return tl


def test_trace_children_tile_root_exactly():
    tl = _timeline(("submitted", 1.0), ("started", 1.5),
                   ("first_execute", 2.0), ("accumulate", 3.0),
                   ("finalize", 3.25), ("finished", 4.0))
    trace = obs.assemble_trace("r-0", tl, state="done")
    assert [s.name for s in trace.spans] == [
        "request", "admission", "schedule", "execute", "accumulate",
        "finalize"]
    children = trace.spans[1:]
    assert sum(s.duration_s for s in children) == trace.duration_s == 3.0
    assert all(s.parent_id == 0 for s in children)
    assert trace.span("schedule").duration_s == pytest.approx(0.5)


def test_trace_of_request_cancelled_while_queued():
    tl = _timeline(("submitted", 1.0), ("finished", 2.0))
    trace = obs.assemble_trace("r-1", tl, state="cancelled")
    assert trace.state == "cancelled"
    assert [s.name for s in trace.spans] == ["request", "admission"]
    assert trace.duration_s == pytest.approx(1.0)


def test_trace_stops_at_last_phase_reached():
    tl = _timeline(("submitted", 1.0), ("started", 2.0),
                   ("first_execute", 2.5), ("finished", 3.0))
    trace = obs.assemble_trace("r-2", tl, state="failed")
    assert [s.name for s in trace.spans] == [
        "request", "admission", "schedule", "execute"]
    assert sum(s.duration_s for s in trace.spans[1:]) == trace.duration_s


def test_timeline_first_wins_except_accumulate():
    tl = _timeline(("submitted", 1.0), ("submitted", 9.0),
                   ("accumulate", 2.0), ("accumulate", 3.0))
    assert tl.at("submitted") == 1.0
    assert tl.at("accumulate") == 3.0       # latest cohort demux
    with pytest.raises(ValueError, match="unknown timeline mark"):
        tl.mark("warp")
    with pytest.raises(ValueError, match="no marks"):
        obs.assemble_trace("r-3", obs.RequestTimeline())


def test_trace_recorder_keeps_first_n():
    rec = obs.TraceRecorder(sample=2)
    for i in range(4):
        tl = _timeline(("submitted", float(i)), ("finished", i + 1.0))
        rec.record(f"r-{i}", tl)
    assert rec.full
    assert [t.trace_id for t in rec.traces()] == ["r-0", "r-1"]
    null = obs.NULL_TRACER
    assert null.record("r", _timeline(("submitted", 0.0))) is None
    assert null.traces() == () and not null.enabled


# -- bit-exactness: metrics on == metrics off --------------------------------

@pytest.mark.parametrize("backend", ["reference", "pallas_fused", "sharded"])
def test_metrics_do_not_perturb_results(sample, refdb, backend):
    cfg = _config(backend=backend)
    off = ProfilingSession(cfg)
    off.adopt_refdb(refdb)
    reg = obs.MetricsRegistry()
    on = ProfilingSession(cfg, metrics=reg)
    on.adopt_refdb(refdb)
    src = _slices(sample, 1)[0]
    assert on.profile(src).to_json() == off.profile(src).to_json()
    # the enabled twin really recorded (the comparison wasn't vacuous)
    assert reg.counter("session_classify_batches_total").total() > 0
    assert reg.counter("session_classify_batches_total").total() \
        == math.ceil(len(sample.lengths) / cfg.batch_size)


def test_pcm_sim_metrics_bit_exact_with_device_noise(sample, refdb):
    """The stats read is a separate graph; its result math must match."""
    cfg = _config(backend="pcm_sim",
                  backend_options={"preset": "pcm", "seed": 3})
    src = _slices(sample, 1)[0]
    off = ProfilingSession(cfg)
    off.adopt_refdb(refdb)
    rep_off = off.profile(src).to_json()
    reg = obs.enable_metrics()              # backends resolve the global
    try:
        on = ProfilingSession(cfg)
        on.adopt_refdb(refdb)
        rep_on = on.profile(src).to_json()
    finally:
        obs.disable()
    assert rep_on == rep_off
    assert reg.counter("pcm_program_events_total").total() >= 1
    assert reg.counter("pcm_reads_total").total() > 0
    stuck = reg.gauge("pcm_stuck_cells")
    assert len(stuck.labelsets()) == 4      # {pos,neg} x {on,off}


# -- service + router end to end ---------------------------------------------

def test_service_metrics_and_traces_end_to_end(sample, refdb):
    cfg = _config(backend="reference")
    session = ProfilingSession(cfg)
    session.adopt_refdb(refdb)
    reg = obs.MetricsRegistry()
    rec = obs.TraceRecorder(sample=8)
    service = ProfilingService(session, max_active=2, max_queue=8,
                               metrics=reg, tracer=rec)
    srcs = _slices(sample, 4)
    handles = [service.submit(s) for s in srcs]
    service.run_until_idle()
    reads = sum(h.result(timeout=0).total_reads for h in handles)

    assert reg.counter("serve_requests_total").value(state="done") == 4
    assert reg.counter("serve_reads_classified_total").total() == reads
    assert reg.histogram("serve_admission_wait_seconds").merged().count == 4
    for phase in ("admit", "assemble", "dispatch", "wait", "demux"):
        assert reg.histogram("serve_step_phase_seconds").count(
            phase=phase) == service.cohorts_run > 0
    fill = reg.histogram("serve_cohort_fill_ratio",
                         buckets=obs.RATIO_BUCKETS).merged()
    assert fill.count > 0 and fill.sum <= fill.count    # ratios in (0, 1]
    assert reg.gauge("serve_queue_depth").value() == 0
    assert reg.gauge("serve_active_requests").value() == 0

    traces = rec.traces()
    assert len(traces) == 4
    for trace in traces:
        assert trace.state == "done"
        assert sum(s.duration_s for s in trace.spans[1:]) \
            == pytest.approx(trace.duration_s)
    # the trace clock IS the handle latency clock (one accounting)
    by_id = {t.trace_id: t for t in traces}
    for h in handles:
        assert by_id[h.request_id].duration_s \
            == pytest.approx(h.latency_s)
        assert h.queue_wait_s + h.service_s == pytest.approx(h.latency_s)


def test_cancelled_and_failed_requests_still_trace(sample, refdb):
    cfg = _config(backend="reference")
    session = ProfilingSession(cfg)
    session.adopt_refdb(refdb)
    reg = obs.MetricsRegistry()
    rec = obs.TraceRecorder(sample=8)
    service = ProfilingService(session, max_active=1, max_queue=8,
                               metrics=reg, tracer=rec)
    srcs = _slices(sample, 3)
    h_done = service.submit(srcs[0])
    service.run_until_idle()
    h_done.result(timeout=0)
    h_cancel = service.submit(srcs[1])
    assert h_cancel.cancel()                # still queued: cancellable
    h_fail = service.submit(srcs[2])
    service.fail_all(RuntimeError("injected"))
    service.run_until_idle()
    states = {t.trace_id: t.state for t in rec.traces()}
    assert states[h_cancel.request_id] == "cancelled"
    assert states[h_fail.request_id] == "failed"
    # cancelled/failed while queued: the trace stops at admission
    for h in (h_cancel, h_fail):
        trace = [t for t in rec.traces()
                 if t.trace_id == h.request_id][0]
        assert [s.name for s in trace.spans] == ["request", "admission"]
    assert reg.counter("serve_requests_total").value(state="cancelled") == 1
    assert reg.counter("serve_requests_total").value(state="failed") == 1


# -- host spans on the device trace's clock ------------------------------------

PHASES = ("serve.admit", "serve.assemble", "session.dispatch", "serve.wait",
          "serve.demux")


def _host_spans(log_dir):
    """``(line, name, start, end, stats)`` of every program span."""
    import pathlib

    from jax.profiler import ProfileData
    (f,) = pathlib.Path(log_dir).rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(f)).planes:
        if plane.name != "/host:CPU":
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(("serve.", "session.")):
                    out.append((k, e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def traced_service(tmp_path_factory, sample, refdb):
    """Four requests served under ``jax.profiler``, from cold caches."""
    import jax

    session = ProfilingSession(_config(backend="reference"))
    session.adopt_refdb(refdb)
    given = []
    classify = session.classify_batch

    def recorded(tokens, lengths, **kw):
        given.append((np.asarray(tokens), np.asarray(lengths)))
        return classify(tokens, lengths, **kw)

    session.classify_batch = recorded
    reg = obs.MetricsRegistry()
    service = ProfilingService(session, max_active=2, metrics=reg)
    handles = [service.submit(s) for s in _slices(sample, 4)]
    log_dir = tmp_path_factory.mktemp("jaxprof")
    jax.clear_caches()                  # the first cohort meets a new shape
    with obs.jax_trace(log_dir):
        service.run_until_idle()
    reports = [h.result(timeout=0).to_json() for h in handles]
    return {"spans": _host_spans(log_dir), "given": given, "reg": reg,
            "handles": handles, "reports": reports,
            "cohorts": service.cohorts_run}


def _steps(spans):
    """The ``serve.step`` spans of steps that ran a cohort."""
    return sorted((s for s in spans
                   if s[1] == "serve.step" and "cohort" in s[4]),
                  key=lambda s: s[2])


def _inside(spans, step, name):
    return [s for s in spans if s[1] == name and s[0] == step[0]
            and step[2] <= s[2] and s[3] <= step[3]]


def test_step_spans_nest_and_tile_the_step(traced_service):
    spans = traced_service["spans"]
    steps = _steps(spans)
    assert len(steps) == traced_service["cohorts"] == 6
    # Requests end on cohort boundaries here: only the drain's last step,
    # whose streams all ended without a read, ran no cohort, and its
    # span carries no arguments.
    bare = [s for s in spans if s[1] == "serve.step" and not s[4]]
    assert len(bare) <= 1 and len(bare) + len(steps) == sum(
        s[1] == "serve.step" for s in spans)
    for step in steps:
        children = [c for n in PHASES for c in _inside(spans, step, n)]
        assert [c[1] for c in sorted(children, key=lambda c: c[2])] \
            == list(PHASES)
        assert sum(c[3] - c[2] for c in children) \
            >= 0.9 * (step[3] - step[2])
        (admit,) = _inside(spans, step, "serve.admit")
        assert _inside(spans, admit, "serve.pull")     # one per pull
        (dispatch,) = _inside(spans, step, "session.dispatch")
        assert dispatch[4] == {"path": "encode_classify"}
    # every program span belongs to a step
    every = [s for s in spans if s[1] == "serve.step"]
    assert all(any(s[0] == st[0] and st[2] <= s[2] and s[3] <= st[3]
                   for st in every) for s in spans)


def test_step_span_counts_equal_the_arrays_dispatched(traced_service):
    steps = _steps(traced_service["spans"])
    for k, (step, (tokens, lengths)) in enumerate(
            zip(steps, traced_service["given"])):
        args = step[4]
        assert args["cohort"] == k
        assert args["rows"] == int((lengths > 0).sum())
        assert args["slots"] == lengths.shape[0] == tokens.shape[0]
        assert args["bucket"] == tokens.shape[1]
        assert args["tokens"] == int(lengths.sum())
    ids = {h.request_id for h in traced_service["handles"]}
    named = [set(st[4]["requests"].split()) for st in steps]
    assert all(n and n <= ids for n in named)
    assert set().union(*named) == ids
    # the padding counter counts what the spans leave out of slots x bucket
    padded = sum(st[4]["slots"] * st[4]["bucket"] - st[4]["tokens"]
                 for st in steps)
    assert traced_service["reg"].counter(
        "serve_cohort_padding_tokens_total").total() == padded > 0


def test_step_span_counts_compiles(traced_service):
    compiles = [st[4]["compiles"] for st in _steps(traced_service["spans"])]
    assert compiles[0] >= 1             # new shape: the session compiled
    assert compiles[1:] == [0] * (len(compiles) - 1)    # warm steps


def test_request_execute_phase_matches_its_cohorts(traced_service):
    """first_execute / accumulate are stamped with no registry or recorder
    enabled, inside the spans of the cohorts that carry the request id."""
    steps = _steps(traced_service["spans"])
    for h in traced_service["handles"]:
        mine = [st for st in steps if h.request_id in st[4]["requests"]
                .split()]
        assert mine
        execute = h.timeline.elapsed("first_execute", "accumulate")
        assert execute is not None and execute > 0
        assert execute <= (mine[-1][3] - mine[0][2]) / 1e9


def test_profiler_leaves_results_bit_identical(traced_service, sample,
                                               refdb):
    session = ProfilingSession(_config(backend="reference"))
    session.adopt_refdb(refdb)
    service = ProfilingService(session, max_active=2)
    handles = [service.submit(s) for s in _slices(sample, 4)]
    service.run_until_idle()
    assert [h.result(timeout=0).to_json() for h in handles] \
        == traced_service["reports"]


def test_spans_record_nothing_without_a_profiler(tmp_path):
    import jax

    assert not obs.capturing()
    with obs.span("serve.step", rows=3) as s:
        s.set_metadata(tokens=5)
    with obs.jax_trace(tmp_path):
        assert obs.capturing()
        with obs.span("serve.other", rows=4):
            pass
    assert not obs.capturing()
    assert [(s[1], s[4]) for s in _host_spans(tmp_path)] \
        == [("serve.other", {"rows": 4})]
    before = obs.compile_count()
    jax.jit(lambda x: x * 3 - 1)(np.arange(7)).block_until_ready()
    assert obs.compile_count() - before >= 1


def test_router_and_registry_metrics_touchpoints(tmp_path, sample, extra):
    reg = obs.MetricsRegistry()
    registry = RefDBRegistry(root=tmp_path / "r", metrics=reg)
    registry.create("food", sample.genomes, _config(backend="reference"))
    router = TenantRouter(registry, metrics=reg)
    router.add_tenant("acme", database="food", max_active=2, max_queue=0)
    router.add_tenant("tiny", database="food", max_active=1, max_queue=0)

    srcs = _slices(sample, 4)
    handles = [router.submit(s, tenant="acme") for s in srcs[:2]]
    router.submit(srcs[2], tenant="tiny")
    with pytest.raises(ServiceOverloaded):
        router.submit(srcs[3], tenant="tiny")
    registry.apply_delta("food", add=extra)         # auto hot-swap
    router.run_until_idle()
    reads = sum(h.result(timeout=300).total_reads for h in handles)
    router.step()                                   # final prune pass
    router.close()

    assert reg.counter("router_requests_total").value(tenant="acme") == 2
    assert reg.counter("router_quota_rejections_total") \
              .value(tenant="tiny") == 1
    assert reg.counter("router_reads_completed_total") \
              .value(tenant="acme") == reads
    assert reg.gauge("router_serving_version").value(database="food") == 2
    assert reg.histogram("router_hot_swap_seconds").merged().count == 1
    assert reg.histogram("router_drain_seconds").merged().count == 1
    assert reg.counter("refdb_publishes_total").value(database="food") == 2
    assert reg.gauge("refdb_current_version").value(database="food") == 2
    builds = reg.histogram("refdb_build_seconds")
    assert builds.count(database="food", kind="create") == 1
    assert builds.count(database="food", kind="delta") == 1


# -- registry garbage collection ---------------------------------------------

def _three_versions(tmp_path, sample, extra, metrics=None):
    registry = RefDBRegistry(root=tmp_path / "r", metrics=metrics)
    registry.create("food", sample.genomes, _config())
    registry.apply_delta("food", add=extra)
    registry.apply_delta("food", remove=["sp_new"])
    assert registry.versions("food") == (1, 2, 3)
    return registry


def test_gc_keep_last_and_reclaimed_bytes(tmp_path, sample, extra):
    reg = obs.MetricsRegistry()
    registry = _three_versions(tmp_path, sample, extra, metrics=reg)
    result = registry.gc("food", keep_last=1)
    assert result.collected == (("food", 1), ("food", 2))
    assert result.reclaimed_bytes > 0
    assert registry.versions("food") == (3,)
    assert not list((tmp_path / "r" / "food").glob("v1.npz"))
    assert reg.counter("refdb_gc_versions_total").total() == 2
    assert reg.counter("refdb_gc_reclaimed_bytes_total").total() \
        == result.reclaimed_bytes
    # idempotent: a second sweep finds nothing
    assert registry.gc("food", keep_last=1).collected == ()
    with pytest.raises(ValueError):
        registry.gc("food", keep_last=0)


def test_gc_refuses_pinned_versions(tmp_path, sample, extra):
    registry = _three_versions(tmp_path, sample, extra)
    registry.pin("food", 1)
    result = registry.gc("food", keep_last=1)
    assert result.collected == (("food", 2),)       # v1 pinned, v3 current
    assert registry.versions("food") == (1, 3)
    registry.release("food", 1)
    assert registry.gc("food", keep_last=1).collected == (("food", 1),)
    with pytest.raises(KeyError):
        registry.pin("food", 99)


def test_gc_max_age_is_a_further_filter(tmp_path, sample, extra):
    registry = _three_versions(tmp_path, sample, extra)
    # nothing is an hour old yet -> nothing collected despite keep_last
    assert registry.gc("food", keep_last=1,
                       max_age_s=3600).collected == ()
    assert registry.versions("food") == (1, 2, 3)
    assert registry.gc("food", keep_last=1,
                       max_age_s=0).collected == (("food", 1), ("food", 2))


def test_gc_never_collects_what_a_live_router_serves(tmp_path, sample,
                                                     extra):
    registry = RefDBRegistry(root=tmp_path / "r")
    registry.create("food", sample.genomes, _config(backend="reference"))
    router = TenantRouter(registry)
    router.add_tenant("acme", database="food")
    assert registry.pins("food") == {1: 1}          # served -> pinned
    srcs = _slices(sample, 2)
    h = router.submit(srcs[0], tenant="acme")
    registry.apply_delta("food", add=extra)         # swap; v1 drains
    # both versions are held: v1 draining h, v2 serving new admissions
    assert registry.gc("food", keep_last=1).collected == ()
    router.run_until_idle()
    h.result(timeout=300)
    router.step()                                   # retire drained v1
    assert registry.pins("food") == {2: 1}
    assert registry.gc("food", keep_last=1).collected == (("food", 1),)
    router.close()
