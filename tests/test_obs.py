"""Observability layer suite (span tracing, metrics, Chrome export).

Covers the ISSUE-6 acceptance criteria: concurrent span recording from
multiple threads while an export is in flight, trace-event JSON schema
validation (positive and negative), counter/gauge/histogram semantics,
the overhead guard for disabled tracing (the hot-path instrumentation
must allocate nothing when no tracer is active), the measured
readiness-stall EWMA -> ``Observation.serial_scale`` -> ``PlanCost``
closure, adaptive readahead pacing, and an end-to-end traced disk-tier
run whose timeline must show the dispatcher/collector main thread plus
both I/O-engine workers.
"""
import dataclasses
import json
import threading

import jax
import pytest

from repro.core import PhysicalPlan, load_graph
from repro.core.ooc import run_out_of_core
from repro.graph import PageRank, rmat_graph
from repro.obs import trace
from repro.obs.export import (chrome_trace, validate_chrome_trace,
                              write_chrome_trace)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               percentile)
from repro.obs.progress import fmt_plan, progress_line
from repro.planner import GraphStats, estimate
from repro.planner.adaptive import AdaptiveController
from repro.planner.stats import StatsCollector, SuperstepStats
from repro.storage.io_engine import IOEngine


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    """Every test starts and ends with tracing disabled — a tracer
    leaked across tests would defeat the overhead guard."""
    trace.stop()
    yield
    trace.stop()


# ------------------------------------------------------ overhead guard

def test_disabled_tracing_allocates_nothing():
    """With no active tracer every span() call returns the SAME cached
    no-op singleton (no per-call allocation on the hot path) and no
    event is buffered anywhere."""
    assert not trace.enabled()
    s1 = trace.span("a", "compute")
    s2 = trace.span("b", "dispatch")
    assert s1 is s2                       # the cached _NULL singleton
    assert trace.annotate("c") is s1
    with s1:
        pass                              # and it is a working no-op CM
    # the fire-and-forget paths are plain early returns
    assert trace.complete("x", "commit", 0.0, 1.0) is None
    assert trace.instant("y", "replan") is None
    assert trace.counter("z", 3) is None
    assert trace.get() is None


def test_disabled_annotate_with_kwargs_is_the_cached_null():
    """``annotate`` with span args, no tracer and no profiler session
    recording still returns the cached no-op singleton."""
    assert not jax.profiler.TraceAnnotation.is_enabled()
    s = trace.annotate("pregel.dispatch", "dispatch", superstep=3)
    assert s is trace.annotate("pregel.wait", "compute", superstep=4)
    assert s is trace.span("a", "compute")


def test_annotate_reaches_a_recording_profiler(tmp_path):
    """While a JAX profiler session records, ``annotate`` enters a
    ``TraceAnnotation`` carrying its kwargs: alone with no tracer, and
    beside the buffered span with one."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.annotate("bare", "compute", superstep=1) as a:
            assert isinstance(a, jax.profiler.TraceAnnotation)
        tr = trace.start()
        with trace.annotate("both", "compute", superstep=2) as a:
            assert isinstance(a._ann, jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
        trace.stop()
    assert [ev[1] for _, _, evs in tr.drain() for ev in evs] == ["both"]


def test_stop_detaches_and_disables():
    t = trace.start()
    with trace.span("work", "compute"):
        pass
    assert trace.stop() is t
    assert not trace.enabled()
    assert trace.span("late", "compute") is trace.span("later", "commit")
    assert t.n_events() == 1              # the detached buffer survives


# ------------------------------------------- recording + export schema

def test_span_events_round_trip_to_chrome_json(tmp_path):
    tr = trace.start()
    with trace.span("outer", "commit", q=2):
        with trace.span("inner", "fault"):
            pass
    trace.instant("mark", "replan", superstep=3)
    trace.counter("depth", 5)
    tracer = trace.stop()
    assert tracer is tr
    obj = chrome_trace(tracer)
    summary = validate_chrome_trace(obj)
    assert summary["spans"] == 2
    assert summary["span_threads"] == 1
    assert set(summary["categories"]) == {"commit", "fault"}
    by_name = {e["name"]: e for e in obj["traceEvents"]}
    assert by_name["outer"]["ph"] == "X"
    assert by_name["outer"]["args"] == {"q": 2}
    assert by_name["inner"]["dur"] <= by_name["outer"]["dur"]
    assert by_name["mark"]["ph"] == "i"
    assert by_name["depth"]["ph"] == "C"
    assert by_name["depth"]["args"]["value"] == 5
    assert all(e.get("ts", 0) >= 0 for e in obj["traceEvents"])
    # file writer emits loadable JSON and the CLI validator accepts it
    p = tmp_path / "trace.json"
    trace.start()
    with trace.span("w", "compute"):
        pass
    write_chrome_trace(str(p))
    reloaded = json.loads(p.read_text())
    assert validate_chrome_trace(reloaded)["spans"] == 1
    from repro.obs.export import main as export_main
    assert export_main([str(p), "--min-threads", "1"]) == 0


def test_explicit_time_complete_spans():
    trace.start()
    trace.complete("stall", "dispatch", 10.0, 10.25, q=1)
    trace.complete("inverted", "commit", 5.0, 4.0)  # clamped, not negative
    tracer = trace.stop()
    events = [ev for _, _, evs in tracer.drain() for ev in evs]
    spans = {e[1]: e for e in events if e[0] == "X"}
    assert spans["stall"][3] == 10.0
    assert spans["stall"][4] == pytest.approx(0.25)
    assert spans["inverted"][4] == 0.0
    validate_chrome_trace(chrome_trace(tracer))


def test_concurrent_recording_while_exporting():
    """N worker threads record spans while the main thread repeatedly
    exports; nothing is lost and every thread gets its own track."""
    n_threads, per_thread = 4, 200
    trace.start()
    # keep all workers alive until everyone recorded: OS thread idents
    # are reused after exit, which would merge tracks in the export
    gate = threading.Barrier(n_threads + 1)

    def worker(k):
        gate.wait()
        for _ in range(per_thread):
            with trace.span(f"w{k}", "readahead"):
                pass
        gate.wait()

    threads = [threading.Thread(target=worker, args=(k,), daemon=True)
               for k in range(n_threads)]
    for th in threads:
        th.start()
    gate.wait()
    # export concurrently with recording — must never raise (the first
    # snapshots may race ahead of any span, hence min_threads=0)
    for _ in range(20):
        validate_chrome_trace(chrome_trace(trace.get()), min_threads=0)
    gate.wait()
    for th in threads:
        th.join()
    tracer = trace.stop()
    obj = chrome_trace(tracer)
    summary = validate_chrome_trace(obj, min_threads=n_threads)
    assert summary["spans"] == n_threads * per_thread
    assert summary["span_threads"] == n_threads


def test_schema_validation_rejects_malformed_traces():
    with pytest.raises(ValueError, match="top level"):
        validate_chrome_trace([])
    with pytest.raises(ValueError, match="must be a list"):
        validate_chrome_trace({"traceEvents": {}})
    ok = {"ph": "X", "name": "s", "cat": "compute", "pid": 1, "tid": 1,
          "ts": 0.0, "dur": 1.0}
    with pytest.raises(ValueError, match="unknown phase"):
        validate_chrome_trace({"traceEvents": [{**ok, "ph": "Z"}]})
    bad = dict(ok)
    del bad["tid"]
    with pytest.raises(ValueError, match="missing name/pid/tid"):
        validate_chrome_trace({"traceEvents": [bad]})
    with pytest.raises(ValueError, match="unknown category"):
        validate_chrome_trace({"traceEvents": [{**ok, "cat": "nonsense"}]})
    with pytest.raises(ValueError, match="bad ts"):
        validate_chrome_trace({"traceEvents": [{**ok, "ts": -1.0}]})
    with pytest.raises(ValueError, match="bad dur"):
        validate_chrome_trace({"traceEvents": [{**ok, "dur": None}]})
    with pytest.raises(ValueError, match="need >= 2"):
        validate_chrome_trace({"traceEvents": [ok]}, min_threads=2)
    # and the valid event passes
    assert validate_chrome_trace({"traceEvents": [ok]})["spans"] == 1


def test_export_cli_lists_every_violation(tmp_path, capsys):
    """The --validate CLI collects ALL schema violations in one run and
    exits nonzero — CI logs show every problem at once, not just the
    first raise."""
    from repro.obs.export import main as export_main, trace_violations
    ok = {"ph": "X", "name": "s", "cat": "compute", "pid": 1, "tid": 1,
          "ts": 0.0, "dur": 1.0}
    broken = {"traceEvents": [
        {**ok, "ph": "Z"},                      # unknown phase
        {k: v for k, v in ok.items() if k != "tid"},  # missing tid
        {**ok, "cat": "nonsense"},              # unknown category
        {**ok, "ts": -1.0},                     # bad ts
        {**ok, "dur": None},                    # bad dur
    ]}
    errs, summary = trace_violations(broken)
    assert len(errs) == 5
    # same scan order as the raise-first validator: the first collected
    # violation IS the one validate_chrome_trace raises
    with pytest.raises(ValueError, match="unknown phase"):
        validate_chrome_trace(broken)
    assert "unknown phase" in errs[0]
    assert summary["events"] == 5
    p = tmp_path / "broken.json"
    p.write_text(json.dumps(broken))
    assert export_main([str(p)]) == 1
    out = capsys.readouterr().out
    assert "INVALID" in out and "5 violation(s)" in out
    for needle in ("unknown phase", "missing name/pid/tid",
                   "unknown category", "bad ts", "bad dur"):
        assert needle in out


# -------------------------------------------------------------- metrics

def test_counter_interval_is_a_delta():
    c = Counter()
    c.inc(3)
    assert c.interval() == 3
    assert c.interval() == 0              # nothing new since the mark
    c.inc(2)
    assert c.snapshot() == 5              # snapshot stays cumulative
    assert c.interval() == 2


def test_gauge_reports_last_level():
    g = Gauge()
    g.set(7)
    assert g.interval() == 7.0
    assert g.snapshot() == 7.0
    assert g.interval() == 7.0            # interval does not reset a level


def test_histogram_percentiles_and_reset():
    h = Histogram()
    for v in range(1, 11):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 10
    assert snap["mean"] == pytest.approx(5.5)
    assert snap["p50"] in (5.0, 6.0)
    assert snap["p90"] in (9.0, 10.0)
    assert snap["max"] == 10.0
    first = h.interval()                  # same numbers, then resets
    assert first == snap
    assert h.interval()["count"] == 0
    # bounded reservoir: overflow still counts, percentiles stay sane
    small = Histogram(cap=8)
    for v in range(100):
        small.observe(v)
    s = small.interval()
    assert s["count"] == 100 and s["max"] == 99.0


def test_percentile_nearest_rank():
    assert percentile([], 0.5) == 0.0
    assert percentile([4.0], 0.9) == 4.0
    assert percentile([1.0, 2.0, 3.0], 0.0) == 1.0
    assert percentile([1.0, 2.0, 3.0], 1.0) == 3.0


def test_registry_get_or_create_and_interval_merge():
    reg = MetricsRegistry()
    assert reg.counter("a") is reg.counter("a")
    assert reg.histogram("h") is reg.histogram("h")
    reg.counter("a").inc(4)
    reg.gauge("g").set(2)
    reg.histogram("h").observe(9)
    view = reg.interval()
    assert view["a"] == 4 and view["g"] == 2.0
    assert view["h"]["count"] == 1 and view["h"]["max"] == 9.0
    assert reg.interval()["a"] == 0       # counters/hists reset per call
    # snapshot is the non-destructive cumulative view
    reg.counter("a").inc(1)
    assert reg.snapshot()["a"] == 5
    assert reg.snapshot()["a"] == 5
    assert MetricsRegistry().interval() == {}


def test_stats_collector_merges_registry_interval():
    reg = MetricsRegistry()
    sc = StatsCollector(n_partitions=4, vertex_capacity=16, msg_dims=1,
                        n_vertices=40, metrics=reg)
    reg.counter("io.reads").inc(5)
    rec = sc.record(0, active=10, messages=3, wall_s=0.01)
    assert rec.extra["metrics"]["io.reads"] == 5
    rec2 = sc.record(1, active=10, messages=3, wall_s=0.01)
    assert rec2.extra["metrics"]["io.reads"] == 0   # per-superstep delta
    assert rec.as_dict()["metrics"]["io.reads"] == 5


# -------------------------- satellite 1: measured stall -> plan pricing

_G = GraphStats(n_vertices=100_000, n_edges=800_000, n_partitions=8,
                vertex_capacity=16_250, edge_capacity=100_000,
                value_dims=2, msg_dims=1)


def _stall_rec(stall_s, *, superstep=5, recompiled=False):
    return SuperstepStats(
        superstep=superstep, active=100_000, messages=400_000,
        frontier_density=1.0, wall_s=0.01, recompiled=recompiled,
        extra={"ooc": True, "streaming": True, "barrier_free": True,
               "super_partitions": 4, "readiness_stall_s": stall_s})


def test_measured_stall_scales_the_serial_plan_leg():
    """Observation -> PlanCost closure: the EWMA'd measured stall shifts
    every candidate's serial inbox-rebuild price by the measured/analytic
    ratio (the ISSUE-6 'planner's serial-leg price demonstrably shifts'
    criterion)."""
    plan = PhysicalPlan(join="full_outer")
    ctrl = AdaptiveController(PageRank(_G.n_vertices, iterations=5),
                              _G, plan)
    # analytic serial leg of the current plan, no measurement yet
    base_obs = ctrl._make_observation(_stall_rec(0.0))
    assert base_obs.serial_scale == 1.0 and base_obs.stall_ewma_s < 0.0
    base = estimate(plan, _G, base_obs, ctrl.machine)
    assert base.serial_seconds > 0.0
    # observe a stall 3x the analytic estimate
    rec = _stall_rec(3.0 * base.serial_seconds)
    ctrl._update_stall_ewma(rec)
    assert ctrl._stall_ewma == pytest.approx(3.0 * base.serial_seconds)
    obs = ctrl._make_observation(rec)
    assert obs.serial_scale == pytest.approx(3.0, rel=1e-6)
    assert obs.stall_ewma_s == pytest.approx(ctrl._stall_ewma)
    scaled = estimate(plan, _G, obs, ctrl.machine)
    assert scaled.serial_seconds == pytest.approx(3.0 * base.serial_seconds)
    assert scaled.terms["inbox_rebuild"] == pytest.approx(
        3.0 * base.terms["inbox_rebuild"])
    # the scale is plan-INDEPENDENT: a 4-way barrier-free candidate keeps
    # its 1/4 analytic advantage under the measured multiplier
    bf1 = dataclasses.replace(obs, barrier_free=False, super_partitions=1)
    assert estimate(plan, _G, bf1, ctrl.machine).serial_seconds == \
        pytest.approx(4.0 * scaled.serial_seconds)


def test_stall_ewma_smooths_and_skips_recompiles():
    ctrl = AdaptiveController(PageRank(_G.n_vertices, iterations=5),
                              _G, PhysicalPlan(join="full_outer"))
    ctrl._update_stall_ewma(_stall_rec(1.0))
    assert ctrl._stall_ewma == pytest.approx(1.0)
    # recompile supersteps are poisoned by jit time -> skipped
    ctrl._update_stall_ewma(_stall_rec(50.0, recompiled=True))
    assert ctrl._stall_ewma == pytest.approx(1.0)
    # in-memory records (no stall key) are skipped too
    ctrl._update_stall_ewma(SuperstepStats(superstep=6, wall_s=0.01))
    assert ctrl._stall_ewma == pytest.approx(1.0)
    ctrl._update_stall_ewma(_stall_rec(2.0))
    a = ctrl.config.stall_alpha
    assert ctrl._stall_ewma == pytest.approx(a * 2.0 + (1 - a) * 1.0)
    # the calibration multiplier is clamped against outliers
    ctrl._stall_ewma = 1e9
    obs = ctrl._make_observation(_stall_rec(1e9))
    assert obs.serial_scale == 8.0
    ctrl._stall_ewma = 1e-12
    obs = ctrl._make_observation(_stall_rec(1e-12))
    assert obs.serial_scale == 0.125
    # and it round-trips through the checkpointed controller state
    ctrl._stall_ewma = 0.5
    state = ctrl.state_dict()
    ctrl2 = AdaptiveController(PageRank(_G.n_vertices, iterations=5),
                               _G, PhysicalPlan(join="full_outer"))
    ctrl2.load_state(state)
    assert ctrl2._stall_ewma == pytest.approx(0.5)


# ------------------------- satellite 1b: adaptive readahead pacing

class _DummyPool:
    def wants_prefetch(self, key):
        return False

    def dirty_eviction_candidates(self, limit):
        return []


def test_autopace_matches_faults_to_the_compute_window():
    eng = IOEngine(_DummyPool(), threads=1, readahead_pages=8)
    try:
        assert eng.readahead_pages == 8   # starts at the ceiling
        # 4 faults in 40ms -> 10ms/fault; a 50ms compute window hides 5
        with eng._mu:
            eng._int_reads, eng._int_read_s = 4, 0.040
        assert eng.autopace(0.050) == 5
        # deep window -> clamped at the configured ceiling
        with eng._mu:
            eng._int_reads, eng._int_read_s = 4, 0.040
        assert eng.autopace(10.0) == 8
        # compute window shorter than one fault -> floor of 1
        with eng._mu:
            eng._int_reads, eng._int_read_s = 4, 0.040
        assert eng.autopace(0.001) == 1
        # no faults observed this superstep -> depth unchanged
        assert eng.autopace(1.0) == 1
        # the sample is consumed: a second call sees no data
        with eng._mu:
            eng._int_reads, eng._int_read_s = 2, 0.002
        eng.autopace(0.010)
        assert eng.autopace(10.0) == eng.readahead_pages
    finally:
        eng.close()


# ------------------------------------------------------- progress lines

def test_progress_line_formats_the_record():
    rec = {"superstep": 7, "active": 12_400, "frontier_density": 0.19,
           "messages": 48_200, "wall_s": 0.031, "cache_hit_rate": 0.97,
           "readiness_stall_s": 0.0021, "readahead_depth": 4}
    line = progress_line(rec, PhysicalPlan(join="left_outer"))
    assert "superstep   7" in line
    assert "active 12.4k (19.0%)" in line
    assert "msgs 48.2k" in line and "wall 0.031s" in line
    assert "hit 0.97" in line and "stall 2.1ms" in line
    assert "ra 4" in line
    assert "plan left_outer/" in line
    assert "recompile" not in line
    # omitted fields simply drop out; events/recompiles are flagged
    assert "hit" not in progress_line({"superstep": 0, "active": 5,
                                       "wall_s": 0.1})
    assert "[recompile]" in progress_line({"superstep": 0, "active": 5,
                                           "wall_s": 0.1,
                                           "recompiled": True})
    assert "[plan-switch]" in progress_line({"superstep": 3,
                                             "event": "plan-switch"})
    assert fmt_plan(None) == ""


def test_progress_line_shows_sharded_exchange_extras():
    """The PR 8 sharded extras render SI-formatted when present and
    drop out otherwise."""
    rec = {"superstep": 2, "active": 220, "messages": 1200,
           "wall_s": 0.01, "exchange_stall_s": 0.0042,
           "exchange_bytes": 1_300_000}
    line = progress_line(rec)
    assert "xstall 4.2ms" in line
    assert "xbytes 1.3M" in line
    bare = progress_line({"superstep": 2, "active": 220, "wall_s": 0.01})
    assert "xstall" not in bare and "xbytes" not in bare


# --------------------------------------------- end-to-end traced run

def test_traced_disk_tier_run_shows_all_pipeline_threads(tmp_path):
    """The acceptance criterion: a barrier-free disk-tier run with
    tracing on yields a valid Chrome trace with spans from the
    dispatcher/collector main thread and BOTH io-engine workers, the
    readiness stall visible as a span, and queue-depth percentiles +
    registry metrics in the per-superstep stats."""
    n = 220
    edges = rmat_graph(n, 1200, seed=7)
    prog = PageRank(n, iterations=6)
    vert = load_graph(edges, n, P=4, value_dims=2)
    progress = []
    trace.start()
    try:
        res = run_out_of_core(
            vert, prog, prog.suggested_plan, budget_partitions=1,
            max_supersteps=8, stream=True, barrier_free=True,
            memory_budget_bytes=16 * 1024, disk_dir=str(tmp_path / "sp"),
            eviction="mru", io_threads=2,
            on_superstep=lambda i, rec: progress.append((i, rec)))
    finally:
        tracer = trace.stop()
    obj = chrome_trace(tracer)
    summary = validate_chrome_trace(obj, min_threads=3)
    assert summary["spans"] > 0
    assert any("pregelix-io" in nm for nm in summary["thread_names"])
    names = {e["name"] for e in obj["traceEvents"] if e["ph"] == "X"}
    assert {"dispatch", "commit", "collect_wait", "prepare", "fold",
            "superstep", "readiness_stall"} <= names
    assert "fault_bg" in names or "page_fault" in names
    cats = set(summary["categories"])
    assert {"dispatch", "compute", "collect", "commit"} <= cats
    # counter tracks for the Perfetto area charts
    counters = {e["name"] for e in obj["traceEvents"] if e["ph"] == "C"}
    assert {"active", "messages", "io_queue_depth"} <= counters
    # satellite 2: real within-superstep queue-depth percentiles
    recs = [s for s in res.stats if "wall_s" in s]
    assert recs
    for s in recs:
        assert s["io_queue_depth_p90"] >= s["io_queue_depth_p50"] >= 0
        assert s["io_queue_depth_max"] >= s["io_queue_depth_p90"]
        assert 1 <= s["readahead_depth"] <= 8
        assert s["metrics"]["io.queue_depth"]["count"] >= 0
    assert any(s["metrics"]["io.queue_depth"]["count"] > 0 for s in recs)
    # the on_superstep callback saw every superstep record, in order,
    # and the records render as progress lines
    assert [i for i, _ in progress] == [s["superstep"] for s in recs]
    for i, rec in progress:
        assert f"superstep {i:>3}" in progress_line(rec, res.plan)


def test_tracing_overhead_free_run_records_nothing():
    """A run WITHOUT trace.start() must leave the module disabled and
    buffer zero events (the instrumentation is permanently in the hot
    path, so this is the regression guard for its cost)."""
    n = 120
    edges = rmat_graph(n, 600, seed=3)
    prog = PageRank(n, iterations=4)
    vert = load_graph(edges, n, P=4, value_dims=2)
    assert not trace.enabled()
    res = run_out_of_core(vert, prog, prog.suggested_plan,
                          budget_partitions=2, max_supersteps=6)
    assert res.supersteps > 0
    assert trace.get() is None            # nothing got started implicitly
    with pytest.raises(ValueError):
        chrome_trace()                    # and there is nothing to export
