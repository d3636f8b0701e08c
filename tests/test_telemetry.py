"""Telemetry subsystem (ISSUE 8): the guarded metrics registry and span
recorder (dark-path overhead, ring wraparound, Perfetto nesting), counter
thread-safety under the evaluation runtime's worker pool (with the race
detector's TrackedLock substituted in), the exactly-once ``note_round``
coverage for every controller, the unified ``stats()`` contract, the
observation-only (decision-parity) guarantee, and the report dashboard +
CLI."""

import json
import threading
import time

import numpy as np
import pytest

import repro.telemetry as telemetry
from repro.analysis.racecheck import TrackedLock
from repro.core import (
    EC2_CATALOG_ADJUSTED,
    ConfigSpace,
    Dimension,
    EvalDispatcher,
    EvalRequest,
    EvalResult,
    FleetController,
    Objective,
    ProcurementController,
    SizingController,
    SurrogateAnnealer,
    TenantSpec,
    TraceReplayController,
    make_ec2_space,
)
from repro.core.costmodel import SimulatedEvaluator
from repro.core.instrumentation import ROUND_HOOKS
from repro.core.sizing import SizingSpace
from repro.telemetry import registry as reg_mod
from repro.telemetry import report, spans as spans_mod
from repro.telemetry.registry import MetricsRegistry
from repro.telemetry.spans import SpanRecorder, span, traced
from repro.workloads.microservice import (
    ContainerSize,
    MicroserviceDAG,
    RequestClass,
    ServiceTier,
)
from repro.workloads.trace import synthetic_trace


@pytest.fixture(autouse=True)
def _dark_telemetry():
    """Each test starts with both sinks detached and ends the same way,
    restoring whatever was armed outside (e.g. REPRO_TELEMETRY=1 CI)."""
    prev = telemetry.get()
    telemetry.disable()
    yield
    telemetry.disable()
    if prev is not None:
        telemetry.enable(metrics=prev.metrics, spans=prev.spans,
                         meta=prev.meta)


# ---------------------------------------------------------------------------
# registry: kinds, ring wraparound, snapshots
# ---------------------------------------------------------------------------


def test_counter_gauge_basics():
    r = MetricsRegistry()
    r.counter("c").inc()
    r.counter("c").inc(2.5)
    r.gauge("g").set(3)
    r.gauge("g").set(7)                      # last write wins
    assert r.counter("c").value == 3.5
    assert r.gauge("g").value == 7.0


def test_series_ring_wraparound_keeps_newest():
    s = MetricsRegistry().series("s", capacity=4)
    for i in range(10):
        s.append(float(i))
    assert len(s) == 4
    assert s.dropped == 6
    t, v = s.points()
    assert v == [6.0, 7.0, 8.0, 9.0]         # oldest first
    assert t == [6.0, 7.0, 8.0, 9.0]         # t defaults to append index
    s2 = MetricsRegistry().series("s2", capacity=4)
    s2.append(1.0, t=42.0)                   # explicit timestamps stick
    assert s2.points() == ([42.0], [1.0])


def test_histogram_summary_percentiles():
    h = MetricsRegistry().histogram("h", capacity=256)
    for i in range(1, 101):
        h.observe(float(i))
    s = h.summary()
    assert s["count"] == 100 and s["min"] == 1.0 and s["max"] == 100.0
    assert s["mean"] == pytest.approx(50.5)
    assert 45 <= s["p50"] <= 55 and 85 <= s["p90"] <= 95
    assert MetricsRegistry().histogram("e").summary()["count"] == 0


def test_snapshot_prefix_filter_and_json():
    r = MetricsRegistry()
    r.counter("fleet/a").inc()
    r.counter("trace/b").inc()
    r.series("fleet/s").append(1.0)
    r.gauge("fleet").set(9)                  # exact-name match kept too
    snap = r.snapshot(prefix="fleet")
    assert set(snap["counters"]) == {"fleet/a"}
    assert set(snap["series"]) == {"fleet/s"}
    assert set(snap["gauges"]) == {"fleet"}
    json.dumps(r.snapshot())                 # plain-JSON contract


# ---------------------------------------------------------------------------
# the dark path: null-span identity + overhead guard
# ---------------------------------------------------------------------------


def test_disabled_writes_are_noops():
    assert reg_mod.get() is None
    reg_mod.inc("x")
    reg_mod.record("x", 1.0)
    reg_mod.set_gauge("x", 1.0)
    reg_mod.observe("x", 1.0)
    assert reg_mod.get() is None             # nothing sprang into being


def test_null_span_singleton_identity():
    """The overhead claim as an identity, not a timing: with no sinks,
    span() returns the one shared no-op object."""
    assert span("a") is span("b") is spans_mod._NULL_SPAN
    with span("a"):                          # and it is a working CM
        pass
    # a metric= request only escalates when a metrics sink is attached
    assert span("a", metric="m") is spans_mod._NULL_SPAN
    with telemetry.session():
        assert span("a") is not spans_mod._NULL_SPAN


def test_dark_path_overhead_guard():
    """100k guarded writes + spans while dark.  The bound is absolute
    and extremely generous (a broken guard that allocates per call is
    orders of magnitude slower); identity is tested above."""
    t0 = time.perf_counter()
    for _ in range(100_000):
        reg_mod.inc("x")
        with span("y"):
            pass
    assert time.perf_counter() - t0 < 5.0


# ---------------------------------------------------------------------------
# spans: nesting, Perfetto export, ring wraparound
# ---------------------------------------------------------------------------


def test_span_nesting_depth_and_perfetto_containment():
    with telemetry.session() as tel:
        with span("outer", cat="test"):
            with span("inner1"):
                pass
            with span("inner2", args={"k": 1}):
                pass
    recs = tel.spans.spans()                 # completion order
    assert [r[0] for r in recs] == ["inner1", "inner2", "outer"]
    depth = {r[0]: r[5] for r in recs}
    assert depth == {"outer": 0, "inner1": 1, "inner2": 1}

    events = tel.spans.to_trace_events()
    meta = [e for e in events if e["ph"] == "M"]
    xs = {e["name"]: e for e in events if e["ph"] == "X"}
    assert meta and meta[0]["args"]["name"] == "main"
    assert xs["outer"]["cat"] == "test"
    assert xs["inner1"]["cat"] == "repro"    # default category
    assert xs["inner2"]["args"] == {"k": 1}
    for inner in ("inner1", "inner2"):       # ts containment
        assert xs["outer"]["ts"] <= xs[inner]["ts"]
        assert (xs[inner]["ts"] + xs[inner]["dur"]
                <= xs["outer"]["ts"] + xs["outer"]["dur"] + 1e-6)
    json.dumps({"traceEvents": events})


def test_span_recorder_ring_wraparound(tmp_path):
    with telemetry.session(span_capacity=3) as tel:
        for i in range(10):
            with span(f"s{i}"):
                pass
    assert [r[0] for r in tel.spans.spans()] == ["s7", "s8", "s9"]
    assert tel.spans.dropped == 7
    path = tmp_path / "t.perfetto.json"
    tel.spans.write(str(path))
    with open(path) as f:
        payload = json.load(f)
    names = [e["name"] for e in payload["traceEvents"]
             if e["ph"] == "X"]
    assert names == ["s7", "s8", "s9"]


def test_span_metric_feeds_histogram_and_traced_decorator():
    with telemetry.session() as tel:
        with span("p", metric="m/dur_s"):
            pass

        @traced(metric="m/fn_s")
        def f(x):
            return x + 1

        assert f(1) == 2
    snap = tel.metrics.snapshot()
    assert snap["histograms"]["m/dur_s"]["count"] == 1
    assert snap["histograms"]["m/fn_s"]["count"] == 1
    # the decorator labels spans with the function's __qualname__
    assert any(r[0].endswith(".f") for r in tel.spans.spans())


def test_session_nesting_restores_outer_sinks():
    with telemetry.session(meta={"w": "outer"}) as outer:
        reg_mod.inc("a")
        with telemetry.session(meta={"w": "inner"}) as inner:
            reg_mod.inc("a")
            assert reg_mod.get() is inner.metrics
        assert reg_mod.get() is outer.metrics
        reg_mod.inc("a")
    assert reg_mod.get() is None
    assert inner.metrics.counter("a").value == 1
    assert outer.metrics.counter("a").value == 2


# ---------------------------------------------------------------------------
# counter thread-safety under the evaluation runtime's worker pool
# ---------------------------------------------------------------------------


def test_counters_exact_under_dispatcher_pool():
    """Worker threads hammer one counter through the guarded seam; the
    registry runs on the race detector's TrackedLock (drop-in Lock
    wrapper), and the total must be exact — the thread-safety claim as
    an equality, not a hope."""
    registry = MetricsRegistry(lock_factory=lambda: TrackedLock())
    n_reqs, k = 64, 25

    def measure(req: EvalRequest) -> EvalResult:
        for _ in range(k):
            reg_mod.inc("test/hits")
        return EvalResult(y=float(req.n))

    telemetry.enable(metrics=registry)
    d = EvalDispatcher(measure, mode="pool", max_workers=8)
    reqs = [EvalRequest(state=(i,), decoded={"i": i}, job="j", n=i)
            for i in range(n_reqs)]
    futures = d.submit_many(reqs)
    ys = sorted(f.result().y for f in futures)
    d.close()
    telemetry.disable()
    assert ys == [float(i) for i in range(n_reqs)]
    assert registry.counter("test/hits").value == n_reqs * k
    assert registry.counter("evalpipe/dispatched").value == n_reqs
    assert registry.counter("evalpipe/landed").value == n_reqs
    # dispatch latency + measure time histograms land once per request
    assert registry.histogram("evalpipe/dispatch_wait_s").count == n_reqs
    assert registry.histogram("evalpipe/measure_s").count == n_reqs


# ---------------------------------------------------------------------------
# controllers: note_round exactly-once, stats() contract, parity
# ---------------------------------------------------------------------------


def _fleet(T=2, seed=0, **kw):
    catalog = EC2_CATALOG_ADJUSTED.with_capacities(
        {f: 12.0 * T for f in EC2_CATALOG_ADJUSTED.names()})
    space = make_ec2_space(catalog, core_counts=tuple(range(4, 68, 8)))
    evaluator = SimulatedEvaluator(catalog)
    jobs = sorted(evaluator.jobs)
    rng = np.random.default_rng(11)
    tenants = [
        TenantSpec(f"t{i}",
                   dict(zip(jobs, rng.dirichlet(np.ones(len(jobs))))))
        for i in range(T)]
    kw.setdefault("steps_per_round", 8)
    return FleetController(space, catalog, evaluator, tenants,
                           budget_usd_hr=1.6 * T, seed=seed, **kw)


def _procurement(seed=0, **kw):
    space = make_ec2_space(EC2_CATALOG_ADJUSTED,
                           core_counts=tuple(range(4, 68, 8)))
    evaluator = SimulatedEvaluator(EC2_CATALOG_ADJUSTED)
    jobs = sorted(evaluator.jobs)
    blend = {j: 1.0 / len(jobs) for j in jobs}
    return ProcurementController(
        space=space, catalog=EC2_CATALOG_ADJUSTED, evaluator=evaluator,
        objective=Objective(lambda_cost=1.0), blend=blend,
        schedule=1.0, seed=seed, **kw)


def _sizing():
    tiers = (ServiceTier("fe", base_rate=60.0),
             ServiceTier("be", base_rate=50.0))
    classes = (RequestClass("r", "fe", {"fe": 1, "be": 1}, slo_s=0.5),)
    dag = MicroserviceDAG(tiers, (("fe", "be"),), classes)
    spec = SizingSpace(dag,
                       sizes=(ContainerSize("s", 1, 2.0),
                              ContainerSize("l", 4, 8.0)),
                       replica_counts=(1, 2), lambda_cost=0.5,
                       slo_penalty=50.0)
    return SizingController(spec, {"r": 20.0}, steps_per_round=8,
                            n_chains=4, seed=0)


def _surrogate():
    space = ConfigSpace((
        Dimension("fam", ("a", "b")),
        Dimension("cores", tuple(range(4, 44, 2)))))

    def fn(cfg):
        f = {"a": 1.0, "b": 0.85}[cfg["fam"]]
        return f * (30.0 + 400.0 / cfg["cores"] + cfg["cores"] ** 0.8)

    return SurrogateAnnealer(space, fn, half_width=6, n_chains=4,
                             steps_per_round=8, measures_per_round=3,
                             n_bootstrap=4, seed=0)


def _replay(seed=0, **kw):
    T = 4
    catalog = EC2_CATALOG_ADJUSTED.with_capacities(
        {f: 12.0 * T for f in EC2_CATALOG_ADJUSTED.names()})
    space = make_ec2_space(catalog, core_counts=tuple(range(4, 68, 8)))
    evaluator = SimulatedEvaluator(catalog)
    trace = synthetic_trace(sorted(evaluator.jobs), n_tenants=T,
                            horizon_s=240.0, seed=seed, n_profiles=3)
    return TraceReplayController(
        trace, space, catalog, evaluator, budget_usd_hr=1.6 * T,
        steps_per_round=8, slo_s=3600.0, seed=seed, **kw)


def test_note_round_fires_exactly_once_per_round():
    """ISSUE 8 satellite: every controller's round boundary increments
    its rounds/<name> counter exactly once per control round."""
    with telemetry.session() as tel:
        _fleet().round()
        ctl = _procurement()
        for _ in range(3):
            ctl.submit()
        _sizing().run(2)
        _surrogate().run(2)
    counters = tel.metrics.snapshot()["counters"]
    assert counters["rounds/FleetController"] == 1
    assert counters["rounds/ProcurementController"] == 3
    assert counters["rounds/SizingController"] == 2
    assert counters["rounds/SurrogateAnnealer"] == 2


def test_trace_replay_counts_both_seams():
    """One TraceReplayController tick == one tick-level note_round AND
    one wrapped FleetController round — attributed separately, each
    exactly once."""
    with telemetry.session() as tel:
        ctl = _replay()
        ctl.replay(max_rounds=3)
    counters = tel.metrics.snapshot()["counters"]
    assert len(ctl.rounds) == 3
    assert counters["rounds/TraceReplayController"] == 3
    assert counters["rounds/FleetController"] == 3


def test_round_hook_shares_seam_without_clobbering():
    """Telemetry adds exactly one ROUND_HOOKS entry while armed and
    removes only its own on disable — a sanitizer hook registered
    alongside survives untouched and sees every round."""
    seen = []
    other = lambda name, owner: seen.append(name)       # noqa: E731
    ROUND_HOOKS.append(other)
    try:
        before = len(ROUND_HOOKS)
        with telemetry.session() as tel:
            assert len(ROUND_HOOKS) == before + 1
            _fleet().round()
        assert len(ROUND_HOOKS) == before
        assert ROUND_HOOKS[-1] is other
        assert seen == ["FleetController"]
        assert tel.metrics.counter("rounds/FleetController").value == 1
    finally:
        ROUND_HOOKS.remove(other)


def test_stats_contract_across_controllers():
    """The unified ControllerMixin.stats() shape: controller, rounds,
    evaluation counts, pipeline, and a 'metrics' sub-snapshot iff a sink
    is armed."""
    with telemetry.session():
        fleet = _fleet()
        fleet.round()
        proc = _procurement()
        proc.submit()
        sizing = _sizing()
        sizing.run(1)
        sa = _surrogate()
        sa.run(1)
        replay = _replay()
        replay.replay(max_rounds=2)
        for ctl, rounds in [(fleet, 1), (proc, 1), (sizing, 1),
                            (sa, 1), (replay, 2)]:
            s = ctl.stats()
            assert s["controller"] == type(ctl).__name__
            assert s["rounds"] == rounds
            assert "pipeline" in s
            assert "metrics" in s            # sink armed
        assert _fleet().stats()["rounds"] == 0
    s = fleet.stats()                        # sink dark again
    assert "metrics" not in s
    # the deprecated entry points still answer (back-compat), routed
    # through stats() and warning once each (pinned below)
    with pytest.deprecated_call():
        assert proc.stats()["pipeline"] == proc.pipeline_stats()
    with pytest.deprecated_call():
        assert replay.stats()["summary"] == replay.summary()
    json.dumps(replay.stats())


def test_telemetry_is_observation_only():
    """Decision parity: the same seeded fleet walks the same decision
    log with sinks armed and dark — telemetry never touches RNG or
    decisions."""

    def run(armed: bool):
        if armed:
            with telemetry.session():
                ctl = _fleet(seed=5)
                return [[(d.tenant, d.action, d.config, d.y)
                         for d in ctl.round()] for _ in range(3)]
        ctl = _fleet(seed=5)
        return [[(d.tenant, d.action, d.config, d.y)
                 for d in ctl.round()] for _ in range(3)]

    assert run(armed=True) == run(armed=False)


def test_fleet_round_records_series_and_spans():
    with telemetry.session() as tel:
        ctl = _fleet()
        ctl.round()
        ctl.round()
    snap = tel.metrics.snapshot()
    for name in ("fleet/objective", "fleet/spend_usd_hr",
                 "fleet/violation", "fleet/tenants"):
        assert len(snap["series"][name]["v"]) == 2, name
    names = {r[0] for r in tel.spans.spans()}
    assert {"fleet.round", "fleet.measure", "fleet.anneal",
            "fleet.arbitrate"} <= names


# ---------------------------------------------------------------------------
# report: sparkline, dashboard, CLI
# ---------------------------------------------------------------------------


def test_sparkline_shapes():
    assert report.sparkline([]) == ""
    assert report.sparkline([1.0]) == report.SPARK[0]
    assert report.sparkline([0, 0, 0]) == report.SPARK[0] * 3  # flat
    up = report.sparkline(range(100), width=10)
    assert len(up) == 10
    assert up[0] == report.SPARK[0] and up[-1] == report.SPARK[-1]


def test_dashboard_and_cli(tmp_path, capsys):
    with telemetry.session(meta={"run": "unit"}) as tel:
        for i in range(5):
            reg_mod.record("fleet/objective", 100.0 - i)
        reg_mod.inc("rounds/FleetController", 5)
        reg_mod.set_gauge("ledger/general/utilization", 0.25)
        with span("fleet.round"):
            pass
        paths = tel.write_artifacts("TELEMETRY_unit", str(tmp_path))
    dash = tel.dashboard(width=20)
    assert "fleet/objective" in dash and "run=unit" in dash
    assert report.main([paths["snapshot"]]) == 0
    out = capsys.readouterr().out
    for needle in ("fleet/objective", "rounds/FleetController",
                   "ledger/general/utilization", "fleet.round"):
        assert needle in out
    assert report.main([paths["snapshot"], "--section", "counters"]) == 0
    out = capsys.readouterr().out
    assert "rounds/FleetController" in out and "-- per-round" not in out
    with open(paths["perfetto"]) as f:       # companion artifact loads
        assert json.load(f)["traceEvents"]


def test_maybe_enable_respects_env(monkeypatch):
    monkeypatch.delenv(telemetry.ENV_FLAG, raising=False)
    assert telemetry.maybe_enable() is None
    monkeypatch.setenv(telemetry.ENV_FLAG, "1")
    tel = telemetry.maybe_enable()
    assert tel is not None and telemetry.get() is tel
    assert telemetry.maybe_enable() is tel   # idempotent
    telemetry.disable()


# ---------------------------------------------------------------------------
# the profiler mirror and the controllers' sync spans
# ---------------------------------------------------------------------------


def _host_annotations(profile_dir) -> dict[str, list[tuple]]:
    """name -> [(plane, line, start_ns, end_ns)] of the host events in
    the newest ``.xplane.pb`` under ``profile_dir``."""
    import glob
    import os

    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        str(profile_dir), "plugins", "profile", "*", "*.xplane.pb")))
    assert paths, "the profiler wrote no trace"
    out: dict[str, list[tuple]] = {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(
                    (plane.name, line.name, ev.start_ns,
                     ev.start_ns + ev.duration_ns))
    return out


def test_spans_mirror_into_the_profiler_trace(tmp_path):
    """Armed spans appear in the .xplane.pb on the host thread's line,
    nested as they ran; dark spans add nothing."""
    import jax

    with telemetry.session():
        with jax.profiler.trace(str(tmp_path)):
            with span("t.outer"):
                with span("t.inner"):
                    jax.numpy.ones(4).block_until_ready()
                with span("t.after"):
                    pass
    ann = _host_annotations(tmp_path)
    (outer,), (inner,), (after,) = (ann["t.outer"], ann["t.inner"],
                                    ann["t.after"])
    assert outer[:2] == inner[:2] == after[:2]       # one plane and line
    for child in (inner, after):
        assert outer[2] <= child[2] and child[3] <= outer[3]
    assert inner[3] <= after[2]
    assert spans_mod._ANNOTATION is None             # detached again


def test_telemetry_imports_and_records_without_jax():
    """repro.telemetry needs no jax: with jax blocked from import it
    still loads, and armed spans record with no profiler mirror."""
    import os
    import subprocess
    import sys

    code = (
        "import sys\n"
        "class Block:\n"
        "    def find_spec(self, name, path=None, target=None):\n"
        "        if name == 'jax' or name.startswith('jax.'):\n"
        "            raise ImportError('jax blocked')\n"
        "sys.meta_path.insert(0, Block())\n"
        "import repro.telemetry as t\n"
        "from repro.telemetry import spans\n"
        "rec = spans.enable()\n"
        "assert spans._ANNOTATION is None\n"
        "with t.span('a'):\n"
        "    pass\n"
        "assert [s[0] for s in rec.spans()] == ['a']\n"
        "assert 'jax' not in sys.modules\n"
        "print('ok')\n")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src)
    env.pop("REPRO_TELEMETRY", None)
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    assert res.stdout.strip() == "ok"


def _children(recs, parent: str, depth: int) -> list[list[str]]:
    """For each ``parent`` span, the names of the spans ``depth`` levels
    below it that lie inside it, in start order."""
    out = []
    for p in (r for r in recs if r[0] == parent):
        lo, hi = p[2], p[2] + p[3]
        kids = sorted((r for r in recs
                       if r[5] == p[5] + depth and r[4] == p[4]
                       and lo <= r[2] and r[2] + r[3] <= hi),
                      key=lambda r: r[2])
        out.append([r[0] for r in kids])
    return out


def test_sizing_round_is_tiled_by_dispatch_sync_measure_commit():
    with telemetry.session() as tel:
        _sizing().run(3)
    recs = tel.spans.spans()
    assert _children(recs, "sizing.round", 1) == [
        ["sizing.dispatch", "sizing.sync", "sizing.measure",
         "sizing.commit"]] * 3
    # refit and anneal only enqueue: they stay inside the dispatch
    assert _children(recs, "sizing.dispatch", 1) == [
        ["sizing.refit", "sizing.anneal"]] * 3


def test_surrogate_sizing_round_counts_probes_and_interpolated_states():
    """With a SurrogateSource each round that builds a table counts its
    probes and its interpolated states, and the table program is
    enqueued inside the round's ``sizing.refit`` span."""
    from repro.core import SurrogateSource

    base = _sizing()
    ctl = SizingController(base.spec, lambda r: {"r": 20.0 + r},
                           objective_source=SurrogateSource(n_probe=8),
                           steps_per_round=8, n_chains=4, seed=0)
    with telemetry.session() as tel:
        ctl.run(3)
    counters = tel.metrics.snapshot(prefix="sizing")["counters"]
    assert counters["sizing/probes"] == 3 * 8
    assert counters["sizing/interp_states"] == 3 * base.space.size()
    assert _children(tel.spans.spans(), "sizing.dispatch", 1) == [
        ["sizing.refit", "sizing.anneal"]] * 3


@pytest.mark.parametrize("table,programs", [
    ("cache_hit", 3), ("build", 4), ("surrogate", 4)])
def test_warm_sizing_round_binds_no_eager_primitive(monkeypatch, table,
                                                    programs):
    """A warmed device-loop round only launches its programs — prep,
    anneal and select, plus the table program when it builds a table —
    and binds no primitive eagerly (a jit call on its slow path would
    bind one too); ``sizing/programs_enqueued`` counts the launches."""
    from jax._src import core as jax_core

    from repro.core import SurrogateSource

    base = _sizing()
    if table == "cache_hit":
        ctl = base
    else:
        ctl = SizingController(
            base.spec, lambda r: {"r": 20.0 + r},
            objective_source=(SurrogateSource(n_probe=8)
                              if table == "surrogate" else None),
            steps_per_round=8, n_chains=4, seed=0)
    ctl.run(3)
    bound = []
    real = jax_core.EvalTrace.process_primitive

    def count(self, primitive, tracers, params):
        bound.append(primitive.name)
        return real(self, primitive, tracers, params)

    monkeypatch.setattr(jax_core.EvalTrace, "process_primitive", count)
    with telemetry.session() as tel:
        ctl.round()
    assert bound == []
    counters = tel.metrics.snapshot(prefix="sizing")["counters"]
    assert counters["sizing/programs_enqueued"] == programs


def test_sizing_decisions_identical_armed_and_dark():
    """The spans reorder no device work and draw no key: the same seeded
    controller commits bit-identical decisions armed and dark."""

    def run(armed: bool):
        ctl = _sizing()
        if armed:
            with telemetry.session():
                ds = ctl.run(6)
        else:
            ds = ctl.run(6)
        return [(d.n, tuple(sorted(d.sizing.items())), d.y, d.explored,
                 d.accepted, d.tau, d.reheated) for d in ds]

    assert run(armed=True) == run(armed=False)


def test_fleet_and_surrogate_wait_in_their_sync_spans():
    with telemetry.session() as tel:
        _fleet().round()
        _surrogate().run(2)
    recs = tel.spans.spans()
    assert _children(recs, "fleet.round", 1) == [
        ["fleet.refit", "fleet.anneal", "fleet.sync", "fleet.detect",
         "fleet.arbitrate", "fleet.ledger", "fleet.measure"]]
    assert _children(recs, "surrogate.round", 1) == [
        ["surrogate.refit", "surrogate.anneal", "surrogate.sync",
         "surrogate.measure"]] * 2
    # no histogram times an enqueue under an anneal or refit name
    hists = tel.metrics.snapshot()["histograms"]
    assert "fleet/measure_s" in hists
    for name in ("fleet/anneal_s", "sizing/anneal_s", "surrogate/anneal_s",
                 "surrogate/refit_s"):
        assert name not in hists
