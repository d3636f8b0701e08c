"""Trace-driven fleet replay at 1k+ tenants.

Replays synthetic Alibaba-style churn traces (tenant arrivals with
heavy-tailed lifetimes, mid-life phase changes, departures releasing
capacity) against the FleetController in its scaled configuration:
event-driven round clock, incremental re-annealing (only churned /
drifted tenants), pow-2 chain bucketing and the incremental reservation
ledger.  Emits the tenants-vs-wall-clock scaling curve and SLO
attainment under churn to the top-level ``BENCH_trace.json``.

Claims checked:
  * the 1024-tenant replay is SUB-LINEAR in wall-clock vs the 64-tenant
    baseline (<= half the linear tenant ratio), compile costs included;
  * incremental rounds anneal a small fraction of tenant-rounds (the
    churned subset), yet the fleet stays feasible: zero aggregate
    capacity/budget violations in the final quarter of every replay;
  * SLO attainment under churn stays above the floor at every scale;
  * the scaled execution paths are DECISION-IDENTICAL to dense on the
    64-tenant parity case: single-device shard_map == direct dispatch,
    bucketed == unbucketed, each under both full and incremental
    policies (same trace, same seeds, same FleetDecision log).
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

import repro.telemetry as telemetry
from repro.compile_cache import enable_compile_cache
from repro.core import (
    EC2_CATALOG_ADJUSTED,
    Objective,
    PenalizedObjective,
    TraceReplayController,
    make_ec2_space,
)
from repro.core.costmodel import SimulatedEvaluator
from repro.launch.mesh import make_tenant_mesh
from repro.workloads.trace import synthetic_trace, trace_fingerprint
from .common import Bench, write_json

CORES = tuple(range(4, 132, 8))
LAMBDA = 200.0
PENALTY_WEIGHT = 25.0
CORES_PER_FAMILY = 12.0      # per family, scaled by T
BUDGET_PER_TENANT = 1.6      # $/hr, scaled by T
SLO_S = 3600.0               # per-job sojourn SLO under churn
N_PROFILES = 12              # finite blend pool (objective-table cache)
TOP_LEVEL_ARTIFACT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_trace.json")


def _controller(T: int, horizon_s: float, seed: int = 0, **kw
                ) -> TraceReplayController:
    catalog = EC2_CATALOG_ADJUSTED.with_capacities(
        {f: CORES_PER_FAMILY * T for f in EC2_CATALOG_ADJUSTED.names()})
    space = make_ec2_space(catalog, core_counts=CORES)
    evaluator = SimulatedEvaluator(catalog)
    trace = synthetic_trace(
        sorted(evaluator.jobs), n_tenants=T, horizon_s=horizon_s,
        seed=seed, n_profiles=N_PROFILES)
    kw.setdefault("incremental", True)
    return TraceReplayController(
        trace, space, catalog, evaluator,
        objective=PenalizedObjective(Objective(lambda_cost=LAMBDA),
                                     weight=PENALTY_WEIGHT),
        budget_usd_hr=BUDGET_PER_TENANT * T,
        steps_per_round=32, slo_s=SLO_S, seed=seed, **kw)


def _decision_sig(ctl: TraceReplayController) -> list[tuple]:
    return [(d.round, d.tenant, d.action, d.config, round(d.y, 9))
            for d in ctl.fleet.decisions]


def _check_telemetry(b: Bench, tel: "telemetry.Telemetry", n_rounds: int,
                     result: dict) -> None:
    """Claim checks on the telemetry-armed baseline leg: the Perfetto
    export must carry one fleet.round span per replay round with the
    measure/anneal/arbitrate phases nested inside it, and the dashboard
    must render the objective/cost/SLO series."""
    spans: dict[str, list] = {}
    for s in tel.spans.spans():               # (name, cat, ts, dur, ...)
        spans.setdefault(s[0], []).append(s)
    rounds = spans.get("fleet.round", [])
    b.check(f"telemetry: one fleet.round span per replay round "
            f"({len(rounds)}/{n_rounds})", len(rounds) == n_rounds)

    def nested(child) -> bool:                # ts containment, +-2us slack
        cs, ce = child[2], child[2] + child[3]
        return any(p[2] - 2 <= cs and ce <= p[2] + p[3] + 2
                   for p in rounds)

    for phase in ("fleet.measure", "fleet.anneal", "fleet.arbitrate"):
        ph = spans.get(phase, [])
        b.check(f"telemetry: {phase} spans present and nested inside "
                f"fleet.round ({len(ph)})",
                bool(ph) and all(nested(s) for s in ph))
    dash = tel.dashboard()
    for series in ("fleet/objective", "fleet/spend_usd_hr",
                   "trace/slo_attainment"):
        b.check(f"telemetry: dashboard renders {series}", series in dash)
    # -- PR 9: provenance exactness on the armed leg -------------------
    # Every committed round inside the flight-recorder window must carry
    # DecisionRecords whose exact_split sums BIT-EQUAL to the committed
    # objective and whose named terms ladder passes the float32 bar.
    recs = tel.provenance.records() if tel.provenance is not None else ()
    fleet_recs = [r for r in recs if r.controller == "fleet"]
    rounds_seen = {r.round for r in fleet_recs}
    lo = min(rounds_seen) if rounds_seen else 0
    committed = set(range(lo, n_rounds))
    b.check(f"provenance: every committed round in recorder window has "
            f"decision records ({len(rounds_seen & committed)}/"
            f"{len(committed)})",
            bool(fleet_recs) and committed <= rounds_seen)
    split_ok = all(sum(v for _, v in r.exact_split) == r.y
                   for r in fleet_recs)
    terms_ok = all(r.check() for r in fleet_recs)
    b.check(f"provenance: exact_split sums bit-equal to committed y "
            f"on all {len(fleet_recs)} records", split_ok)
    b.check(f"provenance: named terms ladder within float32 exactness "
            f"on all {len(fleet_recs)} records", terms_ok)
    result["provenance"] = {
        "records": len(fleet_recs), "rounds_covered": len(rounds_seen),
        "dropped": tel.provenance.dropped if tel.provenance else 0,
        "exact_split_bit_equal": split_ok, "terms_f32_exact": terms_ok}

    pages = [a.rule for a in tel.alerts.fired
             if a.severity == "page"] if tel.alerts is not None else []
    result["alerts"] = {
        "fired": [a.to_dict() for a in tel.alerts.fired]
        if tel.alerts is not None else [],
        "pages": pages,
    }

    paths = tel.write_artifacts(
        "TELEMETRY_trace", out_dir=os.path.dirname(TOP_LEVEL_ARTIFACT))
    with open(paths["perfetto"]) as f:
        events = json.load(f)["traceEvents"]
    b.check(f"telemetry: Perfetto artifact loads "
            f"({len(events)} trace events)", len(events) > 0)
    result["telemetry"] = {"artifacts": paths,
                           "trace_events": len(events),
                           "spans_dropped": tel.spans.dropped}


def _budget_cut_leg(b: Bench, result: dict) -> None:
    """Inject a budget cut on a small replayed fleet and require the
    default ``spend_over_budget`` page alert to fire.  Runs in its own
    telemetry session so the deliberate breach never pollutes the
    baseline leg's ``--fail-on-alerts`` gate."""
    with telemetry.session(meta={"bench": "trace_fleet",
                                 "leg": "budget_cut"}) as tel:
        ctl = _controller(8, 240.0, seed=3, keep_decision_log=False)
        ctl.replay()                       # populate tenants, warm state
        fleet = ctl.fleet
        fleet.budget_usd_hr *= 0.02        # even the cheapest states breach
        for _ in range(6):
            fleet.round()
        fired = [a.rule for a in tel.alerts.fired]
    b.check(f"alerts: spend_over_budget page alert fires under an "
            f"injected 98% budget cut (fired: {fired})",
            "spend_over_budget" in fired)
    result["budget_cut_leg"] = {"fired": fired}


def trace_fleet(tenant_counts=(64, 256, 1024), horizon_s: float = 3600.0,
                parity_T: int = 64, parity_horizon_s: float = 300.0,
                smoke: bool = False) -> dict:
    if smoke:
        tenant_counts, horizon_s = (64,), 600.0
        parity_T, parity_horizon_s = 16, 240.0
    b = Bench("trace_fleet", "sec. 5 (trace-driven fleet, beyond paper)")
    result: dict = {"smoke": smoke, "slo_s": SLO_S,
                    "horizon_s": horizon_s, "scaling": {}, "parity": {}}

    # -- tenants-vs-wall-clock scaling curve ---------------------------
    base_T = tenant_counts[0]
    for T in tenant_counts:
        t0 = time.perf_counter()
        ctl = _controller(T, horizon_s, seed=T, keep_decision_log=False)
        if T == base_T:
            # the baseline leg doubles as the observability deliverable:
            # replay with the metric/span sinks armed and leave the
            # snapshot + Perfetto trace next to BENCH_trace.json (the
            # larger legs stay dark so the scaling curve is unperturbed)
            with telemetry.session(
                    meta={"bench": "trace_fleet", "T": T,
                          "horizon_s": horizon_s}) as tel:
                summary = ctl.replay()
            _check_telemetry(b, tel, len(ctl.rounds), result)
        else:
            summary = ctl.replay()
        total_s = time.perf_counter() - t0
        tail = [r["violation"] for r in
                ctl.rounds[-max(len(ctl.rounds) // 4, 1):]]
        result["scaling"][str(T)] = {
            **summary,
            "total_s": total_s,          # + trace gen, tables, compiles
            "trace": trace_fingerprint(ctl.trace),
            "final_quarter_violations": float(np.sum(tail)),
        }
        b.check(f"T={T}: zero aggregate violations in the final 25% of "
                f"rounds", float(np.sum(tail)) == 0.0)
        b.check(f"T={T}: SLO attainment under churn >= 0.8 "
                f"(got {summary['slo_attainment']:.3f})",
                summary["slo_attainment"] >= 0.8)
        b.check(f"T={T}: incremental rounds anneal < 60% of "
                f"tenant-rounds (got "
                f"{summary['annealed_fraction']:.3f})",
                summary["annealed_fraction"] < 0.6)

    if len(tenant_counts) > 1:
        top = str(tenant_counts[-1])
        w0 = result["scaling"][str(base_T)]["wall_s"]
        w1 = result["scaling"][top]["wall_s"]
        lin = tenant_counts[-1] / base_T
        ratio = w1 / max(w0, 1e-9)
        result["scaling_ratio"] = {
            "tenants": lin, "wall_clock": ratio, "sublinear": ratio < lin}
        b.check(f"{top}-tenant replay sub-linear vs {base_T}-tenant "
                f"baseline: wall ratio {ratio:.1f}x <= {lin / 2:.0f}x "
                f"(half of the {lin:.0f}x linear ratio)",
                ratio <= lin / 2)

    # -- dense vs scaled execution paths: decision identity ------------
    # Same trace + seeds; vary ONLY the execution path (shard_map over a
    # single-device mesh, pow-2 bucket padding) under each policy.  The
    # chains are embarrassingly parallel, so these must be bit-identical.
    mesh = make_tenant_mesh(1)
    variants = {
        "dense_full": dict(incremental=False, chain_bucketing=False),
        "sharded_bucketed_full": dict(incremental=False, mesh=mesh,
                                      chain_bucketing=True),
        "dense_incremental": dict(incremental=True, chain_bucketing=False),
        "sharded_bucketed_incremental": dict(incremental=True, mesh=mesh,
                                             chain_bucketing=True),
    }
    sigs = {}
    for name, kw in variants.items():
        ctl = _controller(parity_T, parity_horizon_s, seed=7,
                          keep_decision_log=True, **kw)
        ctl.replay()
        sigs[name] = _decision_sig(ctl)
        result["parity"][name] = {"rounds": len(ctl.rounds),
                                  "decisions": len(sigs[name])}
    ok_full = sigs["dense_full"] == sigs["sharded_bucketed_full"]
    ok_incr = (sigs["dense_incremental"]
               == sigs["sharded_bucketed_incremental"])
    result["parity"]["full_identical"] = ok_full
    result["parity"]["incremental_identical"] = ok_incr
    b.check(f"T={parity_T}: sharded+bucketed FULL replay "
            f"decision-identical to dense", ok_full)
    b.check(f"T={parity_T}: sharded+bucketed INCREMENTAL replay "
            f"decision-identical to dense", ok_incr)

    # -- PR 9: provenance is observation-only --------------------------
    # Same dense-incremental replay with the flight recorder armed must
    # commit the exact same FleetDecision log as the dark run above.
    with telemetry.session(meta={"bench": "trace_fleet",
                                 "leg": "parity_armed"}):
        ctl = _controller(parity_T, parity_horizon_s, seed=7,
                          keep_decision_log=True,
                          incremental=True, chain_bucketing=False)
        ctl.replay()
        armed_sig = _decision_sig(ctl)
    ok_armed = armed_sig == sigs["dense_incremental"]
    result["parity"]["provenance_armed_identical"] = ok_armed
    b.check(f"T={parity_T}: provenance-armed replay decision-identical "
            f"to dark (observation-only)", ok_armed)

    _budget_cut_leg(b, result)

    write_json("trace_fleet.json", result)
    with open(TOP_LEVEL_ARTIFACT, "w") as f:
        json.dump(result, f, indent=2)
    return b.finish()


def run_all() -> list[dict]:
    return [trace_fleet()]


if __name__ == "__main__":
    import argparse
    import sys
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="64-tenant short-horizon tier-1 gate")
    ap.add_argument("--fail-on-alerts", action="store_true",
                    help="exit 1 if any page-severity alert fired on the "
                         "telemetry-armed baseline leg (nightly gate; the "
                         "injected budget-cut leg is exempt by design)")
    args = ap.parse_args()
    enable_compile_cache()
    out = trace_fleet(smoke=args.smoke)
    print(json.dumps([out], indent=2))
    if args.fail_on_alerts:
        with open(TOP_LEVEL_ARTIFACT) as f:
            pages = (json.load(f).get("alerts") or {}).get("pages") or []
        if pages:
            print(f"[trace_fleet] page alerts fired on baseline leg: "
                  f"{pages}", file=sys.stderr)
            sys.exit(1)
