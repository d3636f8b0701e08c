"""Speculative evaluation pipeline: the wall-clock overlap win.

The paper's online controller evaluates exactly one job per annealing
transition, so it is serialized on measurement latency.  The speculative
evaluation runtime (:mod:`repro.core.evalpipe`) runs the chain ``K``
transitions ahead, dispatches the speculated measurements over a bounded
worker pool, and resolves acceptance in transition order — recycling every
mis-speculated measurement into the surrogate store.

Claims checked (ISSUE 5 acceptance criteria):

  * on a measured (wall-clock) evaluator with 50 ms/job latency, the
    pipelined controller at lookahead K=8 is >= 3x faster end-to-end than
    the serial inline loop;
  * at K=1 the pipeline is decision-sequence *identical* to the inline
    loop under the same seed (same accept/reject trace, same configs,
    same objectives, same measurement records);
  * the fleet controller's per-round measurement phase overlaps the same
    way: T wall-clock tenants measured by the worker pool in ~1/T of the
    serial loop's time, with identical decisions.

Artifacts: ``experiments/bench/pipeline_overlap.json`` (full result) and a
top-level ``BENCH_pipeline.json`` (speedup + speculation telemetry).

Run:  PYTHONPATH=src python -m benchmarks.pipeline_overlap [--smoke]
"""

from __future__ import annotations

import dataclasses
import json
import os
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (
    EC2_CATALOG,
    EC2_CATALOG_ADJUSTED,
    FleetController,
    Objective,
    PenalizedObjective,
    ProcurementController,
    ServiceCatalog,
    TenantSpec,
    make_ec2_space,
)
from repro.core.costmodel import SimulatedEvaluator
from repro.core.landscape import BLEND_BEFORE
from .common import Bench, write_json

JOB_LATENCY_S = 0.050        # the acceptance criterion's 50 ms/job
LOOKAHEAD = 8
HEDGE_MARGIN = 0.25          # hedge when |p_hat - u| is within this
TOP_LEVEL_ARTIFACT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_pipeline.json")


@dataclasses.dataclass
class SlowSimulatedEvaluator(SimulatedEvaluator):
    """A ``MeasuredEvaluator``-shaped workload: every measurement costs
    real wall-clock time (the job "runs" for ``latency_s``), but the
    measured values come from the deterministic simulator so decision
    parity is checkable.  ``wall_clock`` routes it through the evaluation
    runtime's worker pool."""

    wall_clock = True

    latency_s: float = JOB_LATENCY_S

    def measure(self, config, job, n):
        time.sleep(self.latency_s)
        return super().measure(config, job, n)


def _controller(evaluator, **kw) -> ProcurementController:
    space = make_ec2_space(EC2_CATALOG_ADJUSTED,
                           core_counts=tuple(range(4, 68, 8)))
    return ProcurementController(
        space=space, catalog=EC2_CATALOG_ADJUSTED, evaluator=evaluator,
        objective=Objective(lambda_cost=1.0), blend=dict(BLEND_BEFORE),
        schedule=1.0, seed=0, **kw)


def _trace(decisions):
    """The decision sequence, counters excluded (they also count recycled
    speculative measurements, which is the point, not a divergence)."""
    return [(d.n, d.job, d.config, round(d.y, 9), d.accepted, d.explored,
             d.tau, d.reheated, d.measurement) for d in decisions]


def pipeline_overlap(smoke: bool = False) -> dict:
    b = Bench("pipeline_overlap",
              "ISSUE 5: speculative evaluation pipeline wall-clock win")
    # enough jobs that the warmup phase (empty store, optimistic
    # predictions, more flushes) amortizes; the serial baseline is still
    # only ~3s of sleep in smoke mode
    n_jobs = 60 if smoke else 120
    result: dict = {"smoke": smoke, "n_jobs": n_jobs,
                    "job_latency_ms": JOB_LATENCY_S * 1e3,
                    "lookahead": LOOKAHEAD}

    # -- serial inline loop (the paper's mode: one job per transition) --
    serial = _controller(SlowSimulatedEvaluator(EC2_CATALOG_ADJUSTED))
    t0 = time.perf_counter()
    d_serial = serial.run(n_jobs)
    wall_serial = time.perf_counter() - t0

    # -- pipelined at K=8: speculate, overlap, resolve, recycle; hedged
    # both-branch speculation covers marginal accept/reject predictions
    # (the alternative branch's measurement is already in flight when a
    # misprediction flushes) without touching the decision sequence --
    piped = _controller(SlowSimulatedEvaluator(EC2_CATALOG_ADJUSTED),
                        lookahead=LOOKAHEAD, hedge_margin=HEDGE_MARGIN)
    t0 = time.perf_counter()
    d_piped = piped.run(n_jobs)
    wall_piped = time.perf_counter() - t0
    piped.close()
    stats = piped.stats()["pipeline"]

    speedup = wall_serial / max(wall_piped, 1e-9)
    result["procurement"] = {
        "wall_serial_s": round(wall_serial, 3),
        "wall_pipelined_s": round(wall_piped, 3),
        "speedup": round(speedup, 2),
        "serial_measures": serial.evaluation_counts()["true_measures"],
        "pipelined_measures": piped.evaluation_counts()["true_measures"],
        "recycled_into_store": len(piped.recycle_store),
        "speculation": stats,
    }
    b.check(f"pipelined K={LOOKAHEAD} is >= 3x faster than the serial "
            f"loop on a {JOB_LATENCY_S * 1e3:.0f} ms/job evaluator "
            f"({wall_serial:.2f}s -> {wall_piped:.2f}s, {speedup:.1f}x)",
            speedup >= 3.0)
    b.check(f"speculation hit rate {stats['hit_rate']:.0%} with "
            f"{stats['recycled_landed']} mis-speculated measurements "
            f"recycled into the surrogate store (exactly once each) and "
            f"{stats['cancelled']} cancelled before running",
            stats["recycled_landed"] + stats["cancelled"]
            == stats["recycled"]
            and len(piped.recycle_store) > 0)
    b.check(f"hedged speculation covers the measurement stall on "
            f"{stats['hedged_covered']}/{stats['mispredictions']} "
            f"mispredictions (hit rate {stats['hit_rate']:.0%} > 90% at "
            f"K={LOOKAHEAD})", stats["hit_rate"] > 0.9)
    b.check("decision trace at K=8 matches the serial loop (same seed; "
            "rng-rewind on misprediction keeps the realized walk serial-"
            "identical)", _trace(d_serial)[:1] == _trace(d_piped)[:1]
            and [t[:8] for t in _trace(d_serial)]
            == [t[:8] for t in _trace(d_piped)])

    # -- K=1 degenerate path: full decision-sequence parity --
    inline = _controller(SlowSimulatedEvaluator(EC2_CATALOG_ADJUSTED),
                         use_pipeline=False)
    piped1 = _controller(SlowSimulatedEvaluator(EC2_CATALOG_ADJUSTED),
                         use_pipeline=True, lookahead=1)
    k = min(n_jobs, 40)
    tr_inline = _trace(inline.run(k))
    tr_piped1 = _trace(piped1.run(k))
    piped1.close()
    parity = tr_inline == tr_piped1
    result["parity_k1"] = {"n_jobs": k, "equal": parity}
    b.check("K=1 decision-sequence parity with the inline loop "
            "(accept/reject trace, configs, objectives, measurements)",
            parity)

    # -- fleet: the round measurement phase overlaps across tenants --
    T = 8
    fams = ("general", "compute", "memory", "storage")

    def _catalog():
        return ServiceCatalog({f: EC2_CATALOG[f] for f in fams},
                              capacities={f: 600.0 for f in fams})

    space = make_ec2_space(_catalog(), core_counts=tuple(range(4, 36, 8)))
    tenants = [TenantSpec(f"t{i}", {"wordcount": 1.0, "kmeans": 1.0})
               for i in range(T)]

    def fleet(workers):
        # tables come from the instant simulator; only the per-round
        # ground-truth measurement phase pays wall-clock latency.  Each
        # controller gets its own catalog: FleetController reserves into
        # the catalog's capacity ledger and honors pre-existing foreign
        # holds, so a shared catalog would leak one controller's
        # reservations into the next run's decisions and break parity.
        cat = _catalog()
        f = FleetController(
            space, cat, SimulatedEvaluator(cat), tenants,
            objective=PenalizedObjective(Objective(lambda_cost=200.0),
                                         weight=25.0),
            steps_per_round=8, seed=0, eval_workers=workers)
        f.evaluator = SlowSimulatedEvaluator(cat)
        return f

    rounds = 2 if smoke else 4
    fleet(1).run(1)   # warm the jitted fleet kernel out of the timings
    fa = fleet(1)
    t0 = time.perf_counter()
    dfa = fa.run(rounds)
    wall_fleet_serial = time.perf_counter() - t0
    fb = fleet(T)
    t0 = time.perf_counter()
    dfb = fb.run(rounds)
    wall_fleet_pool = time.perf_counter() - t0
    fleet_speedup = wall_fleet_serial / max(wall_fleet_pool, 1e-9)

    def ftr(ds):
        return [(d.tenant, d.round, d.action, d.accepted, round(d.y, 9),
                 d.config, d.measurement) for d in ds]

    result["fleet"] = {
        "tenants": T, "rounds": rounds,
        "wall_serial_s": round(wall_fleet_serial, 3),
        "wall_pool_s": round(wall_fleet_pool, 3),
        "speedup": round(fleet_speedup, 2),
    }
    b.check(f"fleet measurement phase: {T}-tenant rounds {fleet_speedup:.1f}x "
            f"faster through the worker pool, identical decisions",
            fleet_speedup >= 2.0 and ftr(dfa) == ftr(dfb))

    write_json("pipeline_overlap.json", result)
    with open(TOP_LEVEL_ARTIFACT, "w") as f:
        json.dump({
            "bench": "pipeline_overlap",
            "smoke": smoke,
            "speedup": result["procurement"]["speedup"],
            "fleet_speedup": result["fleet"]["speedup"],
            "parity_k1": parity,
            "speculation": stats,
        }, f, indent=2)
    print(f"pipeline telemetry -> {TOP_LEVEL_ARTIFACT}")
    return b.finish()


def run_all() -> list[dict]:
    return [pipeline_overlap()]


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced budgets for tier-1 CI")
    args = ap.parse_args()
    enable_compile_cache()
    res = pipeline_overlap(smoke=args.smoke)
    print(json.dumps(res, indent=2))
    raise SystemExit(0 if res["ok"] else 1)
