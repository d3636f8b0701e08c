"""Container sizing for a microservice DAG: annealed vs static-peak.

The paper's third case study (abstract: "container sizing for
microservice benchmarks") through this repo's stack: an 8-tier
microservice DAG with three request classes whose mix drifts from
browse-heavy daytime to checkout-heavy evening; the
:class:`repro.core.sizing.SizingController` anneals per-tier (vertical
size, replica count) pairs online against the batched Erlang-C +
critical-path evaluator.

Claims checked (ISSUE 4 acceptance criteria):

  * the annealed sizing beats a *static peak-provisioned* baseline
    (every tier sized for the peak mix at a utilization target, never
    resized) on the combined objective Y — lower $/hr at
    equal-or-better SLO attainment — and is also compared against
    *per-tier-independent* tuning (each tier locally optimal for its own
    queue and SLO share, the cross-tier-blind strategy AutoTune warns
    about);
  * the same DAG runs through both ``ExhaustiveSource`` (the 65,536-state
    coarse menu) and ``SurrogateSource`` (probe-and-interpolate), with
    optimality gaps vs the whole-grid optimum reported on the small
    space;
  * with a richer menu the space grows to 1,679,616 states — beyond the
    200k tabulation cap, which ``tabulate`` provably refuses — and the
    surrogate-backed controller still sizes it from sparse real
    measurements (the large-DAG case; tier-2 nightly, skipped in
    ``--smoke``).

Artifacts: ``experiments/bench/container_sizing.json`` (full result) and
a top-level ``BENCH_sizing.json`` with the per-round SLO-attainment and
$/hr trajectories of the annealed deployment vs both baselines.

Run:  PYTHONPATH=src python -m benchmarks.container_sizing [--smoke]
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (
    ExhaustiveSource,
    SizingController,
    SizingSpace,
    SpaceEncoding,
    SurrogateModel,
    SurrogateSource,
    evaluate_sizing_batch,
    full_grid,
    tabulate,
)
from repro.workloads.microservice import (
    ContainerSize,
    DriftingMix,
    MicroserviceDAG,
    RequestClass,
    ServiceTier,
    mmc_sojourn,
)
from .common import Bench, write_json

TOP_LEVEL_ARTIFACT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_sizing.json")

LAMBDA_COST = 0.5     # $/hr weight vs seconds of latency
SLO_PENALTY = 100.0   # per second of per-class deadline violation

#: Daytime: browse/search dominate (catalog/product/pricing load).
MIX_DAY = {"browse": 45.0, "search": 25.0, "checkout": 6.0}
#: Evening: checkout dominates (auth/orders/inventory load).
MIX_EVENING = {"browse": 14.0, "search": 8.0, "checkout": 30.0}

SMALL_SIZES = (ContainerSize("small", 1, 2.0), ContainerSize("large", 4, 8.0))
LARGE_SIZES = (ContainerSize("small", 1, 2.0), ContainerSize("medium", 2, 4.0),
               ContainerSize("large", 4, 8.0))


def make_sizing_dag() -> MicroserviceDAG:
    """An 8-tier e-commerce-shaped DAG: fan-out at the gateway, a shared
    product tier behind search and catalog, pricing/inventory leaves."""
    tiers = (
        ServiceTier("gateway", base_rate=70.0, gamma=0.8),
        ServiceTier("auth", base_rate=90.0, gamma=0.7),
        ServiceTier("search", base_rate=30.0, gamma=0.75,
                    mem_per_rps_gb=0.1),          # memory-bound index
        ServiceTier("catalog", base_rate=45.0, gamma=0.75,
                    mem_per_rps_gb=0.08),
        ServiceTier("orders", base_rate=40.0, gamma=0.7),
        ServiceTier("product", base_rate=35.0, gamma=0.75),
        ServiceTier("pricing", base_rate=100.0, gamma=0.8),
        ServiceTier("inventory", base_rate=55.0, gamma=0.7),
    )
    edges = (
        ("gateway", "auth"), ("gateway", "search"), ("gateway", "catalog"),
        ("gateway", "orders"), ("search", "product"),
        ("catalog", "product"), ("orders", "pricing"),
        ("orders", "inventory"), ("product", "pricing"),
        ("product", "inventory"),
    )
    # deadlines tight enough to BIND: a per-tier-blind tuner must
    # overprovision off-critical-path tiers to stay inside them, which is
    # exactly the cross-tier effect the annealed controller exploits
    classes = (
        RequestClass("browse", "gateway",
                     {"gateway": 1, "catalog": 1, "product": 2,
                      "pricing": 2, "inventory": 1}, slo_s=0.25),
        RequestClass("search", "gateway",
                     {"gateway": 1, "search": 1, "product": 1,
                      "pricing": 1}, slo_s=0.28),
        RequestClass("checkout", "gateway",
                     {"gateway": 1, "auth": 1, "orders": 1, "pricing": 1,
                      "inventory": 2}, slo_s=0.40),
    )
    return MicroserviceDAG(tiers, edges, classes)


def small_spec() -> SizingSpace:
    return SizingSpace(make_sizing_dag(), sizes=SMALL_SIZES,
                       replica_counts=(1, 2), lambda_cost=LAMBDA_COST,
                       slo_penalty=SLO_PENALTY)


def large_spec() -> SizingSpace:
    return SizingSpace(make_sizing_dag(), sizes=LARGE_SIZES,
                       replica_counts=(1, 2), lambda_cost=LAMBDA_COST,
                       slo_penalty=SLO_PENALTY)


# ---------------------------------------------------------------------------
# Baselines.
# ---------------------------------------------------------------------------


def static_peak_sizing(spec: SizingSpace, peak: dict[str, float],
                       util_target: float = 0.55) -> dict:
    """The ops-classic baseline: per tier, the cheapest (size, replicas)
    whose capacity keeps utilization <= ``util_target`` at the PEAK mix;
    never resized afterwards."""
    lam = spec.dag.arrival_rates(peak)
    decoded: dict = {}
    for k, tier in enumerate(spec.dag.tiers):
        options = sorted(
            ((s, r) for s in spec.sizes for r in spec.replica_counts),
            key=lambda sr: (sr[1] * sr[0].cpu, sr[0].cpu))
        pick = None
        for s, r in options:
            if lam[k] <= util_target * r * tier.service_rate(s):
                pick = (s, r)
                break
        if pick is None:                      # saturated even at max: take it
            pick = max(options,
                       key=lambda sr: sr[1] * tier.service_rate(sr[0]))
        decoded[f"{tier.name}.size"] = pick[0].name
        decoded[f"{tier.name}.repl"] = pick[1]
    return decoded


def independent_sizing(spec: SizingSpace, mix: dict[str, float]) -> dict:
    """Per-tier-independent tuning: each tier picks the (size, replicas)
    minimizing its LOCAL objective — its own M/M/c sojourn against a
    visit-proportional share of each class SLO, plus its own cost — with
    no view of the other tiers (the cross-tier-blind strategy AutoTune
    shows oscillates/overspends; here it is even granted an exhaustive
    local search, i.e. the fixed point per-tier annealing converges to)."""
    dag = spec.dag
    lam = dag.arrival_rates(mix)
    rates = dag.rates_array(mix)
    total = rates.sum()
    shares = rates / total if total > 0 else np.zeros_like(rates)
    V = dag.visit_matrix()
    slos = np.asarray([c.slo_s for c in dag.classes])
    vsum = np.maximum(V.sum(axis=1), 1e-12)
    decoded: dict = {}
    for k, tier in enumerate(dag.tiers):
        budget = slos * V[:, k] / vsum            # per-class SLO share
        best, best_y = None, np.inf
        for s in spec.sizes:
            for r in spec.replica_counts:
                t = mmc_sojourn(lam[k], tier.service_rate(s), r,
                                sat_s=spec.sat_s)
                spent = V[:, k] * t                # class time at this tier
                pen = np.maximum(spent - budget, 0.0)
                y = float((shares * (spent + spec.slo_penalty * pen)).sum()
                          + spec.lambda_cost * r * s.cpu
                          * spec.price_per_core_hr)
                if y < best_y:
                    best, best_y = (s, r), y
        decoded[f"{tier.name}.size"] = best[0].name
        decoded[f"{tier.name}.repl"] = best[1]
    return decoded


# ---------------------------------------------------------------------------
# The bench.
# ---------------------------------------------------------------------------


def container_sizing(smoke: bool = False) -> dict:
    b = Bench("container_sizing",
              "paper abstract case study 3: container sizing for "
              "microservice benchmarks")
    result: dict = {"smoke": smoke, "lambda_cost": LAMBDA_COST,
                    "slo_penalty": SLO_PENALTY}
    spec = small_spec()
    n_rounds = 16 if smoke else 36
    change_at = n_rounds // 3
    mix_sched = DriftingMix(MIX_DAY, MIX_EVENING, change_at=change_at)
    result["small_space_states"] = spec.space.size()
    b.check(f"the DAG has 8 tiers (6-10 required), small space "
            f"{spec.space.size():,} states", 6 <= spec.dag.n_tiers <= 10
            and spec.space.size() <= 200_000)

    # -- exhaustive ground truth per mix phase (ONE batched call each) --
    grid = full_grid(spec.space)
    opt_day = float(evaluate_sizing_batch(spec, grid, MIX_DAY)["y"].min())
    opt_eve = float(
        evaluate_sizing_batch(spec, grid, MIX_EVENING)["y"].min())
    result["grid_optimum"] = {"day": opt_day, "evening": opt_eve}

    # -- the online annealed controller vs both baselines, per round --
    ctrl = SizingController(spec, mix_sched, steps_per_round=64,
                            n_chains=16, seed=0)
    static_dec = static_peak_sizing(spec, mix_sched.peak())
    traj = []
    t0 = time.perf_counter()
    for r in range(n_rounds):
        d = ctrl.round()
        mix = mix_sched.at(r)
        stat = spec.host_objective(static_dec, mix)
        ind = spec.host_objective(independent_sizing(spec, mix), mix)
        traj.append({
            "round": r,
            "phase": "day" if r < change_at else "evening",
            "annealed": {"y": d.y, "usd_per_hr": d.usd_per_hr,
                         "slo_attainment": d.slo_attainment,
                         "cores": d.config.total_cores},
            "static_peak": {"y": stat["y"], "usd_per_hr": stat["cost"],
                            "slo_attainment": stat["slo_attainment"]},
            "independent": {"y": ind["y"], "usd_per_hr": ind["cost"],
                            "slo_attainment": ind["slo_attainment"]},
        })
    wall = time.perf_counter() - t0

    warm = traj[3:]                       # skip the cold-start rounds
    mean = lambda rows, who, key: float(
        np.mean([r[who][key] for r in rows]))
    ann_y = mean(warm, "annealed", "y")
    stat_y = mean(warm, "static_peak", "y")
    ind_y = mean(warm, "independent", "y")
    ann_cost = mean(warm, "annealed", "usd_per_hr")
    stat_cost = mean(warm, "static_peak", "usd_per_hr")
    ann_att = mean(warm, "annealed", "slo_attainment")
    stat_att = mean(warm, "static_peak", "slo_attainment")
    result["online"] = {
        "rounds": n_rounds, "change_at": change_at, "wall_s": round(wall, 1),
        "mean_y": {"annealed": ann_y, "static_peak": stat_y,
                   "independent": ind_y},
        "mean_usd_per_hr": {"annealed": ann_cost, "static_peak": stat_cost,
                            "independent": mean(warm, "independent",
                                                "usd_per_hr")},
        "mean_slo_attainment": {"annealed": ann_att,
                                "static_peak": stat_att,
                                "independent": mean(warm, "independent",
                                                    "slo_attainment")},
        "trajectory": traj,
    }
    b.check(f"annealed beats static-peak on combined Y "
            f"({ann_y:.3f} vs {stat_y:.3f})", ann_y < stat_y)
    b.check(f"lower cost at equal-or-better SLO attainment "
            f"(${ann_cost:.2f}/hr vs ${stat_cost:.2f}/hr at attainment "
            f"{ann_att:.3f} vs {stat_att:.3f})",
            ann_cost < stat_cost and ann_att >= stat_att - 1e-9)
    b.check(f"annealed (cross-tier) also beats per-tier-independent "
            f"tuning on Y ({ann_y:.3f} vs {ind_y:.3f})", ann_y < ind_y)

    # -- source seams on the SAME small space: exhaustive + surrogate --
    exh = SizingController(spec, MIX_DAY,
                           objective_source=ExhaustiveSource(),
                           steps_per_round=64, n_chains=16, seed=1)
    exh.run(3 if smoke else 6)
    _, y_exh = exh.best_sizing()
    gap_exh = (y_exh - opt_day) / abs(opt_day)
    # IDW power 6 is near-nearest-neighbour — the right bias when 3200
    # probes must cover a 16-dimensional product (smoother settings pull
    # every estimate toward the global mean and flatten the wells)
    sur_src = SurrogateSource(
        n_probe=3200, seed=2,
        model=SurrogateModel(SpaceEncoding.from_space(spec.space),
                             idw_power=6.0))
    sur = SizingController(spec, MIX_DAY, objective_source=sur_src,
                           steps_per_round=64, n_chains=16, seed=2)
    sur.run(3 if smoke else 6)
    _, y_sur = sur.best_sizing()
    gap_sur = (y_sur - opt_day) / abs(opt_day)
    result["sources_small_space"] = {
        "exhaustive": {"best_y": y_exh, "gap_pct": 100 * gap_exh,
                       "true_measures": exh.objective_source.true_measures},
        "surrogate": {"best_y": y_sur, "gap_pct": 100 * gap_sur,
                      "true_measures": sur_src.true_measures,
                      "probe_fraction": sur_src.true_measures
                      / spec.space.size()},
    }
    b.check(f"exhaustive-source controller within 5% of the grid optimum "
            f"(gap {100 * gap_exh:.2f}%)", gap_exh <= 0.05)
    b.check(f"surrogate-source sizes the same DAG at "
            f"{sur_src.true_measures / spec.space.size():.1%} of the "
            f"exhaustive evaluations (gap {100 * gap_sur:.2f}%)",
            sur_src.true_measures <= 0.05 * spec.space.size()
            and gap_sur <= 0.35)

    # -- the large-DAG case: beyond the tabulation cap (tier-2 nightly) --
    if not smoke:
        big = large_spec()
        result["large_space_states"] = big.space.size()
        b.check(f"rich menu exceeds the 200k tabulation cap "
                f"({big.space.size():,} states)",
                big.space.size() > 200_000)
        try:
            tabulate(big.space, lambda d: 0.0)
            refused = False
        except ValueError:
            refused = True
        b.check("tabulate() refuses the large space", refused)
        t0 = time.perf_counter()
        big_src = SurrogateSource(n_probe=1024, seed=3)
        big_ctrl = SizingController(big, MIX_DAY,
                                    objective_source=big_src,
                                    steps_per_round=64, n_chains=16,
                                    seed=3)
        y_cold = float(big.host_objective(
            big.space.decode(big_ctrl.incumbent), MIX_DAY)["y"])
        big_ds = big_ctrl.run(4)
        _, y_big = big_ctrl.best_sizing()
        result["large"] = {
            "cold_start_y": y_cold, "best_y": y_big,
            "true_measures": big_src.true_measures,
            "slo_attainment": big_ds[-1].slo_attainment,
            "wall_s": round(time.perf_counter() - t0, 1),
        }
        b.check(f"surrogate-backed controller improves the cold-start "
                f"deployment ({y_cold:.2f} -> {y_big:.2f}) with "
                f"{big_src.true_measures} real measures "
                f"({big_src.true_measures / big.space.size():.3%} of the "
                f"space)", y_big < y_cold
                and big_src.true_measures < 0.01 * big.space.size())

    write_json("container_sizing.json", result)
    with open(TOP_LEVEL_ARTIFACT, "w") as f:
        json.dump({
            "bench": "container_sizing",
            "smoke": smoke,
            "trajectory": traj,
            "mean_y": result["online"]["mean_y"],
            "mean_usd_per_hr": result["online"]["mean_usd_per_hr"],
            "mean_slo_attainment": result["online"]["mean_slo_attainment"],
            "gap_pct_small_space": {
                "exhaustive": 100 * gap_exh, "surrogate": 100 * gap_sur},
        }, f, indent=2)
    print(f"SLO/$-trajectory -> {TOP_LEVEL_ARTIFACT}")
    return b.finish()


def run_all() -> list[dict]:
    return [container_sizing()]


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced budgets, skip the large-DAG case "
                         "(tier-1 CI)")
    args = ap.parse_args()
    enable_compile_cache()
    res = container_sizing(smoke=args.smoke)
    print(json.dumps({k: v for k, v in res.items() if k != "checks"},
                     indent=2))
    raise SystemExit(0 if res["ok"] else 1)
