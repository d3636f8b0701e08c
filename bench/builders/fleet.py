"""The multi-tenant fleet under a churn trace.

Set-up builds the catalog, space, evaluator and ``FleetController`` from
the configuration file, generates the trace, runs one round of a
throwaway fleet at every power-of-two chain bucket the trace can reach
(so no bucket compiles inside the window), then the traffic's warm
rounds.  A tick is what a cluster's event loop does each control period:
apply the period's arrivals, departures and phase changes through the
fleet's public API, then run ``round()``, which returns the decisions on
the host.  After the window the reference replays the run (see
``reference/fleet.py``) and decides a sample of its rounds itself.
"""

from __future__ import annotations

import importlib
from typing import Any, Callable, Mapping

import numpy as np

from reference.chains import bucket
from reference.fleet import ACTIONS, FleetReference


def apply_events(events, live: set[str], arrive: Callable,
                 depart: Callable, phase: Callable) -> None:
    """One tick's trace events, as a cluster's event loop applies them:
    an arrival of a tenant not yet live (``arrive(event)``), a departure
    of a live tenant unless it is the last (``depart(tenant)``), a phase
    change of a live tenant (``phase(event)``); ``live`` follows."""
    for e in events:
        if e.kind == "arrive":
            if e.tenant not in live:
                arrive(e)
                live.add(e.tenant)
        elif e.kind == "depart":
            if e.tenant in live and len(live) > 1:
                depart(e.tenant)
                live.discard(e.tenant)
        elif e.tenant in live:
            phase(e)


class Cell:
    def __init__(self, cfg: Mapping[str, Any], traffic: Mapping[str, Any],
                 seeds: Mapping[str, int]):
        self.cfg, self.traffic, self.seeds = cfg, traffic, seeds
        # the traffic file names its generator under ``bench/gen/``
        self.gen = importlib.import_module("gen." + traffic["generator"])
        self.records: list[tuple] = []
        self.window_start = 0

    # -- set-up -------------------------------------------------------------

    def _fleet(self, catalog, tenants):
        from repro.core import FleetController, Objective, PenalizedObjective

        c = self.cfg
        return FleetController(
            self.space, catalog, self.evaluator, tenants,
            objective=PenalizedObjective(
                Objective(lambda_cost=c["lambda_cost"]),
                weight=c["penalty_weight"]),
            budget_usd_hr=c["budget_usd_hr_per_tenant"] * c["n_tenants"],
            steps_per_round=c["steps_per_round"], tau=c["tau"],
            detectors=True, seed=self.seeds["controller"],
            incremental=c["incremental"], settle_rounds=c["settle_rounds"],
            chain_bucketing=c["chain_bucketing"],
            ledger_check_every=c["ledger_check_every"],
            keep_decision_log=False)

    def _spec(self, e):
        from repro.core import TenantSpec

        return TenantSpec(name=e.tenant,
                          blend=dict(self.trace.profiles[e.profile]),
                          priority=e.priority)

    def setup(self) -> None:
        from repro.core import (InstanceFamily, JobModel, ServiceCatalog,
                                SimulatedEvaluator, TenantSpec,
                                make_ec2_space)

        c = self.cfg
        n = int(c["n_tenants"])
        fams = {f["name"]: InstanceFamily(
            f["name"], price_per_core_hr=f["price_per_core_hr"],
            mem_per_core_gb=f["mem_per_core_gb"], spin_up_s=f["spin_up_s"])
            for f in c["families"]}
        self.capacities = {f: c["cores_per_family_per_tenant"] * n
                           for f in fams}
        self.catalog = ServiceCatalog(fams, self.capacities)
        self.space = make_ec2_space(self.catalog,
                                    core_counts=tuple(c["core_counts"]))
        self.evaluator = SimulatedEvaluator(self.catalog, jobs={
            k: JobModel(k, **v) for k, v in c["jobs"].items()})
        self.trace = self.gen.generate(
            self.traffic, self.seeds["traffic"],
            job_names=sorted(c["jobs"]), n_tenants=n)
        founding = [e for e in self.trace.events
                    if e.t == 0.0 and e.kind == "arrive"]
        # one round at every chain bucket up to the trace's peak: every
        # tenant of a new fleet anneals in its first round
        profiles = self.trace.profiles
        P = 1
        while P <= bucket(self.trace.concurrency_peak()):
            self._fleet(ServiceCatalog(fams, self.capacities), [
                TenantSpec(f"warm-{k:05d}", dict(profiles[k % len(profiles)]))
                for k in range(P)]).round()
            P *= 2
        self.fleet = self._fleet(self.catalog,
                                 [self._spec(e) for e in founding])
        self.live = {e.tenant for e in founding}
        self.ticks = self.gen.ticks(self.trace,
                                       self.traffic["control_period_s"])
        self.fam_names = list(fams)
        self.state_of = {
            (f, int(k)): s for s, (f, k) in enumerate(
                (f, k) for f in self.space.dimensions[0].values
                for k in self.space.dimensions[1].values)}
        for _ in range(int(self.traffic["warm_rounds"])):
            self.record(self.tick())
        self.window_start = len(self.records)

    # -- the timed tick ---------------------------------------------------

    def tick(self):
        _, events = next(self.ticks)
        fleet = self.fleet
        apply_events(
            events, self.live, lambda e: fleet.add_tenant(self._spec(e)),
            fleet.remove_tenant,
            lambda e: fleet.retune_tenant(
                e.tenant, dict(self.trace.profiles[e.profile])))
        return fleet.round()

    def decisions_due(self) -> int:
        return len(self.live)

    def record(self, decisions) -> tuple[int, int]:
        """Keep what the reference needs of a round; returns (decisions
        committed, decisions of a round that ended over capacity)."""
        so = self.state_of
        names = tuple(d.tenant for d in decisions)
        states = np.fromiter((so[(d.config.instance_type,
                                  d.config.n_workers)] for d in decisions),
                             np.int64, len(decisions))
        acts = np.fromiter((ACTIONS.index(d.action) for d in decisions),
                           np.int8, len(decisions))
        ys = np.fromiter((d.y for d in decisions), np.float64,
                         len(decisions))
        reserved = np.asarray([self.catalog.reserved(f)
                               for f in self.fam_names])
        violation = float(self.fleet.violation_history[-1])
        self.records.append((names, states, acts, ys, reserved, violation))
        n = len(decisions)
        return n, (n if violation > 1e-9 else 0)

    # -- correctness --------------------------------------------------------

    def check(self, control: bool = False) -> dict[str, dict[str, float]]:
        """Replay the run through the reference and decide its sampled
        rounds.  Returns the numbers compared for the program (and, with
        ``control``, for the reference run one precision lower in its
        place): the share of sampled tenant-decisions whose state or
        action differs from the reference's, the widest relative gap of a
        committed objective from the reference's value of that state, and
        the widest gap in cores between the reservation ledger and the
        committed allocation."""
        window = range(self.window_start, len(self.records))
        rng = np.random.default_rng(self.seeds["sample"])
        k = min(int(self.traffic["check_rounds"]), len(window))
        sample = set(int(r) for r in rng.choice(list(window), k,
                                                replace=False))
        if window:
            sample.add(max(window, key=lambda r: len(self.records[r][0])))
        ref = FleetReference(self.cfg, self.trace.profiles,
                             self.seeds["controller"])
        if ref.model.cat_order != self.fam_names:
            raise RuntimeError("family order differs from the catalog's")
        live: set[str] = set()
        for e in self.trace.events:
            if e.t == 0.0 and e.kind == "arrive":
                ref.arrive(e.tenant, e.profile, e.priority)
                live.add(e.tenant)
        ticks = self.gen.ticks(self.trace,
                                  self.traffic["control_period_s"])
        modes = ("sound", "low") if control else ("sound",)
        who = ("program", "control") if control else ("program",)
        acc = {w: [0, 0, 0.0, 0.0] for w in who}
        for r, rec in enumerate(self.records):
            apply_events(
                next(ticks)[1], live,
                lambda e: ref.arrive(e.tenant, e.profile, e.priority),
                ref.depart, lambda e: ref.phase(e.tenant, e.profile))
            names, states, acts, ys, reserved, _ = rec
            if tuple(ref.names) != names:
                raise RuntimeError(f"round {r}: the program's tenants "
                                   f"differ from the trace's")
            out = ref.round(r, states, acts,
                            decide=modes if r in sample else ())
            if r not in sample:
                continue
            want_s, want_a, _ = out["sound"]
            T = len(names)
            for w in who:
                if w == "program":
                    got_s, got_a, got_y = states, acts, ys
                else:
                    got_s, got_a, got_y = out["low"]
                    got_y = got_y.astype(np.float32)
                a = acc[w]
                a[0] += int(((got_s != want_s) | (got_a != want_a)).sum())
                a[1] += T
                y_ref = out["pen"][np.arange(T), got_s]
                a[2] = max(a[2], float(np.max(np.abs(got_y - y_ref)
                                              / np.maximum(np.abs(y_ref),
                                                           1.0))))
                cores, spend = ref.model.aggregate(got_s)
                held = (cores if ref.model.overshoot(cores, spend) <= 1e-9
                        else np.zeros_like(cores))
                ledger = reserved if w == "program" else held
                a[3] = max(a[3], float(np.max(np.abs(ledger - held))))
        return {w: {"decision_mismatch": a[0] / max(a[1], 1),
                    "y_rel_gap": a[2], "ledger_gap_cores": a[3]}
                for w, a in acc.items()}
