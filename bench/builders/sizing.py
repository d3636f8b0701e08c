"""Container sizing of a microservice DAG under a request-mix schedule.

Set-up builds the ``SizingSpace`` and ``SizingController`` from the
configuration file and runs the traffic's warm rounds, which compile the
round's programs and, for a repeating schedule, fill the table cache.  A
tick is one ``round()``: read the mix, build (or reuse) the objective
table on the device, anneal, read the chosen sizing back and re-measure
it.  After the window the reference replays the run (see
``reference/sizing.py``), decides every round of the window itself, and
compares the tables that a seeded sample of window rounds annealed on
with its own.
"""

from __future__ import annotations

import importlib
from typing import Any, Mapping

import numpy as np

from reference.sizing import SizingReference


class Cell:
    def __init__(self, cfg: Mapping[str, Any], traffic: Mapping[str, Any],
                 seeds: Mapping[str, int]):
        self.cfg, self.traffic, self.seeds = cfg, traffic, seeds
        # the traffic file names its generator under ``bench/gen/``
        self.gen = importlib.import_module("gen." + traffic["generator"])
        self.records: list[tuple] = []
        self.window_start: int | None = None
        self.n_tables = int(traffic["check_tables"])
        self.tables: list[tuple] = []
        self.reservoir = np.random.default_rng(seeds["sample"])

    def setup(self) -> None:
        from repro.core import SizingController, SizingSpace
        from repro.workloads.microservice import (
            ContainerSize, MicroserviceDAG, RequestClass, ServiceTier)

        c = self.cfg
        dag = MicroserviceDAG(
            tuple(ServiceTier(**t) for t in c["tiers"]),
            tuple(tuple(e) for e in c["edges"]),
            tuple(RequestClass(**k) for k in c["classes"]))
        self.spec = SizingSpace(
            dag, sizes=tuple(ContainerSize(**s) for s in c["sizes"]),
            replica_counts=tuple(c["replica_counts"]),
            price_per_core_hr=c["price_per_core_hr"],
            lambda_cost=c["lambda_cost"], slo_penalty=c["slo_penalty"],
            sat_s=c["sat_s"])
        self.mixes = self.gen.generate(self.traffic,
                                           self.seeds["traffic"])
        self.ctrl = SizingController(
            self.spec, self.mixes.__getitem__,
            steps_per_round=c["steps_per_round"], n_chains=c["n_chains"],
            tau=c["tau"], detector=True, seed=self.seeds["controller"],
            measure_topk=c["measure_topk"])
        self.index = [{v: i for i, v in enumerate(d.values)}
                      for d in self.spec.space.dimensions]
        self.dims = [d.name for d in self.spec.space.dimensions]
        for _ in range(int(self.traffic["warm_rounds"])):
            self.record(self.tick())
        self.window_start = len(self.records)

    def tick(self):
        if len(self.records) >= len(self.mixes):
            raise StopIteration
        return self.ctrl.round()

    def decisions_due(self) -> int:
        return 1

    def record(self, d) -> tuple[int, int]:
        state = tuple(ix[d.sizing[k]] for ix, k in zip(self.index,
                                                        self.dims))
        self.records.append((state, float(d.y)))
        if self.window_start is not None:
            # a seeded reservoir of window rounds, each with the device
            # table it annealed on, as the controller's table cache holds
            # it (a reference to the array: nothing is copied or waited for)
            i = len(self.records) - 1 - self.window_start
            table = self.ctrl._dtables[self.ctrl._mix_key(d.mix)]
            if i < self.n_tables:
                self.tables.append((len(self.records) - 1, table))
            else:
                j = int(self.reservoir.integers(i + 1))
                if j < self.n_tables:
                    self.tables[j] = (len(self.records) - 1, table)
        return 1, 0

    def check(self, control: bool = False) -> dict[str, dict[str, float]]:
        """Replay the run through the reference, which decides every
        round of the window itself.  Returns the numbers compared for the
        program (and, with ``control``, for the reference one precision
        lower in its place): the widest relative excess of a committed
        sizing's objective over the round's bound (``SizingReference.
        decide``), the widest relative gap of a committed objective from
        the reference's value of that sizing, and the median over the
        states of the relative gap between the table a sampled round
        annealed on and the reference's, the worst over the sample.
        Reported beside them: the widest such gap, and the widest gap
        over the reference's error bound at the state, which has to stay
        under 1 for the bound on the decision to hold (``TABLE_ERROR``);
        the share of rounds whose sizing differs from the reference's own
        walk, which a chaotic walk makes nonzero in sound runs; and the
        share of chain steps that no table within the error decides
        otherwise."""
        import ml_dtypes

        ref = SizingReference(self.cfg, self.seeds["controller"])
        states = [s for s, _ in self.records]
        _, reheats = ref.replay(self.mixes, states)
        modes = ("sound", "low") if control else ("sound",)
        who = ("program", "control") if control else ("program",)
        acc = {w: [0.0, 0.0, 0.0, 0.0, 0, 0.0, 0.0] for w in who}
        window = range(self.window_start, len(self.records))
        for r, out in ref.decide(window, self.mixes, states, reheats, modes):
            for w in who:
                a = acc[w]
                if w == "program":
                    y = self.records[r][1]
                    gap = abs(y - out["y64"]) / max(abs(out["y64"]), 1e-12)
                    a[0] = max(a[0], out["excess"])
                    a[4] += int(states[r] != out["sound"])
                else:
                    # the control re-measures in float32, one precision
                    # below the configuration's float64
                    y = out["low_y64"]
                    gap = abs(float(np.float32(y)) - y) / max(abs(y), 1e-12)
                    a[0] = max(a[0], out["low_excess"])
                    a[4] += int(out["low"] != out["sound"])
                a[1] = max(a[1], gap)
                a[5] += out["robust_share"]
        for r, table in self.tables:
            mix = self.mixes[r]
            t_ref, err = ref._tables_of(mix)
            for w in who:
                if w == "program":
                    got = np.asarray(table, np.float64)
                else:
                    got = ref.table(mix, "low").astype(
                        ml_dtypes.bfloat16).astype(np.float64)
                a = acc[w]
                rel = np.abs(got - t_ref) / np.abs(t_ref)
                a[2] = max(a[2], float(np.median(rel)))
                a[3] = max(a[3], float(rel.max()))
                a[6] = max(a[6], float((np.abs(got - t_ref) / err).max()))
        n = max(len(window), 1)
        return {w: {"decision_excess": a[0], "y_rel_gap": a[1],
                    "table_rel_gap_median": a[2], "table_rel_gap_max": a[3],
                    "table_gap_over_bound": a[6],
                    "decision_mismatch": a[4] / n, "robust_share": a[5] / n}
                for w, a in acc.items()}
