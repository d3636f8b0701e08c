"""Container sizing past the tabulation cap: the surrogate's table.

As ``sizing.py``, with a space too large to tabulate: the controller
holds a ``SurrogateSource``, and every round's table is built on the
device by one program that draws the configuration's ``n_probe`` probes
from the round's key, scores them on the Erlang-C path at the round's
mix and interpolates every state from them.  A tick is one ``round()``.

After the window the reference (``reference/surrogate.py``) replays the
run, decides every window round on its own float64 interpolation, and
compares the tables that a seeded sample of window rounds annealed on
with its own, at a seeded sample of states plus the states each of
those rounds' chains started from.
"""

from __future__ import annotations

import numpy as np

from builders.sizing import Cell as SizingCell
from reference.surrogate import SurrogateReference

#: states of each sampled table compared with the reference, besides
#: the round's chain starts and its committed sizing
TABLE_SAMPLE = 65_536


class Cell(SizingCell):
    def setup(self) -> None:
        from repro.core import (SizingController, SizingSpace,
                                SpaceEncoding, SurrogateModel)
        # the device table path: a program without it stops here, at once
        from repro.core.surrogate import SurrogateSource, draw_probes  # noqa: F401
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
        s = c["surrogate"]
        model = SurrogateModel(SpaceEncoding.from_space(self.spec.space),
                               kind=s["kind"], idw_power=s["idw_power"])
        self.source = SurrogateSource(n_probe=s["n_probe"], model=model)
        self.mixes = self.gen.generate(self.traffic, self.seeds["traffic"])
        self.ctrl = SizingController(
            self.spec, self.mixes.__getitem__, objective_source=self.source,
            steps_per_round=c["steps_per_round"], n_chains=c["n_chains"],
            tau=c["tau"], detector=True, seed=self.seeds["controller"],
            measure_topk=c["measure_topk"])
        self.index = [{v: i for i, v in enumerate(d.values)}
                      for d in self.spec.space.dimensions]
        self.dims = [d.name for d in self.spec.space.dimensions]
        for _ in range(int(self.traffic["warm_rounds"])):
            self.record(self.tick())
        self.window_start = len(self.records)

    def check(self, control: bool = False) -> dict[str, dict[str, float]]:
        """Replay the run through the reference, which decides every
        round of the window on its own interpolation.  Returns, for the
        program (and with ``control`` for the reference one precision
        lower in its place: the table rounded to bfloat16, bfloat16
        chains, a float32 re-measure), the numbers of ``sizing.Cell``:
        ``decision_excess`` (by the float64 table, past its error bound),
        ``y_rel_gap`` (against the exact model), and over the sampled
        tables ``table_rel_gap_median`` (the median relative gap, worst
        table) and ``table_gap_over_bound`` (the widest gap over the
        reference's error bound, which a sound table keeps under 1; an
        altered entry at a state the chains start from shows here).
        Reported beside them: ``table_rel_gap_max``,
        ``decision_mismatch`` and ``robust_share``."""
        import ml_dtypes

        ref = SurrogateReference(self.cfg, self.seeds["controller"])
        states = [s for s, _ in self.records]
        replayed = ref.replay(self.mixes, states)
        modes = ("sound", "low") if control else ("sound",)
        who = ("program", "control") if control else ("program",)
        acc = {w: [0.0, 0.0, 0.0, 0.0, 0, 0.0, 0.0] for w in who}
        window = range(self.window_start, len(self.records))
        sampled = {r for r, _ in self.tables}
        starts = {}
        for r, out in ref.decide(window, self.mixes, states, replayed,
                                 modes):
            if r in sampled:
                starts[r] = out["starts"]
            for w in who:
                a = acc[w]
                if w == "program":
                    y = self.records[r][1]
                    gap = abs(y - out["y64"]) / max(abs(out["y64"]), 1e-12)
                    a[0] = max(a[0], out["excess"])
                    a[4] += int(states[r] != out["sound"])
                else:
                    y = out["low_y64"]
                    gap = abs(float(np.float32(y)) - y) / max(abs(y), 1e-12)
                    a[0] = max(a[0], out["low_excess"])
                    a[4] += int(out["low"] != out["sound"])
                a[1] = max(a[1], gap)
                a[5] += out["robust_share"]
        rng = np.random.default_rng(self.seeds["sample"])
        for r, table in self.tables:
            ref.probes([r], self.mixes)
            at = np.concatenate([
                rng.choice(ref.size, TABLE_SAMPLE, replace=False),
                starts[r],
                [np.ravel_multi_index(states[r], ref.shape)]])
            t_ref, err = ref.interp_at(r, at)
            for w in who:
                if w == "program":
                    got = np.asarray(table, np.float64)[at]
                else:
                    got = t_ref.astype(np.float32).astype(
                        ml_dtypes.bfloat16).astype(np.float64)
                a = acc[w]
                rel = np.abs(got - t_ref) / np.abs(t_ref)
                a[2] = max(a[2], float(np.median(rel)))
                a[3] = max(a[3], float(rel.max()))
                a[6] = max(a[6], float((np.abs(got - t_ref) / err).max()))
            ref.forget()
        n = max(len(window), 1)
        return {w: {"decision_excess": a[0], "y_rel_gap": a[1],
                    "table_rel_gap_median": a[2], "table_rel_gap_max": a[3],
                    "table_gap_over_bound": a[6],
                    "decision_mismatch": a[4] / n, "robust_share": a[5] / n}
                for w, a in acc.items()}

