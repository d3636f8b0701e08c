"""Plain reference of the multi-tenant fleet's control round.

Written from the semantics, in numpy, and importing nothing of the
program: the EC2 cost model and blended tenant tables (float64), the
coupling penalty of aggregate capacity and budget overshoot, the
Metropolis chains (float32, as the configuration states for the device),
the greedy admission and preemption arbitration, the reservation ledger
and the per-tenant Page-Hinkley drift detector that re-arms the active
set.  Random draws come from ``jax.random`` with the keys the
configuration's seed defines (``chains.py``).

The reference is teacher-forced: it replays the run's trace events and,
after each round, adopts the allocation the program committed, so a
difference in one round does not carry into the next.  In the sampled
rounds it decides the round itself and the harness compares.
"""

from __future__ import annotations

from typing import Any, Mapping, Sequence

import numpy as np

from .chains import chain_keys_fleet, draws, run_chains

ACTIONS = ("admit", "hold", "defer", "preempt")


class FleetModel:
    """The static part: families, the (family, cores) state grid, the
    usage model and one float64 objective table per blend."""

    def __init__(self, cfg: Mapping[str, Any]):
        fams = sorted(cfg["families"], key=lambda f: f["price_per_core_hr"])
        self.families = [f["name"] for f in fams]
        self.cores = [int(c) for c in cfg["core_counts"]]
        self.shape = (len(fams), len(self.cores))
        self.jobs = cfg["jobs"]
        self.lam = float(cfg["lambda_cost"])
        self.weight = float(cfg["penalty_weight"])
        # capacity per family in the catalog's own (name) order, as the
        # program's usage arrays are laid out
        self.cat_order = [f["name"] for f in cfg["families"]]
        self.fam = {f["name"]: f for f in fams}
        n = int(cfg["n_tenants"])
        S = self.shape[0] * self.shape[1]
        F = len(self.cat_order)
        self.cores_by_family = np.zeros((F, S))
        self.spend = np.zeros(S)
        for s in range(S):
            fi, ci = divmod(s, self.shape[1])
            name = self.families[fi]
            self.cores_by_family[self.cat_order.index(name), s] = float(
                self.cores[ci])
            self.spend[s] = (self.fam[name]["price_per_core_hr"]
                             * float(self.cores[ci]))
        self.capacity = np.full(
            F, float(cfg["cores_per_family_per_tenant"]) * n)
        self.budget = float(cfg["budget_usd_hr_per_tenant"]) * n
        self.fallback = int(np.lexsort(
            (self.cores_by_family.sum(0), self.spend))[0])
        self._tables: dict[tuple, np.ndarray] = {}

    def state_of(self, family: str, cores: int) -> int:
        return self.families.index(family) * self.shape[1] \
            + self.cores.index(int(cores))

    def exec_time(self, job: str, family: str, cores: int) -> float:
        j, f = self.jobs[job], self.fam[family]
        t = (j["serial_s"] + j["work"] / (cores * f["speed"])
             + j["coord"] * cores ** 0.8)
        deficit = max(0.0, j["mem_gb_per_core"] - f["mem_per_core_gb"])
        t *= 1.0 + 0.35 * deficit
        if family == "storage":
            t -= j["io_bound"]
        return max(t, 1e-3)

    def table(self, blend: Mapping[str, float]) -> np.ndarray:
        """Blended base objective ``sum_j w_j (t_j + lambda c_j)`` over
        every state, with ``c_j`` the dollars of running job j."""
        key = tuple(blend.items())
        if key not in self._tables:
            names = list(blend)
            w = np.asarray([blend[k] for k in names], np.float64)
            w = w / w.sum()
            out = np.empty(self.shape[0] * self.shape[1])
            for s in range(out.size):
                fi, ci = divmod(s, self.shape[1])
                fam, cores = self.families[fi], self.cores[ci]
                price = self.fam[fam]["price_per_core_hr"]
                total = 0
                for name, wj in zip(names, w):
                    t = self.exec_time(name, fam, cores)
                    c = price * cores * (t / 3600.0)
                    total += wj * (t + self.lam * c)
                out[s] = float(total)
            self._tables[key] = out
        return self._tables[key]

    # -- coupling and feasibility ----------------------------------------

    def coupling_rows(self, inc: np.ndarray) -> np.ndarray:
        """(T, S): weight x the aggregate overshoot tenant i would cause
        at each state, the others staying where they are."""
        cbf = self.cores_by_family
        agg_c = cbf[:, inc].sum(1)
        agg_s = float(self.spend[inc].sum())
        oth_c = agg_c[:, None] - cbf[:, inc]
        oth_s = agg_s - self.spend[inc]
        over_c = np.clip(cbf[:, None, :]
                         + (oth_c - self.capacity[:, None])[:, :, None],
                         0.0, None).sum(0)
        over_b = np.clip(self.spend[None, :]
                         + (oth_s - self.budget)[:, None], 0.0, None)
        return 0.0 + self.weight * (over_c + over_b)

    def overshoot(self, cores: np.ndarray, spend: float) -> float:
        return float(np.clip(cores - self.capacity, 0.0, None).sum()
                     + max(0.0, spend - self.budget))

    def aggregate(self, states: np.ndarray) -> tuple[np.ndarray, float]:
        return (self.cores_by_family[:, states].sum(1),
                float(self.spend[states].sum()))

    def _best_fit(self, base: np.ndarray, cores_wo, spend_wo) -> int:
        row = (np.clip(self.cores_by_family
                       + (cores_wo - self.capacity)[:, None], 0.0,
                       None).sum(0)
               + np.clip(self.spend + (spend_wo - self.budget), 0.0, None))
        fits = row - self.overshoot(cores_wo, spend_wo) <= 1e-9
        if not fits.any():
            return self.fallback
        return int(np.where(fits, base, np.inf).argmin())

    def arbitrate(self, inc, proposals, pen, base, prio):
        """Admit improving proposals greedily by priority-weighted gain
        while the aggregate stays feasible; then, if the incumbents
        themselves overshoot, move the lowest-priority contributors to
        their best fitting state."""
        T = len(inc)
        cur = inc.copy()
        cores, spend = self.aggregate(cur)
        t = np.arange(T)
        deltas = pen[t, cur] - pen[t, proposals]
        actions = ["hold"] * T
        for i in np.argsort(-(prio * deltas), kind="stable"):
            if proposals[i] == cur[i] or deltas[i] <= 0:
                continue
            dc = (self.cores_by_family[:, proposals[i]]
                  - self.cores_by_family[:, cur[i]])
            ds = self.spend[proposals[i]] - self.spend[cur[i]]
            if self.overshoot(cores + dc, spend + ds) <= 1e-9:
                cores, spend = cores + dc, spend + ds
                cur[i] = proposals[i]
                actions[i] = "admit"
            else:
                actions[i] = "defer"
        if self.overshoot(cores, spend) > 1e-9:
            for i in sorted(range(T), key=lambda i: prio[i]):
                v = self.overshoot(cores, spend)
                if v <= 1e-9:
                    break
                cores_wo = cores - self.cores_by_family[:, cur[i]]
                spend_wo = spend - self.spend[cur[i]]
                if v - self.overshoot(cores_wo, spend_wo) <= 1e-9:
                    continue
                best = self._best_fit(base[i], cores_wo, spend_wo)
                if best != cur[i]:
                    cores = cores_wo + self.cores_by_family[:, best]
                    spend = spend_wo + float(self.spend[best])
                    cur[i] = best
                    actions[i] = "preempt"
        return cur, actions


class Detector:
    """Standardized two-sided Page-Hinkley test, one stream per tenant."""

    def __init__(self, p: Mapping[str, float]):
        self.delta, self.threshold = p["delta"], p["threshold"]
        self.min_obs, self.z_clip = p["min_obs"], p["z_clip"]
        self.n = np.zeros(0, np.int64)
        self.mean = np.zeros(0)
        self.m2 = np.zeros(0)
        self.up = np.zeros(0)
        self.down = np.zeros(0)

    def add(self) -> None:
        self.n = np.append(self.n, 0)
        for a in ("mean", "m2", "up", "down"):
            setattr(self, a, np.append(getattr(self, a), 0.0))

    def remove(self, i: int) -> None:
        for a in ("n", "mean", "m2", "up", "down"):
            setattr(self, a, np.delete(getattr(self, a), i))

    def update(self, y: np.ndarray) -> np.ndarray:
        ok = np.isfinite(y)
        y0 = np.where(ok, y, 0.0)
        self.n = self.n + ok
        d = np.where(ok, y0 - self.mean, 0.0)
        self.mean = self.mean + d / np.maximum(self.n, 1)
        self.m2 = self.m2 + d * np.where(ok, y0 - self.mean, 0.0)
        live = ok & (self.n >= self.min_obs)
        std = np.sqrt(self.m2 / np.maximum(self.n - 1, 1)) + 1e-12
        z = np.clip((y0 - self.mean) / std, -self.z_clip, self.z_clip)
        self.up = np.where(live, np.maximum(0.0, self.up + z - self.delta),
                           self.up)
        self.down = np.where(
            live, np.maximum(0.0, self.down - z - self.delta), self.down)
        fired = live & ((self.up > self.threshold)
                        | (self.down > self.threshold))
        for a in ("n", "mean", "m2", "up", "down"):
            arr = getattr(self, a)
            arr[fired] = 0
        return fired


def tau_rows(reheat_at: Sequence[float | None], n0: int, steps: int,
             base: float, hot: float, relax: float) -> np.ndarray:
    ns = np.arange(n0, n0 + steps, dtype=np.float64)
    out = np.empty((len(reheat_at), steps))
    for k, at in enumerate(reheat_at):
        if at is None:
            out[k] = base
        else:
            v = base + (hot - base) * relax ** np.maximum(ns - at, 0.0)
            out[k] = np.where(ns < at, base, v)
    return out


class FleetReference:
    """Replays a run round by round (see the module docstring)."""

    def __init__(self, cfg: Mapping[str, Any], profiles, seed: int):
        self.cfg = cfg
        self.model = FleetModel(cfg)
        self.profiles = profiles
        self.seed = int(seed)
        self.steps = int(cfg["steps_per_round"])
        self.settle_n = int(cfg["settle_rounds"])
        self.sched = (float(cfg["tau"]), float(cfg["tau_hot"]),
                      float(cfg["relax"]))
        self.names: list[str] = []
        self.blend: list[int] = []
        self.prio: list[float] = []
        self.sid: list[int] = []
        self.inc = np.zeros(0, np.int64)
        self.settle = np.zeros(0, np.int64)
        self.pending: list[bool] = []
        self.reheat_at: list[float | None] = []
        self.det = Detector(cfg["detector"])
        self.next_sid = 0

    def arrive(self, name: str, profile: int, priority: float) -> None:
        self.names.append(name)
        self.blend.append(profile)
        self.prio.append(priority)
        self.sid.append(self.next_sid)
        self.next_sid += 1
        self.inc = np.append(self.inc, self.model.fallback)
        self.settle = np.append(self.settle, self.settle_n)
        self.pending.append(False)
        self.reheat_at.append(None)
        self.det.add()

    def depart(self, name: str) -> None:
        i = self.names.index(name)
        for lst in (self.names, self.blend, self.prio, self.sid,
                    self.pending, self.reheat_at):
            del lst[i]
        self.inc = np.delete(self.inc, i)
        self.settle = np.delete(self.settle, i)
        self.det.remove(i)

    def phase(self, name: str, profile: int) -> None:
        i = self.names.index(name)
        if self.blend[i] != profile:
            self.blend[i] = profile
        self.settle[i] = self.settle_n

    def round(self, r: int, committed: np.ndarray, actions: np.ndarray,
              decide: Sequence[str] = ()) -> dict[str, Any]:
        """One round.  ``committed``/``actions`` are the program's, in
        this reference's tenant order.  For each precision in ``decide``
        ("sound": float64 host, float32 chains; "low": float32 host,
        bfloat16 chains) the reference decides the round itself and
        returns its states, actions and objective values."""
        m = self.model
        T = len(self.names)
        base = np.stack([m.table(self.profiles[b]) for b in self.blend])
        rows = m.coupling_rows(self.inc)
        pen = base + rows
        active = np.flatnonzero((self.settle > 0)
                                | np.asarray(self.pending, bool))
        n0 = r * self.steps
        for i in active:
            if self.pending[i]:
                self.reheat_at[i] = float(n0)
                self.pending[i] = False
        out: dict[str, Any] = {"pen": pen}
        if decide and len(active):
            taus = tau_rows([self.reheat_at[i] for i in active], n0,
                            self.steps, *self.sched)
            ax, dr, u = draws(chain_keys_fleet(
                self.seed, r, [self.sid[i] for i in active]), self.steps,
                len(m.shape))
            inits = np.stack(np.unravel_index(self.inc[active], m.shape),
                             axis=-1)
        for mode in decide:
            if mode == "sound":
                base_m, rows_m, chain_dtype = base, rows, "float32"
                pen_m = pen
            else:
                base_m = base.astype(np.float32).astype(np.float64)
                rows_m = rows.astype(np.float32).astype(np.float64)
                pen_m = (base_m + rows_m).astype(np.float32).astype(
                    np.float64)
                chain_dtype = "bfloat16"
            proposals = self.inc.copy()
            if len(active):
                states = run_chains(ax, dr, u, base_m[active],
                                    rows_m[active], taus, inits, m.shape,
                                    chain_dtype)
                flat = np.ravel_multi_index(
                    tuple(np.concatenate([inits[:, None, :], states],
                                         axis=1).transpose(2, 0, 1)),
                    m.shape)
                best = np.take_along_axis(pen_m[active], flat,
                                          axis=1).argmin(1)
                proposals[active] = flat[np.arange(len(active)), best]
            final, acts = m.arbitrate(self.inc, proposals, pen_m, base_m,
                                      np.asarray(self.prio))
            out[mode] = (final, np.asarray([ACTIONS.index(a)
                                            for a in acts]),
                         pen_m[np.arange(T), final])
        self.settle[active] = np.maximum(self.settle[active] - 1, 0)
        fired = self.det.update(pen[np.arange(T), self.inc])
        for i in np.flatnonzero(fired):
            self.pending[i] = True
            self.settle[i] = self.settle_n
        self.inc = np.asarray(committed, np.int64).copy()
        self.settle[np.asarray(actions) == ACTIONS.index("preempt")] = \
            self.settle_n
        return out
