"""Plain reference of the container-sizing control round.

Written from the semantics, in numpy, and importing nothing of the
program.  A state is one (container size, replica count) pair per tier.
Each tier is an M/M/c queue: arrival rate ``rates @ visits``, per-replica
service rate ``base * (cpu / cpu_ref) ** gamma`` capped by memory, and
sojourn = Erlang-C wait + service time, or ``sat_s`` when the tier is
unstable.  A class's latency is the visit-weighted critical path of the
call DAG from its entry tier.  The objective is the mix-share-weighted
latency with an SLO hinge penalty, plus ``lambda_cost`` x $/hr.

The round: chains from random starting states (chain 0 at the incumbent)
walk the float32 table (``chains.py``); the visited state with the lowest
table value, first in chain-major order on ties, is re-measured in
float64 and committed.  A Page-Hinkley test on the committed objective
reheats the next round.  The reference is teacher-forced by the
program's committed sizing of every round (``SizingReference.replay``),
so it can decide any round on its own, and decides many together
(``SizingReference.decide``).

A Metropolis walk is chaotic where the objective is flat on the scale of
its float32 rounding: across the plateau of saturated sizings (values of
1e6 and more, one unit in the last place 0.06 or more, against a
temperature of 1) two float32 tables of one objective send a chain
different ways.  So the reference does not ask for the program's sizing
itself.  It bounds it: up to its first step that a table within the
error bound of the float64 one (``TABLE_ERROR``) could decide otherwise,
every chain walks the same states in any sound implementation, and the
committed sizing can be no worse than the best of those states, give or
take that error (``bound`` in ``SizingReference.decide``).
"""

from __future__ import annotations

import math
from typing import Any, Mapping, Sequence

import numpy as np

from .chains import chain_keys_sizing, draws, run_chains
from .fleet import tau_rows


#: bound on the relative gap between a sound float32 table and the
#: float64 objective, ``TABLE_ERROR + TABLE_ERROR_PER_AMP * amp`` with
#: ``amp`` the state's amplification (``SizingModel.amplification``).  On
#: a TPU v5e the kernel's gap over 98 mixes was at most 3.9e-7 where amp
#: < 2 and at most 2.2e-5 x amp anywhere; near saturation, where
#: ``c mu - lambda`` cancels, it has no other bound
TABLE_ERROR = 1e-5
TABLE_ERROR_PER_AMP = 1e-4


class SizingModel:
    def __init__(self, cfg: Mapping[str, Any]):
        self.tiers = cfg["tiers"]
        self.names = [t["name"] for t in self.tiers]
        K = len(self.tiers)
        self.sizes = cfg["sizes"]
        self.repl = [int(r) for r in cfg["replica_counts"]]
        self.classes = cfg["classes"]
        self.class_names = [c["name"] for c in self.classes]
        self.visits = np.zeros((len(self.classes), K))
        for ci, c in enumerate(self.classes):
            for t, v in c["visits"].items():
                self.visits[ci, self.names.index(t)] = float(v)
        self.adj = np.zeros((K, K), bool)
        for u, v in cfg["edges"]:
            self.adj[self.names.index(u), self.names.index(v)] = True
        self.entry = [self.names.index(c["entry"]) for c in self.classes]
        self.slo = np.asarray([c["slo_s"] for c in self.classes])
        self.price = float(cfg["price_per_core_hr"])
        self.lam_cost = float(cfg["lambda_cost"])
        self.slo_pen = float(cfg["slo_penalty"])
        self.sat_s = float(cfg["sat_s"])
        self.shape = (len(self.sizes), len(self.repl)) * K
        # every state's per-tier menu option (size-major), (S, K)
        grid = np.indices(self.shape).reshape(len(self.shape), -1).T
        self.opt_idx = grid[:, 0::2] * len(self.repl) + grid[:, 1::2]

    def rates(self, mix: Mapping[str, float]) -> np.ndarray:
        return np.asarray([float(mix.get(c, 0.0))
                           for c in self.class_names])

    def tier_options(self, mix: Mapping[str, float]) -> tuple[np.ndarray, ...]:
        """Per tier and menu option (size-major, then replica count):
        cpus, replicas and per-replica service rate, (K, n_opt) each; and
        the tiers' arrival rates (K,).  A state's tier takes option
        ``opt_idx[state, tier]``."""
        K = len(self.tiers)
        grid = np.indices((len(self.sizes), len(self.repl))).reshape(2, -1)
        si = np.broadcast_to(grid[0], (K, grid.shape[1]))
        ri = np.broadcast_to(grid[1], (K, grid.shape[1]))
        cpu = np.asarray([s["cpu"] for s in self.sizes], float)[si]
        mem = np.asarray([s["mem_gb"] for s in self.sizes], float)[si]
        c = np.asarray(self.repl, float)[ri]
        base = np.asarray([t["base_rate"] for t in self.tiers])[:, None]
        ref = np.asarray([t.get("cpu_ref", 1.0) for t in self.tiers])[:, None]
        gamma = np.asarray([t["gamma"] for t in self.tiers])[:, None]
        mrps = np.asarray([t.get("mem_per_rps_gb", 0.0)
                           for t in self.tiers])[:, None]
        mu = base * (cpu / ref) ** gamma
        mu = np.where(mrps > 0, np.minimum(mu, mem / np.where(
            mrps > 0, mrps, 1.0)), mu)
        return cpu, c, mu, self.rates(mix) @ self.visits

    def _gather(self, per_option: np.ndarray,
                rows: np.ndarray | None) -> np.ndarray:
        """(K, n_opt) per tier and option -> (S, K) per state of ``rows``
        (flat indices; all states when None) and tier."""
        opt = self.opt_idx if rows is None else self.opt_idx[rows]
        return per_option[np.arange(opt.shape[1]), opt]

    def amplification(self, mix: Mapping[str, float]) -> np.ndarray:
        """Per state, the largest ``c mu / |c mu - lambda|`` over its
        tiers: how far the cancellation in a tier's spare capacity
        magnifies a rounding error of its rates, on either side of
        saturation."""
        _, c, mu, lam = self.tier_options(mix)
        lam = lam[:, None]
        amp = c * mu / np.maximum(np.abs(c * mu - lam), 1e-12)
        return self._gather(amp, None).max(1)

    def objective(self, mix: Mapping[str, float],
                  rows: np.ndarray | None = None) -> np.ndarray:
        """Float64 objective of the states ``rows`` (flat indices; all
        states when None).  A tier's sojourn depends on its own option
        alone, so it is worked out per tier and option and gathered."""
        cpu, c, mu, lam = self.tier_options(mix)
        lam = lam[:, None]
        rates = self.rates(mix)
        a = lam / mu
        b = np.ones_like(a)
        b_c = np.zeros_like(a)
        for k in range(1, max(self.repl) + 1):
            b = a * b / (k + a * b)
            b_c = np.where(c == k, b, b_c)
        rho = a / c
        p_wait = b_c / np.maximum(1.0 - rho * (1.0 - b_c), 1e-12)
        slack = c * mu - lam
        with np.errstate(divide="ignore", invalid="ignore"):
            soj = np.where(slack > 1e-9, p_wait / slack + 1.0 / mu,
                           self.sat_s)
        soj = self._gather(soj, rows)
        K = len(self.tiers)
        lat = np.empty((soj.shape[0], len(self.classes)))
        for ci in range(len(self.classes)):
            node = self.visits[ci] * soj
            L = np.zeros_like(node)
            for v in range(K - 1, -1, -1):
                kids = np.flatnonzero(self.adj[v])
                child = L[:, kids].max(1) if kids.size else 0.0
                L[:, v] = node[:, v] + np.maximum(child, 0.0)
            lat[:, ci] = L[:, self.entry[ci]]
        total = rates.sum()
        shares = rates / total if total > 0 else np.zeros_like(rates)
        viol = np.maximum(lat - self.slo, 0.0)
        cost = self._gather(c * cpu, rows).sum(1) * self.price
        return ((shares * (lat + self.slo_pen * viol)).sum(1)
                + self.lam_cost * cost)


class PageHinkley:
    def __init__(self, p: Mapping[str, float]):
        self.p = p
        self.reset()

    def reset(self) -> None:
        self.n, self.mean, self.m2, self.up, self.down = 0, 0.0, 0.0, 0.0, 0.0

    def update(self, y: float) -> bool:
        p = self.p
        self.n += 1
        d = y - self.mean
        self.mean += d / self.n
        self.m2 += d * (y - self.mean)
        if self.n < p["min_obs"]:
            return False
        std = math.sqrt(self.m2 / (self.n - 1)) + 1e-12
        z = max(-p["z_clip"], min(p["z_clip"], (y - self.mean) / std))
        self.up = max(0.0, self.up + z - p["delta"])
        self.down = max(0.0, self.down - z - p["delta"])
        if self.up > p["threshold"] or self.down > p["threshold"]:
            self.reset()
            return True
        return False


def mix_key(mix: Mapping[str, float]) -> tuple:
    return tuple(sorted((k, float(v)) for k, v in mix.items()))


class SizingReference:
    def __init__(self, cfg: Mapping[str, Any], seed: int):
        self.model = SizingModel(cfg)
        self.seed = int(seed)
        self.steps = int(cfg["steps_per_round"])
        self.n_chains = int(cfg["n_chains"])
        self.sched = (float(cfg["tau"]), float(cfg["tau_hot"]),
                      float(cfg["relax"]))
        self.detector = cfg["detector"]
        self._tables: dict[tuple, tuple[np.ndarray, np.ndarray]] = {}

    def table(self, mix, precision: str = "sound") -> np.ndarray:
        y = self.model.objective(mix)
        return y if precision == "sound" else y.astype(np.float32)

    def _tables_of(self, mix) -> tuple[np.ndarray, np.ndarray]:
        """The float64 table of a mix and its error bound, kept for the
        last few mixes (a repeating schedule reads them again)."""
        key = mix_key(mix)
        if key not in self._tables:
            y64 = self.table(mix)
            err = np.abs(y64) * (TABLE_ERROR + TABLE_ERROR_PER_AMP
                                 * self.model.amplification(mix))
            self._tables[key] = (y64, err)
            while len(self._tables) > 8:
                self._tables.pop(next(iter(self._tables)))
        return self._tables[key]

    def replay(self, mixes: Sequence[Mapping[str, float]],
               committed: Sequence[Sequence[int]]
               ) -> tuple[np.ndarray, list[float | None]]:
        """Teacher-forced by the program's committed sizing of every
        round: the float64 objective of each committed sizing, and the
        step at which the schedule last reheated before each round (None
        before any), as the Page-Hinkley test on those objectives has
        it."""
        m = self.model
        det = PageHinkley(self.detector)
        pending, reheat_at = False, None
        y_c = np.empty(len(committed))
        reheats: list[float | None] = []
        for r, (mix, state) in enumerate(zip(mixes, committed)):
            if pending:
                reheat_at, pending = float(r * self.steps), False
            reheats.append(reheat_at)
            flat = np.ravel_multi_index(tuple(int(v) for v in state),
                                        m.shape)
            y_c[r] = m.objective(mix, np.asarray([flat]))[0]
            pending = det.update(float(y_c[r]))
        return y_c, reheats

    def decide(self, rounds: Sequence[int],
               mixes: Sequence[Mapping[str, float]],
               committed: Sequence[Sequence[int]],
               reheats: Sequence[float | None],
               modes: Sequence[str] = ("sound",), block: int = 64):
        """Decides each of ``rounds`` itself, in blocks of rounds whose
        chains walk together, and yields ``(r, out)`` per round.  Chain 0
        starts at the sizing committed the round before.  ``out`` holds:
        ``bound``, the best objective over the states every sound
        implementation walks, give or take the table error; ``excess``,
        how far the committed sizing's objective lies above the bound,
        past the table error at the sizing, relative to the bound (0 for
        every sound implementation); ``y64``, the float64 objective of
        the committed sizing; ``sound``, the reference's own choice;
        ``robust_share``, the share of chain steps no table within the
        error decides otherwise; and with ``"low"`` in ``modes`` the
        choice with a float32 table and bfloat16 chains, its ``excess``
        and ``y64`` (``low``, ``low_excess``, ``low_y64``)."""
        m = self.model
        C, ndim = self.n_chains, len(m.shape)
        rounds = list(rounds)
        start = (0,) * ndim
        for b0 in range(0, len(rounds), block):
            rs = rounds[b0:b0 + block]
            kd, inits = chain_keys_sizing(self.seed, rs, C, m.shape)
            for i, r in enumerate(rs):
                inits[i, 0] = committed[r - 1] if r > 0 else start
            inits = inits.reshape(len(rs) * C, ndim)
            ax, up, u = draws(kd.reshape(len(rs) * C, -1), self.steps, ndim)
            taus = np.concatenate([np.repeat(tau_rows(
                [reheats[r]], r * self.steps, self.steps, *self.sched), C, 0)
                for r in rs])
            # one table row per distinct mix of the block
            mix_of: dict[tuple, Mapping[str, float]] = {}
            for r in rs:
                mix_of.setdefault(mix_key(mixes[r]), mixes[r])
            row = {k: i for i, k in enumerate(mix_of)}
            tabs = [self._tables_of(mx) for mx in mix_of.values()]
            y64 = np.stack([tb[0] for tb in tabs])
            err = np.stack([tb[1] for tb in tabs])
            row_of = np.repeat([row[mix_key(mixes[r])] for r in rs], C)
            walked, robust = run_chains(ax, up, u, y64, None, taus, inits,
                                        m.shape, "float32", err_rows=err,
                                        row_of=row_of)
            if "low" in modes:
                y32 = y64.astype(np.float32)
                low = run_chains(ax, up, u, y32, None, taus, inits, m.shape,
                                 "bfloat16", row_of=row_of)
            for i, r in enumerate(rs):
                t = row[mix_key(mixes[r])]
                ch = slice(i * C, (i + 1) * C)
                sure = np.concatenate([inits[ch]] + [
                    walked[k, :robust[k]] for k in range(ch.start, ch.stop)])
                sure = np.ravel_multi_index(tuple(sure.T), m.shape)
                bound = float(np.min(y64[t, sure] + err[t, sure]))

                def excess(state):
                    s = int(np.ravel_multi_index(tuple(state), m.shape))
                    return (max(0.0, y64[t, s] - err[t, s] - bound)
                            / abs(bound), float(y64[t, s]))

                def choice(states, est):
                    visited = np.concatenate(
                        [inits[ch, None, :], states[ch]],
                        axis=1).reshape(-1, ndim)
                    flat = np.ravel_multi_index(tuple(visited.T), m.shape)
                    best = int(flat[np.argsort(est[flat], kind="stable")[0]])
                    return tuple(int(v) for v in np.unravel_index(
                        best, m.shape))

                out = {"bound": bound,
                       "robust_share": float(robust[ch].mean() / self.steps),
                       "sound": choice(walked,
                                       np.asarray(y64[t], np.float32))}
                out["excess"], out["y64"] = excess(committed[r])
                if "low" in modes:
                    out["low"] = choice(low, _bf16(y32[t]))
                    out["low_excess"], out["low_y64"] = excess(out["low"])
                yield r, out


def _bf16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return np.asarray(x, ml_dtypes.bfloat16).astype(np.float32)
