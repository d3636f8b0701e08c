"""Container sizing: anneal microservice DAG sizings online.

The paper's third case study — "container sizing for microservice
benchmarks" — cast in this repo's architecture.  The annealing state is
one (vertical size, replica count) pair per tier of a
:class:`repro.workloads.microservice.MicroserviceDAG`; the objective is
the mix-share-weighted end-to-end latency (visit-weighted DAG critical
path over per-tier M/M/c sojourns) with per-class SLO hinge penalties,
plus ``lambda_cost`` times the deployment's $/hr.

Pieces:

* :class:`SizingSpace` — the ConfigSpace builder: per-tier ``(size,
  replicas)`` ordinal axes over a container menu, plus the evaluation
  tables (service-rate curves, visit matrix, adjacency) shared by every
  evaluation path.

* :func:`evaluate_sizing_batch` — ONE jitted call scoring B candidate
  sizings: menu lookups -> per-tier service rates -> the Erlang-C +
  critical-path kernel (:mod:`repro.kernels.sizing_latency`; Pallas on
  TPU, the jnp reference elsewhere) -> per-class latencies, SLO
  attainment, cost and the scalar objective.  The whole-grid form of
  this call is how small spaces are tabulated.

* :class:`SizingController` — the online loop on
  :class:`repro.core.procurement.ControllerMixin`: each control round
  reads the (drifting) request mix, refreshes the objective table
  (cached per mix), anneals a compiled chain fleet from the incumbent,
  re-measures the chosen sizing on the numpy ground-truth model, and
  feeds drift detection -> reheats.  Tables come from the batched
  evaluator by default; spaces beyond the 200k tabulation cap must
  inject a :class:`repro.core.surrogate.SurrogateSource` (probe and
  interpolate), exactly like the other controllers.

* Fleet integration — :class:`MicroserviceEvaluator` +
  :func:`microservice_config_fn` let microservice tenants join a
  :class:`repro.core.fleet.FleetController`: the deployment's total-core
  footprint flows through the shared capacity ledger and
  coupling-penalty rows like any VM tenant's cores.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Mapping, Sequence

import numpy as np

from .costmodel import Evaluator
from .instrumentation import note_round
from .change_detect import PageHinkley
from .objective import Measurement
from .procurement import ControllerMixin, Decision
from .schedules import AdaptiveReheat
from .state import ClusterConfig, ConfigSpace, Dimension
from .surrogate import ObjectiveSource
from ..telemetry import provenance
from ..telemetry import registry as metrics
from ..telemetry import span
from ..workloads.microservice import (
    DEFAULT_SIZES,
    ContainerSize,
    MicroserviceDAG,
    as_mix_schedule,
)

#: Tabulation ceiling shared with :func:`repro.core.landscape.tabulate` —
#: beyond it, tables must come from a sparse-measurement source.
TABULATE_CAP = 200_000


@dataclasses.dataclass(frozen=True)
class SizingSpace:
    """ConfigSpace builder + evaluation tables for one sizing problem.

    Dimensions are interleaved per tier — ``"<tier>.size"`` (menu entry
    names, ordered by cpu) then ``"<tier>.repl"`` — so the compiled
    chain's +-1 moves are single-knob resizes, the paper's incremental
    exploration requirement on this scenario.
    """

    dag: MicroserviceDAG
    sizes: tuple[ContainerSize, ...] = DEFAULT_SIZES
    replica_counts: tuple[int, ...] = (1, 2, 3, 4, 6, 8)
    price_per_core_hr: float = 0.048
    lambda_cost: float = 1.0
    slo_penalty: float = 10.0
    sat_s: float = 1e4

    def __post_init__(self) -> None:
        if not self.sizes:
            raise ValueError("at least one container size required")
        if sorted(s.cpu for s in self.sizes) != [s.cpu for s in self.sizes]:
            raise ValueError("sizes must be ordered by ascending cpu")
        if (not self.replica_counts
                or any(r < 1 for r in self.replica_counts)
                or sorted(self.replica_counts) != list(self.replica_counts)):
            raise ValueError("replica_counts must be ascending and >= 1")
        if self.lambda_cost < 0 or self.slo_penalty < 0:
            raise ValueError("lambda_cost / slo_penalty must be >= 0")

    # ------------------------------------------------------------------
    # the ConfigSpace
    # ------------------------------------------------------------------

    @functools.cached_property
    def space(self) -> ConfigSpace:
        dims = []
        for tier in self.dag.tiers:
            dims.append(Dimension(f"{tier.name}.size",
                                  tuple(s.name for s in self.sizes)))
            dims.append(Dimension(f"{tier.name}.repl",
                                  tuple(self.replica_counts)))
        return ConfigSpace(tuple(dims))

    @property
    def c_max(self) -> int:
        return int(max(self.replica_counts))

    def sizing_of(
        self, decoded: Mapping[str, Any]
    ) -> dict[str, tuple[ContainerSize, int]]:
        """Decoded ConfigSpace mapping -> tier -> (size, replicas)."""
        by_name = {s.name: s for s in self.sizes}
        return {t.name: (by_name[decoded[f"{t.name}.size"]],
                         int(decoded[f"{t.name}.repl"]))
                for t in self.dag.tiers}

    def total_cores(self, decoded: Mapping[str, Any]) -> int:
        return self.dag.total_cores(self.sizing_of(decoded))

    # ------------------------------------------------------------------
    # ground truth (numpy, one sizing at a time — the "real system")
    # ------------------------------------------------------------------

    def host_objective(
        self, decoded: Mapping[str, Any], mix: Mapping[str, float]
    ) -> dict[str, Any]:
        """The objective and its components for one decoded sizing."""
        sizing = self.sizing_of(decoded)
        lat = self.dag.class_latencies(sizing, mix, sat_s=self.sat_s)
        cost = self.dag.cost_rate(sizing, self.price_per_core_hr)
        rates = self.dag.rates_array(mix)
        total = rates.sum()
        shares = rates / total if total > 0 else np.zeros_like(rates)
        slos = np.asarray([c.slo_s for c in self.dag.classes])
        viol = np.maximum(lat - slos, 0.0)
        pen_lat = float((shares * (lat + self.slo_penalty * viol)).sum())
        return {
            "y": pen_lat + self.lambda_cost * cost,
            "latency": lat,
            "penalized_latency": pen_lat,
            "cost": cost,
            "slo_attainment": (float((shares * (lat <= slos)).sum())
                               if total > 0 else 1.0),
        }

    # ------------------------------------------------------------------
    # batched evaluation tables (device constants, built once)
    # ------------------------------------------------------------------

    @functools.cached_property
    def _eval_body(self):
        """The un-jitted batched scoring closure shared by
        :attr:`_eval_jit` (caller-supplied candidates) and
        :attr:`_table_jit` (in-trace full-grid enumeration)."""
        import jax
        import jax.numpy as jnp

        from ..kernels import ops as kernel_ops
        from ..kernels.ref import sizing_latency_ref

        dag = self.dag
        K, C = dag.n_tiers, len(dag.classes)
        cpu_menu = jnp.asarray([s.cpu for s in self.sizes], jnp.float32)
        mem_menu = jnp.asarray([s.mem_gb for s in self.sizes], jnp.float32)
        repl_menu = jnp.asarray(self.replica_counts, jnp.float32)
        base = jnp.asarray([t.base_rate for t in dag.tiers], jnp.float32)
        cpu_ref = jnp.asarray([t.cpu_ref for t in dag.tiers], jnp.float32)
        gamma = jnp.asarray([t.gamma for t in dag.tiers], jnp.float32)
        mem_rps = jnp.asarray([t.mem_per_rps_gb for t in dag.tiers],
                              jnp.float32)
        visits = jnp.asarray(dag.visit_matrix(), jnp.float32)      # (C, K)
        adj = jnp.asarray(dag.adjacency())
        entries = jnp.asarray(dag.entry_indices(), jnp.int32)
        slos = jnp.asarray([c.slo_s for c in dag.classes], jnp.float32)
        c_max, sat_s = self.c_max, float(self.sat_s)
        price = float(self.price_per_core_hr)
        lam_cost, slo_pen = float(self.lambda_cost), float(self.slo_penalty)

        def run(cand, rates, use_kernel: bool):
            size_idx = cand[:, 0::2]                               # (B, K)
            repl_idx = cand[:, 1::2]
            cpu = cpu_menu[size_idx]
            mem = mem_menu[size_idx]
            mu = base[None, :] * (cpu / cpu_ref[None, :]) ** gamma[None, :]
            cap = jnp.where(mem_rps[None, :] > 0,
                            mem / jnp.maximum(mem_rps[None, :], 1e-12),
                            jnp.inf)
            mu = jnp.minimum(mu, cap)
            repl = repl_menu[repl_idx]
            # full f32: the TPU's default matmul rounds rates to bfloat16
            lam = jnp.matmul(rates, visits,                        # (K,)
                             precision=jax.lax.Precision.HIGHEST)
            B = cand.shape[0]
            # fold classes into rows (row b*C + c) so one kernel pass
            # yields every class's critical path
            lam_r = jnp.broadcast_to(lam, (B * C, K))
            mu_r = jnp.repeat(mu, C, axis=0)
            repl_r = jnp.repeat(repl, C, axis=0)
            w_r = jnp.tile(visits, (B, 1))
            fn = kernel_ops.sizing_latency if use_kernel \
                else sizing_latency_ref
            _, path = fn(lam_r, mu_r, repl_r, w_r, adj,
                         c_max=c_max, sat_s=sat_s)
            lat = path.reshape(B, C, K)[:, jnp.arange(C), entries]  # (B, C)
            cost = (repl * cpu).sum(axis=1) * price
            total = rates.sum()
            shares = jnp.where(total > 0,
                               rates / jnp.maximum(total, 1e-12), 0.0)
            viol = jnp.maximum(lat - slos[None, :], 0.0)
            y = ((shares[None, :] * (lat + slo_pen * viol)).sum(axis=1)
                 + lam_cost * cost)
            attain = jnp.where(
                total > 0,
                (shares[None, :] * (lat <= slos[None, :])).sum(axis=1),
                1.0)
            return y, lat, cost, attain

        return run

    @functools.cached_property
    def _eval_jit(self):
        import jax

        return jax.jit(self._eval_body, static_argnames=("use_kernel",))

    @functools.cached_property
    def _table_jit(self):
        """Full-grid objective table in ONE fused trace: candidate
        enumeration (``jnp.arange`` -> unravel) feeds the Erlang-C +
        critical-path scoring directly — no host-materialized
        (size, 2K) grid and no device->host result pull.  Returns the
        flat (size,) float32 device table for one rate vector."""
        import jax
        import jax.numpy as jnp

        body = self._eval_body
        shape = self.space.shape
        size = int(np.prod(shape))
        strides, acc = [], 1
        for n in reversed(shape):
            strides.append(acc)
            acc *= n
        strides = tuple(reversed(strides))          # row-major

        def run(rates, use_kernel: bool):
            flat = jnp.arange(size, dtype=jnp.int32)
            cand = jnp.stack([(flat // strides[d]) % shape[d]
                              for d in range(len(shape))], axis=1)
            y, _, _, _ = body(cand, rates, use_kernel)
            return y

        return jax.jit(run, static_argnames=("use_kernel",))


def sizing_table_device(
    spec: SizingSpace,
    mix: Mapping[str, float] | np.ndarray,
    use_kernel: bool | None = None,
):
    """Device-resident flat objective table for one request mix —
    candidate enumeration fused with the Erlang-C kernel in one jitted
    call (:attr:`SizingSpace._table_jit`).  The (size,) float32 result
    stays on device; :class:`SizingController`'s device loop reshapes it
    straight into :func:`repro.core.annealing.anneal_fleet`."""
    import jax
    import jax.numpy as jnp

    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    rates = (spec.dag.rates_array(mix) if isinstance(mix, Mapping)
             else np.asarray(mix, np.float64))
    if rates.shape != (len(spec.dag.classes),):
        raise ValueError(
            f"rates shape {rates.shape} != ({len(spec.dag.classes)},)")
    return spec._table_jit(jnp.asarray(rates, jnp.float32),
                           use_kernel=bool(use_kernel))


def evaluate_sizing_batch(
    spec: SizingSpace,
    candidates: np.ndarray | Sequence[Sequence[int]],
    mix: Mapping[str, float] | np.ndarray,
    use_kernel: bool | None = None,
) -> dict[str, np.ndarray]:
    """Score B candidate sizings in ONE jitted call.

    ``candidates`` is (B, 2K) index vectors in ``spec.space`` dimension
    order; ``mix`` a class->req/s mapping (or a class-ordered rate
    array).  ``use_kernel`` selects the Pallas path — default: on the
    TPU backend (elsewhere the jnp reference compiles to the same math
    without paying interpret-mode overhead on big grids).

    Returns ``{"y": (B,), "latency": (B, C), "cost": (B,),
    "slo_attainment": (B,)}`` as numpy arrays.
    """
    import jax
    import jax.numpy as jnp

    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    cand = np.asarray(candidates, np.int32)
    if cand.ndim != 2 or cand.shape[1] != 2 * spec.dag.n_tiers:
        raise ValueError(
            f"candidates shape {cand.shape} != (B, {2 * spec.dag.n_tiers})")
    rates = (spec.dag.rates_array(mix) if isinstance(mix, Mapping)
             else np.asarray(mix, np.float64))
    if rates.shape != (len(spec.dag.classes),):
        raise ValueError(
            f"rates shape {rates.shape} != ({len(spec.dag.classes)},)")
    y, lat, cost, attain = spec._eval_jit(
        jnp.asarray(cand), jnp.asarray(rates, jnp.float32),
        use_kernel=bool(use_kernel))
    return {"y": np.asarray(y, np.float64),
            "latency": np.asarray(lat, np.float64),
            "cost": np.asarray(cost, np.float64),
            "slo_attainment": np.asarray(attain, np.float64)}


def full_grid(space: ConfigSpace) -> np.ndarray:
    """(size, ndim) index vectors over the whole product (small spaces)."""
    return np.indices(space.shape).reshape(len(space.shape), -1).T


@functools.cache
def _sizing_select_jit(shape: tuple, topk: int):
    """Jitted on-device top-K candidate selection + exploration flag.

    Replicates the host path exactly: stable argsort of the visited
    states' table estimates (ties break by visit position, chain-major),
    first-``topk``-distinct dedup, plus the per-chain accepted-uphill
    reduction of :meth:`repro.core.procurement.ControllerMixin.
    explored_flags`.  Returns ((topk, ndim) int32 states with -1
    sentinel rows, scalar explored flag)."""
    import jax
    import jax.numpy as jnp

    strides, acc = [], 1
    for n in reversed(shape):
        strides.append(acc)
        acc *= n
    strides = tuple(reversed(strides))              # row-major

    @jax.jit
    def select(inits, states, table, ys, accepts):
        nd = inits.shape[1]
        visited = jnp.concatenate(
            [inits[:, None, :], states], axis=1).reshape(-1, nd)
        vflat = jnp.zeros(visited.shape[0], jnp.int32)
        iflat = jnp.zeros(inits.shape[0], jnp.int32)
        for d in range(nd):
            vflat = vflat + visited[:, d].astype(jnp.int32) * strides[d]
            iflat = iflat + inits[:, d].astype(jnp.int32) * strides[d]
        order = jnp.argsort(table[vflat], stable=True)

        def body(j, carry):
            chosen, cnt = carry
            f = vflat[order[j]]
            ok = (cnt < topk) & jnp.all(chosen != f)
            upd = chosen.at[jnp.minimum(cnt, topk - 1)].set(f)
            return jnp.where(ok, upd, chosen), cnt + ok.astype(jnp.int32)

        chosen, _ = jax.lax.fori_loop(
            0, vflat.shape[0], body,
            (jnp.full((topk,), -1, jnp.int32), jnp.int32(0)))
        cols, rem = [], chosen
        for d in range(nd):
            cols.append(rem // strides[d])
            rem = rem % strides[d]
        sel = jnp.where(chosen[:, None] >= 0,
                        jnp.stack(cols, axis=1), -1)

        # per-chain accepted-uphill flags (ControllerMixin.explored_flags)
        C, steps = ys.shape
        kk = jnp.arange(steps)[None, :]
        last = jax.lax.cummax(jnp.where(accepts, kk, -1), axis=1)
        prev = jnp.concatenate(
            [jnp.full((C, 1), -1), last[:, :-1]], axis=1)
        inc_before = jnp.where(
            prev >= 0,
            jnp.take_along_axis(ys, jnp.maximum(prev, 0), axis=1),
            table[iflat][:, None])
        explored = (accepts & (ys > inc_before)).any()
        return sel, explored

    return select


# ---------------------------------------------------------------------------
# The online controller.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SizingDecision(Decision):
    """Per-round sizing audit record.

    ``measurement.exec_time_s`` is the deadline-penalized mix-weighted
    end-to-end latency, ``measurement.cost_usd`` the deployment $/hr;
    ``y`` is the ground-truth objective re-measured AFTER the round's
    move (the drift-detector input), not the table estimate.  ``config``
    summarizes the deployment footprint (total cores) so fleet-style
    audit tooling keyed on ``config.total_cores`` works unchanged.
    """

    sizing: Mapping[str, Any]
    mix: Mapping[str, float]
    usd_per_hr: float
    slo_attainment: float


class SizingController(ControllerMixin):
    """Online annealing over container sizings under a drifting mix.

    Each :meth:`round`: read the request mix from the schedule, refresh
    the objective table if the mix changed (cached per mix), anneal
    ``n_chains`` compiled chains for ``steps_per_round`` transitions in
    one :func:`repro.core.annealing.anneal_fleet` call (chain 0 at the
    incumbent), move to the best visited sizing, re-measure it on the
    numpy ground truth and feed the drift detector (reheat next round on
    a signal — covers *unannounced* drift, e.g. a schedule the
    controller cannot see).

    ``objective_source=None`` tabulates via ONE
    :func:`evaluate_sizing_batch` whole-grid call (counted into
    ``true_measures`` — the batched analog of ``ExhaustiveSource``) and
    refuses spaces beyond the 200k cap; inject a
    :class:`repro.core.surrogate.SurrogateSource` to probe-and-
    interpolate large DAGs, or an ``ExhaustiveSource`` to force the
    scalar one-state-at-a-time path.
    """

    def __init__(
        self,
        spec: SizingSpace,
        mix: Mapping[str, float] | Any,
        objective_source: ObjectiveSource | None = None,
        steps_per_round: int = 48,
        n_chains: int = 8,
        tau: float = 1.0,
        tau_hot: float | None = None,
        detector: bool = True,
        seed: int = 0,
        init: Sequence[int] | None = None,
        family: str = "container",
        measure_topk: int = 1,
        eval_workers: int | None = None,
        recycle_store: "Any | None" = None,
        device_loop: bool = True,
    ):
        import jax

        if steps_per_round < 1 or n_chains < 1:
            raise ValueError("steps_per_round and n_chains must be >= 1")
        if measure_topk < 1:
            raise ValueError("measure_topk must be >= 1")
        self.spec = spec
        self.space = spec.space
        self.family = family
        self._mix_at = as_mix_schedule(mix)
        self.objective_source = objective_source
        if (objective_source is None
                and self.space.size() > TABULATE_CAP):
            raise ValueError(
                f"space has {self.space.size()} states — beyond the "
                f"{TABULATE_CAP} tabulation cap; inject a SurrogateSource "
                f"(probe and interpolate) to size this DAG")
        self.measure_topk = int(measure_topk)
        self.eval_workers = eval_workers
        self.recycle_store = recycle_store
        self._init_decision_log()
        self._enc = self.space.encoded(max_size=max(
            self.space.size(), TABULATE_CAP))
        self._shape = self._enc.shape
        self._key = jax.random.key(seed)
        self.steps_per_round = int(steps_per_round)
        self.n_chains = int(n_chains)
        self._schedule = AdaptiveReheat(
            tau_base=tau, tau_hot=8.0 * tau if tau_hot is None else tau_hot,
            relax=0.9)
        self._detector = PageHinkley() if detector else None
        self._reheat_pending = False
        self._tables: dict[tuple, np.ndarray] = {}
        # device-resident control loop (tentpole): table enumeration +
        # scoring fused on device, anneal + top-K selection on device,
        # only the (topk, ndim) decision packet read back
        self.device_loop = bool(device_loop)
        self._dtables: dict[tuple, Any] = {}
        self._round = 0
        if init is None:
            # cheapest deployment: smallest size, fewest replicas per tier
            init = (0,) * len(self._shape)
        if not self.space.contains(init):
            raise ValueError(f"init {tuple(init)} not in the space")
        self.incumbent: tuple[int, ...] = tuple(int(i) for i in init)

    # ------------------------------------------------------------------
    # tables
    # ------------------------------------------------------------------

    def _mix_key(self, rates: Mapping[str, float]) -> tuple:
        return tuple((c, round(float(rates.get(c, 0.0)), 9))
                     for c in self.spec.dag.class_names)

    #: Tables kept for the most recent distinct mixes.  A ramped/continuous
    #: mix schedule yields a fresh key every round; without eviction each
    #: one pins a full-space float64 table (13 MB at the 1.68M-state rich
    #: menu) forever, and old mixes never recur exactly.
    TABLE_CACHE = 8

    def _table_for(self, rates: Mapping[str, float]) -> np.ndarray:
        """Flat (size,) objective table for one request mix; cached for
        the last :attr:`TABLE_CACHE` distinct mixes (stalest evicted)."""
        key = self._mix_key(rates)
        if key in self._tables:
            self._tables[key] = self._tables.pop(key)   # refresh LRU order
        else:
            if self.objective_source is None:
                res = evaluate_sizing_batch(
                    self.spec, full_grid(self.space), rates)
                self._count_measures(self.space.size())
                self._tables[key] = res["y"]
            else:
                def fn(decoded: dict[str, Any]) -> float:
                    self._count_measures(1)
                    return float(
                        self.spec.host_objective(decoded, rates)["y"])

                table = np.asarray(self.objective_source.table(
                    self.space, fn, valid_mask=self._enc.valid_mask),
                    np.float64)
                self._tables[key] = table.reshape(-1)
            while len(self._tables) > self.TABLE_CACHE:
                self._tables.pop(next(iter(self._tables)))
        return self._tables[key]

    def _dtable_for(self, rates: Mapping[str, float]):
        """Device flat (size,) objective table for one mix — the fused
        enumeration+scoring jit when tables come from the batched
        evaluator, a one-way host->device upload when an injected
        ``objective_source`` builds them; same LRU policy as
        :meth:`_table_for`."""
        import jax.numpy as jnp

        key = self._mix_key(rates)
        if key in self._dtables:
            self._dtables[key] = self._dtables.pop(key)
        else:
            if self.objective_source is None:
                self._dtables[key] = sizing_table_device(self.spec, rates)
                self._count_measures(self.space.size())
            else:
                self._dtables[key] = jnp.asarray(
                    self._table_for(rates), jnp.float32)
            while len(self._dtables) > self.TABLE_CACHE:
                self._dtables.pop(next(iter(self._dtables)))
        return self._dtables[key]

    # ------------------------------------------------------------------
    # the control round
    # ------------------------------------------------------------------

    _telemetry_prefix = "sizing"

    def _stats_rounds(self) -> int:
        return self._round

    def round(self) -> SizingDecision:
        with span("sizing.round", cat="sizing"):
            d = self._round_impl()
        if metrics.get() is not None:
            t_r = float(d.n)
            metrics.record("sizing/y", d.y, t_r)
            metrics.record("sizing/cost_usd_hr", d.usd_per_hr, t_r)
            metrics.record("sizing/slo_attainment", d.slo_attainment, t_r)
            if d.reheated:
                metrics.inc("sizing/reheats")
        return d

    def _round_impl(self) -> SizingDecision:
        import jax

        from .annealing import anneal_fleet, random_valid_states

        r = self._round
        rates = self._mix_at(r)

        n0 = r * self.steps_per_round
        reheated = False
        if self._reheat_pending:
            self._schedule.reheat(n0)
            self._reheat_pending = False
            reheated = True
        taus = self._schedule.tau_array(n0, self.steps_per_round)

        key_r = jax.random.fold_in(self._key, r)
        k_init, k_run = jax.random.split(key_r)

        if self.device_loop:
            import jax.numpy as jnp

            # device-resident phase: fused table -> anneal -> top-K
            # without a bulk host round-trip; only the (topk, ndim)
            # decision packet is read back
            with span("sizing.refit", cat="sizing"):
                table_d = self._dtable_for(rates)
            inits_d = random_valid_states(
                k_init, self._enc, self.n_chains).astype(jnp.int32)
            inits_d = inits_d.at[0].set(
                jnp.asarray(self.incumbent, jnp.int32))
            with span("sizing.anneal", cat="sizing",
                      metric="sizing/anneal_s"):
                out = anneal_fleet(
                    k_run, self._enc, table_d.reshape(self._shape),
                    self.steps_per_round,
                    jnp.broadcast_to(
                        jnp.asarray(taus, jnp.float32),
                        (self.n_chains, self.steps_per_round)),
                    inits=inits_d, n_chains=self.n_chains)
            sel, explored_d = _sizing_select_jit(
                self._shape, self.measure_topk)(
                inits_d, out["states"], table_d, out["ys"],
                out["accepts"])
            # .tolist()/bool() read the small decision packet — the one
            # host pull of the round, below the sanitizer's bulk-transfer
            # accounting (np.asarray / device_get)
            explored = bool(explored_d)
            cand_idx = [tuple(int(v) for v in row)
                        for row in sel.tolist() if row[0] >= 0]
            if provenance.get() is not None:
                # armed-only audit pulls (not on the steady-state path)
                inits = np.asarray(inits_d)
                table = np.asarray(table_d, np.float64)
                ys = np.asarray(out["ys"])
                accepts = np.asarray(out["accepts"])
                y0 = table[np.ravel_multi_index(tuple(inits.T),
                                                self._shape)]
                flat = np.ravel_multi_index(
                    tuple(np.concatenate(
                        [inits[:, None, :], np.asarray(out["states"])],
                        axis=1).reshape(-1, self._enc.ndim).T),
                    self._shape)
        else:
            with span("sizing.refit", cat="sizing"):
                table = self._table_for(rates)
            inits = np.array(
                random_valid_states(k_init, self._enc, self.n_chains),
                np.int32)
            inits[0] = np.asarray(self.incumbent, np.int32)
            with span("sizing.anneal", cat="sizing",
                      metric="sizing/anneal_s"):
                out = anneal_fleet(
                    k_run, self._enc,
                    table.reshape(self._shape).astype(np.float32),
                    self.steps_per_round,
                    np.broadcast_to(taus.astype(np.float32),
                                    (self.n_chains, self.steps_per_round)),
                    inits=inits, n_chains=self.n_chains)

            visited = np.concatenate(
                [inits[:, None, :], np.asarray(out["states"])],
                axis=1).reshape(-1, self._enc.ndim)
            flat = np.ravel_multi_index(tuple(visited.T), self._shape)

            # exploration: any chain accepted an uphill move this round
            ys = np.asarray(out["ys"])                    # (n_chains, steps)
            accepts = np.asarray(out["accepts"])
            y0 = table[np.ravel_multi_index(tuple(inits.T), self._shape)]
            explored = bool(self.explored_flags(ys, accepts, y0).any())

            # speculative ground-truth phase: the compiled fleet's
            # visited states ARE the engine-enumerated lookahead —
            # measure the ``measure_topk`` most promising (by table
            # estimate) on the numpy host model, commit to the *measured*
            # argmin, and recycle every measurement (mis-speculated
            # candidates included) into the store.  topk=1 is the
            # historical inline behavior: re-measure the single best
            # visited sizing.
            order = np.argsort(table[flat], kind="stable")
            cand: list[int] = []
            seen: set[int] = set()
            for j in order:
                f = int(flat[j])
                if f not in seen:
                    seen.add(f)
                    cand.append(f)
                if len(cand) == self.measure_topk:
                    break
            cand_idx = [tuple(int(v)
                              for v in np.unravel_index(f, self._shape))
                        for f in cand]
        with span("sizing.measure", cat="sizing"):
            results = self._measure_candidates(cand_idx, rates)
        self._count_measures(len(results))
        if self.recycle_store is not None:
            for st, rr in zip(cand_idx, results):
                self.recycle_store.add(st, float(rr["y"]), float(r))
        k_best = int(np.argmin([rr["y"] for rr in results]))
        prev = self.incumbent
        self.incumbent = cand_idx[k_best]
        decoded = self.space.decode(self.incumbent)
        res = results[k_best]
        y = float(res["y"])
        if self._detector is not None and self._detector.update(y):
            self._reheat_pending = True

        m = Measurement(
            exec_time_s=float(res["penalized_latency"]),
            cost_usd=float(res["cost"]),
            slo_violated=bool(res["slo_attainment"] < 1.0))
        counts = self.evaluation_counts()
        d = SizingDecision(
            n=r, job="mix", config=ClusterConfig(
                self.family, n_workers=self.spec.total_cores(decoded)),
            measurement=m, y=y, accepted=bool(self.incumbent != prev),
            explored=explored, tau=float(taus[-1]), reheated=reheated,
            sizing=decoded, mix=dict(rates),
            usd_per_hr=float(res["cost"]),
            slo_attainment=float(res["slo_attainment"]),
            true_measures=counts["true_measures"],
            surrogate_queries=counts["surrogate_queries"],
        )
        self.decisions.append(d)
        if provenance.get() is not None:
            self._record_round_provenance(
                r, d, res, results, cand_idx, k_best, prev, rates,
                ys, accepts, y0, taus, flat)
        self._round += 1
        note_round("SizingController", self)
        return d

    def _record_round_provenance(self, r, d, res, results, cand_idx,
                                 k_best, prev, rates, ys, accepts, y0,
                                 taus, flat) -> None:
        """One DecisionRecord per sizing round.  Armed-only; every input
        is something the round already computed.

        Exactness: the committed ``y`` came from ``host_objective`` as
        ``pen_lat + lambda_cost * cost``; ``exact_split`` replays those
        two IEEE ops on the same raw values, so it sums bit-for-bit.
        The named ladder splits ``pen_lat`` into its latency and SLO
        hinge shares (float64 round-off, inside the float32 bar)."""
        from .annealing import chain_accept_stats

        spec = self.spec
        pen_lat = res["penalized_latency"]
        cost_term = spec.lambda_cost * res["cost"]
        rates_arr = spec.dag.rates_array(rates)
        total = rates_arr.sum()
        shares = (rates_arr / total if total > 0
                  else np.zeros_like(rates_arr))
        lat_term = float((shares * np.asarray(res["latency"])).sum())
        terms = (("latency", lat_term),
                 ("slo_hinge", float(pen_lat) - lat_term),
                 ("cost", float(cost_term)))
        rejected, rejected_y = None, float("nan")
        others = [(j, float(results[j]["y"]))
                  for j in range(len(results)) if j != k_best]
        if others:
            j = min(others, key=lambda jv: jv[1])[0]
            rejected, rejected_y = cand_idx[j], float(results[j]["y"])
        # the chain that visited the committed state (chain 0 — the
        # incumbent chain — when the winner came from the measured topk
        # of another chain's trajectory)
        flat2 = flat.reshape(self.n_chains, -1)
        f0 = int(np.ravel_multi_index(tuple(np.asarray(self.incumbent)),
                                      self._shape))
        hasf = (flat2 == f0).any(axis=1)
        c = int(np.argmax(hasf)) if hasf.any() else 0
        tau_at, p_at = chain_accept_stats(
            ys, accepts, y0,
            np.broadcast_to(np.asarray(taus, np.float64),
                            (self.n_chains, self.steps_per_round)))
        provenance.record(provenance.DecisionRecord(
            controller="sizing", round=r, tenant="",
            action="accept" if d.accepted else "hold",
            state=tuple(self.incumbent), y=d.y, terms=terms,
            exact_split=(("penalized_latency", float(pen_lat)),
                         ("cost", float(cost_term))),
            tau=float(tau_at[c]), accept_prob=float(p_at[c]),
            rejected=rejected, rejected_y=rejected_y,
            counterfactual=(rejected_y - d.y if rejected is not None
                            else float("nan")),
            reheated=d.reheated))

    def run(self, n_rounds: int) -> list[SizingDecision]:
        return [self.round() for _ in range(n_rounds)]

    def _measure_candidates(
        self, states: Sequence[tuple[int, ...]],
        rates: Mapping[str, float],
    ) -> "list[dict[str, Any]]":
        """Ground-truth host-model measurement of K candidate sizings, in
        candidate order.  With ``eval_workers`` > 1 the measurements run on
        the evaluation runtime's bounded pool (the host model is pure
        numpy and thread-safe); otherwise a plain ordered loop — the two
        paths return identical results."""
        if self.eval_workers and self.eval_workers > 1 and len(states) > 1:
            from .evalpipe import EvalRequest, EvalResult, map_pool

            def measure(req: EvalRequest) -> EvalResult:
                res = self.spec.host_objective(req.decoded, rates)
                return EvalResult(y=float(res["y"]), extra=res)

            results = map_pool(
                measure,
                [EvalRequest(state=tuple(s), decoded=self.space.decode(s),
                             job="mix", n=self._round, kind="round")
                 for s in states],
                max_workers=self.eval_workers)
            return [dict(r.extra) for r in results]
        return [self.spec.host_objective(self.space.decode(s), rates)
                for s in states]

    def force_reheat(self) -> None:
        self._reheat_pending = True

    def best_sizing(self) -> tuple[dict[str, Any], float]:
        """Current incumbent (decoded) and its ground-truth objective at
        the mix of the last COMPLETED round — the mix the incumbent was
        actually annealed for (``_round`` already points at the next
        round, whose mix the controller has not seen yet)."""
        decoded = self.space.decode(self.incumbent)
        res = self.spec.host_objective(
            decoded, self._mix_at(max(self._round - 1, 0)))
        return decoded, float(res["y"])


# ---------------------------------------------------------------------------
# Fleet integration: microservice tenants on a shared catalog.
# ---------------------------------------------------------------------------


class MicroserviceEvaluator(Evaluator):
    """Fleet-facing evaluator: tenant "job types" are named request-mix
    regimes over one :class:`SizingSpace`.

    ``measure_decoded`` scores the tenant's decoded per-tier sizing on
    the DAG ground truth — ``exec_time_s`` is the deadline-penalized
    mix-weighted latency, ``cost_usd`` the deployment $/hr — so the
    fleet's base objective ``t + lambda c`` reproduces the sizing
    objective exactly.  The plain :meth:`measure` contract cannot work
    here (a ClusterConfig's total cores do not determine per-tier
    sizings), so it refuses loudly.
    """

    def __init__(self, spec: SizingSpace,
                 mixes: Mapping[str, Mapping[str, float]]):
        if not mixes:
            raise ValueError("at least one named request mix required")
        self.spec = spec
        self.mixes = {k: dict(v) for k, v in mixes.items()}

    def measure(self, config: ClusterConfig, job: str, n: int) -> Measurement:
        raise TypeError(
            "MicroserviceEvaluator needs the decoded per-tier sizing; "
            "route through measure_decoded (FleetController does)")

    def measure_decoded(
        self, decoded: Mapping[str, Any], job: str, n: int,
        config: ClusterConfig | None = None,
    ) -> Measurement:
        res = self.spec.host_objective(decoded, self.mixes[job])
        return Measurement(
            exec_time_s=float(res["penalized_latency"]),
            cost_usd=float(res["cost"]),
            slo_violated=bool(res["slo_attainment"] < 1.0))


def microservice_config_fn(
    spec: SizingSpace, family: str
) -> Callable[[Mapping[str, Any]], ClusterConfig]:
    """The ``FleetController(config_fn=...)`` hook for microservice
    tenants: a decoded sizing becomes a ClusterConfig whose
    ``total_cores`` is the deployment's core footprint on ``family`` —
    which is all the fleet's capacity ledger and coupling-penalty rows
    need to arbitrate containers against VM tenants."""

    def to_config(decoded: Mapping[str, Any]) -> ClusterConfig:
        return ClusterConfig(
            instance_type=family,
            n_workers=spec.total_cores(decoded),
            cores_per_worker=1)

    return to_config
