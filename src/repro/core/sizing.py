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
  reads the (drifting) request mix, refreshes the device objective
  table (cached per mix), anneals a compiled chain fleet from the
  incumbent and selects the top-K visited sizings on the device, reads
  back only that packet, re-measures it on the numpy ground-truth model,
  and feeds drift detection -> reheats.  Tables come from the fused
  enumeration + Erlang-C program by default; spaces beyond the 200k
  tabulation cap must inject a
  :class:`repro.core.surrogate.SurrogateSource` (probe and interpolate),
  exactly like the other controllers.  Its table is one device program:
  probes drawn from the round's key, scored by the same Erlang-C path,
  every state interpolated.

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
from .surrogate import ObjectiveSource, SurrogateSource
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
        :attr:`_table_jit` (in-trace full-grid enumeration).

        ``run(size_idx, repl_idx, rates, use_kernel)`` takes each state's
        menu indices as (K, B) int arrays, states on the last axis (the
        kernel's lanes), and returns ``y (B,)``, ``latency (C, B)``,
        ``cost (B,)`` and ``slo_attainment (B,)``."""
        import jax
        import jax.numpy as jnp

        from ..kernels import ops as kernel_ops
        from ..kernels.ref import sizing_entry_latency_ref

        dag = self.dag
        K = dag.n_tiers
        cpu = np.asarray([s.cpu for s in self.sizes], np.float64)
        mem = np.asarray([s.mem_gb for s in self.sizes], np.float64)
        # per-(tier, size) service rate: the tier's curve, capped by what
        # the container's memory serves
        mu_menu = np.stack([
            np.minimum(t.base_rate * (cpu / t.cpu_ref) ** t.gamma,
                       mem / t.mem_per_rps_gb if t.mem_per_rps_gb > 0
                       else np.inf)
            for t in dag.tiers])                                   # (K, S)
        cpu_menu = np.broadcast_to(cpu, (K, len(cpu)))
        repl_menu = np.broadcast_to(
            np.asarray(self.replica_counts, np.float64),
            (K, len(self.replica_counts)))
        visit_m = dag.visit_matrix()                               # (C, K)
        visits = jnp.asarray(visit_m, jnp.float32)
        dag_static = {
            "visits": tuple(map(tuple, visit_m.tolist())),
            "edges": tuple((dag.index(u), dag.index(v))
                           for u, v in dag.edges),
            "entries": tuple(int(e) for e in dag.entry_indices()),
            "c_max": self.c_max,
            "sat_s": float(self.sat_s),
        }
        slos = jnp.asarray([c.slo_s for c in dag.classes], jnp.float32)
        price = float(self.price_per_core_hr)
        lam_cost, slo_pen = float(self.lambda_cost), float(self.slo_penalty)

        def menu(table, idx):
            # table[k, idx[k, b]] by selects, which fuse into their
            # consumers (a gather is a pass of its own); clamps like
            # indexing
            out = jnp.broadcast_to(
                jnp.asarray(table[:, :1], jnp.float32), idx.shape)
            for j in range(1, table.shape[1]):
                out = jnp.where(idx >= j,
                                jnp.asarray(table[:, j:j + 1], jnp.float32),
                                out)
            return out

        def run(size_idx, repl_idx, rates, use_kernel: bool):
            mu = menu(mu_menu, size_idx)                           # (K, B)
            repl = menu(repl_menu, repl_idx)
            # full f32: the TPU's default matmul rounds rates to bfloat16
            lam = jnp.matmul(rates, visits,                        # (K,)
                             precision=jax.lax.Precision.HIGHEST)
            fn = kernel_ops.sizing_latency if use_kernel \
                else sizing_entry_latency_ref
            lat = fn(lam, mu, repl, **dag_static)                  # (C, B)
            cost = (repl * menu(cpu_menu, size_idx)).sum(axis=0) * price
            total = rates.sum()
            shares = jnp.where(total > 0,
                               rates / jnp.maximum(total, 1e-12), 0.0)
            viol = jnp.maximum(lat - slos[:, None], 0.0)
            y = ((shares[:, None] * (lat + slo_pen * viol)).sum(axis=0)
                 + lam_cost * cost)
            attain = jnp.where(
                total > 0,
                (shares[:, None] * (lat <= slos[:, None])).sum(axis=0),
                1.0)
            return y, lat, cost, attain

        return run

    @functools.cached_property
    def _eval_jit(self):
        """Jitted :attr:`_eval_body` over (B, 2K) candidate index rows;
        returns ``latency`` as (B, C)."""
        import jax

        body = self._eval_body

        def evaluate(cand, rates, use_kernel: bool):
            # (B, 2K) rows of (size, replicas) per tier -> (2, K, B)
            size_idx, repl_idx = cand.reshape(
                cand.shape[0], -1, 2).transpose(2, 1, 0)
            y, lat, cost, attain = body(size_idx, repl_idx, rates,
                                        use_kernel)
            return y, lat.T, cost, attain

        return jax.jit(evaluate, static_argnames=("use_kernel",))

    @functools.cached_property
    def _table_jit(self):
        """Full-grid objective table in ONE fused trace: candidate
        enumeration (``jnp.arange`` -> digits, states on the last axis)
        feeds the Erlang-C + critical-path scoring directly — no
        host-materialized (size, 2K) grid and no device->host result
        pull.  Returns the flat (size,) float32 device table for one
        rate vector, in row-major state order."""
        import jax
        import jax.numpy as jnp

        size = int(np.prod(self.space.shape))
        score = self._score_flat

        def run(rates, use_kernel: bool):
            return score(jnp.arange(size, dtype=jnp.int32), rates,
                         use_kernel)

        return jax.jit(run, static_argnames=("use_kernel",))

    @functools.cached_property
    def _score_flat(self):
        """``score(flat (B,) int32, rates, use_kernel) -> y (B,)``: the
        objective of the states with row-major flat indices ``flat``, by
        :attr:`_eval_body` (traceable, un-jitted)."""
        import jax.numpy as jnp

        body = self._eval_body
        shape = self.space.shape
        strides, acc = [], 1
        for n in reversed(shape):
            strides.append(acc)
            acc *= n
        strides = tuple(reversed(strides))          # row-major

        def score(flat, rates, use_kernel: bool):
            # one scalar divisor per digit: with an array of divisors XLA
            # folds the whole enumeration at compile time (minutes)
            digits = [(flat // strides[d]) % shape[d]
                      for d in range(len(shape))]
            # sizes and replica counts interleave per tier -> (K, B) each
            y, _, _, _ = body(jnp.stack(digits[0::2]),
                              jnp.stack(digits[1::2]), rates, use_kernel)
            return y

        return score

    @functools.cached_property
    def _probe_scores(self) -> dict[bool, Callable]:
        """``{use_kernel: score(flat, rates)}``: :attr:`_score_flat` with
        the kernel choice bound, one stable callable each (the surrogate
        table program is cached per score callable)."""
        score = self._score_flat
        return {uk: functools.partial(score, use_kernel=uk)
                for uk in (False, True)}


def sizing_table_device(
    spec: SizingSpace,
    mix: Mapping[str, float] | np.ndarray,
    use_kernel: bool | None = None,
):
    """Device-resident flat objective table for one request mix —
    candidate enumeration fused with the Erlang-C kernel in one jitted
    call (:attr:`SizingSpace._table_jit`).  The (size,) float32 result
    stays on device; :class:`SizingController`'s round reshapes it
    straight into :func:`repro.core.annealing.anneal_fleet`."""
    import jax

    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    rates = (spec.dag.rates_array(mix) if isinstance(mix, Mapping)
             else np.asarray(mix, np.float64))
    if rates.shape != (len(spec.dag.classes),):
        raise ValueError(
            f"rates shape {rates.shape} != ({len(spec.dag.classes)},)")
    return spec._table_jit(np.asarray(rates, np.float32),
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

    A stable argsort of the visited states' table estimates (ties break
    by visit position, chain-major), first-``topk``-distinct dedup, plus
    the per-chain accepted-uphill reduction of
    :meth:`repro.core.procurement.ControllerMixin.explored_flags`.  Its
    numpy oracle is
    ``tests/test_sizing.py::test_sizing_select_matches_host_topk``.
    Returns ((topk, ndim) int32 states with -1 sentinel rows, scalar
    explored flag)."""
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


def _sizing_prep_args(r: int, incumbent, taus) -> np.ndarray:
    """The round index, the incumbent and the temperatures' float32 bits
    in one int32 vector, :func:`_sizing_prep_jit`'s one upload a round:
    each host array passed to a jitted call is a transfer of its own, and
    on a TPU host one costs a good part of what a launch does."""
    incumbent = np.asarray(incumbent, np.int32)
    taus = np.asarray(taus, np.float32)
    packed = np.empty(1 + incumbent.size + taus.size, np.int32)
    packed[0] = r
    packed[1:1 + incumbent.size] = incumbent
    packed[1 + incumbent.size:] = taus.view(np.int32)
    return packed


@functools.cache
def _sizing_prep_jit(shape: tuple, n_chains: int):
    """Jitted preparation of one sizing round: everything the round
    feeds its table, anneal and select programs, drawn by the same
    threefry calls in the same order as the eager sequence it replaces —
    ``fold_in`` of the round, ``split`` into the inits' and the chains'
    keys, the inits (:func:`repro.core.annealing.draw_states`) with row 0
    at the incumbent, then :func:`repro.core.annealing.anneal_fleet`'s own
    ``split`` and ``split(n_chains)`` — so decisions are bit-identical.

    ``prep(base_key, packed, valid_idx=None)``: ``packed`` from
    :func:`_sizing_prep_args` (the round index traced: one program serves
    every round), ``valid_idx`` the valid states' flat indices as a
    device argument (None: every state is valid).  Returns ``(key_r,
    k_init, chain_keys, inits, taus_b)``: the round's key (the surrogate
    table's probes are drawn from it), ``anneal_fleet``'s init key and
    (n_chains,) chain keys, the (n_chains, ndim) inits and the
    (n_chains, steps) temperatures."""
    import jax
    import jax.numpy as jnp

    from .annealing import draw_states

    ndim = len(shape)

    @jax.jit
    def prep(base_key, packed, valid_idx=None):
        r, incumbent = packed[0], packed[1:1 + ndim]
        taus = jax.lax.bitcast_convert_type(packed[1 + ndim:], jnp.float32)
        key_r = jax.random.fold_in(base_key, r)
        k_init, k_run = jax.random.split(key_r)
        inits = draw_states(k_init, shape, n_chains, valid_idx)
        inits = inits.at[0].set(incumbent)
        k_chains, k_fleet_init = jax.random.split(k_run)
        chain_keys = jax.random.split(k_chains, n_chains)
        taus_b = jnp.broadcast_to(taus, (n_chains, taus.shape[0]))
        return key_r, k_fleet_init, chain_keys, inits, taus_b

    return prep


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

    ``objective_source=None`` tabulates via ONE fused whole-grid device
    program (:func:`sizing_table_device`, counted into ``true_measures``
    — the batched analog of ``ExhaustiveSource``) and refuses spaces
    beyond the 200k cap; inject a
    :class:`repro.core.surrogate.SurrogateSource` to probe-and-
    interpolate large DAGs (``n_probe`` states drawn from each round's
    key and scored on the device, see
    :meth:`SurrogateSource.device_table`; a source with a
    ``recycle_store`` is refused, its warm start is host-only), or an
    ``ExhaustiveSource`` to force the scalar one-state-at-a-time path
    (its host table uploaded once per mix).
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
    ):
        import jax

        from .annealing import valid_indices

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
        if (isinstance(objective_source, SurrogateSource)
                and objective_source.recycle_store is not None):
            raise ValueError(
                "a SurrogateSource with a recycle_store builds its tables "
                "on the host (SurrogateSource.table); the sizing round "
                "builds them on the device, so pass one without it")
        self.measure_topk = int(measure_topk)
        self.eval_workers = eval_workers
        self.recycle_store = recycle_store
        self._init_decision_log()
        self._enc = self.space.encoded(max_size=max(
            self.space.size(), TABULATE_CAP))
        self._shape = self._enc.shape
        self._key = jax.random.key(seed)
        self._valid_idx = valid_indices(self._enc)
        self.steps_per_round = int(steps_per_round)
        self.n_chains = int(n_chains)
        self._schedule = AdaptiveReheat(
            tau_base=tau, tau_hot=8.0 * tau if tau_hot is None else tau_hot,
            relax=0.9)
        self._detector = PageHinkley() if detector else None
        self._reheat_pending = False
        # mix key -> flat (size,) float32 device table
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
    #: one pins a full-space table forever (a float32 device table is 4 MB
    #: at 1,048,576 states, so the cache holds 32 MB there), and old mixes
    #: never recur exactly.
    TABLE_CACHE = 8

    def _dtable_for(self, rates: Mapping[str, float], round_key):
        """Device flat (size,) objective table for one mix — the fused
        enumeration+scoring jit when tables come from the batched
        evaluator; with a :class:`SurrogateSource`, its device program
        (probes drawn from ``round_key``, scored by the same Erlang-C
        path and interpolated, nothing crossing to the host); a one-way
        host->device upload of the table another ``objective_source``
        builds through ``host_objective``.  Cached for the last
        :attr:`TABLE_CACHE` distinct mixes (stalest evicted)."""
        import jax
        import jax.numpy as jnp

        key = self._mix_key(rates)
        if key in self._dtables:
            self._dtables[key] = self._dtables.pop(key)   # refresh LRU order
        else:
            src = self.objective_source
            if src is None:
                self._dtables[key] = sizing_table_device(self.spec, rates)
                self._count_measures(self.space.size())
                if metrics.get() is not None:
                    metrics.inc("sizing/programs_enqueued")
            elif isinstance(src, SurrogateSource):
                score = self.spec._probe_scores[
                    jax.default_backend() == "tpu"]
                self._dtables[key] = src.device_table(
                    self._enc, score, round_key, np.asarray(
                        self.spec.dag.rates_array(rates), np.float32))
                self._count_measures(src.n_probe)
                if metrics.get() is not None:
                    metrics.inc("sizing/programs_enqueued")
                    metrics.inc("sizing/probes", src.n_probe)
                    metrics.inc("sizing/interp_states", self.space.size())
            else:
                def fn(decoded: dict[str, Any]) -> float:
                    self._count_measures(1)
                    return float(
                        self.spec.host_objective(decoded, rates)["y"])

                table = np.asarray(src.table(
                    self.space, fn, valid_mask=self._enc.valid_mask),
                    np.float64)
                self._dtables[key] = jnp.asarray(
                    table.reshape(-1), jnp.float32)
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
        from .annealing import anneal_fleet

        r = self._round
        rates = self._mix_at(r)

        n0 = r * self.steps_per_round
        reheated = False
        if self._reheat_pending:
            self._schedule.reheat(n0)
            self._reheat_pending = False
            reheated = True
        taus = self._schedule.tau_array(n0, self.steps_per_round)

        # prep -> table (on a cache miss) -> anneal -> top-K: three or
        # four programs enqueued and no eager op; only the (topk, ndim)
        # decision packet is read back
        with span("sizing.dispatch", cat="sizing"):
            key_r, k_init, chain_keys, inits_d, taus_d = \
                _sizing_prep_jit(self._shape, self.n_chains)(
                    self._key, _sizing_prep_args(r, self.incumbent, taus),
                    self._valid_idx)
            with span("sizing.refit", cat="sizing"):
                table_d = self._dtable_for(rates, key_r)
            with span("sizing.anneal", cat="sizing"):
                out = anneal_fleet(
                    k_init, self._enc, table_d, self.steps_per_round,
                    taus_d, inits=inits_d, n_chains=self.n_chains,
                    chain_keys=chain_keys)
            sel, explored_d = _sizing_select_jit(
                self._shape, self.measure_topk)(
                inits_d, out["states"], table_d, out["ys"],
                out["accepts"])
        if metrics.get() is not None:
            # prep, anneal and select; _dtable_for counts a table's
            metrics.inc("sizing/programs_enqueued", 3)
        # .tolist()/bool() read the small decision packet — the one
        # host pull of the round, below the sanitizer's bulk-transfer
        # accounting (np.asarray / device_get), and the only place
        # the round waits for the device
        with span("sizing.sync", cat="sizing"):
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
        with span("sizing.measure", cat="sizing"):
            results = self._measure_candidates(cand_idx, rates)
        with span("sizing.commit", cat="sizing"):
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
