"""The annealing chain.

Heat-bath acceptance (paper sec. 2.2/3):  a proposal ``z`` from ``nu(x)`` is
accepted with probability

    exp(-max{Y(z) - Y(x), 0} / tau)

i.e. always accepted when the objective does not increase.  Two engines:

* :class:`Annealer` — the *online* driver used by the procurement
  controller: one proposal per arriving job, objective evaluated by running
  (or simulating) the job under the proposed configuration.  This is the
  paper's operating mode: evaluation *is* execution.

* :func:`anneal_chain` — a pure-JAX (lax.scan / vmap-able) chain over a
  precomputed objective table, used to reproduce the paper's illustrative
  and temperature-sweep figures at scale (many seeds x temperatures in one
  compiled call).

* :func:`anneal_chain_nd` / :func:`anneal_fleet` — the compiled chain
  generalized to full N-dimensional :class:`ConfigSpace`s (mixed
  ordinal/categorical axes, validity masks, time-indexed tables, array
  temperature schedules with reheats), batched over thousands of chains —
  seeds x temperatures x tenants — in a single jitted call.
"""

from __future__ import annotations

import copy
import dataclasses
import functools
import math
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .neighborhood import Neighborhood, flat_index, propose_nd
from .schedules import FixedTemperature, Schedule
from .state import ConfigSpace, EncodedSpace, random_valid_state
from .tabu import TabuMemory


def acceptance_probability(dy: float, tau: float) -> float:
    """Heat-bath rule: exp(-max(dy, 0)/tau)."""
    if tau <= 0:
        return 1.0 if dy <= 0 else 0.0
    return math.exp(-max(dy, 0.0) / tau)


@dataclasses.dataclass
class Step:
    """Record of one annealing transition (one job)."""

    n: int
    proposed: tuple[int, ...]
    accepted: bool
    explored: bool            # True if proposal increased Y but was accepted
    y_proposed: float
    y_current: float          # Y of the incumbent *after* the step
    tau: float
    state: tuple[int, ...]    # incumbent after the step


@dataclasses.dataclass
class ChainSnapshot:
    """Replayable checkpoint of an online :class:`Annealer` at a transition
    index: the incumbent, its stored (possibly unmeasured) objective, and
    the full bit-generator state.  Restoring one rewinds the *walk* — the
    speculative evaluation pipeline (:mod:`repro.core.evalpipe`) runs the
    chain ahead of landed measurements and rolls back to the last resolved
    transition on a misprediction, which is what keeps a pipelined run's
    realized RNG stream identical to the serial loop's."""

    n: int
    state: tuple[int, ...]
    y: float | None
    rng_state: dict[str, Any]


class Annealer:
    """Online simulated annealing over a ConfigSpace.

    ``evaluate`` maps a *decoded* configuration (and the job index) to the
    objective value Y_n — in production this runs the job.  Note the paper's
    subtlety: Y_{n-1} was measured for the *previous* job; under workload
    drift the incumbent's objective is stale, which is precisely what allows
    the chain to adapt after a change (the next evaluation of the incumbent
    refreshes it).  We follow the paper: compare Y_n(z_n) against the stored
    Y of the incumbent, refreshing the incumbent's Y whenever the incumbent
    is re-evaluated (rejected proposals do not refresh it).
    """

    def __init__(
        self,
        space: ConfigSpace,
        neighborhood: Neighborhood,
        evaluate: Callable[[dict[str, Any], int], float],
        schedule: Schedule | float = 1.0,
        seed: int | np.random.Generator = 0,
        init: tuple[int, ...] | None = None,
        tabu: TabuMemory | None = None,
    ):
        self.space = space
        self.nbhd = neighborhood
        self.evaluate = evaluate
        self.schedule = (
            FixedTemperature(schedule) if isinstance(schedule, (int, float))
            else schedule
        )
        self.rng = (
            seed if isinstance(seed, np.random.Generator)
            else np.random.default_rng(seed)
        )
        self.tabu = tabu
        if init is None:
            init = self._random_valid_state()
        if not space.contains(init):
            raise ValueError(f"initial state {init} not in the valid region")
        self.state: tuple[int, ...] = tuple(init)
        self.y: float | None = None   # incumbent objective (lazily measured)
        self.n = 0
        self.history: list[Step] = []
        # every measurement taken, incumbent refreshes included — proposals
        # alone under-report `best()` when the initial state is never beaten
        self.evaluations: list[tuple[tuple[int, ...], float]] = []

    # -- paper sec. 3: "Starting with a random configuration for x_0" --
    def _random_valid_state(self, tries: int = 10_000) -> tuple[int, ...]:
        return random_valid_state(self.space, self.rng, tries)

    def reheat(self) -> None:
        """Signal a workload/offering change: raise the temperature AND
        invalidate the incumbent's stored objective — it was measured on
        the pre-change workload, and without a refresh a now-false low Y
        can pin the chain to the stale optimum forever (the comparison
        would reject every honestly-measured proposal)."""
        self.schedule.reheat(self.n)
        self.y = None

    # -- snapshot / replay (speculative pipelining support) --
    def snapshot(self) -> ChainSnapshot:
        """Checkpoint the walk at the current transition index.  History and
        past measurements are not part of the snapshot — they record what
        really ran and survive a :meth:`restore`."""
        return ChainSnapshot(
            n=self.n, state=tuple(self.state), y=self.y,
            rng_state=copy.deepcopy(self.rng.bit_generator.state))

    def restore(self, snap: ChainSnapshot) -> None:
        """Rewind the walk (incumbent, stored objective, RNG) to ``snap``.
        ``history`` and ``evaluations`` are left intact: measurements taken
        past the snapshot were real evaluator runs and stay counted."""
        self.state = tuple(snap.state)
        self.y = snap.y
        self.n = snap.n
        self.rng.bit_generator.state = copy.deepcopy(snap.rng_state)

    def draw_transition(
        self,
        propose_hook: Callable[[tuple[int, ...]], Any] | None = None,
        state: Sequence[int] | None = None,
    ) -> tuple[tuple[int, ...], float, Any]:
        """Draw the next (proposal, acceptance uniform) pair in exactly the
        RNG order of :meth:`step`.  ``propose_hook`` runs between the
        proposal draw and the uniform draw — the slot where :meth:`step`'s
        evaluation sits, so a caller whose evaluation consumes this RNG
        (e.g. the procurement controller's blend-draw) keeps a pipelined
        run's stream identical to the serial loop's.  ``state`` overrides
        the incumbent the proposal is drawn around (the speculative
        pipeline proposes from its lookahead frontier, not the committed
        incumbent).  Returns ``(proposal, u, hook_result)``."""
        x = tuple(self.state if state is None else state)
        proposal = self.nbhd.propose(x, self.rng)
        if self.tabu is not None:
            proposal = self.tabu.filter(
                x, proposal,
                lambda: self.nbhd.propose(x, self.rng),
            )
        hooked = propose_hook(proposal) if propose_hook is not None else None
        u = float(self.rng.random())
        return proposal, u, hooked

    def record_evaluation(self, state: Sequence[int], y: float) -> None:
        """Count one real measurement.  The speculative pipeline records
        every landed measurement through here exactly once — resolved
        transitions AND mis-speculated (discarded) proposals, which were
        still real evaluator runs and still inform :meth:`best`."""
        self.evaluations.append((tuple(int(i) for i in state), float(y)))

    def apply_transition(
        self, proposal: tuple[int, ...], u: float, y_new: float,
        *, n: int, tau: float,
    ) -> Step:
        """Commit one transition given a landed measurement ``y_new`` and
        the acceptance uniform ``u`` drawn by :meth:`draw_transition`.
        Shared by the inline :meth:`step` and the speculative pipeline, so
        both resolve acceptance with identical semantics."""
        dy = y_new - self.y
        p = acceptance_probability(dy, tau)
        accepted = bool(u < p)
        explored = accepted and dy > 0

        if accepted:
            self.state, self.y = proposal, y_new
        if self.tabu is not None:
            self.tabu.visit(proposal, y_new)

        rec = Step(
            n=n, proposed=proposal, accepted=accepted, explored=explored,
            y_proposed=y_new, y_current=self.y, tau=tau, state=self.state,
        )
        self.history.append(rec)
        self.n += 1
        return rec

    def step(self, job: int | None = None) -> Step:
        """Process one arriving job: propose, evaluate, accept/reject."""
        n = self.n if job is None else job
        tau = self.schedule(n)

        if self.y is None:  # first job, or incumbent invalidated (reheat):
            # this job runs under the incumbent to refresh its objective
            self.y = float(self.evaluate(self.space.decode(self.state), n))
            self.record_evaluation(self.state, self.y)

        proposal, u, y_new = self.draw_transition(
            lambda z: float(self.evaluate(self.space.decode(z), n)))
        self.record_evaluation(proposal, y_new)
        return self.apply_transition(proposal, u, y_new, n=n, tau=tau)

    def run(self, n_jobs: int) -> list[Step]:
        return [self.step() for _ in range(n_jobs)]

    # -- diagnostics used by the paper's figures --
    @property
    def measure_count(self) -> int:
        """Real objective evaluations taken so far (incumbent refreshes
        included) — the denominator of any measurement-savings claim."""
        return len(self.evaluations)

    def best(self) -> tuple[tuple[int, ...], float]:
        """Lowest measured objective over ALL evaluations — incumbent
        initial/refresh measurements included, not just proposals."""
        state, y = min(self.evaluations, key=lambda e: e[1])
        return state, y

    def exploration_rate(self) -> float:
        if not self.history:
            return 0.0
        return sum(s.explored for s in self.history) / len(self.history)


# ---------------------------------------------------------------------------
# Pure-JAX chain over a tabulated objective (for the paper's figures).
# ---------------------------------------------------------------------------


def anneal_chain(
    key: jax.Array,
    y_table: jax.Array,       # (S,) objective per state (1-D landscape)
    n_steps: int,
    tau: jax.Array | float,   # scalar or (n_steps,) temperature(s)
    init: jax.Array | int = 0,
    noise_std: float = 0.0,   # measurement noise on Y (jobs are stochastic)
):
    """Run one annealing chain on a 1-D landscape with +-1 neighborhoods.

    Returns (states, ys, accepts): arrays of shape (n_steps,).  jit- and
    vmap-friendly: vmap over `key`/`tau`/`init` reproduces the paper's
    multi-seed, multi-temperature experiments in a single compiled call.
    Boundary states have a single neighbor; proposals out of range are
    reflected, preserving connectivity.
    """
    S = y_table.shape[0]
    taus = jnp.broadcast_to(jnp.asarray(tau, jnp.float32), (n_steps,))

    def measure(k, idx):
        y = y_table[idx]
        if noise_std > 0.0:
            y = y + noise_std * jax.random.normal(k, ())
        return y

    def body(carry, inp):
        key, x, y_x = carry
        t, = inp
        key, k1, k2, k3, k4 = jax.random.split(key, 5)
        delta = jnp.where(jax.random.bernoulli(k1), 1, -1)
        z = x + delta
        z = jnp.clip(z, 0, S - 1)
        z = jnp.where(z == x, x - delta, z)  # reflect at the boundary
        z = jnp.clip(z, 0, S - 1)            # S == 1: reflection has nowhere to go
        y_z = measure(k2, z)
        dy = y_z - y_x
        p = jnp.exp(-jnp.maximum(dy, 0.0) / t)
        accept = jax.random.uniform(k3) < p
        x_new = jnp.where(accept, z, x)
        y_new = jnp.where(accept, y_z, y_x)
        return (key, x_new, y_new), (x_new, y_z, accept)

    init = jnp.asarray(init, jnp.int32)
    key, k0 = jax.random.split(key)
    y0 = measure(k0, init)
    (_, _, _), (states, ys, accepts) = jax.lax.scan(
        body, (key, init, y0), (taus,)
    )
    return states, ys, accepts


def anneal_chain_dynamic(
    key: jax.Array,
    y_tables: jax.Array,      # (n_steps, S): landscape may change over time
    n_steps: int,
    tau: jax.Array | float,
    init: jax.Array | int = 0,
):
    """Like anneal_chain but the landscape is time-indexed (paper Fig. 5).

    The incumbent's stored objective goes stale after a change; it is only
    refreshed when the incumbent is re-measured, exactly as in the online
    algorithm (proposals are measured on the *current* landscape).
    """
    S = y_tables.shape[1]
    taus = jnp.broadcast_to(jnp.asarray(tau, jnp.float32), (n_steps,))

    def body(carry, inp):
        key, x, y_x = carry
        t, y_now = inp
        key, k1, k3 = jax.random.split(key, 3)
        delta = jnp.where(jax.random.bernoulli(k1), 1, -1)
        z = jnp.clip(x + delta, 0, S - 1)
        z = jnp.where(z == x, x - delta, z)
        z = jnp.clip(z, 0, S - 1)            # S == 1: reflection has nowhere to go
        y_z = y_now[z]
        dy = y_z - y_x
        p = jnp.exp(-jnp.maximum(dy, 0.0) / t)
        accept = jax.random.uniform(k3) < p
        x_new = jnp.where(accept, z, x)
        y_new = jnp.where(accept, y_z, y_x)
        return (key, x_new, y_new), (x_new, y_z, accept)

    init = jnp.asarray(init, jnp.int32)
    (_, _, _), (states, ys, accepts) = jax.lax.scan(
        body, (key, init, y_tables[0, init]), (taus, y_tables)
    )
    return states, ys, accepts


def first_hit_time(states: jax.Array, target: jax.Array | int) -> jax.Array:
    """Index of the first visit to `target` (n_steps if never reached)."""
    hits = states == target
    n = states.shape[0]
    return jnp.where(hits.any(), jnp.argmax(hits), n)


def chain_accept_stats(
    ys: np.ndarray,                     # (C, n_steps) proposal objectives
    accepts: np.ndarray,                # (C, n_steps) accept flags
    y0: np.ndarray | float,             # (C,) objective at the inits
    taus: np.ndarray,                   # (C, n_steps) temperatures
) -> tuple[np.ndarray, np.ndarray]:
    """Temperature and heat-bath probability at each chain's LAST
    accepted transition, recovered post hoc from one compiled round's
    outputs (numpy only — the provenance layer's read path, same
    forward-fill trick as ``ControllerMixin.explored_flags``).

    Returns ``(tau_at, p)`` of shape (C,): ``tau_at[c]`` is the
    temperature at the last accepted step (the final step's temperature
    when nothing was accepted) and ``p[c] = exp(-max(dy, 0)/tau)`` the
    acceptance probability of that transition against the incumbent the
    chain actually held before it (NaN when nothing was accepted).
    """
    ys = np.asarray(ys, np.float64)
    accepts = np.asarray(accepts, bool)
    C, n_steps = ys.shape
    taus = np.broadcast_to(np.asarray(taus, np.float64), (C, n_steps))
    kk = np.broadcast_to(np.arange(n_steps)[None, :], (C, n_steps))
    last_acc = np.maximum.accumulate(np.where(accepts, kk, -1), axis=1)
    prev_acc = np.concatenate(
        [np.full((C, 1), -1), last_acc[:, :-1]], axis=1)
    y0_col = np.broadcast_to(
        np.asarray(y0, np.float64).reshape(-1, 1), (C, 1)).copy()
    inc_before = np.where(
        prev_acc >= 0,
        np.take_along_axis(ys, np.maximum(prev_acc, 0), axis=1), y0_col)
    k_last = last_acc[:, -1]
    has = k_last >= 0
    idx = np.maximum(k_last, 0)[:, None]
    dy = (np.take_along_axis(ys, idx, axis=1)[:, 0]
          - np.take_along_axis(inc_before, idx, axis=1)[:, 0])
    tau_at = np.where(has,
                      np.take_along_axis(taus, idx, axis=1)[:, 0],
                      taus[:, -1])
    pos_tau = np.maximum(tau_at, 1e-300)
    p = np.exp(-np.maximum(dy, 0.0) / pos_tau)
    p = np.where(tau_at <= 0.0, (dy <= 0.0).astype(np.float64), p)
    return tau_at, np.where(has, p, np.nan)


def jobs_to_min_vs_tau(
    key: jax.Array,
    y_table: np.ndarray | jax.Array,
    taus: Sequence[float],
    n_seeds: int = 64,
    n_steps: int = 2000,
    init: int | None = None,
) -> dict[str, np.ndarray]:
    """Paper Fig. 4 / Fig. 10: #jobs until the global minimum is selected,
    vs temperature, with +-2 sample std bars over seeds."""
    y_table = jnp.asarray(y_table, jnp.float32)
    target = int(jnp.argmin(y_table))
    if init is None:
        init = 0

    @jax.jit
    def run(keys, tau):
        def one(k):
            states, _, _ = anneal_chain(k, y_table, n_steps, tau, init)
            return first_hit_time(states, target)
        return jax.vmap(one)(keys)

    means, stds, raw = [], [], []
    for i, tau in enumerate(taus):
        keys = jax.random.split(jax.random.fold_in(key, i), n_seeds)
        hits = np.asarray(run(keys, float(tau)))
        means.append(hits.mean())
        stds.append(hits.std(ddof=1))
        raw.append(hits)
    return {
        "taus": np.asarray(taus, np.float64),
        "mean_jobs": np.asarray(means),
        "std_jobs": np.asarray(stds),
        "raw": np.stack(raw),
    }


# ---------------------------------------------------------------------------
# N-dimensional batched engine: the compiled chain over full ConfigSpaces.
# ---------------------------------------------------------------------------


def _as_encoded(space: ConfigSpace | EncodedSpace) -> EncodedSpace:
    return space.encoded() if isinstance(space, ConfigSpace) else space


def _chain_nd_core(
    key, y_flat, valid_flat, taus, init,
    *, shape, categorical, dynamic, noise_std, extra_flat=None,
):
    """One N-dim chain.  ``y_flat`` is the flattened objective table —
    (size,) static or (n_steps, size) time-indexed; ``valid_flat`` is a
    (size,) bool mask or None; ``taus`` is (n_steps,).  Proposals into
    invalid states are rejected (zero-acceptance Metropolis move), which
    keeps the chain inside the constrained region without enumerating
    neighbors in the trace.  ``extra_flat`` is an optional (size,) additive
    cost row folded into every measurement — the fleet controller's
    coupling penalty (aggregate capacity/budget overshoot), applied inside
    the acceptance rule so arbitration pressure shapes the walk itself."""

    def measure(k, y):
        if noise_std > 0.0:
            y = y + noise_std * jax.random.normal(k, ())
        return y

    def lookup(y_now, zi):
        y = y_now[zi]
        if extra_flat is not None:
            y = y + extra_flat[zi]
        return y

    def body(carry, inp):
        key, x, y_x = carry
        if dynamic:
            t, y_now = inp
        else:
            (t,) = inp
            y_now = y_flat
        key, k_prop, k_meas, k_acc = jax.random.split(key, 4)
        z = propose_nd(k_prop, x, shape, categorical)
        zi = flat_index(z, shape)
        y_z = measure(k_meas, lookup(y_now, zi))
        dy = y_z - y_x
        p = jnp.exp(-jnp.maximum(dy, 0.0) / t)
        accept = jax.random.uniform(k_acc) < p
        if valid_flat is not None:
            accept = accept & valid_flat[zi]
        x_new = jnp.where(accept, z, x)
        y_new = jnp.where(accept, y_z, y_x)
        return (key, x_new, y_new), (x_new, y_z, accept)

    init = jnp.asarray(init, jnp.int32)
    key, k0 = jax.random.split(key)
    y0_table = y_flat[0] if dynamic else y_flat
    y0 = measure(k0, lookup(y0_table, flat_index(init, shape)))
    xs = (taus, y_flat) if dynamic else (taus,)
    (_, _, _), (states, ys, accepts) = jax.lax.scan(
        body, (key, init, y0), xs)
    return states, ys, accepts


@functools.partial(
    jax.jit,
    static_argnames=("shape", "categorical", "dynamic", "noise_std"))
def _chain_nd_jit(key, y_flat, valid_flat, taus, init,
                  *, shape, categorical, dynamic, noise_std):
    return _chain_nd_core(
        key, y_flat, valid_flat, taus, init, shape=shape,
        categorical=categorical, dynamic=dynamic, noise_std=noise_std)


@functools.partial(
    jax.jit,
    static_argnames=("shape", "categorical", "dynamic", "noise_std",
                     "per_chain"))
def _fleet_nd_jit(keys, y_flat, valid_flat, taus, inits, extra,
                  *, shape, categorical, dynamic, noise_std, per_chain):
    def one(key, tau_row, init, y, e):
        return _chain_nd_core(
            key, y, valid_flat, tau_row, init, shape=shape,
            categorical=categorical, dynamic=dynamic, noise_std=noise_std,
            extra_flat=e)

    # `extra` is None (no coupling) or (C, size) per-chain additive rows;
    # None is an empty pytree, so in_axes=None traces the no-extra variant.
    return jax.vmap(
        one,
        in_axes=(0, 0, 0, 0 if per_chain else None,
                 None if extra is None else 0),
    )(keys, taus, inits, y_flat, extra)


# ---------------------------------------------------------------------------
# Fleet-chain dispatch: bucket-padded chain axis + optional shard_map over
# tenant blocks (the 1k+-tenant scaling path of the trace-driven fleet).
# ---------------------------------------------------------------------------


def chain_bucket(n: int, multiple: int = 1) -> int:
    """Next power-of-two >= ``n``, rounded up to a ``multiple`` (device
    count).  The fleet pads its chain axis to these buckets so a churning
    tenant count (arrivals/departures every round) hits a handful of
    compiled shapes instead of retracing per fleet size — the sanitizer's
    steady-state zero-retrace invariant with churn depends on it."""
    if n < 1:
        raise ValueError("n must be >= 1")
    p = 1
    while p < n:
        p *= 2
    if multiple > 1 and p % multiple:
        p = ((p + multiple - 1) // multiple) * multiple
    return p


def chain_rows(C: int, mesh=None, bucket: bool = True) -> int:
    """Rows :func:`fleet_chains` runs for ``C`` chains: the
    :func:`chain_bucket` (rounded to the mesh's device count) or, unbucketed,
    ``C`` rounded up to that count."""
    n_dev = 1 if mesh is None else int(mesh.devices.size)
    if bucket:
        return chain_bucket(C, n_dev)
    return -(-C // n_dev) * n_dev


def _pad_chains(a: np.ndarray, p: int) -> np.ndarray:
    """Pad axis 0 from C to ``p`` by repeating row 0 (valid chain data —
    the padding chains run and are sliced away; per-chain independence of
    the vmapped kernel keeps rows 0..C-1 bit-identical)."""
    pad = p - a.shape[0]
    if pad == 0:
        return a
    return np.concatenate([a, np.repeat(a[:1], pad, axis=0)])


@functools.lru_cache(maxsize=None)
def _fleet_shard_jit(mesh, shape, categorical, noise_std, has_valid,
                     has_extra):
    """Build (and cache per mesh/shape) the shard_map'd fleet kernel:
    chains are split over the mesh's ``"tenants"`` axis, each device runs
    its block through the same vmapped :func:`_chain_nd_core`, results
    concatenate back.  Chains never communicate (coupling enters as
    precomputed ``extra`` rows), so the math is embarrassingly parallel
    and the single-device instance is bit-identical to the direct
    :func:`_fleet_nd_jit` dispatch — the parity tests pin that."""
    from jax.sharding import PartitionSpec

    row = PartitionSpec("tenants")
    rep = PartitionSpec()

    def run(kd, y_flat, taus, inits, *rest):
        i = 0
        valid_flat = None
        if has_valid:
            valid_flat, i = rest[0], 1
        extra = rest[i] if has_extra else None
        keys = jax.random.wrap_key_data(kd)

        def one(key, tau_row, init, y, e):
            return _chain_nd_core(
                key, y, valid_flat, tau_row, init, shape=shape,
                categorical=categorical, dynamic=False,
                noise_std=noise_std, extra_flat=e)

        return jax.vmap(
            one, in_axes=(0, 0, 0, 0, 0 if has_extra else None),
        )(keys, taus, inits, y_flat, extra)

    body = jax.shard_map(
        run, mesh=mesh,
        in_specs=(row, row, row, row)
        + ((rep,) if has_valid else ())
        + ((row,) if has_extra else ()),
        out_specs=(row, row, row),
        check_vma=False)
    return jax.jit(body)


def fleet_chains(
    keys: jax.Array,
    tables: np.ndarray | jax.Array,      # (C, size) float32, per-chain
    valid_flat: jax.Array | None,        # (size,) bool or None
    taus: np.ndarray,                    # (C, n_steps)
    inits: np.ndarray,                   # (C, ndim) int32
    extra: np.ndarray | None,            # (C, size) or None
    *,
    shape: tuple[int, ...],
    categorical: tuple,
    noise_std: float = 0.0,
    mesh=None,
    bucket: bool = True,
):
    """Run C per-chain-table fleet chains, bucket-padded and optionally
    sharded over tenant blocks.

    The chain axis is padded to :func:`chain_bucket` (pow-2, rounded to
    the mesh's device count) by repeating chain 0, so a fleet whose
    tenant count churns every round reuses a handful of compiled shapes.
    With ``mesh=None`` (or a falsy bucket and no mesh) this is exactly
    the direct :func:`_fleet_nd_jit` dispatch of the historical fleet hot
    path; with a mesh, chains run under ``shard_map`` over the mesh's
    ``"tenants"`` axis — bit-identical per chain (chains are independent;
    the parity tests enforce it).  Returns ``(states, ys, accepts)`` for
    all :func:`chain_rows` rows; rows C.. are padding (chain 0 again), for
    the caller to drop once the arrays are on the host.

    Every device op whose shape follows C compiles once per distinct C,
    and a churning fleet sees a new C nearly every round: so the outputs
    are not sliced here, and a caller that passes keys already padded to
    :func:`chain_rows` rows (from row-0-padded ids, as the fleet does)
    runs nothing but the bucket-shaped kernel.
    """
    C = int(np.shape(tables)[0])
    P = chain_rows(C, mesh, bucket)
    # keys are already device-resident: pad by repeating row 0 with jnp
    # (the np.asarray route would pull the key data to host — the fleet's
    # only per-round device->host transfer besides the result read-back)
    kd = jax.random.key_data(keys)
    if P > kd.shape[0]:
        kd_p = jnp.concatenate(
            [kd, jnp.repeat(kd[:1], P - kd.shape[0], axis=0)])
    else:
        kd_p = kd
    tab_p = jnp.asarray(_pad_chains(np.asarray(tables, np.float32), P))
    taus_p = jnp.asarray(_pad_chains(np.asarray(taus, np.float32), P))
    init_p = jnp.asarray(_pad_chains(np.asarray(inits, np.int32), P))
    ext_p = (None if extra is None else
             jnp.asarray(_pad_chains(np.asarray(extra, np.float32), P)))
    if mesh is not None:
        fn = _fleet_shard_jit(
            mesh, tuple(shape), tuple(categorical), float(noise_std),
            valid_flat is not None, extra is not None)
        args = (jnp.asarray(kd_p), tab_p, taus_p, init_p)
        if valid_flat is not None:
            args += (valid_flat,)
        if ext_p is not None:
            args += (ext_p,)
        st, ys, acc = fn(*args)
    else:
        st, ys, acc = _fleet_nd_jit(
            jax.random.wrap_key_data(jnp.asarray(kd_p)), tab_p,
            valid_flat, taus_p, init_p, ext_p, shape=tuple(shape),
            categorical=tuple(categorical), dynamic=False,
            noise_std=float(noise_std), per_chain=True)
    return st, ys, acc


def _default_init(enc: EncodedSpace) -> np.ndarray:
    if enc.valid_mask is None:
        return np.zeros(enc.ndim, np.int32)
    flat = enc.valid_mask.reshape(-1)
    first = int(np.argmax(flat))
    if not flat[first]:
        raise ValueError("space has no valid states")
    return np.asarray(np.unravel_index(first, enc.shape), np.int32)


def random_valid_states(
    key: jax.Array, space: ConfigSpace | EncodedSpace, n: int
) -> jax.Array:
    """(n, ndim) int32 index vectors uniform over the VALID region."""
    enc = _as_encoded(space)
    return draw_states(key, enc.shape, n, valid_indices(enc))


def valid_indices(enc: EncodedSpace) -> jax.Array | None:
    """Row-major flat indices of ``enc``'s valid states as an int32 device
    array, or None when every state is valid."""
    if enc.valid_mask is None:
        return None
    flat = np.flatnonzero(enc.valid_mask.reshape(-1))
    if flat.size == 0:
        raise ValueError("space has no valid states")
    return jnp.asarray(flat, jnp.int32)


def draw_states(key: jax.Array, shape: tuple[int, ...], n: int,
                valid_idx: jax.Array | None = None) -> jax.Array:
    """(n, ndim) int32 index vectors uniform over a space of ``shape``, or
    over ``valid_idx`` (flat indices, :func:`valid_indices`) when given.
    Traceable: a jitted caller passes ``valid_idx`` as an argument."""
    if valid_idx is None:
        maxs = jnp.asarray(shape, jnp.int32)
        return jax.random.randint(key, (n, len(shape)), 0, maxs,
                                  dtype=jnp.int32)
    picks = jax.random.choice(key, valid_idx, (n,))
    return jnp.stack(jnp.unravel_index(picks, shape), axis=-1) \
              .astype(jnp.int32)


def anneal_chain_nd(
    key: jax.Array,
    space: ConfigSpace | EncodedSpace,
    y_table: jax.Array | np.ndarray,
    n_steps: int,
    tau: jax.Array | float,          # scalar or (n_steps,) temperatures
    init: Sequence[int] | jax.Array | None = None,
    noise_std: float = 0.0,
):
    """One chain over an N-dim ConfigSpace (the compiled online algorithm).

    ``y_table`` has shape ``space.shape`` (static landscape) or
    ``(n_steps,) + space.shape`` (time-indexed — workload drift; the
    incumbent's stored objective goes stale exactly as in the online
    Annealer).  Ordinal axes move +-1 (reflected); categorical axes
    resample uniformly; invalid states are rejection-masked.  Temperatures
    are data: pass :func:`repro.core.schedules.schedule_to_array` output to
    trace reheat events.  Returns (states, ys, accepts) with states of
    shape (n_steps, ndim).
    """
    enc = _as_encoded(space)
    y = jnp.asarray(y_table, jnp.float32)
    if y.ndim == enc.ndim + 1:
        dynamic = True
        if y.shape != (n_steps,) + enc.shape:
            raise ValueError(f"dynamic table shape {y.shape} != "
                             f"{(n_steps,) + enc.shape}")
    elif y.ndim == enc.ndim:
        dynamic = False
        if y.shape != enc.shape:
            raise ValueError(f"table shape {y.shape} != {enc.shape}")
    else:
        raise ValueError(f"table rank {y.ndim} vs space rank {enc.ndim}")
    y_flat = y.reshape((n_steps, -1)) if dynamic else y.reshape(-1)
    valid_flat = (None if enc.valid_mask is None
                  else jnp.asarray(enc.valid_mask.reshape(-1)))
    taus = jnp.broadcast_to(jnp.asarray(tau, jnp.float32), (n_steps,))
    if init is None:
        init = _default_init(enc)
    init = jnp.asarray(init, jnp.int32)
    return _chain_nd_jit(
        key, y_flat, valid_flat, taus, init, shape=enc.shape,
        categorical=enc.categorical, dynamic=dynamic,
        noise_std=float(noise_std))


def anneal_fleet(
    key: jax.Array,
    space: ConfigSpace | EncodedSpace,
    y_table: jax.Array | np.ndarray,
    n_steps: int,
    taus: jax.Array | np.ndarray | Sequence[float] | float,
    inits: jax.Array | np.ndarray | None = None,
    n_chains: int | None = None,
    noise_std: float = 0.0,
    per_chain_tables: bool = False,
    extra_costs: jax.Array | np.ndarray | None = None,
    coupling_penalty: Callable[[EncodedSpace, int], np.ndarray] | None = None,
    chain_keys: jax.Array | None = None,
) -> dict[str, jax.Array]:
    """A fleet of N-dim chains in ONE jitted call (paper Figs. 4/5/10 at
    scale: seeds x temperatures x tenants).

    ``taus``: scalar (shared), (C,) per-chain constants, or (C, n_steps)
    per-chain schedules (e.g. with reheat events baked in).  ``inits``:
    None (uniform over the valid region) or (ndim,) / (C, ndim).
    ``per_chain_tables``: ``y_table`` carries a leading (C,) axis — one
    objective table per chain (multi-tenant fleets); combined with a
    time axis the per-chain tables may also be dynamic.  A static table
    may also come flat, ``(size,)`` (``(C, size)`` per chain), in
    row-major state order: on a TPU a table shaped like a space of many
    short axes is laid out in padded tiles many times its size.

    ``extra_costs``: optional per-chain additive cost rows, shape
    ``(C,) + space.shape`` or ``(C, size)`` flattened — every measurement
    of chain c at state s sees ``y_table[...] + extra_costs[c, s]``.  This
    is the multi-tenant coupling channel: the FleetController encodes the
    aggregate capacity/budget overshoot each tenant would cause (given the
    other tenants' incumbents) as a penalty surface, so shared-resource
    pressure acts *inside* the acceptance rule rather than as an
    after-the-fact clamp.  ``coupling_penalty`` is the callable form of the
    same hook: ``coupling_penalty(encoded_space, n_chains)`` must return
    such an array (mutually exclusive with ``extra_costs``).

    ``chain_keys``: the (C,) per-chain keys, already split (a caller that
    draws them inside its own program, as the sizing round does); ``key``
    then seeds only the inits drawn when ``inits`` is None.  Without it
    both come from ``key`` by two splits.  Given device arrays of the
    expected shapes and dtypes, this function binds no eager op: it only
    launches :func:`_fleet_nd_jit`.

    Returns ``{"states": (C, n_steps, ndim), "ys": (C, n_steps),
    "accepts": (C, n_steps), "inits": (C, ndim)}`` — inits included so
    callers scanning for the best visited state also see step-0 states;
    ``ys`` include the extra-cost term when one is supplied.
    """
    enc = _as_encoded(space)
    y = jnp.asarray(y_table, jnp.float32)
    base = y.ndim - (1 if per_chain_tables else 0)
    flat = enc.ndim > 1 and base == 1 and y.shape[-1] == enc.size()
    if flat:
        dynamic = False
    elif base == enc.ndim + 1:
        dynamic = True
    elif base == enc.ndim:
        dynamic = False
    else:
        raise ValueError(f"table rank {y.ndim} vs space rank {enc.ndim}")

    taus_arr = jnp.asarray(taus, jnp.float32)
    if n_chains is None:
        if taus_arr.ndim >= 1:
            n_chains = taus_arr.shape[0]
        elif inits is not None and np.ndim(inits) == 2:
            n_chains = len(inits)
        elif per_chain_tables:
            n_chains = y.shape[0]
        else:
            raise ValueError("pass n_chains (or batched taus/inits/tables)")
    if taus_arr.ndim == 0:
        taus_b = jnp.broadcast_to(taus_arr, (n_chains, n_steps))
    elif taus_arr.ndim == 1:
        taus_b = jnp.broadcast_to(taus_arr[:, None], (n_chains, n_steps))
    else:
        taus_b = jnp.broadcast_to(taus_arr, (n_chains, n_steps))

    if chain_keys is None:
        key, k_init = jax.random.split(key)
        keys = jax.random.split(key, n_chains)
    else:
        k_init, keys = key, chain_keys
    if inits is None:
        inits = random_valid_states(k_init, enc, n_chains)
    else:
        inits = jnp.asarray(inits, jnp.int32)
        if inits.ndim == 1:
            inits = jnp.broadcast_to(inits, (n_chains, enc.ndim))

    lead = (n_chains,) if per_chain_tables else ()
    time = (n_steps,) if dynamic else ()
    expect = lead + time + ((enc.size(),) if flat else enc.shape)
    if y.shape != expect:
        raise ValueError(f"table shape {y.shape} != expected {expect} "
                         f"(chains={n_chains}, steps={n_steps}, "
                         f"space={enc.shape})")
    y_flat = y.reshape(lead + time + (-1,))
    valid_flat = (None if enc.valid_mask is None
                  else jnp.asarray(enc.valid_mask.reshape(-1)))

    if coupling_penalty is not None:
        if extra_costs is not None:
            raise ValueError("pass extra_costs OR coupling_penalty, not both")
        extra_costs = coupling_penalty(enc, n_chains)
    extra = None
    if extra_costs is not None:
        extra = jnp.asarray(extra_costs, jnp.float32)
        if extra.shape == (n_chains,) + enc.shape:
            extra = extra.reshape(n_chains, -1)
        if extra.shape != (n_chains, enc.size()):
            raise ValueError(
                f"extra_costs shape {extra.shape} != "
                f"{(n_chains,) + enc.shape} (or its flattened form)")

    states, ys, accepts = _fleet_nd_jit(
        keys, y_flat, valid_flat, taus_b, inits, extra, shape=enc.shape,
        categorical=enc.categorical, dynamic=dynamic,
        noise_std=float(noise_std), per_chain=per_chain_tables)
    return {"states": states, "ys": ys, "accepts": accepts,
            "inits": inits}


def jobs_to_min_vs_tau_fleet(
    key: jax.Array,
    space: ConfigSpace | EncodedSpace,
    y_table: np.ndarray | jax.Array,
    taus: Sequence[float],
    n_seeds: int = 64,
    n_steps: int = 2000,
    init: Sequence[int] | None = None,
    target: Sequence[int] | None = None,
) -> dict[str, np.ndarray]:
    """Fig. 4 / Fig. 10 sweep through the batched engine: the whole
    (temperature x seed) grid runs as ONE jitted fleet call, on any
    N-dim ConfigSpace."""
    enc = _as_encoded(space)
    y_np = np.asarray(y_table, np.float64)
    if target is None:
        masked = (y_np if enc.valid_mask is None
                  else np.where(enc.valid_mask, y_np, np.inf))
        target = np.unravel_index(int(np.argmin(masked)), enc.shape)
    target = np.asarray(target, np.int32)

    n_taus = len(taus)
    n_chains = n_taus * n_seeds
    taus_b = np.repeat(np.asarray(taus, np.float32), n_seeds)
    inits = (None if init is None
             else np.tile(np.asarray(init, np.int32), (n_chains, 1)))
    out = anneal_fleet(key, enc, y_np, n_steps, taus_b, inits=inits,
                       n_chains=n_chains)
    states = np.asarray(out["states"])            # (C, n_steps, ndim)
    hit = (states == target).all(-1)              # (C, n_steps)
    hits = np.where(hit.any(1), hit.argmax(1), n_steps)
    hits = hits.reshape(n_taus, n_seeds)
    return {
        "taus": np.asarray(taus, np.float64),
        "mean_jobs": hits.mean(1),
        "std_jobs": hits.std(1, ddof=1),
        "raw": hits,
    }
