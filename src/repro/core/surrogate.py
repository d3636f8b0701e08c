"""Surrogate objective: anneal on spaces too large to tabulate.

The compiled engines (:func:`repro.core.annealing.anneal_chain_nd` /
:func:`anneal_fleet`) consume *tables*, and :func:`repro.core.landscape.
tabulate` hard-caps the product at 200k states — evaluating
``fn(decode(idx))`` over a million-state procurement space is exactly what
it exists to refuse.  But the paper's online algorithm never needed the
full table: it only ever measures the configurations it visits.  This
module closes the gap the way AutoTune (Chang et al.) and "Lifting the
Fog of Uncertainties" (Zhang et al.) make microservice/cloud config
spaces tractable — learn a cheap predictive model from sparse online
measurements and let the optimizer move on the model, spending the real
evaluation budget only where the model is promising or uncertain.

Pieces:

* :class:`MeasurementStore` — (state, objective, timestamp) observations
  with recency decay and latest-wins-per-state semantics, so a drifting
  landscape (paper sec. 4.3) overwrites stale measurements instead of
  averaging against them.

* :class:`SpaceEncoding` + :class:`SurrogateModel` — batched pure-JAX
  inverse-distance / RBF interpolation over the mixed ordinal-categorical
  encoding: ordinal axes become [0, 1]-scaled coordinates, categorical
  axes one-hot / sqrt(2), so ONE Euclidean squared-distance matrix
  carries both metrics (a categorical mismatch costs exactly as much as
  traversing a full ordinal axis).  The interpolation is one fused
  Pallas kernel (:func:`repro.kernels.surrogate_distance.fused_interp`,
  query states on lanes, no (Q, M) matrix in HBM) with a jnp reference;
  :meth:`SurrogateModel.predict` returns estimates AND an uncertainty
  channel (distance to the nearest measurement, scaled to objective
  units).

* :class:`ObjectiveSource` — the injectable "where do objective tables
  come from" seam for the controllers: :class:`ExhaustiveSource` wraps
  :func:`tabulate` (the historical behavior, one real evaluation per
  valid state), :class:`SurrogateSource` probes a sparse sample and
  interpolates the rest — which frees the fleet path to drive
  :class:`repro.core.costmodel.MeasuredEvaluator` workloads, where every
  avoided evaluation is real cluster time.  Where the objective is itself
  a device program (the sizing controller's Erlang-C scoring),
  :meth:`SurrogateSource.device_table` draws, scores and interpolates in
  one jitted program and the table never leaves the device.

* :class:`SurrogateAnnealer` — the measure-refit-anneal loop.  Each round
  anneals a fleet of compiled chains on the surrogate restricted to a
  moving *window* (a sub-:class:`ConfigSpace` around the incumbent, so
  no materialized array ever scales with the full product), with the
  uncertainty channel folded into acceptance through the engine's
  ``extra_costs`` channel as an exploration bonus; it then spends the
  real budget on the most promising and most uncertain visited states
  and feeds the measurements back.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Sequence

import numpy as np

from .instrumentation import note_round, race_access
from .landscape import tabulate
from .state import ConfigSpace, Dimension, EncodedSpace, random_valid_state
from ..telemetry import provenance
from ..telemetry import registry as metrics
from ..telemetry import span


# ---------------------------------------------------------------------------
# Feature embedding of the mixed ordinal-categorical index space.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SpaceEncoding:
    """Index vectors -> real features whose squared Euclidean distance is
    the mixed metric: ordinal axes contribute ((i - j) / (n - 1))^2,
    categorical axes contribute 1 on mismatch (one-hot / sqrt(2)).

    Built from space *metadata* only — no validity enumeration — so it
    works on spaces far beyond the 200k-state tabulation cap.
    """

    shape: tuple[int, ...]
    categorical: tuple[bool, ...]

    @classmethod
    def from_space(cls, space: ConfigSpace | EncodedSpace) -> "SpaceEncoding":
        if isinstance(space, ConfigSpace):
            return cls(space.shape,
                       tuple(d.kind == "categorical" for d in space.dimensions))
        return cls(space.shape, space.categorical)

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def feature_dim(self) -> int:
        return sum(n if c else 1
                   for n, c in zip(self.shape, self.categorical))

    def features(self, states: np.ndarray | Sequence[Sequence[int]]
                 ) -> np.ndarray:
        """(N, ndim) index vectors -> (N, feature_dim) fp32 features."""
        states = np.asarray(states, np.int64).reshape(-1, self.ndim)
        cols = []
        for d, (n, cat) in enumerate(zip(self.shape, self.categorical)):
            idx = states[:, d]
            if cat:
                oh = np.zeros((len(states), n), np.float32)
                oh[np.arange(len(states)), idx] = 1.0 / np.sqrt(2.0)
                cols.append(oh)
            else:
                cols.append((idx / max(n - 1, 1)).astype(np.float32)[:, None])
        return np.concatenate(cols, axis=1)


# ---------------------------------------------------------------------------
# Sparse online observations.
# ---------------------------------------------------------------------------


class MeasurementStore:
    """(encoded state, objective, timestamp) observations.

    Latest-wins per state: re-measuring a configuration replaces its entry
    (the landscape may have drifted).  ``half_life`` sets the recency
    decay used by :meth:`weights` — ``None`` means no decay (static
    landscapes).  ``capacity`` bounds memory; the stalest entries are
    evicted first (entries are kept in refresh order, so eviction is
    deterministic).
    """

    def __init__(self, ndim: int, half_life: float | None = None,
                 capacity: int = 8192):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        if half_life is not None and half_life <= 0:
            raise ValueError("half_life must be > 0 (or None)")
        self.ndim = int(ndim)
        self.half_life = half_life
        self.capacity = int(capacity)
        self._data: dict[tuple[int, ...], tuple[float, float]] = {}
        # monotone add counter: lets a device-resident twin detect
        # out-of-band adds (a shared recycle store fed by a pipeline)
        # and resync instead of silently diverging
        self._version = 0

    def __len__(self) -> int:
        return len(self._data)

    def add(self, state: Sequence[int], y: float, t: float) -> None:
        key = tuple(int(i) for i in state)
        if len(key) != self.ndim:
            raise ValueError(f"state rank {len(key)} != ndim {self.ndim}")
        # the store is unlocked by contract: all adds/reads happen on the
        # controller thread (workers hand results back through futures);
        # the race seam lets the lockset detector verify that contract
        race_access("store", self)
        # delete-then-insert keeps dict order == refresh order, which makes
        # capacity eviction (pop the front) evict the stalest entry
        self._data.pop(key, None)
        self._data[key] = (float(y), float(t))
        while len(self._data) > self.capacity:
            self._data.pop(next(iter(self._data)))
        self._version += 1

    def arrays(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(states (M, ndim) int32, ys (M,) f64, ts (M,) f64), refresh order."""
        race_access("store", self, write=False)
        if not self._data:
            z = np.zeros(0)
            return np.zeros((0, self.ndim), np.int32), z, z.copy()
        states = np.asarray(list(self._data), np.int32)
        vals = np.asarray(list(self._data.values()), np.float64)
        return states, vals[:, 0].copy(), vals[:, 1].copy()

    def weights(self, now: float) -> np.ndarray:
        """(M,) recency weights: 2^(-(now - t) / half_life), 1 if no decay."""
        _, _, ts = self.arrays()
        if self.half_life is None:
            return np.ones(len(ts))
        return np.exp2(-np.maximum(now - ts, 0.0) / self.half_life)

    def __contains__(self, state: Sequence[int]) -> bool:
        return tuple(int(i) for i in state) in self._data

    def timestamp(self, state: Sequence[int]) -> float:
        """When the state was last measured (KeyError if never)."""
        return self._data[tuple(int(i) for i in state)][1]

    def best(
        self, now: float | None = None, max_age: float | None = None
    ) -> tuple[tuple[int, ...], float]:
        """The state with the lowest (latest) measured objective.

        With ``max_age`` set, only measurements taken within the last
        ``max_age`` time units of ``now`` compete — on a drifting
        landscape an old low reading is a claim about a surface that no
        longer exists.  Falls back to the unrestricted argmin when every
        entry is stale (better a suspect answer than none)."""
        if not self._data:
            raise ValueError("empty MeasurementStore")
        items = list(self._data.items())
        if max_age is not None:
            if now is None:
                raise ValueError("max_age requires now")
            fresh = [kv for kv in items if now - kv[1][1] <= max_age]
            items = fresh or items
        key, (y, _) = min(items, key=lambda kv: kv[1][0])
        return key, y


# ---------------------------------------------------------------------------
# The interpolator.
# ---------------------------------------------------------------------------


#: Smallest padded axis length — below this, bucketing buys nothing.
_PAD_MIN = 64


def _bucket(n: int) -> int:
    """Next power of two >= n (floored at ``_PAD_MIN``): the store grows
    by a few measurements per round, and without bucketing every refit
    would present a brand-new (Q, M) shape to the jitted interpolator —
    one recompilation per controller round, forever (caught by
    ``repro.analysis.sanitize``)."""
    return max(_PAD_MIN, 1 << max(0, int(n) - 1).bit_length())


@functools.cache
def _interp_jit(kind: str):
    import jax

    from ..kernels.surrogate_distance import fused_interp

    @functools.partial(jax.jit, static_argnames=(
        "shape", "categorical", "qshape", "length_scale", "idw_power",
        "eps", "with_dmin"))
    def run(probes, y, w_rec, valid, queries, offsets, *, shape,
            categorical, qshape, length_scale, idw_power, eps,
            with_dmin=True):
        # distance + recency-weighted reduction fused in ONE Pallas pass
        # (no (Q, M) matrix in HBM); queries are explicit states, or every
        # state of ``qshape`` at ``offsets``, enumerated in the kernel.
        # The hyper-parameters are static — they are model constants, and
        # static scalars let the kernel bake them into the trace
        return fused_interp(probes, y, w_rec, shape=shape,
                            categorical=categorical, queries=queries,
                            qshape=qshape, offsets=offsets, valid=valid,
                            kind=kind, length_scale=length_scale,
                            idw_power=idw_power, eps=eps,
                            with_dmin=with_dmin)

    return run


def host_interp(
    xq: np.ndarray, xm: np.ndarray, ys: np.ndarray, rec: np.ndarray,
    *, kind: str = "idw", length_scale: float = 0.25,
    idw_power: float = 2.0, eps: float = 1e-9,
) -> tuple[np.ndarray, np.ndarray]:
    """Plain-numpy mirror of the fused device refit — ONE shared
    encoding/metric path for every host-side interpolation (the
    pipeline's :class:`repro.core.evalpipe.StorePredictor` delegates
    here), so predictor and surrogate cannot drift apart.

    xq (Q, F), xm (M, F), ys (M,), rec (M,) -> (mean (Q,), dmin (Q,))
    float64; ``dmin`` is the nearest-measurement distance before
    objective-unit scaling."""
    xq = np.asarray(xq, np.float64)
    xm = np.asarray(xm, np.float64)
    d2 = ((xq[:, None, :] - xm[None, :, :]) ** 2).sum(-1)    # (Q, M)
    if kind == "rbf":
        k = np.exp(-d2 / (2.0 * length_scale**2))
    else:                                                    # "idw"
        k = 1.0 / (d2 ** (idw_power / 2.0) + eps)
    k = k * rec[None, :]
    wsum = k.sum(axis=1)
    # recency-weighted global mean as the far-field fallback
    fallback = (ys * rec).sum() / max(float(rec.sum()), 1e-12)
    mean = np.where(wsum > 1e-12, k @ ys / np.maximum(wsum, 1e-12),
                    fallback)
    dmin = np.sqrt(d2.min(axis=1))
    return mean, dmin


# ---------------------------------------------------------------------------
# Device-resident measurement store: the numpy store's twin on device.
# ---------------------------------------------------------------------------


@functools.cache
def _dstore_insert_jit(capacity: int):
    """Jitted single-row insert with latest-wins dedup and stalest-first
    eviction; donates the store buffers (the old arrays are dead after
    the functional update — donation lets XLA update in place)."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
    def insert(states, ys, ts, seq, wmask, state, y, t, next_seq):
        cap = seq.shape[0]
        usable = jnp.arange(cap, dtype=jnp.int32) < capacity
        valid = seq >= 0
        # latest-wins dedup: overwrite the matching slot in place
        match = valid & jnp.all(states == state[None, :], axis=1)
        slot_match = jnp.argmax(match).astype(jnp.int32)
        # else the lowest free slot (valid rows stay a compact prefix)
        empty = usable & ~valid
        slot_empty = jnp.argmax(empty).astype(jnp.int32)
        # else evict the stalest entry (lowest seq = front of the numpy
        # store's refresh-ordered dict)
        imax = jnp.iinfo(jnp.int32).max
        slot_evict = jnp.argmin(
            jnp.where(valid, seq, imax)).astype(jnp.int32)
        slot = jnp.where(match.any(), slot_match,
                         jnp.where(empty.any(), slot_empty, slot_evict))
        return (states.at[slot].set(state), ys.at[slot].set(y),
                ts.at[slot].set(t),
                seq.at[slot].set(next_seq), wmask.at[slot].set(1.0))

    return insert


@functools.cache
def _dstore_decay_jit(half_life: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def decay(wmask, ts, now):
        return wmask * jnp.exp2(-jnp.maximum(now - ts, 0.0) / half_life)

    return decay


@functools.cache
def _dstore_best_jit():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def best(ys, ts, seq, now, max_age):
        valid = seq >= 0
        fresh = valid & ((now - ts) <= max_age)
        use = jnp.where(fresh.any(), fresh, valid)   # all-stale fallback
        inf = jnp.float32(jnp.inf)
        ym = jnp.where(use, ys, inf)
        m = ym.min()
        # first-minimal in refresh order == lowest seq among the minima
        imax = jnp.iinfo(jnp.int32).max
        idx = jnp.argmin(jnp.where(use & (ym == m), seq, imax))
        return idx, m

    return best


@functools.cache
def _dstore_scale_jit():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def scale(ys, seq):
        valid = seq >= 0
        inf = jnp.float32(jnp.inf)
        spread = (jnp.where(valid, ys, -inf).max()
                  - jnp.where(valid, ys, inf).min())
        cnt = jnp.maximum(valid.sum(), 1)
        mean = jnp.where(valid, ys, 0.0).sum() / cnt
        return jnp.where(spread > 0, spread,
                         jnp.maximum(1.0, jnp.abs(mean)))

    return scale


class DeviceMeasurementStore:
    """Device-resident twin of :class:`MeasurementStore`.

    Fixed-capacity, pow-2-bucketed device arrays — states (cap, ndim)
    int32, objectives / timestamps (cap,) f32, a refresh-order sequence number
    (cap,) int32 (-1 = empty) and a validity weight mask (cap,) f32 —
    updated by a jitted, buffer-donating insert with latest-wins dedup
    and stalest-first eviction, so the numpy store's ``best()`` /
    snapshot semantics hold bit-for-bit (pinned by the parity tests)
    while the refit inputs never leave the device.

    Valid rows always form a compact prefix (inserts take the lowest
    free slot; eviction reuses the evicted slot), so
    :meth:`refit_view`'s pow-2-bucket slices carry every live entry plus
    padding rows of zero weight, marked dead — the same padding contract
    as :meth:`SurrogateModel.predict`.

    A host-side key shadow (dict in refresh order, no device reads)
    mirrors membership and count; ``load`` bulk-rebuilds from a numpy
    store (host->device only) when a twin detects out-of-band adds.
    """

    def __init__(self, encoding: SpaceEncoding,
                 half_life: float | None = None, capacity: int = 8192):
        import jax.numpy as jnp

        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        if half_life is not None and half_life <= 0:
            raise ValueError("half_life must be > 0 (or None)")
        self.encoding = encoding
        self.ndim = encoding.ndim
        self.half_life = half_life
        self.capacity = int(capacity)
        self.cap = _bucket(self.capacity)
        self._states = jnp.zeros((self.cap, self.ndim), jnp.int32)
        self._ys = jnp.zeros((self.cap,), jnp.float32)
        self._ts = jnp.zeros((self.cap,), jnp.float32)
        self._seq = jnp.full((self.cap,), -1, jnp.int32)
        self._wmask = jnp.zeros((self.cap,), jnp.float32)
        self._next_seq = 0
        self._keys: dict[tuple[int, ...], None] = {}

    def __len__(self) -> int:
        return len(self._keys)

    def __contains__(self, state: Sequence[int]) -> bool:
        return tuple(int(i) for i in state) in self._keys

    def add(self, state: Sequence[int], y: float, t: float) -> None:
        import jax.numpy as jnp

        key = tuple(int(i) for i in state)
        if len(key) != self.ndim:
            raise ValueError(f"state rank {len(key)} != ndim {self.ndim}")
        (self._states, self._ys, self._ts, self._seq,
         self._wmask) = _dstore_insert_jit(self.capacity)(
            self._states, self._ys, self._ts, self._seq, self._wmask,
            jnp.asarray(key, jnp.int32), jnp.float32(y), jnp.float32(t),
            jnp.int32(self._next_seq))
        self._next_seq += 1
        # host key shadow: delete-then-insert + pop-front, the numpy
        # store's exact refresh-order semantics
        self._keys.pop(key, None)
        self._keys[key] = None
        while len(self._keys) > self.capacity:
            self._keys.pop(next(iter(self._keys)))

    def load(self, store: MeasurementStore) -> None:
        """Bulk-rebuild from a numpy store (host->device only): refresh
        order becomes seq order, so twin semantics pick up exactly where
        the numpy store stands."""
        import jax.numpy as jnp

        obs, ys, ts = store.arrays()
        n = len(obs)
        self._states = jnp.zeros((self.cap, self.ndim), jnp.int32)
        self._ys = jnp.zeros((self.cap,), jnp.float32)
        self._ts = jnp.zeros((self.cap,), jnp.float32)
        self._seq = jnp.full((self.cap,), -1, jnp.int32)
        self._wmask = jnp.zeros((self.cap,), jnp.float32)
        if n:
            self._states = self._states.at[:n].set(
                jnp.asarray(obs, jnp.int32))
            self._ys = self._ys.at[:n].set(jnp.asarray(ys, jnp.float32))
            self._ts = self._ts.at[:n].set(jnp.asarray(ts, jnp.float32))
            self._seq = self._seq.at[:n].set(
                jnp.arange(n, dtype=jnp.int32))
            self._wmask = self._wmask.at[:n].set(1.0)
        self._next_seq = n
        self._keys = {tuple(int(i) for i in s): None for s in obs}

    def weights_device(self, now: float):
        """(cap,) device recency weights — zero on empty/padding rows,
        ``2^(-(now - t)/half_life)`` (1 with no decay) on live rows."""
        import jax.numpy as jnp

        if self.half_life is None:
            return self._wmask
        return _dstore_decay_jit(float(self.half_life))(
            self._wmask, self._ts, jnp.float32(now))

    def refit_view(self, now: float, m_bucket: int | None = None):
        """Device (states, ys, recency, live) slices for the fused refit:
        ``m_bucket`` rows (default: the pow-2 bucket of the live count)
        — every live entry plus padding rows whose zero weight
        contributes exactly nothing and whose zero ``live`` keeps them
        from ever being the nearest measurement."""
        if m_bucket is None:
            m_bucket = _bucket(len(self._keys))
        m_bucket = min(m_bucket, self.cap)
        rec = self.weights_device(now)
        return (self._states[:m_bucket], self._ys[:m_bucket],
                rec[:m_bucket], self._wmask[:m_bucket])

    def y_scale_device(self):
        """Device objective scale: spread of live objectives, or
        ``max(1, |mean|)`` when flat — the numpy predict's formula."""
        return _dstore_scale_jit()(self._ys, self._seq)

    def best_device(self, now: float, max_age: float | None = None):
        """Device (slot index, objective) of the best credible entry —
        the numpy store's ``best`` semantics (fresh-filter with
        all-stale fallback, first-minimal-in-refresh-order tie-break)."""
        import jax.numpy as jnp

        age = jnp.float32(jnp.inf if max_age is None else max_age)
        return _dstore_best_jit()(self._ys, self._ts, self._seq,
                                  jnp.float32(now), age)

    def best(self, now: float | None = None,
             max_age: float | None = None) -> tuple[tuple[int, ...], float]:
        """Host-facing ``best`` (pulls one row — parity tests/debug)."""
        if not self._keys:
            raise ValueError("empty DeviceMeasurementStore")
        if max_age is not None and now is None:
            raise ValueError("max_age requires now")
        idx, y = self.best_device(0.0 if now is None else now, max_age)
        i = int(idx)
        return (tuple(int(v) for v in self._states[i].tolist()),
                float(y))

    def snapshot(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(states, ys, ts) numpy in refresh order — the numpy store's
        ``arrays()`` contract.  Host pull; tests/debug only."""
        import jax.numpy as jnp

        n = len(self._keys)
        if n == 0:
            z = np.zeros(0)
            return np.zeros((0, self.ndim), np.int32), z, z.copy()
        imax = jnp.iinfo(jnp.int32).max
        order = jnp.argsort(jnp.where(self._seq >= 0, self._seq, imax),
                            stable=True)[:n]
        return (np.asarray(self._states[order]),
                np.asarray(self._ys[order], np.float64),
                np.asarray(self._ts[order], np.float64))


@functools.cache
def _select_jit(shape: tuple, acquisition: str, m: int, n_exp: int):
    """Jitted on-device measurement selection: dedup the visited states,
    score them under the acquisition, and pick the ``m`` winners —
    ``m - n_exp`` by acquisition rank, the rest by uncertainty — in
    stable-argsort order over the distinct visited states in ascending
    flat order (first-occurrence masking over a stable sort, so ties
    break as ``np.unique`` then ``np.argsort(kind="stable")`` break
    them; the numpy oracle is ``tests/test_surrogate.py::
    test_window_select_matches_numpy_ranking``).  Returns (m, ndim)
    int32 window-local states with -1 sentinel rows when fewer than
    ``m`` distinct states were visited."""
    import jax
    import jax.numpy as jnp

    strides, acc = [], 1
    for n in reversed(shape):
        strides.append(acc)
        acc *= n
    strides = tuple(reversed(strides))          # row-major, host constants
    inv_sqrt2 = 1.0 / math.sqrt(2.0)            # trace-time constants
    inv_sqrt2pi = 1.0 / math.sqrt(2.0 * math.pi)

    @jax.jit
    def select(inits, states, mean_w, unc_w, kappa, y_best):
        nd = inits.shape[1]
        visited = jnp.concatenate(
            [inits[:, None, :], states], axis=1).reshape(-1, nd)
        vflat = jnp.zeros(visited.shape[0], jnp.int32)
        for d in range(nd):
            vflat = vflat + visited[:, d].astype(jnp.int32) * strides[d]
        order0 = jnp.argsort(vflat, stable=True)
        s = vflat[order0]
        first = jnp.concatenate(
            [jnp.ones(1, bool), s[1:] != s[:-1]])   # unique, ascending
        meanv = mean_w[s]
        uncv = unc_w[s]
        if acquisition == "ei":
            sd = jnp.maximum(uncv, 1e-12)
            z = (y_best - meanv) / sd
            cdf = 0.5 * (1.0 + jax.lax.erf(z * inv_sqrt2))
            pdf = jnp.exp(-0.5 * z * z) * inv_sqrt2pi
            acq = -(sd * (z * cdf + pdf))       # lower score = earlier
        else:
            acq = meanv - kappa * uncv
        inf = jnp.float32(jnp.inf)
        acq_m = jnp.where(first, acq, inf)      # duplicates sort last
        unc_m = jnp.where(first, -uncv, inf)
        ord_acq = jnp.argsort(acq_m, stable=True)
        ord_unc = jnp.argsort(unc_m, stable=True)
        cand = jnp.concatenate([ord_acq[:m - n_exp], ord_unc])

        def body(j, carry):
            chosen, cnt = carry
            pos = cand[j]
            f = s[pos]
            ok = first[pos] & (cnt < m) & jnp.all(chosen != f)
            upd = chosen.at[jnp.minimum(cnt, m - 1)].set(f)
            return (jnp.where(ok, upd, chosen),
                    cnt + ok.astype(jnp.int32))

        chosen, _ = jax.lax.fori_loop(
            0, cand.shape[0], body,
            (jnp.full((m,), -1, jnp.int32), jnp.int32(0)))
        cols, rem = [], chosen
        for d in range(nd):
            cols.append(rem // strides[d])
            rem = rem % strides[d]
        sel = jnp.stack(cols, axis=1)
        return jnp.where(chosen[:, None] >= 0, sel, -1)

    return select


@dataclasses.dataclass
class SurrogateModel:
    """Batched interpolator with an uncertainty channel.

    ``kind="idw"`` (default) is Shepard inverse-distance weighting —
    parameter-free across spaces and exact at measured states; ``"rbf"``
    is a Gaussian kernel of width ``length_scale`` (normalized feature
    units, where a full ordinal axis spans 1.0).  Predictions are
    recency-weighted by the store, so stale measurements of a drifted
    landscape fade rather than anchor the estimate.

    The uncertainty channel is the distance to the nearest measurement,
    scaled by the observed objective spread: zero exactly at measured
    states, growing toward unexplored regions, in objective units so it
    can ride the compiled chain's additive ``extra_costs`` channel.
    """

    encoding: SpaceEncoding
    kind: str = "idw"
    length_scale: float = 0.25
    idw_power: float = 2.0
    eps: float = 1e-9
    chunk: int = 8192

    def __post_init__(self) -> None:
        if self.kind not in ("idw", "rbf"):
            raise ValueError(f"unknown surrogate kind {self.kind!r}")

    def _refit_args(self, store: MeasurementStore, now: float | None):
        """The store's device refit inputs and its objective scale.

        The measurement axis is padded to a power-of-two bucket so the
        online store's growth doesn't retrace the jitted interpolator
        every round: padded rows carry zero recency weight (exactly zero
        kernel contribution) and are marked dead (never the nearest)."""
        if len(store) == 0:
            raise ValueError("cannot predict from an empty MeasurementStore")
        import jax.numpy as jnp

        obs, ys, ts = store.arrays()
        rec = store.weights(float(ts.max()) if now is None else float(now))
        spread = float(ys.max() - ys.min())
        y_scale = spread if spread > 0 else max(1.0, abs(float(ys.mean())))
        pad = _bucket(len(obs)) - len(obs)
        live = np.concatenate([np.ones(len(obs)), np.zeros(pad)])
        obs = np.concatenate([obs, np.zeros((pad, obs.shape[1]), np.int32)])
        ys = np.concatenate([ys, np.zeros(pad)])
        rec = np.concatenate([rec, np.zeros(pad)])
        return ((jnp.asarray(obs, jnp.int32), jnp.asarray(ys, jnp.float32),
                 jnp.asarray(rec, jnp.float32),
                 jnp.asarray(live, jnp.float32)), y_scale)

    def _static(self) -> dict[str, Any]:
        enc = self.encoding
        return {"shape": enc.shape, "categorical": enc.categorical,
                "length_scale": self.length_scale,
                "idw_power": self.idw_power, "eps": self.eps}

    def predict(
        self,
        states: np.ndarray | Sequence[Sequence[int]],
        store: MeasurementStore,
        now: float | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """(Q, ndim) query index vectors -> (estimates (Q,), uncertainty
        (Q,)), both float64.  Requires at least one measurement."""
        import jax.numpy as jnp

        args, y_scale = self._refit_args(store, now)
        run = _interp_jit(self.kind)
        states = np.asarray(states, np.int64).reshape(-1, self.encoding.ndim)
        means, dmins = [], []
        for lo in range(0, len(states), self.chunk):
            q = states[lo:lo + self.chunk]
            n_q = len(q)
            # queries bucket too: a fresh Q shape is just as much a
            # retrace as a fresh M
            q_cap = min(_bucket(n_q), self.chunk)
            q = np.concatenate([q, np.zeros((q_cap - n_q, q.shape[1]),
                                            np.int64)])
            m, d = run(*args, jnp.asarray(q, jnp.int32), None, qshape=None,
                       **self._static())
            means.append(np.asarray(m, np.float64)[:n_q])
            dmins.append(np.asarray(d, np.float64)[:n_q])
        mean = np.concatenate(means)
        unc = y_scale * np.concatenate(dmins)
        return mean, unc

    def predict_all(self, store: MeasurementStore,
                    now: float | None = None) -> np.ndarray:
        """Estimates (float64) of every state of the encoding's space, in
        row-major order: the kernel enumerates the states itself, so no
        grid of them is built anywhere."""
        args, _ = self._refit_args(store, now)
        mean = _interp_jit(self.kind)(*args, None, None, qshape=None,
                                      with_dmin=False, **self._static())
        return np.asarray(mean, np.float64)


# ---------------------------------------------------------------------------
# ObjectiveSource: the injectable table provider for the controllers.
# ---------------------------------------------------------------------------


class ObjectiveSource:
    """Where controller objective tables come from.

    ``table(space, fn, valid_mask)`` returns an array of shape
    ``space.shape``; implementations track ``true_measures`` (calls of the
    real ``fn``) and ``surrogate_queries`` (model evaluations) for
    standalone use.  The controllers count evaluator runs themselves
    (their ``fn`` closures may take several measurements per call), so
    their decision logs read ``surrogate_queries`` from here but keep
    their own ``true_measures``.
    """

    def __init__(self) -> None:
        self.true_measures = 0
        self.surrogate_queries = 0

    def counts(self) -> dict[str, int]:
        return {"true_measures": self.true_measures,
                "surrogate_queries": self.surrogate_queries}

    def table(
        self,
        space: ConfigSpace,
        fn: Callable[[dict[str, Any]], float],
        valid_mask: np.ndarray | None = None,
    ) -> np.ndarray:
        raise NotImplementedError


#: ``fold_in`` data that derives a round's probe key from its round key,
#: so the probe draw is independent of the chains' (start, run) split
PROBE_STREAM = 0x70726F62


def draw_probes(key, size: int, n: int):
    """``n`` distinct flat states of ``[0, size)`` from ``key``, on the
    device: ``2 n`` uniform draws, of which the first ``n`` distinct in
    draw order are kept.  Returns (flat (n,) int32, weight (n,) float32):
    weight 1 on a drawn state, 0 on a slot left over when the draws hold
    fewer than ``n`` distinct states (there the slot repeats state 0 and
    contributes nothing).  A plain reference can repeat the draw with
    ``jax.random.randint`` and a first-occurrence dedup."""
    import jax
    import jax.numpy as jnp

    cand = jax.random.randint(key, (2 * n,), 0, size, dtype=jnp.int32)
    order = jnp.argsort(cand, stable=True)
    s = cand[order]
    first_sorted = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    first = jnp.zeros((2 * n,), bool).at[order].set(first_sorted)
    rank = jnp.cumsum(first) - 1                   # among distinct draws
    slot = jnp.where(first & (rank < n), rank, n)
    flat = jnp.zeros((n,), jnp.int32).at[slot].set(cand, mode="drop")
    weight = jnp.zeros((n,), jnp.float32).at[slot].set(1.0, mode="drop")
    return flat, weight


@functools.cache
def _surrogate_table_jit(shape: tuple, categorical: tuple, n_probe: int,
                         kind: str, length_scale: float, idw_power: float,
                         eps: float, score: Callable):
    """The jitted device table program of :meth:`SurrogateSource.
    device_table`, one per (space, source settings, score)."""
    import jax
    import jax.numpy as jnp

    from ..kernels.surrogate_distance import fused_interp

    size = math.prod(shape)
    strides, acc = [], 1
    for n in reversed(shape):
        strides.append(acc)
        acc *= n
    strides = tuple(reversed(strides))              # row-major

    def surrogate_table(key, *args):
        flat, weight = draw_probes(jax.random.fold_in(key, PROBE_STREAM),
                                   size, n_probe)
        y = score(flat, *args)
        probes = jnp.stack([(flat // st) % n
                            for st, n in zip(strides, shape)], axis=1)
        return fused_interp(probes, y, weight, shape=shape,
                            categorical=categorical, kind=kind,
                            length_scale=length_scale,
                            idw_power=idw_power, eps=eps, with_dmin=False)

    return jax.jit(surrogate_table)


class ExhaustiveSource(ObjectiveSource):
    """The historical behavior: one real evaluation per valid state."""

    def __init__(self, max_size: int = 200_000):
        super().__init__()
        self.max_size = int(max_size)

    def table(self, space, fn, valid_mask=None):
        Y = tabulate(space, fn, max_size=self.max_size,
                     valid_mask=valid_mask)
        if valid_mask is not None:
            self.true_measures += int(np.asarray(valid_mask).sum())
        elif space.is_valid is None:
            self.true_measures += space.size()
        else:
            self.true_measures += int(np.isfinite(Y).sum())
        return Y


class SurrogateSource(ObjectiveSource):
    """Probe ``n_probe`` valid states, interpolate the rest.

    The table is still materialized over the full product (the compiled
    fleet needs a (T, size) array), but the *real* evaluation count drops
    from one-per-valid-state to ``n_probe`` — the difference between a
    simulator sweep and a day of cluster time under a
    :class:`repro.core.costmodel.MeasuredEvaluator`.

    :meth:`table` probes through a host ``fn`` and returns a host array.
    :meth:`device_table` is the device path for an objective that is
    itself a device program: probes drawn from a JAX key, scored and
    interpolated in ONE jitted program whose table never leaves the
    device.

    With ``recycle_store`` set (typically the same store a
    :class:`repro.core.evalpipe.SpeculativePipeline` recycles
    mis-speculated measurements into), every in-bounds entry warm-starts
    the table build at its original timestamp: those states are neither
    re-probed nor re-counted — each real measurement is paid for exactly
    once, where it was taken.
    """

    def __init__(
        self,
        n_probe: int = 256,
        model: SurrogateModel | None = None,
        half_life: float | None = None,
        max_size: int = 2_000_000,
        seed: int = 0,
        recycle_store: MeasurementStore | None = None,
    ):
        super().__init__()
        if n_probe < 1:
            raise ValueError("n_probe must be >= 1")
        self.n_probe = int(n_probe)
        self.model = model
        self.half_life = half_life
        self.max_size = int(max_size)
        self.recycle_store = recycle_store
        self.recycled_used = 0
        self._rng = np.random.default_rng(seed)

    def device_table(self, space: ConfigSpace | EncodedSpace,
                     score: Callable, key, *args):
        """(size,) float32 device table of one round, built by ONE jitted
        program (``jit_surrogate_table``): ``n_probe`` distinct states
        drawn from ``key`` (:func:`draw_probes`), scored on the device by
        ``score(flat (n,) int32, *args) -> (n,)``, and the model's
        interpolation of every state from them, in row-major order.
        Nothing is read back and nothing is uploaded but ``args``.

        ``score`` must be a stable callable (the program is cached per
        source settings and ``score``); every state of the space must be
        valid, and the recycle store, whose measurements live on the
        host, warm-starts :meth:`table` only."""
        if isinstance(space, ConfigSpace):
            space = space.encoded(max_size=max(space.size(), 1))
        if space.valid_mask is not None:
            raise ValueError("device_table needs a space whose every state "
                             "is valid")
        if self.recycle_store is not None:
            raise ValueError("recycled host measurements warm-start the "
                             "host table path only: build the table with "
                             "SurrogateSource.table")
        size = math.prod(space.shape)
        if size > self.max_size:
            raise ValueError(f"space too large to materialize: {size}")
        model = self.model or SurrogateModel(SpaceEncoding.from_space(space))
        run = _surrogate_table_jit(
            space.shape, space.categorical, self.n_probe, model.kind,
            model.length_scale, model.idw_power, model.eps, score)
        self.true_measures += self.n_probe
        self.surrogate_queries += size
        return run(key, *args)

    def _probe_states(self, space: ConfigSpace,
                      valid_mask: np.ndarray | None) -> np.ndarray:
        if valid_mask is not None:
            flat = np.flatnonzero(np.asarray(valid_mask).reshape(-1))
            if flat.size == 0:
                raise ValueError("space has no valid states")
            picks = self._rng.choice(
                flat, size=min(self.n_probe, flat.size), replace=False)
            return np.stack(
                np.unravel_index(np.sort(picks), space.shape), axis=-1)
        # dict keys preserve insertion order; repeated draws may collide,
        # so very constrained spaces can yield fewer than n_probe probes
        out: dict[tuple[int, ...], None] = {}
        for _ in range(20 * self.n_probe):
            out.setdefault(random_valid_state(space, self._rng), None)
            if len(out) == self.n_probe:
                break
        return np.asarray(list(out), np.int64)

    def _recycled_entries(
        self, space: ConfigSpace, valid_mask: np.ndarray | None
    ) -> list[tuple[tuple[int, ...], float, float]]:
        """In-bounds, valid entries of the shared recycle store — real
        measurements already paid for elsewhere (a pipeline's
        mis-speculations), free to warm-start this table build."""
        if self.recycle_store is None or len(self.recycle_store) == 0:
            return []
        obs, ys, ts = self.recycle_store.arrays()
        if obs.shape[1] != len(space.shape):
            return []
        mask = (np.asarray(valid_mask, bool)
                if valid_mask is not None else None)
        out = []
        for s, y, t in zip(obs, ys, ts):
            key = tuple(int(i) for i in s)
            if any(i < 0 or i >= n for i, n in zip(key, space.shape)):
                continue
            if mask is not None:
                if not mask[key]:
                    continue
            elif not space.contains(key):
                continue
            out.append((key, float(y), float(t)))
        return out

    def table(self, space, fn, valid_mask=None):
        if space.size() > self.max_size:
            raise ValueError(
                f"space too large to materialize: {space.size()}")
        recycled = self._recycled_entries(space, valid_mask)
        probes = self._probe_states(space, valid_mask)
        store = MeasurementStore(
            len(space.shape), half_life=self.half_life,
            capacity=max(len(probes) + len(recycled), 1))
        for key, y, t in recycled:
            store.add(key, y, t)             # counted where it was taken
        self.recycled_used += len(recycled)
        for s in probes:
            if s in store:
                continue                     # recycled measurement wins
            store.add(s, float(fn(space.decode([int(i) for i in s]))), 0.0)
            self.true_measures += 1
        model = self.model or SurrogateModel(SpaceEncoding.from_space(space))
        Y = model.predict_all(store).reshape(space.shape)
        self.surrogate_queries += space.size()
        if valid_mask is not None:
            Y = np.where(np.asarray(valid_mask), Y, np.inf)
        return Y


# ---------------------------------------------------------------------------
# Windowed sub-spaces: nothing materialized scales with the full product.
# ---------------------------------------------------------------------------


def window_space(
    space: ConfigSpace,
    center: Sequence[int],
    half_width: int = 6,
) -> tuple[ConfigSpace, np.ndarray]:
    """A sub-ConfigSpace around ``center``: ordinal axes keep a contiguous
    ``2 * half_width + 1`` slice (clipped at the boundary without
    shrinking, so window shapes — and jit traces — are stable as the
    window moves), categorical axes keep every value.  The validity
    predicate carries over unchanged (it sees decoded values, which are
    the same values).  Returns (sub_space, per-axis index offsets)."""
    if half_width < 1:
        raise ValueError("half_width must be >= 1")
    dims, offs = [], []
    for dim, c in zip(space.dimensions, center):
        n = len(dim)
        w = 2 * half_width + 1
        if dim.kind == "categorical" or n <= w:
            lo = 0
            vals = dim.values
        else:
            lo = int(np.clip(int(c) - half_width, 0, n - w))
            vals = dim.values[lo:lo + w]
        offs.append(lo)
        dims.append(Dimension(dim.name, tuple(vals), dim.kind))
    return (ConfigSpace(tuple(dims), space.is_valid),
            np.asarray(offs, np.int64))


# ---------------------------------------------------------------------------
# Acquisition scores: how the real-measurement budget is ranked.
# ---------------------------------------------------------------------------


def expected_improvement(
    mean: np.ndarray, unc: np.ndarray, y_best: float
) -> np.ndarray:
    """EI under a Gaussian belief (minimization): ``s (z Phi(z) + phi(z))``
    with ``z = (y_best - mean) / s`` and ``s`` the uncertainty channel
    read as a standard deviation.  Exactly-measured states (``s = 0``)
    get their deterministic improvement ``max(y_best - mean, 0)`` — no
    exploration credit for what is already known."""
    mean = np.asarray(mean, np.float64)
    s = np.maximum(np.asarray(unc, np.float64), 1e-12)
    z = (y_best - mean) / s
    cdf = 0.5 * (1.0 + np.asarray([math.erf(v / math.sqrt(2.0))
                                   for v in np.ravel(z)]).reshape(z.shape))
    pdf = np.exp(-0.5 * z * z) / math.sqrt(2.0 * math.pi)
    return s * (z * cdf + pdf)


# ---------------------------------------------------------------------------
# The measure-refit-anneal loop.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SurrogateRound:
    """Audit record of one measure-refit-anneal round."""

    n: int
    incumbent: tuple[int, ...]
    best_y: float                # best (latest) measured objective so far
    window_size: int             # states interpolated this round
    true_measures: int           # cumulative real evaluations
    surrogate_queries: int       # cumulative model evaluations
    measured: tuple[tuple[tuple[int, ...], float], ...]  # this round's


class SurrogateAnnealer:
    """Online annealing on spaces too large to tabulate.

    Each :meth:`round`:

    1. slice a window sub-space around the incumbent
       (:func:`window_space`) and interpolate the surrogate objective and
       its uncertainty over every window state;
    2. run ``n_chains`` compiled chains for ``steps_per_round``
       transitions on the surrogate table in ONE jitted
       :func:`repro.core.annealing.anneal_fleet` call, with
       ``-kappa * uncertainty`` threaded through ``extra_costs`` so the
       acceptance rule itself prefers unexplored states (optimism in the
       face of uncertainty);
    3. spend ``measures_per_round`` real evaluations on the visited
       states ranked by the chosen ``acquisition`` — ``"lcb"`` (default:
       surrogate lower confidence bound, ``mean - kappa *
       uncertainty``) or ``"ei"`` (expected improvement over the best
       measurement, :func:`expected_improvement`) — reserving an
       ``explore_frac`` share for the most *uncertain* visited states;
    4. feed the measurements back and move the incumbent to the best
       measured state.

    The first round starts with a *global* bootstrap design:
    ``n_bootstrap`` uniform valid states measured across the full space,
    so the incumbent jumps straight to the best sampled basin instead of
    walking there one window at a time (the standard initial design of
    sparse-measurement tuners).

    Everything that is materialized — window table, uncertainty row,
    chain traces — scales with the window, never the full product, so a
    million-state :class:`ConfigSpace` costs the same per round as a
    thousand-state one.  Deterministic under a fixed ``seed``.
    """

    def __init__(
        self,
        space: ConfigSpace,
        evaluate: Callable[[dict[str, Any]], float],
        model: SurrogateModel | None = None,
        store: MeasurementStore | None = None,
        half_width: int = 6,
        n_chains: int = 16,
        steps_per_round: int = 64,
        tau: float = 1.0,
        kappa: float = 1.0,
        measures_per_round: int = 8,
        explore_frac: float = 0.25,
        n_bootstrap: int | None = None,
        init: Sequence[int] | None = None,
        seed: int = 0,
        acquisition: str = "lcb",
        eval_workers: int | None = None,
    ):
        import jax

        if measures_per_round < 1:
            raise ValueError("measures_per_round must be >= 1")
        if acquisition not in ("lcb", "ei"):
            raise ValueError(f"unknown acquisition {acquisition!r} "
                             f"(expected 'lcb' or 'ei')")
        self.acquisition = acquisition
        self.space = space
        self.evaluate = evaluate
        self.model = (SurrogateModel(SpaceEncoding.from_space(space))
                      if model is None else model)
        # `store or default` would discard a caller's EMPTY store (len 0
        # is falsy) — and with it the half_life drift configuration
        self.store = (MeasurementStore(len(space.dimensions))
                      if store is None else store)
        self.half_width = int(half_width)
        self.n_chains = int(n_chains)
        self.steps_per_round = int(steps_per_round)
        self.tau = float(tau)
        self.kappa = float(kappa)
        self.measures_per_round = int(measures_per_round)
        self.explore_frac = float(explore_frac)
        self.n_bootstrap = (max(self.measures_per_round, 8)
                            if n_bootstrap is None else int(n_bootstrap))
        if self.n_bootstrap < 1:
            raise ValueError("n_bootstrap must be >= 1")
        # > 1: the round's real measurements (bootstrap design and ranked
        # acquisition picks) run on the evaluation runtime's bounded
        # worker pool (repro.core.evalpipe) — for wall-clock `evaluate`
        # callables, which must then be thread-safe.  The store is fed in
        # rank order either way, so the outcome matches the serial loop.
        self.eval_workers = eval_workers
        self._rng = np.random.default_rng(seed)
        self._key = jax.random.key(seed)
        self.true_measures = 0
        self.surrogate_queries = 0
        self.stale_refreshes = 0     # drift mode: stale incumbents re-measured
        self.rounds: list[SurrogateRound] = []
        self._n = 0
        self._enc_cache: dict[tuple[int, ...], Any] = {}
        # refit + anneal + selection stay on device; the numpy store keeps
        # authority over best()/bootstrap (a host dict, no transfers)
        self._dstore: DeviceMeasurementStore | None = None
        self._dstore_version = -1
        self._offs_cache: dict[tuple[int, ...], Any] = {}
        if init is None:
            init = self._random_valid_state()
        if not space.contains(init):
            raise ValueError(f"initial state {tuple(init)} not valid")
        self.incumbent: tuple[int, ...] = tuple(int(i) for i in init)

    def _random_valid_state(self, tries: int = 10_000) -> tuple[int, ...]:
        return random_valid_state(self.space, self._rng, tries)

    def _commit(self, key: tuple[int, ...], y: float, t: float) -> None:
        """Feed one measurement to the numpy store and, in lockstep, its
        device twin — keeping the twin's version current so the round
        sync is a no-op (zero host->device bulk reloads) unless someone
        added to the store out of band."""
        self.store.add(key, y, t)
        self.true_measures += 1
        if self._dstore is not None:
            self._dstore.add(key, y, t)
            self._dstore_version = self.store._version

    def _measure(self, state: Sequence[int], t: float
                 ) -> tuple[tuple[int, ...], float]:
        key = tuple(int(i) for i in state)
        y = float(self.evaluate(self.space.decode(key)))
        self._commit(key, y, t)
        return key, y

    def _measure_states(
        self, states: Sequence[Sequence[int]], t: float
    ) -> list[tuple[tuple[int, ...], float]]:
        """Measure a ranked batch of states — the speculative probes of
        this controller.  With ``eval_workers`` > 1 they dispatch
        concurrently on the evaluation runtime's pool (submission follows
        the caller's rank order, so the acquisition/uncertainty priority
        decides what is measured first); the store is always fed in rank
        order on the main thread, with counting exactly once per probe,
        so pooled and serial runs produce identical stores."""
        if not states:
            return []
        if self.eval_workers and self.eval_workers > 1 and len(states) > 1:
            from .evalpipe import EvalRequest, EvalResult, map_pool

            keys = [tuple(int(i) for i in s) for s in states]
            results = map_pool(
                lambda req: EvalResult(
                    y=float(self.evaluate(dict(req.decoded)))),
                [EvalRequest(state=k, decoded=self.space.decode(k),
                             job="probe", n=self._n, kind="probe")
                 for k in keys],
                max_workers=self.eval_workers)
            out = []
            for k, r in zip(keys, results):
                self._commit(k, float(r.y), t)
                out.append((k, float(r.y)))
            return out
        return [self._measure(s, t) for s in states]

    def _sync_device_store(self) -> None:
        """Bring the device twin up to date.  Steady state this is a
        version compare (host ints) — per-measurement mirroring in
        :meth:`_commit` keeps the twin current; a mismatch means the
        numpy store was fed out of band (a shared recycle store) and
        triggers one bulk host->device reload."""
        if self._dstore is None:
            self._dstore = DeviceMeasurementStore(
                self.model.encoding, half_life=self.store.half_life,
                capacity=self.store.capacity)
        if self._dstore_version != self.store._version:
            self._dstore.load(self.store)
            self._dstore_version = self.store._version

    def _window_offsets(self, offs: np.ndarray):
        """Device copy of a window's per-axis offsets, cached per window
        position (uploaded once per position the incumbent ever
        centers)."""
        key = tuple(int(o) for o in offs)
        off_d = self._offs_cache.get(key)
        if off_d is None:
            import jax.numpy as jnp

            off_d = jnp.asarray(key, jnp.int32)
            self._offs_cache[key] = off_d
        return off_d

    def _window_enc(self, sub: ConfigSpace, offs: np.ndarray):
        key = tuple(int(o) for o in offs)
        enc = self._enc_cache.get(key)
        if enc is None:
            # window sizes are capped by half_width, far below the
            # tabulation ceiling; raise it so huge-but-windowed spaces
            # with wide categorical axes still encode
            enc = sub.encoded(max_size=10_000_000)
            self._enc_cache[key] = enc
        return enc

    def round(self) -> SurrogateRound:
        """One measure-refit-anneal round; returns its audit record."""
        with span("surrogate.round", cat="surrogate"):
            rec = self._round_impl()
        if metrics.get() is not None:
            t_r = float(rec.n)
            metrics.record("surrogate/best_y", rec.best_y, t_r)
            metrics.record("surrogate/window", float(rec.window_size), t_r)
            metrics.set_gauge("surrogate/store_size", float(len(self.store)))
            metrics.set_gauge("surrogate/stale_refreshes",
                              float(self.stale_refreshes))
        return rec

    def _round_impl(self) -> SurrogateRound:
        import jax
        import jax.numpy as jnp

        from .annealing import anneal_fleet, random_valid_states

        t = float(self._n)
        prev_inc = self.incumbent
        measured: list[tuple[tuple[int, ...], float]] = []
        if len(self.store) == 0:
            # global bootstrap design: incumbent + uniform valid states
            # over the FULL space, then recenter on the best sample
            # (dispatched as one concurrent batch when eval_workers > 1)
            measured.extend(self._measure_states(
                [self.incumbent] + [self._random_valid_state()
                                    for _ in range(self.n_bootstrap - 1)],
                t))
            self.incumbent = self.store.best()[0]
        elif (self.store.half_life is not None and self.incumbent in self.store
              and t - self.store.timestamp(self.incumbent)
              >= self.store.half_life):
            # drift mode: the incumbent's reading is stale — refresh it
            # before trusting it as the window center (the online
            # Annealer's staleness rule: re-measuring the incumbent is
            # what lets the loop adapt after a landscape change)
            self.stale_refreshes += 1
            measured.append(self._measure(self.incumbent, t))
            self.incumbent = self._best(t)[0]

        sub, offs = window_space(self.space, self.incumbent, self.half_width)
        enc = self._window_enc(sub, offs)
        W = sub.size()
        n_exp = min(int(round(self.explore_frac * self.measures_per_round)),
                    self.measures_per_round - 1)
        key_r = jax.random.fold_in(self._key, self._n)
        k_init, k_run = jax.random.split(key_r)

        # refit -> anneal -> select without a single bulk host
        # round-trip; only the final (m, ndim) decision packet is read
        # back
        self._sync_device_store()
        mb = min(_bucket(len(self.store)), self._dstore.cap)
        probes, ys_d, rec_d, live_d = self._dstore.refit_view(t, mb)
        with span("surrogate.refit", cat="surrogate"):
            # every window state, enumerated inside the kernel
            mean_w, dmin_w = _interp_jit(self.model.kind)(
                probes, ys_d, rec_d, live_d, None,
                self._window_offsets(offs), qshape=sub.shape,
                **self.model._static())
        unc_w = self._dstore.y_scale_device() * dmin_w
        self.surrogate_queries += W

        # chain 0 starts at the incumbent (always inside its own
        # window); the rest uniform over the window's valid region
        inits_d = random_valid_states(
            k_init, enc, self.n_chains).astype(jnp.int32)
        inits_d = inits_d.at[0].set(jnp.asarray(
            np.asarray(self.incumbent, np.int64) - offs, jnp.int32))
        bonus = jnp.broadcast_to(
            (-self.kappa * unc_w).astype(jnp.float32)[None, :],
            (self.n_chains, W))
        with span("surrogate.anneal", cat="surrogate"):
            out = anneal_fleet(
                k_run, enc, mean_w.reshape(sub.shape),
                self.steps_per_round, self.tau, inits=inits_d,
                n_chains=self.n_chains, extra_costs=bonus)
        sel = _select_jit(sub.shape, self.acquisition,
                          self.measures_per_round, n_exp)(
            inits_d, out["states"], mean_w, unc_w,
            jnp.float32(self.kappa), jnp.float32(self._best(t)[1]))
        # .tolist() reads the m*ndim-int decision packet — the one
        # host pull of the round, below the sanitizer's bulk-transfer
        # accounting (np.asarray / device_get), and its one wait for
        # the device
        with span("surrogate.sync", cat="surrogate"):
            rows = sel.tolist()
        with span("surrogate.measure", cat="surrogate"):
            measured.extend(self._measure_states(
                [tuple(int(v) + int(o) for v, o in zip(r, offs))
                 for r in rows if r[0] >= 0], t))

        self.incumbent, best_y = self._best(t)
        rec = SurrogateRound(
            n=self._n, incumbent=self.incumbent, best_y=best_y,
            window_size=W, true_measures=self.true_measures,
            surrogate_queries=self.surrogate_queries,
            measured=tuple(measured))
        self.rounds.append(rec)
        if provenance.get() is not None:
            self._record_round_provenance(
                rec, prev_inc, measured, out, np.asarray(inits_d),
                np.asarray(mean_w, np.float64),
                np.asarray(unc_w, np.float64), sub, offs)
        self._n += 1
        note_round("SurrogateAnnealer", self)
        return rec

    def _record_round_provenance(self, rec, prev_inc, measured, out,
                                 inits, mean, unc, sub, offs) -> None:
        """One DecisionRecord per surrogate round.  Armed-only.

        The committed value IS a single real measurement (the store's
        best credible reading), so both decomposition tiers are the
        trivial one-term ladder — trivially bit-exact.  The interesting
        provenance is the rest: the runner-up *measured* candidate this
        round (counterfactual), and the temperature / acceptance
        probability at the incumbent chain's last accepted move on the
        acquisition surface (mean - kappa*unc), recovered from the
        compiled round's outputs."""
        from .annealing import chain_accept_stats

        ys = np.asarray(out["ys"])
        accepts = np.asarray(out["accepts"])
        flat0 = np.ravel_multi_index(tuple(np.asarray(inits).T), sub.shape)
        y0 = mean[flat0] - self.kappa * unc[flat0]
        tau_at, p_at = chain_accept_stats(
            ys, accepts, y0,
            np.full((self.n_chains, self.steps_per_round), self.tau))
        rejected, rejected_y = None, float("nan")
        others = [(st, y) for st, y in measured
                  if tuple(st) != tuple(rec.incumbent)]
        if others:
            st, y = min(others, key=lambda sy: sy[1])
            rejected, rejected_y = tuple(st), float(y)
        terms = (("measured_y", rec.best_y),)
        provenance.record(provenance.DecisionRecord(
            controller="surrogate", round=int(rec.n), tenant="",
            action=("accept" if tuple(rec.incumbent) != tuple(prev_inc)
                    else "hold"),
            state=tuple(rec.incumbent), y=float(rec.best_y), terms=terms,
            exact_split=terms, tau=float(tau_at[0]),
            accept_prob=float(p_at[0]),
            rejected=rejected, rejected_y=rejected_y,
            counterfactual=(rejected_y - float(rec.best_y)
                            if rejected is not None else float("nan"))))

    def run(self, n_rounds: int) -> list[SurrogateRound]:
        return [self.round() for _ in range(n_rounds)]

    def _best(self, now: float) -> tuple[tuple[int, ...], float]:
        """Best measured state; on drifting landscapes (store.half_life
        set) only readings younger than 4 half-lives compete — beyond
        that a measurement has decayed to < 7% credibility."""
        hl = self.store.half_life
        return self.store.best(now=now,
                               max_age=None if hl is None else 4.0 * hl)

    def best(self) -> tuple[tuple[int, ...], float]:
        """Best measured (state, objective) — measurements, not estimates."""
        return self._best(float(self._n))

    def counts(self) -> dict[str, int]:
        """Cumulative evaluation counters.  Prefer :meth:`stats`, which
        embeds these in the unified controller contract."""
        return {"true_measures": self.true_measures,
                "surrogate_queries": self.surrogate_queries}

    def stats(self) -> dict[str, Any]:
        """The unified per-controller stats contract
        (:meth:`repro.core.procurement.ControllerMixin.stats`) for the
        surrogate loop, which is not a ``ControllerMixin``: same keys,
        ``pipeline`` is always None (probes go through ``map_pool``, not
        a speculative pipeline), plus the store/refresh extras."""
        out: dict[str, Any] = {
            "controller": type(self).__name__,
            "rounds": self._n,
            **self.counts(),
            "pipeline": None,
            "store_size": len(self.store),
            "stale_refreshes": self.stale_refreshes,
        }
        reg = metrics.get()
        if reg is not None:
            out["metrics"] = reg.snapshot(prefix="surrogate")
        return out
