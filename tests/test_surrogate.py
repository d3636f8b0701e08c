"""Surrogate-objective subsystem (repro.core.surrogate + the Pallas
distance kernel): interpolation correctness, windowing, the
measure-refit-anneal loop's convergence/determinism, and the
ObjectiveSource seam in both controllers."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ConfigSpace,
    Dimension,
    ExhaustiveSource,
    MeasurementStore,
    Objective,
    PenalizedObjective,
    ProcurementController,
    SpaceEncoding,
    SurrogateAnnealer,
    SurrogateModel,
    SurrogateSource,
    tabulate,
    tabulate_dynamic,
    window_space,
)
from repro.core.costmodel import SimulatedEvaluator
from repro.core.fleet import FleetController, TenantSpec
from repro.core.pricing import EC2_CATALOG_ADJUSTED
from repro.core.procurement import make_ec2_space
from repro.kernels import ops, ref


def _smooth_space(n_cores: int = 120):
    return ConfigSpace((
        Dimension("fam", ("a", "b", "c", "d")),
        Dimension("cores", tuple(range(4, 4 + 2 * n_cores, 2))),
    ))


def _smooth_fn(cfg):
    f = {"a": 1.0, "b": 0.82, "c": 1.15, "d": 0.95}[cfg["fam"]]
    c = cfg["cores"]
    return f * (30.0 + 4000.0 / c + 0.9 * c ** 0.8)


# ---------------------------------------------------------------------------
# Pallas distance kernel vs jnp reference.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("Q,M,F", [
    (5, 3, 7),          # tiny, everything padded
    (300, 17, 130),     # feature dim over one lane width
    (513, 256, 6),      # row counts straddling block boundaries
])
def test_pairwise_sqdist_kernel_matches_ref(Q, M, F):
    rng = np.random.default_rng(Q + M + F)
    xq = jnp.asarray(rng.normal(size=(Q, F)), jnp.float32)
    xm = jnp.asarray(rng.normal(size=(M, F)), jnp.float32)
    got = np.asarray(ops.pairwise_sqdist(xq, xm))
    want = np.asarray(ref.pairwise_sqdist_ref(xq, xm))
    assert got.shape == (Q, M)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_pairwise_sqdist_zero_diagonal():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(40, 9)), jnp.float32)
    d2 = np.asarray(ops.pairwise_sqdist(x, x))
    np.testing.assert_allclose(np.diag(d2), 0.0, atol=1e-5)
    assert (d2 >= 0).all()


# ---------------------------------------------------------------------------
# Feature encoding: the mixed ordinal-categorical metric.
# ---------------------------------------------------------------------------


def test_space_encoding_mixed_metric():
    space = ConfigSpace((
        Dimension("ord", tuple(range(5))),
        Dimension("cat", ("x", "y", "z"), kind="categorical"),
    ))
    enc = SpaceEncoding.from_space(space)
    assert enc.feature_dim == 1 + 3
    x = enc.features([[0, 0], [4, 0], [2, 0], [2, 1]])
    d2 = np.asarray(ref.pairwise_sqdist_ref(jnp.asarray(x), jnp.asarray(x)))
    # full ordinal traversal costs 1.0; categorical mismatch costs 1.0
    np.testing.assert_allclose(d2[0, 1], 1.0, atol=1e-6)
    np.testing.assert_allclose(d2[0, 2], 0.25, atol=1e-6)
    np.testing.assert_allclose(d2[2, 3], 1.0, atol=1e-6)
    # same categorical value -> zero categorical contribution
    np.testing.assert_allclose(d2[0, 0], 0.0, atol=1e-6)


# ---------------------------------------------------------------------------
# MeasurementStore.
# ---------------------------------------------------------------------------


def test_measurement_store_latest_wins_and_decay():
    st = MeasurementStore(2, half_life=2.0)
    st.add((0, 1), 5.0, 0.0)
    st.add((3, 2), 7.0, 1.0)
    st.add((0, 1), 4.0, 4.0)          # re-measure: replaces, re-stamps
    assert len(st) == 2
    states, ys, ts = st.arrays()
    assert states.tolist() == [[3, 2], [0, 1]]   # refresh order
    assert ys.tolist() == [7.0, 4.0]
    w = st.weights(now=4.0)
    np.testing.assert_allclose(w, [2.0 ** (-1.5), 1.0])
    assert st.best() == ((0, 1), 4.0)


def test_measurement_store_capacity_evicts_stalest():
    st = MeasurementStore(1, capacity=2)
    st.add((0,), 1.0, 0.0)
    st.add((1,), 2.0, 1.0)
    st.add((0,), 1.5, 2.0)            # refresh keeps (0,) newest
    st.add((2,), 3.0, 3.0)            # evicts (1,), the stalest
    states, _, _ = st.arrays()
    assert states.tolist() == [[0], [2]]


# ---------------------------------------------------------------------------
# The interpolator.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["idw", "rbf"])
def test_surrogate_predict_anchors_and_uncertainty(kind):
    space = _smooth_space(30)
    model = SurrogateModel(SpaceEncoding.from_space(space), kind=kind)
    st = MeasurementStore(2)
    obs = [(0, 3), (1, 10), (3, 25), (2, 18)]
    for s in obs:
        st.add(s, _smooth_fn(space.decode(s)), 0.0)
    mean, unc = model.predict(np.asarray(obs), st)
    ys = np.asarray([_smooth_fn(space.decode(s)) for s in obs])
    if kind == "idw":  # Shepard weights are exact at measured states
        np.testing.assert_allclose(mean, ys, rtol=1e-4)
    np.testing.assert_allclose(unc, 0.0, atol=1e-4)
    # uncertainty grows with distance from the data
    far = np.asarray([[0, 29]])
    _, unc_far = model.predict(far, st)
    assert unc_far[0] > 1.0


def test_surrogate_predict_requires_measurements():
    space = _smooth_space(8)
    model = SurrogateModel(SpaceEncoding.from_space(space))
    with pytest.raises(ValueError, match="empty"):
        model.predict(np.zeros((1, 2), np.int64), MeasurementStore(2))


# ---------------------------------------------------------------------------
# Windowing.
# ---------------------------------------------------------------------------


def test_window_space_shapes_and_offsets():
    space = ConfigSpace((
        Dimension("a", tuple(range(40))),
        Dimension("b", tuple(range(5))),
        Dimension("c", ("x", "y", "z"), kind="categorical"),
    ))
    sub, offs = window_space(space, (20, 2, 1), half_width=4)
    assert sub.shape == (9, 5, 3)          # clipped vs whole-axis vs cat
    assert offs.tolist() == [16, 0, 0]
    # boundary clip keeps the window SIZE (stable jit shapes)
    sub2, offs2 = window_space(space, (1, 0, 0), half_width=4)
    assert sub2.shape == (9, 5, 3)
    assert offs2.tolist() == [0, 0, 0]
    # decoded values (hence validity semantics) carry over
    assert sub.decode((0, 0, 0))["a"] == 16


def test_window_space_preserves_validity():
    space = ConfigSpace(
        (Dimension("n", tuple(range(1, 33))),
         Dimension("tp", tuple(range(1, 9)))),
        is_valid=lambda c: c["n"] % c["tp"] == 0)
    sub, offs = window_space(space, (15, 3), half_width=3)
    for idx in [(0, 0), (3, 2), (6, 3)]:
        full = tuple(np.asarray(idx) + offs)
        assert sub.contains(idx) == space.contains(full)


# ---------------------------------------------------------------------------
# The measure-refit-anneal loop.
# ---------------------------------------------------------------------------


def test_surrogate_annealer_converges_within_tolerance():
    """ISSUE 3: surrogate optimum within 5% of the tabulate optimum at
    <= 10% of the exhaustive evaluation count."""
    space = _smooth_space(120)                     # 480 states
    table = tabulate(space, _smooth_fn)
    y_star = float(table.min())
    sa = SurrogateAnnealer(space, _smooth_fn, half_width=6, n_chains=16,
                           steps_per_round=48, measures_per_round=6,
                           n_bootstrap=8, seed=0)
    sa.run(6)
    _, y_best = sa.best()
    assert sa.true_measures <= 0.10 * space.size()
    assert (y_best - y_star) / abs(y_star) <= 0.05
    # counters are reflected in the audit records, cumulative
    assert sa.rounds[-1].true_measures == sa.true_measures
    assert sa.rounds[-1].surrogate_queries == sa.surrogate_queries
    assert [r.true_measures for r in sa.rounds] == sorted(
        r.true_measures for r in sa.rounds)


def test_surrogate_annealer_ei_converges_on_960_state_validation_space():
    """ISSUE 4 satellite: the expected-improvement acquisition converges
    on the 960-state EC2 blended validation space (the surrogate_scale
    bench's non-smoke problem) within the same gap/budget envelope as
    LCB — 5% of the exhaustive optimum at <= 10% of the evaluations."""
    from repro.core import Objective, cluster_config_from, make_ec2_space

    catalog = EC2_CATALOG_ADJUSTED
    space = make_ec2_space(catalog, core_counts=tuple(range(4, 244, 1)))
    assert space.size() == 960
    ev = SimulatedEvaluator(catalog)
    obj = Objective(lambda_cost=200.0)
    blend = {"wordcount": 0.5, "kmeans": 0.3, "pagerank": 0.2}

    def fn(decoded):
        cfg = cluster_config_from(decoded)
        return float(sum(w * obj(ev.measure(cfg, name, 0))
                         for name, w in blend.items()))

    y_star = float(tabulate(space, fn).min())
    sa = SurrogateAnnealer(space, fn, acquisition="ei", half_width=6,
                           n_chains=16, steps_per_round=48,
                           measures_per_round=6, n_bootstrap=8, seed=0)
    sa.run(14)
    _, y_best = sa.best()
    assert sa.true_measures <= 0.10 * space.size()
    assert (y_best - y_star) / abs(y_star) <= 0.05


@pytest.mark.parametrize("acquisition", ["lcb", "ei"])
def test_window_select_matches_numpy_ranking(acquisition):
    """The device measurement select against its numpy oracle on the
    same float32 mean and uncertainty: the distinct visited states in
    ascending flat order (np.unique), the first ``m - n_exp`` by
    acquisition rank, then the rest by uncertainty, both stable."""
    from repro.core import expected_improvement
    from repro.core.surrogate import _select_jit

    shape, C, steps, m, n_exp = (5, 4, 3), 4, 12, 6, 2
    kappa, y_best = 1.5, 2.0
    rng = np.random.default_rng(5)
    size = int(np.prod(shape))
    # quarter steps: exact in float32, so equal scores tie exactly
    mean = (0.5 * rng.integers(0, 10, size)).astype(np.float32)
    unc = (0.25 * rng.integers(0, 5, size)).astype(np.float32)
    inits = np.stack([rng.integers(0, n, C) for n in shape],
                     axis=1).astype(np.int32)
    states = np.stack([rng.integers(0, n, (C, steps)) for n in shape],
                      axis=2).astype(np.int32)

    visited = np.unique(np.concatenate(
        [inits[:, None, :], states], axis=1).reshape(-1, 3), axis=0)
    vflat = np.ravel_multi_index(tuple(visited.T), shape)
    mv, uv = mean[vflat].astype(np.float64), unc[vflat].astype(np.float64)
    acq = (-expected_improvement(mv, uv, y_best) if acquisition == "ei"
           else mv - kappa * uv)
    chosen: list[int] = []
    for pos in (list(np.argsort(acq, kind="stable")[:m - n_exp])
                + list(np.argsort(-uv, kind="stable"))):
        if pos not in chosen:
            chosen.append(int(pos))
        if len(chosen) == m:
            break
    want = visited[chosen]
    assert len(vflat) > m and len(set(acq.tolist())) < len(acq)

    sel = _select_jit(shape, acquisition, m, n_exp)(
        inits, states, jnp.asarray(mean), jnp.asarray(unc),
        jnp.float32(kappa), jnp.float32(y_best))
    np.testing.assert_array_equal(np.asarray(sel), want)


def test_surrogate_annealer_rejects_unknown_acquisition():
    with pytest.raises(ValueError, match="acquisition"):
        SurrogateAnnealer(_smooth_space(20), _smooth_fn,
                          acquisition="ucb")


def test_expected_improvement_prefers_low_mean_and_high_uncertainty():
    from repro.core import expected_improvement

    ei = expected_improvement(
        np.asarray([5.0, 1.0, 5.0, 9.0]),
        np.asarray([0.0, 0.0, 2.0, 2.0]), y_best=4.0)
    assert ei[0] == pytest.approx(0.0, abs=1e-9)   # known, no improvement
    assert ei[1] == pytest.approx(3.0, rel=1e-6)   # known 3.0 improvement
    assert ei[2] > ei[0]                           # uncertainty earns credit
    assert ei[2] > ei[3]                           # but a bad mean costs


def test_surrogate_annealer_deterministic_under_fixed_seed():
    space = _smooth_space(60)
    runs = []
    for _ in range(2):
        sa = SurrogateAnnealer(space, _smooth_fn, half_width=5, n_chains=8,
                               steps_per_round=32, measures_per_round=4,
                               seed=7)
        sa.run(3)
        runs.append((sa.best(),
                     [r.incumbent for r in sa.rounds],
                     [r.measured for r in sa.rounds]))
    assert runs[0] == runs[1]


def test_surrogate_annealer_tracks_drifting_landscape():
    """With a recency half-life, a stale incumbent is re-measured and old
    low readings age out of best(), so the loop re-converges after the
    landscape moves (paper sec. 4.3, the surrogate way)."""
    space = ConfigSpace((Dimension("x", tuple(range(60))),))
    target = {"v": 10}

    def fn(cfg):
        return abs(cfg["x"] - target["v"]) + 1.0

    sa = SurrogateAnnealer(space, fn, store=MeasurementStore(1, half_life=2.0),
                           half_width=6, n_chains=8, steps_per_round=32,
                           measures_per_round=6, seed=0)
    sa.run(5)
    s1, _ = sa.best()
    assert abs(s1[0] - 10) <= 2
    target["v"] = 50                        # the landscape drifts
    sa.run(14)
    s2, y2 = sa.best()
    assert abs(s2[0] - 50) <= 3, (s2, y2)


def test_surrogate_annealer_respects_validity():
    space = ConfigSpace(
        (Dimension("n", tuple(range(1, 65))),
         Dimension("tp", (1, 2, 4, 8))),
        is_valid=lambda c: c["n"] % c["tp"] == 0)

    def fn(cfg):
        assert cfg["n"] % cfg["tp"] == 0, "measured an invalid state"
        return abs(cfg["n"] - 40) + 3.0 * cfg["tp"]

    sa = SurrogateAnnealer(space, fn, half_width=4, n_chains=8,
                           steps_per_round=24, measures_per_round=4, seed=1)
    sa.run(4)
    state, _ = sa.best()
    assert space.contains(state)


# ---------------------------------------------------------------------------
# ObjectiveSource: the controllers' table seam.
# ---------------------------------------------------------------------------


def test_exhaustive_source_matches_tabulate_and_counts():
    space = _smooth_space(20)
    src = ExhaustiveSource()
    got = src.table(space, _smooth_fn)
    np.testing.assert_allclose(got, tabulate(space, _smooth_fn))
    assert src.counts() == {"true_measures": space.size(),
                            "surrogate_queries": 0}


def test_surrogate_source_near_argmin_with_fraction_of_measures():
    space = _smooth_space(60)                       # 240 states
    table = tabulate(space, _smooth_fn)
    src = SurrogateSource(n_probe=48, seed=0)
    est = src.table(space, _smooth_fn)
    assert est.shape == table.shape
    assert src.true_measures == 48
    assert src.surrogate_queries == space.size()
    y_at_est_argmin = table[np.unravel_index(np.argmin(est), table.shape)]
    assert (y_at_est_argmin - table.min()) / table.min() <= 0.05


@pytest.mark.parametrize("size,n", [(1 << 20, 1024), (4096, 64), (40, 64)])
def test_draw_probes_matches_the_plain_draw(size, n):
    """The device draw is the plain one from the same key: ``2 n``
    uniform draws, the first ``n`` distinct in draw order, and weight 0
    (state 0) on the slots the draws leave over (40 states cannot give
    64 distinct probes)."""
    from repro.core.surrogate import draw_probes

    key = jax.random.key(2_147_483_659 % (1 << 32))
    flat, weight = draw_probes(key, size, n)
    cand = np.asarray(jax.random.randint(key, (2 * n,), 0, size,
                                         dtype=jnp.int32))
    _, first = np.unique(cand, return_index=True)
    want = cand[np.sort(first)[:n]]
    flat, weight = np.asarray(flat), np.asarray(weight)
    k = len(want)
    assert k == n if size >= 2 * n else k <= size
    np.testing.assert_array_equal(flat[:k], want)
    assert (weight[:k] == 1.0).all() and (weight[k:] == 0.0).all()
    assert (flat[k:] == 0).all()
    assert len(set(flat[:k].tolist())) == k


def test_device_table_refuses_what_it_cannot_build():
    """A space with invalid states and a host recycle store belong to the
    host table path; the device path says so instead of ignoring them."""
    dims = (Dimension("a", tuple(range(8))), Dimension("b", tuple(range(4))))
    score = lambda flat: flat.astype(jnp.float32)
    with pytest.raises(ValueError, match="valid"):
        SurrogateSource(n_probe=4).device_table(
            ConfigSpace(dims, lambda cfg: cfg["a"] < 7), score,
            jax.random.key(0))
    free = ConfigSpace(dims)
    with pytest.raises(ValueError, match="host table path"):
        SurrogateSource(n_probe=4, recycle_store=MeasurementStore(2)
                        ).device_table(free, score, jax.random.key(0))
    src = SurrogateSource(n_probe=4)
    table = np.asarray(src.device_table(free, score, jax.random.key(0)))
    assert table.shape == (32,)
    assert src.counts() == {"true_measures": 4, "surrogate_queries": 32}


def test_fleet_controller_with_surrogate_source_saves_measures():
    catalog = EC2_CATALOG_ADJUSTED.with_capacities(
        {f: 300.0 for f in EC2_CATALOG_ADJUSTED.names()})
    space = make_ec2_space(catalog, core_counts=tuple(range(4, 68, 8)))
    tenants = [TenantSpec("t0", {"wordcount": 1.0}),
               TenantSpec("t1", {"kmeans": 1.0})]

    def build(source):
        cat = EC2_CATALOG_ADJUSTED.with_capacities(
            {f: 300.0 for f in EC2_CATALOG_ADJUSTED.names()})
        return FleetController(
            space, cat, SimulatedEvaluator(cat), tenants,
            objective=PenalizedObjective(Objective(lambda_cost=200.0)),
            budget_usd_hr=60.0, steps_per_round=16, seed=0,
            objective_source=source)

    exhaustive = build(None)
    surrogate = build(SurrogateSource(n_probe=12, seed=0))
    d_ex = exhaustive.run(2)
    d_su = surrogate.run(2)
    ce, cs = exhaustive.evaluation_counts(), surrogate.evaluation_counts()
    assert cs["true_measures"] < ce["true_measures"]
    assert cs["surrogate_queries"] == 2 * space.size()   # one per blend
    # cumulative counters ride the decision log
    assert d_ex[-1].true_measures == ce["true_measures"]
    assert d_su[-1].surrogate_queries == cs["surrogate_queries"]
    assert d_su[-1].action in ("admit", "hold", "defer", "preempt")


def test_procurement_plan_with_surrogate_source_counts():
    catalog = EC2_CATALOG_ADJUSTED
    space = make_ec2_space(catalog, core_counts=tuple(range(4, 132, 8)))
    ctrl = ProcurementController(
        space=space, catalog=catalog, evaluator=SimulatedEvaluator(catalog),
        objective=Objective(lambda_cost=200.0), blend={"wordcount": 1.0},
        seed=0, objective_source=SurrogateSource(n_probe=16, seed=2))
    ctrl.plan(n_chains=32, n_steps=60)
    d = ctrl.submit()
    counts = ctrl.evaluation_counts()
    assert counts["true_measures"] < space.size()
    assert counts["surrogate_queries"] == space.size()
    assert d.true_measures == counts["true_measures"]
    assert d.surrogate_queries == counts["surrogate_queries"]


def test_procurement_plan_counts_exhaustive_tabulation():
    """Regression: plan() with the default (exhaustive) source must count
    its tabulation measurements — they are real evaluator runs."""
    catalog = EC2_CATALOG_ADJUSTED
    space = make_ec2_space(catalog, core_counts=tuple(range(4, 68, 8)))
    ctrl = ProcurementController(
        space=space, catalog=catalog, evaluator=SimulatedEvaluator(catalog),
        objective=Objective(lambda_cost=200.0),
        blend={"wordcount": 0.5, "kmeans": 0.5}, seed=0)
    ctrl.plan(n_chains=16, n_steps=40)
    # 2 blend members measured per tabulated state
    assert ctrl.evaluation_counts()["true_measures"] == 2 * space.size()


def test_decision_counts_default_zero_for_plain_annealer_logs():
    from repro.core import Annealer, StepNeighborhood

    space = _smooth_space(10)
    ann = Annealer(space, StepNeighborhood(space),
                   lambda cfg, n: _smooth_fn(cfg), seed=0)
    ann.run(5)
    assert ann.measure_count == len(ann.evaluations) == 6  # init + 5 steps


# ---------------------------------------------------------------------------
# Satellite: tabulate_dynamic valid_mask passthrough.
# ---------------------------------------------------------------------------


def test_tabulate_dynamic_valid_mask_passthrough():
    space = ConfigSpace(
        (Dimension("n", tuple(range(1, 13))),
         Dimension("tp", (1, 2, 3))),
        is_valid=lambda c: c["n"] % c["tp"] == 0)
    enc = space.encoded()
    calls = {"n": 0}

    def fn(cfg, t):
        calls["n"] += 1
        return cfg["n"] * (t + 1) + cfg["tp"]

    want = tabulate_dynamic(space, fn, 4)
    n_without = calls["n"]
    calls["n"] = 0
    got = tabulate_dynamic(space, fn, 4, valid_mask=enc.valid_mask)
    assert calls["n"] == n_without           # same fn calls, no re-validation
    np.testing.assert_allclose(got, want)
    assert (~enc.valid_mask).any()
    assert np.isinf(got[:, ~enc.valid_mask]).all()


def test_annealer_keeps_a_caller_supplied_empty_store():
    """Regression: ``store or default`` discarded a caller's EMPTY store
    (len 0 is falsy) — silently dropping its half_life drift
    configuration and capacity bound."""
    space = ConfigSpace((Dimension("x", tuple(range(12))),))
    store = MeasurementStore(1, half_life=3.0, capacity=17)
    sa = SurrogateAnnealer(space, lambda cfg: float(cfg["x"]), store=store,
                           half_width=3, n_chains=2, steps_per_round=4,
                           measures_per_round=2, seed=0)
    assert sa.store is store
    sa.run(1)
    assert sa.store is store and len(store) > 0
