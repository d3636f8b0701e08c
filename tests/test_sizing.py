"""Container-sizing subsystem (ISSUE 4): the microservice-DAG queueing
model, the Pallas sizing-latency kernel vs its jnp reference, the batched
sizing evaluator vs the numpy ground truth, the online SizingController
(drift tracking, source seams), and container tenants inside the
multi-tenant FleetController's capacity ledger."""

import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (
    EC2_CATALOG,
    ExhaustiveSource,
    FleetController,
    MicroserviceEvaluator,
    Objective,
    PenalizedObjective,
    ServiceCatalog,
    SizingController,
    SizingDecision,
    SizingSpace,
    SurrogateSource,
    TenantSpec,
    evaluate_sizing_batch,
    full_grid,
    microservice_config_fn,
)
from repro.core.procurement import ControllerMixin
from repro.core.sizing import _sizing_select_jit, sizing_table_device
from repro.kernels.ref import sizing_entry_latency_ref, sizing_latency_ref
from repro.kernels.sizing_latency import sizing_latency
from repro.workloads.microservice import (
    ContainerSize,
    DriftingMix,
    MicroserviceDAG,
    RequestClass,
    ServiceTier,
    mmc_sojourn,
)

SIZES = (ContainerSize("s", 1, 2.0), ContainerSize("l", 4, 8.0))


def _dag():
    """A 6-tier DAG with fan-out, memory-bound and cpu-bound tiers, and
    two request classes whose load concentrates on different tiers."""
    tiers = (
        ServiceTier("gw", base_rate=60.0),
        ServiceTier("auth", base_rate=80.0),
        ServiceTier("catalog", base_rate=40.0, mem_per_rps_gb=0.08),
        ServiceTier("product", base_rate=35.0),
        ServiceTier("pricing", base_rate=90.0),
        ServiceTier("inventory", base_rate=50.0),
    )
    edges = (("gw", "auth"), ("gw", "catalog"), ("catalog", "product"),
             ("product", "pricing"), ("product", "inventory"),
             ("auth", "inventory"))
    classes = (
        RequestClass("browse", "gw",
                     {"gw": 1, "catalog": 1, "product": 2, "pricing": 2,
                      "inventory": 1}, slo_s=0.35),
        RequestClass("checkout", "gw",
                     {"gw": 1, "auth": 1, "inventory": 2, "pricing": 1},
                     slo_s=0.5),
    )
    return MicroserviceDAG(tiers, edges, classes)


def _spec(**kw):
    kw.setdefault("sizes", SIZES)
    kw.setdefault("replica_counts", (1, 2, 3))
    kw.setdefault("lambda_cost", 0.5)
    kw.setdefault("slo_penalty", 50.0)
    return SizingSpace(_dag(), **kw)


MIX_BROWSE = {"browse": 40.0, "checkout": 8.0}
MIX_CHECKOUT = {"browse": 10.0, "checkout": 45.0}


# ---------------------------------------------------------------------------
# M/M/c ground truth.
# ---------------------------------------------------------------------------


def test_mmc_sojourn_matches_mm1_closed_form():
    for lam, mu in [(1.0, 5.0), (4.0, 10.0), (0.0, 3.0)]:
        assert mmc_sojourn(lam, mu, 1) == pytest.approx(
            1.0 / (mu - lam), rel=1e-12)


def test_mmc_sojourn_decreases_with_replicas_and_saturates():
    lam, mu = 9.0, 4.0
    ts = [mmc_sojourn(lam, mu, c) for c in (3, 4, 6, 10)]
    assert ts == sorted(ts, reverse=True)
    assert ts[-1] == pytest.approx(1.0 / mu, rel=1e-3)  # wait vanishes
    assert mmc_sojourn(lam, mu, 2, sat_s=123.0) == 123.0  # 2*4 < 9
    with pytest.raises(ValueError):
        mmc_sojourn(1.0, 1.0, 0)


# ---------------------------------------------------------------------------
# The Pallas kernel vs the jnp reference (acceptance: 1e-5).
# ---------------------------------------------------------------------------

BOUTIQUE = json.loads((Path(__file__).resolve().parents[1] / "bench"
                       / "configs" / "boutique-sizing-59k.json").read_text())


def _boutique_dag(replica_counts=None):
    """Online Boutique's 10 services, 14 call edges and 6 Locust tasks."""
    c = BOUTIQUE
    dag = MicroserviceDAG(
        tuple(ServiceTier(**t) for t in c["tiers"]),
        tuple(tuple(e) for e in c["edges"]),
        tuple(RequestClass(**k) for k in c["classes"]))
    return SizingSpace(
        dag, sizes=tuple(ContainerSize(**s) for s in c["sizes"]),
        replica_counts=tuple(replica_counts or c["replica_counts"]),
        price_per_core_hr=c["price_per_core_hr"],
        lambda_cost=c["lambda_cost"], slo_penalty=c["slo_penalty"],
        sat_s=c["sat_s"])


def _static_dag(kind, rng):
    """(visits, edges, entries) of the Boutique DAG, or of a random
    topologically ordered one with fan-out from tier 0, two roots (tiers
    0 and 1), an isolated last tier and a class entering at each."""
    if kind == "boutique":
        dag = _boutique_dag().dag
        return (tuple(map(tuple, dag.visit_matrix().tolist())),
                tuple((dag.index(u), dag.index(v)) for u, v in dag.edges),
                tuple(int(e) for e in dag.entry_indices()))
    K = 7
    edges = [(0, 2), (0, 3), (0, 4)]
    edges += [(v, u) for v in range(1, K - 1) for u in range(v + 1, K - 1)
              if rng.random() < 0.4]
    visits = rng.uniform(0.0, 2.0, (4, K)) * (rng.random((4, K)) < 0.8)
    return (tuple(map(tuple, visits.tolist())), tuple(edges),
            (0, 1, K - 1, 3))


def _kernel_inputs(rng, K, B, c_max):
    """Tier rates, and per-state service rates and replicas, with the
    utilization bounded away from 1 (realistic deployments; the
    near-critical regime is covered by the saturation test below)."""
    lam = rng.uniform(5.0, 100.0, K).astype(np.float32)
    repl = rng.integers(1, c_max + 1, (K, B)).astype(np.float32)
    util = rng.uniform(0.05, 0.9, (K, B))
    mu = (lam[:, None] / (util * repl)).astype(np.float32)
    return tuple(map(jnp.asarray, (lam, mu, repl)))


@pytest.mark.parametrize("dag,B,c_max", [
    ("random", 1, 1),         # one state, heavily padded
    ("random", 33, 8),        # odd batch vs the lane block
    ("random", 700, 3),       # two blocks, the second partial
    ("random", 1024, 1),      # exact block multiple
    ("boutique", 1, 3),
    ("boutique", 513, 8),     # one state over a block
    ("boutique", 1500, 3),    # the benchmark's replica range
])
def test_sizing_latency_kernel_matches_ref(dag, B, c_max):
    rng = np.random.default_rng(B + c_max)
    visits, edges, entries = _static_dag(dag, rng)
    kw = dict(visits=visits, edges=edges, entries=entries, c_max=c_max)
    args = _kernel_inputs(rng, len(visits[0]), B, c_max)
    got = np.asarray(sizing_latency(*args, **kw))
    want = np.asarray(sizing_entry_latency_ref(*args, **kw))
    assert got.shape == (len(visits), B)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


def test_sizing_latency_kernel_saturation_agrees_with_ref():
    """Every cell unstable: one class entering each tier of an edgeless
    DAG reads each tier's sojourn, all at ``sat_s``."""
    rng = np.random.default_rng(3)
    K, B = 5, 16
    mu = rng.uniform(5.0, 40.0, (K, B)).astype(np.float32)
    repl = rng.integers(1, 5, (K, B)).astype(np.float32)
    lam = (mu * repl).max(axis=1) * 1.5                  # all unstable
    args = tuple(map(jnp.asarray, (lam.astype(np.float32), mu, repl)))
    kw = dict(visits=tuple(map(tuple, np.eye(K).tolist())), edges=(),
              entries=tuple(range(K)), c_max=4, sat_s=777.0)
    soj_k = np.asarray(sizing_latency(*args, **kw))
    soj_r = np.asarray(sizing_entry_latency_ref(*args, **kw))
    assert (soj_k == 777.0).all()
    assert (soj_r == 777.0).all()


def test_sizing_latency_ops_wrapper_matches_ref():
    """The public jitted ops entry point (what SizingSpace's batched
    evaluator calls on TPU) stays in sync with the reference."""
    from repro.kernels import ops

    rng = np.random.default_rng(7)
    visits, edges, entries = _static_dag("boutique", rng)
    args = _kernel_inputs(rng, len(visits[0]), 300, 3)
    kw = dict(visits=visits, edges=edges, entries=entries, c_max=3)
    np.testing.assert_allclose(
        np.asarray(ops.sizing_latency(*args, **kw)),
        np.asarray(sizing_entry_latency_ref(*args, **kw)),
        rtol=1e-5, atol=1e-7)


def test_sizing_latency_critical_path_semantics():
    """Sequential chains sum; parallel fan-out takes the max branch."""
    # tiers 0 -> 1 -> {2, 3}; sojourns fixed via M/M/inf-like idle queues
    mu = np.full((1, 4), 10.0, np.float32)              # sojourn = 0.1 each
    lam = np.zeros((1, 4), np.float32)
    repl = np.ones((1, 4), np.float32)
    adj = np.zeros((4, 4), bool)
    adj[0, 1] = adj[1, 2] = adj[1, 3] = True
    w = np.asarray([[1.0, 1.0, 3.0, 1.0]], np.float32)  # branch 2 is heavy
    _, path = sizing_latency_ref(*map(jnp.asarray, (lam, mu, repl, w, adj)),
                                 c_max=1)
    # L[3] = 0.1, L[2] = 0.3, L[1] = 0.1 + max = 0.4, L[0] = 0.1 + 0.4
    want = [0.5, 0.4, 0.3, 0.1]
    np.testing.assert_allclose(np.asarray(path)[0], want, rtol=1e-5)
    # the kernel, one class entering at each tier
    got = sizing_latency(jnp.asarray(lam[0]), jnp.asarray(mu.T),
                         jnp.asarray(repl.T), visits=(tuple(w[0]),) * 4,
                         edges=((0, 1), (1, 2), (1, 3)),
                         entries=(0, 1, 2, 3), c_max=1)
    np.testing.assert_allclose(np.asarray(got)[:, 0], want, rtol=1e-5)


@pytest.mark.parametrize("bad", [
    dict(edges=((1, 0),)),                 # against the topological order
    dict(edges=((0, 4),)),                 # unknown tier
    dict(entries=(4,)),                    # unknown entry tier
    dict(visits=((1.0, 1.0),)),            # visits not (C, K)
])
def test_sizing_latency_rejects_a_malformed_dag(bad):
    kw = dict(visits=((1.0, 1.0, 1.0, 1.0),), edges=((0, 1),),
              entries=(0,), c_max=2)
    kw.update(bad)
    ones = jnp.ones((4, 8), jnp.float32)
    with pytest.raises(ValueError):
        sizing_latency(jnp.zeros(4, jnp.float32), ones, ones, **kw)


def test_sizing_table_kernel_matches_ref_program():
    """The whole table program (enumeration, menus, kernel, objective) on
    the Boutique DAG at 2 replica counts, 1,024 states: the kernel path
    against the jnp reference path, the chip smoke run's check."""
    spec = _boutique_dag(replica_counts=(1, 2))
    assert spec.space.size() == 1024
    # the night load: 10 tasks/s at Locust's task weights
    weights = {"index": 1, "setCurrency": 2, "browseProduct": 10,
               "addToCart": 2, "viewCart": 3, "checkout": 1}
    mix = {k: 10.0 * w / sum(weights.values()) for k, w in weights.items()}
    t_kernel = np.asarray(sizing_table_device(spec, mix, use_kernel=True),
                          np.float64)
    t_ref = np.asarray(sizing_table_device(spec, mix, use_kernel=False),
                       np.float64)
    assert t_kernel.shape == (1024,)
    np.testing.assert_allclose(t_kernel, t_ref, rtol=1e-5)
    assert int(np.argmin(t_kernel)) == int(np.argmin(t_ref))


# ---------------------------------------------------------------------------
# Batched evaluator vs numpy ground truth.
# ---------------------------------------------------------------------------


def test_evaluate_sizing_batch_matches_host_model():
    spec = _spec()
    rng = np.random.default_rng(0)
    grid = full_grid(spec.space)
    cand = grid[rng.choice(len(grid), 32, replace=False)]
    res = evaluate_sizing_batch(spec, cand, MIX_BROWSE)
    for i, idx in enumerate(cand):
        host = spec.host_objective(
            spec.space.decode([int(v) for v in idx]), MIX_BROWSE)
        assert res["y"][i] == pytest.approx(host["y"], rel=2e-4)
        assert res["cost"][i] == pytest.approx(host["cost"], rel=1e-5)
        assert res["slo_attainment"][i] == pytest.approx(
            host["slo_attainment"], abs=1e-6)
        np.testing.assert_allclose(res["latency"][i], host["latency"],
                                   rtol=2e-4)


def test_evaluate_sizing_batch_kernel_path_matches_ref_path():
    spec = _spec()
    grid = full_grid(spec.space)[::97]
    a = evaluate_sizing_batch(spec, grid, MIX_BROWSE, use_kernel=True)
    b = evaluate_sizing_batch(spec, grid, MIX_BROWSE, use_kernel=False)
    np.testing.assert_allclose(a["y"], b["y"], rtol=1e-5)


def test_evaluate_sizing_batch_validates_shapes():
    spec = _spec()
    with pytest.raises(ValueError):
        evaluate_sizing_batch(spec, np.zeros((4, 3), np.int32), MIX_BROWSE)
    with pytest.raises(ValueError):
        evaluate_sizing_batch(spec, full_grid(spec.space)[:4],
                              np.zeros(5))


def test_sizing_space_layout_and_round_trip():
    spec = _spec()
    space = spec.space
    assert space.size() == (2 * 3) ** 6
    assert space.names[:4] == ("gw.size", "gw.repl", "auth.size",
                               "auth.repl")
    decoded = space.decode((1, 2, 0, 0, 1, 1, 0, 0, 0, 0, 1, 2))
    sizing = spec.sizing_of(decoded)
    assert sizing["gw"] == (SIZES[1], 3)
    assert sizing["auth"] == (SIZES[0], 1)
    # footprint: gw 4*3, auth 1, catalog 4*2, product 1, pricing 1, inv 4*3
    assert spec.total_cores(decoded) == 12 + 1 + 8 + 1 + 1 + 12


def test_sizing_space_validation():
    with pytest.raises(ValueError):
        _spec(replica_counts=(2, 1))
    with pytest.raises(ValueError):
        _spec(sizes=(ContainerSize("b", 4, 8.0), ContainerSize("a", 1, 2.0)))
    with pytest.raises(ValueError):
        ContainerSize("zero", 0, 1.0)


def test_drifting_mix_schedule_and_peak():
    d = DriftingMix(MIX_BROWSE, MIX_CHECKOUT, change_at=5, ramp=4)
    assert d.at(0) == MIX_BROWSE
    assert d.at(100) == MIX_CHECKOUT
    mid = d.at(6)
    assert MIX_CHECKOUT["browse"] < mid["browse"] < MIX_BROWSE["browse"]
    assert d.peak() == {"browse": 40.0, "checkout": 45.0}


def test_microservice_dag_validation():
    tiers = (ServiceTier("a", 10.0), ServiceTier("b", 10.0))
    cls = (RequestClass("r", "a", {"a": 1.0}, slo_s=1.0),)
    with pytest.raises(ValueError):                 # edge against topo order
        MicroserviceDAG(tiers, (("b", "a"),), cls)
    with pytest.raises(ValueError):                 # unknown tier in edge
        MicroserviceDAG(tiers, (("a", "zz"),), cls)
    with pytest.raises(ValueError):                 # entry not visited
        RequestClass("bad", "x", {"y": 1.0}, slo_s=1.0)


# ---------------------------------------------------------------------------
# The online controller.
# ---------------------------------------------------------------------------


def test_sizing_controller_converges_and_tracks_drift():
    spec = _spec()
    grid = full_grid(spec.space)
    opt1 = float(evaluate_sizing_batch(spec, grid, MIX_BROWSE)["y"].min())
    opt2 = float(evaluate_sizing_batch(spec, grid, MIX_CHECKOUT)["y"].min())
    ctrl = SizingController(
        spec, DriftingMix(MIX_BROWSE, MIX_CHECKOUT, change_at=6),
        steps_per_round=64, n_chains=16, seed=0)
    ds = ctrl.run(14)
    assert all(isinstance(d, SizingDecision) for d in ds)
    pre = ds[5]                                     # settled, pre-change
    post = ds[-1]
    assert pre.y <= 1.10 * opt1
    assert post.y <= 1.10 * opt2
    assert post.slo_attainment == 1.0
    # the move tracked the mix: post-change deployment differs
    assert pre.sizing != post.sizing
    # objective never beats the exhaustive optimum of its round's mix
    assert pre.y >= opt1 - 1e-9 and post.y >= opt2 - 1e-9
    # audit counters are cumulative and monotone
    tms = [d.true_measures for d in ds]
    assert tms == sorted(tms)


def test_sizing_controller_is_deterministic_under_seed():
    runs = []
    for _ in range(2):
        ctrl = SizingController(_spec(), MIX_BROWSE, steps_per_round=16,
                                n_chains=4, seed=3)
        ds = ctrl.run(4)
        runs.append([(d.sizing, d.y) for d in ds])
    assert runs[0] == runs[1]


@pytest.mark.parametrize("masked", [False, True])
def test_sizing_prep_program_matches_the_eager_sequence(masked):
    """The round's prep program draws the round key, the chains'
    keys, the inits and the temperatures bit for bit as the eager
    sequence it replaced: ``fold_in``, ``split``, the inits (uniform, or
    over the valid region), row 0 set to the incumbent, then
    ``anneal_fleet``'s ``split`` and ``split(n_chains)``."""
    import jax

    from repro.core.annealing import valid_indices
    from repro.core.sizing import _sizing_prep_args, _sizing_prep_jit
    from repro.core.state import EncodedSpace

    shape, n, steps = (3, 4, 2, 5), 16, 12
    mask = None
    if masked:
        grid = np.indices(shape)
        mask = (grid[0] + grid[1] + grid[3]) % 3 != 0
    enc = EncodedSpace(shape, (False,) * len(shape), mask)
    incumbent = np.asarray((2, 1, 0, 4), np.int32)
    taus = np.linspace(0.5, 4.0, steps).astype(np.float32)
    base = jax.random.key(3150000211)
    prep = _sizing_prep_jit(shape, n)
    for r in (0, 7, 123456):
        key_r = jax.random.fold_in(base, r)
        k_init, k_run = jax.random.split(key_r)
        if mask is None:
            inits = jax.random.randint(
                k_init, (n, len(shape)), 0, jnp.asarray(shape, jnp.int32),
                dtype=jnp.int32)
        else:
            picks = jax.random.choice(
                k_init, jnp.asarray(np.flatnonzero(mask), jnp.int32), (n,))
            inits = jnp.stack(jnp.unravel_index(picks, shape),
                              axis=-1).astype(jnp.int32)
        inits = inits.at[0].set(jnp.asarray(incumbent, jnp.int32))
        k_chains, k_fleet_init = jax.random.split(k_run)
        chain_keys = jax.random.split(k_chains, n)
        taus_b = jnp.broadcast_to(jnp.asarray(taus, jnp.float32),
                                  (n, steps))

        got = prep(base, _sizing_prep_args(r, incumbent, taus),
                   valid_indices(enc))
        want = (key_r, k_fleet_init, chain_keys, inits, taus_b)
        for g, w in zip(got[:3], want[:3]):
            np.testing.assert_array_equal(jax.random.key_data(g),
                                          jax.random.key_data(w))
        for g, w in zip(got[3:], want[3:]):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        if mask is not None:
            assert mask[tuple(np.asarray(got[3])[1:].T)].all()


def _host_topk(inits, states, table, topk, shape):
    """numpy top-K over the visited states: stable argsort of their table
    estimates (ties by visit position, chain-major), first-distinct
    dedup, -1 rows past the distinct states."""
    nd = inits.shape[1]
    visited = np.concatenate([inits[:, None, :], states],
                             axis=1).reshape(-1, nd)
    flat = np.ravel_multi_index(tuple(visited.T), shape)
    cand: list[int] = []
    for j in np.argsort(table[flat], kind="stable"):
        if int(flat[j]) not in cand:
            cand.append(int(flat[j]))
        if len(cand) == topk:
            break
    out = np.full((topk, nd), -1, np.int64)
    for i, f in enumerate(cand):
        out[i] = np.unravel_index(f, shape)
    return out


@pytest.mark.parametrize("topk", [1, 4])
def test_sizing_select_matches_host_topk(topk):
    """The device top-K select against its numpy oracle, on a float32
    table whose minimum is tied between states visited by different
    chains, so the chain-major tie order decides the pick."""
    shape, C, steps = (4, 3, 5), 4, 6
    rng = np.random.default_rng(11)
    inits = np.stack([rng.integers(0, n, C) for n in shape],
                     axis=1).astype(np.int32)
    states = np.stack([rng.integers(0, n, (C, steps)) for n in shape],
                      axis=2).astype(np.int32)
    table = rng.integers(2, 6, int(np.prod(shape))).astype(np.float32)
    visited = np.concatenate([inits[:, None, :], states], axis=1)
    tied = [np.ravel_multi_index(tuple(visited[c, 2 + c]), shape)
            for c in (3, 1, 2)]
    assert len(set(tied)) == 3
    table[tied] = 1.0                                # three-way tie
    # a sound tie order matters: reversed visit order picks otherwise
    want = _host_topk(inits, states, table, topk, shape)
    rev = _host_topk(inits[::-1], states[::-1], table, topk, shape)
    assert not np.array_equal(want, rev)

    select = _sizing_select_jit(shape, topk)
    iflat = np.ravel_multi_index(tuple(inits.T), shape)
    ys = table[np.ravel_multi_index(tuple(states.reshape(-1, 3).T),
                                    shape)].reshape(C, steps)
    flags = []
    for accepts in (rng.random((C, steps)) < 0.5,
                    np.zeros((C, steps), bool)):
        sel, explored = select(inits, states, table, ys, accepts)
        np.testing.assert_array_equal(np.asarray(sel), want)
        flags.append(bool(explored))
        assert flags[-1] == bool(ControllerMixin.explored_flags(
            ys, accepts, table[iflat]).any())
    assert flags == [True, False]
    # fewer distinct visited states than topk: -1 sentinel rows
    same = np.broadcast_to(inits[:1, None, :], states.shape)
    sel, _ = select(inits[:1], same[:1], table, ys[:1], accepts[:1])
    np.testing.assert_array_equal(
        np.asarray(sel), _host_topk(inits[:1], same[:1], table, topk, shape))


def test_sizing_controller_refuses_large_space_without_source():
    spec = _spec(sizes=(ContainerSize("s", 1, 2.0),
                        ContainerSize("m", 2, 4.0),
                        ContainerSize("l", 4, 8.0)),
                 replica_counts=(1, 2, 3, 4))       # 12^6 = 2.99M states
    with pytest.raises(ValueError, match="SurrogateSource"):
        SizingController(spec, MIX_BROWSE)


def test_sizing_controller_exhaustive_source_matches_batched_table():
    """The scalar one-state-at-a-time seam and the batched whole-grid
    tabulation must produce the same device table (they share the
    math)."""
    spec = _spec(replica_counts=(1, 2))             # 4^6 = 4096 states
    a = SizingController(spec, MIX_BROWSE, seed=0)
    b = SizingController(spec, MIX_BROWSE,
                         objective_source=ExhaustiveSource(), seed=0)
    key = jax.random.key(0)
    ta = np.asarray(a._dtable_for(MIX_BROWSE, key))
    tb = np.asarray(b._dtable_for(MIX_BROWSE, key))
    assert ta.shape == tb.shape == (spec.space.size(),)
    assert tb.dtype == np.float32
    np.testing.assert_allclose(ta, tb, rtol=2e-4)
    assert b.objective_source.true_measures == spec.space.size()


def test_sizing_table_cache_keeps_the_last_mixes():
    """Under a drifting mix the table cache holds at most TABLE_CACHE
    tables: a recurring mix is a hit (no rebuild, moved to the fresh
    end) and a new mix evicts the stalest."""
    spec = _spec(replica_counts=(1, 2))             # 4096 states
    cap = SizingController.TABLE_CACHE
    mixes = [{"browse": 40.0 + i, "checkout": 8.0} for i in range(cap + 4)]
    # rounds 0..cap-1 fill the cache, round cap revisits mix 0, then
    # four new mixes
    order = list(range(cap)) + [0] + list(range(cap, cap + 4))
    ctrl = SizingController(spec, lambda r: mixes[order[r]],
                            steps_per_round=8, n_chains=4, seed=0)
    ctrl.run(cap)
    key0 = ctrl._mix_key(mixes[0])
    t0 = ctrl._dtables[key0]
    assert len(ctrl._dtables) == cap
    builds = ctrl.evaluation_counts()["true_measures"]
    ctrl.round()                                    # mix 0 again: a hit
    assert ctrl._dtables[key0] is t0
    assert list(ctrl._dtables)[-1] == key0
    # a hit tabulates nothing; only the round's one re-measure counts
    assert ctrl.evaluation_counts()["true_measures"] == builds + 1
    ctrl.run(4)
    want = [ctrl._mix_key(mixes[i])
            for i in list(range(5, cap)) + [0] + list(range(cap, cap + 4))]
    assert list(ctrl._dtables) == want
    assert ctrl._dtables[key0] is t0


def test_sizing_controller_refuses_a_recycling_surrogate_source():
    """A recycle store's measurements live on the host and warm-start
    only SurrogateSource.table; the sizing round builds its tables on
    the device, so the controller refuses such a source up front."""
    from repro.core import MeasurementStore

    spec = _spec(replica_counts=(1, 2))
    store = MeasurementStore(len(spec.space.dimensions))
    src = SurrogateSource(n_probe=16, recycle_store=store)
    with pytest.raises(ValueError, match="recycle_store"):
        SizingController(spec, MIX_BROWSE, objective_source=src)


def test_sizing_controller_surrogate_source_runs_with_sparse_probes():
    spec = _spec(replica_counts=(1, 2))             # 4096 states
    grid = full_grid(spec.space)
    opt = float(evaluate_sizing_batch(spec, grid, MIX_BROWSE)["y"].min())
    src = SurrogateSource(n_probe=256, seed=0)
    ctrl = SizingController(spec, MIX_BROWSE, objective_source=src,
                            steps_per_round=48, n_chains=16, seed=0)
    ds = ctrl.run(6)
    # sparse probing: far fewer real evaluations than the grid
    assert src.true_measures <= 256
    assert ds[-1].surrogate_queries >= spec.space.size()
    # interpolation error bounds the gap loosely, but the result must be
    # a sane deployment, not a saturated one
    assert ds[-1].y <= 3.0 * opt
    assert ds[-1].slo_attainment == 1.0


# ---------------------------------------------------------------------------
# The surrogate table on the device against a plain float64 reference.
# ---------------------------------------------------------------------------

#: the probe stream of ``repro.core.surrogate.PROBE_STREAM``, restated: the
#: reference derives its keys without the program
PROBE_STREAM = 0x70726F62
#: relative gap allowed the device table from the float64 interpolation of
#: float64 probe values: the probes are scored in float32 (their relative
#: error here, at loads far from saturation, is a few 1e-7) and weighted
#: and summed in float32 over 64 probes (a few 1e-7 more); 2e-5 leaves
#: room above both and is 50 times below a bfloat16 table's gap
SURROGATE_RTOL = 2e-5


def _plain_probes(seed, r, size, n):
    """The probe draw restated: ``2 n`` uniform draws from the round's
    probe key, the first ``n`` distinct in draw order."""
    import jax

    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(seed), r),
                             PROBE_STREAM)
    cand = np.asarray(jax.random.randint(key, (2 * n,), 0, size,
                                         dtype=jnp.int32))
    _, first = np.unique(cand, return_index=True)
    return cand[np.sort(first)[:n]]


def _plain_table(spec, mix, probes, states, eps=1e-9):
    """Float64 Shepard interpolation (power 2) at flat ``states`` from the
    numpy ground truth at flat ``probes``; an ordinal axis of n values
    spans 1."""
    shape = spec.space.shape
    scale = np.asarray([1.0 / max(n - 1, 1) for n in shape])
    y = np.asarray([spec.host_objective(spec.space.decode(
        np.unravel_index(f, shape)), mix)["y"] for f in probes])
    p = np.stack(np.unravel_index(probes, shape), 1) * scale
    q = np.stack(np.unravel_index(states, shape), 1) * scale
    d2 = ((q[:, None, :] - p[None, :, :]) ** 2).sum(-1)
    k = 1.0 / (d2 + eps)
    return (k @ y) / k.sum(1)


def _surrogate_ctrl(seed=5, n_probe=64, mix=None, **kw):
    spec = _spec(replica_counts=(1, 2))             # 4^6 = 4096 states
    drift = DriftingMix(MIX_BROWSE, MIX_CHECKOUT, change_at=0, ramp=6)
    ctrl = SizingController(
        spec, drift if mix is None else mix,
        objective_source=SurrogateSource(n_probe=n_probe),
        steps_per_round=32, n_chains=8, seed=seed, **kw)
    return spec, ctrl


def test_device_surrogate_table_matches_float64_reference():
    """The controller's device table of a round, built from 64 probes
    drawn from the round's key, against the float64 interpolation of the
    exact model at the reference's own draw, at every one of the 4,096
    states; probes are reproduced exactly."""
    spec, ctrl = _surrogate_ctrl()
    size = spec.space.size()
    for r in range(3):
        ctrl.round()
        mix = ctrl.decisions[-1].mix
        table = np.asarray(ctrl._dtables[ctrl._mix_key(mix)], np.float64)
        probes = _plain_probes(5, r, size, 64)
        assert len(probes) == 64
        want = _plain_table(spec, mix, probes, np.arange(size))
        np.testing.assert_allclose(table, want, rtol=SURROGATE_RTOL)
        # exact at the probes themselves: IDW reproduces a measurement
        np.testing.assert_allclose(table[probes], want[probes],
                                   rtol=SURROGATE_RTOL)


def test_surrogate_committed_sizings_stay_within_the_bound():
    """Each round commits the best visited state by its table; chain 0
    starts at the previous incumbent, so by the float64 reference table
    the committed sizing lies no higher than that incumbent, give or take
    the table's error at both; and the committed y is the exact model's."""
    spec, ctrl = _surrogate_ctrl(seed=11)
    size, shape = spec.space.size(), spec.space.shape
    prev = ctrl.incumbent
    for r in range(6):
        d = ctrl.round()
        probes = _plain_probes(11, r, size, 64)
        at = np.asarray([np.ravel_multi_index(prev, shape),
                         np.ravel_multi_index(ctrl.incumbent, shape)])
        y_prev, y_new = _plain_table(spec, d.mix, probes, at)
        assert y_new <= y_prev * (1 + 2 * SURROGATE_RTOL)
        assert d.y == pytest.approx(spec.host_objective(
            spec.space.decode(ctrl.incumbent), d.mix)["y"], rel=1e-12)
        prev = ctrl.incumbent
    counts = ctrl.evaluation_counts()
    assert counts["true_measures"] == 6 * (64 + 1)   # probes + re-measure
    assert counts["surrogate_queries"] == 6 * size


def test_surrogate_round_uploads_no_table_and_reads_back_only_the_packet(
        monkeypatch):
    """Under the sanitizer: no host table is built (the host source path
    is never called), no device->host transfer beyond the decision packet
    (which ``tolist`` reads, below its accounting) and no recompilation
    after the first round."""
    from repro.analysis import sanitize

    def no_host_table(*a, **kw):
        raise AssertionError("the host table path ran")

    monkeypatch.setattr(SurrogateSource, "table", no_host_table)
    pre_armed = sanitize.current().installed
    san = sanitize.current() if pre_armed else sanitize.install()
    mark = len(san.rounds)
    try:
        spec, ctrl = _surrogate_ctrl(seed=3)
        ctrl.run(4)
        rounds = [r for r in san.rounds[mark:]
                  if r["controller"] == "SizingController"]
        assert len(rounds) == 4
        assert all(r["transfers"] == 0 for r in rounds)
        assert rounds[0]["entries"]["surrogate_refit"]["calls"] == 1
        assert all(d["compiles"] == 0
                   for r in rounds[1:] for d in r["entries"].values())
        assert all(isinstance(t, jax.Array)
                   for t in ctrl._dtables.values())
    finally:
        if not pre_armed:
            sanitize.uninstall()


# ---------------------------------------------------------------------------
# Fleet integration: container tenants on a shared catalog.
# ---------------------------------------------------------------------------


def _small_fleet(cap=40.0, budget=float("inf"), n_tenants=2, **kw):
    tiers = (ServiceTier("fe", base_rate=50.0),
             ServiceTier("api", base_rate=40.0),
             ServiceTier("db", base_rate=30.0))
    dag = MicroserviceDAG(
        tiers, (("fe", "api"), ("api", "db")),
        (RequestClass("req", "fe", {"fe": 1, "api": 1, "db": 1},
                      slo_s=0.4),))
    catalog = ServiceCatalog({"general": EC2_CATALOG["general"]},
                             capacities={"general": cap})
    spec = SizingSpace(
        dag, sizes=SIZES, replica_counts=(1, 2, 3),
        price_per_core_hr=catalog["general"].price_per_core_hr,
        lambda_cost=10.0, slo_penalty=50.0)
    ev = MicroserviceEvaluator(
        spec, {"steady": {"req": 25.0}, "surge": {"req": 60.0}})
    tenants = [TenantSpec(f"svc{i}", {"steady": 1.0}) for i in
               range(n_tenants)]
    fc = FleetController(
        spec.space, catalog, ev, tenants,
        objective=PenalizedObjective(Objective(lambda_cost=10.0),
                                    weight=25.0),
        budget_usd_hr=budget, steps_per_round=16, seed=0,
        config_fn=microservice_config_fn(spec, "general"), **kw)
    return fc, spec, catalog


def test_fleet_microservice_tenants_share_capacity_ledger():
    fc, spec, catalog = _small_fleet(cap=40.0)
    fc.run(4)
    allocs = fc.allocations()
    total = 0
    for name, a in allocs.items():
        cfg = a["config"]
        assert cfg.instance_type == "general"
        # the ledgered footprint is the decoded sizing's core total
        idx = fc.space.decode(tuple(
            int(v) for v in np.unravel_index(
                fc._incumbents[list(fc.tenants).index(
                    next(t for t in fc.tenants if t.name == name))],
                fc.space.shape)))
        assert cfg.total_cores == spec.total_cores(idx)
        total += cfg.total_cores
    assert total <= catalog.capacity("general") + 1e-9
    assert catalog.reserved("general") == pytest.approx(total)
    assert fc.violation_history[-1] == 0.0


def test_fleet_microservice_tight_capacity_forces_arbitration():
    # 3 tenants x 3-core minimum footprint against a 10-core cap: barely
    # feasible, so growth proposals must be deferred or preempted away
    fc, _, _ = _small_fleet(cap=10.0, n_tenants=3)
    ds = fc.run(3)
    actions = {d.action for d in ds}
    assert actions <= {"admit", "hold", "defer", "preempt"}
    assert fc.violation_history[-1] == 0.0
    cores = fc.aggregate_usage()["cores"]["general"]
    assert cores <= 10.0 + 1e-9


def test_microservice_evaluator_requires_decoded_path():
    _, spec, _ = _small_fleet()
    ev = MicroserviceEvaluator(spec, {"steady": {"req": 10.0}})
    with pytest.raises(TypeError, match="measure_decoded"):
        ev.measure(None, "steady", 0)
    m = ev.measure_decoded(
        spec.space.decode((0,) * len(spec.space.shape)), "steady", 0)
    assert m.exec_time_s > 0 and m.cost_usd > 0


# ---------------------------------------------------------------------------
# Tier-2 (nightly) gate: the full bench, including the large-DAG
# surrogate-backed case beyond the 200k tabulation cap.
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_container_sizing_bench_meets_claims(tmp_path):
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmarks import common
    from benchmarks import container_sizing as bench

    old_out = common.OUT_DIR
    common.OUT_DIR = str(tmp_path)
    old_artifact = bench.TOP_LEVEL_ARTIFACT
    bench.TOP_LEVEL_ARTIFACT = str(tmp_path / "BENCH_sizing.json")
    try:
        res = bench.container_sizing(smoke=False)
    finally:
        common.OUT_DIR = old_out
        bench.TOP_LEVEL_ARTIFACT = old_artifact

    assert res["ok"], \
        f"failed checks: {[c for c in res['checks'] if not c['ok']]}"
    import json
    with open(tmp_path / "container_sizing.json") as f:
        data = json.load(f)
    # the acceptance claims, re-asserted from the artifact
    assert data["online"]["mean_y"]["annealed"] \
        < data["online"]["mean_y"]["static_peak"]
    assert data["online"]["mean_usd_per_hr"]["annealed"] \
        < data["online"]["mean_usd_per_hr"]["static_peak"]
    assert data["online"]["mean_slo_attainment"]["annealed"] \
        >= data["online"]["mean_slo_attainment"]["static_peak"] - 1e-9
    assert data["large_space_states"] > 200_000
    assert data["large"]["best_y"] < data["large"]["cold_start_y"]
