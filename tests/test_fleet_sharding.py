"""Sharded / bucketed / incremental fleet execution parity: every scaled
execution path must be BIT-IDENTICAL (decisions, incumbents, chain
outputs) to the dense dispatch it replaces."""

import jax
import numpy as np
import pytest

from repro.core import (
    EC2_CATALOG_ADJUSTED,
    FleetController,
    TenantSpec,
    chain_bucket,
    fleet_chains,
    make_ec2_space,
)
from repro.core.annealing import _fleet_nd_jit, chain_rows
from repro.core.costmodel import SimulatedEvaluator
from repro.launch.mesh import make_tenant_mesh

T = 5
ROUNDS = 5


def _controller(seed=4, **kw):
    catalog = EC2_CATALOG_ADJUSTED.with_capacities(
        {f: 12.0 * T for f in EC2_CATALOG_ADJUSTED.names()})
    space = make_ec2_space(catalog, core_counts=tuple(range(4, 68, 8)))
    evaluator = SimulatedEvaluator(catalog)
    jobs = sorted(evaluator.jobs)
    rng = np.random.default_rng(17)
    tenants = [
        TenantSpec(f"t{i}",
                   dict(zip(jobs, rng.dirichlet(np.ones(len(jobs))))),
                   priority=1.0 + 0.5 * (i % 3))
        for i in range(T)]
    return FleetController(space, catalog, evaluator, tenants,
                           budget_usd_hr=1.6 * T, steps_per_round=16,
                           seed=seed, **kw)


def _sig(decisions):
    return [(d.round, d.tenant, d.action, d.config, d.y, d.explored)
            for d in decisions]


# ---------------------------------------------------------------------------
# chain_bucket unit behavior
# ---------------------------------------------------------------------------


def test_chain_bucket_pow2():
    assert [chain_bucket(n) for n in (1, 2, 3, 5, 8, 9, 64, 65)] == \
        [1, 2, 4, 8, 8, 16, 64, 128]


def test_chain_bucket_device_multiple():
    assert chain_bucket(5, multiple=3) == 9     # pow2 8, rounded to 3s
    assert chain_bucket(8, multiple=4) == 8
    with pytest.raises(ValueError):
        chain_bucket(0)


def test_bucketing_reuses_shapes_under_churn():
    """Distinct active-set sizes within one bucket share one padded
    shape — the compiled-shape reuse the sanitizer invariant rests on."""
    assert len({chain_bucket(n) for n in range(33, 65)}) == 1


# ---------------------------------------------------------------------------
# fleet_chains: direct vs shard_map vs padding, bit-identical
# ---------------------------------------------------------------------------


def _chain_inputs(C=6, size=24, steps=10, seed=0):
    rng = np.random.default_rng(seed)
    shape = (size,)
    keys = jax.random.split(jax.random.key(seed), C)
    tables = rng.uniform(0.0, 10.0, (C, size))
    taus = np.full((C, steps), 0.7)
    inits = rng.integers(0, size, (C, 1)).astype(np.int32)
    extra = rng.uniform(0.0, 2.0, (C, size))
    return keys, tables, taus, inits, extra, shape


def test_fleet_chains_matches_direct_kernel():
    keys, tables, taus, inits, extra, shape = _chain_inputs()
    import jax.numpy as jnp
    direct = _fleet_nd_jit(
        keys, jnp.asarray(tables, jnp.float32), None,
        jnp.asarray(taus, jnp.float32), jnp.asarray(inits),
        jnp.asarray(extra, jnp.float32), shape=shape, categorical=(False,),
        dynamic=False, noise_std=0.0, per_chain=True)
    routed = fleet_chains(keys, tables, None, taus, inits, extra,
                          shape=shape, categorical=(False,), mesh=None,
                          bucket=True)
    for a, b in zip(direct, routed):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[:6])


def test_fleet_chains_shard_map_bit_identical():
    keys, tables, taus, inits, extra, shape = _chain_inputs(C=7)
    mesh = make_tenant_mesh(1)
    plain = fleet_chains(keys, tables, None, taus, inits, extra,
                         shape=shape, categorical=(False,), mesh=None,
                         bucket=False)
    sharded = fleet_chains(keys, tables, None, taus, inits, extra,
                           shape=shape, categorical=(False,), mesh=mesh,
                           bucket=True)
    for a, b in zip(plain, sharded):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[:7])


def test_fleet_chains_padding_invariant():
    """Bucket padding must not perturb the real chains: C=5 padded to 8
    returns rows identical to the unpadded run, and the padding rows
    repeat chain 0 (its inputs and its key)."""
    keys, tables, taus, inits, extra, shape = _chain_inputs(C=5)
    assert chain_rows(5) == 8 and chain_rows(5, bucket=False) == 5
    padded = fleet_chains(keys, tables, None, taus, inits, extra,
                          shape=shape, categorical=(False,), bucket=True)
    plain = fleet_chains(keys, tables, None, taus, inits, extra,
                         shape=shape, categorical=(False,), bucket=False)
    for a, b in zip(padded, plain):
        a = np.asarray(a)
        assert a.shape[0] == 8 and np.asarray(b).shape[0] == 5
        np.testing.assert_array_equal(a[:5], np.asarray(b))
        np.testing.assert_array_equal(a[5:], np.repeat(a[:1], 3, axis=0))


def test_active_count_within_a_bucket_lowers_no_program():
    """A new active-tenant count inside a chain bucket already seen must
    reuse the compiled programs: no device op may take its shape from the
    count (on a TPU each such op costs a fresh compile every round)."""
    lowered = []

    def on_event(event, secs, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(event)

    ctl = _controller(incremental=True, settle_rounds=1, detectors=False)
    ctl.run(2)
    counts = []
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        for n in (3, 4):              # both in chain bucket 4
            for t in ctl.tenants[:n]:
                ctl.retune_tenant(t.name, dict(ctl.tenants[-1].blend))
            del lowered[:]
            ctl.round()
            assert ctl.last_annealed == n
            counts.append(len(lowered))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert counts[1] == 0, counts


# ---------------------------------------------------------------------------
# controller-level parity over full replayed rounds
# ---------------------------------------------------------------------------


def test_sharded_controller_decision_identical():
    a = _controller(chain_bucketing=False)
    b = _controller(mesh=make_tenant_mesh(1), chain_bucketing=True)
    for _ in range(ROUNDS):
        da, db = a.round(), b.round()
        assert _sig(da) == _sig(db)
    assert np.array_equal(a._incumbents, b._incumbents)


def test_sharded_parity_survives_churn():
    a = _controller(chain_bucketing=False)
    b = _controller(mesh=make_tenant_mesh(1), chain_bucketing=True)
    for ctl in (a, b):
        ctl.round()
        victim = ctl.tenants[2]
        ctl.remove_tenant(victim.name)
        ctl.add_tenant(TenantSpec("late", dict(victim.blend)))
    for _ in range(3):
        assert _sig(a.round()) == _sig(b.round())


def test_incremental_matches_full_when_all_active():
    """With detectors off and a settle window covering the horizon, the
    incremental path re-anneals everyone every round — and must then be
    decision-identical to the full path (the gating machinery adds no
    math of its own)."""
    a = _controller(incremental=False, detectors=False)
    b = _controller(incremental=True, settle_rounds=ROUNDS + 1,
                    detectors=False)
    for _ in range(ROUNDS):
        da, db = a.round(), b.round()
        assert b.last_annealed == T
        assert _sig(da) == _sig(db)
    assert np.array_equal(a._incumbents, b._incumbents)


def test_incremental_annealed_subset_shrinks():
    """After the founding settle window drains (no churn, detectors
    off), incremental rounds anneal zero chains and the jitted kernel is
    not dispatched at all."""
    ctl = _controller(incremental=True, settle_rounds=2, detectors=False)
    counts = []
    for _ in range(5):
        ctl.round()
        counts.append(ctl.last_annealed)
    assert counts[0] == T
    assert counts[-1] == 0


def test_retune_reactivates_single_tenant():
    ctl = _controller(incremental=True, settle_rounds=1, detectors=False)
    ctl.run(3)
    assert ctl.last_annealed == 0
    other = dict(ctl.tenants[0].blend)
    ctl.retune_tenant("t3", other)
    ctl.round()
    assert ctl.last_annealed == 1         # only the retuned tenant
    ctl.round()
    assert ctl.last_annealed == 0
