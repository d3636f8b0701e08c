"""The batched N-dim engine: mixed ordinal/categorical ConfigSpaces,
validity masking, time-indexed tables, array schedules with reheats,
per-chain (tenant) tables, 1-D statistical equivalence with the original
`anneal_chain`, and the offline planner warm start."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AdaptiveReheat,
    anneal_chain,
    anneal_chain_nd,
    anneal_fleet,
    bimodal_landscape,
    changed_landscape,
    jobs_to_min_vs_tau_fleet,
    offline_plan,
    propose_nd,
    random_valid_states,
    schedule_to_array,
    tabulate,
    tabulate_dynamic,
)
from repro.core.state import ConfigSpace, Dimension


def _mixed_space():
    """3-axis, mixed ordinal/categorical, with a constrained region."""
    return ConfigSpace((
        Dimension("family", ("general", "compute", "memory", "storage"),
                  kind="categorical"),
        Dimension("cores", tuple(range(4, 68, 4))),
        Dimension("remat", ("none", "block", "full"), kind="categorical"),
    ), is_valid=lambda c: not (c["family"] == "storage"
                               and c["cores"] > 32))


def _mixed_table(space):
    fam_pen = {"general": 0.0, "compute": -2.0, "memory": 1.0,
               "storage": 3.0}
    rem_pen = {"none": 0.0, "block": -1.0, "full": 2.0}
    return tabulate(space, lambda c: (10.0 + 0.1 * c["cores"]
                                      + fam_pen[c["family"]]
                                      + rem_pen[c["remat"]]))


def _space_1d(n):
    return ConfigSpace((Dimension("x", tuple(range(n))),))


# ---------------------------------------------------------------------------
# Traced proposal kernel.
# ---------------------------------------------------------------------------


def test_propose_nd_moves_one_axis_within_range():
    space = _mixed_space()
    enc = space.encoded()
    x = jnp.asarray([1, 5, 2], jnp.int32)
    keys = jax.random.split(jax.random.key(0), 300)
    zs = np.asarray(jax.vmap(
        lambda k: propose_nd(k, x, enc.shape, enc.categorical))(keys))
    diffs = (zs != np.asarray(x)).sum(axis=1)
    assert (diffs == 1).all(), "each proposal changes exactly one axis"
    assert (zs >= 0).all() and (zs < np.asarray(enc.shape)).all()
    # categorical axis 0 reaches ALL other values (resample, not +-1)
    moved_fam = zs[zs[:, 0] != 1][:, 0]
    assert set(moved_fam.tolist()) == {0, 2, 3}
    # ordinal axis 1 only steps +-1
    moved_cores = zs[zs[:, 1] != 5][:, 1]
    assert set(moved_cores.tolist()) <= {4, 6}


def test_propose_nd_size_one_axis_stays_put():
    shape, cat = (1, 4), (False, False)
    x = jnp.asarray([0, 2], jnp.int32)
    keys = jax.random.split(jax.random.key(1), 200)
    zs = np.asarray(jax.vmap(lambda k: propose_nd(k, x, shape, cat))(keys))
    assert (zs[:, 0] == 0).all()
    assert (zs[:, 1] >= 0).all() and (zs[:, 1] <= 3).all()


# ---------------------------------------------------------------------------
# Chain semantics: validity masking, dynamic tables, schedules.
# ---------------------------------------------------------------------------


def test_nd_chain_respects_validity_mask():
    space = _mixed_space()
    Y = _mixed_table(space)
    states, ys, accepts = anneal_chain_nd(
        jax.random.key(0), space, Y, 800, tau=4.0)  # hot: wanders widely
    states = np.asarray(states)
    assert all(space.contains(tuple(s)) for s in states)


def test_nd_fleet_1000_chains_one_jitted_call():
    """Acceptance criterion: >= 1000 chains over a >= 3-axis mixed space
    in a single jitted call, converging on the constrained optimum."""
    space = _mixed_space()
    enc = space.encoded()
    Y = _mixed_table(space)
    out = anneal_fleet(jax.random.key(1), space, Y, 300, taus=1.0,
                       n_chains=1000)
    states = np.asarray(out["states"])
    assert states.shape == (1000, 300, 3)
    masked = np.where(enc.valid_mask, Y, np.inf)
    target = np.unravel_index(int(np.argmin(masked)), enc.shape)
    hit = (states == np.asarray(target)).all(-1).any(1)
    assert hit.mean() > 0.5, f"only {hit.mean():.0%} of chains found the min"
    # spot-check validity across the fleet
    sample = states.reshape(-1, 3)[::997]
    assert all(space.contains(tuple(s)) for s in sample)


def test_nd_dynamic_tables_track_landscape_change():
    y1, y2 = bimodal_landscape(), changed_landscape()
    n, change = 6000, 2000
    space = _space_1d(len(y1))
    tables = tabulate_dynamic(
        space, lambda c, t: float((y1 if t < change else y2)[c["x"]]), n,
        max_size=300_000)
    states, _, _ = anneal_chain_nd(
        jax.random.key(2), space, tables, n, tau=1.0,
        init=(int(np.argmin(y1)),))
    post = np.asarray(states)[change:, 0]
    new_target = int(np.argmin(y2))
    assert (post == new_target).any()
    tail = post[len(post) // 2:]
    assert np.mean(np.abs(tail - new_target) <= 3) > 0.2


def test_nd_single_state_space_stays_in_range():
    space = _space_1d(1)
    states, _, _ = anneal_chain_nd(
        jax.random.key(3), space, np.asarray([2.0]), 64, tau=1.0)
    assert np.all(np.asarray(states) == 0)


def test_schedule_to_array_exports_reheats_without_mutation():
    s = AdaptiveReheat(tau_base=1.0, tau_hot=8.0, relax=0.5)
    taus = schedule_to_array(s, 40, reheats=(10,))
    assert taus[9] == 1.0
    assert taus[10] == 8.0
    assert 1.0 < taus[12] < 8.0
    assert abs(taus[35] - 1.0) < 1e-6
    assert s(10) == 1.0, "exporting must not mutate the live schedule"
    assert np.all(schedule_to_array(0.5, 7) == 0.5)


def test_nd_chain_consumes_reheat_schedule():
    """Traced reheat: the exported temperature array drives exploration up
    exactly at the reheat index."""
    y = bimodal_landscape()
    space = _space_1d(len(y))
    taus = schedule_to_array(
        AdaptiveReheat(tau_base=0.05, tau_hot=8.0, relax=0.995),
        3000, reheats=(1500,))
    states, _, accepts = anneal_chain_nd(
        jax.random.key(4), space, y, 3000, tau=taus, init=(10,))
    accepts = np.asarray(accepts)
    # cold pre-reheat chain barely moves; hot post-reheat chain explores
    assert accepts[500:1500].mean() < accepts[1500:2500].mean()


# ---------------------------------------------------------------------------
# Batching: per-chain (tenant) tables, random valid inits.
# ---------------------------------------------------------------------------


def test_fleet_per_chain_tables_are_independent_tenants():
    t1 = np.full(8, 5.0); t1[2] = 1.0
    t2 = np.full(8, 5.0); t2[6] = 1.0
    space = _space_1d(8)
    out = anneal_fleet(jax.random.key(5), space, np.stack([t1, t2]), 300,
                       taus=0.3, n_chains=2, per_chain_tables=True)
    tails = np.asarray(out["states"])[:, -50:, 0]
    assert np.bincount(tails[0]).argmax() == 2
    assert np.bincount(tails[1]).argmax() == 6


@pytest.mark.parametrize("per_chain", [False, True])
def test_fleet_takes_a_static_table_flat(per_chain):
    """A flat (size,) table, or (C, size) per chain, in row-major state
    order walks exactly as the same table shaped like the space."""
    space = _mixed_space()
    table = np.nan_to_num(_mixed_table(space), nan=1e6, posinf=1e6)
    shaped = np.stack([table, table[::-1]]) if per_chain else table
    flat = shaped.reshape((2, -1) if per_chain else (-1,))
    runs = [anneal_fleet(jax.random.key(8), space, t, 40, taus=0.5,
                         n_chains=2, per_chain_tables=per_chain)
            for t in (shaped, flat)]
    for k in ("states", "ys", "accepts", "inits"):
        np.testing.assert_array_equal(np.asarray(runs[0][k]),
                                      np.asarray(runs[1][k]))


def test_fleet_rejects_mismatched_table_shape():
    """A dynamic table whose time axis disagrees with n_steps must raise,
    not silently reshape into interleaved garbage."""
    space = _space_1d(4)
    tables = np.zeros((100, 4))
    with pytest.raises(ValueError, match="table shape"):
        anneal_fleet(jax.random.key(0), space, tables, 50, taus=1.0,
                     n_chains=2)


def test_random_valid_states_uniform_over_valid_region():
    space = _mixed_space()
    enc = space.encoded()
    states = np.asarray(random_valid_states(jax.random.key(6), enc, 500))
    assert states.shape == (500, 3)
    assert all(space.contains(tuple(s)) for s in states)
    # covers the space, not just a corner
    assert len({tuple(s) for s in states}) > 100


# ---------------------------------------------------------------------------
# Equivalence with the 1-D engine (acceptance criterion).
# ---------------------------------------------------------------------------


def test_nd_matches_1d_acceptance_statistics():
    """On a 1-D space the N-dim engine's proposal law reduces to the same
    +-1 reflected walk: occupancy and acceptance statistics must match
    `anneal_chain` within the seed-to-seed noise floor."""
    y = jnp.asarray(bimodal_landscape(), jnp.float32)
    S = y.shape[0]
    space = _space_1d(S)
    n_steps, n_chains, tau = 3000, 256, 1.0
    burn = n_steps // 5

    keys = jax.random.split(jax.random.key(7), n_chains)
    s_old, _, a_old = jax.vmap(
        lambda k: anneal_chain(k, y, n_steps, tau, init=0))(keys)
    out = anneal_fleet(jax.random.key(8), space, np.asarray(y), n_steps,
                       taus=np.full(n_chains, tau, np.float32),
                       inits=np.zeros((n_chains, 1), np.int32))
    s_new = np.asarray(out["states"])[..., 0]

    def occupancy(s):
        c = np.bincount(np.asarray(s)[:, burn:].ravel(),
                        minlength=S).astype(float)
        return c / c.sum()

    tv = 0.5 * np.abs(occupancy(s_old) - occupancy(s_new)).sum()
    assert tv < 0.08, f"occupancy TV distance {tv:.3f}"
    acc_old = float(np.asarray(a_old)[:, burn:].mean())
    acc_new = float(np.asarray(out["accepts"])[:, burn:].mean())
    assert abs(acc_old - acc_new) < 0.02, (acc_old, acc_new)


def test_jobs_to_min_vs_tau_fleet_monotone():
    """P2 (Fig. 4) through the batched engine: jobs-to-minimum decreases
    with temperature, one jitted call for the whole grid."""
    y = bimodal_landscape()
    space = _space_1d(len(y))
    res = jobs_to_min_vs_tau_fleet(jax.random.key(9), space, y,
                                   taus=[0.25, 1.0, 4.0], n_seeds=48,
                                   n_steps=4000, init=(0,))
    m = res["mean_jobs"]
    assert m[0] > m[1] > m[2], m
    assert res["raw"].shape == (3, 48)


# ---------------------------------------------------------------------------
# Offline planner.
# ---------------------------------------------------------------------------


def test_offline_plan_finds_constrained_optimum():
    space = _mixed_space()
    enc = space.encoded()
    Y = _mixed_table(space)
    best_idx, best_y = offline_plan(
        space, lambda c: float(Y[space.encode(c)]),
        n_chains=128, n_steps=200, tau=1.0, seed=0)
    assert space.contains(best_idx)
    masked = np.where(enc.valid_mask, Y, np.inf)
    assert best_y <= 1.02 * float(masked.min())
