"""DeviceMeasurementStore: the numpy store's device-resident twin.

Parity is the contract (ISSUE 10): the jitted, buffer-donating insert
with latest-wins dedup and stalest-first eviction must reproduce
:class:`repro.core.MeasurementStore`'s ``best()`` / ``arrays()``
semantics bit for bit — including recency decay and drift-aged ``best``
— and the donation must never invalidate a view a caller still holds.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ConfigSpace,
    DeviceMeasurementStore,
    Dimension,
    MeasurementStore,
    SpaceEncoding,
)


def _enc():
    space = ConfigSpace((
        Dimension("ord", tuple(range(6))),
        Dimension("cat", ("x", "y", "z"), kind="categorical"),
    ))
    return SpaceEncoding.from_space(space)


def _pair(half_life=None, capacity=8192):
    enc = _enc()
    return (MeasurementStore(enc.ndim, half_life=half_life,
                             capacity=capacity),
            DeviceMeasurementStore(enc, half_life=half_life,
                                   capacity=capacity))


def _assert_snapshot_parity(host, dev):
    hs, hy, ht = host.arrays()
    ds, dy, dt = dev.snapshot()
    np.testing.assert_array_equal(ds, hs)
    # device objectives/timestamps are f32; the host adds in this file
    # use exactly-representable values so equality is exact
    np.testing.assert_array_equal(dy, hy.astype(np.float32))
    np.testing.assert_array_equal(dt, ht.astype(np.float32))
    assert len(dev) == len(host)
    for s in hs:
        assert tuple(int(v) for v in s) in dev


def test_insert_and_snapshot_parity_randomized():
    host, dev = _pair()
    rng = np.random.default_rng(11)
    for _ in range(120):
        s = (int(rng.integers(6)), int(rng.integers(3)))
        y = float(np.float32(rng.normal() * 10.0))
        t = float(rng.integers(0, 50))
        host.add(s, y, t)
        dev.add(s, y, t)
    _assert_snapshot_parity(host, dev)
    assert dev.best() == (host.best()[0], np.float32(host.best()[1]))


def test_latest_wins_dedup_and_refresh_order():
    host, dev = _pair()
    for s, y, t in [((0, 1), 5.0, 0.0), ((3, 2), 7.0, 1.0),
                    ((0, 1), 4.0, 4.0)]:      # re-measure: replace, re-stamp
        host.add(s, y, t)
        dev.add(s, y, t)
    _assert_snapshot_parity(host, dev)
    ds, dy, _ = dev.snapshot()
    assert ds.tolist() == [[3, 2], [0, 1]]     # refresh order
    assert dy.tolist() == [7.0, 4.0]
    assert dev.best() == ((0, 1), 4.0)


def test_capacity_evicts_stalest_parity():
    host, dev = _pair(capacity=2)
    for s, y, t in [((0, 0), 1.0, 0.0), ((1, 0), 2.0, 1.0),
                    ((0, 0), 1.5, 2.0),       # refresh keeps (0,0) newest
                    ((2, 0), 3.0, 3.0),       # evicts (1,0), the stalest
                    ((3, 1), 0.5, 4.0)]:      # evicts (0,0)
        host.add(s, y, t)
        dev.add(s, y, t)
    _assert_snapshot_parity(host, dev)
    ds, _, _ = dev.snapshot()
    assert ds.tolist() == [[2, 0], [3, 1]]
    assert (1, 0) not in dev and (0, 0) not in dev


def test_recency_decay_weights_parity():
    host, dev = _pair(half_life=2.0)
    for s, y, t in [((0, 1), 5.0, 0.0), ((3, 2), 7.0, 1.0),
                    ((5, 0), 6.0, 4.0)]:
        host.add(s, y, t)
        dev.add(s, y, t)
    hw = host.weights(now=4.0)                 # refresh order
    # device weights are slot-ordered with zero padding: compare the
    # live multiset (no eviction here, so slot order == insert order)
    dw = np.asarray(dev.weights_device(4.0))
    assert (dw[len(dev):] == 0.0).all()
    np.testing.assert_allclose(sorted(dw[:len(dev)]), sorted(hw),
                               rtol=1e-6)


@pytest.mark.parametrize("now,max_age", [
    (10.0, 100.0),     # everything fresh
    (10.0, 6.5),       # the early low reading ages out
    (10.0, 0.5),       # everything stale -> unrestricted fallback
])
def test_best_drift_aging_parity(now, max_age):
    host, dev = _pair(half_life=3.0)
    for s, y, t in [((0, 0), 1.0, 0.0),        # lowest, but old
                    ((1, 1), 2.0, 5.0),
                    ((2, 2), 3.0, 9.0)]:
        host.add(s, y, t)
        dev.add(s, y, t)
    hk, hy = host.best(now=now, max_age=max_age)
    dk, dy = dev.best(now=now, max_age=max_age)
    assert dk == hk
    assert dy == np.float32(hy)


def test_load_resyncs_from_numpy_store_and_stays_in_step():
    host, _ = _pair(half_life=2.0)
    rng = np.random.default_rng(3)
    for _ in range(30):                        # out-of-band adds
        host.add((int(rng.integers(6)), int(rng.integers(3))),
                 float(np.float32(rng.normal())), float(rng.integers(20)))
    dev = DeviceMeasurementStore(_enc(), half_life=2.0)
    dev.load(host)
    _assert_snapshot_parity(host, dev)
    # further twin adds pick up exactly where the numpy store stands
    for s, y, t in [((0, 0), -5.0, 21.0), ((5, 2), -6.0, 22.0)]:
        host.add(s, y, t)
        dev.add(s, y, t)
    _assert_snapshot_parity(host, dev)
    assert dev.best(now=22.0, max_age=5.0) == host.best(now=22.0,
                                                        max_age=5.0)


def test_donation_safety_held_views_survive_inserts():
    """The insert donates the store buffers to XLA for in-place update;
    refit views handed out before an insert must stay readable and
    unchanged (a donated buffer is dead — reading it through a stale
    view would be use-after-free)."""
    host, dev = _pair(half_life=4.0)
    rng = np.random.default_rng(5)
    for i in range(8):
        s = (int(rng.integers(6)), int(rng.integers(3)))
        host.add(s, float(i), float(i))
        dev.add(s, float(i), float(i))
    states0, ys0, rec0, live0 = dev.refit_view(now=8.0)
    before = (np.asarray(states0).copy(), np.asarray(ys0).copy(),
              np.asarray(rec0).copy(), np.asarray(live0).copy())
    for i in range(8, 40):                     # donating inserts churn on
        s = (int(rng.integers(6)), int(rng.integers(3)))
        host.add(s, float(i), float(i))
        dev.add(s, float(i), float(i))
        # interleaved reads through every accessor stay coherent
        assert len(dev) == len(host)
        assert dev.best()[0] == host.best()[0]
    np.testing.assert_array_equal(np.asarray(states0), before[0])
    np.testing.assert_array_equal(np.asarray(ys0), before[1])
    np.testing.assert_array_equal(np.asarray(rec0), before[2])
    np.testing.assert_array_equal(np.asarray(live0), before[3])
    _assert_snapshot_parity(host, dev)


def test_refit_view_padding_is_inert():
    """Bucket padding rows carry zero weight and are marked dead: growing
    the bucket must not change what a fused refit would see live."""
    _, dev = _pair()
    for i in range(5):
        dev.add((i, i % 3), float(i + 1), float(i))
    states, ys, rec, live = dev.refit_view(now=5.0)
    n = len(dev)
    assert states.shape[0] >= n and states.shape[0] == ys.shape[0]
    assert (np.asarray(rec[n:]) == 0.0).all()
    assert (np.asarray(live[:n]) == 1.0).all()
    assert (np.asarray(live[n:]) == 0.0).all()
    bigger = dev.refit_view(now=5.0, m_bucket=2 * states.shape[0])
    np.testing.assert_array_equal(np.asarray(bigger[0][:n]),
                                  np.asarray(states[:n]))
    assert (np.asarray(bigger[2][n:]) == 0.0).all()
    assert (np.asarray(bigger[3][n:]) == 0.0).all()


def test_empty_and_validation_errors_match_numpy_semantics():
    host, dev = _pair()
    with pytest.raises(ValueError):
        dev.best()
    with pytest.raises(ValueError):
        host.best()
    with pytest.raises(ValueError):
        dev.add((1,), 0.0, 0.0)                # wrong rank
    with pytest.raises(ValueError):
        DeviceMeasurementStore(_enc(), capacity=0)
    with pytest.raises(ValueError):
        DeviceMeasurementStore(_enc(), half_life=0.0)
    s, y, t = dev.snapshot()
    assert s.shape == (0, 2) and len(y) == 0 and len(t) == 0


def test_y_scale_matches_numpy_predict_formula():
    _, dev = _pair()
    dev.add((0, 0), 2.0, 0.0)
    dev.add((1, 1), 6.0, 1.0)
    assert float(dev.y_scale_device()) == 4.0      # spread
    flat = DeviceMeasurementStore(_enc())
    flat.add((0, 0), -3.0, 0.0)
    flat.add((1, 1), -3.0, 1.0)
    assert float(flat.y_scale_device()) == 3.0     # max(1, |mean|) when flat
