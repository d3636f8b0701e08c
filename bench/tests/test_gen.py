"""The traffic generators: the churn copy against the program's own
generator, the burst option, and the mix schedules."""

from __future__ import annotations

import numpy as np
import pytest

from gen import churn_trace, mix_schedule


JOBS = ("kmeans", "pagerank", "wordcount")


@pytest.mark.parametrize("seed,n,horizon", [
    (0, 64, 3600.0), (7, 256, 7200.0), (3_000_000_017, 32, 1800.0)])
def test_churn_copy_equals_program_generator(seed, n, horizon):
    from repro.workloads.trace import (replay_ticks, synthetic_trace,
                                       trace_fingerprint)

    kw = dict(n_profiles=12, mean_lifetime_s=900.0, min_lifetime_s=60.0,
              lifetime_sigma=1.0, churn=1.0, phase_changes_per_lifetime=0.5)
    ours = churn_trace.churn_trace(JOBS, n, horizon, seed, **kw)
    theirs = synthetic_trace(JOBS, n_tenants=n, horizon_s=horizon,
                             seed=seed, **kw)
    fp, want = churn_trace.fingerprint(ours), trace_fingerprint(theirs)
    for k in fp:
        assert fp[k] == want[k], k
    got_ticks = [(t, [e.sort_key() for e in ev])
                 for t, ev in churn_trace.ticks(ours, 30.0)]
    want_ticks = [(t, [e.sort_key() for e in ev])
                  for t, ev in replay_ticks(theirs, 30.0)]
    assert got_ticks == want_ticks


def test_burst_keeps_the_mean_rate_and_bunches_arrivals():
    burst = {"every_s": 900.0, "on_s": 120.0, "factor": 6.0}
    horizon, n, life = 900.0 * 400, 64, 900.0
    trace = churn_trace.churn_trace(JOBS, n, horizon, 5, churn=1.0,
                                    mean_lifetime_s=life, burst=burst)
    t = np.asarray([e.t for e in trace.events
                    if e.kind == "arrive" and e.t > 0.0])
    rate = n / life
    # Poisson count over the horizon: within 4 standard deviations
    assert abs(len(t) - rate * horizon) < 4.0 * np.sqrt(rate * horizon)
    on = (t % burst["every_s"]) < burst["on_s"]
    share_on = burst["factor"] * burst["on_s"] / (
        burst["factor"] * burst["on_s"] + burst["every_s"] - burst["on_s"])
    assert abs(on.mean() - share_on) < 0.02


def test_burst_rejects_a_bad_shape():
    with pytest.raises(ValueError):
        churn_trace.churn_trace(JOBS, 8, 3600.0, 0, burst={
            "every_s": 100.0, "on_s": 200.0, "factor": 2.0})


def test_diurnal_mixes_never_repeat_and_follow_the_seed():
    traffic = {"shape": "diurnal", "period_rounds": 240, "noise": 0.05,
               "mix_a": {"browse": 45.0, "search": 25.0, "checkout": 6.0},
               "mix_b": {"browse": 14.0, "search": 8.0, "checkout": 30.0},
               "warm_rounds": 3, "max_rounds": 500}
    a = mix_schedule.generate(traffic, 11)
    assert a == mix_schedule.generate(traffic, 11)
    assert a != mix_schedule.generate(traffic, 12)
    assert len(a) == 503
    assert len({tuple(sorted(m.items())) for m in a}) == len(a)


def test_alternating_mixes_show_both_in_the_warm_rounds():
    traffic = {"shape": "alternate", "every_rounds": 100, "noise": 0.0,
               "mix_a": {"browse": 45.0, "search": 25.0, "checkout": 6.0},
               "mix_b": {"browse": 14.0, "search": 8.0, "checkout": 30.0},
               "warm_rounds": 3, "max_rounds": 400}
    mixes = mix_schedule.generate(traffic, 4)
    keys = [tuple(sorted(m.items())) for m in mixes]
    assert len(set(keys[:2])) == 2 and len(set(keys)) == 2
    window = keys[3:]
    changes = [i for i in range(1, len(window)) if window[i] != window[i - 1]]
    assert changes == [100, 200, 300]
