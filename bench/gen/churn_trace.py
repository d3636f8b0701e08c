"""Tenant churn traces for the fleet cells: arrivals, departures and phase
changes, drawn from one seed.

A copy of the program's ``repro.workloads.trace.synthetic_trace`` (the
benchmark keeps its own, so that a change to the program cannot move the
traffic it is measured with), extended with on/off arrival bursts.  With
``burst`` unset the draws are the program's, in the program's order, so
the two give the same events for the same parameters; a self-check under
``bench/tests`` compares their fingerprints.

* The founding cohort of ``n_tenants`` arrives at t=0.
* Later arrivals are Poisson at ``churn * n_tenants / mean_lifetime_s``
  (Little's law keeps the mean concurrency near ``n_tenants``).  With a
  burst ``{"every_s", "on_s", "factor"}`` the rate is ``factor`` times the
  base rate for the first ``on_s`` seconds of every ``every_s``, and the
  base rate is lowered so that the mean rate stays the same.
* Lifetimes are lognormal with mean ``mean_lifetime_s``, floored.
* Phase changes are Poisson over the lifetime, each to another profile.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Any, Iterator, Mapping, Sequence

import numpy as np

_KIND_ORDER = {"depart": 0, "arrive": 1, "phase": 2}


@dataclasses.dataclass(frozen=True)
class TraceEvent:
    t: float
    kind: str
    tenant: str
    profile: int = -1
    priority: float = 1.0

    def sort_key(self) -> tuple:
        return (self.t, _KIND_ORDER[self.kind], self.tenant)


@dataclasses.dataclass(frozen=True)
class Trace:
    events: tuple[TraceEvent, ...]
    profiles: tuple[Mapping[str, float], ...]
    priorities: tuple[float, ...]
    horizon_s: float
    seed: int

    def concurrency_peak(self) -> int:
        n = peak = 0
        for e in self.events:
            if e.kind == "arrive":
                n += 1
                peak = max(peak, n)
            elif e.kind == "depart":
                n -= 1
        return peak


def churn_trace(
    job_names: Sequence[str],
    n_tenants: int,
    horizon_s: float,
    seed: int,
    n_profiles: int = 8,
    mean_lifetime_s: float = 900.0,
    min_lifetime_s: float = 60.0,
    lifetime_sigma: float = 1.0,
    churn: float = 1.0,
    phase_changes_per_lifetime: float = 0.5,
    priority_classes: Sequence[float] = (1.0, 1.5, 2.0),
    burst: Mapping[str, float] | None = None,
) -> Trace:
    if not job_names or n_tenants < 1 or n_profiles < 2:
        raise ValueError("need job names, n_tenants >= 1, n_profiles >= 2")
    rng = np.random.default_rng(seed)
    profiles = tuple(
        {j: float(w) for j, w in
         zip(job_names, rng.dirichlet(np.ones(len(job_names)) * 2.0))}
        for _ in range(n_profiles))
    mu = float(np.log(mean_lifetime_s)) - 0.5 * lifetime_sigma ** 2
    events: list[TraceEvent] = []
    tid = 0

    def admit(t_arrive: float) -> None:
        nonlocal tid
        name = f"job-{tid:05d}"
        tid += 1
        prof = int(rng.integers(n_profiles))
        prio = float(priority_classes[int(rng.integers(
            len(priority_classes)))])
        events.append(TraceEvent(t_arrive, "arrive", name, prof, prio))
        life = max(float(rng.lognormal(mu, lifetime_sigma)),
                   float(min_lifetime_s))
        t_depart = t_arrive + life
        if t_depart <= horizon_s:
            events.append(TraceEvent(t_depart, "depart", name))
        span = min(t_depart, horizon_s) - t_arrive
        k = int(rng.poisson(phase_changes_per_lifetime))
        if k > 0 and span > 0:
            cur = prof
            for dt in np.sort(rng.uniform(0.0, span, k)):
                nxt = int(rng.integers(n_profiles - 1))
                if nxt >= cur:
                    nxt += 1
                events.append(TraceEvent(
                    float(t_arrive + dt), "phase", name, nxt, prio))
                cur = nxt

    for _ in range(n_tenants):
        admit(0.0)
    if churn > 0:
        rate = churn * n_tenants / float(mean_lifetime_s)
        if burst is None:
            t = 0.0
            while True:
                t += float(rng.exponential(1.0 / rate))
                if t >= horizon_s:
                    break
                admit(t)
        else:
            for t in _burst_arrivals(rng, rate, horizon_s, **burst):
                admit(t)
    events.sort(key=TraceEvent.sort_key)
    return Trace(events=tuple(events), profiles=profiles,
                 priorities=tuple(float(p) for p in priority_classes),
                 horizon_s=float(horizon_s), seed=int(seed))


def _burst_arrivals(rng, mean_rate: float, horizon_s: float, *,
                    every_s: float, on_s: float,
                    factor: float) -> Iterator[float]:
    """Arrival times of a Poisson process whose rate is ``factor`` x base
    in the first ``on_s`` of every ``every_s`` seconds, base elsewhere,
    with the time-averaged rate ``mean_rate``.  Exact, by inverting the
    integrated rate one unit-exponential draw at a time."""
    if not 0.0 < on_s < every_s or factor < 1.0:
        raise ValueError("burst needs 0 < on_s < every_s and factor >= 1")
    base = mean_rate / (1.0 + (factor - 1.0) * on_s / every_s)
    t = 0.0
    while True:
        need = float(rng.exponential(1.0))
        while True:
            phase = t % every_s
            on = phase < on_s
            seg_end = t - phase + (on_s if on else every_s)
            r = base * (factor if on else 1.0)
            if need <= (seg_end - t) * r:
                t += need / r
                break
            need -= (seg_end - t) * r
            t = seg_end
        if t >= horizon_s:
            return
        yield t


def fingerprint(trace: Trace) -> dict[str, Any]:
    """CRCs over the canonical event sequence and the profile pool, in
    the program's ``trace_fingerprint`` format, with the event counts."""
    canon = "\n".join(
        f"{e.kind}:{e.tenant}:{e.t:.6f}:{e.profile}:{e.priority:.3f}"
        for e in trace.events)
    kinds = {k: 0 for k in _KIND_ORDER}
    for e in trace.events:
        kinds[e.kind] += 1
    return {
        "n_events": len(trace.events),
        "arrivals": kinds["arrive"],
        "departures": kinds["depart"],
        "phase_changes": kinds["phase"],
        "crc32": zlib.crc32(canon.encode()),
        "profile_crc32": zlib.crc32(
            "\n".join(
                ",".join(f"{k}={v:.9f}" for k, v in sorted(p.items()))
                for p in trace.profiles).encode()),
    }


def ticks(trace: Trace, control_period_s: float
          ) -> Iterator[tuple[float, list[TraceEvent]]]:
    """Group the events into control ticks: each tick takes what is due
    by one control period after the previous tick, or jumps to the next
    event across a quiet gap (the program's ``replay_ticks``)."""
    events = list(trace.events)
    i, t, n = 0, 0.0, len(events)
    while i < n:
        t_due = t + control_period_s
        j = i
        while j < n and events[j].t <= t_due:
            j += 1
        if j == i:
            t_due = events[i].t
            while j < n and events[j].t <= t_due:
                j += 1
        yield min(t_due, trace.horizon_s), events[i:j]
        t, i = t_due, j
    if t < trace.horizon_s:
        yield trace.horizon_s, []


def generate(traffic: Mapping[str, Any], seed: int, *,
             job_names: Sequence[str], n_tenants: int) -> Trace:
    """The trace a churn traffic file describes, long enough for its
    warm rounds and ``max_rounds`` control periods."""
    rounds = int(traffic["warm_rounds"]) + int(traffic["max_rounds"])
    return churn_trace(
        job_names, n_tenants,
        horizon_s=float(traffic["control_period_s"]) * rounds, seed=seed,
        n_profiles=int(traffic["n_profiles"]),
        mean_lifetime_s=float(traffic["mean_lifetime_s"]),
        min_lifetime_s=float(traffic["min_lifetime_s"]),
        lifetime_sigma=float(traffic["lifetime_sigma"]),
        churn=float(traffic["churn"]),
        phase_changes_per_lifetime=float(
            traffic["phase_changes_per_lifetime"]),
        priority_classes=tuple(traffic["priority_classes"]),
        burst=traffic.get("burst"))
