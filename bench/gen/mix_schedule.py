"""Request-mix schedules for the sizing cells, drawn from one seed.

``diurnal``: the per-class rates move along ``(1 - w) a + w b`` with
``w = (1 - cos(2 pi (r + phase) / period)) / 2`` and a seeded phase, each
class multiplied by ``exp(noise * N(0, 1))`` drawn afresh every round, so
no two rounds share a mix.

``alternate``: ``a`` and ``b`` take turns every ``every_rounds`` rounds.
The first ``warm_rounds`` rounds show each mix once (set-up builds both
tables); the seed picks which mix leads.
"""

from __future__ import annotations

from typing import Any, Mapping

import numpy as np


def generate(traffic: Mapping[str, Any], seed: int
             ) -> list[dict[str, float]]:
    """The mix of every round the run can reach, warm rounds first."""
    a, b = dict(traffic["mix_a"]), dict(traffic["mix_b"])
    names = sorted(set(a) | set(b))
    n = int(traffic["warm_rounds"]) + int(traffic["max_rounds"])
    rng = np.random.default_rng(seed)
    va = np.asarray([a.get(k, 0.0) for k in names])
    vb = np.asarray([b.get(k, 0.0) for k in names])
    if traffic["shape"] == "diurnal":
        period = float(traffic["period_rounds"])
        phase = rng.uniform(0.0, period)
        w = 0.5 * (1.0 - np.cos(2.0 * np.pi * (np.arange(n) + phase)
                                / period))
        rates = (1.0 - w)[:, None] * va + w[:, None] * vb
        rates *= np.exp(float(traffic["noise"])
                        * rng.standard_normal(rates.shape))
    elif traffic["shape"] == "alternate":
        every = int(traffic["every_rounds"])
        warm = int(traffic["warm_rounds"])
        lead = int(rng.integers(2))
        pick = [(lead + r) % 2 if r < warm
                else (lead + (r - warm) // every) % 2 for r in range(n)]
        rates = np.where(np.asarray(pick)[:, None] == 0, va, vb)
    else:
        raise ValueError(f"unknown mix shape {traffic['shape']!r}")
    return [{k: float(v) for k, v in zip(names, row)} for row in rates]
