"""The sizing table's operation and byte counts on a hand-worked shape,
and the roofline share they give."""

from __future__ import annotations

import pytest

import run
from metrics import sizing_table_roofline as roof


def tiny_config():
    """Two tiers, one class, one call edge; 2 sizes x replicas (1, 2)."""
    return {"tiers": [{}, {}], "classes": [{}], "edges": [["a", "b"]],
            "sizes": [{}, {}], "replica_counts": [1, 2]}


def test_table_work_by_hand():
    # states (2 * 2) ** 2 = 16; mean replicas 1.5
    # per tier 4 + 12 + 3 * 1.5 = 20.5; per class 2*2 + 1 + 2 + 5 = 12
    # per state 2 * 20.5 + 1 * 12 + 2 * 2 + 3 = 60
    flops, nbytes = roof.table_work(tiny_config())
    assert flops == 16 * 60
    assert nbytes == 16 * 4


def test_boutique_table_work():
    import json
    import os

    with open(os.path.join(run.BENCH, "configs",
                           "boutique-sizing-59k.json")) as f:
        cfg = json.load(f)
    flops, nbytes = roof.table_work(cfg)
    # 3 ** 10 = 59,049 states; per tier 4 + 12 + 3 * 2 (replicas 1-3);
    # per class 2 * 10 + 14 edges + 10 + 5, six classes
    per_state = 10 * 22 + 6 * 49 + 20 + 3
    assert flops == 59_049 * per_state
    assert nbytes == 59_049 * 4


class _Trace:
    def __init__(self, secs, n):
        self._t = (secs, n)

    def module_time(self, pattern):
        assert pattern == roof.TABLE_PROGRAM
        return self._t


class _W:
    def __init__(self, trace):
        self.trace, self.config = trace, tiny_config()
        self.peaks = {"flops_per_s": 1e3, "hbm_bytes_per_s": 1e2}


def test_share_is_the_bound_over_the_kernel_time():
    # least time per table: max(960 / 1e3, 64 / 1e2) = 0.96 s, compute
    # bound; two tables in 4 s -> 48%
    assert roof.read(_W(_Trace(4.0, 2))) == pytest.approx(48.0)
    assert roof.read(_W(_Trace(0.0, 0))) is None
