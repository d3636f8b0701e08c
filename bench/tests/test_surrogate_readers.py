"""The surrogate cell's per-layer readers on hand-made traces: the
interpolation's operation and byte counts, its roofline share, and the
refit's device time; both read nothing where the program has no
surrogate table program (as on a program without the device path)."""

from __future__ import annotations

import pytest

from metrics import surrogate_interp_roofline as roof
from metrics import surrogate_refit_ms as refit


def tiny_config():
    """Two tiers (4 features), 2 sizes x replicas (1, 2): 16 states;
    8 probes."""
    return {"tiers": [{}, {}], "sizes": [{}, {}], "replica_counts": [1, 2],
            "surrogate": {"n_probe": 8}}


def test_interp_work_by_hand():
    # 16 states x 8 probes x (3 * 4 + 6) operations; 16 x 4 bytes written
    # and 8 probes x (4 features + 1 value) x 4 bytes read
    ops, nbytes = roof.interp_work(tiny_config())
    assert ops == 16 * 8 * 18
    assert nbytes == 16 * 4 + 8 * 5 * 4


def test_boutique_1m_interp_work():
    import json
    import os

    import run

    with open(os.path.join(run.BENCH, "configs",
                           "boutique-sizing-1m.json")) as f:
        cfg = json.load(f)
    ops, nbytes = roof.interp_work(cfg)
    assert ops == 2 ** 20 * 1024 * (3 * 20 + 6)
    assert nbytes == 4 * 2 ** 20 + 4 * 1024 * 21


class _Trace:
    def __init__(self, ops, modules):
        self.ops, self._modules = ops, modules

    def module_time(self, pattern):
        assert pattern in (roof.TABLE_PROGRAM, refit.TABLE_PROGRAM)
        return self._modules


class _W:
    def __init__(self, trace, rounds=4):
        self.trace, self.rounds, self.config = trace, rounds, tiny_config()
        self.peaks = {"flops_per_s": 2304.0, "hbm_bytes_per_s": 1e9}


def test_roofline_is_the_bound_over_the_kernel_time():
    # least time per table: max(2304 / 2304, 224 / 1e9) = 1 s, compute
    # bound; two tables whose kernels took 1.5 + 2.5 s -> 50%
    ops = [("jit_surrogate_table/fused_interp.1", 0.0, 1.5e9),
           ("jit_surrogate_table/fused_interp.1", 9.0, 2.5e9),
           ("jit_select/while.3", 5.0, 7e9)]
    w = _W(_Trace(ops, (6.0, 2)))
    assert roof.read(w) == pytest.approx(50.0)
    # the refit: 6 s of table programs over 4 rounds
    assert refit.read(w) == pytest.approx(1500.0)


def test_readers_read_nothing_without_the_table_program():
    w = _W(_Trace([("jit_run/sizing_latency.1", 0.0, 1e6)], (0.0, 0)))
    assert roof.read(w) is None
    assert refit.read(w) is None
