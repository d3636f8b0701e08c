"""A run with the timed path broken underneath comes out not correct.

Each test drives the whole harness (set-up, closed loop, reference check)
on a cut cell with one fault of ``faults.py`` planted in the program.
"""

from __future__ import annotations

import pytest

from cells import run_small, small_spec
from faults import KINDS, plant

SEED = 2_147_483_659          # above 2**31


def test_sound_runs_are_correct():
    for cell in ("fleet-1k-steady", "boutique-59k-drift"):
        res = run_small(cell, SEED)
        assert res["correct"], (cell, res["checks"])
        assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("cell", ["fleet-1k-steady", "boutique-59k-drift"])
def test_a_planted_fault_is_not_correct(monkeypatch, cell, kind):
    plant(monkeypatch, small_spec(cell)["config"], kind)
    res = run_small(cell, SEED)
    assert not res["correct"], (cell, kind, res["checks"])
