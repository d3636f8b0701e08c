"""The control of ``correct`` at a test size: the reference one precision
lower in the program's place fails a limit, while the program passes
every limit.  ``bench/control.py`` makes the same readings on the chip at
the cells' own sizes."""

from __future__ import annotations

import pytest

import control
from cells import small_spec


@pytest.mark.parametrize("cell", ["fleet-1k-burst", "boutique-59k-steady"])
def test_control_fails_and_program_passes(cell):
    out = control.control_run(small_spec(cell), 2_147_483_701, 60.0)
    assert out["program_correct"], out
    assert not out["control_correct"], out
