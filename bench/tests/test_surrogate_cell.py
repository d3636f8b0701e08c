"""The surrogate cell, cut to a size a test run holds on the CPU, through
the whole harness: a sound run is correct, and a run with a fault planted
in the surrogate's table is not.

The cuts, from ``boutique-1m-surrogate-drift`` as ``BENCHMARK.json`` has
it: 64 probes a round instead of 1,024 (interpret mode interpolates all
1,048,576 states on the CPU), and 6 window rounds of the traffic
instead of up to 20,000 after its 3 warm rounds (every one of them
checked and its table compared).  The space, the chains and the traffic
mix are the cell's own.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np
import pytest

import run

SEED = 2_147_483_659          # above 2**31
N_PROBE = 64
ROUNDS = 6


def run_cut(seed: int = SEED) -> dict:
    spec = run.load_cell("boutique-1m-surrogate-drift")
    spec["config"]["surrogate"]["n_probe"] = N_PROBE
    spec["traffic"]["max_rounds"] = ROUNDS
    return run.run_cell(spec, seed, 60.0, False, time.perf_counter())


def half_the_probes(monkeypatch):
    """Every other probe of the round's draw gets zero weight."""
    import repro.core.surrogate as surrogate

    real = surrogate.draw_probes

    def half(key, size, n):
        flat, weight = real(key, size, n)
        return flat, weight * (jnp.arange(n) % 2 == 0)

    monkeypatch.setattr(surrogate, "draw_probes", half)


def stale_probes(monkeypatch):
    """Each round's table is built from the previous round's probes,
    scored at the new mix."""
    import repro.core.sizing as sizing

    real = sizing.SizingController._dtable_for

    def stale(self, rates, round_key):
        prev = getattr(self, "_stale_key", round_key)
        self._stale_key = round_key
        return real(self, rates, prev)

    monkeypatch.setattr(sizing.SizingController, "_dtable_for", stale)


def altered_entry(monkeypatch):
    """The table's entry at the round's incumbent is halved, in the table
    the controller caches and anneals on."""
    import repro.core.sizing as sizing

    real = sizing.SizingController._dtable_for

    def altered(self, rates, round_key):
        t = real(self, rates, round_key)
        f = int(np.ravel_multi_index(self.incumbent, self._shape))
        t = t.at[f].multiply(0.5)
        self._dtables[self._mix_key(rates)] = t
        return t

    monkeypatch.setattr(sizing.SizingController, "_dtable_for", altered)


def test_sound_cut_run_is_correct():
    res = run_cut()
    assert res["correct"], res["checks"]
    assert res["attempted"] == ROUNDS and res["failed"] == 0


@pytest.mark.parametrize("plant", [half_the_probes, stale_probes,
                                   altered_entry],
                         ids=["half", "stale", "altered"])
def test_a_planted_table_fault_is_not_correct(monkeypatch, plant):
    plant(monkeypatch)
    res = run_cut()
    assert not res["correct"], res["checks"]
