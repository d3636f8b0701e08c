"""The per-layer readers of the program's sizing-round spans, on
hand-made spans whose answers are worked out by hand."""

from __future__ import annotations

import importlib

import pytest


class _W:
    def __init__(self, spans, rounds):
        self.spans = spans
        self.rounds = rounds


def read(name, w):
    return importlib.import_module("metrics." + name).read(w)


def _round(t):
    """(name, start_s, dur_s, depth) of one sizing round starting at t:
    8 ms dispatch, 3 ms wait, 0.5 ms measure, 0.25 ms commit."""
    return [("sizing.round", t, 0.012, 0),
            ("sizing.dispatch", t, 0.008, 1),
            ("sizing.sync", t + 0.008, 0.003, 1),
            ("sizing.measure", t + 0.011, 0.0005, 1),
            ("sizing.commit", t + 0.0115, 0.00025, 1)]


def test_span_readers_per_round():
    w = _W(_round(0.0) + _round(0.02), rounds=2)
    assert read("dispatch_ms", w) == pytest.approx(8.0)
    assert read("device_wait_ms", w) == pytest.approx(3.0)
    assert read("sizing_host_ms", w) == pytest.approx(0.75)


def test_device_wait_counts_the_fleet_sync():
    w = _W([("fleet.round", 0.0, 0.2, 0), ("fleet.sync", 0.05, 0.004, 1)],
           rounds=1)
    assert read("device_wait_ms", w) == pytest.approx(4.0)
    assert read("dispatch_ms", w) is None
    assert read("sizing_host_ms", w) is None


def test_span_readers_read_nothing_without_their_spans():
    # a program whose round has only the round and measure spans
    old = [("sizing.round", 0.0, 0.012, 0),
           ("sizing.measure", 0.011, 0.0005, 1)]
    for name in ("dispatch_ms", "device_wait_ms", "sizing_host_ms"):
        assert read(name, _W(old, rounds=1)) is None
        assert read(name, _W([], rounds=0)) is None
        assert read(name, _W(_round(0.0), rounds=0)) is None
