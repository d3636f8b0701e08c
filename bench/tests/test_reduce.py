"""The reduction from a profiler trace to per-layer numbers, and the
per-layer readers, on small hand-made traces whose answers are worked out
by hand."""

from __future__ import annotations

import importlib

import pytest

import reduce as R
import run

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def events():
    """A 100 us window on one device: modules and ops at known places."""
    return [
        (HOST, "python", "bench.window", 1_000.0, 100_000.0),
        (DEV, "XLA Modules", "jit__fleet_nd_jit(12)", 10_000.0, 20_000.0),
        (DEV, "XLA Ops", "fusion.1", 10_000.0, 15_000.0),
        (DEV, "XLA Ops", "fusion.2", 20_000.0, 10_000.0),   # overlaps .1
        (DEV, "XLA Modules", "jit_run(3)", 60_000.0, 10_000.0),
        (DEV, "XLA Ops", "sizing_latency", 60_000.0, 10_000.0),
        (DEV, "XLA Ops", "before.window", 0.0, 500.0),       # outside
        (DEV, "Steps", "0", 10_000.0, 80_000.0),              # not an op
    ]


def test_union_merges_overlaps():
    assert R.union_ns([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_busy_idle_and_module_time():
    red = R.Reduced(events(), (1_000.0, 101_000.0))
    assert red.window_s == pytest.approx(100e-6)
    # busy: [10, 30) and [60, 70) us
    assert red.busy_s == pytest.approx(30e-6)
    assert red.module_time(r"fleet_nd_jit") == (pytest.approx(20e-6), 1)
    assert red.module_time(r"^jit_run\b") == (pytest.approx(10e-6), 1)
    assert red.module_time(r"nothing") == (0, 0)
    assert red.gaps == [(1_000.0, 10_000.0), (30_000.0, 60_000.0),
                        (70_000.0, 101_000.0)]


def test_breakdown_attributes_gaps_to_the_innermost_host_label():
    labels = [(0.0, 50_000.0, "tick"), (30_000.0, 40_000.0, "fleet.refit")]
    red = R.Reduced(events(), (1_000.0, 101_000.0), labels)
    b = red.breakdown()
    ops = dict(b["device_ops"])
    assert ops == {"jit__fleet_nd_jit/fusion.1": pytest.approx(15e-6),
                   "jit__fleet_nd_jit/fusion.2": pytest.approx(10e-6),
                   "jit_run/sizing_latency": pytest.approx(10e-6)}
    idle = dict(b["idle_gaps"])
    # [1, 10) tick; [30, 40) refit; [40, 50) tick; [50, 60) and [70, 101)
    # between ticks
    assert idle == {"tick": pytest.approx(19e-6),
                    "fleet.refit": pytest.approx(10e-6),
                    "between ticks": pytest.approx(41e-6)}


def test_ops_fall_back_to_other_lines_without_an_xla_ops_line():
    ev = [(DEV, "TensorFlow Ops", "op", 0.0, 10.0),
          (DEV, "XLA Modules", "m", 0.0, 10.0)]
    red = R.Reduced(ev, (0.0, 20.0))
    assert red.busy_s == pytest.approx(10e-9)
    assert [o[0] for o in red.ops] == ["m/op"]


def test_op_label():
    op = ("%sizing_latency.1 = (f32[196608,128]{1,0:T(8,128)}) "
          "custom-call(f32[196608,128]{1,0:T(8,128)} %broadcast.445)")
    assert R.op_label(op, "jit_run(7274124312260366437)") == \
        "jit_run/sizing_latency.1"
    assert R.op_label(op, None) == "sizing_latency.1"


def test_recorded_trace():
    """0.3 s of a traced fleet window on the chip: the busy time equals a
    brute-force union of its operations, and the chain engine's programs
    are found by name."""
    import json
    import os

    with open(os.path.join(os.path.dirname(__file__), "data",
                           "fleet_trace_sample.json")) as f:
        ev = [tuple(e) for e in json.load(f)["events"]]
    w = (0.0, max(s + d for *_, s, d in ev))
    red = R.Reduced(ev, w)
    ops = sorted((s, s + d) for _, line, _, s, d in ev if line == "XLA Ops")
    busy, end = 0.0, float("-inf")
    for s, e in ops:                       # sweep, no merging helper
        if e > end:
            busy += e - max(s, end)
            end = e
    assert red.busy_s == pytest.approx(busy * 1e-9)
    assert 0 < red.busy_s < red.window_s
    mods = [d for _, line, n, _, d in ev
            if line == "XLA Modules" and "fleet_nd_jit" in n]
    assert red.module_time(r"fleet_nd_jit") == (
        pytest.approx(sum(mods) * 1e-9), len(mods))
    top = red.breakdown()["device_ops"][0][0]
    assert top.startswith("jit__fleet_nd_jit/while")


class _W:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def _window(red, **kw):
    base = dict(rounds=10, lowered=0, spans=[], trace=red, config={},
                peaks=run.load_peaks("TPU v5 lite"))
    base.update(kw)
    return _W(**base)


def test_readers():
    red = R.Reduced(events(), (1_000.0, 101_000.0))
    read = lambda name, w: importlib.import_module("metrics." + name).read(w)
    w = _window(red, spans=[("fleet.refit", 0.0, 0.002, 1),
                            ("fleet.anneal", 0.0, 5.0, 1),
                            ("fleet.measure", 0.0, 0.003, 1)])
    assert read("anneal_device_ms", w) == pytest.approx(20e-3 / 10)
    assert read("device_idle_share", w) == pytest.approx(70.0)
    # fleet.anneal closes before the device is done: never read
    assert read("fleet_host_ms", w) == pytest.approx(5e-3 * 1e3 / 10)
    assert read("programs_lowered", w) == 0.0
    assert read("fleet_host_ms", _window(red)) is None
    empty = R.Reduced([(HOST, "python", "bench.window", 0.0, 10.0)],
                      (0.0, 10.0))
    assert read("anneal_device_ms", _window(empty)) is None
    assert read("sizing_table_roofline", _window(empty)) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit):
        run.load_peaks("TPU v9 imaginary")
