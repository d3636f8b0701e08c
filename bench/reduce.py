"""From a profiler trace and the program's spans to per-layer numbers.

``reduce_profile`` reads the ``.xplane.pb`` the JAX profiler wrote,
keeps the device events inside the harness's ``bench.window``
annotation, and puts the program's spans (host ``perf_counter`` seconds)
on the trace's clock by the window annotation's start.  ``Reduced``
holds the device operations and programs (XLA modules) of the window,
the busy time (the union of operation intervals, averaged over the
devices that ran any) and a breakdown: the operations that took most
time, and the device's idle time by what the host was doing meanwhile
(the innermost program span open, else the harness's own tick or the
time between ticks).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
from collections import defaultdict
from typing import Iterable, Sequence

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def op_label(op: str, module: str | None) -> str:
    """A stable name for a device operation: its program's name without
    the fingerprint, and the operation's HLO name without its text
    (``jit_run/sizing_latency.1`` for
    ``%sizing_latency.1 = (f32[...]) custom-call(...)`` in
    ``jit_run(7274124312260366437)``)."""
    name = op.split(" = ", 1)[0].lstrip("%")
    if module is None:
        return name
    return re.sub(r"\(\d+\)$", "", module) + "/" + name


def union_ns(intervals: Iterable[tuple[float, float]]) -> list[tuple]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def timeline(intervals: Sequence[tuple[float, float, str]], base: str
             ) -> tuple[list[float], list[str]]:
    """Change points of the innermost label of properly nested
    (start, end, label) intervals; ``base`` outside all of them."""
    pts: list[tuple[float, str]] = [(float("-inf"), base)]
    stack: list[tuple[float, str]] = []
    for s, e, name in sorted(intervals, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, _ = stack.pop()
            pts.append((end, stack[-1][1] if stack else base))
        stack.append((e, name))
        pts.append((s, name))
    while stack:
        end, _ = stack.pop()
        pts.append((end, stack[-1][1] if stack else base))
    return [p[0] for p in pts], [p[1] for p in pts]


def attribute(gaps: Sequence[tuple[float, float]], times: list[float],
              labels: list[str]) -> dict[str, float]:
    """Length of ``gaps`` under each label of the timeline."""
    out: dict[str, float] = defaultdict(float)
    for a, b in gaps:
        j = bisect.bisect_right(times, a) - 1
        while j < len(times) and times[j] < b:
            lo = max(a, times[j])
            hi = min(b, times[j + 1]) if j + 1 < len(times) else b
            if hi > lo:
                out[labels[j]] += hi - lo
            j += 1
    return dict(out)


class Reduced:
    def __init__(self, events, window: tuple[float, float],
                 host_labels: Sequence[tuple[float, float, str]] = ()):
        """``events``: (plane, line, name, start_ns, dur_ns) tuples;
        ``window``: (start_ns, end_ns) on the same clock;
        ``host_labels``: (start_ns, end_ns, label) of host activity."""
        w0, w1 = window
        self.window_s = (w1 - w0) * 1e-9
        self.ops: list[tuple[str, float, float]] = []
        self.modules: list[tuple[str, float, float]] = []
        per_dev: dict[str, list] = defaultdict(list)
        lines_of: dict[str, set] = defaultdict(set)
        mods_of: dict[str, list] = defaultdict(list)
        for plane, line, *_ in events:
            lines_of[plane].add(line)
        ops = []
        for plane, line, name, s, d in events:
            if not DEVICE_PLANE.match(plane) or s + d <= w0 or s >= w1:
                continue
            if line == "XLA Modules":
                self.modules.append((name, s, d))
                mods_of[plane].append((s, s + d, name))
            elif line == "XLA Ops" or (
                    "XLA Ops" not in lines_of[plane]
                    and line not in ("Steps", "XLA Modules")):
                ops.append((plane, name, s, d))
                per_dev[plane].append((max(s, w0), min(s + d, w1)))
        for mods in mods_of.values():
            mods.sort()
        starts = {p: [m[0] for m in mods] for p, mods in mods_of.items()}
        for plane, name, s, d in ops:
            j = bisect.bisect_right(starts.get(plane, []), s) - 1
            mod = mods_of[plane][j] if j >= 0 else None
            self.ops.append((op_label(
                name, mod[2] if mod and s < mod[1] else None), s, d))
        busy = {p: union_ns(iv) for p, iv in per_dev.items()}
        self.busy_s = (sum(sum(e - s for s, e in iv) for iv in busy.values())
                       / max(len(busy), 1)) * 1e-9
        self.devices = len(busy)
        # idle intervals of the first device, for the breakdown
        first = busy[min(busy)] if busy else []
        gaps, t = [], w0
        for s, e in first:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < w1:
            gaps.append((t, w1))
        self.gaps = gaps
        self._host = host_labels

    def module_time(self, pattern: str) -> tuple[float, int]:
        """(seconds, count) of the programs whose name matches."""
        rx = re.compile(pattern)
        hits = [d for n, _, d in self.modules if rx.search(n)]
        return sum(hits) * 1e-9, len(hits)

    def breakdown(self, k: int = 10) -> dict[str, list]:
        by_op: dict[str, float] = defaultdict(float)
        for name, _, d in self.ops:
            by_op[name] += d * 1e-9
        times, labels = timeline(self._host, "between ticks")
        idle = attribute(self.gaps, times, labels)
        top = lambda m: [[n, v] for n, v in sorted(
            m.items(), key=lambda x: -x[1])[:k]]
        return {"device_ops": top(by_op),
                "idle_gaps": top({n: v * 1e-9 for n, v in idle.items()})}


def profile_events(profile_dir: str) -> list[tuple]:
    """The device events of the newest trace under ``profile_dir``, and
    the host's ``bench.window`` annotation."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"no profiler trace under {profile_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = []
    for plane in pd.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if device or name == "bench.window":
                    out.append((plane.name, line.name, name,
                                float(ev.start_ns), float(ev.duration_ns)))
    return out


def reduce_profile(profile_dir: str, t_win0: float,
                   spans: Sequence[tuple], ticks: Sequence[tuple] = ()
                   ) -> Reduced:
    """Reduce the trace, then delete it.  ``t_win0`` is the host
    ``perf_counter`` second at which the ``bench.window`` annotation
    opened; ``spans`` are (name, start_s, dur_s, depth) and ``ticks``
    (start_s, end_s) on that clock."""
    try:
        events = profile_events(profile_dir)
    finally:
        shutil.rmtree(profile_dir, ignore_errors=True)
    win = [(s, d) for plane, _, name, s, d in events
           if name == "bench.window" and not DEVICE_PLANE.match(plane)]
    if not win:
        raise RuntimeError("the trace has no bench.window annotation")
    w0, wd = win[0]
    to_ns = lambda t: w0 + (t - t_win0) * 1e9
    labels = [(to_ns(s), to_ns(e), "tick outside the program's spans")
              for s, e in ticks]
    labels += [(to_ns(s), to_ns(s + d), name) for name, s, d, _ in spans]
    return Reduced(events, (w0, w0 + wd), labels)
