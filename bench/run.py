"""Run one benchmark cell once, on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic are looked up by name:
``BENCHMARK.json`` names the configuration file and the traffic mix of
each cell; the configuration names its builder (``bench/builders/``),
the traffic file its generator (``bench/gen/``), and each per-layer
metric is read by ``bench/metrics/<metric>.py``.  Adding a cell,
configuration, traffic mix or metric is adding files and entries.

The run: set-up (the persistent compile cache, the configuration built
and its traffic generated from ``--seed``, every shape the traffic uses
warmed up), then a closed loop of control rounds for ``--seconds``, one
caller, each round timed from the start of its tick to its decisions on
the host.  Then the reference replays the run and decides a sample of
its rounds; ``correct`` says whether every number compared is within its
limit.  The last line of standard output is the result as JSON; the
numbers compared are also the last lines of standard error.

With ``--trace 1`` the window runs under the JAX profiler and the
program's span recorder, and the result carries the per-layer metrics,
the device's busy and window seconds, and a breakdown.  Without a TPU,
or with fewer chips than the cell asks for, the run exits 2 and prints
no result.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
PROFILE_DIR = os.path.join(ROOT, ".bench_profile")


def process_age_s() -> float:
    """Seconds since this process was created."""
    import psutil

    return time.time() - psutil.Process().create_time()


def load_cell(name: str, bench_json: str | None = None) -> dict:
    """The cell's entry, configuration and traffic, and the per-layer
    metrics that read it."""
    path = bench_json or os.path.join(ROOT, "BENCHMARK.json")
    with open(path) as f:
        b = json.load(f)
    cells = {w["name"]: w for w in b["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in b["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [m for m in b["end_to_end"]
           if name in m.get("workloads", [name])]
    layer = [m for m in b["per_layer"]
             if name in m.get("workloads", [name])]
    return {"cell": cell, "config": cfg, "traffic": traffic,
            "end_to_end": e2e, "per_layer": layer}


def seeds_of(seed: int) -> dict[str, int]:
    """Independent sub-seeds (traffic, controller, sample) from --seed."""
    import numpy as np

    s = np.random.SeedSequence(int(seed)).generate_state(3)
    return {"traffic": int(s[0]), "controller": int(s[1] & 0x7FFFFFFF),
            "sample": int(s[2])}


def enable_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def load_peaks(device_kind: str) -> dict:
    """The chip's published peaks from ``bench/peaks.json``; a device
    kind that is not there is an error."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json")
    return peaks[device_kind]


class Lowered:
    """Counts the programs JAX lowers (each then compiles or is read back
    from the persistent cache)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self) -> None:
        self.n = 0

    def __call__(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.n += 1


#: program spans that close before the device has finished their work
#: (they time the enqueue only); no reading and no attribution uses them
ENQUEUE_ONLY = frozenset({"fleet.anneal", "sizing.anneal", "sizing.refit"})


class Tracer:
    """The JAX profiler and the program's span recorder over the traced
    part of the window, under a ``bench.window`` annotation.  ``spans``
    holds (name, start_s, dur_s, depth) on the host ``perf_counter``
    clock once stopped."""

    def __init__(self) -> None:
        self.on = False
        self.spans: list[tuple] = []

    def start(self) -> float:
        """Start tracing; returns the ``perf_counter`` second at which
        the window annotation opened."""
        import jax
        from jax.profiler import ProfileOptions
        from repro.telemetry import spans as program_spans

        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        self._rec = program_spans.enable(program_spans.SpanRecorder(
            capacity=1 << 20))
        # the recorder's clock against perf_counter, from one span
        a = time.perf_counter()
        with program_spans.span("bench.clock"):
            pass
        b = time.perf_counter()
        self._offset = (a + b) / 2 - self._rec.spans()[-1][2] * 1e-6
        shutil.rmtree(PROFILE_DIR, ignore_errors=True)
        jax.profiler.start_trace(PROFILE_DIR, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()
        self.on = True
        return time.perf_counter()

    def stop(self) -> None:
        import jax
        from repro.telemetry import spans as program_spans

        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        program_spans.disable()
        self.on = False
        self.spans = [(s[0], s[2] * 1e-6 + self._offset, s[3] * 1e-6, s[5])
                      for s in self._rec.spans()
                      if s[0] not in ENQUEUE_ONLY and s[0] != "bench.clock"]


class Window:
    """What the per-layer readers see of the measured window."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             t_setup0: float) -> dict:
    """Set up, measure, check; returns the result object."""
    import jax
    import numpy as np

    for p in (BENCH, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    lowered = Lowered()
    jax.monitoring.register_event_duration_secs_listener(lowered)
    builder = importlib.import_module("builders." + spec["config"]["builder"])
    cell = builder.Cell(spec["config"], spec["traffic"], seeds_of(seed))
    cell.setup()
    dev = jax.devices()[0]
    setup_s = time.perf_counter() - t_setup0

    # a traced run profiles the first ``trace_seconds`` of its window (the
    # traffic file's, else the whole window): a trace of every round of a
    # fast cell is more than the reduction can read in time
    trace_s = float(spec["traffic"].get("trace_seconds", seconds))
    tracer = Tracer() if trace else None
    lat, ticks, n_dec, n_failed, n_raised = [], [], 0, 0, 0
    traced_rounds = 0
    gc.collect()
    lowered0 = lowered.n
    t_win0 = time.perf_counter()
    t_end = t_win0 + seconds
    if tracer is not None:
        t_win0 = tracer.start()
        t_end = t_win0 + seconds
    while time.perf_counter() < t_end:
        if tracer is not None and tracer.on and \
                time.perf_counter() - t_win0 >= trace_s:
            tracer.stop()
            traced_rounds = len(lat)
        t0 = time.perf_counter()
        try:
            out = cell.tick()
        except StopIteration:
            print("bench: the traffic ran out before the window closed",
                  file=sys.stderr)
            break
        except Exception as e:                   # a round that raised
            print(f"bench: round raised {type(e).__name__}: {e}",
                  file=sys.stderr)
            n_raised += cell.decisions_due()
            continue
        t1 = time.perf_counter()
        lat.append(t1 - t0)
        if tracer is not None and tracer.on:
            ticks.append((t0, t1))
        n, f = cell.record(out)
        n_dec += n
        n_failed += f
    t_win1 = time.perf_counter()
    if tracer is not None and tracer.on:
        tracer.stop()
        traced_rounds = len(lat)
    window_lowered = lowered.n - lowered0
    stats = dev.memory_stats() or {}
    result = {"correct": False, "attempted": n_dec + n_raised,
              "failed": n_failed + n_raised, "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": int(
                             stats.get("peak_bytes_in_use", 0))}}
    lat_ms = np.asarray(lat) * 1e3
    if not trace:
        e2e = {"decisions_per_s": (n_dec / (t_win1 - t_win0), "decisions/s"),
               "round_p50_ms": (float(np.percentile(lat_ms, 50)), "ms"),
               "round_p95_ms": (float(np.percentile(lat_ms, 95)), "ms"),
               "setup_s": (setup_s, "s")}
        for m in spec["end_to_end"]:
            v, unit = e2e[m["name"]]
            result["metrics"][m["name"]] = {"value": float(v), "unit": unit}
    else:
        from reduce import reduce_profile

        red = reduce_profile(PROFILE_DIR, t_win0, tracer.spans, ticks)
        w = Window(rounds=traced_rounds, latencies_ms=lat_ms[:traced_rounds],
                   lowered=window_lowered, spans=tracer.spans, trace=red,
                   config=spec["config"], traffic=spec["traffic"],
                   cell=spec["cell"]["name"],
                   peaks=load_peaks(dev.device_kind))
        for m in spec["per_layer"]:
            v = importlib.import_module("metrics." + m["name"]).read(w)
            if v is not None:
                result["metrics"][m["name"]] = {"value": float(v),
                                                "unit": m["unit"]}
        result["device"]["busy_s"] = red.busy_s
        result["device"]["window_s"] = red.window_s
        result["breakdown"] = red.breakdown()
    print(f"bench: {len(lat)} rounds, {n_dec} decisions in "
          f"{t_win1 - t_win0:.3f} s; set-up {setup_s:.3f} s; "
          f"{window_lowered} programs lowered in the window",
          file=sys.stderr)

    limits = spec["config"]["correct"]
    t_check = time.perf_counter()
    try:
        got = cell.check()["program"]
    except Exception as e:                       # the replay broke down
        print(f"bench: the reference replay failed: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        got = {k: float("inf") for k in limits}
    print(f"bench: the reference check took "
          f"{time.perf_counter() - t_check:.3f} s", file=sys.stderr)
    checks = {k: {"value": float(got[k]), "limit": float(lim)}
              for k, lim in limits.items()}
    result["correct"] = bool(len(lat) > 0 and n_raised == 0 and all(
        c["value"] <= c["limit"] for c in checks.values()))
    for k, v in got.items():
        if k not in checks:
            print(f"bench: {k} {v!r} (reported, not compared)",
                  file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    t_setup0 = time.perf_counter() - process_age_s()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, but JAX found platform "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 2
    if len(devices) < spec["cell"]["chips"]:
        print(f"bench: {args.workload} needs {spec['cell']['chips']} "
              f"chips, JAX found {len(devices)}", file=sys.stderr)
        return 2
    enable_compile_cache()
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      t_setup0)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
