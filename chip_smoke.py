#!/usr/bin/env python3
"""Run the online controllers once on a TPU, at the repo's largest sizes.

    python chip_smoke.py               # one chip: fleet, sizing, rich sizing,
                                       # surrogate, kernel-vs-reference parity
    python chip_smoke.py --four-chips  # four chips: the 1,024-tenant replay
                                       # sharded over a "tenants" mesh against
                                       # direct dispatch, and nothing else

Every phase is built from the builders the benches use, with its data
made from fixed seeds, so a later benchmark cell runs the same programs.  Each phase prints its first-round time (compilation included)
apart from its steady rounds, then its checks.  The last line of standard
output is ``{"ok": true, "device": {...}}`` as JAX reports the device.

The script refuses to run anywhere but a TPU: it exits non-zero, naming
the platform JAX found, before it imports any of the repo.  It runs in one
process and starts no other, since a chip belongs to one process.  It
writes nothing but the persistent compilation cache
(:mod:`repro.compile_cache`).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: Trace-fleet size (the bench's largest replay) and rounds replayed.
FLEET_T = 1024
FLEET_ROUNDS = 40
FLEET_HORIZON_S = 3600.0
#: The device store's capacity bucket and a refit's query block.
STORE_CAP = 8192
PARITY_Q = 1024
#: tests/test_kernels.py's float32 kernel-vs-reference tolerance.
KERNEL_TOL = dict(atol=2e-5, rtol=1e-4)


class Checks:
    """Prints each check as it is made and remembers the failures."""

    def __init__(self) -> None:
        self.failed: list[str] = []

    def __call__(self, phase: str, what: str, ok: bool) -> None:
        print(f"  {'ok  ' if ok else 'FAIL'} {phase}: {what}", flush=True)
        if not ok:
            self.failed.append(f"{phase}: {what}")


class Lowered:
    """Counts the programs JAX lowers (each is then compiled or read back
    from the persistent cache), so a phase shows whether its steady rounds
    build anything new."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self) -> None:
        self.n = 0

    def __call__(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.n += 1


LOWERED = Lowered()


def _times(label: str, walls: list[float], lowered: list[int]) -> str:
    steady = walls[1:]
    line = (f"{label}: first round {walls[0]:.6f} s (compile included, "
            f"{lowered[0]} programs lowered)")
    if steady:
        line += (f"; {len(steady)} steady rounds median "
                 f"{statistics.median(steady):.6f} s, max {max(steady):.6f} s"
                 f", {sum(lowered[1:])} programs lowered")
    return line


def _timed(step, n: int) -> tuple[list[float], list[int]]:
    walls, lowered = [], []
    for _ in range(n):
        n0, t0 = LOWERED.n, time.perf_counter()
        step()
        walls.append(time.perf_counter() - t0)
        lowered.append(LOWERED.n - n0)
    return walls, lowered


def _replay(ctl, rounds: int) -> tuple[dict, list[int]]:
    """``ctl.replay`` with the programs lowered in each fleet round, read
    at the controllers' round boundary (``instrumentation.ROUND_HOOKS``)."""
    from repro.core.instrumentation import ROUND_HOOKS

    marks = [LOWERED.n]

    def mark(name: str, _owner) -> None:
        if name == "FleetController":
            marks.append(LOWERED.n)

    ROUND_HOOKS.append(mark)
    try:
        summary = ctl.replay(max_rounds=rounds)
    finally:
        ROUND_HOOKS.remove(mark)
    return summary, [b - a for a, b in zip(marks, marks[1:])]


def _has_kernel(compiled_text: str) -> bool:
    """A Pallas kernel compiled for the TPU appears as a custom call; in
    interpret mode it would be an emulating while loop instead."""
    return "tpu_custom_call" in compiled_text


# ---------------------------------------------------------------------------
# Phases.
# ---------------------------------------------------------------------------


def phase_fleet(check: Checks, T: int = FLEET_T,
                rounds: int = FLEET_ROUNDS) -> None:
    """The trace-driven fleet: incremental, bucketed, direct dispatch."""
    from benchmarks.trace_fleet import _controller

    ctl = _controller(T, FLEET_HORIZON_S, seed=T, keep_decision_log=False)
    summary, lowered = _replay(ctl, rounds)
    print(f"[fleet] T={T}: " + _times(
        "fleet.round", [r["wall_s"] for r in ctl.rounds], lowered),
        flush=True)
    print(f"[fleet] peak tenants {summary['peak_tenants']}, annealed "
          f"fraction {summary['annealed_fraction']:.4f}, SLO attainment "
          f"{summary['slo_attainment']:.4f}", flush=True)
    check("fleet", f"{len(ctl.rounds)} rounds replayed",
          len(ctl.rounds) == rounds)
    check("fleet", "every round commits one decision per live tenant",
          all(r["n_tenants"] > 0
              and sum(r["actions"].values()) == r["n_tenants"]
              for r in ctl.rounds))
    ctl.fleet._ledger_crosscheck()           # raises on any ledger drift
    check("fleet", "incremental ledger == from-scratch rebuild", True)
    tail = ctl.rounds[-max(len(ctl.rounds) // 4, 1):]
    check("fleet", f"zero aggregate violations in the final "
                   f"{len(tail)} rounds",
          sum(r["violation"] for r in tail) == 0.0)


def phase_sizing(check: Checks, rounds: int = 8) -> None:
    """Container sizing on the 65,536-state small space: the Pallas
    Erlang-C kernel builds every table."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.container_sizing import MIX_DAY, MIX_EVENING, small_spec
    from repro.core import SizingController
    from repro.core.sizing import sizing_table_device
    from repro.workloads.microservice import DriftingMix

    spec = small_spec()
    n = spec.space.size()
    ctrl = SizingController(
        spec, DriftingMix(MIX_DAY, MIX_EVENING, change_at=rounds // 2),
        steps_per_round=64, n_chains=16, seed=0)
    print(f"[sizing] {n:,} states: "
          + _times("sizing.round", *_timed(lambda: ctrl.run(1), rounds)),
          flush=True)
    ds = ctrl.decisions
    check("sizing", f"{len(ds)} rounds, every objective finite and "
                    f"attainment in [0, 1]",
          len(ds) == rounds and all(
              np.isfinite(d.y) and 0.0 <= d.slo_attainment <= 1.0
              for d in ds))

    rates = jnp.asarray(spec.dag.rates_array(MIX_DAY), jnp.float32)
    text = spec._table_jit.lower(rates, use_kernel=True).compile().as_text()
    check("sizing", "table program compiles to tpu_custom_call",
          _has_kernel(text))
    check("sizing", f"default path is the kernel (backend "
                    f"{jax.default_backend()})",
          jax.default_backend() == "tpu")

    t_kernel = np.asarray(sizing_table_device(spec, MIX_DAY), np.float64)
    t_ref = np.asarray(sizing_table_device(spec, MIX_DAY, use_kernel=False),
                       np.float64)
    rel = float(np.max(np.abs(t_kernel - t_ref)
                       / np.maximum(np.abs(t_ref), 1e-30)))
    check("sizing", f"kernel table vs jnp reference: max relative "
                    f"difference {rel:.3e} <= 1e-5", rel <= 1e-5)
    best = int(np.argmin(t_kernel))
    check("sizing", "kernel and reference tables share the argmin",
          best == int(np.argmin(t_ref)))
    # the numpy host model is the ground truth the controller measures
    rng = np.random.default_rng(0)
    idx = np.concatenate([[best], rng.choice(n, 63, replace=False)])
    host = np.asarray([spec.host_objective(spec.space.decode(
        np.unravel_index(i, spec.space.shape)), MIX_DAY)["y"] for i in idx])
    host_rel = float(np.max(np.abs(t_kernel[idx] - host) / np.abs(host)))
    check("sizing", f"kernel table vs numpy host model on 64 states: max "
                    f"relative difference {host_rel:.3e} <= 1e-4",
          host_rel <= 1e-4)


def phase_rich_sizing(check: Checks, spec=None, rounds: int = 4) -> None:
    """The 1,679,616-state rich menu, beyond the tabulation cap: tables
    come from probes interpolated by the fused refit kernel."""
    from benchmarks.container_sizing import MIX_DAY, large_spec
    from repro.core import SizingController, SurrogateSource

    spec = large_spec() if spec is None else spec
    n = spec.space.size()
    src = SurrogateSource(n_probe=1024, seed=3)
    ctrl = SizingController(spec, MIX_DAY, objective_source=src,
                            steps_per_round=64, n_chains=16, seed=3)
    y_cold = float(spec.host_objective(
        spec.space.decode(ctrl.incumbent), MIX_DAY)["y"])
    print(f"[rich sizing] {n:,} states: "
          + _times("sizing.round", *_timed(lambda: ctrl.run(1), rounds)),
          flush=True)
    _, y_best = ctrl.best_sizing()
    check("rich sizing", f"improves the cold-start deployment "
                         f"({y_cold:.6f} -> {y_best:.6f})", y_best < y_cold)
    check("rich sizing", f"{src.true_measures} real measures, under 1% of "
                         f"the space", src.true_measures < 0.01 * n)


def phase_surrogate(check: Checks, rounds: int = 6,
                    store_cap: int = STORE_CAP, q: int = PARITY_Q) -> None:
    """Surrogate-driven annealing on the 1,179,648-state TPU space, then
    the refit kernels against their jnp references at the store's full
    capacity bucket."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.surrogate_scale import scale_problem
    from repro.core import SpaceEncoding, SurrogateAnnealer
    from repro.core.surrogate import _interp_jit
    from repro.kernels import ref
    from repro.kernels.surrogate_distance import pairwise_sqdist

    space, fn = scale_problem()
    sa = SurrogateAnnealer(space, fn, half_width=6, n_chains=16,
                           steps_per_round=64, measures_per_round=8,
                           kappa=1.0, seed=0)
    print(f"[surrogate] {space.size():,} states: "
          + _times("surrogate.round", *_timed(sa.round, rounds)), flush=True)
    y_first = sa.rounds[0].measured[0][1]
    _, y_best = sa.best()
    check("surrogate", f"improves on the first measured state "
                       f"({y_first:.6f} -> {y_best:.6f}) with "
                       f"{sa.true_measures} real measures",
          y_best < y_first and sa.true_measures < 1000)

    # same distribution as tests/test_kernels.py, at real widths
    F = SpaceEncoding.from_space(space).feature_dim
    rng = np.random.default_rng(F)
    xq = jnp.asarray(rng.normal(size=(q, F)), jnp.float32)
    xm = jnp.asarray(rng.normal(size=(store_cap, F)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(store_cap,)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(store_cap,)), jnp.float32)
    m = sa.model
    for kind in ("idw", "rbf"):
        refit = _interp_jit(kind)
        args = (xq, xm, y, w, m.length_scale, m.idw_power, m.eps)
        text = refit.lower(*args).compile().as_text()
        check("surrogate", f"{kind} refit program compiles to "
                           f"tpu_custom_call", _has_kernel(text))
        mean, dmin = refit(*args)
        want_mean, want_dmin = ref.fused_interp_ref(
            xq, xm, y, w, kind=kind, length_scale=m.length_scale,
            idw_power=m.idw_power, eps=m.eps)
        for name, got, want in (("mean", mean, want_mean),
                                ("dmin", dmin, want_dmin)):
            got, want = np.asarray(got), np.asarray(want)
            ok = np.allclose(got, want, **KERNEL_TOL)
            err = float(np.max(np.abs(got - want)))
            check("surrogate", f"fused_interp {kind} {name} vs reference "
                               f"at Q={q}, M={store_cap}: max abs "
                               f"difference {err:.3e}", ok)
    d2 = np.asarray(jax.jit(pairwise_sqdist)(xq, xm[:q]))
    d2_ref = np.asarray(ref.pairwise_sqdist_ref(xq, xm[:q]))
    check("surrogate", f"pairwise_sqdist vs reference at Q=M={q}: max abs "
                       f"difference {float(np.max(np.abs(d2 - d2_ref))):.3e}",
          np.allclose(d2, d2_ref, **KERNEL_TOL))


def phase_four_chips(check: Checks, T: int = FLEET_T,
                     rounds: int = FLEET_ROUNDS) -> None:
    """The fleet's chain dispatch sharded over a 4-device "tenants" mesh
    against direct dispatch: same trace, same seeds."""
    import jax
    import numpy as np

    from benchmarks.trace_fleet import CORES, _controller, _decision_sig
    from repro.core import EC2_CATALOG_ADJUSTED, fleet_chains, make_ec2_space
    from repro.launch.mesh import make_tenant_mesh

    n_dev = 4
    mesh = make_tenant_mesh(n_dev)
    sigs = {}
    for name, kw in (("sharded", {"mesh": mesh}), ("direct", {})):
        ctl = _controller(T, FLEET_HORIZON_S, seed=7, keep_decision_log=True,
                          **kw)
        _, lowered = _replay(ctl, rounds)
        sigs[name] = _decision_sig(ctl)
        print(f"[four chips] {name}, T={T}: " + _times(
            "fleet.round", [r["wall_s"] for r in ctl.rounds], lowered),
            flush=True)
    check("four chips", f"sharded and direct decision logs identical "
                        f"({len(sigs['sharded'])} decisions)",
          bool(sigs["sharded"]) and sigs["sharded"] == sigs["direct"])

    # one chain block of the same shape, to look at where the rows live
    space = make_ec2_space(EC2_CATALOG_ADJUSTED, core_counts=CORES)
    enc = space.encoded()
    rng = np.random.default_rng(7)
    size = space.size()
    args = (jax.random.split(jax.random.key(7), T),
            rng.uniform(0.0, 10.0, (T, size)), None,
            np.full((T, 32), 0.7),
            rng.integers(0, enc.shape, (T, enc.ndim)).astype(np.int32),
            rng.uniform(0.0, 1.0, (T, size)))
    kw = dict(shape=enc.shape, categorical=enc.categorical)
    sharded = fleet_chains(*args, mesh=mesh, **kw)
    direct = fleet_chains(*args, **kw)
    check("four chips", "sharded chain outputs bit-identical to direct",
          all(np.array_equal(np.asarray(a), np.asarray(b))
              for a, b in zip(sharded, direct)))
    for out in sharded:
        shards = out.addressable_shards
        devs = {s.device for s in shards}
        rows = sorted(s.data.shape[0] for s in shards)
        check("four chips", f"{out.shape} output: rows {rows} over "
                            f"{len(devs)} devices",
              len(devs) == n_dev and rows == [T // n_dev] * n_dev
              and devs == set(mesh.devices.flat))


# ---------------------------------------------------------------------------
# Entry point.
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-vs-direct fleet replay on "
                         "four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache = {"hits": 0, "writes": 0}

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["writes"] += 1

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(LOWERED)
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache_dir}", flush=True)

    check = Checks()
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(check)
    else:
        phase_fleet(check)
        phase_sizing(check)
        phase_rich_sizing(check)
        phase_surrogate(check)
    check("process", "repro.launch.dryrun (512 host devices) never "
                     "imported", "repro.launch.dryrun" not in sys.modules)
    print(f"total {time.perf_counter() - t0:.6f} s; compile cache: "
          f"{cache['hits']} hits, {cache['writes']} writes", flush=True)
    if check.failed:
        print("chip_smoke: failed checks:\n  " + "\n  ".join(check.failed),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
