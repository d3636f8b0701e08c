"""Faults planted in the program under a benchmark run, for the self-checks
(``test_faults.py``, at a cut size) and for the chip at a cell's own
size:

    python3 bench/tests/faults.py --workload <cell> --faults unchanged half \
        --seeds <n> ... --seconds <s>

runs the cell once per fault and seed, in one process, with the fault
planted, and prints one JSON line each with ``correct`` and the numbers
compared.  Exits 1 if any run comes out correct.  The faults:

* ``unchanged``: the round returns its state unchanged;
* ``half``: half of the round's chains are left out (they stay where they
  start);
* ``altered``: an answer is altered where it is produced (a committed
  fleet decision; a sizing table from the kernel's program).

The one-chip cells exchange nothing between chips, so that fault has no
plant here.
"""

from __future__ import annotations

import dataclasses
import os
import sys

import jax.numpy as jnp
import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(BENCH), "src"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

KINDS = ("unchanged", "half", "altered")


def _leave_half_out(states, accepts, inits, n):
    """Rows n//2..n-1 of a chain batch stay at their starting state."""
    st, acc = np.array(states), np.array(accepts)
    h = n // 2
    st[h:n] = np.asarray(inits)[h:n, None, :]
    acc[h:n] = False
    return jnp.asarray(st), jnp.asarray(acc)


def fleet_fault(monkeypatch, kind):
    import repro.core.fleet as fleet

    if kind == "unchanged":
        monkeypatch.setattr(
            fleet.FleetController, "_arbitrate",
            lambda self, proposals, pen: (self._incumbents.copy(),
                                          ["hold"] * len(self.tenants)))
    elif kind == "half":
        real = fleet.fleet_chains

        def half(keys, tables, valid, taus, inits, extra, **kw):
            st, ys, acc = real(keys, tables, valid, taus, inits, extra, **kw)
            st, acc = _leave_half_out(st, acc, inits, len(inits))
            return st, ys, acc

        monkeypatch.setattr(fleet, "fleet_chains", half)
    else:
        real = fleet.FleetController.round

        def altered(self):
            out = real(self)
            d = out[0]
            s = int(np.ravel_multi_index(
                (self.space.dimensions[0].values.index(
                    d.config.instance_type),
                 self.space.dimensions[1].values.index(d.config.n_workers)),
                self._shape))
            other = self._decode_config((s + 1) % self._enc.size())[1]
            out[0] = dataclasses.replace(d, config=other)
            return out

        monkeypatch.setattr(fleet.FleetController, "round", altered)


def sizing_fault(monkeypatch, kind):
    import repro.core.annealing as annealing
    import repro.core.sizing as sizing

    if kind == "unchanged":
        monkeypatch.setattr(
            sizing, "_sizing_select_jit",
            lambda shape, topk: (lambda inits, states, table, ys, acc:
                                 (inits[:1], jnp.asarray(False))))
    elif kind == "half":
        real = annealing.anneal_fleet

        def half(*a, **kw):
            out = dict(real(*a, **kw))
            out["states"], out["accepts"] = _leave_half_out(
                out["states"], out["accepts"], out["inits"],
                out["inits"].shape[0])
            return out

        monkeypatch.setattr(annealing, "anneal_fleet", half)
    else:
        real = sizing.sizing_table_device

        def altered(spec, mix, use_kernel=None):
            t = real(spec, mix, use_kernel)
            return t * (1.0 + 1e-3 * jnp.sin(jnp.arange(t.shape[0],
                                                        dtype=t.dtype)))

        monkeypatch.setattr(sizing, "sizing_table_device", altered)


def plant(monkeypatch, cfg, kind: str) -> None:
    """Plant ``kind`` in the program that the configuration's builder
    drives."""
    {"fleet": fleet_fault, "sizing": sizing_fault}[cfg["builder"]](
        monkeypatch, kind)


def main(argv=None) -> int:
    import argparse
    import json
    import time

    import pytest

    import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--faults", nargs="+", choices=KINDS, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    run.enable_compile_cache()
    caught = True
    for kind in args.faults:
        for seed in args.seeds:
            with pytest.MonkeyPatch.context() as mp:
                plant(mp, spec["config"], kind)
                res = run.run_cell(spec, seed, args.seconds, False,
                                   time.perf_counter())
            print(json.dumps({"fault": kind, "seed": seed,
                              "correct": res["correct"],
                              "checks": res["checks"]}), flush=True)
            caught &= not res["correct"]
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
