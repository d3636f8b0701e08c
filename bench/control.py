"""The control of ``correct``: the reference one precision lower in the
program's place must come out as not correct.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> ...

For each seed, in one process: set the cell up, run its closed loop for
``--seconds`` as a benchmark run does, then compare both the program and
the lower-precision reference (``"low"``: float32 where the configuration
states float64, bfloat16 where it states float32) with the reference.
Prints one JSON line per seed with both sets of numbers beside the
configuration's limits; the program's are the lower readings of the
limits, the control's the upper.  Exits 1 when the control passes a
limit set in the configuration on every number, or the program fails
one.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run


def control_run(spec: dict, seed: int, seconds: float) -> dict:
    import importlib

    for p in (run.BENCH, run.ROOT + "/src"):
        if p not in sys.path:
            sys.path.insert(0, p)
    builder = importlib.import_module("builders." + spec["config"]["builder"])
    cell = builder.Cell(spec["config"], spec["traffic"], run.seeds_of(seed))
    cell.setup()
    t_end = time.perf_counter() + seconds
    rounds = 0
    while time.perf_counter() < t_end:
        try:
            cell.record(cell.tick())
        except StopIteration:
            break
        rounds += 1
    got = cell.check(control=True)
    limits = spec["config"]["correct"]
    return {"seed": seed, "rounds": rounds, "limits": limits, **got,
            "program_correct": all(got["program"][k] <= v
                                   for k, v in limits.items()),
            "control_correct": all(got["control"][k] <= v
                                   for k, v in limits.items())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    run.enable_compile_cache()
    ok = True
    for seed in args.seeds:
        out = control_run(spec, seed, args.seconds)
        print(json.dumps(out), flush=True)
        ok &= out["program_correct"] and not out["control_correct"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
