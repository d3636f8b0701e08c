"""Percent of its roofline that the surrogate's interpolation reaches:
the least time the chip could take for the interpolation's algorithmic
work, over the profiler's time of the interpolation kernel
(``fused_interp``) inside the surrogate table program in the window.

The work is counted from the configuration's shapes alone, the same
whatever implements it (``interp_work``): every one of the Q states
against every one of the M probes, at about 3F + 6 operations a pair
(per feature a difference, a square and an add; then the power, the
weight, its reciprocal, two products and two sums), F the number of
features, one per axis.  The bytes are the float32 table written and the
probes read (their features and values).  The least time is the larger
of operations over the peak FLOP/s and bytes over the peak bandwidth
(``bench/peaks.json``).
"""

import re

#: the table program, and the interpolation kernel's operations in it
TABLE_PROGRAM = r"^jit_surrogate_table\b"
INTERP_OP = re.compile(r"^jit_surrogate_table/fused_interp\b")


def interp_work(cfg) -> tuple[float, float]:
    """(operations, bytes) of one interpolated table."""
    K = len(cfg["tiers"])
    Q = (len(cfg["sizes"]) * len(cfg["replica_counts"])) ** K
    M = cfg["surrogate"]["n_probe"]
    F = 2 * K                                  # a size and a replica axis
    return float(Q * M * (3 * F + 6)), 4.0 * Q + 4.0 * M * (F + 1)


def read(w):
    durs = [d for name, _, d in w.trace.ops if INTERP_OP.search(name)]
    if not durs or not w.rounds:
        return None
    secs = sum(durs) * 1e-9
    tables = w.trace.module_time(TABLE_PROGRAM)[1]
    ops, nbytes = interp_work(w.config)
    least = max(ops / w.peaks["flops_per_s"],
                nbytes / w.peaks["hbm_bytes_per_s"])
    return 100.0 * tables * least / secs
