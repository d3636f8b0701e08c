"""Percent of its roofline that the sizing table program reaches: the
least time the chip could take for the table's algorithmic work, over
the profiler's time of the table programs in the window.

The work is counted from the configuration's shapes alone, the same
whatever computes the table (``table_work``): per state, each tier's
service rate, the Erlang-B recurrence up to the tier's replica count and
the Erlang-C sojourn; per class, the visit-weighted node costs, one
max-and-add per call edge and node of the DAG and the SLO hinge; per
state, the cost.  The bytes are the float32 table written (the inputs
are a few hundred numbers).  The least time is the larger of operations
over the peak FLOP/s and bytes over the peak bandwidth
(``bench/peaks.json``).
"""

#: the table program: the one jitted program that holds the Erlang-C
#: kernel (its name is the scoring closure's, ``run``)
TABLE_PROGRAM = r"^jit_run\b"


def table_work(cfg) -> tuple[float, float]:
    """(operations, bytes) of one objective table."""
    K = len(cfg["tiers"])
    C = len(cfg["classes"])
    E = len(cfg["edges"])
    n_sizes, repl = len(cfg["sizes"]), cfg["replica_counts"]
    states = (n_sizes * len(repl)) ** K
    mean_c = sum(repl) / len(repl)
    per_tier = 4 + 12 + 3 * mean_c       # rate, load/slack/C/sojourn, B_k
    per_class = 2 * K + E + K + 5        # node costs, path, hinge
    per_state = K * per_tier + C * per_class + 2 * K + 3
    return states * per_state, states * 4.0


def read(w):
    secs, n = w.trace.module_time(TABLE_PROGRAM)
    if not n or secs <= 0:
        return None
    flops, nbytes = table_work(w.config)
    least = max(flops / w.peaks["flops_per_s"],
                nbytes / w.peaks["hbm_bytes_per_s"])
    return 100.0 * n * least / secs
