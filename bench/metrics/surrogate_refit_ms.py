"""Device milliseconds per round of the surrogate refit: the profiler's
durations of the surrogate table program (``jit_surrogate_table``: the
probe draw, the probes scored on the Erlang-C path and every state
interpolated) in the window, over its rounds.  Reads nothing where the
program has no such program."""

#: the one jitted program that builds a round's surrogate table
TABLE_PROGRAM = r"^jit_surrogate_table\b"


def read(w):
    secs, n = w.trace.module_time(TABLE_PROGRAM)
    if not n or not w.rounds:
        return None
    return secs * 1e3 / w.rounds
