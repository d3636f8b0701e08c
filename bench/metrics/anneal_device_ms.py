"""Device milliseconds per round of the chain engine: the profiler's
durations of the ``_fleet_nd_jit`` programs (every fleet and sizing
anneal runs through it) in the window, over its rounds."""


def read(w):
    secs, n = w.trace.module_time(r"fleet_nd_jit")
    if not n or not w.rounds:
        return None
    return secs * 1e3 / w.rounds
