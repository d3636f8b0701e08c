"""Milliseconds per round that the sizing round spends dispatching: the
program's ``sizing.dispatch`` span (key splits, the table, chain inits,
the anneal and the top-K select enqueued, up to the round's first device
read), summed over the traced window, over its rounds."""


def read(w):
    durs = [d for name, _, d, _ in w.spans if name == "sizing.dispatch"]
    if not durs or not w.rounds:
        return None
    return sum(durs) * 1e3 / w.rounds
