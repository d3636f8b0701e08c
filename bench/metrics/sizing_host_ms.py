"""Milliseconds per round in the sizing round's host-only phases after
the device read: the program's ``sizing.measure`` (re-measuring the
top-K candidates) and ``sizing.commit`` (detector, decision record,
round hooks) spans, summed over the traced window, over its rounds.
Reads nothing where the program has no ``sizing.commit`` span."""

HOST_SPANS = frozenset({"sizing.measure", "sizing.commit"})


def read(w):
    names = [name for name, _, _, _ in w.spans]
    if "sizing.commit" not in names or not w.rounds:
        return None
    durs = [d for name, _, d, _ in w.spans if name in HOST_SPANS]
    return sum(durs) * 1e3 / w.rounds
