"""Milliseconds per round that the controller waits for the device: the
program's ``sizing.sync`` and ``fleet.sync`` spans (the round's one
blocking device read), summed over the traced window, over its rounds."""

SYNC_SPANS = frozenset({"sizing.sync", "fleet.sync"})


def read(w):
    durs = [d for name, _, d, _ in w.spans if name in SYNC_SPANS]
    if not durs or not w.rounds:
        return None
    return sum(durs) * 1e3 / w.rounds
