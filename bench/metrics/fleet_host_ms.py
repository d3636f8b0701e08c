"""Milliseconds per round in the fleet's host-only phases: the program's
``fleet.refit``, ``fleet.detect``, ``fleet.arbitrate``, ``fleet.ledger``
and ``fleet.measure`` spans, summed over the window, over its rounds.
(``fleet.anneal`` is left out: it closes before the device finishes.)"""

HOST_SPANS = frozenset({"fleet.refit", "fleet.detect", "fleet.arbitrate",
                        "fleet.ledger", "fleet.measure"})


def read(w):
    durs = [d for name, _, d, _ in w.spans if name in HOST_SPANS]
    if not durs or not w.rounds:
        return None
    return sum(durs) * 1e3 / w.rounds
