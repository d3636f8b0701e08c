"""Runtime sanitizer for the compiled control loop (``REPRO_SANITIZE=1``).

ROADMAP item 3's premise is that the controllers' steady-state rounds run
entirely out of compiled code: the first round may trace, every later
round must reuse its executables.  Nothing enforced that — a drifting
static argument or a shape wobble retraces silently and the "light-weight
online controller" claim quietly dies.  This module wraps the four jitted
entry points

* ``anneal_chain_nd``'s kernel (``repro.core.annealing._chain_nd_jit``),
* the fleet kernel (``_fleet_nd_jit``, including the binding
  ``repro.core.fleet`` imported at module load, and the shard_map'd
  per-mesh instances built by ``_fleet_shard_jit`` — both count under
  the ``anneal_fleet`` entry),
* ``evaluate_sizing_batch`` (compiles through ``SizingSpace._eval_jit``),
* the surrogate refit (``repro.core.surrogate._interp_jit``, and the
  device table programs built by ``_surrogate_table_jit``),

counts **compilations** (via the jitted callable's tracing-cache size
before/after each call) and **device->host transfers** (``np.asarray`` /
``np.array`` / ``np.ascontiguousarray`` / ``jax.device_get`` applied to a
``jax.Array``; ``float()``/``.item()`` coercions are not interceptable
from Python — the static ``host-coercion-in-jit`` lint rule covers
those), attributes both to controller rounds through the
:mod:`repro.core.instrumentation` round hooks, and asserts the
**steady-state zero-retrace invariant**: after each controller's warm-up
round, zero new compilations.

Enable with ``REPRO_SANITIZE=1`` (``repro.core`` arms it at import) or
call :func:`install` directly.  ``python -m repro.analysis.run
--sanitize`` drives representative steady-state scenarios of the three
controllers under it and writes the per-round report that seeds the
ROADMAP item-4 baseline.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import threading
from typing import Any, Callable

ENV_FLAG = "REPRO_SANITIZE"

ENTRY_POINTS = ("anneal_chain_nd", "anneal_fleet", "evaluate_sizing_batch",
                "surrogate_refit")


class RetraceError(AssertionError):
    """A steady-state controller round recompiled a jitted entry point."""


def enabled() -> bool:
    return os.environ.get(ENV_FLAG) == "1"


# ---------------------------------------------------------------------------
# Counters.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EntryStats:
    calls: int = 0
    compiles: int = 0

    def snapshot(self) -> tuple[int, int]:
        return (self.calls, self.compiles)


class Sanitizer:
    """Counters plus the patch set.  One module-level instance
    (:data:`_SANITIZER`) is shared by :func:`install`/:func:`uninstall`."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.entries: dict[str, EntryStats] = {
            name: EntryStats() for name in ENTRY_POINTS}
        self.transfers = 0
        self.rounds: list[dict[str, Any]] = []
        self._round_mark: dict[str, tuple[int, int]] = {}
        self._transfer_mark = 0
        self._unpatch: list[Callable[[], None]] = []
        self.installed = False

    # -- recording ---------------------------------------------------------

    def record(self, entry: str, *, calls: int = 0, compiles: int = 0,
               ) -> None:
        with self._lock:
            st = self.entries[entry]
            st.calls += calls
            st.compiles += compiles

    def record_transfer(self, n: int = 1) -> None:
        with self._lock:
            self.transfers += n

    def note_round(self, controller: str, owner: Any) -> None:
        """Round-boundary hook: snapshot per-entry deltas since the last
        boundary and attribute them to this controller round."""
        with self._lock:
            deltas: dict[str, dict[str, int]] = {}
            for name, st in self.entries.items():
                prev = self._round_mark.get(name, (0, 0))
                cur = st.snapshot()
                if cur != prev:
                    deltas[name] = {"calls": cur[0] - prev[0],
                                    "compiles": cur[1] - prev[1]}
                self._round_mark[name] = cur
            transfers = self.transfers - self._transfer_mark
            self._transfer_mark = self.transfers
            self.rounds.append({
                "controller": controller,
                "round": sum(r["controller"] == controller
                             for r in self.rounds),
                "entries": deltas,
                "transfers": transfers,
            })

    def reset(self) -> None:
        with self._lock:
            for st in self.entries.values():
                st.calls = st.compiles = 0
            self.transfers = 0
            self.rounds.clear()
            self._round_mark.clear()
            self._transfer_mark = 0

    # -- reporting ---------------------------------------------------------

    def report(self) -> dict[str, Any]:
        with self._lock:
            return {
                "entry_points": {
                    name: dataclasses.asdict(st)
                    for name, st in self.entries.items()},
                "transfers_total": self.transfers,
                "rounds": [dict(r) for r in self.rounds],
            }

    def assert_steady_state(
            self, warmup: int = 1,
            transfer_budget: dict[str, int] | None = None) -> None:
        """Every controller round after its first ``warmup`` rounds must
        compile nothing, and — when ``transfer_budget`` maps controller
        names to per-round device->host transfer ceilings — must stay
        within its budget (controllers absent from the mapping are not
        budget-checked).  Raises :class:`RetraceError` with the offending
        (controller, round, entry) triples."""
        bad: list[str] = []
        for rec in self.rounds:
            if rec["round"] < warmup:
                continue
            for name, d in rec["entries"].items():
                if d["compiles"] > 0:
                    bad.append(
                        f"{rec['controller']} round {rec['round']}: "
                        f"{name} recompiled {d['compiles']}x")
            if transfer_budget is not None:
                limit = transfer_budget.get(rec["controller"])
                if limit is not None and rec["transfers"] > limit:
                    bad.append(
                        f"{rec['controller']} round {rec['round']}: "
                        f"{rec['transfers']} host transfers "
                        f"(budget {limit})")
        if bad:
            raise RetraceError(
                "steady-state zero-retrace invariant violated:\n  "
                + "\n  ".join(bad))

    # -- patching ----------------------------------------------------------

    def _patch(self, obj: Any, attr: str, value: Any) -> None:
        orig = getattr(obj, attr)
        setattr(obj, attr, value)
        self._unpatch.append(lambda: setattr(obj, attr, orig))

    def install(self) -> None:
        if self.installed:
            return
        # flag BEFORE the repro.core import: with REPRO_SANITIZE=1 that
        # import runs core._arm_analysis(), which calls install() again —
        # a re-entrant second pass would double-wrap every probe
        self.installed = True
        import jax
        import numpy as np

        from repro.core import (annealing, fleet, instrumentation, sizing,
                                surrogate)

        probe_chain = _JitProbe("anneal_chain_nd", annealing._chain_nd_jit,
                                self)
        self._patch(annealing, "_chain_nd_jit", probe_chain)

        probe_fleet = _JitProbe("anneal_fleet", annealing._fleet_nd_jit,
                                self)
        self._patch(annealing, "_fleet_nd_jit", probe_fleet)
        # fleet.py binds the name at import time — patch that site too
        self._patch(fleet, "_fleet_nd_jit", probe_fleet)

        # the sharded fleet path builds per-(mesh, shape) jitted kernels
        # through a cached factory — wrap each built instance in a probe
        # (the surrogate._interp_jit pattern), same entry-point bucket
        orig_shard = annealing._fleet_shard_jit

        @functools.cache
        def shard_jit(*key):
            return _JitProbe("anneal_fleet", orig_shard(*key), self)

        self._patch(annealing, "_fleet_shard_jit", shard_jit)

        orig_esb = sizing.evaluate_sizing_batch
        san = self

        @functools.wraps(orig_esb)
        def esb(spec, candidates, mix, use_kernel=None):
            inner = spec._eval_jit     # builds device tables on first use
            size = getattr(inner, "_cache_size", None)
            before = size() if size is not None else 0
            try:
                return orig_esb(spec, candidates, mix, use_kernel)
            finally:
                after = size() if size is not None else 0
                san.record("evaluate_sizing_batch", calls=1,
                           compiles=max(0, after - before))

        self._patch(sizing, "evaluate_sizing_batch", esb)
        # repro.core re-exports the name at import time; patch that
        # binding too so direct callers are counted
        import repro.core as core_pkg
        if getattr(core_pkg, "evaluate_sizing_batch", None) is orig_esb:
            self._patch(core_pkg, "evaluate_sizing_batch", esb)

        # the device-resident table build is the same entry-point bucket:
        # it compiles through SizingSpace._table_jit instead of _eval_jit
        orig_std = sizing.sizing_table_device

        @functools.wraps(orig_std)
        def std(spec, mix, use_kernel=None):
            inner = spec._table_jit
            size = getattr(inner, "_cache_size", None)
            before = size() if size is not None else 0
            try:
                return orig_std(spec, mix, use_kernel)
            finally:
                after = size() if size is not None else 0
                san.record("evaluate_sizing_batch", calls=1,
                           compiles=max(0, after - before))

        self._patch(sizing, "sizing_table_device", std)

        orig_interp = surrogate._interp_jit

        @functools.cache
        def interp(kind: str):
            return _JitProbe("surrogate_refit", orig_interp(kind), self)

        self._patch(surrogate, "_interp_jit", interp)

        orig_table = surrogate._surrogate_table_jit

        @functools.cache
        def table(*key):
            return _JitProbe("surrogate_refit", orig_table(*key), self)

        self._patch(surrogate, "_surrogate_table_jit", table)

        # device->host transfer counting: numpy's coercion entry points
        # plus jax.device_get, counted only for jax.Array operands
        for name in ("asarray", "array", "ascontiguousarray"):
            orig_np = getattr(np, name)

            def counted(a, *args, _orig=orig_np, **kw):
                if isinstance(a, jax.Array):
                    san.record_transfer()
                return _orig(a, *args, **kw)

            self._patch(np, name, counted)

        orig_get = jax.device_get

        def device_get(x):
            san.record_transfer()
            return orig_get(x)

        self._patch(jax, "device_get", device_get)

        instrumentation.ROUND_HOOKS.append(self.note_round)
        self._unpatch.append(
            lambda: instrumentation.ROUND_HOOKS.remove(self.note_round))

    def uninstall(self) -> None:
        while self._unpatch:
            self._unpatch.pop()()
        self.installed = False


class _JitProbe:
    """Callable proxy around a jitted function: counts calls and, via the
    tracing-cache size before/after, compilations."""

    def __init__(self, name: str, fn: Callable, sanitizer: Sanitizer):
        self._name = name
        self._fn = fn
        self._san = sanitizer
        self._size = getattr(fn, "_cache_size", None)

    def _cache_size(self) -> int:
        return self._size() if self._size is not None else 0

    def __call__(self, *args, **kwargs):
        before = self._cache_size()
        try:
            return self._fn(*args, **kwargs)
        finally:
            self._san.record(self._name, calls=1,
                             compiles=max(0, self._cache_size() - before))

    def __getattr__(self, attr):
        return getattr(self._fn, attr)


# ---------------------------------------------------------------------------
# Module-level facade.
# ---------------------------------------------------------------------------


_SANITIZER = Sanitizer()


def install() -> Sanitizer:
    _SANITIZER.install()
    return _SANITIZER


def uninstall() -> None:
    _SANITIZER.uninstall()


def maybe_install() -> Sanitizer | None:
    """Install iff ``REPRO_SANITIZE=1`` (the conftest / repro.core seam)."""
    if enabled():
        return install()
    return None


def current() -> Sanitizer:
    return _SANITIZER


def report() -> dict[str, Any]:
    return _SANITIZER.report()
