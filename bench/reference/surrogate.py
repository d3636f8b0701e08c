"""Plain reference of the surrogate sizing round (``boutique-sizing-1m``).

Written from the semantics, in numpy float64 plus ``jax.random`` for the
keys, and importing nothing of the program.  The space is too large to
tabulate, so every round's table is an interpolation: ``n_probe``
distinct states drawn from the round's key are scored on the exact model
at the round's mix (``SizingModel.objective``), and every other state is
their inverse-distance-weighted (Shepard) mean,

    mean(x) = sum_i k_i y_i / sum_i k_i,   k_i = 1 / (d(x, p_i)^p + eps),

with ``d`` the distance of the mixed encoding: an ordinal axis of ``n``
values spans 1 (``(i - j) / (n - 1)`` per step).  This reference
evaluates that mean at any set of states, in blocks, and never holds the
whole table.

The probe draw: the round key is ``fold_in(key(seed), r)``, the probe
key ``fold_in`` of it with ``PROBE_STREAM``; ``2 n_probe`` uniform draws
of a flat state, of which the first ``n_probe`` distinct in draw order
are the probes (a slot left over, which takes some 500 collisions among
2,048 draws from a million states, holds state 0 at weight 0).

A round is then decided as ``sizing.py`` decides one, on this table:
chains from the same keys walk it, the walk is followed only as far as
no table within the error bound could decide a step otherwise, and the
committed sizing must be no worse, by the float64 table, than the best
state every sound implementation walks (``SurrogateReference.decide``).
The table's error bound at a state is the probes' own (``TABLE_ERROR``
of the Erlang-C scoring, weighted as the mean weights them: every
objective is positive) plus ``INTERP_ERROR`` of the mean for the float32
weights and sums.
"""

from __future__ import annotations

import functools
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .chains import _cpu, _round_to, bucket, chain_keys_sizing, draws
from .fleet import tau_rows
from .sizing import TABLE_ERROR, TABLE_ERROR_PER_AMP, SizingReference

#: ``fold_in`` data that derives the probe key from the round key
PROBE_STREAM = 0x70726F62

#: relative error allowed the float32 interpolation of float32 probe
#: values beyond the probes' own: the rounding of each weight and of the
#: two sums over 1,024 probes, a few units of 6e-8 each, with room
INTERP_ERROR = 1e-5


@functools.lru_cache(maxsize=None)
def _draw_fn(n: int, size: int):
    def one(seed, r):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(seed), r), PROBE_STREAM)
        return jax.random.randint(key, (2 * n,), 0, size, dtype=jnp.int32)

    return jax.jit(jax.vmap(one, in_axes=(None, 0)))


def probe_draw(seed: int, rounds: Sequence[int], n: int, size: int
               ) -> tuple[np.ndarray, np.ndarray]:
    """Each round's probes: flat states (R, n) and weights (R, n)."""
    R = len(rounds)
    rs = np.asarray(list(rounds) + [rounds[-1]] * (bucket(R) - R), np.int32)
    with jax.default_device(_cpu()):
        cand = np.asarray(_draw_fn(n, size)(
            jnp.asarray(seed, jnp.uint32), jnp.asarray(rs)))[:R]
    flat = np.zeros((R, n), np.int64)
    weight = np.zeros((R, n))
    for i, c in enumerate(cand):
        _, first = np.unique(c, return_index=True)
        keep = np.sort(first)[:n]
        flat[i, :len(keep)] = c[keep]
        weight[i, :len(keep)] = 1.0
    return flat, weight


class SurrogateReference:
    def __init__(self, cfg: Mapping[str, Any], seed: int):
        self.base = SizingReference(cfg, seed)
        self.model = self.base.model
        self.seed = int(seed)
        s = cfg["surrogate"]
        if s["kind"] != "idw":
            raise ValueError(f"no reference for interpolation {s['kind']!r}")
        self.n_probe = int(s["n_probe"])
        self.power = float(s["idw_power"])
        self.eps = float(s.get("eps", 1e-9))
        self.shape = self.model.shape
        self.size = int(np.prod(self.shape))
        self.scale = np.asarray([1.0 / max(n - 1, 1) for n in self.shape])
        self._probes: dict[int, tuple] = {}
        self._stacked: tuple = ((), None)

    # -- the table -------------------------------------------------------

    def coords(self, flat: np.ndarray) -> np.ndarray:
        """(..., ndim) encoded coordinates of flat states."""
        idx = np.stack(np.unravel_index(np.asarray(flat), self.shape), -1)
        return idx * self.scale

    def probes(self, rounds: Sequence[int],
               mixes: Sequence[Mapping[str, float]]) -> None:
        """Draws and scores the probes of ``rounds`` (kept per round):
        coordinates, float64 objective, weight and error bound."""
        todo = [r for r in rounds if r not in self._probes]
        if not todo:
            return
        m = self.model
        flat, weight = probe_draw(self.seed, todo, self.n_probe, self.size)
        for r, f, w in zip(todo, flat, weight):
            mix = mixes[r]
            y = m.objective(mix, f)
            _, c, mu, lam = m.tier_options(mix)
            amp = c * mu / np.maximum(np.abs(c * mu - lam[:, None]), 1e-12)
            e = TABLE_ERROR + TABLE_ERROR_PER_AMP * m._gather(amp, f).max(1)
            self._probes[r] = (self.coords(f), y, w, e, f)

    def forget(self) -> None:
        """Drops every round's probes."""
        self._probes.clear()
        self._stacked = ((), None)

    def interp(self, rounds: np.ndarray, flat: np.ndarray
               ) -> tuple[np.ndarray, np.ndarray]:
        """The float64 table and its error bound at states ``flat`` (R, Q)
        of rounds ``rounds`` (R,), whose probes are drawn."""
        key = tuple(int(r) for r in rounds)
        if self._stacked[0] != key:
            P, Y, W, E = (np.stack([self._probes[r][i] for r in key])
                          for i in range(4))             # (R, M, ...)
            # |q - p|^2 = |q|^2 + |p|^2 - 2 q.p as one product:
            # [|q|^2, 1, q] . [1, |p|^2, -2 p]
            B = np.concatenate([np.ones(P.shape[:2] + (1,)),
                                (P * P).sum(-1, keepdims=True), -2.0 * P],
                               axis=2).transpose(0, 2, 1).copy()
            self._stacked = (key, (B, Y, W, Y * E))
        B, Y, W, YE = self._stacked[1]
        q = self.coords(flat)                             # (R, Q, ndim)
        A = np.concatenate([(q * q).sum(-1, keepdims=True),
                            np.ones(q.shape[:2] + (1,)), q], axis=2)
        k = np.matmul(A, B)                               # (R, Q, M): d2
        # the true distances are 0 or at least the smallest axis step
        # squared; the expansion's rounding is far below either
        k[k < 0.5 * (self.scale ** 2).min()] = 0.0
        if self.power != 2.0:
            k **= self.power / 2.0
        k += self.eps
        np.divide(W[:, None, :], k, out=k)                # the weights
        ksum = k.sum(-1)
        ky = np.matmul(k, Y[:, :, None])[..., 0]
        kye = np.matmul(k, YE[:, :, None])[..., 0]
        fallback = (Y * W).sum(-1) / np.maximum(W.sum(-1), 1e-12)
        ok = ksum > 1e-12
        mean = np.where(ok, ky / np.maximum(ksum, 1e-12), fallback[:, None])
        err = (np.where(ok, kye / np.maximum(ksum, 1e-12), 0.0)
               + INTERP_ERROR * np.abs(mean))
        return mean, err

    def interp_at(self, r: int, flat: np.ndarray, block: int = 8192):
        """:meth:`interp` of one round at many states, in blocks."""
        out = [self.interp(np.asarray([r]), flat[None, lo:lo + block])
               for lo in range(0, len(flat), block)]
        return (np.concatenate([o[0][0] for o in out]),
                np.concatenate([o[1][0] for o in out]))

    # -- the rounds ------------------------------------------------------

    def replay(self, mixes, committed):
        """``SizingReference.replay``: the exact float64 objective of each
        committed sizing, and the reheat schedule its detector gives."""
        return self.base.replay(mixes, committed)

    def _walk(self, axis, up, u, rounds_of, taus, inits, dtype: str,
              bounded: bool):
        """The Metropolis walk of ``chains.run_chains`` on the lazily
        evaluated table: chain ``c`` reads round ``rounds_of[c]``.
        Returns states (C, steps, ndim), the float64 table and bound at
        the start and every state walked ((C, steps + 1) each), and with
        ``bounded`` the count of leading steps no table within the bound
        decides otherwise."""
        C, steps = axis.shape
        shape = self.shape
        sizes = np.asarray(shape, np.int64)
        strides = np.asarray([int(np.prod(shape[d + 1:]))
                              for d in range(len(shape))], np.int64)
        rows = np.unique(rounds_of)
        pos = np.searchsorted(rows, rounds_of)
        per = C // len(rows)
        # chains are grouped by round, ``per`` consecutive chains a round
        assert (pos == np.repeat(np.arange(len(rows)), per)).all()

        def look(zi):
            y, e = self.interp(rows, zi.reshape(len(rows), per))
            return y.reshape(C), e.reshape(C)

        c = np.arange(C)
        tau = np.asarray(taus, np.float32)
        x = np.asarray(inits, np.int64).copy()
        xi = x @ strides
        y64, e64 = look(xi)
        ys, es = [y64], [e64]
        y_x = _round_to(y64, dtype)
        robust = np.full(C, steps)
        out = np.empty((C, steps, len(shape)), np.int64)
        for k in range(steps):
            a = axis[:, k]
            n = sizes[a]
            cur = x[c, a]
            d = np.where(up[:, k], 1, -1)
            z = np.clip(cur + d, 0, n - 1)
            z = np.where(z == cur, cur - d, z)
            z = np.clip(z, 0, n - 1)
            xz = x.copy()
            xz[c, a] = z
            zi = xz @ strides
            z64, ze = look(zi)
            y_z = _round_to(z64, dtype)
            dy = (y_z - y_x).astype(np.float32)
            p = np.exp((-np.maximum(dy, np.float32(0.0))) / tau[:, k])
            acc = u[:, k] < p
            if bounded:
                m = e64 + ze
                t = tau[:, k].astype(np.float64)
                p_lo = np.exp(-np.maximum(dy + m, 0.0) / t) * (1.0 - 1e-6)
                p_hi = np.exp(-np.maximum(dy - m, 0.0) / t) * (1.0 + 1e-6)
                amb = (u[:, k] >= p_lo) & (u[:, k] < p_hi)
                robust = np.where(amb & (robust == steps), k, robust)
            x = np.where(acc[:, None], xz, x)
            xi = np.where(acc, zi, xi)
            y_x = np.where(acc, y_z, y_x)
            y64 = np.where(acc, z64, y64)
            e64 = np.where(acc, ze, e64)
            out[:, k] = x
            ys.append(y64)
            es.append(e64)
        return out, np.stack(ys, 1), np.stack(es, 1), robust

    def decide(self, rounds: Sequence[int],
               mixes: Sequence[Mapping[str, float]],
               committed: Sequence[Sequence[int]],
               replayed: tuple[np.ndarray, Sequence[float | None]],
               modes: Sequence[str] = ("sound",), block: int = 64):
        """Decides each of ``rounds`` itself on its interpolated table, in
        blocks of rounds whose chains walk together, and yields ``(r,
        out)`` per round, as ``SizingReference.decide`` does: ``bound``,
        ``excess`` (of the committed sizing over the bound, by the
        float64 table, past its error), ``y64`` (the exact objective of
        the committed sizing, from ``replayed``, what :meth:`replay`
        returns), ``sound`` (the reference's own choice),
        ``robust_share``, ``starts`` (the chains' starting states, flat);
        with ``"low"`` the walk and choice on the table rounded to
        bfloat16 (``low``, ``low_excess``, ``low_y64``)."""
        b = self.base
        m = self.model
        y_committed, reheats = replayed
        C, ndim = b.n_chains, len(self.shape)
        rounds = list(rounds)
        start = (0,) * ndim
        for b0 in range(0, len(rounds), block):
            rs = rounds[b0:b0 + block]
            self.probes(rs, mixes)
            kd, inits = chain_keys_sizing(self.seed, rs, C, self.shape)
            for i, r in enumerate(rs):
                inits[i, 0] = committed[r - 1] if r > 0 else start
            inits = inits.reshape(len(rs) * C, ndim)
            ax, up, u = draws(kd.reshape(len(rs) * C, -1), b.steps, ndim)
            taus = np.concatenate([np.repeat(tau_rows(
                [reheats[r]], r * b.steps, b.steps, *b.sched), C, 0)
                for r in rs])
            rounds_of = np.repeat(rs, C)
            walked, ys, es, robust = self._walk(
                ax, up, u, rounds_of, taus, inits, "float32", True)
            if "low" in modes:
                low, low_ys, _, _ = self._walk(
                    ax, up, u, rounds_of, taus, inits, "bfloat16", False)
            at = np.asarray([np.ravel_multi_index(tuple(committed[r]),
                                                  self.shape) for r in rs])
            y_at, e_at = self.interp(np.asarray(rs), at[:, None])
            for i, r in enumerate(rs):
                ch = slice(i * C, (i + 1) * C)
                sure = np.concatenate([ys[k, :robust[k] + 1] + es[k, :robust[k]
                                                                  + 1]
                                       for k in range(ch.start, ch.stop)])
                bound = float(sure.min())

                def excess(y, e):
                    return max(0.0, y - e - bound) / abs(bound)

                def choice(states, est):
                    visited = np.concatenate(
                        [inits[ch, None, :], states[ch]],
                        axis=1).reshape(-1, ndim)
                    j = int(np.argsort(est[ch].reshape(-1),
                                       kind="stable")[0])
                    return tuple(int(v) for v in visited[j]), j

                def exact(state):
                    f = np.ravel_multi_index(tuple(state), self.shape)
                    return float(m.objective(mixes[r], np.asarray([f]))[0])

                sound, _ = choice(walked, _round_to(ys, "float32"))
                out = {"bound": bound,
                       "robust_share": float(robust[ch].mean() / b.steps),
                       "sound": sound,
                       "excess": excess(y_at[i, 0], e_at[i, 0]),
                       "y64": float(y_committed[r]),
                       "starts": inits[ch] @ np.asarray(
                           [int(np.prod(self.shape[d + 1:]))
                            for d in range(ndim)])}
                if "low" in modes:
                    lc, j = choice(low, _round_to(low_ys, "bfloat16"))
                    yl, el = self.interp(
                        np.asarray([r]), np.asarray([[np.ravel_multi_index(
                            lc, self.shape)]]))
                    out["low"] = lc
                    out["low_excess"] = excess(yl[0, 0], el[0, 0])
                    out["low_y64"] = exact(lc)
                yield r, out
            self.forget()
