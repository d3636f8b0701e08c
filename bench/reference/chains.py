"""The Metropolis chain of the reference, and the random draws it takes.

The draws come from ``jax.random`` (threefry, integer arithmetic, the same
bits on any backend) with the key layout of the annealing engine the
configurations state: per step ``split(key, 4)`` into (proposal, noise,
accept), the proposal key ``split`` into (axis, direction, category).
Every axis of both configurations is ordinal: a proposal moves one axis
by +-1 and reflects at its ends.  The chain itself runs in numpy: the
objective and the penalty row are rounded to the stated device precision
(float32, or bfloat16 for the control), added in float32, and a move
from ``y_x`` to ``y_z`` is accepted when ``u < exp(-max(y_z - y_x, 0) /
tau)``.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np


def _cpu():
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return jax.devices()[0]


def bucket(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@functools.lru_cache(maxsize=None)
def _draws_fn(steps: int, ndim: int):
    def one(key):
        key, _ = jax.random.split(key)

        def body(key, _):
            key, k_prop, _, k_acc = jax.random.split(key, 4)
            k_axis, k_dir, _ = jax.random.split(k_prop, 3)
            axis = jax.random.randint(k_axis, (), 0, ndim)
            up = jax.random.bernoulli(k_dir)
            u = jax.random.uniform(k_acc)
            return key, (axis, up, u)

        _, out = jax.lax.scan(body, key, None, length=steps)
        return out

    return jax.jit(lambda kd: jax.vmap(one)(jax.random.wrap_key_data(kd)))


def draws(key_data: np.ndarray, steps: int, ndim: int):
    """(axis, up, u), each (C, steps), for C chains from their raw key
    data.  Rows are padded to a power of two so few shapes compile."""
    C = key_data.shape[0]
    P = bucket(C)
    kd = np.concatenate([key_data, np.repeat(key_data[:1], P - C, 0)])
    with jax.default_device(_cpu()):
        ax, up, u = _draws_fn(steps, ndim)(jnp.asarray(kd))
    return (np.asarray(ax)[:C], np.asarray(up)[:C],
            np.asarray(u, np.float32)[:C])


def chain_keys_fleet(seed: int, r: int, stream_ids: Sequence[int]):
    """Per-tenant keys ``fold_in(fold_in(key(seed), r), stream_id)``."""
    with jax.default_device(_cpu()):
        base = jax.random.fold_in(jax.random.key(seed), r)
        keys = jax.vmap(lambda s: jax.random.fold_in(base, s))(
            jnp.asarray(np.asarray(stream_ids), jnp.uint32))
        return np.asarray(jax.random.key_data(keys))


@functools.lru_cache(maxsize=None)
def _sizing_keys_fn(n_chains: int, shape: tuple[int, ...]):
    def one(seed, r):
        k_init, k_run = jax.random.split(
            jax.random.fold_in(jax.random.key(seed), r))
        inits = jax.random.randint(
            k_init, (n_chains, len(shape)), 0,
            jnp.asarray(shape, jnp.int32), dtype=jnp.int32)
        key, _ = jax.random.split(k_run)
        return jax.random.key_data(jax.random.split(key, n_chains)), inits

    return jax.jit(jax.vmap(one, in_axes=(None, 0)))


def chain_keys_sizing(seed: int, rounds: Sequence[int], n_chains: int,
                      shape: Sequence[int]):
    """Each sizing round's chain keys and random starting states, (R, C,
    2) and (R, C, ndim): the round key ``fold_in(key(seed), r)`` splits
    into (starts, run); the run key splits once more and then into one
    key per chain.  Rounds are padded to a power of two so few shapes
    compile."""
    R = len(rounds)
    rs = np.asarray(list(rounds) + [rounds[-1]] * (bucket(R) - R), np.int32)
    with jax.default_device(_cpu()):
        kd, inits = _sizing_keys_fn(n_chains, tuple(int(s) for s in shape))(
            jnp.asarray(seed, jnp.uint32), jnp.asarray(rs))
    return np.asarray(kd)[:R], np.asarray(inits)[:R].astype(np.int64)


def _round_to(x: np.ndarray, dtype: str) -> np.ndarray:
    if dtype == "float32":
        return np.asarray(x, np.float32)
    return np.asarray(x, ml_dtypes.bfloat16).astype(np.float32)


def run_chains(axis, up, u, y_rows, extra_rows, taus, inits,
               shape: Sequence[int], dtype: str = "float32",
               err_rows=None, row_of=None):
    """States (C, steps, ndim) of C chains; ``y_rows``/``extra_rows``
    (C, size) objective and additive penalty (``extra_rows`` may be
    None), ``taus`` (C, steps), ``inits`` (C, ndim).  With ``row_of``
    (C,), chain k reads row ``row_of[k]`` of the tables instead of row k.

    With ``err_rows`` (C, size), a bound on how far another
    implementation's objective may lie from ``y_rows`` at each state,
    also returns each chain's count of leading steps whose acceptance no
    objective within that bound would decide otherwise: up to there,
    every such implementation walks the same states."""
    C, steps = axis.shape
    y = _round_to(y_rows, dtype)
    e = None if extra_rows is None else _round_to(extra_rows, dtype)
    err = None if err_rows is None else np.asarray(err_rows, np.float64)
    tau = np.asarray(taus, np.float32)
    sizes = np.asarray(shape, np.int64)
    strides = np.asarray([int(np.prod(shape[d + 1:]))
                          for d in range(len(shape))], np.int64)
    c = np.arange(C)
    row = c if row_of is None else np.asarray(row_of, np.int64)
    x = np.asarray(inits, np.int64).copy()
    robust = np.full(C, steps)

    def look(zi):
        v = y[row, zi]
        return v if e is None else np.float32(v + e[row, zi])

    xi = x @ strides
    y_x = look(xi)
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
        y_z = look(zi)
        dy = (y_z - y_x).astype(np.float32)
        p = np.exp((-np.maximum(dy, np.float32(0.0))) / tau[:, k])
        acc = u[:, k] < p
        if err is not None:
            m = err[row, xi] + err[row, zi]
            t = tau[:, k].astype(np.float64)
            p_lo = np.exp(-np.maximum(dy + m, 0.0) / t) * (1.0 - 1e-6)
            p_hi = np.exp(-np.maximum(dy - m, 0.0) / t) * (1.0 + 1e-6)
            amb = (u[:, k] >= p_lo) & (u[:, k] < p_hi)
            robust = np.where(amb & (robust == steps), k, robust)
        x = np.where(acc[:, None], xz, x)
        xi = np.where(acc, zi, xi)
        y_x = np.where(acc, y_z, y_x)
        out[:, k] = x
    return out if err is None else (out, robust)
