"""Pallas TPU kernels of the surrogate: pairwise distances and the
fused interpolation.

The surrogate's metric lives on the mixed ordinal-categorical index space
(:class:`repro.core.surrogate.SpaceEncoding`): an ordinal axis of ``n``
values contributes ``((i - j) / (n - 1))^2``, a categorical axis 1 on
mismatch, so ONE squared Euclidean distance carries both.

:func:`pairwise_sqdist` takes states already embedded as features and
expands ``D = ||q||^2 + ||m||^2 - 2 q m^T`` into a tiled matmul (MXU)
plus two row-norm passes, one (block_q, block_m) output tile per VMEM
pass over its operand rows.

:func:`fused_interp` is the surrogate refit: the IDW / RBF estimate (and
optionally the nearest-measurement distance) of every query state from M
measured states, without the (Q, M) distance matrix ever reaching HBM.
Its layout puts the query states on lanes:

* a query block is ``block_q`` consecutive flat indices of a row-major
  query shape (the block index plus a lane iota), or ``block_q`` columns
  of an explicit (ndim, Q) state array; each state's axis values are
  one-hot encoded in VMEM, one row per (axis, value);
* each measurement's row holds its distance contribution to every
  (axis, value), ``cost_d[m_d, v]`` (built once per call outside the
  kernel), so ``d2 = rows @ one_hot`` is one bf16 MXU pass with float32
  accumulation.  The contributions are split into as many bf16 parts as
  their float32 values need (one where they are bf16-exact, as 0/1 are),
  the one-hot is exact, and every term is non-negative: distances come
  out exact to float32 rounding and exactly 0 at a measured state, where
  IDW must reproduce the measurement;
* measurements sit on sublanes in 128-row chunks; the weighted sums
  reduce over sublanes into (1, block_q) accumulators, and the outputs
  are lane-dense (Q,) rows;
* the IDW weight ``1 / (d^p + eps)`` is the hardware's approximate
  reciprocal plus one Newton step, ``r + r * (1 - x * r)``: the sequence
  the TPU compiler emits for ``1.0 / x``, without the fix-ups it appends
  for 0, infinity, NaN and the 2^126 overflow edge.  With ``eps`` a
  normal positive float32, ``x = d^p + eps`` is a finite normal positive
  number (``d2`` sums non-negative costs, at most ``ndim``), no fix-up
  can fire, and on the chip the weight is bit-identical to the
  division's (interpret mode's approximate reciprocal is a bfloat16 one,
  so there the step leaves about 2^-16 of relative error).  IDW refuses
  a zero, denormal or NaN ``eps``.
"""

from __future__ import annotations

import functools
import struct

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Queries far outside the data cloud must dominate every kernel weight;
# padding rows sit at this coordinate so their distances are huge without
# needing a separate mask input.
_PAD_SENTINEL = 1e4
# Full-f32 MXU passes: at the default precision the TPU rounds both
# operands to bfloat16, which moves distances by ~0.1 at unit-scale
# features and flips RBF estimates (measured on a v5e against the f32
# reference); the interpret path on the CPU is f32 either way.
_F32_DOT = jax.lax.Precision.HIGHEST


def _sqdist_kernel(q_ref, m_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)            # (block_q, F)
    m = m_ref[...].astype(jnp.float32)            # (block_m, F)
    qq = jnp.sum(q * q, axis=1, keepdims=True)    # (block_q, 1)
    mm = jnp.sum(m * m, axis=1, keepdims=True)    # (block_m, 1)
    g = jax.lax.dot_general(
        q, m, (((1,), (1,)), ((), ())), precision=_F32_DOT,
        preferred_element_type=jnp.float32)       # (block_q, block_m)
    out_ref[...] = jnp.maximum(qq + mm.T - 2.0 * g, 0.0)


def pairwise_sqdist(xq, xm, *, block_q: int = 256, block_m: int = 256,
                    interpret: bool | None = None):
    """xq (Q, F), xm (M, F) fp32 -> (Q, M) squared Euclidean distances.

    Q, M and F are padded up to tile multiples (F to the 128-lane width);
    padded feature columns are zero (distance-neutral) and padded rows sit
    at a far sentinel so downstream min-distance reductions ignore them
    after the slice back to (Q, M).
    """
    Q, F = xq.shape
    M, F2 = xm.shape
    if F != F2:
        raise ValueError(f"feature dims differ: {F} vs {F2}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    bq = min(block_q, max(Q, 8))
    bm = min(block_m, max(M, 8))
    Qp = -(-Q // bq) * bq
    Mp = -(-M // bm) * bm
    Fp = -(-F // 128) * 128

    def pad(x, rows):
        r, f = x.shape
        out = jnp.full((rows, Fp), 0.0, jnp.float32)
        out = out.at[r:, 0].set(_PAD_SENTINEL)
        return out.at[:r, :f].set(x.astype(jnp.float32))

    d2 = pl.pallas_call(
        _sqdist_kernel,
        grid=(Qp // bq, Mp // bm),
        in_specs=[
            pl.BlockSpec((bq, Fp), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, Fp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Qp, Mp), jnp.float32),
        interpret=interpret,
    )(pad(xq, Qp), pad(xm, Mp))
    return d2[:Q, :M]


#: Measurement rows per sublane chunk of the fused refit, and per grid
#: step (the chunks of one step run in an in-kernel loop).
_CHUNK_M = 128
_BLOCK_M = 1024


def _idw_weight(x):
    """``1 / x`` for a finite, normal, positive float32 ``x``, inside a
    kernel: the approximate reciprocal and one Newton step, in the order
    the TPU compiler emits them for ``1.0 / x``."""
    r = pl.reciprocal(x, approx=True)
    return r + r * (1.0 - x * r)


def _digit(flat, stride: int, n: int):
    """Row-major axis value of flat indices (static stride and length):
    shifts and masks where both are powers of two, else division."""
    if stride & (stride - 1) == 0 and n & (n - 1) == 0:
        return (flat >> (stride.bit_length() - 1)) & (n - 1)
    return (flat // stride) % n


def _fused_interp_kernel(*refs, qshape, segs, n_split, explicit, kind,
                         length_scale, idw_power, eps, with_dmin, block_q,
                         n_chunks):
    if explicit:
        q_ref, *refs = refs
    rows_ref, y_ref, w_ref, v_ref, fb_ref, mean_ref, *refs = refs
    if with_dmin:
        dmin_ref, *refs = refs
    (acc_ref,) = refs
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        acc_ref[2:3, :] = jnp.full((1, block_q), jnp.inf, jnp.float32)

    # each query state's value on every axis, (1, block_q) int32 rows
    if explicit:
        digits = [q_ref[d:d + 1, :] for d in range(len(qshape))]
    else:
        flat = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_q), 1)
        strides, acc = [], 1
        for n in reversed(qshape):
            strides.append(acc)
            acc *= n
        digits = [_digit(flat, st, n)
                  for st, n in zip(reversed(strides), qshape)]
    # the one-hot, built 16 rows (a bf16 sublane tile) at a time; a group
    # looks only at the axes whose rows it holds
    kp = rows_ref.shape[1] // n_split
    groups = []
    for lo in range(0, kp, 16):
        hit = None
        for d, (s0, n) in enumerate(segs):
            if s0 < lo + 16 and s0 + n > lo:
                r = jax.lax.broadcasted_iota(jnp.int32, (16, block_q), 0)
                h = r == (digits[d] + (s0 - lo))
                hit = h if hit is None else hit | h
        groups.append(jnp.zeros((16, block_q), jnp.float32) if hit is None
                      else jnp.where(hit, 1.0, 0.0))
    onehot = jnp.concatenate(groups, axis=0).astype(jnp.bfloat16)

    def chunk(c, carry):
        ky, wsum, d2min = carry
        r0 = pl.multiple_of(c * _CHUNK_M, _CHUNK_M)
        d2 = None
        for p in range(n_split):
            part = jax.lax.dot_general(
                rows_ref[pl.ds(r0, _CHUNK_M), pl.ds(p * kp, kp)], onehot,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)   # (CHUNK_M, block_q)
            d2 = part if d2 is None else d2 + part
        if kind == "rbf":
            k = jnp.exp(d2 * (-1.0 / (2.0 * length_scale * length_scale)))
        else:                                         # "idw" (Shepard)
            dp = d2 if idw_power == 2.0 else d2 ** (idw_power / 2.0)
            k = _idw_weight(dp + eps)
        k = k * w_ref[pl.ds(r0, _CHUNK_M), :]
        ky = ky + jnp.sum(k * y_ref[pl.ds(r0, _CHUNK_M), :], axis=0,
                          keepdims=True)
        wsum = wsum + jnp.sum(k, axis=0, keepdims=True)
        if with_dmin:
            live = v_ref[pl.ds(r0, _CHUNK_M), :] > 0.0
            d2min = jnp.minimum(d2min, jnp.min(
                jnp.where(live, d2, jnp.inf), axis=0, keepdims=True))
        return ky, wsum, d2min

    ky, wsum, d2min = jax.lax.fori_loop(
        0, n_chunks, chunk,
        (acc_ref[0:1, :], acc_ref[1:2, :], acc_ref[2:3, :]))
    acc_ref[0:1, :] = ky
    acc_ref[1:2, :] = wsum
    acc_ref[2:3, :] = d2min

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        # the recency-weighted global mean is the far-field fallback
        mean_ref[...] = jnp.where(
            wsum > 1e-12, ky / jnp.maximum(wsum, 1e-12), fb_ref[...])
        if with_dmin:
            dmin_ref[...] = jnp.sqrt(d2min)


def _axis_costs(shape, categorical) -> list[list[list[float]]]:
    """Per axis, the (n, n) table of distance contributions between its
    values: ``((i - j) / (n - 1))^2`` on an ordinal axis, 1 on a
    categorical mismatch (the encoding of
    :class:`repro.core.surrogate.SpaceEncoding`).  Plain Python: these
    are trace-time constants."""
    out = []
    for n, cat in zip(shape, categorical):
        step = 1.0 / max(n - 1, 1)
        out.append([[(1.0 if i != j else 0.0) if cat else ((i - j) * step) ** 2
                     for j in range(n)] for i in range(n)])
    return out


def _bf16_exact(v: float) -> bool:
    """Whether ``v`` rounded to float32 is also a bfloat16 (its low 16
    mantissa bits are zero)."""
    return struct.pack("<f", v)[:2] == b"\x00\x00"


def fused_interp(probes, y, w_rec, *, shape, categorical=None,
                 queries=None, qshape=None, offsets=None, valid=None,
                 kind: str = "idw", length_scale: float = 0.25,
                 idw_power: float = 2.0, eps: float = 1e-9,
                 with_dmin: bool = True, block_q: int = 2048,
                 interpret: bool | None = None):
    """Fused surrogate refit: the IDW / RBF estimate at every query state
    from M measured states, in one pass over the measurements.

    ``probes`` (M, ndim) int measured states, ``y`` (M,) objectives,
    ``w_rec`` (M,) recency weights, ``valid`` (M,) 1 on live rows (None:
    all live; a dead row must carry zero weight).  ``shape`` and
    ``categorical`` (static) describe the space.  The queries are either
    ``queries`` (Q, ndim) explicit states, or every state of the
    row-major ``qshape`` (static; default ``shape``) shifted by
    ``offsets`` (ndim,) into the space, enumerated inside the kernel:
    Q = prod(qshape), and no query array exists at all.

    Returns ``(mean (Q,), dmin (Q,))`` float32, ``dmin`` the distance to
    the nearest live measurement, or ``mean`` alone with ``with_dmin``
    False.  The estimate is kernel-weighted with the recency-weighted
    global mean as the far-field fallback.  ``kind`` / ``length_scale`` /
    ``idw_power`` / ``eps`` are Python-static (baked into the trace);
    IDW takes ``eps`` at least the smallest normal float32.
    """
    shape = tuple(int(n) for n in shape)
    ndim = len(shape)
    categorical = (tuple(bool(c) for c in categorical)
                   if categorical is not None else (False,) * ndim)
    if probes.ndim != 2 or probes.shape[1] != ndim:
        raise ValueError(f"probes shape {probes.shape} != (M, {ndim})")
    if kind not in ("idw", "rbf"):
        raise ValueError(f"unknown interp kind {kind!r}")
    if kind == "idw" and not eps >= 2.0 ** -126:     # least normal f32
        raise ValueError(f"IDW eps {eps!r} is not a normal positive "
                         "float32: the weight's reciprocal needs one")
    if block_q < 128 or block_q % 128:
        raise ValueError("block_q must be a positive multiple of 128")
    explicit = queries is not None
    if explicit:
        if qshape is not None or offsets is not None:
            raise ValueError("explicit queries take no qshape / offsets")
        qshape = shape
        Q = queries.shape[0]
    else:
        qshape = shape if qshape is None else tuple(int(n) for n in qshape)
        if len(qshape) != ndim or any(
                not 1 <= q <= n for q, n in zip(qshape, shape)):
            raise ValueError(f"qshape {qshape} does not fit in {shape}")
        Q = 1
        for n in qshape:
            Q *= n
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    M = probes.shape[0]

    # each measurement's distance contribution to every (axis, value) of
    # the queries, concatenated over the axes: (M, sum of qshape)
    costs = _axis_costs(shape, categorical)
    probes = probes.astype(jnp.int32)
    cols, segs, s0 = [], [], 0
    for d, (c, nq) in enumerate(zip(costs, qshape)):
        row = jnp.asarray(c, jnp.float32)[probes[:, d]]   # (M, n)
        if offsets is not None:
            row = jax.lax.dynamic_slice_in_dim(
                row, jnp.asarray(offsets, jnp.int32)[d], nq, axis=1)
        elif nq < row.shape[1]:
            row = row[:, :nq]
        cols.append(row)
        segs.append((s0, nq))
        s0 += nq
    kp = -(-s0 // 128) * 128
    mc = min(-(-M // _CHUNK_M) * _CHUNK_M, _BLOCK_M)
    Mp = -(-M // mc) * mc
    rows = jnp.pad(jnp.concatenate(cols, axis=1),
                   ((0, Mp - M), (0, kp - s0)))
    # bf16 parts whose sum is the float32 value: as many as it needs
    exact = all(_bf16_exact(v) for c in costs for r in c for v in r)
    parts = []
    for _ in range(1 if exact else 3):
        p = rows.astype(jnp.bfloat16)
        parts.append(p)
        rows = rows - p.astype(jnp.float32)
    rows = jnp.concatenate(parts, axis=1)

    def col(x):
        return jnp.pad(x.astype(jnp.float32), (0, Mp - M)).reshape(Mp, 1)

    w32 = w_rec.astype(jnp.float32)
    y32 = y.astype(jnp.float32)
    live = jnp.ones((M,), jnp.float32) if valid is None \
        else valid.astype(jnp.float32)
    fallback = ((y32 * w32).sum()
                / jnp.maximum(w32.sum(), 1e-12)).reshape(1, 1)

    bq = min(block_q, -(-Q // 128) * 128)
    Qp = -(-Q // bq) * bq
    in_specs = [
        pl.BlockSpec((mc, len(parts) * kp), lambda i, j: (j, 0)),
        pl.BlockSpec((mc, 1), lambda i, j: (j, 0)),          # y
        pl.BlockSpec((mc, 1), lambda i, j: (j, 0)),          # w
        pl.BlockSpec((mc, 1), lambda i, j: (j, 0)),          # live
        pl.BlockSpec((1, 1), lambda i, j: (0, 0)),           # fallback
    ]
    args = [rows, col(y32), col(w32), col(live), fallback]
    if explicit:
        nd8 = -(-ndim // 8) * 8
        qs = jnp.pad(queries.astype(jnp.int32).T,
                     ((0, nd8 - ndim), (0, Qp - Q)))
        in_specs.insert(0, pl.BlockSpec((nd8, bq), lambda i, j: (0, i)))
        args.insert(0, qs)
    n_out = 2 if with_dmin else 1
    kern = functools.partial(
        _fused_interp_kernel, qshape=qshape, segs=tuple(segs),
        n_split=len(parts), explicit=explicit, kind=kind,
        length_scale=float(length_scale), idw_power=float(idw_power),
        eps=float(eps), with_dmin=with_dmin, block_q=bq,
        n_chunks=mc // _CHUNK_M)
    outs = pl.pallas_call(
        kern,
        grid=(Qp // bq, Mp // mc),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, bq), lambda i, j: (0, i))] * n_out,
        out_shape=[jax.ShapeDtypeStruct((1, Qp), jnp.float32)] * n_out,
        scratch_shapes=[pltpu.VMEM((8, bq), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="fused_interp",
    )(*args)
    if with_dmin:
        return outs[0][0, :Q], outs[1][0, :Q]
    return outs[0][0, :Q]
