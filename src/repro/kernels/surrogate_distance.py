"""Pallas TPU kernel: pairwise squared-distance matrix for the surrogate.

The surrogate interpolator's hot spot is ``D[i, j] = ||q_i - m_j||^2``
between Q windowed query states and M stored measurements, both already
embedded in the mixed ordinal-categorical feature space
(:class:`repro.core.surrogate.SpaceEncoding`: ordinal axes are [0, 1]
scaled coordinates, categorical axes one-hot / sqrt(2), so ONE Euclidean
distance carries both metrics).  Expanding

    D = ||q||^2 + ||m||^2 - 2 q m^T

turns the inner loop into a tiled matmul (MXU) plus two row-norm passes;
the grid tiles (Q, M) so each (block_q, block_m) output tile is computed
in a single VMEM pass over its operand rows.  The fp32 feature matrices
are read once per tile row/column — the window is re-interpolated every
surrogate round, so this runs at controller frequency.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

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


def _fused_interp_kernel(q_ref, m_ref, yw_ref, mean_ref, dmin_ref, *,
                         kind, length_scale, idw_power, eps):
    q = q_ref[...].astype(jnp.float32)            # (bq, Fp)
    m = m_ref[...].astype(jnp.float32)            # (Mp, Fp)
    yw = yw_ref[...].astype(jnp.float32)          # (8, Mp): rows 0=y, 1=w
    y = yw[0, :]
    w = yw[1, :]
    qq = jnp.sum(q * q, axis=1, keepdims=True)    # (bq, 1)
    mm = jnp.sum(m * m, axis=1)                   # (Mp,)
    g = jax.lax.dot_general(
        q, m, (((1,), (1,)), ((), ())), precision=_F32_DOT,
        preferred_element_type=jnp.float32)       # (bq, Mp)
    d2 = jnp.maximum(qq + mm[None, :] - 2.0 * g, 0.0)
    if kind == "rbf":
        k = jnp.exp(-d2 / (2.0 * length_scale * length_scale))
    else:                                         # "idw" (Shepard)
        k = 1.0 / (d2 ** (idw_power / 2.0) + eps)
    k = k * w[None, :]
    wsum = jnp.sum(k, axis=1)                     # (bq,)
    ky = jnp.sum(k * y[None, :], axis=1)          # (bq,)
    # recency-weighted global mean as the far-field fallback
    fallback = jnp.sum(y * w) / jnp.maximum(jnp.sum(w), 1e-12)
    mean = jnp.where(wsum > 1e-12,
                     ky / jnp.maximum(wsum, 1e-12), fallback)
    dmin = jnp.sqrt(jnp.min(d2, axis=1))
    mean_ref[...] = jnp.broadcast_to(mean[:, None], mean_ref.shape)
    dmin_ref[...] = jnp.broadcast_to(dmin[:, None], dmin_ref.shape)


def fused_interp(xq, xm, y, w_rec, *, kind: str = "idw",
                 length_scale: float = 0.25, idw_power: float = 2.0,
                 eps: float = 1e-9, block_q: int = 128,
                 interpret: bool | None = None):
    """Fused surrogate refit: distance + recency-weighted reduction in
    one pass over the measurement axis.

    xq (Q, F) query features, xm (M, F) measurement features, y (M,)
    objectives, w_rec (M,) recency weights -> (mean (Q,), dmin (Q,))
    fp32 — the IDW/RBF estimate (recency-weighted global mean as the
    far-field fallback) and the nearest-measurement distance.  Compared
    with the :func:`pairwise_sqdist` + jnp-reduction composition this
    never materializes the (Q, M) distance matrix in HBM: each query
    block reads the measurement rows once and reduces in VMEM.

    M is padded to the 128-lane width with rows at the far sentinel and
    zero y/weight (exactly-zero kernel contribution, never the nearest),
    so callers holding pow-2-bucketed device stores can pass slices
    without re-padding.  ``kind``/``length_scale``/``idw_power``/``eps``
    are Python-static (baked into the trace).
    """
    Q, F = xq.shape
    M, F2 = xm.shape
    if F != F2:
        raise ValueError(f"feature dims differ: {F} vs {F2}")
    if kind not in ("idw", "rbf"):
        raise ValueError(f"unknown interp kind {kind!r}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    bq = min(block_q, max(Q, 8))
    Qp = -(-Q // bq) * bq
    Mp = -(-M // 128) * 128
    Fp = -(-F // 128) * 128

    xq_p = jnp.zeros((Qp, Fp), jnp.float32).at[:Q, :F].set(
        xq.astype(jnp.float32))
    xm_p = jnp.zeros((Mp, Fp), jnp.float32)
    xm_p = xm_p.at[M:, 0].set(_PAD_SENTINEL)
    xm_p = xm_p.at[:M, :F].set(xm.astype(jnp.float32))
    yw = jnp.zeros((8, Mp), jnp.float32)
    yw = yw.at[0, :M].set(y.astype(jnp.float32))
    yw = yw.at[1, :M].set(w_rec.astype(jnp.float32))

    kern = functools.partial(
        _fused_interp_kernel, kind=kind, length_scale=float(length_scale),
        idw_power=float(idw_power), eps=float(eps))
    mean, dmin = pl.pallas_call(
        kern,
        grid=(Qp // bq,),
        in_specs=[
            pl.BlockSpec((bq, Fp), lambda i: (i, 0)),
            pl.BlockSpec((Mp, Fp), lambda i: (0, 0)),
            pl.BlockSpec((8, Mp), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, 128), lambda i: (i, 0)),
            pl.BlockSpec((bq, 128), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qp, 128), jnp.float32),
            jax.ShapeDtypeStruct((Qp, 128), jnp.float32),
        ],
        interpret=interpret,
    )(xq_p, xm_p, yw)
    return mean[:Q, 0], dmin[:Q, 0]
