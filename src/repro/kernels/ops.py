"""Jitted public wrappers around the Pallas kernels.

Layout adapters between the model convention (B, S, H, hd) and the kernel
convention (B, H, S, hd), interpret-mode auto-detection (CPU validation vs
TPU execution), and the custom-VJP glue that pairs the kernel forward with
the reference backward for training.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import decode_attention as _dec
from . import flash_attention as _fa
from . import quantize as _q
from . import ref
from . import rglru_scan as _rg
from . import rwkv6_wkv as _wkv
from . import sizing_latency as _sl
from . import surrogate_distance as _sd


@functools.partial(jax.jit, static_argnames=("kind", "window", "softcap"))
def flash_attention(q, k, v, kind: str = "causal", window: int = 0,
                    softcap: float = 0.0):
    """Model layout: q (B,S,H,hd), k/v (B,S,K,hd) -> (B,S,H,hd)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _fa.flash_attention(qt, kt, vt, kind=kind, window=window,
                              softcap=softcap)
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_trainable(q, k, v, kind: str = "causal",
                              window: int = 0, softcap: float = 0.0):
    """Kernel forward + reference backward (jax.custom_vjp).

    The backward recomputes attention with the differentiable reference
    path — flash-style recomputation (no saved S^2 tensors), exactly the
    remat behaviour the roofline's flash adjustment models.
    """
    return flash_attention(q, k, v, kind, window, softcap)


def _fat_fwd(q, k, v, kind, window, softcap):
    return flash_attention(q, k, v, kind, window, softcap), (q, k, v)


def _fat_bwd(kind, window, softcap, res, g):
    q, k, v = res

    def f(q, k, v):
        qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        out = ref.flash_attention_ref(qt, kt, vt, kind=kind, window=window,
                                      softcap=softcap)
        return out.transpose(0, 2, 1, 3)

    _, vjp = jax.vjp(f, q, k, v)
    return vjp(g)


flash_attention_trainable.defvjp(_fat_fwd, _fat_bwd)


@jax.jit
def flash_decode(q, k_cache, v_cache, valid_mask):
    """Model layout: q (B,1,H,hd), caches (B,W,K,hd), valid (B,W).

    Returns (B,1,H,hd).
    """
    B, _, H, hd = q.shape
    K = k_cache.shape[2]
    G = H // K
    qk = q[:, 0].reshape(B, K, G, hd)
    out = _dec.flash_decode(qk, k_cache.transpose(0, 2, 1, 3),
                            v_cache.transpose(0, 2, 1, 3), valid_mask)
    return out.reshape(B, 1, H, hd)


@jax.jit
def rglru_scan(a, b):
    """(B,S,R) decay/input -> (B,S,R) scanned state."""
    return _rg.rglru_scan(a, b)


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv6(r, k, v, logw, u, chunk: int = 64):
    """Model layout r/k/v/logw (B,S,H,hd), u (H,hd) -> (B,S,H,hd) f32."""
    out = _wkv.wkv6(r.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                    v.transpose(0, 2, 1, 3), logw.transpose(0, 2, 1, 3),
                    u, chunk=chunk)
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=(
    "visits", "edges", "entries", "c_max", "sat_s", "block_b"))
def sizing_latency(lam, mu, repl, visits, edges, entries, c_max: int,
                   sat_s: float = 1e4, block_b: int = 512):
    """(K,) tier arrival rates + (K, B) per-state service rates/replicas,
    static DAG (visits (C, K), edges, entries as tuples) -> (C, B) fp32
    entry-tier critical paths (container-sizing M/M/c evaluator)."""
    return _sl.sizing_latency(lam, mu, repl, visits=visits, edges=edges,
                              entries=entries, c_max=c_max, sat_s=sat_s,
                              block_b=block_b)


@functools.partial(jax.jit, static_argnames=("block_q", "block_m"))
def pairwise_sqdist(xq, xm, block_q: int = 256, block_m: int = 256):
    """xq (Q, F), xm (M, F) -> (Q, M) squared distances (surrogate metric)."""
    return _sd.pairwise_sqdist(xq, xm, block_q=block_q, block_m=block_m)


@functools.partial(jax.jit, static_argnames=("kind", "length_scale",
                                             "idw_power", "eps", "block_q"))
def fused_interp(xq, xm, y, w_rec, kind: str = "idw",
                 length_scale: float = 0.25, idw_power: float = 2.0,
                 eps: float = 1e-9, block_q: int = 128):
    """Fused surrogate refit: xq (Q, F), xm (M, F), y (M,), w_rec (M,)
    -> (mean (Q,), dmin (Q,)) fp32 — IDW/RBF estimate plus
    nearest-measurement distance in ONE kernel pass (no (Q, M) distance
    matrix in HBM)."""
    return _sd.fused_interp(xq, xm, y, w_rec, kind=kind,
                            length_scale=length_scale, idw_power=idw_power,
                            eps=eps, block_q=block_q)


@jax.jit
def quantize_int8(x):
    """(..., N) -> (int8 payload, fp32 row scales); rows = leading dims."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    q, s = _q.quantize_int8(x2)
    return q.reshape(shape), s.reshape(shape[:-1] + (1,))
