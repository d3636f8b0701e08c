"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

These share math with the model modules (repro.models.attention /
rglru / rwkv6) — the kernels are drop-in replacements for exactly these
functions on the TPU target.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models.attention import AttnSpec


def flash_attention_ref(q, k, v, *, kind: str = "causal", window: int = 0,
                        softcap: float = 0.0):
    """q (B,H,S,hd), k/v (B,K,S,hd) -> (B,H,S,hd); full-score softmax."""
    B, H, Sq, hd = q.shape
    K = k.shape[1]
    spec = AttnSpec(d_model=H * hd, n_heads=H, n_kv_heads=K, head_dim=hd,
                    kind=kind, window=window, logit_softcap=softcap,
                    use_rope=False, tp=1)
    # model layout is (B, S, H, hd)
    out = attn_mod._attend_dense(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), spec)
    return out.transpose(0, 2, 1, 3)


def flash_decode_ref(q, k_cache, v_cache, valid_mask, *,
                     softcap: float = 0.0):
    """q (B,K,G,hd); caches (B,K,S,hd); valid (B,S) -> (B,K,G,hd)."""
    s = jnp.einsum("bkgd,bksd->bkgs", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32))
    s = s / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(valid_mask[:, None, None, :], s, -2e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", w, v_cache.astype(jnp.float32))
    return out.astype(q.dtype)


def rglru_scan_ref(a, b):
    """h_t = a_t h_{t-1} + b_t via associative scan (B, S, R)."""
    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    _, h = jax.lax.associative_scan(combine, (af, bf), axis=1)
    return h.astype(a.dtype)


def wkv6_ref(r, k, v, logw, u):
    """Sequential-exact RWKV6 recurrence.  r/k/v/logw (B,H,S,hd); u (H,hd).

    Returns (B,H,S,hd) fp32.
    """
    B, H, S, hd = r.shape

    def step(S_prev, inp):
        rt, kt, vt, lwt = inp                       # (B,H,hd)
        kv = jnp.einsum("bhk,bhv->bhkv", kt, vt)
        o = jnp.einsum("bhk,bhkv->bhv", rt,
                       S_prev + u[None, :, :, None] * kv)
        S_new = jnp.exp(lwt)[..., None] * S_prev + kv
        return S_new, o

    rs = r.astype(jnp.float32).transpose(2, 0, 1, 3)
    ks = k.astype(jnp.float32).transpose(2, 0, 1, 3)
    vs = v.astype(jnp.float32).transpose(2, 0, 1, 3)
    lws = logw.astype(jnp.float32).transpose(2, 0, 1, 3)
    S0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    _, os = jax.lax.scan(step, S0, (rs, ks, vs, lws))
    return os.transpose(1, 2, 0, 3)


def quantize_int8_ref(x):
    from repro.optim.compression import quantize_int8 as q
    return q(x)


def pairwise_sqdist_ref(xq, xm):
    """xq (Q, F), xm (M, F) -> (Q, M) squared Euclidean distances, with
    full-f32 matmuls (the TPU's default precision rounds to bfloat16)."""
    xq = xq.astype(jnp.float32)
    xm = xm.astype(jnp.float32)
    qq = jnp.sum(xq * xq, axis=1, keepdims=True)
    mm = jnp.sum(xm * xm, axis=1, keepdims=True)
    g = jnp.matmul(xq, xm.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(qq + mm.T - 2.0 * g, 0.0)


def fused_interp_ref(xq, xm, y, w_rec, *, kind: str = "idw",
                     length_scale: float = 0.25, idw_power: float = 2.0,
                     eps: float = 1e-9):
    """Fused surrogate refit: distance + recency-weighted IDW/RBF
    reduction in one pass; mirrors
    :func:`repro.kernels.surrogate_distance.fused_interp`.

    xq (Q, F), xm (M, F), y (M,), w_rec (M,) -> (mean (Q,), dmin (Q,)),
    fp32.  ``mean`` is the kernel-weighted estimate with the
    recency-weighted global mean as the far-field fallback; ``dmin`` the
    distance to the nearest measurement (the uncertainty channel, before
    objective-unit scaling).
    """
    d2 = pairwise_sqdist_ref(xq, xm)                        # (Q, M)
    if kind == "rbf":
        k = jnp.exp(-d2 / (2.0 * length_scale**2))
    else:                                                   # "idw" (Shepard)
        k = 1.0 / (d2 ** (idw_power / 2.0) + eps)
    y32 = y.astype(jnp.float32)
    w32 = w_rec.astype(jnp.float32)
    k = k * w32[None, :]
    wsum = k.sum(axis=1)
    fallback = (y32 * w32).sum() / jnp.maximum(w32.sum(), 1e-12)
    mean = jnp.where(wsum > 1e-12,
                     jnp.matmul(k, y32, precision=jax.lax.Precision.HIGHEST)
                     / jnp.maximum(wsum, 1e-12), fallback)
    dmin = jnp.sqrt(d2.min(axis=1))
    return mean, dmin


def sizing_latency_ref(lam, mu, repl, visit_w, adj, *, c_max: int,
                       sat_s: float = 1e4):
    """M/M/c sojourns + DAG critical path; mirrors
    :func:`repro.kernels.sizing_latency.sizing_latency`.

    lam/mu/repl/visit_w (B, K) -> (sojourn (B, K), path (B, K)), fp32.
    Erlang C through the in-[0, 1] Erlang-B recurrence; unstable cells
    (lam >= repl * mu) saturate to ``sat_s``; ``path[:, v]`` is the
    heaviest visit-weighted path of the sub-DAG rooted at v.
    """
    lam = lam.astype(jnp.float32)
    mu = mu.astype(jnp.float32)
    c = repl.astype(jnp.float32)
    w = visit_w.astype(jnp.float32)
    a = lam / mu
    b = jnp.ones_like(a)
    b_c = jnp.zeros_like(a)
    for k in range(1, int(c_max) + 1):
        # plain int `k`: weakly-typed, promotes to the array dtype without
        # a host float() coercion (jaxlint host-coercion-in-jit)
        b = a * b / (k + a * b)
        b_c = jnp.where(c == k, b, b_c)
    rho = a / jnp.maximum(c, 1.0)
    p_wait = b_c / jnp.maximum(1.0 - rho * (1.0 - b_c), 1e-12)
    slack = c * mu - lam
    soj = jnp.where(slack > 1e-9,
                    p_wait / jnp.maximum(slack, 1e-12) + 1.0 / mu,
                    jnp.float32(sat_s))
    node = w * soj
    edges = jnp.asarray(adj, bool)
    latency = node
    for _ in range(lam.shape[1]):
        masked = jnp.where(edges[None, :, :], latency[:, None, :], -1e30)
        latency = node + jnp.maximum(jnp.max(masked, axis=2), 0.0)
    return soj, latency


def sizing_entry_latency_ref(lam, mu, repl, *, visits, edges, entries,
                             c_max: int, sat_s: float = 1e4):
    """:func:`sizing_latency_ref` on the inputs of
    :func:`repro.kernels.sizing_latency.sizing_latency`: lam (K,),
    mu/repl (K, B), static visits (C, K), (caller, callee) edges and
    entry tiers -> (C, B) entry-tier critical paths, fp32.

    Folds every class into its own row (row b * C + c) and relaxes the
    dense adjacency ``K`` times — the kernel's sweep shares none of it.
    """
    K, B = mu.shape
    w = jnp.asarray(visits, jnp.float32)                   # (C, K)
    C = w.shape[0]
    adj = jnp.zeros((K, K), bool)
    for v, u in edges:
        adj = adj.at[v, u].set(True)
    _, path = sizing_latency_ref(
        jnp.broadcast_to(lam, (B * C, K)), jnp.repeat(mu.T, C, axis=0),
        jnp.repeat(repl.T, C, axis=0), jnp.tile(w, (B, 1)), adj,
        c_max=c_max, sat_s=sat_s)
    entry = jnp.asarray(entries, jnp.int32)
    return path.reshape(B, C, K)[:, jnp.arange(C), entry].T
