"""Pallas TPU kernel: M/M/c tier sojourns + DAG critical-path latency.

The container-sizing evaluator's hot spot is scoring B candidate sizings
(states) of a K-tier microservice DAG in one shot: for every (tier,
state) cell, an Erlang-C M/M/c sojourn (queue wait + service) from the
tier's arrival rate, per-replica service rate and replica count; then,
per request class, the visit-weighted *critical path* from the class's
entry tier — the heaviest entry-to-leaf path where each node costs
``visits x sojourn`` and parallel fan-out composes by max (sequential
chains by sum).  Jackson's independence approximation makes the
per-tier queues separable, so the whole thing is elementwise VPU work.

Layout: states on lanes, tiers on sublanes.  Inputs are (K, B) with B
on the lane axis, so each tier's sojourn is computed once per state (not
once per class) and no lane is padding; the per-tier arrival rates (K,)
are the only traced per-call input besides them.

Erlang C is computed through the Erlang-B blocking recurrence

    B_0 = 1,   B_k = a B_{k-1} / (k + a B_{k-1}),
    C(c, a) = B_c / (1 - rho (1 - B_c)),   rho = a / c,

which stays in [0, 1] throughout — no a^c / c! overflow — and costs one
fused multiply-divide per replica step up to the static ``c_max``.
Unstable cells (lambda >= c mu) saturate to ``sat_s`` seconds, a finite
cliff the annealing acceptance rule can walk off of.

The critical path is one reverse-topological sweep over the DAG's static
edge list, all classes at once (classes on sublanes):

    L[c, v] = w[c, v] * T[v] + max(0, max_{(v,u) in E} L[c, u]),
    v = K-1 .. 0

exact because the tiers are topologically ordered (every edge points to
a later tier); class c's latency is L at its entry tier.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _sizing_kernel(lam_ref, mu_ref, repl_ref, out_ref, *, c_max: int,
                   sat_s: float, visits, children, entries):
    lam = lam_ref[...].astype(jnp.float32)        # (K, 1)
    mu = mu_ref[...].astype(jnp.float32)          # (K, block_b)
    c = repl_ref[...].astype(jnp.float32)

    a = lam / mu                                   # offered load (Erlangs)
    b = jnp.ones_like(a)
    b_c = jnp.zeros_like(a)
    for k in range(1, c_max + 1):
        b = a * b / (k + a * b)
        b_c = jnp.where(c == k, b, b_c)
    rho = a / jnp.maximum(c, 1.0)
    p_wait = b_c / jnp.maximum(1.0 - rho * (1.0 - b_c), 1e-12)
    slack = c * mu - lam                           # spare service capacity
    soj = jnp.where(slack > 1e-9,
                    p_wait / jnp.maximum(slack, 1e-12) + 1.0 / mu,
                    sat_s)

    cls = jax.lax.broadcasted_iota(jnp.int32, (len(visits), 1), 0)

    def per_class(vals):
        # static per-class scalars -> (C, 1) column, without a constant
        # operand
        col = jnp.full((len(vals), 1), vals[0], jnp.float32)
        for ci, x in enumerate(vals[1:], 1):
            col = jnp.where(cls == ci, x, col)
        return col

    path = [None] * len(children)
    for v in reversed(range(len(children))):
        # visit-weighted node cost, (C, block_b)
        node = per_class([w[v] for w in visits]) * soj[v:v + 1, :]
        if children[v]:
            child = path[children[v][0]]
            for u in children[v][1:]:
                child = jnp.maximum(child, path[u])
            node = node + jnp.maximum(child, 0.0)
        path[v] = node
    out = path[entries[0]]
    for ci, e in enumerate(entries):
        if e != entries[0]:
            out = jnp.where(cls == ci, path[e], out)
    out_ref[...] = out


def sizing_latency(lam, mu, repl, *, visits, edges, entries, c_max: int,
                   sat_s: float = 1e4, block_b: int = 512,
                   interpret: bool | None = None):
    """lam (K,), mu/repl (K, B) fp32 -> entry latency (C, B) fp32.

    ``lam`` is the tier arrival rate, ``mu`` the per-replica service rate
    of each (tier, state) (must be > 0), ``repl`` the integer replica
    count as float (1 <= repl <= c_max).  The DAG is static: ``visits``
    the (C, K) per-class visit weights, ``edges`` (caller, callee) tier
    index pairs with caller < callee (tiers topologically ordered),
    ``entries`` each class's entry tier.  ``out[c, b]`` is class c's
    weighted critical path from its entry tier in state b.  States are
    padded to a ``block_b`` multiple with load-free cells (mu 1, repl 1),
    sliced off on return.
    """
    K, B = mu.shape
    if repl.shape != (K, B):
        raise ValueError(f"repl shape {repl.shape} != {(K, B)}")
    if lam.shape != (K,):
        raise ValueError(f"lam shape {lam.shape} != {(K,)}")
    visits = tuple(tuple(float(x) for x in row) for row in visits)
    entries = tuple(int(e) for e in entries)
    C = len(visits)
    if any(len(row) != K for row in visits) or len(entries) != C or not C:
        raise ValueError(f"visits must be (C, {K}) with one entry a class")
    if any(not 0 <= e < K for e in entries):
        raise ValueError(f"entry tiers {entries} out of range({K})")
    children: list[list[int]] = [[] for _ in range(K)]
    for v, u in edges:
        if not 0 <= v < u < K:
            raise ValueError(f"edge ({v}, {u}) is not caller < callee < K")
        children[v].append(int(u))
    if c_max < 1:
        raise ValueError("c_max must be >= 1")
    if block_b < 128 or block_b % 128:
        raise ValueError("block_b must be a positive multiple of 128")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    Bp = -(-B // block_b) * block_b

    def pad(x):
        x = x.astype(jnp.float32)
        if Bp == B:
            return x
        return jnp.pad(x, ((0, 0), (0, Bp - B)), constant_values=1.0)

    kernel = lambda *refs: _sizing_kernel(
        *refs, c_max=int(c_max), sat_s=float(sat_s), visits=visits,
        children=tuple(map(tuple, children)), entries=entries)
    out = pl.pallas_call(
        kernel,
        grid=(Bp // block_b,),
        in_specs=[
            pl.BlockSpec((K, 1), lambda i: (0, 0)),          # lam
            pl.BlockSpec((K, block_b), lambda i: (0, i)),    # mu
            pl.BlockSpec((K, block_b), lambda i: (0, i)),    # repl
        ],
        out_specs=pl.BlockSpec((C, block_b), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((C, Bp), jnp.float32),
        interpret=interpret,
        name="sizing_latency",
    )(lam.astype(jnp.float32).reshape(K, 1), pad(mu), pad(repl))
    return out[:, :B]
