"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
in interpret mode on the CPU.  Compiling for the TPU target is checked by
``tests/test_tpu_compile.py``; ``chip_smoke.py`` runs the main-path
kernels on the chip against the same oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand(key, shape, dtype, scale=1.0):
    return (scale * jax.random.normal(key, shape)).astype(dtype)


def _tol(dtype):
    return dict(atol=0.03, rtol=0.05) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# Flash attention: kinds x shapes x dtypes.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind,window", [
    ("causal", 0), ("window", 64), ("chunk", 64), ("bidir", 0)])
@pytest.mark.parametrize("B,H,K,S,hd", [
    (1, 2, 1, 128, 64),     # MQA
    (2, 4, 2, 256, 64),     # GQA
    (1, 2, 2, 192, 128),    # MHA, odd-ish seq (block < S, S % 64 == 0)
])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_matches_ref(kind, window, B, H, K, S, hd, dtype):
    ks = jax.random.split(jax.random.key(B * S + hd), 3)
    q = _rand(ks[0], (B, S, H, hd), dtype)
    k = _rand(ks[1], (B, S, K, hd), dtype)
    v = _rand(ks[2], (B, S, K, hd), dtype)
    out = ops.flash_attention(q, k, v, kind, window)
    want = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), kind=kind, window=window
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        **_tol(dtype))


def test_flash_attention_softcap():
    ks = jax.random.split(jax.random.key(7), 3)
    q = _rand(ks[0], (1, 128, 2, 64), jnp.bfloat16, 2.0)
    k = _rand(ks[1], (1, 128, 2, 64), jnp.bfloat16, 2.0)
    v = _rand(ks[2], (1, 128, 2, 64), jnp.bfloat16)
    out = ops.flash_attention(q, k, v, "causal", 0, softcap=20.0)
    want = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), kind="causal", softcap=20.0
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=0.03, rtol=0.05)


def test_flash_attention_block_size_invariance():
    ks = jax.random.split(jax.random.key(11), 3)
    q = _rand(ks[0], (1, 512, 2, 64), jnp.float32)
    k = _rand(ks[1], (1, 512, 1, 64), jnp.float32)
    v = _rand(ks[2], (1, 512, 1, 64), jnp.float32)
    from repro.kernels.flash_attention import flash_attention as fa
    qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    o1 = fa(qt, kt, vt, kind="causal", block_q=512, block_k=512)
    o2 = fa(qt, kt, vt, kind="causal", block_q=128, block_k=256)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=2e-5, rtol=1e-4)


def test_flash_trainable_grads_match_reference():
    ks = jax.random.split(jax.random.key(3), 3)
    q = _rand(ks[0], (1, 128, 2, 64), jnp.float32)
    k = _rand(ks[1], (1, 128, 1, 64), jnp.float32)
    v = _rand(ks[2], (1, 128, 1, 64), jnp.float32)

    def loss_k(q, k, v):
        return jnp.sum(ops.flash_attention_trainable(
            q, k, v, "causal", 0, 0.0).astype(jnp.float32) ** 2)

    def loss_r(q, k, v):
        o = ref.flash_attention_ref(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), kind="causal").transpose(0, 2, 1, 3)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# Flash decode.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,K,G,S,hd", [
    (2, 2, 3, 1024, 64), (1, 1, 8, 2048, 128), (4, 2, 1, 512, 64)])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_decode_matches_ref(B, K, G, S, hd, dtype):
    ks = jax.random.split(jax.random.key(S + hd), 3)
    q = _rand(ks[0], (B, 1, K * G, hd), dtype)
    kc = _rand(ks[1], (B, S, K, hd), dtype)
    vc = _rand(ks[2], (B, S, K, hd), dtype)
    lens = jnp.linspace(S // 3, S, B).astype(jnp.int32)
    valid = jnp.arange(S)[None, :] < lens[:, None]
    out = ops.flash_decode(q, kc, vc, valid)
    want = ref.flash_decode_ref(
        q[:, 0].reshape(B, K, G, hd), kc.transpose(0, 2, 1, 3),
        vc.transpose(0, 2, 1, 3), valid).reshape(B, 1, K * G, hd)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_decode_ref_matches_model_decode_attend():
    """Kernel oracle == the model's decode_attend math."""
    from repro.models.attention import AttnSpec, decode_attend
    B, K, G, S, hd = 2, 2, 2, 256, 32
    ks = jax.random.split(jax.random.key(0), 3)
    q = _rand(ks[0], (B, 1, K * G, hd), jnp.float32)
    kc = _rand(ks[1], (B, S, K, hd), jnp.float32)
    vc = _rand(ks[2], (B, S, K, hd), jnp.float32)
    valid = jnp.arange(S)[None, :] < jnp.array([[100], [256]])
    spec = AttnSpec(d_model=K * G * hd, n_heads=K * G, n_kv_heads=K,
                    head_dim=hd, tp=1)
    want = decode_attend(q, kc, vc, valid, spec)
    out = ref.flash_decode_ref(q[:, 0].reshape(B, K, G, hd),
                               kc.transpose(0, 2, 1, 3),
                               vc.transpose(0, 2, 1, 3),
                               valid).reshape(B, 1, K * G, hd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# RG-LRU scan.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,R", [(2, 512, 256), (1, 256, 128),
                                   (3, 128, 384)])
def test_rglru_scan_matches_ref(B, S, R):
    ks = jax.random.split(jax.random.key(S + R), 2)
    a = jnp.exp(-jnp.abs(_rand(ks[0], (B, S, R), jnp.float32, 0.5)))
    b = _rand(ks[1], (B, S, R), jnp.float32, 0.5)
    out = ops.rglru_scan(a, b)
    want = ref.rglru_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-4)


def test_rglru_kernel_plugs_into_model_block():
    """Kernel as scan_fn inside the Griffin block == jnp scan path."""
    from repro.models import rglru
    from repro.models.common import split_boxes
    spec = rglru.RGLRUSpec(d_model=128, d_rnn=128, conv_width=4)
    params, _ = split_boxes(rglru.init_rglru(jax.random.key(0), spec))
    x = _rand(jax.random.key(1), (2, 64, 128), jnp.bfloat16)

    def kernel_scan(p, rec):
        log_a, gated = rglru._gates(p, rec)
        a = jnp.exp(log_a)
        beta = jnp.exp(0.5 * jnp.log1p(-jnp.exp(2.0 * log_a) + 1e-12))
        return ops.rglru_scan(a, beta * gated).astype(rec.dtype)

    want = rglru.rglru_block_fwd(params, x, spec)
    out = rglru.rglru_block_fwd(params, x, spec, scan_fn=kernel_scan)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=0.03, rtol=0.05)


# ---------------------------------------------------------------------------
# RWKV6 wkv.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,H,S,hd,chunk", [
    (2, 2, 128, 64, 64), (1, 4, 256, 64, 32), (2, 1, 64, 128, 64)])
def test_wkv6_kernel_matches_sequential_ref(B, H, S, hd, chunk):
    ks = jax.random.split(jax.random.key(S + hd), 4)
    r = _rand(ks[0], (B, S, H, hd), jnp.float32, 0.5)
    k = _rand(ks[1], (B, S, H, hd), jnp.float32, 0.5)
    v = _rand(ks[2], (B, S, H, hd), jnp.float32, 0.5)
    logw = -jnp.exp(_rand(ks[3], (B, S, H, hd), jnp.float32, 0.5) - 2.0)
    u = _rand(jax.random.key(9), (H, hd), jnp.float32, 0.3)
    out = ops.wkv6(r, k, v, logw, u, chunk=chunk)
    want = ref.wkv6_ref(
        r.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), logw.transpose(0, 2, 1, 3), u
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=5e-4, rtol=1e-3)


def test_wkv6_model_chunked_matches_sequential_ref():
    """The model's chunked formulation == sequential recurrence."""
    from repro.models.rwkv6 import wkv6_chunked
    B, H, S, hd = 1, 2, 96, 32
    ks = jax.random.split(jax.random.key(2), 4)
    r = _rand(ks[0], (B, S, H, hd), jnp.float32, 0.5)
    k = _rand(ks[1], (B, S, H, hd), jnp.float32, 0.5)
    v = _rand(ks[2], (B, S, H, hd), jnp.float32, 0.5)
    logw = -jnp.exp(_rand(ks[3], (B, S, H, hd), jnp.float32, 0.5) - 2.0)
    u = _rand(jax.random.key(5), (H, hd), jnp.float32, 0.3)
    out = wkv6_chunked(r, k, v, logw, u, chunk=32)
    want = ref.wkv6_ref(
        r.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), logw.transpose(0, 2, 1, 3), u
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=5e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# int8 quantizer.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("M,N", [(64, 384), (256, 128), (8, 1024)])
def test_quantize_kernel_matches_ref(M, N):
    x = _rand(jax.random.key(M + N), (M, N), jnp.float32, 3.0)
    q, s = ops.quantize_int8(x)
    qr, sr = ref.quantize_int8_ref(x)
    assert bool(jnp.all(q == qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)


def test_quantize_roundtrip_error_bounded():
    x = _rand(jax.random.key(1), (128, 512), jnp.float32, 5.0)
    q, s = ops.quantize_int8(x)
    deq = np.asarray(q, np.float32) * np.asarray(s)
    # per-row max error <= scale/2 (round-to-nearest)
    err = np.abs(deq - np.asarray(x))
    assert (err <= np.asarray(s) * 0.505 + 1e-6).all()


# ---------------------------------------------------------------------------
# Direct kernel-module entry points, no ops layout adapters: each Pallas
# kernel against its jnp oracle in the kernel's native layout — the
# tolerance contract repro.analysis.jaxlint's kernel-ref pairing rule
# requires for every kernel in the package.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind,window", [("causal", 0), ("window", 64)])
def test_flash_attention_kernel_direct_vs_ref(kind, window):
    from repro.kernels.flash_attention import flash_attention as fa
    B, H, K, S, hd = 1, 4, 2, 256, 64
    ks = jax.random.split(jax.random.key(21), 3)
    q = _rand(ks[0], (B, H, S, hd), jnp.float32)
    k = _rand(ks[1], (B, K, S, hd), jnp.float32)
    v = _rand(ks[2], (B, K, S, hd), jnp.float32)
    out = fa(q, k, v, kind=kind, window=window)
    want = ref.flash_attention_ref(q, k, v, kind=kind, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_flash_decode_kernel_direct_vs_ref():
    from repro.kernels.decode_attention import flash_decode as fd
    B, K, G, S, hd = 2, 2, 4, 512, 64
    ks = jax.random.split(jax.random.key(23), 3)
    q = _rand(ks[0], (B, K, G, hd), jnp.float32)
    kc = _rand(ks[1], (B, K, S, hd), jnp.float32)
    vc = _rand(ks[2], (B, K, S, hd), jnp.float32)
    valid = jnp.arange(S)[None, :] < jnp.array([[200], [512]])
    out = fd(q, kc, vc, valid, block_s=128)
    want = ref.flash_decode_ref(q, kc, vc, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_rglru_kernel_direct_block_sweep():
    from repro.kernels.rglru_scan import rglru_scan as rg
    B, S, R = 2, 512, 256
    ks = jax.random.split(jax.random.key(29), 2)
    a = jnp.exp(-jnp.abs(_rand(ks[0], (B, S, R), jnp.float32, 0.5)))
    b = _rand(ks[1], (B, S, R), jnp.float32, 0.5)
    want = ref.rglru_scan_ref(a, b)
    for block_r, block_s in ((128, 256), (256, 128), (128, 512)):
        out = rg(a, b, block_r=block_r, block_s=block_s)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=1e-5, rtol=1e-4)


def test_wkv6_kernel_direct_vs_ref():
    from repro.kernels.rwkv6_wkv import wkv6 as wkv
    B, H, S, hd = 1, 2, 128, 64
    ks = jax.random.split(jax.random.key(31), 4)
    r = _rand(ks[0], (B, H, S, hd), jnp.float32, 0.5)
    k = _rand(ks[1], (B, H, S, hd), jnp.float32, 0.5)
    v = _rand(ks[2], (B, H, S, hd), jnp.float32, 0.5)
    logw = -jnp.exp(_rand(ks[3], (B, H, S, hd), jnp.float32, 0.5) - 2.0)
    u = _rand(jax.random.key(33), (H, hd), jnp.float32, 0.3)
    out = wkv(r, k, v, logw, u, chunk=32)
    want = ref.wkv6_ref(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=5e-4, rtol=1e-3)


@pytest.mark.parametrize("kind", ["idw", "rbf"])
@pytest.mark.parametrize("Q,M,F", [
    (5, 3, 7),          # tiny, everything padded
    (300, 37, 9),       # row counts straddling the query block
    (130, 256, 130),    # feature dim over one lane width, M at a lane edge
])
def test_fused_interp_kernel_direct_vs_ref(kind, Q, M, F):
    from repro.kernels.surrogate_distance import fused_interp
    rng = np.random.default_rng(Q + M + F)
    xq = jnp.asarray(rng.normal(size=(Q, F)), jnp.float32)
    xm = jnp.asarray(rng.normal(size=(M, F)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(M,)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(M,)), jnp.float32)
    mean, dmin = fused_interp(xq, xm, y, w, kind=kind)
    want_mean, want_dmin = ref.fused_interp_ref(xq, xm, y, w, kind=kind)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(want_mean),
                               atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(dmin), np.asarray(want_dmin),
                               atol=2e-5, rtol=1e-4)


def test_fused_interp_zero_weight_rows_contribute_nothing():
    """The pow-2-bucket padding contract: rows with zero recency weight
    (the device store's empty slots) must not shift the estimate, and
    all-zero weights fall back to the recency-weighted global mean."""
    from repro.kernels.surrogate_distance import fused_interp
    rng = np.random.default_rng(7)
    xq = jnp.asarray(rng.normal(size=(17, 5)), jnp.float32)
    xm = jnp.asarray(rng.normal(size=(12, 5)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(12,)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.2, 1.0, size=(12,)), jnp.float32)
    base_mean, _ = fused_interp(xq, xm, y, w)
    # append dead rows: far features, arbitrary y, zero weight
    xm_pad = jnp.concatenate([xm, jnp.full((20, 5), 1e3, jnp.float32)])
    y_pad = jnp.concatenate([y, jnp.full((20,), 99.0, jnp.float32)])
    w_pad = jnp.concatenate([w, jnp.zeros((20,), jnp.float32)])
    pad_mean, _ = fused_interp(xq, xm_pad, y_pad, w_pad)
    np.testing.assert_allclose(np.asarray(pad_mean), np.asarray(base_mean),
                               atol=2e-5, rtol=1e-4)


def test_kernel_ref_pairing_is_complete():
    """Every Pallas kernel in repro.kernels has a jnp oracle in ref.py, a
    tolerance test in this directory and an export in the package
    __all__ — the same invariant `python -m repro.analysis.run --lint`
    gates on (rule: kernel-ref-pairing)."""
    from pathlib import Path

    import repro
    from repro.analysis.jaxlint import Linter

    # repro is a namespace package: locate it via __path__
    src_root = Path(next(iter(repro.__path__)))
    tests_dir = Path(__file__).parent
    findings = [f for f in Linter(src_root).run(tests_dir=tests_dir)
                if f.rule == "kernel-ref-pairing"]
    assert not findings, "\n".join(f.message for f in findings)
