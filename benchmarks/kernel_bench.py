"""Kernel benchmark: correctness sweep + static VMEM/roofline accounting.

Run on the host it checks (a) numerical equivalence at production tile
shapes, with the kernels in interpret mode, and (b) the static working-set
/ arithmetic-intensity accounting that justifies the BlockSpec choices;
its wall clock is the interpreter's, not a kernel time.  The main-path
kernels are compiled for the TPU v5e target by ``tests/test_tpu_compile.py``
and run on the chip against their references by ``chip_smoke.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from .common import Bench, write_csv

VMEM_BYTES = 16 * 2 ** 20          # v5e ~16 MB VMEM per core


def flash_tile_accounting(block_q=512, block_k=512, hd=128) -> dict:
    tiles = {
        "q": block_q * hd * 2,
        "k": block_k * hd * 2,
        "v": block_k * hd * 2,
        "scores_f32": block_q * block_k * 4,
        "acc_f32": block_q * hd * 4,
        "m_l": 2 * block_q * 128 * 4,
        "o": block_q * hd * 2,
    }
    total = sum(tiles.values())
    flops = 2 * 2 * block_q * block_k * hd          # qk^T + pv
    hbm = tiles["q"] + tiles["k"] + tiles["v"] + tiles["o"]
    return {"tiles": tiles, "total": total, "double_buffered": 2 * total,
            "arith_intensity": flops / hbm}


def kernels() -> dict:
    b = Bench("kernel_bench", "kernels/ (Pallas)")

    acc = flash_tile_accounting()
    b.check(f"flash tiles fit VMEM double-buffered "
            f"({2 * acc['total'] / 2**20:.1f} MiB < 16 MiB)",
            acc["double_buffered"] < VMEM_BYTES)
    b.check(f"flash arithmetic intensity {acc['arith_intensity']:.0f} "
            f"flops/byte > v5e ridge (197e12/819e9 = 241)",
            acc["arith_intensity"] > 241)

    # production tile-shape correctness spot checks (bigger than the
    # test-suite sweep; still CPU-feasible)
    ks = jax.random.split(jax.random.key(0), 3)
    q = (0.5 * jax.random.normal(ks[0], (1, 1024, 4, 128))).astype(jnp.bfloat16)
    k = (0.5 * jax.random.normal(ks[1], (1, 1024, 1, 128))).astype(jnp.bfloat16)
    v = (0.5 * jax.random.normal(ks[2], (1, 1024, 1, 128))).astype(jnp.bfloat16)
    out = ops.flash_attention(q, k, v, "causal")
    want = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), kind="causal").transpose(0, 2, 1, 3)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    b.check(f"flash @ (S=1024, hd=128, GQA4): max err {err:.4f} <= 0.05",
            err <= 0.05)

    rows = [["flash_attention", str({k: f"{v/2**10:.0f}KiB"
                                     for k, v in acc['tiles'].items()}),
             f"{acc['arith_intensity']:.0f}"]]

    # decode kernel at a long-context shard shape
    S = 32768
    ks = jax.random.split(jax.random.key(1), 3)
    qd = (0.5 * jax.random.normal(ks[0], (1, 1, 8, 128))).astype(jnp.bfloat16)
    kc = (0.5 * jax.random.normal(ks[1], (1, S, 1, 128))).astype(jnp.bfloat16)
    vc = (0.5 * jax.random.normal(ks[2], (1, S, 1, 128))).astype(jnp.bfloat16)
    valid = jnp.arange(S)[None, :] < S - 5
    outd = ops.flash_decode(qd, kc, vc, valid)
    wantd = ref.flash_decode_ref(qd[:, 0].reshape(1, 1, 8, 128),
                                 kc.transpose(0, 2, 1, 3),
                                 vc.transpose(0, 2, 1, 3), valid
                                 ).reshape(1, 1, 8, 128)
    errd = float(jnp.max(jnp.abs(outd.astype(jnp.float32)
                                 - wantd.astype(jnp.float32))))
    b.check(f"flash_decode @ 32k cache shard: max err {errd:.4f} <= 0.05",
            errd <= 0.05)
    rows.append(["flash_decode", f"S={S} block_s=1024", f"err={errd:.4f}"])

    write_csv("kernel_bench.csv", ["kernel", "tiles", "metric"], rows)
    return b.finish()


def run_all() -> list[dict]:
    return [kernels()]
