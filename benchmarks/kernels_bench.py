"""Kernel microbenchmarks + BlockSpec/VMEM roofline accounting.

Wall time here is interpret-mode (CPU emulation) — meaningful only for
relative comparisons; the ``derived`` column carries the TPU-relevant
numbers: VMEM working set per BlockSpec tile and arithmetic intensity,
vs the v5e ridge point (197e12 / 819e9 = 241 FLOP/B)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bpbs import BpbsConfig, weight_planes
from repro.kernels.cima_mvm import cima_mvm
from repro.kernels.flash_attention import flash_attention

from .common import emit, time_call

V5E_RIDGE = 197e12 / 819e9


def cima_vmem_bytes(bank_n, block_b, block_m, bx, ba):
    x_tile = block_b * bx * bank_n          # int8
    w_tile = bank_n * ba * block_m          # int8
    out = block_b * block_m * 4
    return x_tile + w_tile + out


def run():
    rng = np.random.default_rng(0)
    # --- cima_mvm: chip-shaped tile (the CIMA itself: 2304 x 256)
    for (ba, bx, n, m, bb, bm) in ((1, 1, 2304, 256, 64, 128),
                                   (4, 4, 2304, 64, 32, 64)):
        x = jnp.asarray(2 * rng.integers(-4, 5, (bb, n)), jnp.float32)
        w = jnp.asarray(2 * rng.integers(-4, 5, (n, m)), jnp.float32)
        cfg = BpbsConfig(ba=ba, bx=bx)
        ws = weight_planes(w, cfg).astype(jnp.int8)   # stationary weights
        us = time_call(lambda x=x, ws=ws, cfg=cfg, bb=bb, bm=bm: cima_mvm(
            x, ws, cfg, block_b=bb, block_m=bm), iters=3, warmup=1)
        flops = 2.0 * bb * n * m * ba * bx
        vmem = cima_vmem_bytes(cfg.bank_n, bb, bm, bx, ba)
        hbm = bb * bx * n + n * ba * m + bb * m * 4
        ai = flops / hbm
        emit(f"kernel_cima_mvm_Ba{ba}_Bx{bx}", us,
             f"vmem_tile_bytes={vmem};arith_intensity={ai:.0f};"
             f"ridge={V5E_RIDGE:.0f};bound={'compute' if ai > V5E_RIDGE else 'memory'}")

    # --- flash attention: 32k-feasibility tile accounting
    b, h, s, d = 1, 2, 512, 128
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(b, 1, s, d)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(b, 1, s, d)), jnp.bfloat16)
    us = time_call(lambda: flash_attention(q, k, v, block_q=128,
                                           block_k=128),
                   iters=3, warmup=1)
    bq = bk = 128
    vmem = (bq * d + 2 * bk * d) * 2 + bq * d * 4 + bq * (4 + 4)
    # full-seq dense scores at 32k would be:
    dense_32k = 32768 * 32768 * 2
    emit("kernel_flash_attention", us,
         f"vmem_tile_bytes={vmem};dense_scores_32k_bytes={dense_32k};"
         f"ratio={dense_32k // vmem}x")
