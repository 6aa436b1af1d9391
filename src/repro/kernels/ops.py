"""Jitted public entry points for the Pallas kernels.

On TPU these run compiled Pallas; in this CPU container they run in
``interpret=True`` mode (the kernel body executed op-by-op), which is how
all correctness tests validate them against the ``ref.py`` oracles.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.core.bpbs import BpbsConfig

from . import cima_mvm as _cima
from . import flash_attention as _fa


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def cima_mvm(
    x_q: jax.Array,
    w_q: jax.Array,
    cfg: BpbsConfig,
    block_b: int = 128,
    block_m: int = 256,
    interpret: Optional[bool] = None,
    escale: Optional[jax.Array] = None,
    pbias: Optional[jax.Array] = None,
    act: Optional[str] = None,
    by_bits: Optional[int] = None,
) -> jax.Array:
    """BP/BS mixed-signal MVM kernel: [..., N] x [N, M] -> [..., M] (f32).
    ``escale``/``pbias``/``act``/``by_bits`` arm the fused near-memory
    datapath epilogue inside the kernel."""
    if interpret is None:
        interpret = not on_tpu()
    return _cima.cima_mvm(x_q, w_q, cfg, block_b, block_m, interpret,
                          escale, pbias, act, by_bits)


def cima_mvm_from_planes(
    x_q: jax.Array,
    ws: jax.Array,
    cfg: BpbsConfig,
    block_b: int = 128,
    block_m: int = 256,
    interpret: Optional[bool] = None,
    escale: Optional[jax.Array] = None,
    pbias: Optional[jax.Array] = None,
    act: Optional[str] = None,
    by_bits: Optional[int] = None,
) -> jax.Array:
    """Weight-stationary kernel entry: ``ws`` [N, BA, M] int8 bit planes
    from a compiled CIMA image; [..., N] inputs -> [..., M] (f32)."""
    if interpret is None:
        interpret = not on_tpu()
    return _cima.cima_mvm_from_planes(x_q, ws, cfg, block_b, block_m,
                                      interpret, escale, pbias, act, by_bits)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    if interpret is None:
        interpret = not on_tpu()
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
