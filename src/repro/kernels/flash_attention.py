"""Pallas TPU kernel: online-softmax (flash) attention.

Needed for the 32k-token prefill shapes: materializing S x S scores at
seq 32768 is ~2 GB per (batch, head) in bf16, far beyond VMEM/HBM budgets;
the online-softmax recurrence keeps the working set at
``(bq x d) + (bq x bk)`` per grid step.

Supports causal masking, GQA (kv heads indexed by ``h // group``), and a
sliding local window (recurrentgemma's local-attention layers).

Grid: ``(batch, heads, q_blocks, kv_blocks)``, kv innermost; running max,
normalizer and weighted accumulator live in VMEM scratch across kv steps.
Fully-masked kv blocks (future blocks under causality, expired blocks
under windowing) are skipped with ``pl.when``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


NEG_INF = -1e30


def _kernel(
    q_ref,            # [1, 1, bq, d]
    k_ref,            # [1, 1, bk, d]
    v_ref,            # [1, 1, bk, d]
    o_ref,            # [1, 1, bq, d]
    m_scr,            # [bq, 1] running max
    l_scr,            # [bq, 1] running normalizer
    acc_scr,          # [bq, d] running weighted sum
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    bq: int,
    bk: int,
    kv_blocks: int,
):
    j = pl.program_id(3)
    i = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # block-level skip: entirely-future (causal) or entirely-expired (window)
    live = True
    if causal:
        live = jnp.logical_and(live, j * bk <= i * bq + bq - 1)
    if window is not None:
        live = jnp.logical_and(live, (j + 1) * bk - 1 >= i * bq - window + 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)                # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # [bq, bk]
        qi = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kj = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), dtype=jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, qi >= kj)
        if window is not None:
            mask = jnp.logical_and(mask, kj > qi - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)       # guard all-masked rows
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(j == kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _pad_seq(x, block, axis):
    pad = (-x.shape[axis]) % block
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k",
                     "interpret"),
)
def flash_attention(
    q: jax.Array,                 # [B, H, Sq, D]
    k: jax.Array,                 # [B, HKV, Sk, D]
    v: jax.Array,                 # [B, HKV, Sk, D]
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Online-softmax attention, [B, H, Sq, D] -> [B, H, Sq, D].
    ``interpret=None`` compiles the kernel on a TPU and interprets it
    elsewhere."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert h % hkv == 0, "GQA requires heads % kv_heads == 0"
    group = h // hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    # lane-align the head dim; zero-padding is exact for dot products
    dp = -(-d // 128) * 128
    if dp != d:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, dp - d)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, dp - d)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dp - d)))
    q = _pad_seq(q, block_q, 2)
    # padded kv rows would attend as real keys: mask via NEG_INF is handled by
    # the causal/window mask only, so require exact kv blocking instead
    k = _pad_seq(k, block_k, 2)
    v = _pad_seq(v, block_k, 2)
    sqp, skp = q.shape[2], k.shape[2]
    # padded keys sit at positions >= sk; with sq == sk and causal masking
    # every real query has qi < sk <= kj, so they are masked exactly.
    assert skp == sk or (causal and sq == sk), (
        "kv padding requires causal self-attention (else pass seq_k % block_k == 0)")
    kv_blocks = skp // block_k

    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, causal=causal, window=window,
            bq=block_q, bk=block_k, kv_blocks=kv_blocks,
        ),
        grid=(b, h, sqp // block_q, kv_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dp), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, dp),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, dp),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dp), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sqp, dp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
    return out[:, :, :sq, :d]
