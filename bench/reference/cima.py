"""Plain reference of one CIMA matrix-vector product (arXiv:1811.04047,
Figs. 2-5), written from the chip's semantics in plain ``jax.numpy``; it
imports nothing of the program.

* Operands are quantized symmetrically onto the XNOR grid: a ``B``-bit
  element is one of the even integers ``-2^(B-1) .. 2^(B-1)`` (``2^(B-1)+1``
  levels) times a scale ``amax / 2^(B-1)``; one bit is the sign (zero
  counts as +1) times the mean magnitude.  Inputs take one scale for the
  whole tensor, or one per row; weights one per output column.
* An element is ``B`` planes of +-1 with significances
  ``[2^(B-2), ..., 2, 1, 1]``.  An input element that quantizes to zero is
  masked: its capacitors are reset and its planes contribute nothing.
* Rows are split into banks of ``bank_n``.  For every bank and every pair
  of input plane and weight plane the column popcount
  ``p = (d + nu) / 2`` (``d`` the plane dot product, ``nu`` the unmasked
  rows of the bank) is converted by an ``adc_bits`` SAR ADC whose full
  scale is the bank's row count, and reconstructed:
  ``p_hat = round(round(p * (codes-1) / fs) * fs / (codes-1))``.
* The near-memory datapath shifts each ``2 * p_hat - nu`` by its joint
  significance and accumulates over plane pairs and banks, then rescales by
  the input and weight scales.

Plane dot products of +-1/0 values are exact in bfloat16 with float32
accumulation; every other step runs in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def xnor_quantize(x, bits: int, axis=None):
    """``(q, scale)``: ``q`` on the even-integer grid, ``x ~ q * scale``.
    ``axis=None``: one scale for the tensor; ``axis=-1``: one per row;
    ``axis=0``: one per column of a matrix."""
    if bits == 1:
        # one bit: the sign, at the mean magnitude
        if axis is None:
            mean = jnp.mean(jnp.abs(x))
        else:
            mean = jnp.mean(jnp.abs(x), axis=axis, keepdims=True)
        return jnp.where(x >= 0, 1.0, -1.0), jnp.maximum(mean, 1e-12)
    half = 2.0 ** (bits - 2)
    if axis is None:
        amax = jnp.max(jnp.abs(x))
    else:
        amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    amax = jnp.maximum(amax, 1e-12)
    scale = amax / (2.0 * half)
    level = jnp.clip(jnp.round(x / (2.0 * scale)), -half, half)
    return 2.0 * level, scale


def significances(bits: int) -> np.ndarray:
    return np.array([2.0 ** k for k in range(bits - 2, -1, -1)] + [1.0])


def xnor_planes(q, bits: int, mask_zeros: bool):
    """+-1 planes of ``q``, plane axis last (MSB first, the extra LSB
    plane last); zeros masked to 0 when ``mask_zeros``."""
    big = 2.0 ** (bits - 1)
    u = (q + big) / 2.0
    top = u >= big
    v = jnp.where(top, big - 1.0, u)
    planes = [jnp.mod(jnp.floor(v / 2.0 ** k), 2.0)
              for k in range(bits - 2, -1, -1)]
    planes.append(jnp.where(top, 1.0, 0.0))
    out = 2.0 * jnp.stack(planes, axis=-1) - 1.0
    if mask_zeros:
        out = out * (q != 0)[..., None]
    return out


def adc(p, fs: float, adc_bits: int):
    codes = jnp.float32(2 ** adc_bits - 1)
    fs = jnp.float32(fs)
    code = jnp.clip(jnp.round(jnp.clip(p, 0.0, fs) * (codes / fs)), 0.0,
                    codes)
    return jnp.round(code * (fs / codes))


def cima_int(xq, wq, bx: int, ba: int, bank_n: int, adc_bits: int):
    """Integer-grid output ``[R, M]`` of the chip for inputs ``xq [R, N]``
    and weights ``wq [N, M]`` (both on their XNOR grids)."""
    r, n = xq.shape
    m = wq.shape[1]
    xs = xnor_planes(xq, bx, mask_zeros=True)            # [R, N, BX]
    ws = xnor_planes(wq, ba, mask_zeros=False)           # [N, M, BA]
    sig = jnp.asarray(np.outer(significances(bx), significances(ba)),
                      jnp.float32)                       # [BX, BA]
    y = jnp.zeros((r, m), jnp.float32)
    for s in range(0, n, bank_n):
        e = min(s + bank_n, n)
        nu = jnp.sum((xq[:, s:e] != 0).astype(jnp.float32), axis=1)
        xb = jnp.swapaxes(xs[:, s:e], 1, 2).reshape(r * bx, e - s)
        wb = ws[s:e].reshape(e - s, m * ba)
        d = jnp.dot(xb.astype(jnp.bfloat16), wb.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
        d = d.reshape(r, bx, m, ba)
        nu_ = nu[:, None, None, None]
        p_hat = adc((d + nu_) * 0.5, float(e - s), adc_bits)
        d_hat = 2.0 * p_hat - nu_
        y = y + jnp.einsum("rxma,xa->rm", d_hat, sig,
                           precision=jax.lax.Precision.HIGHEST)
    return y
