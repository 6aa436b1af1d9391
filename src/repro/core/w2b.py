"""The w2b Reshaping Buffer's window extraction (paper Fig. 6a): a layer's
pixels become the CIMA's input vectors, one ``k x k`` SAME window per
output pixel.

The window axis is SPATIAL-major: element ``(kh*k + kw)*C + c`` holds
input channel ``c`` at window offset ``(kh, kw)`` — the chip's ``9*C_in``
CIMA row order, so exported weight matrices map onto the array
deterministically.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _pads(k: int) -> tuple[int, int]:
    """SAME padding of a ``k``-wide window: ``(before, after)``."""
    return (k - 1) // 2, k // 2


def im2col(x: jax.Array, k: int = 3, pad_value=0) -> jax.Array:
    """``x`` [B, H, W, C] -> windows [B, H, W, k*k*C] (SAME padding with
    ``pad_value``, stride 1), spatial-major.

    Built from ``k*k`` shifted slices of the padded input laid side by
    side on the last (lane) axis, so it keeps ``x``'s dtype: on the int8
    input grid it writes the kernel's int8 operand directly, padded with
    the grid's value of 0 (which is not 0 under 1-b XNOR, where the sign
    of 0 is +1).  No convolution and no float patches."""
    b, h, w, c = x.shape
    lo, hi = _pads(k)
    with jax.named_scope("cnn.im2col"):
        xp = jnp.pad(x, ((0, 0), (lo, hi), (lo, hi), (0, 0)),
                     constant_values=jnp.asarray(pad_value, x.dtype))
        return jnp.concatenate([xp[:, kh:kh + h, kw:kw + w, :]
                                for kh in range(k) for kw in range(k)],
                               axis=-1)


def multiplicity(h: int, w: int, k: int = 3) -> np.ndarray:
    """``[H, W]``: how many of :func:`im2col`'s windows hold each pixel
    (for ``k = 3``: 9 inside, 6 on an edge, 4 in a corner), so that a sum
    over the windows of ``f(x)`` with ``f(pad) = 0`` is the sum over the
    pixels of ``f(x)`` times this."""
    lo, _ = _pads(k)

    def along(n):
        # output positions o whose window o - lo .. o - lo + k - 1 holds i
        i = np.arange(n)[:, None]
        o = i + lo - np.arange(k)[None, :]
        return ((o >= 0) & (o < n)).sum(axis=1)

    return np.outer(along(h), along(w)).astype(np.float32)
