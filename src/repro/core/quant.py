"""Bit-plane quantization codings for the BP/BS scheme (paper Fig. 4).

Two codings are supported, exactly as in the paper:

* ``AND``  — standard 2's-complement.  A ``B``-bit integer ``q`` in
  ``[-2^(B-1), 2^(B-1)-1]`` is decomposed into ``B`` planes with bits in
  ``{0,1}`` and plane weights ``[1, 2, ..., 2^(B-2), -2^(B-1)]``.  The
  bit-cell operation between two planes is a logical AND (product of
  ``{0,1}`` bits), so zero-valued elements contribute nothing to any plane
  ("sparsity-proportional energy savings are inherently achieved").

* ``XNOR`` — bits map to ``{-1,+1}``.  Representing zero requires two
  planes with LSB weighting (paper §2), so a ``B``-bit element uses plane
  weights ``[2^(B-2), ..., 2, 1, 1]`` (for ``B >= 2``; ``[1]`` for
  ``B == 1``).  The representable grid is the even integers in
  ``[-2^(B-1), 2^(B-1)]`` — i.e. ``2^(B-1)+1`` symmetric levels with the
  factor of two absorbed into the scale.  The bit-cell operation is XNOR,
  whose column popcount relates to the signed dot product by
  ``dot = 2*p - n`` (n = number of unmasked rows).

All plane tensors put the plane index in the LAST axis.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


class Coding(str, enum.Enum):
    XNOR = "xnor"
    AND = "and"


def plane_weights(bits: int, coding: Coding) -> np.ndarray:
    """Significance weight of each bit plane (float64 numpy, length ``bits``)."""
    coding = Coding(coding)
    if bits < 1:
        raise ValueError(f"bits must be >= 1, got {bits}")
    if coding == Coding.XNOR:
        if bits == 1:
            return np.array([1.0])
        # [2^(B-2), ..., 2, 1, 1] — two LSB-weight planes to represent zero.
        return np.array([2.0 ** k for k in range(bits - 2, -1, -1)] + [1.0])
    else:
        # 2's complement: [1, 2, ..., 2^(B-2), -2^(B-1)]  (B=1 -> unsigned {0,1})
        if bits == 1:
            return np.array([1.0])
        return np.array([2.0 ** k for k in range(bits - 1)] + [-(2.0 ** (bits - 1))])


def int_range(bits: int, coding: Coding) -> tuple[int, int]:
    """Inclusive integer grid range representable by the coding."""
    coding = Coding(coding)
    if coding == Coding.XNOR:
        if bits == 1:
            return (-1, 1)
        return (-(2 ** (bits - 1)), 2 ** (bits - 1))  # even integers only
    else:
        if bits == 1:
            return (0, 1)
        return (-(2 ** (bits - 1)), 2 ** (bits - 1) - 1)


def n_levels(bits: int, coding: Coding) -> int:
    coding = Coding(coding)
    if coding == Coding.XNOR:
        return 2 if bits == 1 else 2 ** (bits - 1) + 1
    return 2 ** bits


@dataclasses.dataclass
class QTensor:
    """A quantized tensor: ``value ~= q * scale`` with ``q`` on the coding grid."""

    q: jax.Array          # integer-valued (stored float32 or int32)
    scale: jax.Array      # scalar or broadcastable per-channel scale
    bits: int
    coding: Coding

    @property
    def dequant(self) -> jax.Array:
        return self.q * self.scale


def quantize(
    x: jax.Array,
    bits: int,
    coding: Coding,
    axis: Optional[int] = None,
    eps: float = 1e-12,
    per_row: bool = False,
) -> QTensor:
    """Symmetric (per-tensor, per-axis, or per-row) quantization onto the
    coding grid.

    ``per_row=True`` reduces over the LAST axis only, keeping independent
    scales for every leading index (shape ``x.shape[:-1] + (1,)``) — the
    per-vector range a real input DAC sees.  Each row's grid then depends
    only on that row, so batch composition cannot change any element's
    quantized value (the batch-decoupling property serving relies on).
    Mutually exclusive with ``axis``.
    """
    coding = Coding(coding)
    if per_row and axis is not None:
        raise ValueError("quantize: per_row and axis are mutually exclusive")

    def _reduce(fn):
        if per_row:
            return fn(jnp.abs(x), axis=-1, keepdims=True)
        if axis is None:
            return fn(jnp.abs(x))
        reduce_axes = tuple(i for i in range(x.ndim) if i != axis)
        return fn(jnp.abs(x), axis=reduce_axes, keepdims=True)

    amax = jnp.maximum(_reduce(jnp.max), eps)

    if coding == Coding.XNOR:
        if bits == 1:
            # BNN-style: q in {-1, +1}; scale = E|x| preserves magnitude.
            scale = jnp.maximum(_reduce(jnp.mean), eps)
            q = jnp.where(x >= 0, 1.0, -1.0)
            return QTensor(q, scale, bits, coding)
        half = 2.0 ** (bits - 2)          # max level index
        scale = amax / (2.0 * half)       # q = 2 * level, level in [-half, half]
        level = jnp.clip(jnp.round(x / (2.0 * scale)), -half, half)
        return QTensor(2.0 * level, scale, bits, coding)
    else:
        if bits == 1:
            scale = amax
            q = jnp.clip(jnp.round(x / scale), 0, 1)
            return QTensor(q, scale, bits, coding)
        qmax = 2.0 ** (bits - 1) - 1
        qmin = -(2.0 ** (bits - 1))
        scale = amax / (2.0 ** (bits - 1))
        q = jnp.clip(jnp.round(x / scale), qmin, qmax)
        return QTensor(q, scale, bits, coding)


def int_plane_list(q: jax.Array, bits: int, coding: Coding) -> list:
    """The bit planes of :func:`int_to_planes` as a list in plane order,
    each shaped like ``q`` (float32, exact small integers).

    The form a kernel evaluates: every step is elementwise on ``q``, so
    the planes of one VMEM tile come out as dense tiles of that shape.
    Divisions by powers of two are multiplications by their exact
    reciprocals.
    """
    coding = Coding(coding)
    q = q.astype(jnp.float32)
    if coding == Coding.XNOR:
        if bits == 1:
            return [jnp.where(q >= 0, 1.0, -1.0)]
        big = 2.0 ** (bits - 1)
        u = (q + big) * 0.5                       # in [0, 2^(B-1)], integer
        top = u >= big
        e = jnp.where(top, 1.0, 0.0)              # second LSB-weight plane
        v = jnp.where(top, big - 1.0, u)          # u == big -> v = all-ones
        # v in [0, 2^(B-1)-1]: standard binary over weights [2^(B-2) .. 1]
        planes = []
        rem = v
        for k in range(bits - 2, -1, -1):
            w = 2.0 ** k
            b = jnp.floor(rem * (1.0 / w))
            rem = rem - b * w
            planes.append(b)
        planes.append(e)
        return [2.0 * b - 1.0 for b in planes]    # {0,1} -> {-1,+1}
    if bits == 1:
        return [jnp.clip(q, 0, 1)]
    # two's complement: q + 2^(B-1) = unsigned B-bit value
    u = q + 2.0 ** (bits - 1)
    # weights [1, 2, ..., 2^(B-2), -2^(B-1)]; extract MSB-first from u.
    # u = q + 2^(B-1): q >= 0 <-> msb = 1, and the sign bit of 2's
    # complement is 1 for negatives: sign_bit = 1 - msb.
    msb = jnp.floor(u * 2.0 ** -(bits - 1))
    sign_bit = 1.0 - msb
    rem = u - msb * (2.0 ** (bits - 1))
    low = []
    for k in range(bits - 2, -1, -1):
        w = 2.0 ** k
        b = jnp.floor(rem * (1.0 / w))
        rem = rem - b * w
        low.append(b)
    low.reverse()                                 # now LSB-first: weights 1,2,...
    return low + [sign_bit]


def int_to_planes(q: jax.Array, bits: int, coding: Coding) -> jax.Array:
    """Decompose integers on the coding grid into bit planes.

    Returns planes with values in {0,1} (AND) or {-1,+1} (XNOR), shape
    ``q.shape + (bits,)``, dtype float32 (exact small integers).
    """
    return jnp.stack(int_plane_list(q, bits, coding), axis=-1)


def planes_to_int(planes: jax.Array, bits: int, coding: Coding) -> jax.Array:
    """Inverse of :func:`int_to_planes` (weighted recombination)."""
    w = jnp.asarray(plane_weights(bits, coding), dtype=jnp.float32)
    return jnp.sum(planes * w, axis=-1)
