"""Plain reference of a CIFAR network of arXiv:1811.04047 Fig. 11 on the
chip, for a configuration file of ``bench/configs`` (imports nothing of the
program).

Per layer: 3x3 SAME windows laid out spatial-major (row
``(kh * 3 + kw) * C_in + c``, the chip's ``9 * C_in`` row order) of the
activations, or the flattened NHWC activations for an fc layer; one CIMA
product (:mod:`cima`) with the input scale over the whole batch tensor and
one weight scale per column; then the datapath: ``y * (s_x * s_w * g) + b``
with the batch-norm registers ``g = gamma / sqrt(var + 1e-5)``,
``b = beta - mean * g``, the activation (ReLU for ADC readout, the sign
for ABN readout; none after the last layer), saturation to B_y bits
(16 when B_X + B_A <= 5, else 32), and 2x2 max pooling where the layer
pools.

The float steps (the windows, both operand scales, the batch-norm fold
and every step of the datapath) are rounded to the configuration's
``dtype``: float32 as the configurations state, bfloat16 in the control.
The integer CIMA core is exact in either.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from . import cima

BN_EPS = 1e-5
ROW_CHUNK = 32768


def windows(x):
    """[B, H, W, C] -> [B, H, W, 9 * C], spatial-major, zero padded."""
    b, h, w, c = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    return jnp.concatenate([xp[:, kh:kh + h, kw:kw + w, :]
                            for kh in range(3) for kw in range(3)], axis=-1)


def round_to(x, dtype):
    """``x`` rounded to ``dtype``'s precision, kept in float32 (an explicit
    rounding: XLA may drop a float32 -> bfloat16 -> float32 round trip)."""
    fi = jnp.finfo(dtype)
    if fi.bits >= 32:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def _by_chunks(fn, xq, chunk):
    r = xq.shape[0]
    if r <= chunk or r % chunk:
        return fn(xq)
    parts = jax.lax.map(fn, xq.reshape(r // chunk, chunk, xq.shape[1]))
    return parts.reshape(r, -1)


def layer_out(h, p, config, bx, last):
    ba = config["ba"]
    r = functools.partial(round_to, dtype=config["dtype"])
    xq, sx = cima.xnor_quantize(r(h), bx, axis=None)
    wq, sw = cima.xnor_quantize(r(p["w"]), ba, axis=0)
    sx, sw = r(sx), r(sw)
    y = _by_chunks(lambda x: cima.cima_int(x, wq, bx, ba, config["bank_n"],
                                           config["adc_bits"]),
                   xq, ROW_CHUNK)
    g = r(r(p["bn_scale"]) * r(jax.lax.rsqrt(r(r(p["bn_var"]) + BN_EPS))))
    b = r(r(p["bn_bias"]) - r(r(p["bn_mean"]) * g))
    y = r(r(y * r(r(sx * sw.reshape(-1)) * g)) + b)
    if not last:
        y = (jnp.where(y >= 0, 1.0, -1.0) if config["readout"] == "abn"
             else jnp.maximum(y, 0.0))
    hi = 2.0 ** (output_bits(bx, ba) - 1) - 1
    return jnp.clip(y, -(hi + 1), hi)


def output_bits(bx, ba):
    """B_y, the datapath's output word (paper Fig. 8)."""
    return 16 if bx + ba <= 5 else 32


def logits(params, images, config):
    """[B, 32, 32, 3] -> [B, n_classes] float32."""
    bx = config["bx"]
    x = images.astype(jnp.float32)
    n = len(config["layers"])
    for i, (layer, p) in enumerate(zip(config["layers"], params["layers"])):
        if layer["kind"] == "conv":
            b, hh, ww, _ = x.shape
            h = windows(x)
            h = h.reshape(b * hh * ww, -1)
            y = layer_out(h, p, config, bx, i == n - 1)
            y = y.reshape(b, hh, ww, -1)
            if layer.get("pool"):
                y = y.reshape(b, hh // 2, 2, ww // 2, 2, -1).max(axis=(2, 4))
        else:
            y = layer_out(x.reshape(x.shape[0], -1), p, config, bx, i == n - 1)
        x = y
    return x


@functools.lru_cache(maxsize=None)
def _jitted(config_key):
    config = json.loads(config_key)
    return jax.jit(lambda p, im: logits(p, im, config))


def run(params, images, config):
    """The reference logits, jitted once per configuration."""
    return _jitted(json.dumps(config, sort_keys=True))(params, images)
