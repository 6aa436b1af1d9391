"""The paper's CIFAR-10 networks (Fig. 11), mapped exactly as the chip maps
them: every 3x3 conv is im2col'd (:mod:`repro.core.w2b`) into an MVM of
dimensionality N = 9*C_in (<= 2304 = 3*3*256, the CIMA's designed-for
shape) and executed through the CIMU; batch-norm folds into the
near-memory datapath's scale/bias registers; Network B's binary
activations are the ABN comparator.

Inference runs the chip's own pipeline (DESIGN.md §10): the BN **running
statistics** fold through :func:`repro.core.datapath.fold_batchnorm` into
a :class:`~repro.core.datapath.Postreduce` — scale, bias, activation and
B_y saturation all execute as the matmul's fused epilogue, so a single
image's logits never depend on what else shares its batch.  Training
(``train=True``) normalizes with live batch statistics (standard BN
training) and surfaces those statistics so the trainer can maintain the
running averages the chip's registers are programmed from.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.numpy as jnp

from repro import accel
from repro.configs.cifar_nets import CnnConfig
from repro.core.datapath import Postreduce, fold_batchnorm
from repro.core.w2b import im2col
from repro.optim.qat import ste_sign

from .layers import truncated_normal_init


def init_cnn(key, net: CnnConfig) -> dict:
    """Per layer: the im2col'd weight matrix plus the BN parameters AND
    running statistics (``bn_mean``/``bn_var``) the inference datapath
    registers are folded from."""
    params: dict = {"layers": []}
    for layer in net.layers:
        key, k1 = jax.random.split(key)
        n = layer.cin * (9 if layer.kind == "conv" else 1)
        p = {
            "w": truncated_normal_init(k1, (n, layer.cout), n ** -0.5),
            "bn_scale": jnp.ones((layer.cout,), jnp.float32),
            "bn_bias": jnp.zeros((layer.cout,), jnp.float32),
            "bn_mean": jnp.zeros((layer.cout,), jnp.float32),
            "bn_var": jnp.ones((layer.cout,), jnp.float32),
        }
        params["layers"].append(p)
    return params


def _batchnorm(y, scale, bias, eps=1e-5):
    """Training-mode BN on live batch statistics.  Returns the normalized
    output plus the per-channel (mean, var) so the caller can update the
    running statistics inference folds into the datapath."""
    axes = tuple(range(y.ndim - 1))
    mu = jnp.mean(y, axes, keepdims=True)
    var = jnp.var(y, axes, keepdims=True)
    out = (y - mu) * jax.lax.rsqrt(var + eps) * scale + bias
    return out, (mu.reshape(-1), var.reshape(-1))


def update_bn_stats(params: dict, stats, momentum: float = 0.9) -> dict:
    """EMA-update the running BN statistics from one training batch's
    ``stats`` (the ``bn_stats`` aux of :func:`cnn_loss`).  Pure function;
    apply OUTSIDE the gradient (the stats are stop-gradient'd)."""
    new = {"layers": []}
    for p, (mu, var) in zip(params["layers"], stats):
        q = dict(p)
        q["bn_mean"] = momentum * p["bn_mean"] + (1.0 - momentum) * mu
        q["bn_var"] = momentum * p["bn_var"] + (1.0 - momentum) * var
        new["layers"].append(q)
    return new


def _layer(x, i: int, layer, p: dict, net: CnnConfig, train: bool,
           bn_stats: list):
    """One layer of :func:`cnn_forward`: the CIMA matmul of the 3x3
    windows (or the flattened activations) with its datapath epilogue,
    and the optional pool.

    At inference a conv layer hands ``accel.matmul`` its pixels with
    ``window=3``: where the spec allows, the windows are formed after
    the input quantizer, on the int8 grid.  Training takes the float
    windows (:func:`~repro.core.w2b.im2col`) as its STE operand."""
    if layer.kind == "conv":
        h, window = (im2col(x), None) if train else (x, 3)
    else:
        with jax.named_scope("cnn.im2col"):
            h, window = x.reshape(x.shape[0], -1), None  # flatten
    spec = net.policy.resolve(f"layer{i}", kind=layer.kind, layer=i)
    last = i == len(net.layers) - 1
    if train:
        y = accel.matmul(h, p["w"], spec, dtype=jnp.float32)
        y, st = _batchnorm(y, p["bn_scale"], p["bn_bias"])
        bn_stats.append(jax.tree_util.tree_map(jax.lax.stop_gradient, st))
        if not last:
            y = ste_sign(y) if net.readout == "abn" else jax.nn.relu(y)
    else:
        s, b = fold_batchnorm(p["bn_scale"], p["bn_bias"],
                              p["bn_mean"], p["bn_var"])
        post = Postreduce(
            scale=s, bias=b,
            act=None if last else
            ("sign" if net.readout == "abn" else "relu"),
            saturate=True)
        y = accel.matmul(h, p["w"], spec, dtype=jnp.float32, post=post,
                         window=window)
    if layer.kind == "conv" and layer.pool:
        with jax.named_scope("cnn.pool"):
            b_, hh, ww, c = y.shape
            y = y.reshape(b_, hh // 2, 2, ww // 2, 2, c).max(axis=(2, 4))
    return y


def cnn_forward(params, images, net: CnnConfig,
                backend: Optional[str] = None, train: bool = False):
    """images: [B, 32, 32, 3] -> logits [B, 10]  (plus the per-layer BN
    batch statistics when ``train=True``).

    ``backend`` (digital / digital_int / bpbs / ...) runs the whole net
    under :func:`repro.accel.override` so the same parameters can be
    evaluated under the ideal and the chip model — the Fig. 11 accuracy
    comparison.  Layer-index policy rules apply here: the CNN loop is
    unrolled, so each layer resolves with its static index.

    ``train=False`` (inference) is the chip's datapath pipeline: running
    BN stats fold into the Postreduce scale/bias registers and the
    activation + B_y saturation fuse into the matmul epilogue — logits
    are a function of the single image, never of batch composition.
    ``train=True`` normalizes with live batch statistics (and STE
    activations) exactly as QAT training always did.
    """
    ov = (accel.override(backend=backend) if backend is not None
          else contextlib.nullcontext())
    x = images
    bn_stats = []
    with ov:
        for i, (layer, p) in enumerate(zip(net.layers, params["layers"])):
            with jax.named_scope(f"cnn.layer{i}"):
                x = _layer(x, i, layer, p, net, train, bn_stats)
    return (x, bn_stats) if train else x


def cnn_loss(params, batch, net: CnnConfig, backend: Optional[str] = None,
             train: bool = True):
    """Cross-entropy + accuracy.  ``metrics["bn_stats"]`` carries the
    (stop-gradient'd) per-layer batch statistics for
    :func:`update_bn_stats` when ``train=True``."""
    if train:
        logits, bn_stats = cnn_forward(params, batch["images"], net,
                                       backend, train=True)
    else:
        logits, bn_stats = cnn_forward(params, batch["images"], net,
                                       backend), []
    labels = batch["labels"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    loss = jnp.mean(logz - ll)
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    metrics = {"loss": loss, "acc": acc}
    if train:
        metrics["bn_stats"] = bn_stats
    return loss, metrics
