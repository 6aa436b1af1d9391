"""Built-in execution backends.

Every quantizing backend shares one operand-quantization discipline
(:func:`quantize_input` / :func:`weight_grid` / :func:`rescale`), so
``digital_int`` is the bit-true reference for ``bpbs``/``bpbs_ref``/
``pallas`` by construction: they consume identical integer grids and
differ only in how the integer MVM itself is evaluated.

Weight-stationary serving: when ``ctx.image`` carries a compiled
:class:`~repro.accel.program.CimaImage`, the weight side comes from the
stored bit planes (a transpose/recombination of exact small integers —
bit-identical to quantizing on the fly) and **zero** per-call
``quantize``/``weight_planes`` ops run.  The input side is dynamic and
still quantizes per call, exactly as the chip streams activations.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.bpbs import (bpbs_matmul_planes, bpbs_matmul_planes_reference,
                             weight_planes)
from repro.core.quant import QTensor, quantize

from .context import ExecContext
from .registry import register_backend
from .spec import ExecSpec


def quantize_input(x: jax.Array, spec: ExecSpec) -> QTensor:
    """Quantize the (dynamic) input operand onto the spec's coding grid.

    The paper's C_x discipline at TP scale: any cross-device regather of
    the activations happens on the quantized int8 values (B_X bits on the
    chip's DMA), not on f32 planes — 16x fewer bytes (§Perf cell c).

    ``spec.x_per_row`` switches to one scale per input row (the
    per-vector DAC range): ``qx.scale`` is then ``x.shape[:-1] + (1,)``
    and every downstream rescale broadcasts it — the batch-decoupling
    discipline serving defaults to.
    """
    from repro.distributed.autoshard import cs

    with jax.named_scope("cima.quantize_x"):
        qx = quantize(x, spec.bx, spec.coding, per_row=spec.x_per_row)
        q_int = cs(qx.q.astype(jnp.int8), ("dp",))
        return dataclasses.replace(qx, q=q_int)


def weight_grid(w: jax.Array, spec: ExecSpec,
                ctx: ExecContext) -> QTensor:
    """The weight operand on the spec's integer grid.

    Program path: the image's stored int16 grid casts straight to f32
    (exact small integers; zero quantize ops).  Fallback: quantize per
    call.
    """
    img = ctx.image
    with jax.named_scope("cima.quantize_w"):
        if img is not None:
            return QTensor(img.wq.astype(jnp.float32), img.scale,
                           spec.ba, spec.coding)
        return quantize(w, spec.ba, spec.coding,
                        axis=1 if spec.per_channel else None)


def weight_planes_for(w: jax.Array, spec: ExecSpec,
                      ctx: ExecContext) -> tuple[jax.Array, jax.Array]:
    """``(ws [N, B_A, M], scale)`` for the plane-consuming backends.

    Program path: the image's planes in the kernel layout, widened to
    f32 in one pass.  (Measured on CPU XLA, one upfront int8->f32 cast
    beats feeding int8 straight into the per-bank bf16 GEMMs by ~1.6x —
    the element-wise widening fuses poorly inside the bank loop.  The
    ``pallas`` backend is the true 1-byte-per-plane-element streaming
    path: it consumes the stored int8 image directly and casts in-tile.)
    Fallback: quantize + decompose + transpose per call.
    """
    img = ctx.image
    with jax.named_scope("cima.quantize_w"):
        if img is not None:
            return img.ws.astype(jnp.float32), img.scale
        qw = quantize(w, spec.ba, spec.coding,
                      axis=1 if spec.per_channel else None)
        return jnp.transpose(weight_planes(qw.q, spec.bpbs()),
                             (0, 2, 1)), qw.scale


def quantize_operands(x: jax.Array, w: jax.Array,
                      spec: ExecSpec) -> tuple[QTensor, QTensor]:
    """Quantize both operands onto the spec's coding grids (the on-the-fly
    path; kept for external callers)."""
    qx = quantize_input(x, spec)
    qw = quantize(w, spec.ba, spec.coding,
                  axis=1 if spec.per_channel else None)
    return qx, qw


def rescale(y_int: jax.Array, x_scale: jax.Array, w_scale: jax.Array,
            spec: ExecSpec) -> jax.Array:
    with jax.named_scope("cima.post"):
        sw = w_scale if not spec.per_channel else w_scale.reshape(1, -1)
        return y_int * x_scale * sw


def apply_post(y: jax.Array, post, spec: ExecSpec) -> jax.Array:
    """Run a fused :class:`~repro.core.datapath.Postreduce` epilogue on a
    backend's rescaled output (scale -> bias -> activation -> B_y
    saturation, paper Fig. 8).  No-op when ``post`` is None — every
    quantizing backend ends with this so the fused path is the SAME
    function composition as matmul-then-postreduce (bit-for-bit parity
    by construction)."""
    if post is None:
        return y
    with jax.named_scope("cima.post"):
        return post.apply(y, spec.bx, spec.ba)


@register_backend("digital")
def digital(x: jax.Array, w: jax.Array, spec: ExecSpec,
            ctx: ExecContext) -> jax.Array:
    """Plain float GEMM — the "not in-memory computing" baseline."""
    return apply_post(jnp.einsum("...n,nm->...m", x, w), ctx.post, spec)


@register_backend("digital_int")
def digital_int(x: jax.Array, w: jax.Array, spec: ExecSpec,
                ctx: ExecContext) -> jax.Array:
    """Bit-true integer compute at (B_A, B_X) — the Fig. 11 "ideal"."""
    qx = quantize_input(x, spec)
    qw = weight_grid(w, spec, ctx)
    y_int = jnp.einsum("...n,nm->...m", qx.q.astype(jnp.float32),
                       qw.q.astype(jnp.float32))
    return apply_post(rescale(y_int, qx.scale, qw.scale, spec),
                      ctx.post, spec)


@register_backend("bpbs")
def bpbs(x: jax.Array, w: jax.Array, spec: ExecSpec,
         ctx: ExecContext) -> jax.Array:
    """Mixed-signal BP/BS pipeline, fast GEMM-identity path.  The fused
    ``ctx.post`` epilogue applies right after plane recombination, inside
    the same jitted op — XLA fuses it with the barrel-shift einsum, no
    HBM round-trip between reduce and post-ops."""
    qx = quantize_input(x, spec)
    ws, w_scale = weight_planes_for(w, spec, ctx)
    y_int = bpbs_matmul_planes(qx.q, ws, spec.bpbs(), ctx.key)
    return apply_post(rescale(y_int, qx.scale, w_scale, spec),
                      ctx.post, spec)


@register_backend("bpbs_ref")
def bpbs_ref(x: jax.Array, w: jax.Array, spec: ExecSpec,
             ctx: ExecContext) -> jax.Array:
    """Cell-by-cell charge-share physics (slow; validation only)."""
    qx = quantize_input(x, spec)
    ws, w_scale = weight_planes_for(w, spec, ctx)
    y_int = bpbs_matmul_planes_reference(qx.q, ws, spec.bpbs())
    return apply_post(rescale(y_int, qx.scale, w_scale, spec),
                      ctx.post, spec)


def _kernel_fusable(post, m: int) -> bool:
    """Can this epilogue run inside the Pallas kernel?  The chip's
    datapath registers are per-COLUMN, so only scalar / per-column
    scale+bias fuse in-kernel; a tensor-valued bias (e.g. a residual
    stream on the bias port) applies after the kernel instead — still
    inside the same jit, so XLA keeps it on-chip."""
    def per_col(a):
        return a is None or (a.ndim <= 1 and a.size in (1, m))

    return per_col(post.scale) and per_col(post.bias)


@register_backend("pallas")
def pallas(x: jax.Array, w: jax.Array, spec: ExecSpec,
           ctx: ExecContext) -> jax.Array:
    """The Pallas TPU kernel (interpret mode on CPU unless overridden).
    A per-column ``ctx.post`` fuses into the kernel's datapath epilogue:
    the quantization rescale folds into the scale registers and the
    output leaves the kernel already post-reduced."""
    from repro.kernels import ops as kernel_ops

    qx = quantize_input(x, spec)
    img = ctx.image
    if img is not None:
        ws_planes, w_scale = img.ws, img.scale
    else:
        with jax.named_scope("cima.quantize_w"):
            qw = quantize(w, spec.ba, spec.coding,
                          axis=1 if spec.per_channel else None)
        ws_planes, w_scale = None, qw.scale

    post = ctx.post
    m = int(w.shape[-1])
    if post is not None and _kernel_fusable(post, m):
        sw = w_scale.reshape(-1) if spec.per_channel else w_scale
        escale = qx.scale * sw
        if post.scale is not None:
            escale = escale * post.scale
        fused = dict(escale=escale, pbias=post.bias, act=post.act,
                     by_bits=post.resolve_bits(spec.bx, spec.ba))
        if img is not None:
            return kernel_ops.cima_mvm_from_planes(
                qx.q, ws_planes, spec.bpbs(), interpret=spec.interpret,
                **fused)
        return kernel_ops.cima_mvm(qx.q, qw.q, spec.bpbs(),
                                   interpret=spec.interpret, **fused)

    if img is not None:
        # the image already stores the kernel's [N, BA, M] int8 layout
        y_int = kernel_ops.cima_mvm_from_planes(qx.q, ws_planes, spec.bpbs(),
                                                interpret=spec.interpret)
    else:
        y_int = kernel_ops.cima_mvm(qx.q, qw.q, spec.bpbs(),
                                    interpret=spec.interpret)
    return apply_post(rescale(y_int, qx.scale, w_scale, spec), post, spec)
