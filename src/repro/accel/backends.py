"""Built-in execution backends.

``digital`` is the plain float GEMM.  Every quantizing backend is one row
of :data:`INT_CORES`: the weight operand its integer MVM reads — the
grid ``[N, M]`` or the bit planes ``[N, B_A, M]`` — and the MVM itself.
One scaffold (:func:`run_core`) runs every row: the input grid
(:func:`quantize_input`, or :func:`quantize_windows` for a conv layer's
pixels), the weight operand (:func:`weight_operand`), the core, then
:func:`rescale` and :func:`apply_post` — or, for the core that fuses the
epilogue (the Pallas kernel), the epilogue handed to the core.  So
``digital_int`` is the bit-true reference for ``bpbs``/``bpbs_ref``/
``pallas`` by construction: they consume identical integer grids and
differ only in how the integer MVM itself is evaluated.  The mesh path
(:mod:`repro.accel.shard`) runs the same cores on its per-device tiles.

Weight-stationary serving: when ``ctx.image`` carries a compiled
:class:`~repro.accel.program.CimaImage`, the weight side comes from the
stored grid or bit planes (exact small integers — bit-identical to
quantizing on the fly) and **zero** per-call ``quantize``/
``weight_planes`` ops run.  The input side is dynamic and still
quantizes per call, exactly as the chip streams activations.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bpbs import (bpbs_matmul_planes, bpbs_matmul_planes_reference,
                             weight_planes)
from repro.core.quant import (EPS, QTensor, grid_scale, quantize, to_grid,
                              uses_mean)
from repro.core.w2b import im2col, multiplicity

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


def quantize_windows(x: jax.Array, spec: ExecSpec, k: int) -> QTensor:
    """The per-tensor input grid of ``im2col(x, k)``, formed from the
    pixels ``x`` [B, H, W, C]: each pixel quantized and cast to int8
    once, then the ``k*k`` windows written on the int8 grid, padded with
    the grid's value of 0.

    The grid is an elementwise function of the pixel and one scale, and
    the scale comes from the pixels: ``max|x|`` is the windows' (every
    pixel lies in a window, the padding adds 0), and ``E|x|`` over the
    windows (1-b XNOR) is the sum of ``|x|`` weighted by how many windows
    hold each pixel, over the windows' element count.  So this is
    :func:`quantize_input` of the float windows bit for bit, except that
    the 1-b mean is summed in another order (its last ulp may differ).
    A per-row scale would be per window: it has no such form.
    """
    from repro.distributed.autoshard import cs

    b, h, w, c = x.shape
    bits, coding = spec.bx, spec.coding
    with jax.named_scope("cima.quantize_x"):
        ax = jnp.abs(x)
        if uses_mean(bits, coding):
            per_pixel = jnp.sum(ax, axis=(0, 3))                  # [H, W]
            stat = (jnp.sum(per_pixel * multiplicity(h, w, k))
                    / (b * h * w * k * k * c))
        else:
            stat = jnp.max(ax)
        scale = grid_scale(jnp.maximum(stat, EPS), bits)
        q = to_grid(x, scale, bits, coding).astype(jnp.int8)
        with jax.ensure_compile_time_eval():
            zero = int(to_grid(np.float32(0), np.float32(1), bits, coding))
    return QTensor(cs(im2col(q, k, zero), ("dp",)), scale, bits, coding)


def weight_operand(w: jax.Array, spec: ExecSpec, ctx: ExecContext,
                   core: IntCore) -> tuple[jax.Array, jax.Array]:
    """``(operand, scale)``: the weight side ``core`` reads, in its dtype.

    Program path: the image's stored grid or planes, cast where the core
    reads another dtype (exact small integers; zero quantize ops).
    Fallback: quantize per call, then, for the planes, decompose
    (:func:`repro.core.bpbs.weight_planes`).
    """
    img = ctx.image
    with jax.named_scope("cima.quantize_w"):
        if img is not None:
            return getattr(img, core.reads).astype(core.dtype), img.scale
        qw = quantize(w, spec.ba, spec.coding,
                      axis=1 if spec.per_channel else None)
    if core.reads == "wq":
        return qw.q, qw.scale
    with jax.named_scope("cima.planes"):
        return weight_planes(qw.q, spec.bpbs()).astype(core.dtype), qw.scale


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


def _int_gemm(x_q, wq, spec, key):
    """Bit-true integer compute at (B_A, B_X) — the Fig. 11 "ideal"."""
    return jnp.einsum("...n,nm->...m", x_q.astype(jnp.float32),
                      wq.astype(jnp.float32))


def _bpbs(x_q, ws, spec, key):
    """Mixed-signal BP/BS pipeline, fast GEMM-identity path; the epilogue
    applies right after plane recombination, inside the same jitted op —
    XLA fuses it with the barrel-shift einsum."""
    return bpbs_matmul_planes(x_q, ws, spec.bpbs(), key)


def _bpbs_ref(x_q, ws, spec, key):
    """Cell-by-cell charge-share physics (slow; validation only)."""
    return bpbs_matmul_planes_reference(x_q, ws, spec.bpbs())


def _pallas(x_q, ws, spec, key, **fused):
    """The Pallas TPU kernel (interpret mode off the TPU unless the spec
    says otherwise); ``fused`` arms its datapath epilogue."""
    from repro.kernels.cima_mvm import cima_mvm

    return cima_mvm(x_q, ws, spec.bpbs(), interpret=spec.interpret, **fused)


@dataclasses.dataclass(frozen=True)
class IntCore:
    """One quantizing backend: its integer MVM and the weight operand it
    reads."""

    # the CimaImage field the core reads: "wq", the grid [N, M], or "ws",
    # the bit planes [N, B_A, M]
    reads: str
    # the operand's dtype on one device: per-call operands are made in
    # it and an image's is cast to it (the mesh path passes the image's)
    dtype: Any
    # (x_q, w, spec, key) -> y_int [..., M]; with ``fuses``, also takes
    # the kernel's epilogue (escale, pbias, act, by_bits) by keyword
    mvm: Callable
    fuses: bool = False


INT_CORES = {
    "digital_int": IntCore("wq", jnp.float32, _int_gemm),
    "bpbs": IntCore("ws", jnp.float32, _bpbs),
    "bpbs_ref": IntCore("ws", jnp.float32, _bpbs_ref),
    "pallas": IntCore("ws", jnp.int8, _pallas, fuses=True),
}


def _kernel_fusable(post, m: int) -> bool:
    """Can this epilogue run inside the Pallas kernel?  The chip's
    datapath registers are per-COLUMN, so only scalar / per-column
    scale+bias fuse in-kernel; a tensor-valued bias (e.g. a residual
    stream on the bias port) applies after the kernel instead — still
    inside the same jit, so XLA keeps it on-chip."""
    def per_col(a):
        return a is None or (a.ndim <= 1 and a.size in (1, m))

    return per_col(post.scale) and per_col(post.bias)


def run_core(core: IntCore, x: jax.Array, w: jax.Array, spec: ExecSpec,
             ctx: ExecContext) -> jax.Array:
    """One quantizing backend: quantize -> weight operand -> integer MVM
    -> rescale -> epilogue.  With ``ctx.window`` set, ``x`` is a layer's
    pixels and the windows are formed on the int8 grid
    (:func:`quantize_windows`).  A per-column ``ctx.post`` fuses into a
    ``fuses`` core: the quantization rescale folds into the scale
    registers and the output leaves the core already post-reduced."""
    qx = (quantize_windows(x, spec, ctx.window) if ctx.window
          else quantize_input(x, spec))
    wop, w_scale = weight_operand(w, spec, ctx, core)
    post = ctx.post
    if core.fuses and post is not None \
            and _kernel_fusable(post, int(w.shape[-1])):
        sw = w_scale.reshape(-1) if spec.per_channel else w_scale
        escale = qx.scale * sw
        if post.scale is not None:
            escale = escale * post.scale
        return core.mvm(qx.q, wop, spec, ctx.key, escale=escale,
                        pbias=post.bias, act=post.act,
                        by_bits=post.resolve_bits(spec.bx, spec.ba))
    y_int = core.mvm(qx.q, wop, spec, ctx.key)
    return apply_post(rescale(y_int, qx.scale, w_scale, spec), post, spec)


for _name, _core in INT_CORES.items():
    register_backend(_name, functools.partial(run_core, _core))
