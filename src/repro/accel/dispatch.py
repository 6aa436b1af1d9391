"""The single matmul entry point every managed projection goes through.

``matmul`` resolves the effective spec (applying any scoped
:func:`~repro.accel.context.override`), records the MVM for energy/
roofline tracing, and dispatches to the registered backend.  Non-digital
backends get straight-through-estimator (STE) gradients — the backward
pass is that of the plain float GEMM, which is what quantization-aware
training of the paper's CIFAR networks uses.

When the caller supplies a compiled weight ``image`` (a
:class:`~repro.accel.program.CimaImage`, threaded through the param tree
by :func:`~repro.accel.program.install_program`), the dispatcher
validates it against the *resolved* spec — so a scoped
``override(backend=...)`` keeps the image (all quantizing backends share
one weight grid) while an ``override(ba=...)`` correctly drops back to
on-the-fly quantization — and hands it to the backend through
``ExecContext``.  The program path keeps the same STE gradients as the
on-the-fly path (the custom_vjp operands are the float master operands;
the image's integer planes are non-differentiable closure constants) —
training still never installs images, because a compiled image is a
*stale snapshot* the moment the optimizer moves the weights.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.analysis.sanitize import active as _san_active
from repro.core.w2b import im2col

from .context import (ExecContext, MvmRecord, current_override,
                      current_pad_mask, next_noise_key, record,
                      streamed_load_seen, tracing)
from .registry import get_backend
from .spec import ExecSpec


def _guard_out(y: jax.Array, spec: ExecSpec) -> jax.Array:
    """Sanitizer NaN/Inf guard on the dispatch output (eager only)."""
    san = _san_active()
    if san is not None:
        san.check_finite(y, f"accel.matmul[{spec.tag or spec.backend}] "
                            f"output")
    return y


def _strip_pad(x: jax.Array) -> jax.Array:
    """Drop positions an ambient :func:`~repro.accel.context.pad_positions`
    scope marks as padding before measuring sparsity: left-pad zeros are
    not exploitable — the controller saves nothing on tokens that don't
    exist.  Eager-only (a Tracer mask is ignored, matching the
    measurement's own eager-only contract); a mask whose shape doesn't
    prefix-match ``x`` is ignored (e.g. the unembed's last-token slice)."""
    mask = current_pad_mask()
    if mask is None or isinstance(mask, jax.core.Tracer):
        return x
    if mask.ndim >= x.ndim or x.shape[:mask.ndim] != mask.shape:
        return x
    return x[jnp.asarray(mask, bool)]     # [n_real, ...trailing]


def _measured_sparsity(spec: ExecSpec, x: jax.Array) -> Optional[float]:
    """Input bit-plane sparsity the AND-logic controller would gate
    (repro.core.sparsity, paper Fig. 6b): the zero fraction of the input
    quantized onto the spec's coding grid.  Only measurable when the
    dispatch sees concrete values — inside a jit trace ``x`` is a Tracer
    and the record carries None (energy_summary then falls back to its
    uniform sparsity argument)."""
    if spec.backend == "digital" or isinstance(x, jax.core.Tracer):
        return None
    from repro.core.quant import quantize
    from repro.core.sparsity import element_mask, sparsity_fraction

    qx = quantize(_strip_pad(x), spec.bx, spec.coding,
                  per_row=spec.x_per_row)
    return float(sparsity_fraction(element_mask(qx.q)))


def _measured_planes(spec: ExecSpec, x: jax.Array) \
        -> tuple[Optional[int], Optional[int]]:
    """``(planes_skipped, planes_total)``: all-zero (bank, input-plane)
    serial steps the plane-skip fast path gates off for this dispatch
    (repro.core.sparsity.count_zero_planes), at the spec's banking.
    Eager-only, like :func:`_measured_sparsity`.  Pad positions are NOT
    stripped here: the skip predicate in the execution path sees the
    padded batch, so the measurement must match what actually skips."""
    if spec.backend == "digital" or not spec.skip_zero_planes \
            or isinstance(x, jax.core.Tracer):
        return None, None
    from repro.core.quant import quantize
    from repro.core.sparsity import count_zero_planes

    qx = quantize(x, spec.bx, spec.coding, per_row=spec.x_per_row)
    return count_zero_planes(qx.q, spec.bpbs())


def _record_mvm(spec: ExecSpec, x: jax.Array, w: jax.Array,
                image=None, post=None, window=None) -> None:
    if not tracing():
        return
    if window and not isinstance(x, jax.core.Tracer):
        x = im2col(x, window)       # the rows the eager measurements see
    streamed = image is not None and not image.resident
    overlap = streamed and getattr(image, "overlap", False)
    # double-buffer prologue: the first streamed load of a pass has no
    # in-flight compute to hide behind; every later one prefetches into
    # the spare bank set during the previous dispatch's MVMs.  Checked
    # against the innermost trace scope, BEFORE this record lands.
    prologue = 1 if (overlap and not streamed_load_seen()) else 0
    skipped, total = _measured_planes(spec, x)
    # devices/partition come from the image's COMPILED layout: the trace
    # is the chip cost model, and a program built for an N-chip mesh
    # describes an N-chip system whether or not the host run actually
    # shard_maps (numerics are identical either way) — this is what
    # keeps BENCH_shard's analytic curve and a real mesh run in
    # agreement record-for-record.
    record(MvmRecord(
        tag=spec.tag, backend=spec.backend,
        n=int(w.shape[0]), m=int(w.shape[1]),
        ba=spec.ba, bx=spec.bx,
        calls=int(math.prod(x.shape[:-1])),
        program=image is not None,
        loads=1 if streamed else 0,
        load_segments=image.segments if streamed else 0,
        stream_overlap=overlap,
        load_prologue=prologue,
        devices=image.devices if image is not None else 1,
        partition=(image.partition or "") if image is not None else "",
        data_shards=(max(getattr(image, "data_shards", 1), 1)
                     if image is not None else 1),
        post_ops=post.n_ops() if post is not None else 0,
        sparsity=_measured_sparsity(spec, x),
        planes_skipped=skipped,
        planes_total=total,
        grid_windows=1 if window else 0,
    ))


def _windows_on_grid(spec: ExecSpec, mesh) -> bool:
    """Does a conv dispatch hand the backend its pixels, to form the
    windows after quantizing, on the int8 grid?  The ``pallas`` kernel's
    operand is that grid; a per-tensor input scale is a function of the
    pixels (a per-row scale is one per window); the mesh path takes
    rows."""
    return spec.backend == "pallas" and not spec.x_per_row and mesh is None


def _ste_grads(x, w, g, window):
    """``(dx, dw)`` of the plain float GEMM (the STE), through the
    windows where ``x`` is a layer's pixels."""
    if window:
        h, unwindow = jax.vjp(lambda x_: im2col(x_, window), x)
    else:
        h, unwindow = x, lambda d: (d,)
    dx, = unwindow(jnp.einsum("...m,nm->...n", g, w))
    return dx, jnp.einsum("...n,...m->nm", h, g)


def _shard_mesh(image):
    """The ambient mesh, iff it matches the image's compiled partition.

    Records stay logical either way: the record is emitted ONCE with the
    full (n, m) before shard_map, so a sharded trace reports the same
    total MVM count and loads as the unsharded trace of the same
    workload — only the ``devices``/``partition`` annotations change.
    """
    if image is None or image.partition is None or image.devices <= 1:
        return None
    from repro.distributed.autoshard import get_mesh, in_manual

    mesh = get_mesh()
    if mesh is None or in_manual() or "model" not in mesh.axis_names:
        return None
    if int(dict(mesh.shape).get("model", 1)) != image.devices:
        return None
    return mesh


def matmul(
    x: jax.Array,
    w: jax.Array,
    spec: Optional[ExecSpec] = None,
    ctx: Optional[ExecContext] = None,
    *,
    dtype=None,
    image=None,
    post=None,
    window: Optional[int] = None,
) -> jax.Array:
    """``x @ w`` under ``spec``'s execution backend.

    * ``spec=None`` means *digital by design* (dynamic operands, routers,
      recurrence gates): always a plain GEMM, exempt from overrides and
      tracing.
    * A digital spec computes at ``dtype`` (default: ``x.dtype``) and
      returns that dtype.
    * Any other backend quantizes per its spec, computes in float32 with
      STE gradients, and returns float32 — callers cast.
    * ``image`` (optional): this projection's compiled
      :class:`~repro.accel.program.CimaImage`.  If it matches the
      resolved spec, the backend consumes its bit planes instead of
      quantizing ``w`` — bit-for-bit the same result, zero weight
      quantize/decompose ops, and the same STE gradients.
    * ``post`` (optional): a :class:`~repro.core.datapath.Postreduce`
      epilogue (scale -> bias -> activation -> B_y saturation, paper
      Fig. 8) executed FUSED at the accelerator: inside the Pallas
      kernel's datapath stage, after plane recombination in the fast
      bpbs path, and after the row-parallel psum under shard_map.  The
      result is bit-for-bit ``post.apply(matmul(x, w, spec))`` — the
      backends end with the identical function composition — and the
      gradients are exactly the unfused composition's: STE through the
      quantized matmul, the true VJP through the epilogue (including
      cotangents for ``post.scale``/``post.bias``).
    * ``window`` (optional, ``k``): ``x`` is a layer's pixels
      [B, H, W, C] and the product is the ``k x k`` SAME convolution,
      ``im2col(x, k) @ w`` (:func:`repro.core.w2b.im2col`), recorded as
      that MVM.  On ``pallas`` at a per-tensor input scale the backend
      quantizes the pixels and writes the windows on the int8 grid;
      otherwise they are formed here, on the float activations.  STE
      gradients either way.
    """
    if spec is None:
        if window:
            x = im2col(x, window)
        dt = dtype or x.dtype
        y = jnp.einsum("...n,nm->...m", x.astype(dt), w.astype(dt))
        return post.apply(y) if post is not None else y

    ov = current_override()
    if ov:
        spec = dataclasses.replace(spec, **ov)

    from .program import image_matches

    if image is not None and not image_matches(image, spec, w):
        image = None
    mesh = _shard_mesh(image)
    if window and not _windows_on_grid(spec, mesh):
        x, window = im2col(x, window), None
    _record_mvm(spec, x, w, image, post, window)

    if mesh is not None:
        # mesh-partitioned program path: the backend runs under shard_map,
        # one per-device tile of the image per chip (repro.accel.shard)
        from .shard import sharded_program_matmul

        img = image

        def fn(x_, w_, spec_, ctx_):
            return sharded_program_matmul(x_, spec_, img, mesh,
                                          key=ctx_.key, post=ctx_.post)
    else:
        fn = get_backend(spec.backend)
    if ctx is None:
        ctx = ExecContext(key=next_noise_key())
    if image is not None:
        ctx = dataclasses.replace(ctx, image=image)
    if window:
        ctx = dataclasses.replace(ctx, window=window)
    san = _san_active()
    if san is not None:
        where = spec.tag or spec.backend
        san.observe_dispatch(spec, ctx)
        san.check_finite(x, f"accel.matmul[{where}] input")
        san.check_finite(w, f"accel.matmul[{where}] weight")
    if spec.is_digital:
        # digital computes at the caller's dtype and takes no STE wrapper,
        # but still goes through the registry so a re-registered "digital"
        # backend governs this path too (the epilogue applies inside the
        # backend, differentiably — digital needs no STE)
        dt = dtype or x.dtype
        if post is not None:
            ctx = dataclasses.replace(ctx, post=post)
        return _guard_out(fn(x.astype(dt), w.astype(dt), spec, ctx), spec)
    xf = x.astype(jnp.float32)
    wf = w.astype(jnp.float32)

    if post is None:
        @jax.custom_vjp
        def _op(x, w):
            return fn(x, w, spec, ctx)

        def _fwd(x, w):
            return _op(x, w), (x, w)

        def _bwd(res, g):
            x, w = res
            return _ste_grads(x, w, g, window)

        _op.defvjp(_fwd, _bwd)
        return _guard_out(_op(xf, wf), spec)

    # fused-epilogue path: the primal runs the backend WITH ctx.post (the
    # kernel-fused forward); differentiation runs matmul-then-epilogue —
    # the same values (the backends compose identically) with the
    # pre-epilogue output saved as the residual the epilogue VJP needs.
    pargs = post.dyn_args()

    def _epi(y_pre, *pa):
        return post.with_dyn_args(pa).apply(y_pre, spec.bx, spec.ba)

    @jax.custom_vjp
    def _opf(x, w, *pa):
        return fn(x, w, spec,
                  dataclasses.replace(ctx, post=post.with_dyn_args(pa)))

    def _fwd(x, w, *pa):
        y_pre = fn(x, w, spec, ctx)
        return _epi(y_pre, *pa), (x, w, y_pre, pa)

    def _bwd(res, g):
        x, w, y_pre, pa = res
        _, pvjp = jax.vjp(_epi, y_pre, *pa)
        gy, *gpa = pvjp(g)
        return (*_ste_grads(x, w, gy, window), *gpa)

    _opf.defvjp(_fwd, _bwd)
    return _guard_out(_opf(xf, wf, *pargs), spec)
