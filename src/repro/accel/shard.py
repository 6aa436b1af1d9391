"""Mesh-sharded ("multi-chip") execution of compiled CIMA programs.

One 65nm chip aligns storage and compute spatially across its 16 banks;
this module does the same across *devices*: a compiled
:class:`~repro.accel.program.CimaImage` whose ``partition`` metadata says
how its bit planes split over the mesh ``"model"`` axis is executed under
:func:`jax.shard_map`, one per-device tile of the
program per chip (DESIGN.md §9):

* ``"col"`` (column-parallel): every device holds ``m/devices`` output
  columns of ALL rows.  The input vector is broadcast (replicated), each
  device evaluates its own columns — bank grid, ADC epilogue and
  near-memory accumulation entirely local — and the outputs concatenate.
  No collective on the MVM itself.
* ``"row"`` (row-parallel): every device holds ``n/devices`` contraction
  rows of ALL columns.  The input splits along N, each device runs its
  local banks *and its own ADC epilogue* (each chip digitizes its own
  column sums — exactly the physical multi-chip behaviour), and the
  digital partial sums are combined with a single ``psum`` all-reduce.

Input quantization is GLOBAL (outside ``shard_map``): the dynamic input
scale — per-tensor, or per-row under ``spec.x_per_row`` — must be
computed from the full activation, exactly as the single-chip path does —
sharding must never change the operand grid.  Likewise the final
``rescale`` runs on the combined integer result with the image's (global)
weight scales; a per-row ``qx.scale`` (last dim 1) rides into the body
replicated and broadcasts against the local tile.

A 2D ``data x model`` mesh (DESIGN.md §13) composes orthogonally: when
the mesh carries a ``"data"`` axis that divides the activation's leading
(batch) dim, batch rows split along it — each data shard holds a full
replica of the image's per-device tiles and runs its slice of the batch.
Weights/planes stay data-replicated, the row-parallel ``psum`` stays on
``"model"`` only (data shards hold disjoint rows; nothing to reduce),
and per-row epilogue operands (``qx.scale`` under ``x_per_row``, tensor
biases carrying the residual) split their leading dim with the batch.
Because quantization is global and the grid is fixed before the split,
the 2D path is bit-for-bit identical to the 1D and unsharded paths.

The body runs the backend's integer core from
:data:`~repro.accel.backends.INT_CORES`, the same one the single-device
path runs, on the image field that core reads.  The Pallas kernel
composes directly: inside the body it sees the local
``[N_loc, BA, M_loc]`` planes, so its bank grid dimension *is* the
per-device tile.

Trace semantics (no per-shard double-counting): the dispatcher records
ONE logical :class:`~repro.accel.context.MvmRecord` per matmul — with the
full logical (n, m) plus ``devices``/``partition`` — *before* entering
``shard_map``; nothing records inside the body.  Total MVM counts and
image loads therefore match the unsharded trace exactly, and
:func:`~repro.accel.context.energy_summary` derives per-device wall
cycles from the local tile and system energy by summing shards.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _data_axis(mesh, x_shape) -> Optional[str]:
    """The mesh "data" axis name, iff batch rows can split along it.

    Requires a >1-sized ``"data"`` axis, an activation with a distinct
    leading batch dim (ndim >= 2), and divisibility.  Anything else
    falls back to data-replicated execution — placement only, never a
    numerics decision.
    """
    if "data" not in getattr(mesh, "axis_names", ()):
        return None
    d = int(dict(mesh.shape)["data"])
    if d <= 1 or len(x_shape) < 2 or x_shape[0] % d != 0:
        return None
    return "data"


def _x_spec(ndim: int, partition: str, lead: Optional[str] = None) -> P:
    spec = [None] * ndim
    if lead is not None:
        spec[0] = lead
    if partition == "row":
        spec[-1] = "model"
    return P(*spec)


def _out_spec(ndim: int, partition: str, lead: Optional[str] = None) -> P:
    spec = [None] * ndim
    if lead is not None:
        spec[0] = lead
    if partition == "col":
        spec[-1] = "model"
    return P(*spec)


def _w_spec(partition: str, ndim: int) -> P:
    # the grid [N, M] or the planes [N, BA, M] — always data-replicated
    spec = [None] * ndim
    spec[0 if partition == "row" else -1] = "model"
    return P(*spec)


def _post_spec(arr, part: str, m: int, lead: Optional[str] = None,
               rows: int = 0) -> P:
    """Placement of one epilogue operand: arrays whose last dim is the
    output dim split with the columns under "col"; arrays whose leading
    dim is the batch (per-row input scales, tensor biases carrying the
    residual) split with the rows over "data"; everything else (scalars,
    per-tensor scales, row-parallel operands applied after the psum) is
    replicated."""
    nd = arr.ndim
    spec = [None] * nd
    if lead is not None and nd >= 2 and arr.shape[0] == rows:
        spec[0] = lead
    if part == "col" and nd and arr.shape[-1] == m:
        spec[-1] = "model"
    return P(*spec)


def sharded_program_matmul(x: jax.Array, spec, image, mesh,
                           key: Optional[jax.Array] = None,
                           post=None) -> jax.Array:
    """``x @ w`` from a partitioned compiled image, under ``shard_map``.

    ``image.partition`` must be ``"col"`` or ``"row"`` and
    ``mesh.shape["model"] == image.devices`` (the dispatcher checks).
    Returns float32, same contract as the on-the-fly backends.

    ``post`` (a :class:`~repro.core.datapath.Postreduce`) fuses the
    near-memory datapath epilogue INSIDE the shard_map body — exactly
    where the chip applies it: column-parallel tiles rescale and
    post-reduce their own output columns locally (scale/bias registers
    split with the columns); row-parallel tiles apply scale/bias/act
    right after the digital partial-sum all-reduce, so the epilogue runs
    once per output on-device instead of on the gathered result.
    """
    from repro.distributed.autoshard import manual

    from .backends import INT_CORES, quantize_input, rescale

    part = image.partition
    assert part in ("col", "row"), part
    # dynamic-operand quantization on the FULL activation (global scale)
    qx = quantize_input(x, spec)
    # 2D mesh: batch rows split over "data" when the axis divides them;
    # decided AFTER quantization so the operand grid never sees the mesh
    lead = _data_axis(mesh, qx.q.shape)
    rows = int(qx.q.shape[0]) if qx.q.ndim >= 2 else 0

    # one scaffold (psum placement, manual() scoping, in/out specs) for
    # every backend — only the local tile compute differs: the backend's
    # integer core on the image field it reads
    core = INT_CORES.get(spec.backend)
    if core is None:
        raise ValueError(
            f"backend {spec.backend!r} has no shard_map execution path; "
            f"mesh-partitioned images support {', '.join(INT_CORES)}")
    w_op = getattr(image, core.reads)
    operands = (w_op,) + ((key,) if key is not None else ())
    w_specs = ((_w_spec(part, w_op.ndim),)
               + ((P(),) if key is not None else ()))

    def local(xq, w, *k):
        # local banks AND local ADC epilogue: each chip digitizes its
        # own column sums before the digital partial-sum all-reduce.
        # Each chip has its own ADCs: fold the device index into the
        # noise key so shards draw INDEPENDENT noise fields (a
        # replicated key would correlate the chips bit-for-bit).  The
        # kernel's bank grid dimension is the per-device tile.
        kd = None
        if k:
            kd = jax.random.fold_in(k[0], jax.lax.axis_index("model"))
            if lead is not None:
                # data shards are distinct chips too: decorrelate
                # their ADC noise fields exactly like model shards
                kd = jax.random.fold_in(kd, jax.lax.axis_index(lead))
        return core.mvm(xq, w, spec, kd)

    m = image.m
    n_local = len(operands)
    if post is not None:
        # epilogue operands ride into the body: the quantization scales
        # plus the datapath registers, placed so "col" tiles hold their
        # own columns' registers and "row" tiles see the full (post-psum)
        # vectors replicated
        epi_ops = (qx.scale, image.scale) + post.dyn_args()
        epi_specs = tuple(_post_spec(jnp.asarray(a), part, m, lead, rows)
                          for a in epi_ops)
        operands = operands + epi_ops
        w_specs = w_specs + epi_specs

    def body(xq, *ops):
        y = local(xq, *ops[:n_local])
        if part == "row":
            y = jax.lax.psum(y, "model")
        if post is None:
            return y
        # near-memory datapath, per chip: rescale on the local (or
        # psum-combined) integer result, then scale -> bias -> act ->
        # B_y saturation — the output leaves the body post-reduced
        xsc, wsc, *pa = ops[n_local:]
        y = rescale(y, xsc, wsc, spec)
        return post.with_dyn_args(pa).apply(y, spec.bx, spec.ba)

    ndim = qx.q.ndim
    with manual():
        y = jax.shard_map(
            body, mesh=mesh,
            in_specs=(_x_spec(ndim, part, lead),) + w_specs,
            out_specs=_out_spec(ndim, part, lead), check_vma=False,
        )(qx.q, *operands)
    if post is None:
        return rescale(y, qx.scale, image.scale, spec)
    return y
