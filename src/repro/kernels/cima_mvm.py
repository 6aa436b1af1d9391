"""Pallas TPU kernel: the CIMU's BP/BS mixed-signal MVM (paper Figs. 2-5).

TPU-native mapping of the chip's dataflow (see DESIGN.md §2):

* The 2304-row CIMA *bank* is the reduction tile — it is both the chip's
  charge-share/ADC boundary and (conveniently) a VMEM-sized, 128-aligned
  MXU tile (2304 = 18 * 128).  One full bank of weight bit planes at a
  256-column tile is ~590 KB of int8 — literally the chip's array size —
  and fits VMEM with room for double buffering.  The column tile is the
  array's 256 columns, or M rounded up to 128 lanes where M is narrower.
  Where N fits one bank, the block is N rounded up to 128 lanes
  (:func:`bank_width`): the ADC still converts one bank over its real
  rows, and no zero lanes ride along.
* The input arrives as its integer grid, one byte per element where the
  grid fits int8.  The w2b Reshaping Buffer and Sparsity Controller sit
  next to the array: from the grid's VMEM tile the kernel forms the
  masked bit planes, the bank's unmasked-row count ``nu`` and each
  plane's liveness for the plane-skip gate, from the same definitions
  the fast path evaluates (:func:`repro.core.bpbs.input_plane_list`),
  once per (row tile, bank).  Where the output spans several column
  tiles, VMEM scratch holds them across the column tiles, as the chip's
  reshaping buffer holds an input vector for every column it drives.
* B_A weight bit planes are laid out in parallel in the last (lane)
  dimension, as the chip lays bit-columns side by side; B_X input planes
  stream through an in-kernel serial loop, as the chip streams input bits.
* Each (kx, ka) plane pair is one MXU matmul over the bank — the
  mixed-signal column evaluation — followed by the ADC transfer (clip +
  round to 256 codes over the bank's full scale) on the VPU.
* The near-memory digital datapath is the fused epilogue: barrel-shift
  (plane-weight scaling) and accumulation over kx, ka, and banks, without
  any HBM round-trip between reduce and post-ops.

Grid: ``(batch_tiles, column_tiles, banks)`` with the bank dimension
innermost ("arbitrary" semantics) so output tiles accumulate in place.
The input grid and each weight plane arrive as dense 2D blocks, and the
per-bank ADC full scales as a scalar-prefetch operand in SMEM (with the
held planes, the column axis is "arbitrary" too): every
block obeys the TPU tiling rules, so the kernel compiles with
``interpret=False`` at any bank count (tests/test_chip_compile.py).

Planes are cast to bf16 in-kernel for the MXU (values are exactly
representable; f32 accumulation of <=2304 unit products is exact).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bpbs import BpbsConfig, gemm_adc_epilogue, input_plane_list
from repro.core.quant import Coding, int_range

LANES = 128     # TPU vector lanes: a block's minor dimension is a multiple


def _kernel(
    fs_ref,     # SMEM [banks] f32: ADC full scale per bank (static gating)
    x_ref,      # [bb, bank_k] input grid tile (int8 where the grid fits)
    *refs,      # BA x [bank_k, bm] int8 weight planes (bit-parallel), then
                # the fused epilogue's es_ref, pb_ref [1|bb, bm] f32, then
                # out_ref, then (held planes only) the scratch xp_ref, nu_ref,
                # live_ref
    cfg: BpbsConfig,
    wx: tuple,
    wa: tuple,
    n_banks: int = 0,
    act: str = "",
    by_bits: int = 0,
    held: bool = False,
):
    ws_refs, rest = refs[:cfg.ba], refs[cfg.ba:]
    if held:
        rest, (xp_ref, nu_ref, live_ref) = rest[:-3], rest[-3:]
    out_ref = rest[-1]  # [bb, bm] f32: recombined output
    fused = len(rest) > 1
    j, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def _prepare():
        # w2b reshaping buffer + sparsity controller, on this tile of the
        # grid: the masked bit planes, the bank's unmasked-row count and
        # each plane's liveness, from the same definitions the fast path
        # evaluates.  Padded lanes and rows are zero: masked, so never
        # counted.  The controller knows a plane's liveness before the
        # evaluation fires: under XNOR every plane of an unmasked element
        # is +-1, so a plane is live iff the tile has an unmasked element;
        # AND planes are {0, 1}.
        planes, mask = input_plane_list(x_ref[...].astype(jnp.float32), cfg)
        nu = jnp.sum(mask, axis=1, keepdims=True)     # [bb, 1]
        live = [(jnp.max(nu) if cfg.coding == Coding.XNOR
                 else jnp.max(p)) > 0 for p in planes]
        return planes, nu, live

    if held:
        # Wide outputs: the buffer holds the row tile's planes of every
        # bank across the column tiles, so they are formed once per row
        # tile (at the first column tile), not once per column tile.
        @pl.when(j == 0)
        def _fill():
            planes, nu, live = _prepare()
            nu_ref[k] = nu
            for kx in range(cfg.bx):
                xp_ref[k * cfg.bx + kx] = planes[kx].astype(xp_ref.dtype)
                live_ref[k * cfg.bx + kx] = live[kx].astype(jnp.int32)

        planes = [xp_ref[k * cfg.bx + kx] for kx in range(cfg.bx)]
        nu = nu_ref[k]
        live = [live_ref[k * cfg.bx + kx] != 0 for kx in range(cfg.bx)]
    else:
        planes, nu, live = _prepare()
    fs_static = fs_ref[k]

    acc = jnp.zeros(out_ref.shape, dtype=jnp.float32)
    for kx in range(cfg.bx):
        def _gemms(xk):
            x = xk.astype(jnp.bfloat16)
            # mixed-signal column evaluations: one MXU pass per plane pair
            return tuple(
                jax.lax.dot_general(
                    x, ws_refs[ka][...].astype(jnp.bfloat16),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                for ka in range(cfg.ba))

        if cfg.skip_zero_planes:
            # Sparsity-controller plane skip (Fig. 6b): an all-zero input
            # bit plane broadcasts nothing, so the serial step's MXU
            # passes are gated off at runtime.  Only the (provably zero)
            # dot products are skipped; the ADC epilogue below still runs
            # on the zeros, keeping the output bit-identical to the dense
            # path for every coding/precision.
            ds = jax.lax.cond(
                live[kx], _gemms,
                lambda _: tuple(jnp.zeros(out_ref.shape, jnp.float32)
                                for _ in range(cfg.ba)),
                planes[kx])
        else:
            ds = _gemms(planes[kx])
        for ka in range(cfg.ba):
            # popcount recovery + SAR ADC transfer + signed-dot recovery:
            # the same epilogue definition the fast path evaluates (no
            # noise draw in-kernel: key=None — at adc_sigma_lsb > 0 this
            # warns that the kernel path runs noiseless)
            d_hat = gemm_adc_epilogue(ds[ka], nu, fs_static, cfg)
            # near-memory datapath: barrel shift + accumulate (time & space)
            acc = acc + (wx[kx] * wa[ka]) * d_hat
    out_ref[...] += acc

    if fused:
        es_ref, pb_ref = rest[0], rest[1]

        # near-memory datapath post-reduce (paper Fig. 8), fused after the
        # LAST bank accumulates: combined rescale+scale registers -> bias
        # registers -> activation -> B_y output saturation, all before the
        # result ever leaves the kernel (no HBM round-trip).
        @pl.when(k == n_banks - 1)
        def _postreduce():
            y = out_ref[...] * es_ref[...] + pb_ref[...]
            if act:
                from repro.core.datapath import ACTIVATIONS

                y = ACTIVATIONS[act](y)
            if by_bits:
                hi = 2.0 ** (by_bits - 1) - 1
                y = jnp.clip(y, -(hi + 1), hi)
            out_ref[...] = y


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def bank_width(n: int, cfg: BpbsConfig) -> int:
    """Lanes of one contraction block: a whole bank, or for N inside one
    bank its rows rounded up to the 128-lane tile.  An ADC conversion
    still covers one bank; a narrow bank just carries no zero lanes it
    never had."""
    return min(cfg.bank_n, -(-n // LANES) * LANES)


def _grid_dtype(cfg: BpbsConfig):
    """The narrowest integer dtype that holds the input coding grid."""
    lo, hi = int_range(cfg.bx, cfg.coding)
    return jnp.int8 if -128 <= lo and hi <= 127 else jnp.int32


def cima_mvm(
    x_q: jax.Array,                       # [..., N] integer input grid
    ws: jax.Array,                        # [N, BA, M] int8 weight planes
    cfg: BpbsConfig,
    block_b: int = 128,
    block_m: int = 256,
    interpret: Optional[bool] = None,
    escale: Optional[jax.Array] = None,   # [M]|[...,M]|scalar
    pbias: Optional[jax.Array] = None,    # [M]|scalar: datapath bias regs
    act: Optional[str] = None,
    by_bits: Optional[int] = None,
) -> jax.Array:
    """The BP/BS MVM kernel: the quantized inputs ``x_q`` times the weight
    bit planes ``ws`` (:func:`repro.core.bpbs.weight_planes`, the layout a
    :class:`~repro.accel.program.CimaImage` stores) -> [..., M] f32.

    The input grid reaches the kernel as one ``[rows, N]`` operand in the
    narrowest integer dtype that holds it; the w2b Reshaping Buffer and
    Sparsity Controller roles (bit planes, per-bank unmasked counts,
    plane liveness) run in-kernel on its VMEM tile, once per (row tile,
    bank).  The ADC full scale of each bank is its row count, derived
    from N.  ``block_m`` shrinks to M rounded up to 128 lanes where M is
    narrower.  ``interpret=None`` compiles the kernel on a TPU and
    interprets it elsewhere.

    ``escale``/``pbias``/``act``/``by_bits`` arm the fused near-memory
    datapath epilogue (paper Fig. 8): after the last bank accumulates,
    the kernel applies ``y*escale + pbias``, the activation, and the B_y
    saturation in-VMEM — the output leaves the kernel already
    post-reduced.  ``escale`` combines the quantization rescale
    (``x_scale * w_scale``) with the datapath scale registers; without
    the epilogue the kernel returns the recombined integer-grid output.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    lead, n = x_q.shape[:-1], ws.shape[0]
    n_banks = -(-n // cfg.bank_n)
    rows = np.minimum(cfg.bank_n, n - np.arange(n_banks) * cfg.bank_n)
    with jax.named_scope("cima.planes"):
        x2 = x_q.reshape(-1, x_q.shape[-1]).astype(_grid_dtype(cfg))
        fs = jnp.asarray(rows, dtype=jnp.float32)
    y = _cima_mvm_planes(x2, ws, fs, cfg, block_b, block_m, interpret,
                         escale, pbias, act, by_bits)
    with jax.named_scope("cima.post"):
        return y.reshape(*lead, ws.shape[2])


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "block_b", "block_m", "interpret", "act",
                     "by_bits"),
)
def _cima_mvm_planes(
    x: jax.Array,           # [B, N] integer input grid
    ws: jax.Array,          # [N, BA, M] int8 weight planes
    fs: jax.Array,          # [n_banks] f32 ADC full scale per bank
    cfg: BpbsConfig,
    block_b: int,
    block_m: int,
    interpret: bool,
    escale: Optional[jax.Array],
    pbias: Optional[jax.Array],
    act: Optional[str],
    by_bits: Optional[int],
) -> jax.Array:
    """The ``pallas_call`` on 2D operands, padded to its blocks: [B, M]."""
    b, n = x.shape
    n_w, ba, m = ws.shape
    assert n_w == n and ba == cfg.ba
    n_banks = -(-n // cfg.bank_n)
    bank_k = bank_width(n, cfg)
    block_m = min(block_m, -(-m // LANES) * LANES)

    with jax.named_scope("cima.pad"):
        x = _pad_to(_pad_to(x, 0, block_b), 1, bank_k)
        ws = _pad_to(_pad_to(ws, 0, bank_k), 2, block_m)
    bp, mp = x.shape[0], ws.shape[2]

    fused = (escale is not None or pbias is not None
             or bool(act) or bool(by_bits))
    # Weight planes flatten into their trailing axis (a free, row-major
    # reshape): plane ka of column tile j in bank k is the tile
    # (k, ka*col_tiles + j) of [N, BA*M].  One operand per plane keeps
    # every block a dense 2D tile (no strided plane slicing in-kernel).
    col_tiles = mp // block_m
    with jax.named_scope("cima.planes"):
        scalars = [fs.astype(jnp.float32).reshape(-1)]
        operands = [x] + [ws.reshape(n_banks * bank_k, cfg.ba * mp)] * cfg.ba
    in_specs = [
        pl.BlockSpec((block_b, bank_k), lambda i, j, k, *_: (i, k)),
    ] + [
        pl.BlockSpec((bank_k, block_m),
                     lambda i, j, k, *_, ka=ka: (k, ka * col_tiles + j))
        for ka in range(cfg.ba)
    ]
    if fused:
        @jax.named_scope("cima.pad")
        def col_vec(v, fill):
            if v is None:
                v = jnp.full((1, m), fill, jnp.float32)
            else:
                v = jnp.asarray(v, jnp.float32)
                if v.ndim >= 2:
                    # per-ROW operand (batch-decoupled input scales folded
                    # into the datapath registers): one row of scale
                    # registers per batch row, blocked like the output
                    v = v.reshape(-1, v.shape[-1])
                    v = jnp.broadcast_to(v, (v.shape[0], m))
                else:
                    v = jnp.broadcast_to(v.reshape(-1), (m,)).reshape(1, m)
            v = _pad_to(v, 1, block_m)
            return _pad_to(v, 0, block_b) if v.shape[0] > 1 else v

        def vec_spec(v):
            if v.shape[0] > 1:
                return pl.BlockSpec((block_b, block_m),
                                    lambda i, j, k, *_: (i, j))
            return pl.BlockSpec((1, block_m), lambda i, j, k, *_: (0, j))

        es, pb = col_vec(escale, 1.0), col_vec(pbias, 0.0)
        operands += [es, pb]
        in_specs += [vec_spec(es), vec_spec(pb)]

    # Wider than one column tile, a row tile's planes are held in VMEM
    # (int8: exact for {-1, 0, +1}) across the column tiles, so the column
    # axis runs in order, and the scoped VMEM budget grows by the buffer
    # (its `nu` column pads to a lane tile).
    held = col_tiles > 1
    scratch = [
        pltpu.VMEM((n_banks * cfg.bx, block_b, bank_k), jnp.int8),
        pltpu.VMEM((n_banks, block_b, 1), jnp.float32),
        pltpu.SMEM((n_banks * cfg.bx,), jnp.int32),
    ] if held else []
    held_bytes = n_banks * block_b * (cfg.bx * bank_k + 4 * LANES)

    grid = (bp // block_b, col_tiles, n_banks)
    kernel = pl.pallas_call(
        functools.partial(
            _kernel,
            cfg=cfg,
            wx=tuple(float(v) for v in cfg.wx),
            wa=tuple(float(v) for v in cfg.wa),
            n_banks=n_banks,
            act=(act or "") if fused else "",
            by_bits=(by_bits or 0) if fused else 0,
            held=held,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((block_b, block_m),
                                   lambda i, j, k, *_: (i, j)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((bp, mp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "arbitrary" if held else "parallel",
                "arbitrary"),
            vmem_limit_bytes=(32 << 20) + held_bytes if held else None,
        ),
        interpret=interpret,
        name="cima_bpbs_mvm",
    )
    with jax.named_scope("cima.kernel"):
        out = kernel(*scalars, *operands)
    with jax.named_scope("cima.post"):
        return out[:b, :m]
