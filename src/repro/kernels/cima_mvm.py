"""Pallas TPU kernel: the CIMU's BP/BS mixed-signal MVM (paper Figs. 2-5).

TPU-native mapping of the chip's dataflow (see DESIGN.md §2):

* The 2304-row CIMA *bank* is the reduction tile — it is both the chip's
  charge-share/ADC boundary and (conveniently) a VMEM-sized, 128-aligned
  MXU tile (2304 = 18 * 128).  One full bank of weight bit planes at a
  256-column tile is ~590 KB of int8 — literally the chip's array size —
  and fits VMEM with room for double buffering.
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
Each input and weight plane arrives as its own dense 2D block, the
per-bank unmasked counts bank-major, and the plane liveness (plane-skip
gate) and per-bank ADC full scales as scalar-prefetch operands in SMEM:
every block obeys the TPU tiling rules, so the kernel compiles with
``interpret=False`` at any bank count (tests/test_chip_compile.py).

Inputs are int8 bit planes (HBM traffic = 1 byte/plane-element); they are
cast to bf16 in-kernel for the MXU (values are exactly representable; f32
accumulation of <=2304 unit products is exact).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bpbs import BpbsConfig, gemm_adc_epilogue


def _kernel(
    live_ref,   # SMEM [tiles*banks*BX] i32: is input plane (i, k, kx) live?
    fs_ref,     # SMEM [banks] f32: ADC full scale per bank (static gating)
    *refs,      # BX x [bb, bank_n] int8 input planes (masked), BA x
                # [bank_n, bm] int8 weight planes (bit-parallel), nu [bb, 1]
                # f32 unmasked rows of this bank, then the fused epilogue's
                # es_ref, pb_ref [1|bb, bm] f32 — then out_ref
    cfg: BpbsConfig,
    wx: tuple,
    wa: tuple,
    n_banks: int = 0,
    act: str = "",
    by_bits: int = 0,
):
    xs_refs, refs = refs[:cfg.bx], refs[cfg.bx:]
    ws_refs, refs = refs[:cfg.ba], refs[cfg.ba:]
    nu_ref, rest = refs[0], refs[1:]
    out_ref = rest[-1]  # [bb, bm] f32: recombined output
    fused = len(rest) > 1
    i, k = pl.program_id(0), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    nu = nu_ref[...]                                  # [bb, 1]
    fs_static = fs_ref[k]

    acc = jnp.zeros(out_ref.shape, dtype=jnp.float32)
    for kx in range(cfg.bx):
        xk = xs_refs[kx][...]                         # [bb, bank_n] int8

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
            # path for every coding/precision.  Liveness of this tile's
            # (bank, plane) is precomputed into SMEM (prepare step) and
            # read here as a scalar: the controller knows the mask before
            # the evaluation fires.
            live = live_ref[(i * n_banks + k) * cfg.bx + kx]
            ds = jax.lax.cond(
                live != 0, _gemms,
                lambda _: tuple(jnp.zeros(out_ref.shape, jnp.float32)
                                for _ in range(cfg.ba)),
                xk)
        else:
            ds = _gemms(xk)
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


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "block_b", "block_m", "interpret", "act",
                     "by_bits"),
)
def cima_mvm_planes(
    xs: jax.Array,          # [B, BX, N] int8 masked input planes
    ws: jax.Array,          # [N, BA, M] int8 weight planes
    nu: jax.Array,          # [B, n_banks] f32 unmasked rows per bank
    fs: jax.Array,          # [n_banks] f32 ADC full scale per bank
    cfg: BpbsConfig,
    block_b: int = 128,
    block_m: int = 128,
    interpret: bool = True,
    escale: Optional[jax.Array] = None,   # [M]|[B,M]|scalar: rescale*scale
    pbias: Optional[jax.Array] = None,    # [M]|scalar: datapath bias regs
    act: Optional[str] = None,
    by_bits: Optional[int] = None,
) -> jax.Array:
    """Raw kernel entry on pre-decomposed planes.  Returns [B, M] f32.

    ``escale``/``pbias``/``act``/``by_bits`` arm the fused near-memory
    datapath epilogue (paper Fig. 8): after the last bank accumulates,
    the kernel applies ``y*escale + pbias``, the activation, and the B_y
    saturation in-VMEM — the output leaves the kernel already
    post-reduced.  ``escale`` combines the quantization rescale
    (``x_scale * w_scale``) with the datapath scale registers; without
    the epilogue the kernel returns the recombined integer-grid output
    as before.
    """
    b, bx, n = xs.shape
    n_w, ba, m = ws.shape
    assert n_w == n and bx == cfg.bx and ba == cfg.ba
    n_banks = -(-n // cfg.bank_n)

    xs = _pad_to(_pad_to(xs, 0, block_b), 2, cfg.bank_n)
    ws = _pad_to(_pad_to(ws, 0, cfg.bank_n), 2, block_m)
    nu = _pad_to(nu, 0, block_b)
    bp, mp = xs.shape[0], ws.shape[2]

    fused = (escale is not None or pbias is not None
             or bool(act) or bool(by_bits))
    # scalar-prefetch operands (SMEM): per-(row tile, bank, input plane)
    # liveness for the plane-skip gate, and the per-bank ADC full scale
    live = jnp.any(
        xs.reshape(bp // block_b, block_b, cfg.bx, n_banks, cfg.bank_n) != 0,
        axis=(1, 4))                                  # [tiles, BX, banks]
    live = jnp.transpose(live, (0, 2, 1)).reshape(-1).astype(jnp.int32)
    scalars = [live, fs.astype(jnp.float32).reshape(-1)]
    # Planes flatten into their trailing axis (free, row-major reshapes):
    # input plane kx of bank k is the 2D tile (i, kx*banks + k) of
    # [B, BX*N], weight plane ka of column tile j the tile
    # (k, ka*col_tiles + j) of [N, BA*M].  One operand per plane keeps
    # every block a dense 2D tile (no strided plane slicing in-kernel).
    # Per-bank unmasked counts go bank-major, so each block is a legal
    # (block_b, 1) tile of a [banks, B, 1] array.
    col_tiles = mp // block_m
    xs2 = xs.reshape(bp, cfg.bx * n_banks * cfg.bank_n)
    ws2 = ws.reshape(n_banks * cfg.bank_n, cfg.ba * mp)
    operands = [xs2] * cfg.bx + [ws2] * cfg.ba + [
        jnp.transpose(nu)[:, :, None]]
    in_specs = [
        pl.BlockSpec((block_b, cfg.bank_n),
                     lambda i, j, k, *_, kx=kx: (i, kx * n_banks + k))
        for kx in range(cfg.bx)
    ] + [
        pl.BlockSpec((cfg.bank_n, block_m),
                     lambda i, j, k, *_, ka=ka: (k, ka * col_tiles + j))
        for ka in range(cfg.ba)
    ] + [
        pl.BlockSpec((pl.Squeezed(), block_b, 1),
                     lambda i, j, k, *_: (k, i, 0)),
    ]
    if fused:
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

    grid = (bp // block_b, mp // block_m, n_banks)
    out = pl.pallas_call(
        functools.partial(
            _kernel,
            cfg=cfg,
            wx=tuple(float(v) for v in cfg.wx),
            wa=tuple(float(v) for v in cfg.wa),
            n_banks=n_banks,
            act=(act or "") if fused else "",
            by_bits=(by_bits or 0) if fused else 0,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((block_b, block_m),
                                   lambda i, j, k, *_: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((bp, mp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="cima_bpbs_mvm",
    )(*scalars, *operands)
    return out[:b, :m]


def prepare_inputs(x_q: jax.Array, cfg: BpbsConfig):
    """Input bit planes + per-bank unmasked counts (the w2b Reshaping Buffer
    and Sparsity Controller roles, in XLA)."""
    from repro.core.bpbs import input_planes

    lead = x_q.shape[:-1]
    n = x_q.shape[-1]
    x2 = x_q.reshape(-1, n)
    planes, mask = input_planes(x2, cfg)           # [B, N, BX], [B, N]
    xs = jnp.transpose(planes, (0, 2, 1)).astype(jnp.int8)
    n_banks = -(-n // cfg.bank_n)
    pad = n_banks * cfg.bank_n - n
    mask_p = jnp.pad(mask, ((0, 0), (0, pad)))
    nu = mask_p.reshape(-1, n_banks, cfg.bank_n).sum(-1).astype(jnp.float32)
    return xs, nu, lead


def bank_full_scales(n: int, cfg: BpbsConfig) -> jax.Array:
    """Static ADC full scale per bank: the bank's (possibly ragged last)
    row count.  Derivable from N alone, so a stored weight image never
    needs to carry it."""
    n_banks = -(-n // cfg.bank_n)
    sizes = np.minimum(
        np.full(n_banks, cfg.bank_n), n - np.arange(n_banks) * cfg.bank_n
    )
    return jnp.asarray(sizes, dtype=jnp.float32)


def prepare_weights(w_q: jax.Array, cfg: BpbsConfig):
    """Weight bit planes [N, BA, M] (precomputable: weights are stationary
    in the CIMA — reloading costs ~18k cycles on-chip, paper Fig. 8).
    This is exactly the layout a :class:`~repro.accel.program.CimaImage`
    stores once at program-load time."""
    from repro.core.bpbs import weight_planes

    wp = weight_planes(w_q, cfg)                   # [N, M, BA]
    ws = jnp.transpose(wp, (0, 2, 1)).astype(jnp.int8)
    return ws, bank_full_scales(w_q.shape[0], cfg)


def cima_mvm(
    x_q: jax.Array,
    w_q: jax.Array,
    cfg: BpbsConfig,
    block_b: int = 128,
    block_m: int = 128,
    interpret: bool = True,
    escale: Optional[jax.Array] = None,
    pbias: Optional[jax.Array] = None,
    act: Optional[str] = None,
    by_bits: Optional[int] = None,
) -> jax.Array:
    """BP/BS MVM on integer-grid operands: [..., N] x [N, M] -> [..., M].
    ``escale``/``pbias``/``act``/``by_bits`` arm the fused datapath
    epilogue (see :func:`cima_mvm_planes`)."""
    xs, nu, lead = prepare_inputs(x_q, cfg)
    ws, fs = prepare_weights(w_q, cfg)
    y = cima_mvm_planes(xs, ws, nu, fs, cfg, block_b, block_m, interpret,
                        escale, pbias, act, by_bits)
    return y.reshape(*lead, w_q.shape[1])


def cima_mvm_from_planes(
    x_q: jax.Array,
    ws: jax.Array,                # [N, BA, M] int8 weight bit planes
    cfg: BpbsConfig,
    block_b: int = 128,
    block_m: int = 128,
    interpret: bool = True,
    escale: Optional[jax.Array] = None,
    pbias: Optional[jax.Array] = None,
    act: Optional[str] = None,
    by_bits: Optional[int] = None,
) -> jax.Array:
    """BP/BS MVM consuming a pre-compiled weight image: the weight-
    stationary serving path.  Only the (dynamic) inputs are decomposed
    per call; the planes come straight from the loaded program."""
    xs, nu, lead = prepare_inputs(x_q, cfg)
    fs = bank_full_scales(ws.shape[0], cfg)
    y = cima_mvm_planes(xs, ws, nu, fs, cfg, block_b, block_m, interpret,
                        escale, pbias, act, by_bits)
    return y.reshape(*lead, ws.shape[2])
