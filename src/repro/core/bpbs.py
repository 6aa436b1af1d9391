"""Bit-parallel / bit-serial (BP/BS) multi-bit MVM (paper Fig. 4).

The B_A bits of each matrix element map to parallel CIMA columns; the B_X
bits of each input element are applied serially.  Every (bit-column,
bit-step) pair yields one mixed-signal column evaluation whose popcount is
digitized by the per-column ADC, then barrel-shifted by its joint
significance and accumulated by the near-memory digital datapath — in
time (over kx) and space (over ka).

Two implementations, which agree bit-for-bit (asserted in tests):

* the *physics* path through :mod:`repro.core.cima` (cell-by-cell), and
* the *fast* path below, which uses the GEMM identity
  ``d = sum_n m_n * s_a * s_x  =  2p - n_unmasked`` (XNOR) / ``d = p``
  (AND) so each plane-pair evaluation is one (masked) matmul followed by
  an affine map, the ADC model, and the inverse affine map.

Banking: the N (input) dimension is split into banks of ``bank_n`` rows
(2304 on the chip).  Each bank is a separate charge-share + ADC conversion;
bank partials are summed digitally.  This is exactly how the chip's 4x4
activity-gated banks compose larger dimensionalities, and it makes the
quantization boundary explicit for the roofline/kernel layers.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from .adc import adc_quantize_sum
from .quant import Coding, int_plane_list, int_to_planes, plane_weights
from .sparsity import element_mask


@dataclasses.dataclass(frozen=True)
class BpbsConfig:
    """Static configuration of one CIMU MVM."""

    ba: int = 4                    # matrix-element bits (parallel columns)
    bx: int = 4                    # input-element bits (serial steps)
    coding: Coding = Coding.XNOR
    bank_n: int = 2304             # rows per charge-share/ADC boundary
    adc_bits: int = 8
    adc_sigma_lsb: float = 0.0     # analog non-ideality (Fig 10), LSB units
    adaptive_range: bool = False   # ADC full-scale tracks unmasked rows
    ideal_adc: bool = False        # bypass the ADC (bit-true integer compute)
    # Sparsity-controller plane skip (Fig. 6b): gate the GEMM of any
    # (bank, kx) input bit plane that is all-zero across the batch.  BS
    # cost is linear in B_X, so each skipped plane is a saved serial step.
    # Bit-identical to the dense path by construction: only the plane dot
    # product (provably zero) is skipped — the ADC epilogue still runs.
    skip_zero_planes: bool = True

    def __post_init__(self):
        object.__setattr__(self, "coding", Coding(self.coding))

    @property
    def wa(self):
        return plane_weights(self.ba, self.coding)

    @property
    def wx(self):
        return plane_weights(self.bx, self.coding)


def weight_planes(w_q: jax.Array, cfg: BpbsConfig) -> jax.Array:
    """Matrix-element bit planes ``[N, B_A, M]`` (float32, exact small
    integers): the layout the Pallas kernel reads and a compiled
    :class:`~repro.accel.program.CimaImage` stores, as the chip lays a
    row's B_A bit-columns side by side.  Callers cast (int8 for the image
    and the kernel)."""
    return jnp.transpose(int_to_planes(w_q, cfg.ba, cfg.coding), (0, 2, 1))


def input_plane_list(x_q: jax.Array, cfg: BpbsConfig) -> tuple[list, jax.Array]:
    """Input bit planes as a list of ``x_q``-shaped arrays (plane order),
    with the controller mask folded in, and the mask.

    XNOR planes of masked (zero-valued) elements are zeroed — the
    capacitor-reset behaviour; AND planes of zero elements are all-zero
    by construction.  The Pallas kernel evaluates this on each VMEM tile
    of the input grid; :func:`input_planes` stacks it for the fast path.
    """
    mask = element_mask(x_q)
    if cfg.coding == Coding.XNOR and cfg.bx == 1:
        # one +-1 plane with the masked (zero) elements reset: the grid
        # {-1, 0, +1} itself
        return [x_q.astype(jnp.float32)], mask
    planes = int_plane_list(x_q, cfg.bx, cfg.coding)
    if cfg.coding == Coding.XNOR:
        planes = [p * mask for p in planes]
    return planes, mask


def input_planes(x_q: jax.Array, cfg: BpbsConfig) -> tuple[jax.Array, jax.Array]:
    """Input bit planes [..., N, B_X] with the controller mask folded in.

    Returns ``(planes, mask)``; see :func:`input_plane_list`.
    """
    planes, mask = input_plane_list(x_q, cfg)
    return jnp.stack(planes, axis=-1), mask


def adc_full_scale(nu: jax.Array, bank_rows, cfg: BpbsConfig):
    """The ADC full scale of one bank conversion (shared by the fast path,
    the physics reference, and the Pallas kernel epilogue — parity between
    them is structural, not copy-pasted).

    With ``adaptive_range`` the Sparsity Controller sets the range to the
    unmasked-row count ``nu`` (it knows the mask before the evaluation
    fires); otherwise the range is the bank's static row count.  Clamping
    to >= 1 happens inside :func:`repro.core.adc.adc_quantize_sum`.
    """
    return nu if cfg.adaptive_range else bank_rows


def gemm_adc_epilogue(
    d: jax.Array,
    nu: jax.Array,
    bank_rows,
    cfg: BpbsConfig,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """GEMM-identity epilogue of one plane-pair evaluation.

    ``d`` is the raw plane dot product; ``nu`` (broadcastable to ``d``) is
    the bank's unmasked-row count.  Recovers the column popcount
    (``p = (d + nu) / 2`` under XNOR, ``p = d`` under AND), applies the
    ADC transfer over :func:`adc_full_scale`, and maps back to the signed
    dot.  This is the single definition the fast path AND the Pallas
    kernel evaluate — the duplicated full-scale/``nu`` handling the
    backends used to carry inline.
    """
    from .cima import signed_dot_from_popcount

    if cfg.coding == Coding.XNOR:
        p = (d + nu) * 0.5
    else:
        p = d
    if cfg.ideal_adc:
        p_hat = p
    else:
        fs = adc_full_scale(nu, bank_rows, cfg)
        p_hat = adc_quantize_sum(p, fs, cfg.adc_bits, cfg.adc_sigma_lsb, key)
    return signed_dot_from_popcount(p_hat, nu, cfg.coding)


def bpbs_matmul_planes(
    x_q: jax.Array,               # [..., N] integers on the coding grid
    ws: jax.Array,                # [N, BA, M] weight bit planes (kernel layout)
    cfg: BpbsConfig,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """BP/BS MVM consuming pre-decomposed weight bit planes.

    This is the plane-level execution path: weights are stationary in the
    CIMA, so a compiled :class:`~repro.accel.program.CimaImage` supplies
    ``ws`` directly — in the kernel's ``[N, B_A, M]`` layout, any exact
    small-int dtype (int8 images stream at 1 byte/plane-element) — and no
    per-call ``quantize``/``weight_planes`` runs.  :func:`bpbs_matmul_int`
    is the on-the-fly wrapper that decomposes ``w_q`` first; both produce
    bit-identical results by construction.
    """
    xs, mask = input_planes(x_q, cfg)           # [..., N, BX], [..., N]
    n = x_q.shape[-1]
    wxv = jnp.asarray(cfg.wx, dtype=jnp.float32)
    wav = jnp.asarray(cfg.wa, dtype=jnp.float32)

    from repro.distributed.autoshard import cs

    m = ws.shape[2]
    y = jnp.zeros(x_q.shape[:-1] + (m,), dtype=jnp.float32)
    n_banks = -(-n // cfg.bank_n)
    for b in range(n_banks):
        s, e = b * cfg.bank_n, min((b + 1) * cfg.bank_n, n)
        # planes are exactly representable in bf16 (+-1/0/1 and {0,1});
        # halving the streamed bytes of the dominant GEMM is free accuracy-wise
        xb = xs[..., s:e, :].astype(jnp.bfloat16)
        wb = ws[s:e].astype(jnp.bfloat16)
        mb = mask[..., s:e]
        nu = jnp.sum(mb, axis=-1)                # [...] unmasked rows in bank
        # one GEMM per bank covering all (kx, ka) plane pairs.  Formulated
        # as a plain 2-D matmul [T*BX, N] @ [N, BA*M] — the chip's own
        # column-parallel layout — so it inherits the digital path's
        # sharding behaviour (N: FSDP, BA*M: TP).  The 4-D einsum form left
        # XLA all-reducing the full [tokens, BX, M, BA] tensor over the
        # data axis (§Perf cell c, iteration 1).
        lead = xb.shape[:-2]
        t = 1
        for dim in lead:
            t *= dim
        nb = e - s
        w2 = wb.reshape(nb, cfg.ba * m)
        # gather the (tiny, bf16) weight planes over the FSDP axis up front:
        # left to itself the partitioner all-reduces the full f32
        # [T*BX, BA*M] partial products over "data" — 4.3 GB vs the 33 MB
        # plane gather (§Perf cell c, iterations 1-2)
        w2 = cs(w2, (None, ["tp"]))
        x2 = jnp.swapaxes(xb, -1, -2).reshape(t * cfg.bx, nb)
        if cfg.skip_zero_planes:
            # Sparsity-controller skip (Fig. 6b): gate the bank's GEMM on
            # whether ANY of its input planes broadcasts a live bit.  A
            # skipped bank's dot products are exactly zero, so feeding the
            # zeros into the UNCHANGED epilogue below keeps the result
            # bit-identical to the dense path for every coding/precision/
            # noise setting (plane products are exact in f32).  The gate is
            # whole-bank here — splitting the fused [T*BX, nb] GEMM into
            # per-plane dots costs XLA-CPU ~1.7x on DENSE inputs, wiping
            # out the very savings being modeled — while the cost model
            # accounts skips per (bank, plane) serial step
            # (core.sparsity.count_zero_planes), and the Pallas kernel,
            # whose loop is already per serial step, gates per plane.
            d2 = jax.lax.cond(
                jnp.any(x2 != 0),
                lambda a: jnp.dot(a, w2, preferred_element_type=jnp.float32),
                lambda a: jnp.zeros((t * cfg.bx, cfg.ba * m), jnp.float32),
                x2,
            )
        else:
            d2 = jnp.dot(x2, w2, preferred_element_type=jnp.float32)
        d = d2.reshape(*lead, cfg.bx, cfg.ba, m)
        subkey = None
        if key is not None:
            key, subkey = jax.random.split(key)
        d_hat = gemm_adc_epilogue(d, nu[..., None, None, None],
                                  float(e - s), cfg, subkey)
        # near-memory datapath: barrel shift (plane weights) + accumulate.
        # Exact digital arithmetic: HIGHEST keeps the TPU from rounding
        # the (integer, up to bank-sized) plane sums to bf16 in the dot.
        y = y + jnp.einsum("...xam,x,a->...m", d_hat, wxv, wav,
                           precision=jax.lax.Precision.HIGHEST)
    return y


def bpbs_matmul_int(
    x_q: jax.Array,               # [..., N] integers on the coding grid
    w_q: jax.Array,               # [N, M]   integers on the coding grid
    cfg: BpbsConfig,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """BP/BS MVM on the integer grids: returns [..., M] (float32, integer-valued
    when ``adc_sigma_lsb == 0``).  Matches ``x_q @ w_q`` exactly whenever the
    per-bank column dynamic range fits the ADC (paper §3).

    On-the-fly wrapper: decomposes ``w_q`` per call, then runs the
    plane-level path (:func:`bpbs_matmul_planes`)."""
    return bpbs_matmul_planes(x_q, weight_planes(w_q, cfg), cfg, key)


def bpbs_matmul_planes_reference(
    x_q: jax.Array, ws: jax.Array, cfg: BpbsConfig
) -> jax.Array:
    """Physics-path reference via the cell-level CIMA model, consuming
    pre-decomposed weight planes ``ws`` [N, BA, M] (slow; tests only)."""
    from . import cima

    _, mask = input_planes(x_q, cfg)
    # NOTE: for the cell model, XNOR planes must stay +-1 and masking is a
    # separate signal; recompute unmasked planes here.
    planes = int_to_planes(x_q, cfg.bx, cfg.coding)
    n, m = ws.shape[0], ws.shape[2]
    wxv = jnp.asarray(cfg.wx, dtype=jnp.float32)
    wav = jnp.asarray(cfg.wa, dtype=jnp.float32)
    y = jnp.zeros(x_q.shape[:-1] + (m,), dtype=jnp.float32)
    for b in range(-(-n // cfg.bank_n)):
        s, e = b * cfg.bank_n, min((b + 1) * cfg.bank_n, n)
        nu = jnp.sum(mask[..., s:e], axis=-1)
        for ka in range(cfg.ba):
            for kx in range(cfg.bx):
                p = cima.column_popcount(
                    ws[s:e, ka, :].astype(jnp.float32),
                    planes[..., s:e, kx], mask[..., s:e], cfg.coding
                )
                if not cfg.ideal_adc:
                    fs = adc_full_scale(nu[..., None], float(e - s), cfg)
                    p = adc_quantize_sum(p, fs, cfg.adc_bits)
                d = cima.signed_dot_from_popcount(p, nu[..., None], cfg.coding)
                y = y + wxv[kx] * wav[ka] * d
    return y


def bpbs_matmul_int_reference(
    x_q: jax.Array, w_q: jax.Array, cfg: BpbsConfig
) -> jax.Array:
    """On-the-fly physics reference: decompose ``w_q``, then the cell model."""
    return bpbs_matmul_planes_reference(x_q, weight_planes(w_q, cfg), cfg)
