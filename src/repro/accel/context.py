"""Execution context, scoped overrides, and the energy trace hook.

* :class:`ExecContext` carries per-call runtime state (the PRNG key for
  ADC noise) into a backend.
* :func:`override` is a context manager that rewrites every
  policy-managed spec at dispatch time — the eval-parity recipe
  (``with accel.override(backend="digital_int"): ...``) flips a whole
  model between substrates without rebuilding configs.
* :func:`trace` collects one :class:`MvmRecord` per dispatched matmul so
  :mod:`repro.core.energy` and the roofline can be fed from the *same*
  spec the compute used (no parallel bookkeeping to drift).

Both :func:`override` and :func:`trace` act at JAX *trace* time: wrap the
call that traces (the first call of a fresh ``jit``, or any eager call).
A cached jit executable replays compiled code and neither re-dispatches
nor re-records.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Iterator, Optional

import jax


@dataclasses.dataclass
class ExecContext:
    """Runtime state threaded into a backend call."""

    key: Optional[jax.Array] = None     # PRNG key for ADC noise sampling
    # compiled weight image for this projection (repro.accel.program):
    # when armed, the backend consumes precompiled bit planes instead of
    # quantizing the weight operand — the weight-stationary serving path
    image: Optional[object] = None      # CimaImage | None
    # fused near-memory datapath epilogue (repro.core.datapath): when
    # armed, the backend applies scale/bias/activation/B_y-saturation on
    # the recombined output before returning — the chip's post-reduce
    # pipeline, with no HBM round-trip between reduce and post-ops
    post: Optional[object] = None       # core.datapath.Postreduce | None
    # conv window size: when set, ``x`` is a layer's pixels [B, H, W, C]
    # and the backend forms the k x k windows after quantizing, on the
    # integer input grid (the dispatcher sets it for backends that do)
    window: Optional[int] = None


# ------------------------------------------------------------- overrides

_OVERRIDE_STACK: list[dict] = []


@contextlib.contextmanager
def override(**spec_kw) -> Iterator[None]:
    """Scoped spec rewrite applied to every policy-managed dispatch.

    Any :class:`~repro.accel.spec.ExecSpec` field can be overridden, most
    usefully ``backend`` (eval parity), ``ba``/``bx`` (precision sweeps)
    or ``ideal_adc`` (isolate operand quantization from ADC effects).
    Nested overrides compose; inner wins per field.  Calls that pass
    ``spec=None`` (projections that are digital *by design*, e.g. MoE
    routers) are never rewritten.
    """
    from .spec import ExecSpec

    fields = {f.name for f in dataclasses.fields(ExecSpec)}
    unknown = set(spec_kw) - fields
    if unknown:
        raise TypeError(
            f"override(): unknown ExecSpec field(s) {sorted(unknown)}; "
            f"valid: {sorted(fields)}")
    _OVERRIDE_STACK.append(dict(spec_kw))
    try:
        yield
    finally:
        _OVERRIDE_STACK.pop()


def current_override() -> dict:
    """The merged override in effect (inner scopes win)."""
    merged: dict = {}
    for frame in _OVERRIDE_STACK:
        merged.update(frame)
    return merged


# ----------------------------------------------------------- energy trace

@dataclasses.dataclass(frozen=True)
class MvmRecord:
    """One dispatched MVM: the resolved spec plus its static shape.

    ``program`` marks dispatches served from a compiled
    :class:`~repro.accel.program.CimaImage` (zero weight quantize /
    plane-decompose ops).  ``loads``/``load_segments`` charge the
    weight-stationary model's reload schedule: a dispatch whose image is
    *streamed* (not resident under the allocator's capacity) rewrites
    ``load_segments`` 768-b row segments per image copy; ``loads`` counts
    copies and is scaled by :func:`vmapped` exactly like ``calls``
    (scanned layers / experts are separate array loads, batch rows are
    not).

    ``stream_overlap``/``load_prologue`` carry the allocator's
    double-buffer schedule (DESIGN.md §13): when set, the image's segment
    prefetch into the spare bank set runs concurrently with CIMU compute,
    so :func:`energy_summary` charges ``max(compute, load)`` wall cycles
    per image copy instead of their sum — except for ``load_prologue``
    copies (the first load of a pass has no compute to hide behind; NOT
    scaled by :func:`vmapped`, the prologue is charged exactly once).
    Load *energy* is always billed in full: pJ is work done, cycles are
    wall time.
    """

    tag: str          # the layer path the policy resolved (spec.tag)
    backend: str
    n: int            # contraction dim (input vector length) — LOGICAL
    m: int            # output dim — LOGICAL (full, never per-shard)
    ba: int
    bx: int
    calls: int        # number of row-vector MVMs (prod of leading dims)
    program: bool = False   # served from a compiled weight image?
    loads: int = 0          # image-copy reloads charged to this dispatch
    load_segments: int = 0  # 768-b row segments per reload (per device)
    # double-buffered streaming (repro.accel.program, DESIGN.md §13):
    # ``stream_overlap`` marks reloads the allocator scheduled to
    # prefetch into the spare bank set while the other set computes;
    # ``load_prologue`` counts this dispatch's un-hideable first loads
    # (1 on the first streamed dispatch of a trace, else 0 — NOT scaled
    # by vmapped, a pass has exactly one pipeline fill).
    stream_overlap: bool = False
    load_prologue: int = 0
    # multi-chip mapping (repro.accel.shard): the record is emitted once
    # per LOGICAL matmul before shard_map — a sharded trace has the same
    # record count/calls/loads as the unsharded trace — and these two
    # fields carry how the work was cut so energy_summary can derive
    # per-device wall cycles (local tile) and system energy (x devices).
    devices: int = 1        # mesh "model"-axis shards executing this MVM
    partition: str = ""     # "col" | "row" | "" (unsharded)
    # mesh "data"-axis replicas: batch rows split over "data" while the
    # image (and its reloads) replicate per data shard — per-device wall
    # cycles divide the calls, system energy multiplies the loads
    data_shards: int = 1
    # fused near-memory datapath: post-reduce ops per output element
    # (scale / bias / activation / saturate each count 1) — what
    # energy_summary charges as datapath post-op energy
    post_ops: int = 0
    # measured input sparsity (repro.core.sparsity, paper Fig. 6b): the
    # fraction of zero-valued quantized input elements whose broadcast
    # the AND-logic controller gates off.  Only measurable when the
    # dispatch sees CONCRETE inputs (an eager call under trace()); a
    # jitted trace records None and energy_summary falls back to its
    # uniform ``sparsity`` argument.  Positions marked pad by an ambient
    # ``pad_positions`` scope are excluded — left-pad zeros are not
    # exploitable sparsity.
    sparsity: Optional[float] = None
    # measured plane-level skips (repro.core.sparsity.count_zero_planes):
    # all-zero (bank, input-plane) serial steps the controller skips
    # outright, out of ``planes_total = n_banks * bx`` at the spec's
    # banking.  Same eager-only caveat as ``sparsity``; energy_summary
    # discounts cycles and per-conversion pJ by the measured fraction —
    # the hot path's savings estimate comes from these, not the uniform
    # ``sparsity`` argument.
    planes_skipped: Optional[int] = None
    planes_total: Optional[int] = None
    # the ambient vmapped()/scan scale product at record time (scanned
    # layers x experts visible to this dispatch).  ``calls`` and ``loads``
    # are already multiplied by it; the tuner's repricer needs the raw
    # factor to reconstruct how many image-copy reloads this dispatch
    # WOULD charge if a candidate allocation streamed its image
    # (loads-if-streamed == copies, exactly what the traced ``loads``
    # equals whenever the image actually streamed).
    copies: int = 1
    # 1 where this conv dispatch formed its windows on the integer input
    # grid (quantized per pixel, then windowed), 0 where it was handed
    # float windows or is no conv
    grid_windows: int = 0


class Trace(list):
    """The record buffer a :func:`trace` scope yields: a plain list of
    :class:`MvmRecord` plus the VDD corner the run was traced *for*.

    Carrying the corner on the buffer threads it from the one place a
    run's operating point is decided (the ``trace(vdd=...)`` call) into
    :func:`energy_summary`, instead of every pricing call re-defaulting
    it independently."""

    def __init__(self, vdd: Optional[float] = None):
        super().__init__()
        self.vdd = vdd


_TRACE_STACK: list[list] = []
_CALL_SCALE_STACK: list[int] = []


@contextlib.contextmanager
def trace(vdd: Optional[float] = None) -> Iterator[Trace]:
    """Collect an :class:`MvmRecord` per dispatched matmul in this scope.

    ``vdd`` (optional) stamps the supply corner the run targets onto the
    yielded :class:`Trace`; :func:`energy_summary` then prices at that
    corner without the caller re-passing it.  Validated against the
    chip's measured corners up front.
    """
    if vdd is not None:
        from repro.core.energy import validate_vdd

        validate_vdd(vdd)
    buf = Trace(vdd=vdd)
    _TRACE_STACK.append(buf)
    try:
        yield buf
    finally:
        _TRACE_STACK.pop()


@contextlib.contextmanager
def vmapped(n: int) -> Iterator[None]:
    """Scale recorded call counts by ``n`` for dispatches whose mapped
    axis is invisible to the dispatcher's ``x.shape``.

    ``jax.vmap`` and ``jax.lax.scan`` trace their body ONCE, so a caller
    that maps over e.g. MoE experts or scanned transformer layers must
    wrap the mapped call in ``with accel.vmapped(n):`` for the energy
    trace to count every instance's MVMs (the model zoo does this for
    its expert vmaps and layer scans).  Nested scopes multiply.
    """
    _CALL_SCALE_STACK.append(int(n))
    try:
        yield
    finally:
        _CALL_SCALE_STACK.pop()


def record(rec: MvmRecord) -> None:
    if not _TRACE_STACK:
        return
    # vmapped/scanned instances scale the work (calls, loads) but NOT the
    # prologue: the double-buffer pipeline fills once per pass, and every
    # later instance's load hides behind the previous instance's compute
    for n in _CALL_SCALE_STACK:
        rec = dataclasses.replace(rec, calls=rec.calls * n,
                                  loads=rec.loads * n,
                                  copies=rec.copies * n)
    for buf in _TRACE_STACK:
        buf.append(rec)


def streamed_load_seen() -> bool:
    """Has the innermost trace scope already recorded a streamed load?

    The dispatcher uses this to place the double-buffer *prologue*: the
    first streamed dispatch of a pass has no in-flight compute to hide
    its load behind, every later one prefetches during the previous
    dispatch's MVMs.  Innermost scope on purpose — a nested trace is a
    fresh pass from its own first load.
    """
    return any(r.loads for r in _TRACE_STACK[-1]) if _TRACE_STACK else False


# ------------------------------------------------------------- ADC noise

_NOISE_STACK: list[list] = []      # frames of [key, counter]


@contextlib.contextmanager
def adc_noise(key: jax.Array) -> Iterator[None]:
    """Scoped PRNG source for ADC noise sampling (``adc_sigma_lsb > 0``).

    Without a key the analog non-ideality model is deterministic-off
    (``adc_quantize_sum`` skips the noise draw), so specs with
    ``adc_sigma_lsb > 0`` need ``with accel.adc_noise(jax.random.PRNGKey
    (0)): ...`` around the (tracing) call.  Each dispatched matmul folds
    a fresh counter into the key, decorrelating noise across layers.
    """
    _NOISE_STACK.append([key, 0])
    try:
        yield
    finally:
        _NOISE_STACK.pop()


def next_noise_key() -> Optional[jax.Array]:
    """A fresh per-dispatch key from the innermost adc_noise scope."""
    if not _NOISE_STACK:
        return None
    frame = _NOISE_STACK[-1]
    frame[1] += 1
    return jax.random.fold_in(frame[0], frame[1])


def tracing() -> bool:
    return bool(_TRACE_STACK)


# ------------------------------------------------------------ pad positions

_PAD_STACK: list = []


@contextlib.contextmanager
def pad_positions(mask) -> Iterator[None]:
    """Mark which leading positions of the activations are PADDING.

    ``mask`` is boolean (True = real token), shaped like the activations'
    leading dims (e.g. ``[B, S]`` for a padded prefill).  Measured-
    sparsity/plane-skip accounting excludes masked-out positions: left-pad
    zeros look exactly like exploitable input sparsity to the dispatcher,
    but the controller never saves real work on tokens that don't exist —
    counting them overstates the savings.

    Eager-only like the measurement itself: inside a jit trace the
    activations are Tracers and nothing is measured anyway, so a Tracer
    mask is simply ignored.  A mask whose shape doesn't prefix-match the
    activation being measured is ignored too (e.g. the single-token
    unembed slice of a padded prefill).
    """
    _PAD_STACK.append(mask)
    try:
        yield
    finally:
        _PAD_STACK.pop()


def current_pad_mask():
    """The innermost ambient pad mask (None outside any scope)."""
    return _PAD_STACK[-1] if _PAD_STACK else None


def energy_summary(records, vdd: Optional[float] = None,
                   sparsity: float = 0.0, readout: str = "adc") -> dict:
    """Chip-model cost of a traced run, from :mod:`repro.core.energy`.

    ``vdd`` resolves in order: an explicit argument, the corner stamped
    on the :class:`Trace` buffer (``trace(vdd=...)``), then the 0.85 V
    low-power corner.  Only the chip's measured corners are accepted —
    anything else raises (there is no interpolation model between them).

    ``sparsity`` is the uniform input-sparsity assumption; a record that
    carries its own measured ``MvmRecord.sparsity`` (eager dispatches —
    see the field) uses that instead, and the calls-weighted mean of the
    measured values is surfaced as ``input_sparsity`` (None when nothing
    was measured).

    Records carrying measured ``planes_skipped``/``planes_total``
    additionally discount CIMU cycles and every per-conversion pJ term by
    the skipped-plane fraction (the Fig. 6b controller skips all-zero
    (bank, input-plane) serial steps outright — see
    ``BpbsConfig.skip_zero_planes``); the calls-weighted mean fraction is
    surfaced as ``plane_skip`` (None when nothing was measured).  This is
    the measured hot-path savings — the uniform ``sparsity`` argument
    only gates broadcast energy of the surviving conversions.

    Digital records are counted (``mvms``) but carry no accelerator
    energy — they never touched the CIMU.  Dispatches whose weight image
    is *streamed* (over the bank allocator's capacity) additionally
    charge the matrix (re)load: ``load_segments`` 768-b row segments per
    image copy, DMA-bound at ``max(C_A, C_LOAD)`` cycles and
    ``A_ROW_SEGMENT / DMA_WORD`` DMA words each (paper Fig. 8's ~18k-
    cycle full-array reload).  Returns totals plus a per-tag breakdown
    (energy in pJ, CIMU cycles, reload cycles).

    **Double-buffered streaming** (``stream_overlap``, DESIGN.md §13):
    the DMA and CIMU are independent engines, so a reload the allocator
    scheduled for overlap prefetches the next segment list into the
    spare bank set while the other set computes.  Per image copy the
    charged wall cycles become ``max(compute, load)`` instead of their
    sum; the ``load_prologue`` copies (the pipeline fill — nothing is
    computing yet) stay fully exposed.  ``load_cycles`` remains the
    FULL per-device load-cycle figure (the DMA work done), split into
    ``load_cycles_hidden`` (behind compute) and ``load_cycles_exposed``
    (on the wall clock); only the exposed share enters
    ``total_cycles``.  Load *energy* is always billed in full — pJ is
    work done, cycles are wall time.

    Mesh-sharded records (``devices > 1`` model shards and/or
    ``data_shards > 1`` batch replicas, DESIGN.md §9/§13) aggregate
    without double-counting under two explicit conventions:

    * ``pj`` totals are SYSTEM energy: the local tile's energy summed
      over all shards (devices run their tiles concurrently; every
      joule is real).  Data replicas each hold — and reload — their own
      image copy, so load energy multiplies by ``data_shards``.
    * ``cycles`` totals are PER-DEVICE wall cycles: the local tile's
      cycles (shards run in parallel, so per-device cycles are the
      latency proxy).  Batch rows split over "data", so per-device MVM
      calls divide by ``data_shards``; per-device reload cycles do not
      (every replica writes its own banks).

    Fused datapath epilogues (``post_ops > 0``) charge the near-memory
    post-reduce pipeline: one ``datapath_out`` pJ per op per LOGICAL
    output element (the datapath runs the pipeline once per output,
    wherever its shard lands) — surfaced as ``post_pj`` in the totals
    and per tag.
    """
    from repro.core import energy as E
    from .program import segment_cycles, segment_dma_words

    if vdd is None:
        vdd = getattr(records, "vdd", None)
        vdd = 0.85 if vdd is None else vdd
    E.validate_vdd(vdd)

    # one definition of the per-segment load cost, shared with the
    # allocator's reload schedule (CimaProgram.reload_cycles_per_pass)
    seg_cycles = segment_cycles()
    seg_words = segment_dma_words()
    e_dma = E.ENERGY_PJ[vdd]["dma_32b"]

    e_post = E.ENERGY_PJ[vdd]["datapath_out"]

    by_tag: dict[str, dict] = {}
    total_pj = 0.0
    total_cycles = 0
    load_pj = 0.0
    load_cycles = 0
    load_hidden = 0
    load_exposed = 0
    post_pj = 0.0
    sp_weight = 0
    sp_sum = 0.0
    skip_weight = 0
    skip_sum = 0.0
    for r in records:
        row = by_tag.setdefault(
            r.tag or r.backend,
            {"backend": r.backend, "mvms": 0, "pj": 0.0, "cycles": 0,
             "load_cycles": 0, "load_cycles_hidden": 0,
             "load_cycles_exposed": 0, "post_pj": 0.0})
        row["mvms"] += r.calls
        if r.backend == "digital":
            continue
        d_sh = max(getattr(r, "devices", 1), 1)
        d_dp = max(getattr(r, "data_shards", 1), 1)
        n_loc = r.n // d_sh if r.partition == "row" else r.n
        m_loc = r.m // d_sh if r.partition == "col" else r.m
        shape = E.MvmShape(n=n_loc, m=m_loc, ba=r.ba, bx=r.bx)
        r_sp = getattr(r, "sparsity", None)
        if r_sp is not None:
            sp_sum += r_sp * r.calls
            sp_weight += r.calls
        skip = 0.0
        if getattr(r, "planes_skipped", None) is not None \
                and getattr(r, "planes_total", None):
            skip = r.planes_skipped / r.planes_total
            skip_sum += skip * r.calls
            skip_weight += r.calls
        pj = E.mvm_energy_pj(shape, vdd,
                             sparsity if r_sp is None else r_sp,
                             readout, plane_skip=skip)["total"] \
            * r.calls * d_sh
        # per-device wall cycles: batch rows split over the "data" axis
        calls_dev = -(-r.calls // d_dp)
        cyc = E.mvm_cycles(shape, readout, plane_skip=skip) * calls_dev
        if r.loads:
            segs = r.loads * r.load_segments       # per-device segments
            lc = segs * seg_cycles                 # per-device DMA cycles
            lp = segs * seg_words * e_dma * d_sh * d_dp   # system energy
            hidden = 0
            if getattr(r, "stream_overlap", False):
                # double-buffer schedule: each non-prologue copy's load
                # runs during a compute window of one copy's MVMs, so it
                # hides min(load, compute) of its cycles
                lc_copy = r.load_segments * seg_cycles
                cc_copy = cyc // r.loads
                p = min(max(getattr(r, "load_prologue", 0), 0), r.loads)
                hidden = (r.loads - p) * min(lc_copy, cc_copy)
            exposed = lc - hidden
            row["load_cycles"] += lc
            row["load_cycles_hidden"] += hidden
            row["load_cycles_exposed"] += exposed
            load_cycles += lc
            load_hidden += hidden
            load_exposed += exposed
            load_pj += lp
            pj += lp
            cyc += exposed
        if getattr(r, "post_ops", 0):
            pp = r.post_ops * r.m * r.calls * e_post
            row["post_pj"] += pp
            post_pj += pp
            pj += pp
        row["pj"] += pj
        row["cycles"] += cyc
        total_pj += pj
        total_cycles += cyc
    return {"vdd": vdd,
            "total_pj": total_pj, "total_cycles": total_cycles,
            "load_pj": load_pj, "load_cycles": load_cycles,
            "load_cycles_hidden": load_hidden,
            "load_cycles_exposed": load_exposed,
            "post_pj": post_pj,
            "input_sparsity": (sp_sum / sp_weight if sp_weight else None),
            "plane_skip": (skip_sum / skip_weight if skip_weight else None),
            "by_tag": by_tag}
