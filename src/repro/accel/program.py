"""Weight-stationary CIMA programs: compile-once bit-plane images plus a
capacity-aware bank allocator (paper Fig. 8; DESIGN.md §8).

The chip is weight-stationary: matrix elements are written into the 590kb
CIMA once (a full-array reload costs ~18k cycles) and every MVM reuses
them.  The execution backends mirror that here: :func:`build_program`
walks a model's params under its :class:`~repro.accel.policy.
PrecisionPolicy` once, quantizes every managed projection onto its spec's
coding grid, and decomposes it into the kernel's ``[N, B_A, M]`` int8
bit-plane layout — a :class:`CimaImage` per projection.  :func:`
install_program` threads each image into the param pytree right next to
the weight it was compiled from, so ``lax.scan`` over stacked layers and
``vmap`` over MoE experts slice images exactly like they slice weights,
and dispatch (:mod:`repro.accel.dispatch`) consumes the image through
``ExecContext`` instead of re-quantizing — zero weight ``quantize``/
``weight_planes`` ops on the serving hot path, bit-for-bit identical to
the on-the-fly path by construction.

The **bank allocator** places images onto a virtual array of
``capacity_chips`` physical CIMAs (2304 rows x 256 columns = 590kb each,
the paper's macro).  An image of shape [N, M] at B_A bits occupies
``ceil(N/2304) * ceil(M*B_A/256)`` array tiles per copy (scanned layers
and experts are separate copies).  Images are placed first-fit in model
order; whatever exceeds capacity is *streamed*: scheduled for a reload on
every forward pass, charged through the measured ``C_LOAD``/``C_A``/
``A_ROW_SEGMENT`` constants of :mod:`repro.core.energy` and surfaced per
dispatch in :func:`repro.accel.context.trace` records and
:func:`~repro.accel.context.energy_summary`.

Dispatch keeps the same STE gradients on the program path (the image's
integer planes are non-differentiable closure constants of the
custom_vjp), but training still never installs images: a compiled image
is a stale snapshot the moment the optimizer moves the weights.
:class:`ProgramManager` owns that freshness contract — the trainer
invalidates it after every optimizer update and serving/eval rebuilds
lazily.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Iterator, Optional

import jax
import jax.numpy as jnp

from repro.core.quant import Coding, quantize

from .backends import INT_CORES

# Backends whose weight-side numerics are the shared integer grid of
# repro.core.quant — a compiled image is valid for ANY of them (that is
# what lets override(backend=...) flip substrates without recompiling).
PROGRAM_BACKENDS = tuple(INT_CORES)


# ---------------------------------------------------------------- images

@dataclasses.dataclass
class CimaImage:
    """One projection compiled for the CIMA: int8 bit planes + scales.

    ``ws`` is the kernel layout ``[..., N, B_A, M]`` (leading axes are
    stacked copies: scanned layers, experts); ``wq`` is the same matrix
    on the integer coding grid (int16 — what ``digital_int`` consumes,
    avoiding a per-call plane recombination); ``scale`` is the weight
    quantization scale (``[..., 1, M]`` per-channel or ``[...]``
    per-tensor).  Static metadata rides in the pytree treedef, so an
    image sliced by ``scan``/``vmap`` keeps its identity and the
    dispatcher can validate it against the resolved spec without
    touching traced values.
    """

    ws: jax.Array                 # int8 bit planes, [..., N, BA, M]
    wq: jax.Array                 # int16 integer grid, [..., N, M]
    scale: jax.Array              # f32 weight scale
    path: str = ""                # param-tree location (unique program key)
    tag: str = ""                 # policy path the spec resolved (reporting)
    ba: int = 4
    coding: Coding = Coding.XNOR
    per_channel: bool = True
    n: int = 0                    # per-copy rows
    m: int = 0                    # per-copy output columns
    copies: int = 1               # stacked instances (layers x experts)
    tiles: int = 0                # 2304x256 array tiles per copy PER DEVICE
    segments: int = 0             # 768-b row segments per copy PER DEVICE
    resident: bool = True         # placed in the standing allocation?
    # multi-chip mapping (DESIGN.md §9): how the image splits over the
    # mesh "model" axis.  "col" = bit planes split along M (each device
    # owns m/devices output columns, no collective); "row" = split along
    # N (each device owns n/devices contraction rows, partial sums
    # all-reduced after the ADC epilogue); None = unsharded.
    partition: Optional[str] = None
    devices: int = 1              # model-axis shards the image is cut into
    # double-buffered streaming (DESIGN.md §13): the allocator schedules
    # a streamed image's reload to prefetch its segment list into the
    # spare bank set while the other set computes — dispatch records the
    # schedule (MvmRecord.stream_overlap) so energy_summary charges
    # max(compute, load) wall cycles instead of their sum.  Accounting
    # only: the arithmetic is identical to the synchronous path.
    overlap: bool = False
    # mesh "data"-axis replicas: batch rows split over "data"; the image
    # itself (and its reloads) replicates per data shard
    data_shards: int = 1


jax.tree_util.register_dataclass(
    CimaImage,
    data_fields=["ws", "wq", "scale"],
    meta_fields=["path", "tag", "ba", "coding", "per_channel", "n", "m",
                 "copies", "tiles", "segments", "resident", "partition",
                 "devices", "overlap", "data_shards"],
)


def image_tiles(n: int, m: int, ba: int) -> int:
    """Array tiles (full 2304x256 CIMAs) one [N, M] image copy occupies."""
    from repro.core import energy as E

    return math.ceil(n / E.CIMA_ROWS) * math.ceil(m * ba / E.CIMA_COLS)


def image_segments(n: int, m: int, ba: int) -> int:
    """768-b row segments written to load one [N, M] image copy.

    Per column tile the loader streams N rows of the 256-b physical row
    width: ``ceil(N * 256 / 768)`` segments — for a full array exactly the
    768 segments behind the paper's ~18k-cycle reload
    (:func:`repro.core.energy.matrix_load_cycles`).
    """
    from repro.core import energy as E

    col_tiles = math.ceil(m * ba / E.CIMA_COLS)
    return col_tiles * math.ceil(n * E.CIMA_COLS / E.A_ROW_SEGMENT)


def segment_cycles() -> int:
    """Cycles per 768-b row segment: DMA-bound at max(C_A, C_LOAD)."""
    from repro.core import energy as E

    return max(E.C_A, E.C_LOAD)


def segment_dma_words() -> int:
    """32-b DMA words delivered per 768-b row segment."""
    from repro.core import energy as E

    return E.A_ROW_SEGMENT // E.DMA_WORD


# tag leaves whose projection is the second GEMM of a Megatron pair (the
# input is already TP-sharded): split along N, partial-sum all-reduce
# after the ADC epilogue.  DERIVED from the single source of truth —
# sharding._ROW_PARALLEL_PARENTS (param-tree names) — through the
# name->policy-tag-leaf map, so adding a row-parallel projection to one
# layer without the other fails loudly at import instead of silently
# cutting the image against the grain of its weight's placement.
_PARENT_TO_TAG_LEAF = {"down": "down", "wo": "o", "out": "out",
                       "out_proj": "out_proj", "w_ukv": "ukv"}


def _row_parallel_leaves() -> tuple:
    from repro.distributed.sharding import _ROW_PARALLEL_PARENTS

    return tuple(_PARENT_TO_TAG_LEAF[p] for p in _ROW_PARALLEL_PARENTS)


_ROW_PARALLEL_LEAVES = _row_parallel_leaves()


def sharding_excluded(tag: str) -> bool:
    """Is this projection consumed under ``vmap`` and therefore never
    partitioned over the mesh "model" axis?

    MoE expert stacks and whisper's per-layer cross-attention dispatch
    inside a ``vmap`` — their mapped axis is the natural EP/layer shard,
    not M/N.  Surfaced in :meth:`CimaProgram.summary` (``excluded_from_
    sharding``) so capacity planning on a mesh isn't silently wrong
    about which images actually shrink per device.
    """
    return tag in _MOE_EXPERT.values() or tag.startswith("cross.")


def partition_for(tag: str, n: int, m: int, shards: int) -> Optional[str]:
    """How one projection splits across ``shards`` model-axis devices.

    Column-parallel by default (bit planes split along M: every device
    owns ``m/shards`` output columns of the SAME rows — no collective,
    the chip's own column-parallel layout scaled out); row-parallel for
    the second GEMM of each Megatron pair (split along N, all-reduce
    after the ADC epilogue).  Falls back to the other axis when the
    preferred dim is not divisible, and to ``None`` (replicated) when
    neither divides.  Projections consumed under ``vmap`` (MoE expert
    stacks, whisper's per-layer cross-attention) stay unpartitioned —
    their mapped axis is the natural EP/layer shard, not M/N.
    """
    if shards <= 1:
        return None
    if sharding_excluded(tag):
        return None
    leaf = tag.rsplit(".", 1)[-1]
    if leaf in _ROW_PARALLEL_LEAVES:
        if n % shards == 0:
            return "row"
        return "col" if m % shards == 0 else None
    if m % shards == 0:
        return "col"
    return "row" if n % shards == 0 else None


@functools.partial(jax.jit, static_argnames=("ba", "coding", "per_channel"))
def _compile_matrices(w: jax.Array, ba: int, coding: Coding,
                      per_channel: bool):
    """Quantize + decompose a stack of matrices ``[C, N, M]`` into
    ``(ws [C, N, BA, M] int8, wq [C, N, M] int16, scale)``.

    One jitted program per shape, mapped over the stack with
    ``lax.map``: the f32 plane intermediates (``[N, BA, M]``) exist for
    one matrix at a time, never for the whole stack — a full-width
    model's images build next to its params on one device.
    """
    from repro.core.bpbs import BpbsConfig, weight_planes

    cfg = BpbsConfig(ba=ba, coding=coding)

    def one(wi):
        qw = quantize(wi.astype(jnp.float32), ba, coding,
                      axis=1 if per_channel else None)
        return (weight_planes(qw.q, cfg).astype(jnp.int8),
                qw.q.astype(jnp.int16), qw.scale)

    return jax.lax.map(one, w)


def _compile_image(w: jax.Array, spec, path: str,
                   shards: int = 1,
                   partition: Optional[str] = None) -> CimaImage:
    """Quantize + decompose one projection (possibly stacked) into planes.

    Applies exactly the per-matrix quantization the on-the-fly backends
    apply per call (mapped over stacked copies), so reconstruction at
    dispatch is bit-identical.  ``partition``/``shards`` only change the
    *accounting* (tiles/segments are per-device shard sizes) and the
    metadata dispatch uses to route through ``shard_map`` — the stored
    planes are the full logical arrays; placement is a sharding.
    """
    lead = w.shape[:-2]
    n, m = int(w.shape[-2]), int(w.shape[-1])
    copies = int(math.prod(lead)) if lead else 1
    ws, wq, scale = _compile_matrices(
        w.reshape((copies,) + w.shape[-2:]), spec.ba, Coding(spec.coding),
        spec.per_channel)
    ws = ws.reshape(lead + ws.shape[1:])
    wq = wq.reshape(lead + wq.shape[1:])
    scale = scale.reshape(lead + scale.shape[1:])
    devices = shards if partition in ("col", "row") else 1
    n_loc = n // devices if partition == "row" else n
    m_loc = m // devices if partition == "col" else m
    return CimaImage(
        ws=ws, wq=wq, scale=scale, path=path, tag=spec.tag, ba=spec.ba,
        coding=Coding(spec.coding), per_channel=spec.per_channel,
        n=n, m=m, copies=copies,
        tiles=image_tiles(n_loc, m_loc, spec.ba),
        segments=image_segments(n_loc, m_loc, spec.ba),
        partition=partition if devices > 1 else None,
        devices=devices,
    )


def image_matches(img: Optional[CimaImage], spec, w: jax.Array) -> bool:
    """Is ``img`` a valid compiled form of ``w`` under ``spec``?

    The weight grid is shared by every PROGRAM_BACKENDS substrate, so
    validity only depends on the grid fields (B_A, coding, per_channel)
    and the shape — a scoped ``override(backend=...)`` keeps the image;
    an ``override(ba=...)`` correctly drops to the on-the-fly path.
    """
    return (
        img is not None
        and spec.backend in PROGRAM_BACKENDS
        and img.ba == spec.ba
        and Coding(img.coding) == Coding(spec.coding)
        and img.per_channel == spec.per_channel
        and img.ws.ndim == 3
        and img.ws.shape == (w.shape[0], spec.ba, w.shape[1])
    )


# ------------------------------------------------------ param-tree walk

# attention param names -> policy path suffixes (see repro.models.attention)
_ATTN = {"wq": "q", "wk": "k", "wv": "v", "wo": "o",
         "w_dkv": "dkv", "w_krope": "krope", "w_ukv": "ukv"}
# raw stacked expert arrays in the moe dict -> policy paths
_MOE_EXPERT = {"w_gate": "moe.gate", "w_up": "moe.up", "w_down": "moe.down"}


def _classify(names: tuple) -> Optional[tuple]:
    """(policy_path, kind) of the linear dict at key chain ``names``, or
    None for unmanaged / by-design-digital projections (routers, RG-LRU
    gates — those dispatch with ``spec=None`` and never quantize)."""
    leaf = names[-1]
    if leaf == "lm_head":
        return "unembed", "unembed"
    if "attn" in names:
        if leaf in _ATTN:
            prefix = "cross" if "cross" in names else "attn"
            return f"{prefix}.{_ATTN[leaf]}", "attn"
        return None
    if "rec" in names:
        return (f"rec.{leaf}", "rec") if leaf in ("in_x", "in_gate", "out") \
            else None
    if "ssm" in names:
        return (f"ssm.{leaf}", "ssm") if leaf in ("in_proj", "out_proj") \
            else None
    if "moe" in names:
        if "shared" in names and leaf in ("gate", "up", "down"):
            return f"moe.shared.{leaf}", "moe"
        return None                       # router: digital by design
    if "mlp" in names and leaf in ("gate", "up", "down"):
        return f"mlp.{leaf}", "mlp"
    return None


def _walk(params: Any, cfg) -> Iterator[tuple]:
    """Yield ``(container_path, install_key, tag, kind, w)`` per managed
    projection, in model order.  ``container_path`` addresses the dict the
    image is installed into (under ``install_key``)."""

    def visit(node, path):
        if isinstance(node, dict):
            if "w" in node and hasattr(node["w"], "ndim") \
                    and node["w"].ndim >= 2:
                names = tuple(k for k in path if isinstance(k, str))
                hit = _classify(names) if names else None
                if hit is not None:
                    yield path, "cima", hit[0], hit[1], node["w"]
                return                      # a linear dict is a leaf module
            for k, v in node.items():
                if k in _MOE_EXPERT and "moe" in path \
                        and hasattr(v, "ndim") and v.ndim >= 2:
                    yield (path, ("cima", _MOE_EXPERT[k].split(".")[1]),
                           _MOE_EXPERT[k], "moe", v)
                else:
                    yield from visit(v, path + (k,))
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                yield from visit(v, path + (i,))

    yield from visit(params, ())
    # tied unembed: the managed MVM is x @ table.T — compile the transpose
    if cfg.tie_embeddings and isinstance(params, dict) \
            and "embed" in params and "table" in params["embed"]:
        yield (("embed",), "cima", "unembed", "unembed",
               params["embed"]["table"].T)


def _path_str(path: tuple, key) -> str:
    parts = [str(p) for p in path]
    parts += list(key) if isinstance(key, tuple) else [key]
    return ".".join(parts)


# ----------------------------------------------------- footprints & plans

@dataclasses.dataclass(frozen=True)
class ImageFootprint:
    """The policy-independent shape of one managed projection.

    What the bank allocator needs to place an image — and nothing it
    would have to quantize or decompose bit planes to learn.  A model's
    footprint list is computed once (:func:`model_footprint`) and
    re-placed under arbitrary policies/capacities/meshes by
    :func:`plan_allocation` — the factored allocator the design-space
    tuner (:mod:`repro.tune`) re-runs per candidate without touching a
    single weight value.
    """

    path: str         # param-tree install path (unique program key)
    tag: str          # policy path the projection resolves under
    kind: str         # policy kind ("attn", "mlp", ...)
    n: int            # per-copy contraction rows
    m: int            # per-copy output columns
    copies: int = 1   # stacked instances (scanned layers x experts)


@dataclasses.dataclass(frozen=True)
class Placement:
    """One allocator decision: where a footprint lands under a policy.

    ``spec`` is the resolved :class:`~repro.accel.spec.ExecSpec` (its
    ``ba`` sets the tile geometry); ``tiles``/``segments`` are
    PER-DEVICE shard sizes exactly as :class:`CimaImage` carries them.
    """

    footprint: ImageFootprint
    spec: object                       # ExecSpec
    partition: Optional[str] = None    # "col" | "row" | None
    devices: int = 1
    tiles: int = 0
    segments: int = 0
    resident: bool = True
    overlap: bool = False
    data_shards: int = 1


def model_footprint(params, cfg) -> list:
    """Every policy-managed projection of ``params`` as an
    :class:`ImageFootprint`, in model (= allocation) order.

    Works on concrete arrays or ``jax.eval_shape`` structs — only
    ``.shape`` is read.  Policy-independent on purpose: one footprint
    list serves every candidate policy a tuner sweeps.
    """
    out = []
    for path, key, tag, kind, w in _walk(params, cfg):
        lead = w.shape[:-2]
        out.append(ImageFootprint(
            path=_path_str(path, key), tag=tag, kind=kind,
            n=int(w.shape[-2]), m=int(w.shape[-1]),
            copies=int(math.prod(lead)) if lead else 1))
    return out


def plan_allocation(footprints, policy, capacity_chips: Optional[int] = None,
                    model_shards: int = 1, data_shards: int = 1,
                    double_buffer: bool = True) -> dict:
    """First-fit bank allocation of ``footprints`` under ``policy``:
    ``{path: Placement}`` for every projection the policy routes to a
    program backend (digital projections are never compiled).

    This is the single allocator — :func:`build_program` compiles images
    to exactly this plan, and the tuner re-runs it per design point
    (new ``capacity_chips``/mesh/per-layer precisions) against a fixed
    footprint list, so re-placement never re-decomposes a bit plane.
    Placement is first-fit in model order against the PER-DEVICE
    ``capacity_chips`` budget; whatever exceeds it streams, with
    ``overlap`` stamped per ``double_buffer``.
    """
    plan: dict = {}
    used = 0
    for fp in footprints:
        spec = policy.resolve(fp.tag, kind=fp.kind)
        if spec.backend not in PROGRAM_BACKENDS:
            continue
        part = partition_for(fp.tag, fp.n, fp.m, model_shards)
        devices = model_shards if part in ("col", "row") else 1
        n_loc = fp.n // devices if part == "row" else fp.n
        m_loc = fp.m // devices if part == "col" else fp.m
        tiles = image_tiles(n_loc, m_loc, spec.ba)
        segments = image_segments(n_loc, m_loc, spec.ba)
        need = tiles * fp.copies
        resident = not (capacity_chips is not None
                        and used + need > capacity_chips)
        if resident:
            used += need
        plan[fp.path] = Placement(
            footprint=fp, spec=spec,
            partition=part if devices > 1 else None, devices=devices,
            tiles=tiles, segments=segments, resident=resident,
            overlap=(not resident) and bool(double_buffer),
            data_shards=max(int(data_shards), 1))
    return plan


# -------------------------------------------------------------- programs

@dataclasses.dataclass
class CimaProgram:
    """A compiled weight-stationary program: images + their allocation.

    ``images`` is keyed by the (unique) param-tree install path; the
    ``tag`` on each image is the policy path it resolved.  ``version``
    tracks the weight snapshot the images were built from (see
    :class:`ProgramManager`).
    """

    images: dict
    capacity_tiles: Optional[int] = None    # None = unbounded array (PER DEVICE)
    version: int = 0
    model_shards: int = 1                   # mesh "model"-axis size at build
    data_shards: int = 1                    # mesh "data"-axis size at build
    double_buffer: bool = True              # overlap-schedule streamed reloads?
    # policy tags excluded from model-axis partitioning (vmapped MoE
    # expert / cross-attention images, see sharding_excluded) — their
    # tiles do NOT shrink with model_shards
    excluded: tuple = ()

    def __bool__(self) -> bool:
        return bool(self.images)

    @property
    def tiles_used(self) -> int:
        return sum(i.tiles * i.copies for i in self.images.values()
                   if i.resident)

    @property
    def tiles_total(self) -> int:
        return sum(i.tiles * i.copies for i in self.images.values())

    def reload_segments_per_pass(self) -> int:
        """Row segments rewritten per forward pass (streamed images)."""
        return sum(i.segments * i.copies for i in self.images.values()
                   if not i.resident)

    def reload_cycles_per_pass(self) -> int:
        return self.reload_segments_per_pass() * segment_cycles()

    def initial_load_cycles(self) -> int:
        """One-time cycles to write the standing (resident) allocation."""
        return sum(i.segments * i.copies for i in self.images.values()
                   if i.resident) * segment_cycles()

    def stream_schedule(self) -> list:
        """Per-image reload schedule of the streamed set (DESIGN.md §13).

        One row per non-resident image: how many copies reload per pass,
        the per-copy segment count, the full per-pass DMA cycles, and
        whether the reload is ``overlap``-scheduled (double-buffered —
        hidden behind compute up to ``max(compute, load)`` per copy) or
        synchronous.  The hidden/exposed *split* is trace-dependent
        (compute cycles per copy) and reported by
        :func:`~repro.accel.context.energy_summary`; this is the static
        schedule the allocator committed to.
        """
        rows = []
        for img in self.images.values():
            if img.resident:
                continue
            rows.append({
                "tag": img.tag or img.path,
                "path": img.path,
                "copies": img.copies,
                "segments": img.segments,
                "reload_cycles_per_pass":
                    img.segments * img.copies * segment_cycles(),
                "overlap": img.overlap,
            })
        return sorted(rows, key=lambda r: (r["tag"], r["path"]))

    def summary(self) -> dict:
        from repro.core import energy as E

        return {
            "images": len(self.images),
            "copies": sum(i.copies for i in self.images.values()),
            "model_shards": self.model_shards,
            "data_shards": self.data_shards,
            "double_buffer": self.double_buffer,
            "partitioned": sum(1 for i in self.images.values()
                               if i.partition is not None),
            "excluded_from_sharding": sorted(self.excluded),
            "excluded_count": len(self.excluded),
            "capacity_tiles": self.capacity_tiles,
            "capacity_bits": (None if self.capacity_tiles is None else
                              self.capacity_tiles * E.CIMA_ROWS * E.CIMA_COLS),
            "tiles_total": self.tiles_total,
            "tiles_resident": self.tiles_used,
            "streamed": sorted(i.tag or i.path
                               for i in self.images.values()
                               if not i.resident),
            "streamed_images": self.stream_schedule(),
            "initial_load_cycles": self.initial_load_cycles(),
            "reload_cycles_per_pass": self.reload_cycles_per_pass(),
        }


def build_program(params, cfg, capacity_chips: Optional[int] = None,
                  version: int = 0, mesh=None,
                  model_shards: Optional[int] = None,
                  data_shards: Optional[int] = None,
                  double_buffer: bool = True) -> CimaProgram:
    """Compile every policy-managed projection of ``params`` into a
    :class:`CimaImage` and place the images on the virtual array.

    ``capacity_chips`` bounds the standing allocation to that many
    2304x256 (590kb) CIMA macros **per device**; ``None`` means every
    image is resident (single-load).  Placement is first-fit in model
    order — the paper's own strategy of keeping the hottest,
    earliest-touched matrices stationary and streaming the tail.

    ``mesh`` (a :class:`jax.sharding.Mesh` with ``"model"`` and/or
    ``"data"`` axes) or explicit ``model_shards``/``data_shards`` turns
    on the multi-chip mapping (DESIGN.md §9/§13): each projection is
    partitioned over "model" per :func:`partition_for`, its
    tiles/segments become per-device shard sizes, and residency is
    decided against the per-device ``capacity_chips`` budget — a
    projection that streams on 1 device can be resident on 8.  The
    "data" axis never cuts an image (batch rows split, weights
    replicate); it is stamped on every image so the trace charges
    per-device calls and per-replica load energy correctly.

    ``double_buffer`` (default on) overlap-schedules every streamed
    image: its reload prefetches into the spare bank set while the
    other set computes, so the trace charges ``max(compute, load)``
    wall cycles per copy plus a once-per-pass prologue instead of their
    sum.  Accounting only — numerics are bit-identical either way.
    """
    shards = int(model_shards) if model_shards is not None else (
        int(dict(mesh.shape).get("model", 1)) if mesh is not None else 1)
    data = int(data_shards) if data_shards is not None else (
        int(dict(mesh.shape).get("data", 1)) if mesh is not None else 1)
    # one allocator: placement decisions come from the same plan the
    # tuner re-runs per design point (repro.tune), compilation just
    # materializes the planned images
    plan = plan_allocation(model_footprint(params, cfg), cfg.policy,
                           capacity_chips=capacity_chips,
                           model_shards=shards, data_shards=data,
                           double_buffer=double_buffer)
    images: dict = {}
    excluded: list = []
    for path, key, tag, _kind, w in _walk(params, cfg):
        pstr = _path_str(path, key)
        pl = plan.get(pstr)
        if pl is None:
            continue
        if shards > 1 and sharding_excluded(tag):
            excluded.append(tag)
        img = _compile_image(w, pl.spec, pstr,
                             shards=shards, partition=pl.partition)
        if data > 1:
            img = dataclasses.replace(img, data_shards=data)
        if not pl.resident:
            img = dataclasses.replace(img, resident=False,
                                      overlap=pl.overlap)
        images[img.path] = img
    return CimaProgram(images=images, capacity_tiles=capacity_chips,
                       version=version, model_shards=shards,
                       data_shards=data, double_buffer=bool(double_buffer),
                       excluded=tuple(sorted(set(excluded))))


def _set_in(tree, path: tuple, key, value):
    """Immutable insert of ``value`` at ``tree[path...][key]`` (nested key
    tuples create intermediate dicts)."""
    if not path:
        if isinstance(key, tuple):
            if len(key) == 1:
                key = key[0]
            else:
                sub = dict(tree.get(key[0], {})) if isinstance(tree, dict) \
                    else {}
                sub = _set_in(sub, (), key[1:], value)
                tree = dict(tree)
                tree[key[0]] = sub
                return tree
        out = dict(tree)
        out[key] = value
        return out
    head, rest = path[0], path[1:]
    if isinstance(tree, dict):
        out = dict(tree)
        out[head] = _set_in(tree[head], rest, key, value)
        return out
    out = list(tree)
    out[head] = _set_in(tree[head], rest, key, value)
    return type(tree)(out)           # preserve list vs tuple containers


def install_program(params, program: CimaProgram, cfg):
    """A copy of ``params`` with each image inserted next to its weight
    (key ``"cima"``), where :func:`repro.models.layers.linear`,
    ``unembed`` and the MoE expert vmap pick it up.  Don't train on
    installed params: gradients are the usual STE gradients, but the
    images would go stale on the first optimizer step — strip and
    rebuild via :class:`ProgramManager` instead (DESIGN.md §8)."""
    if not program:
        return params
    out = params
    for path, key, _tag, _kind, _w in _walk(params, cfg):
        pstr = _path_str(path, key)
        if pstr in program.images:
            out = _set_in(out, path, key, program.images[pstr])
    return out


def strip_program(params):
    """Remove every installed image (the inverse of install_program).

    Drops image leaves AND image-only container dicts (the MoE expert
    install writes ``moe["cima"] = {"gate": img, ...}`` — leaving an
    empty dict behind would change the treedef and trip
    ``params.get("cima")`` consumers).
    """
    def is_image_container(v):
        return isinstance(v, dict) and v and \
            all(isinstance(x, CimaImage) for x in v.values())

    def strip(node):
        if isinstance(node, dict):
            return {k: strip(v) for k, v in node.items()
                    if not isinstance(v, CimaImage)
                    and not is_image_container(v)}
        if isinstance(node, (list, tuple)):
            return type(node)(strip(v) for v in node)
        return node

    return strip(params)


# ---------------------------------------------------------- invalidation

class ProgramManager:
    """Freshness contract between training and serving/eval.

    The trainer calls :meth:`invalidate` after every optimizer update
    (weights moved; compiled images are stale); consumers call
    :meth:`ensure` with the current params and get a cached program
    unless it was invalidated — rebuild is lazy, once per weight
    snapshot, not per forward.
    """

    def __init__(self, cfg, capacity_chips: Optional[int] = None,
                 mesh=None, model_shards: Optional[int] = None,
                 data_shards: Optional[int] = None,
                 double_buffer: bool = True):
        self.cfg = cfg
        self.capacity_chips = capacity_chips
        self.mesh = mesh
        self.model_shards = model_shards
        self.data_shards = data_shards
        self.double_buffer = double_buffer
        self._program: Optional[CimaProgram] = None
        self._dirty = True
        self.version = 0

    def invalidate(self) -> None:
        """Weights changed (an optimizer step applied): images are stale."""
        self._dirty = True

    def ensure(self, params) -> CimaProgram:
        """The current program for ``params`` (rebuilt only if stale)."""
        if self._dirty or self._program is None:
            self.version += 1
            self._program = build_program(
                params, self.cfg, capacity_chips=self.capacity_chips,
                version=self.version, mesh=self.mesh,
                model_shards=self.model_shards,
                data_shards=self.data_shards,
                double_buffer=self.double_buffer)
            self._dirty = False
        return self._program
