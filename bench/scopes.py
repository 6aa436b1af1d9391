"""Device time by network layer and phase: the trace joined to the compiled
program's scope metadata.

The program names its phases with fixed ``jax.named_scope`` segments:
``cnn.layer<i>`` around each layer of the CNN, ``cnn.im2col`` and
``cnn.pool`` inside it, and the steps of ``accel.matmul``
(``cima.quantize_x``, ``cima.quantize_w``, ``cima.planes``, ``cima.pad``,
``cima.liveness``, ``cima.kernel``, ``cima.post``).  JAX writes the scope
stack into each instruction's ``op_name`` metadata, and the compiled
module's text (``jitted.lower(...).compile().as_text()``) gives it per
instruction: ``%fusion.88 = ... metadata={op_name="jit(f)/cnn.layer3/..."}``.
The profiler's ``XLA Ops`` events carry the same instruction names, so
device seconds per instruction join to (layer, phase) by name.

An instruction the compiler added without metadata (a layout copy, a
prefetch into VMEM) is placed with the instruction that consumes it.  A
fusion carries its root's ``op_name`` only; the log names the other
phases fused into it.

The parse and the join match names only and import nothing of the
program.  A metric reader sees the benchmark's window only as its
reduced summary, which keys device time by op name without the
instruction number, so :func:`window` traces a short window of its own
of the cell's step, after the benchmark's, and joins that; the readers
``bench/metrics/operand_prep_us.py`` and ``bench/metrics/im2col_us.py``
read device microseconds per image from it.
"""
from __future__ import annotations

import collections
import dataclasses
import re

from bench import trace

LAYER = re.compile(r"^cnn\.layer\d+$")
PHASE_PREFIXES = ("cnn.", "cima.")
UNSCOPED = "unscoped"       # neither a layer nor a phase scope
LAYER_BODY = "layer"        # inside a layer, outside every phase scope

OPERAND_PREP = ("cima.quantize_x", "cima.quantize_w", "cima.planes",
                "cima.pad", "cima.liveness")
IM2COL = ("cnn.im2col",)
MIN_COVERAGE = 0.9          # share of device seconds the join must place

# ``%name = <shape> <opcode>(...)``, optionally ``ROOT``; names are unique
# in a module
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_OPCODE = re.compile(r"\s*([\w-]+)\(")
_OPERAND = re.compile(r"%([\w.\-]+)")


@dataclasses.dataclass(frozen=True)
class Instr:
    name: str
    opcode: str
    shape: str
    op_name: str            # "" where the compiler left no metadata
    operands: tuple         # the instructions and computations it names
    entry: bool


def _split_shape(rest: str) -> tuple:
    """``f32[8]{0} add(...)`` -> (``f32[8]{0}``, ``add(...)``); a tuple
    shape ``(a, (b, c))`` is taken whole."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                return rest[:i + 1], rest[i + 1:]
    shape, _, tail = rest.partition(" ")
    return shape, " " + tail


def computations(hlo_text: str) -> dict:
    """``{computation name: [Instr, ...]}`` of a module's text, each in
    the text's order (operands before their users)."""
    out, cur, entry = {}, None, False
    for line in hlo_text.splitlines():
        if cur is None:
            if line.rstrip().endswith("{") and not line.startswith(" "):
                entry = line.startswith("ENTRY ")
                cur = out.setdefault(
                    line.split()[1 if entry else 0].lstrip("%"), [])
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        shape, tail = _split_shape(m.group(2))
        op = _OPCODE.match(tail)
        name = _OP_NAME.search(tail)
        cur.append(Instr(m.group(1), op.group(1) if op else "", shape,
                         name.group(1) if name else "",
                         tuple(_OPERAND.findall(tail)), entry))
    return out


def instructions(hlo_text: str) -> dict:
    """``{name: Instr}`` of every computation of a module's text."""
    return {i.name: i for comp in computations(hlo_text).values()
            for i in comp}


def scope_of(op_name: str) -> tuple:
    """``(layer, phase)`` of one ``op_name``: the layer is its
    ``cnn.layer<i>`` segment (None without one), the phase its innermost
    other ``cnn.``/``cima.`` segment, else :data:`LAYER_BODY` inside a
    layer and :data:`UNSCOPED` outside every scope."""
    segs = op_name.split("/")
    layer = next((s for s in segs if LAYER.match(s)), None)
    phases = [s for s in segs
              if s.startswith(PHASE_PREFIXES) and not LAYER.match(s)]
    if phases:
        return layer, phases[-1]
    return layer, LAYER_BODY if layer else UNSCOPED


def scope_map(hlo_text: str) -> dict:
    """``{instruction name: (layer, phase)}`` of a compiled module.

    An instruction the compiler added without metadata (a layout copy, a
    prefetch into VMEM, the concatenation of a split operand) belongs to
    the phase that consumes it: it takes the scope of its first user that
    has one, through chains of such instructions."""
    out = {}
    for comp in computations(hlo_text).values():
        users = collections.defaultdict(list)
        for ins in comp:
            for o in ins.operands:
                users[o].append(ins.name)
        for ins in reversed(comp):
            scope = scope_of(ins.op_name)
            if not ins.op_name:
                scope = next((out[u] for u in users[ins.name]
                              if out[u][1] != UNSCOPED), scope)
            out[ins.name] = scope
    return out


def fused_phases(hlo_text: str) -> dict:
    """``{instruction name: phases}``: the phases of the instructions in
    the computations an instruction calls (a fusion's body), where they
    are more than its own.  A fusion carries one ``op_name``, its root's;
    this says which other phases XLA fused into it."""
    comps = computations(hlo_text)
    memo = {}

    def inside(comp: str) -> frozenset:
        if comp not in memo:
            memo[comp] = frozenset()
            found = set()
            for ins in comps[comp]:
                found.add(scope_of(ins.op_name)[1])
                found.update(*(inside(c) for c in ins.operands if c in comps))
            memo[comp] = frozenset(found - {UNSCOPED, LAYER_BODY})
        return memo[comp]

    out = {}
    for comp in comps.values():
        for ins in comp:
            called = set().union(*(inside(c) for c in ins.operands
                                   if c in comps))
            if called - {scope_of(ins.op_name)[1]}:
                out[ins.name] = frozenset(called)
    return out


@dataclasses.dataclass
class Attribution:
    rows: dict          # (layer, phase) -> {op_key: device seconds}
    missing: dict       # op_key -> seconds of instructions not in the text
    images: int         # images completed in the traced window
    fused: dict = dataclasses.field(default_factory=dict)
    # (layer, phase) -> {op_key: other phases fused into those ops}

    @property
    def found_s(self) -> float:
        return sum(sum(ops.values()) for ops in self.rows.values())

    @property
    def coverage(self) -> float:
        total = self.found_s + sum(self.missing.values())
        return self.found_s / total if total > 0 else 0.0

    @property
    def scoped(self) -> bool:
        """Does any placed instruction carry the program's scopes?"""
        return any(phase != UNSCOPED for _, phase in self.rows)

    def phase_s(self, phases) -> float:
        return sum(sum(ops.values()) for (_, phase), ops in self.rows.items()
                   if phase in phases)

    def us_per_image(self, phases):
        """Device microseconds per image under ``phases``; None when the
        program carries no scopes or the join places under
        :data:`MIN_COVERAGE` of the device seconds."""
        if not self.scoped or self.coverage < MIN_COVERAGE or not self.images:
            return None
        return 1e6 * self.phase_s(phases) / self.images

    def table(self) -> list:
        """``[layer, phase, seconds, [[op_key, seconds], ...]]``, longest
        first."""
        rows = sorted(self.rows.items(), key=lambda kv: -sum(kv[1].values()))
        return [[layer, phase, sum(ops.values()),
                 sorted(([k, v] for k, v in ops.items()),
                        key=lambda kv: -kv[1])]
                for (layer, phase), ops in rows]


def attribute(inst_s: dict, smap: dict, images: int = 0,
              fused: dict = None) -> Attribution:
    """Device seconds per instruction name, placed by ``smap``
    (:func:`scope_map`); ``fused`` (:func:`fused_phases`) notes the other
    phases inside each placed op."""
    rows = collections.defaultdict(lambda: collections.defaultdict(float))
    mixed = collections.defaultdict(lambda: collections.defaultdict(set))
    missing = collections.defaultdict(float)
    for name, sec in inst_s.items():
        key = trace.op_key(name)
        if name not in smap:
            missing[key] += sec
            continue
        rows[smap[name]][key] += sec
        other = (fused or {}).get(name, frozenset()) - {smap[name][1]}
        if other:
            mixed[smap[name]][key] |= other
    return Attribution({k: dict(v) for k, v in rows.items()}, dict(missing),
                       images, {k: dict(v) for k, v in mixed.items()})


# ------------------------------------------------------------------ trace

def instruction_name(event_name: str) -> str:
    """``%fusion.88 = f32[...] fusion(...)`` -> ``fusion.88``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class TracedWindow:
    inst_s: dict        # full instruction name -> device seconds (all chips)
    window_s: float
    long_gaps: list     # [(seconds, [(host line, event name), ...])]


def reduce_instructions(path: str, window_span: str,
                        min_gap_s: float = 0.01) -> TracedWindow:
    """Device seconds per full instruction name inside the host span
    ``window_span``, clipped to it as :func:`bench.trace.reduce` clips
    them; and each idle gap of ``min_gap_s`` or more with every host
    event, on any thread, that covers its middle."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    windows = [s for s in trace.host_spans(pd, window_span)
               if s[0] == window_span]
    if not windows:
        raise ValueError(f"trace has no host span {window_span!r}")
    _, w0, w1 = max(windows, key=lambda s: s[2] - s[1])
    inst_s = collections.defaultdict(float)
    gaps = []
    for plane in pd.planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != trace.OPS_LINE:
                continue
            intervals = []
            for ev in line.events:
                s = max(ev.start_ns, w0)
                e = min(ev.start_ns + ev.duration_ns, w1)
                if e <= s:
                    continue
                intervals.append((s, e))
                inst_s[instruction_name(ev.name)] += (e - s) * 1e-9
            gaps += [g for g in trace._gaps(intervals, w0, w1)
                     if (g[1] - g[0]) * 1e-9 >= min_gap_s]
    return TracedWindow(dict(inst_s), (w1 - w0) * 1e-9,
                        [((ge - gs) * 1e-9,
                          host_events_at(pd, (gs + ge) / 2))
                         for gs, ge in sorted(gaps,
                                              key=lambda g: g[0] - g[1])])


def host_events_at(pd, t_ns: float) -> list:
    """``(line name, event name)`` of every host event covering ``t_ns``,
    on any host thread."""
    return [(line.name, ev.name)
            for plane in pd.planes if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events
            if ev.start_ns <= t_ns <= ev.start_ns + ev.duration_ns]


# ------------------------------------------------------------------ window

WINDOW_SPAN = "bench.scopes"
STEPS = 32      # batches in the attribution window
SEED = 0        # its weights and images; no phase but the kernel's
                # plane-skip gate depends on their values


def window(r, steps: int = STEPS, interpret=None):
    """The :class:`Attribution` of a short traced window of the cell's own
    step (``r`` a ``bench/run.py`` ``Reading``), measured once per reading
    after the benchmark's window and logged as a table; None where the
    cell's session exposes no jitted step."""
    if not hasattr(r, "_device_scopes"):
        r._device_scopes = _measure(r.cell, steps, interpret)
    return r._device_scopes


def _measure(cell, steps: int, interpret):
    import shutil
    import tempfile

    import jax
    import numpy as np

    from bench import harness

    model = harness.load_module("models", cell.config["kind"])
    sess = model.Session(cell.config, cell.traffic, SEED, interpret=interpret)
    fwd = getattr(sess, "_fwd", None)      # the jitted step, as it is timed
    trace_dir = tempfile.mkdtemp(prefix="bench-scopes-")
    try:
        if fwd is None:
            return None
        jax.block_until_ready(sess.step(0))
        text = fwd.lower(sess.params, sess.pool,
                         np.int32(0)).compile().as_text()
        pending = collections.deque()
        jax.profiler.start_trace(trace_dir)
        try:
            with harness.span(WINDOW_SPAN):
                for i in range(steps):
                    pending.append(sess.step(i))
                    if len(pending) >= cell.traffic["in_flight"]:
                        jax.block_until_ready(pending.popleft())
                while pending:
                    jax.block_until_ready(pending.popleft())
        finally:
            jax.profiler.stop_trace()
        win = reduce_instructions(trace.find_xplane(trace_dir), WINDOW_SPAN)
        attr = attribute(win.inst_s, scope_map(text), steps * sess.batch,
                         fused_phases(text))
    finally:
        sess.free()
        shutil.rmtree(trace_dir, ignore_errors=True)
    log_attribution(attr, win)
    return attr


def log_attribution(attr: Attribution, win: TracedWindow) -> None:
    """The attribution on standard error: totals by phase, then device
    seconds by (layer, phase) with the op keys under each, then the long
    idle gaps with the host events at their middle."""
    from bench import harness

    total = attr.found_s + sum(attr.missing.values())
    missing = sorted(attr.missing.items(), key=lambda kv: -kv[1])
    harness.log(f"scopes: {attr.images} images in a {win.window_s:.6f} s "
                f"window; the compiled text places "
                f"{100 * attr.coverage:.3f}% of {total:.6f} device "
                f"op-seconds; not placed: {missing[:5]}")
    phases = collections.defaultdict(float)
    for (_, phase), ops in attr.rows.items():
        phases[phase] += sum(ops.values())
    for phase, sec in sorted(phases.items(), key=lambda kv: -kv[1]):
        harness.log(f"scopes: phase {phase:<16} {sec:.6f} s "
                    f"{1e6 * sec / max(attr.images, 1):.3f} us/image")
    for layer, phase, sec, ops in attr.table():
        mixed = attr.fused.get((layer, phase), {})
        harness.log(f"scopes: {layer or '-':>12} {phase:<16} {sec:.6f} s  "
                    + ", ".join(f"{k} {v:.6f}" + (
                        f" (+{'+'.join(sorted(mixed[k]))})" if k in mixed
                        else "") for k, v in ops[:8]))
    for sec, events in win.long_gaps:
        harness.log(f"scopes: idle gap {sec:.6f} s, host events at its "
                    f"middle: {events}")
