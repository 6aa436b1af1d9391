"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

* busy: the union of the intervals in which an operation ran on a chip,
  inside the traced window, averaged over the chips used;
* per-operation device seconds;
* the device seconds of the operations named for a kernel (the Pallas
  kernel is named ``cima_bpbs_mvm`` in ``kernels/cima_mvm.py``, and its
  calls appear as ``%cima_bpbs_mvm.<n> = ...``);
* the idle gaps between operations, each attributed to the innermost host
  span (``jax.profiler.TraceAnnotation``) that covers its middle.

The window is the host span named ``window_span`` (the benchmark opens it
around the measured loop); events are clipped to it.  Reads the file with
``jax.profiler.ProfileData`` and nothing else.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                       # averaged over the chips
    chips: int
    op_s: dict                          # op_key -> device seconds (all chips)
    matched_s: dict                     # kernel name -> device seconds
    gaps: list                          # [(host span name, seconds)], longest first
    n_ops: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:top]]}


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_key(name: str) -> str:
    """``%cima_bpbs_mvm.12 = f32[...] custom-call(...)`` -> ``cima_bpbs_mvm``:
    the instruction's name without its number, so the breakdown adds up
    the calls of one operation."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _union(intervals) -> float:
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _gaps(intervals, w0, w1):
    """Idle intervals of ``[w0, w1]`` not covered by ``intervals``."""
    out, cur = [], w0
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < w1:
        out.append((cur, w1))
    return out


def host_spans(pd, prefix: str) -> list:
    """``(name, start_ns, end_ns)`` of host events whose name starts with
    ``prefix``, from every non-device plane."""
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return spans


def reduce(path: str, window_span: str, kernels=(), span_prefix: str = "bench.",
           device_plane=DEVICE_PLANE) -> Summary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans = host_spans(pd, span_prefix)
    windows = [s for s in spans if s[0] == window_span]
    if not windows:
        raise ValueError(f"trace has no host span {window_span!r}")
    _, w0, w1 = max(windows, key=lambda s: s[2] - s[1])
    inner = sorted((s for s in spans if s[0] != window_span),
                   key=lambda s: s[2] - s[1])           # innermost first

    busy, op_s, gaps = [], {}, []
    matched = {k: 0.0 for k in kernels}
    chips = n_ops = 0
    for plane in pd.planes:
        if not device_plane.match(plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines:
            continue
        chips += 1
        intervals = []
        for ev in lines[OPS_LINE].events:
            s = max(ev.start_ns, w0)
            e = min(ev.start_ns + ev.duration_ns, w1)
            if e <= s:
                continue
            intervals.append((s, e))
            n_ops += 1
            sec = (e - s) * 1e-9
            key = op_key(ev.name)
            op_s[key] = op_s.get(key, 0.0) + sec
            if key in matched:
                matched[key] += sec
        busy.append(_union(intervals) * 1e-9)
        for gs, ge in _gaps(intervals, w0, w1):
            mid = 0.5 * (gs + ge)
            owner = next((n for n, s, e in inner if s <= mid <= e), "none")
            gaps.append((owner, (ge - gs) * 1e-9))
    if not chips:
        raise ValueError(f"trace {path} has no device plane with an "
                         f"{OPS_LINE!r} line")
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(w1 - w0) * 1e-9, busy_s=sum(busy) / chips,
                   chips=chips, op_s=op_s,
                   matched_s=matched, gaps=gaps, n_ops=n_ops)
