#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chip, and print its
result as the last line of standard output.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, loop, reference and per-layer
metric readers are found by name (``bench/harness.py``).  Weights and
traffic come from ``--seed``; set-up warms every shape the cell uses; the
window lasts ``--seconds``; then the plain reference checks what the
window produced.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` runs the same window under the profiler and reports its
per-layer metrics, the device's busy seconds and a breakdown.

Without a TPU (or with fewer chips than the cell asks for) the command
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

KERNEL = "cima_bpbs_mvm"


class Reading:
    """What a per-layer metric reader may read: the cell, the loop's
    counters and work, the reduced trace and the chip's peaks."""

    def __init__(self, cell, result, summary, peak):
        self.cell, self.result, self.summary, self.peak = \
            cell, result, summary, peak

    @property
    def window_s(self) -> float:
        return self.result["window_s"]


def make_tracer(trace_dir):
    import jax

    from bench import harness

    @contextlib.contextmanager
    def tracer(span_name):
        if trace_dir is not None:
            jax.profiler.start_trace(trace_dir)
        try:
            with harness.span(span_name):
                yield
        finally:
            if trace_dir is not None:
                jax.profiler.stop_trace()
    return tracer


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t0: float = T0, interpret=None, session_hook=None) -> dict:
    """One run; returns the result object (``correct``, ``attempted``,
    ``failed``, ``metrics``, ``device``, [``breakdown``], ``checks``)."""
    from bench import harness
    from bench import trace as trace_mod

    loop = harness.load_module("loops", cell.traffic["loop"])
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        res = loop.run(cell, seed, seconds, make_tracer(trace_dir), devices,
                       interpret=interpret, session_hook=session_hook)
        summary = None
        if trace:
            summary = trace_mod.reduce(trace_mod.find_xplane(trace_dir),
                                       loop.WINDOW_SPAN, (KERNEL,))
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    setup_s = res["setup_end"] - t0
    harness.log(f"set-up {setup_s:.3f} s, window {res['window_s']:.3f} s, "
                f"programs compiled or loaded inside the window: "
                f"{res['compiled_in_window']}")
    harness.log(f"counters {json.dumps(res['counters'])}")
    device = harness.device_info(devices, res["memory_peak_bytes"])
    metrics, breakdown = {}, None
    if not trace:
        values = dict(res["e2e"], setup_s=setup_s)
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        peak = harness.peaks(device["kind"])
        reading = Reading(cell, res, summary, peak)
        for m in cell.per_layer:
            v = harness.metric_reader(m["name"]).read(m["name"], reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = summary.breakdown()
        harness.log(f"trace: {summary.n_ops} device ops in the window, "
                    f"busy {summary.busy_s:.6f} s of {summary.window_s:.6f} s, "
                    f"{KERNEL} {summary.matched_s[KERNEL]:.6f} s, its "
                    f"roofline bound by {res['kernel_work'].binding(peak)}")

    readings = res["readings"]
    harness.log(f"readings {json.dumps(readings)}")
    checks = {name: {"value": readings[name], "limit": limit}
              for name, limit in cell.limits.items()}
    correct = bool(checks) and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    harness.check_line(checks)
    return {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device,
            "breakdown": breakdown, "checks": checks}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.find_cell(args.workload)
    devices = harness.require_chips(cell.chips)
    harness.log(f"compile cache {harness.enable_compile_cache()}")
    d = devices[0]
    harness.log(f"platform {d.platform}, device_kind {d.device_kind}, "
                f"device count {len(devices)}")
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    from bench.harness import result_line

    print(result_line(out["correct"], out["attempted"], out["failed"],
                      out["metrics"], out["device"], out["checks"],
                      out["breakdown"]), flush=True)


if __name__ == "__main__":
    main()
