#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's numbers compared and
the control's, on many seeds, in one process (set-up is paid once per
seed, compiles once).

    python bench/calibrate.py --workload <name> --seconds <s> --seeds 11 12 13 ...

For each seed: one short window of the cell's own loop at its own load,
then the reference; the control is the reference with the configuration's
``control`` overrides (for a float32 configuration: every float step in
bfloat16), compared on the same answers as the program.  Each seed prints
one JSON line ``{"seed", "program": {...}, "control": {...}}``; the last
line gives, per number, the largest program reading and the smallest
control reading.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def calibrate(cell, seeds, seconds, devices, interpret=None, out=None):
    from bench import harness

    loop = harness.load_module("loops", cell.traffic["loop"])

    @contextlib.contextmanager
    def no_trace(name):
        with harness.span(name):
            yield

    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        res = loop.run(cell, seed, seconds, no_trace, devices,
                       interpret=interpret, control=True)
        row = {"seed": seed, **res["readings"], "e2e": res["e2e"],
               "setup_s": res["setup_end"] - t0,
               "memory_peak_bytes": res["memory_peak_bytes"],
               "counters": res["counters"]}
        rows.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if out is not None:
            with open(out, "a") as f:
                f.write(line + "\n")
    summary = {}
    for name in rows[0]["program"]:
        summary[name] = {
            "program_max": max(r["program"][name] for r in rows),
            "control_min": min(r["control"][name] for r in rows)}
    return summary


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=None, help="also append each line here")
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.find_cell(args.workload)
    devices = harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    summary = calibrate(cell, args.seeds, args.seconds, devices,
                        out=args.out)
    print(json.dumps({"summary": summary}), flush=True)


if __name__ == "__main__":
    main()
