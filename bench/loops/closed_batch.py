"""Closed loop of whole batches: the next batch is dispatched once the one
before the last has completed (``in_flight`` batches on the device at
most), for ``--seconds``.  Reports the units (images) completed per second
over all the work and all the time of the window, the window's drain
included.

Correctness: once the window has closed and the program's state is freed,
``check_batches`` of the window's batches, drawn from the seed, are
recomputed by the configuration's plain reference and compared answer by
answer (:func:`compare`).
"""
from __future__ import annotations

import collections

import numpy as np

from bench import harness

WINDOW_SPAN = "bench.window"


def compare(prog, ref) -> dict:
    """Readings of one set of answers ``[K, classes]`` against the
    reference: the share of answers whose class is not the reference's
    top class, and the widest gap by which the chosen class's reference
    logit lies below the reference's best, over the reference's spread."""
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    chosen = prog.argmax(-1)
    best = ref.max(-1)
    gap = best - np.take_along_axis(ref, chosen[:, None], -1)[:, 0]
    spread = np.maximum(best - ref.min(-1), 1e-30)
    return {"class_mismatch_share": float((chosen != ref.argmax(-1)).mean()),
            "widest_gap": float((gap / spread).max()),
            "max_abs_diff": float(np.abs(prog - ref).max()),
            "answers": int(len(prog))}


def sample_steps(completed: int, k: int, seed: int) -> list:
    rng = np.random.default_rng([seed, 7])
    return sorted(rng.choice(completed, size=min(k, completed),
                             replace=False).tolist())


def run(cell, seed: int, seconds: float, tracer, devices, interpret=None,
        session_hook=None, control: bool = False) -> dict:
    """One run of the cell.  ``control``: also read the control, the
    reference at the configuration's ``control`` precision put in the
    program's place (calibration only; the benchmark's own runs never
    do)."""
    import jax

    model = harness.load_module("models", cell.config["kind"])
    reference = harness.load_module("reference", cell.config["reference"])
    traffic = cell.traffic
    clock = harness.CompileClock()

    sess = model.Session(cell.config, traffic, seed, interpret=interpret)
    if session_hook is not None:
        session_hook(sess)
    with harness.span("bench.warmup"):
        jax.block_until_ready(sess.step(0))          # compiles here
    snap = clock.snapshot()
    harness.log(f"set-up: {clock.compiles} programs compiled in "
                f"{clock.compile_s:.1f} s, {clock.cache_hits} loaded from "
                f"the compile cache")

    outs, pending = {}, collections.deque()
    win = harness.Window(seconds)
    i = 0
    with tracer(WINDOW_SPAN):
        win.start()
        setup_end = win.t0
        while win.open():
            with harness.span("bench.dispatch"):
                pending.append((i, sess.step(i)))
            i += 1
            if len(pending) >= traffic["in_flight"]:
                j, y = pending.popleft()
                with harness.span("bench.wait"):
                    outs[j] = jax.block_until_ready(y)
        with harness.span("bench.drain"):
            while pending:
                j, y = pending.popleft()
                outs[j] = jax.block_until_ready(y)
        win.close()
    compiled_in_window = clock.since(snap)
    mem = harness.memory_peak_bytes(devices)

    units = len(outs) * sess.batch
    work = sess.work_per_step() * len(outs)
    picks = sample_steps(len(outs), traffic["check_batches"], seed)
    answers = {j: np.asarray(outs[j]) for j in picks}
    outs.clear()
    sess.free()
    del sess

    # the plain reference, after the window and with the program freed
    params = model.make_params(cell.config, seed)
    pool = model.make_pool(cell.config, traffic, seed)
    prog = np.concatenate([answers[j] for j in picks])
    ref = np.concatenate([np.asarray(reference.run(
        params, pool[j % traffic["pool_batches"]], cell.config))
        for j in picks])
    readings = compare(prog, ref)
    if control:
        low_config = dict(cell.config, **cell.config["control"])
        low = np.concatenate([np.asarray(reference.run(
            params, pool[j % traffic["pool_batches"]], low_config))
            for j in picks])
        readings = {"program": readings, "control": compare(low, ref)}
    return {
        "setup_end": setup_end,
        "window_s": win.length,
        "units": units,
        "attempted": i * traffic["batch"],
        "failed": 0,
        "work": work,
        "kernel_work": work,
        "compiled_in_window": compiled_in_window,
        "memory_peak_bytes": mem,
        "readings": readings,
        "counters": {"batches": units // traffic["batch"], "images": units},
        "e2e": {"images_per_s": units / win.length},
    }
