"""Drive a whole run at a size a CPU test holds (the harness's look for a
chip skipped, Pallas in interpret mode): sound, it is correct; with the
timed path broken underneath (an answer altered where it is produced, or
the control put in the program's place), ``correct`` comes out false; and
on every seed the program reads under every limit and the control's
logits lie off the reference's."""
import dataclasses
import importlib.util
import time

import jax

from bench import calibrate, harness

SEED = 2 ** 31 + 977
CELL = "cifar-net-b.batch256"


def _run_module():
    spec = importlib.util.spec_from_file_location(
        "bench_run", harness.BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def small_cnn_cell():
    """The cell with the network cut to CPU size: 8x8 images, two conv
    and two fc layers, 512-row banks (so the second and third layers
    span banks, and the ADC quantizes: a bank's popcount range exceeds
    its codes), batches of 16."""
    cell = harness.find_cell(CELL)
    config = dict(cell.config, image_hw=8, bank_n=512, layers=[
        {"kind": "conv", "cin": 3, "cout": 64},
        {"kind": "conv", "cin": 64, "cout": 64, "pool": True},
        {"kind": "fc", "cin": 4 * 4 * 64, "cout": 64},
        {"kind": "fc", "cin": 64, "cout": 10}])
    traffic = dict(cell.traffic, batch=16, pool_batches=2, check_batches=2)
    return dataclasses.replace(cell, config=config, traffic=traffic)


def _run(cell, hook=None):
    return _run_module().run_cell(cell, SEED, 1.0, False, jax.devices()[:1],
                                  t0=time.perf_counter(), interpret=True,
                                  session_hook=hook)


def test_sound_run_is_correct():
    out = _run(small_cnn_cell())
    assert out["correct"], out["checks"]
    assert out["metrics"]["images_per_s"]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0


def _alter_one_answer(sess):
    step = sess.step

    def altered(i):
        y = step(i)
        return y.at[0].set(-y[0])       # image 0's class becomes its worst
    sess.step = altered


def test_an_altered_answer_is_caught():
    out = _run(small_cnn_cell(), hook=_alter_one_answer)
    assert not out["correct"], out["checks"]


def _control_in_place(cell):
    """Put the control, the reference with the configuration's
    ``control`` overrides, in the program's place on the same weights and
    images."""
    reference = harness.load_module("reference", cell.config["reference"])
    low = dict(cell.config, **cell.config["control"])

    def hook(sess):
        params, pool, n = sess.params, sess.pool, sess.n_pool
        sess.step = lambda i: reference.run(params, pool[i % n], low)
    return hook


def test_the_control_in_the_programs_place_is_caught():
    # At this size the control moves a class on some seeds only (on the
    # others just the last layer's rounding, about 0.02); SEED is one
    # where it does.  At the cell's size it reads above every limit on
    # every seed (PERF.md, section 4).
    cell = small_cnn_cell()
    out = _run(cell, hook=_control_in_place(cell))
    assert not out["correct"], out["checks"]


def test_control_reads_above_the_program():
    cell = small_cnn_cell()
    for seed in (1, 2, 3):
        summary = calibrate.calibrate(cell, [seed], 1.0, jax.devices()[:1],
                                      interpret=True)
        assert all(summary[n]["program_max"] <= limit
                   for n, limit in cell.limits.items())
        assert (summary["max_abs_diff"]["control_min"]
                > summary["max_abs_diff"]["program_max"])
