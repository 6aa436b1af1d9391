"""Fig. 11 CNN correctness: im2col row order, the windows formed on the
int8 input grid, inference BN on running statistics through the fused
datapath, and training-stat maintenance."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import accel
from repro.accel.backends import quantize_input, quantize_windows
from repro.configs.cifar_nets import NETWORK_A, NETWORK_B
from repro.core.w2b import im2col, multiplicity
from repro.models.cnn import cnn_forward, cnn_loss, init_cnn, update_bn_stats

KEY = jax.random.PRNGKey(0)


def test_im2col_is_spatial_major_9xC():
    """Patch row (kh*k + kw)*C + c must hold channel c at window offset
    (kh, kw) — the chip's 9*C_in CIMA row order.  (The raw
    conv_general_dilated_patches output is CHANNEL-major C*k*k; older
    code returned that while claiming 9*C.)"""
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 5, 5, 3)),
                    jnp.float32)
    p = np.asarray(im2col(x, k=3))
    assert p.shape == (2, 5, 5, 27)
    xp = np.pad(np.asarray(x), ((0, 0), (1, 1), (1, 1), (0, 0)))  # SAME
    for (b, i, j) in [(0, 0, 0), (0, 2, 3), (1, 4, 4)]:
        win = xp[b, i:i + 3, j:j + 3, :]            # [kh, kw, C]
        np.testing.assert_array_equal(p[b, i, j], win.reshape(-1))


def test_init_cnn_has_running_stats():
    net = NETWORK_A.reduced()
    params = init_cnn(KEY, net)
    for p, layer in zip(params["layers"], net.layers):
        assert p["bn_mean"].shape == (layer.cout,)
        assert p["bn_var"].shape == (layer.cout,)
        np.testing.assert_array_equal(np.asarray(p["bn_var"]), 1.0)


def test_eval_logits_batch_independent():
    """The inference bugfix: a single image's logits are the same alone
    and inside a batch of different images (running stats folded into the
    datapath — no live batch statistics).  The old live-stats eval
    differed at O(1); the residual tolerance here is XLA's batch-shape
    GEMM tiling, orders of magnitude below the bug."""
    net = NETWORK_A.reduced()
    params = init_cnn(KEY, net)
    imgs = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3))
    # give the running stats a non-trivial value via one training batch
    _, m = cnn_loss(params, {"images": imgs,
                             "labels": jnp.asarray([0, 1, 2, 3])}, net)
    params = update_bn_stats(params, m["bn_stats"])

    alone = cnn_forward(params, imgs[:1], net, backend="digital")
    batch = cnn_forward(params, imgs, net, backend="digital")
    np.testing.assert_allclose(np.asarray(alone[0]), np.asarray(batch[0]),
                               rtol=1e-5, atol=1e-6)

    # and the training path (live batch stats) IS batch dependent — the
    # behaviour eval used to have, kept only where it belongs
    alone_t, _ = cnn_forward(params, imgs[:1], net, backend="digital",
                             train=True)
    batch_t, _ = cnn_forward(params, imgs, net, backend="digital",
                             train=True)
    assert float(jnp.abs(alone_t[0] - batch_t[0]).max()) > 1e-3


def test_eval_runs_fused_datapath_train_does_not():
    net = NETWORK_B.reduced()       # ABN/sign readout path
    params = init_cnn(KEY, net)
    imgs = jax.random.normal(KEY, (2, 32, 32, 3))
    with accel.trace() as recs:
        cnn_forward(params, imgs, net)
    assert recs and all(r.post_ops >= 3 for r in recs)  # s, b, (act,) sat
    with accel.trace() as recs_t:
        cnn_forward(params, imgs, net, train=True)
    assert recs_t and all(r.post_ops == 0 for r in recs_t)


def test_train_step_updates_running_stats_and_grads_flow():
    net = NETWORK_A.reduced()
    params = init_cnn(KEY, net)
    batch = {"images": jax.random.normal(KEY, (4, 32, 32, 3)),
             "labels": jnp.asarray([0, 1, 2, 3])}
    (loss, m), grads = jax.value_and_grad(
        lambda p: cnn_loss(p, batch, net), has_aux=True)(params)
    assert np.isfinite(float(loss))
    g0 = grads["layers"][0]
    assert float(jnp.abs(g0["w"]).max()) > 0
    assert float(jnp.abs(g0["bn_scale"]).max()) > 0
    # running stats don't take gradients (stop_gradient'd aux)
    np.testing.assert_array_equal(np.asarray(g0["bn_mean"]), 0.0)
    p2 = update_bn_stats(params, m["bn_stats"], momentum=0.5)
    assert float(jnp.abs(p2["layers"][0]["bn_mean"]
                         - params["layers"][0]["bn_mean"]).max()) > 0
    # EMA: new = .5*old + .5*batch
    mu = m["bn_stats"][0][0]
    np.testing.assert_allclose(np.asarray(p2["layers"][0]["bn_mean"]),
                               np.asarray(0.5 * mu), rtol=1e-6)


def _patches(x, k=3):
    """The windows through XLA's patch convolution (what the CNN path ran
    before the slice-built :func:`im2col`): channel-major, transposed."""
    b, h, w, c = x.shape
    p = jax.lax.conv_general_dilated_patches(
        x, (k, k), (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    return jnp.swapaxes(p.reshape(b, h, w, c, k * k), -1, -2).reshape(
        b, h, w, k * k * c)


@pytest.mark.parametrize("shape", [(2, 5, 7, 3), (1, 1, 4, 2), (2, 6, 3, 8)])
def test_im2col_equals_patch_convolution_and_its_vjp(shape):
    """Same windows and the same transpose (the STE's dx) as the patch
    convolution, odd and one-pixel sizes included."""
    x = jax.random.normal(jax.random.PRNGKey(3), shape)
    np.testing.assert_array_equal(np.asarray(im2col(x)),
                                  np.asarray(_patches(x)))
    g = jax.random.normal(jax.random.PRNGKey(4), shape[:3] + (9 * shape[3],))
    got = jax.vjp(im2col, x)[1](g)[0]
    want = jax.vjp(_patches, x)[1](g)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("hw", [(5, 7), (1, 1), (2, 3), (32, 32)])
def test_multiplicity_counts_the_windows_of_each_pixel(hw):
    """The transpose of the window extraction applied to ones counts, per
    pixel, the windows that hold it: 9 inside, 6 on an edge, 4 in a
    corner (fewer on a one- or two-pixel side)."""
    x = jnp.zeros((1, *hw, 2))
    ones = jnp.ones(hw + (18,))[None]
    counted = jax.vjp(im2col, x)[1](ones)[0]
    np.testing.assert_array_equal(
        np.asarray(counted),
        np.broadcast_to(multiplicity(*hw)[None, :, :, None], x.shape))


@pytest.mark.parametrize("shape", [(2, 5, 7, 3), (1, 1, 4, 2), (2, 6, 3, 8)])
@pytest.mark.parametrize("bx,coding", [(1, "xnor"), (4, "xnor"), (4, "and")])
def test_windows_on_the_grid_are_the_quantized_windows(shape, bx, coding):
    """Quantize per pixel, then window on the int8 grid: the grid of the
    float windows bit for bit, the padded border included (+1 under 1-b
    XNOR, 0 otherwise); the scale exact from max|x|.  The 1-b mean is
    summed in another order: it is held to the float64 mean of the
    windows, within four float32 epsilons (the float windows' own float32
    mean is no yardstick here: XLA's CPU reduction of it lies 9 ulp off
    at the smallest shape)."""
    spec = accel.ExecSpec(backend="pallas", ba=1, bx=bx, coding=coding)
    x = jax.random.normal(jax.random.PRNGKey(5), shape)
    got = quantize_windows(x, spec, 3)
    want = quantize_input(im2col(x), spec)
    assert got.q.dtype == jnp.int8 and got.q.shape == want.q.shape
    np.testing.assert_array_equal(np.asarray(got.q), np.asarray(want.q))
    pad = 1 if (bx, coding) == (1, "xnor") else 0
    assert int(got.q[0, 0, 0, 0]) == pad          # the corner's (-1, -1)
    if bx == 1:
        exact = np.abs(np.asarray(im2col(x), np.float64)).mean()
        np.testing.assert_allclose(float(got.scale), exact,
                                   rtol=4 * np.finfo(np.float32).eps)
    else:
        assert float(got.scale) == float(want.scale)


def _netb(**spec_kw):
    net = NETWORK_B.reduced()
    spec = accel.ExecSpec(backend="pallas", ba=1, bx=1, **spec_kw)
    return dataclasses.replace(net,
                               policy=accel.PrecisionPolicy.uniform(spec))


def _conv_records(recs, net):
    n_conv = sum(layer.kind == "conv" for layer in net.layers)
    assert len(recs) == len(net.layers)
    return recs[:n_conv], recs[n_conv:]


def test_pallas_windows_on_the_grid_match_digital_int():
    """Network B (reduced) on ``pallas``: all six conv MVMs take their
    windows on the grid and the logits are ``digital_int``'s (the ideal,
    float windows); the fc layers bypass."""
    net = _netb()
    params = init_cnn(KEY, net)
    imgs = jax.random.normal(jax.random.PRNGKey(6), (2, 32, 32, 3))
    with accel.trace() as recs:
        got = jax.jit(lambda p, x: cnn_forward(p, x, net))(params, imgs)
    conv, fc = _conv_records(recs, net)
    assert [r.grid_windows for r in conv] == [1] * 6
    assert [r.grid_windows for r in fc] == [0] * 3
    want = cnn_forward(params, imgs, net, backend="digital_int")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", ["x_per_row", "bpbs", "train"])
def test_float_windows_where_the_grid_cannot_take_them(case):
    """A per-row input scale, another backend, or training: the windows
    are formed on the float activations, so no conv MVM counts."""
    net = _netb(x_per_row=case == "x_per_row")
    params = init_cnn(KEY, net)
    imgs = jax.random.normal(jax.random.PRNGKey(7), (2, 32, 32, 3))
    with accel.trace() as recs:
        if case == "train":
            cnn_forward(params, imgs, net, train=True)
        else:
            cnn_forward(params, imgs, net,
                        backend="bpbs" if case == "bpbs" else None)
    conv, _ = _conv_records(recs, net)
    assert len(conv) == 6 and all(r.grid_windows == 0 for r in conv)


def test_conv_dispatch_records_the_same_mvm():
    """The energy trace sees the conv as the MVM of its windows — the
    same rows, N, M, measured sparsity and plane skips — whether the
    windows were formed on the grid or handed in."""
    spec = accel.ExecSpec(backend="pallas", ba=1, bx=1)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 6, 6, 4))
    w = jax.random.normal(jax.random.PRNGKey(9), (36, 16))
    with accel.trace() as on_grid:
        y = accel.matmul(x, w, spec, window=3)
    with accel.trace() as handed:
        y0 = accel.matmul(im2col(x), w, spec)
    assert on_grid[0].grid_windows == 1 and on_grid[0].sparsity is not None
    assert [dataclasses.replace(r, grid_windows=0) for r in on_grid] \
        == list(handed)
    assert accel.energy_summary(on_grid) == accel.energy_summary(handed)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0), rtol=1e-6)


@pytest.mark.parametrize("fused", [False, True])
def test_conv_dispatch_has_the_ste_gradients(fused):
    """Gradients through ``window=3`` are those of the float windows'
    GEMM (STE), with and without a fused epilogue."""
    spec = accel.ExecSpec(backend="pallas", ba=1, bx=1)
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 5, 5, 4))
    w = jax.random.normal(jax.random.PRNGKey(11), (36, 8))
    post = (accel.Postreduce(scale=jnp.full((8,), 0.5), bias=None,
                             act="relu", saturate=True) if fused else None)

    def loss(x, w, window):
        h = x if window else im2col(x)
        y = accel.matmul(h, w, spec, post=post, window=window)
        return jnp.sum(y * jnp.arange(y.size).reshape(y.shape) / y.size)

    got = jax.grad(loss, argnums=(0, 1))(x, w, 3)
    want = jax.grad(loss, argnums=(0, 1))(x, w, None)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
