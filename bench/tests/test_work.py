"""Work counted from shapes, against figures worked out by hand."""
import pytest

from bench import harness, work


# olmo-1b (arXiv:2402.00838) at 4-b/4-b
OLMO_1B = {"n_layers": 16, "d_model": 2048, "n_heads": 16, "n_kv_heads": 16,
           "d_ff": 8192, "vocab": 50304, "ba": 4, "bx": 4}
# Network A (arXiv:1811.04047 Fig. 11) at 4-b/4-b
NETWORK_A = {"image_hw": 32, "ba": 4, "bx": 4, "layers": [
    {"kind": "conv", "cin": 3, "cout": 128},
    {"kind": "conv", "cin": 128, "cout": 128, "pool": True},
    {"kind": "conv", "cin": 128, "cout": 256},
    {"kind": "conv", "cin": 256, "cout": 256, "pool": True},
    {"kind": "conv", "cin": 256, "cout": 256},
    {"kind": "conv", "cin": 256, "cout": 256, "pool": True},
    {"kind": "fc", "cin": 4096, "cout": 1024},
    {"kind": "fc", "cin": 1024, "cout": 1024},
    {"kind": "fc", "cin": 1024, "cout": 10}]}


def _config(name):
    return harness.load_json(harness.BENCH / "configs" / f"{name}.json")


def test_olmo_plane_products_per_token():
    cfg = OLMO_1B
    w = work.lm_token(cfg)
    # 16 x (4 x 2048^2 + 3 x 2048 x 8192) + 2048 x 50304 weights
    assert w.macs == 16 * (4 * 2048 ** 2 + 3 * 2048 * 8192) + 2048 * 50304
    assert w.plane_ops == pytest.approx(3.8e10, rel=1e-2)
    assert w.plane_ops == 2 * 16 * w.macs


def test_network_a_macs_per_image():
    cfg = NETWORK_A
    w = work.cnn_batch(cfg, 1)
    assert w.macs == pytest.approx(462e6, rel=1e-3)
    padded = work.cnn_padded_macs(cfg, batch=256) / 256
    assert padded == pytest.approx(989e6, rel=1e-3)
    assert w.plane_ops == 2 * 16 * w.macs


def test_a_batch_reads_each_weight_image_once():
    cfg = _config("cifar-net-b")
    one, batch = work.cnn_batch(cfg, 1), work.cnn_batch(cfg, 256)
    assert batch.plane_ops == 256 * one.plane_ops
    weights = sum(n * m * cfg["ba"] / 8 for _, _, n, m in work.cnn_layers(cfg))
    assert batch.bytes == pytest.approx(256 * (one.bytes - weights) + weights)


def test_least_time_takes_the_binding_peak():
    peak = harness.peaks("TPU v5 lite")
    compute = work.Work(plane_ops=393e12, bytes=1.0)
    memory = work.Work(plane_ops=1.0, bytes=819e9)
    assert compute.least_seconds(peak) == pytest.approx(1.0)
    assert compute.binding(peak) == "compute"
    assert memory.least_seconds(peak) == pytest.approx(1.0)
    assert memory.binding(peak) == "bandwidth"


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")
