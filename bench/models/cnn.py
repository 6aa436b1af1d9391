"""CIFAR networks of arXiv:1811.04047 Fig. 11 through the program's
inference path: the jitted ``repro.models.cnn.cnn_forward(train=False)``
with every layer on the configuration's backend (``pallas`` on the chip:
im2col -> ``accel.matmul`` -> ``cima_bpbs_mvm`` with the fused datapath
epilogue -> pooling).

The benchmark makes the weights and the images itself, on the device, in
one jitted call each, from the seed; the program receives only them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import work as work_mod


def program_config(config: dict, interpret=None):
    """The program's ``CnnConfig`` for a configuration file."""
    from repro.accel import ExecSpec, PrecisionPolicy
    from repro.configs.cifar_nets import CnnConfig, CnnLayer

    spec = ExecSpec(backend=config["backend"], ba=config["ba"],
                    bx=config["bx"], coding=config["coding"],
                    bank_n=config["bank_n"], adc_bits=config["adc_bits"],
                    adc_sigma_lsb=config["adc_sigma_lsb"],
                    per_channel=config["weight_scale"] == "per_column",
                    x_per_row=config["input_scale"] == "per_row",
                    interpret=(config["interpret"] if interpret is None
                               else interpret))
    layers = tuple(CnnLayer(l["kind"], l["cin"], l["cout"],
                            bool(l.get("pool", False)))
                   for l in config["layers"])
    return CnnConfig(name=config["name"], layers=layers, ba=config["ba"],
                     bx=config["bx"], readout=config["readout"],
                     policy=PrecisionPolicy.uniform(spec),
                     image_hw=config["image_hw"],
                     n_classes=config["n_classes"])


def _params(config, key):
    out = []
    for i, layer in enumerate(config["layers"]):
        k = jax.random.split(jax.random.fold_in(key, i), 5)
        n = layer["cin"] * (9 if layer["kind"] == "conv" else 1)
        m = layer["cout"]
        out.append({
            "w": n ** -0.5 * jax.random.truncated_normal(
                k[0], -2.0, 2.0, (n, m), jnp.float32),
            "bn_scale": jax.random.uniform(k[1], (m,), jnp.float32,
                                           0.75, 1.25),
            "bn_bias": 0.05 * jax.random.normal(k[2], (m,), jnp.float32),
            "bn_mean": 0.05 * jax.random.normal(k[3], (m,), jnp.float32),
            "bn_var": jax.random.uniform(k[4], (m,), jnp.float32, 0.5, 1.5),
        })
    return {"layers": out}


def make_params(config: dict, seed: int):
    """Weights and batch-norm registers from the seed (the configuration
    file's ``assumed``), made on the device in one jitted call."""
    key = jax.random.PRNGKey(seed)
    return jax.jit(functools.partial(_params, config))(key)


def make_pool(config: dict, traffic: dict, seed: int):
    """``[pool_batches, batch, H, W, 3]`` images from the seed, on the
    device."""
    hw = config["image_hw"]
    shape = (traffic["pool_batches"], traffic["batch"], hw, hw, 3)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 1 << 20)
    return jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32))(key)


class Session:
    """The program at one configuration, its weights and its image pool."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 interpret=None):
        from repro.models.cnn import cnn_forward

        self.config, self.traffic = config, traffic
        net = program_config(config, interpret)
        self.params = make_params(config, seed)
        self.pool = make_pool(config, traffic, seed)
        self.n_pool = traffic["pool_batches"]
        self.batch = traffic["batch"]
        self._fwd = jax.jit(
            lambda p, pool, i: cnn_forward(p, pool[i], net, train=False))

    def step(self, i: int):
        """Dispatch one batch (pool entry ``i mod pool_batches``); returns
        its logits, still on the device."""
        return self._fwd(self.params, self.pool, np.int32(i % self.n_pool))

    def work_per_step(self) -> work_mod.Work:
        return work_mod.cnn_batch(self.config, self.batch)

    def free(self):
        self.params = self.pool = None
