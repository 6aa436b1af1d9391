"""Work of the simulated computation, counted from shapes.

The work is that of the chip being simulated, never of the present
implementation, so no later change to the program can raise a share of a
peak above 100%:

* a CIMA projection of ``rows x N x M`` at B_A/B_X bits is
  ``2 * rows * N * M * B_A * B_X`` plane products (each 0/+-1 plane pair
  is one exact int8 multiply-add, so the int8 peak bounds them);
* its bytes are the weights at B_A bits, the inputs at B_X bits and the
  float32 outputs once;
* padded rows, padded banks and per-call copies are no work;
* float work outside the CIMA (attention, norms, pooling) is bounded by the
  bf16 peak.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Work:
    plane_ops: float = 0.0      # int8-bound operations (CIMA plane products)
    float_ops: float = 0.0      # bf16-bound operations outside the CIMA
    bytes: float = 0.0          # HBM bytes the kernel calls must move
    macs: float = 0.0           # real multiply-accumulates of the projections

    def __add__(self, o: "Work") -> "Work":
        return Work(self.plane_ops + o.plane_ops, self.float_ops + o.float_ops,
                    self.bytes + o.bytes, self.macs + o.macs)

    def __mul__(self, k: float) -> "Work":
        return Work(self.plane_ops * k, self.float_ops * k, self.bytes * k,
                    self.macs * k)

    __rmul__ = __mul__

    def compute_seconds(self, peak: dict) -> float:
        return (self.plane_ops / peak["int8_ops"]
                + self.float_ops / peak["bf16_flops"])

    def least_seconds(self, peak: dict) -> float:
        """The least time the chip could take: compute at its peaks, or
        bytes at its bandwidth, whichever binds."""
        return max(self.compute_seconds(peak),
                   self.bytes / peak["hbm_bytes_per_s"])

    def binding(self, peak: dict) -> str:
        return ("compute" if self.compute_seconds(peak)
                >= self.bytes / peak["hbm_bytes_per_s"] else "bandwidth")


def cima_call(rows: int, n: int, m: int, ba: int, bx: int) -> Work:
    """One ``cima_bpbs_mvm`` call on ``rows`` real input rows against an
    ``N x M`` weight image."""
    return Work(plane_ops=2.0 * rows * n * m * ba * bx,
                bytes=n * m * ba / 8 + rows * n * bx / 8 + rows * m * 4,
                macs=float(rows * n * m))


def padded_macs(rows: int, n: int, m: int, bank_n: int = 2304,
                block_b: int = 128, block_m: int = 128) -> float:
    """The multiply-accumulates the kernel runs after padding rows to
    ``block_b``, N to whole banks and M to ``block_m`` (not work: for
    comparison with ``cima_call``)."""
    def up(v, k):
        return -(-v // k) * k
    return float(up(rows, block_b) * up(n, bank_n) * up(m, block_m))


# ------------------------------------------------------------------ CNN

def cnn_layers(config: dict) -> list:
    """``(kind, rows_per_image, N, M)`` of each layer of a CNN config."""
    hw = config["image_hw"]
    out = []
    for layer in config["layers"]:
        if layer["kind"] == "conv":
            out.append(("conv", hw * hw, 9 * layer["cin"], layer["cout"]))
            if layer.get("pool"):
                hw //= 2
        else:
            out.append(("fc", 1, layer["cin"], layer["cout"]))
    return out


def cnn_batch(config: dict, batch: int) -> Work:
    """Work of one batch through the whole network: one kernel call per
    layer on all the batch's rows (the weights are read once a call)."""
    w = Work()
    for _, rows, n, m in cnn_layers(config):
        w = w + cima_call(batch * rows, n, m, config["ba"], config["bx"])
    return w


def cnn_padded_macs(config: dict, batch: int) -> float:
    return sum(padded_macs(batch * rows, n, m)
               for _, rows, n, m in cnn_layers(config))


# ------------------------------------------------------------------ LM

def lm_projections(config: dict) -> list:
    """``(name, N, M)`` of every CIMA projection a token passes, per layer
    repeated ``n_layers`` times, plus the tied unembedding."""
    d, f = config["d_model"], config["d_ff"]
    hd = config.get("head_dim") or d // config["n_heads"]
    q, kv = config["n_heads"] * hd, config["n_kv_heads"] * hd
    layer = [("attn.q", d, q), ("attn.k", d, kv), ("attn.v", d, kv),
             ("attn.o", q, d), ("mlp.gate", d, f), ("mlp.up", d, f),
             ("mlp.down", f, d)]
    return layer * config["n_layers"] + [("unembed", d, config["vocab"])]


def lm_token(config: dict) -> Work:
    """CIMA work of one token through the model (decode or prefill)."""
    w = Work()
    for _, n, m in lm_projections(config):
        w = w + cima_call(1, n, m, config["ba"], config["bx"])
    return w
