"""Pallas TPU kernels for the perf-critical compute layers.

* :mod:`repro.kernels.cima_mvm` — the paper's accelerator: BP/BS bit-plane
  GEMM with per-bank ADC quantization and fused near-memory epilogue; one
  entry, :func:`~repro.kernels.cima_mvm.cima_mvm`, on the integer input
  grid and the weight bit planes.
* :mod:`repro.kernels.flash_attention` — online-softmax attention for the
  32k prefill shapes (causal, GQA, sliding window).

Each entry compiles its kernel on a TPU and interprets it elsewhere
(``interpret=None``); ``ref.py`` holds the pure-jnp oracles every kernel
is validated against.
"""
from . import cima_mvm, flash_attention, ref

__all__ = ["cima_mvm", "flash_attention", "ref"]
