#!/usr/bin/env python3
"""Serve olmo-1b at its published widths on a TPU through the main path.

    PagedScheduler -> Engine -> compiled CimaImage program -> cima_bpbs_mvm

Every managed projection runs through the Pallas kernel compiled for the
chip (``interpret=False``), weights are random from ``--seed``, and the
run checks its own results:

* the compiled decode-block program holds ``cima_bpbs_mvm`` as a
  ``tpu_custom_call``;
* the first prompt's prefill logits under ``pallas`` agree with the
  ``bpbs`` XLA path at the same spec (tolerance below);
* the scheduler answers every request, and its streams equal
  ``Engine.generate`` run one request at a time (README "Serving
  semantics").

``--chips 4`` runs only sharded serving instead: the same prompts through
a ``PagedScheduler`` on a 1x4 ``data x model`` mesh (partitioned images
under ``shard_map``), compared with a single-chip run in the same process
(greedy tokens equal, first-step logits allclose).

Run from the checkout root:

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # sharded serving on four chips

Where JAX finds no TPU the script exits non-zero before any model code
runs.  The last line of its output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
the lines before it are diagnostics, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "olmo-1b"
MAX_SEQ = 512                  # 512 tokens x 4 slots ~ 0.27 GB of bf16 KV
N_SLOTS = 4
NEW_TOKENS = 16
# bucket-sized lengths: the scheduler's bucketed prefill and the
# one-request Engine.generate run the same unpadded shapes
PROMPT_LENS = (16, 64, 32, 64)
# pallas vs bpbs prefill logits.  Both paths produce the same integer
# plane sums and ADC codes; they differ in where the input rescale
# associates and in the silu implementation of the fused gate/up
# epilogue (Mosaic vs XLA).  In a 4-bit quantized bfloat16 model such ulp
# differences can move an input across a quantization level, which then
# compounds through the residual stream.  So, as the repo pins for two
# bf16 orderings of one computation (test_datapath_fusion::
# test_model_fused_no_worse_than_unfused_under_bf16), the two must agree
# within BF16_BAND x the bf16-vs-float32 gap of the reference path.
BF16_BAND = 2.0
# --chips 4 vs one chip, both at float32 activations: the repo's sharded
# pallas parity (test_shard_exec) holds logits to MESH_LOGIT_ATOL and
# greedy tokens to equality.  The row-parallel projections (attn.o,
# mlp.down) split their rows over the four chips, and every chip converts
# its own banks; their single-chip banks must end on those device
# boundaries for the two runs to compute the same conversions (DESIGN.md
# §9).  512 divides both per-chip row counts (2048/4 and 8192/4).
MESH_LOGIT_ATOL = 1e-4
MESH_BANK_N = 512


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require_tpu(chips: int):
    """The TPU devices to use; exits non-zero anywhere else."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX backend is {backend!r}")
    devices = jax.devices()
    if len(devices) < chips:
        sys.exit(f"chip_smoke: needs {chips} TPU chips, found "
                 f"{len(devices)}")
    return devices[:chips]


class CompileClock:
    """Backend compile seconds and persistent-cache hits, per phase."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, h0, t0 = self.compile_s, self.cache_hits, time.perf_counter()
        yield
        log(f"phase {name}: wall {time.perf_counter() - t0:.2f} s, "
            f"compile {self.compile_s - c0:.2f} s, "
            f"cache hits {self.cache_hits - h0}")


def smoke_config(**spec_kw):
    from repro.configs import get_config

    return get_config(ARCH).with_accel("pallas", ba=4, bx=4,
                                       interpret=False, **spec_kw)


def make_prompts(seed: int, vocab: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, n).astype(np.int32) for n in PROMPT_LENS]


def serve(params, cfg, prompts, mesh=None):
    """A PagedScheduler over ``params`` answers every prompt; returns
    ``(scheduler, {rid: tokens})``."""
    from repro.serve import PagedScheduler, ServeConfig

    scfg = ServeConfig(max_seq=MAX_SEQ, max_new_tokens=NEW_TOKENS,
                       mesh=mesh)
    sched = PagedScheduler(params, cfg, scfg, n_slots=N_SLOTS)
    rids = [sched.submit(p) for p in prompts]
    streams = sched.run()
    missing = [r for r in rids if len(streams.get(r, ())) != NEW_TOKENS]
    assert not missing, f"requests without {NEW_TOKENS} tokens: {missing}"
    return sched, streams


def compiled_block_text(sched) -> str:
    """HLO of the scheduler's compiled decode-block program."""
    import jax.numpy as jnp

    zeros = jnp.zeros(len(sched.tables), jnp.int32)
    return sched._block.lower(
        sched.params, sched.paged, jnp.asarray(sched.tables),
        zeros, zeros, zeros).compile().as_text()


def first_logits(engine, prompt):
    """Prefill logits [V] of one prompt, at the scheduler's bucket."""
    import numpy as np

    logits, _ = engine.prefill_single(prompt)
    return np.asarray(logits, np.float32)[0]


def bpbs_first_logits(engine, cfg, prompt):
    """The same prefill with every managed projection on the ``bpbs`` XLA
    path, reading the same compiled images, at ``cfg.dtype``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import accel
    from repro.models import prefill

    def run(p, t):
        with accel.override(backend="bpbs"):
            return prefill(p, t, cfg, MAX_SEQ)[0]

    logits = jax.jit(engine._meshed(run))(engine.params,
                                           jnp.asarray(prompt[None]))
    return np.asarray(logits, np.float32)[0]


def peak_bytes(devices) -> list:
    return [d.memory_stats().get("peak_bytes_in_use") for d in devices]


def max_diff(a, b) -> float:
    import numpy as np

    return float(np.abs(a - b).max())


def one_chip(args, clock, devices) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import init_params

    cfg = smoke_config()
    prompts = make_prompts(args.seed, cfg.vocab)
    with clock.phase("init params"):
        params = init_params(cfg, jax.random.PRNGKey(args.seed),
                             max_seq=MAX_SEQ)
        jax.block_until_ready(params)
    with clock.phase("program build + PagedScheduler serve"):
        sched, streams = serve(params, cfg, prompts)
    del params
    engine = sched.engine
    log(f"program: {len(engine.program.images)} images, backend "
        f"{cfg.policy.default.backend}, interpret="
        f"{cfg.policy.default.interpret}")
    log(f"scheduler: {len(streams)}/{len(prompts)} requests answered, "
        f"{sched.stats['generated_tokens']} tokens, "
        f"{sched.stats['decode_blocks']} decode blocks, "
        f"{sched.stats['prefills']} prefills")

    with clock.phase("decode-block kernel check"):
        text = compiled_block_text(sched)
    n_calls = text.count('custom_call_target="tpu_custom_call"')
    assert n_calls and "cima_bpbs_mvm" in text, \
        "cima_bpbs_mvm is not a compiled tpu_custom_call in the decode block"
    log(f"decode block: cima_bpbs_mvm compiled as tpu_custom_call "
        f"({n_calls} tpu_custom_call sites in the compiled HLO)")

    with clock.phase("pallas vs bpbs prefill logits"):
        lg_pallas = first_logits(engine, prompts[0])
        lg_bpbs = bpbs_first_logits(engine, cfg, prompts[0])
        lg_f32 = bpbs_first_logits(
            engine, dataclasses.replace(cfg, dtype="float32"), prompts[0])
    assert lg_pallas.shape == (cfg.vocab,) and np.isfinite(lg_pallas).all()
    diff, band = max_diff(lg_pallas, lg_bpbs), max_diff(lg_bpbs, lg_f32)
    log(f"prefill logits, prompt 0: max |pallas - bpbs| {diff:.6g}; "
        f"bf16-vs-float32 gap of bpbs {band:.6g}; bound {BF16_BAND} x gap; "
        f"max |logit| {float(np.abs(lg_bpbs).max()):.6g}; argmax equal "
        f"{int(lg_pallas.argmax()) == int(lg_bpbs.argmax())}")
    assert diff <= BF16_BAND * band, \
        f"pallas vs bpbs logits differ by {diff} > {BF16_BAND} x {band}"

    with clock.phase("Engine.generate one request at a time"):
        solo = [engine.generate(jnp.asarray(p[None]))[0].tolist()
                for p in prompts]
    for rid, ref in enumerate(solo):
        assert streams[rid] == ref, \
            f"request {rid}: scheduler {streams[rid]} != generate {ref}"
    log(f"streams equal Engine.generate for all {len(solo)} requests")
    log(f"peak_bytes_in_use: {peak_bytes(devices)[0]}")


def four_chips(args, clock, devices) -> None:
    import jax
    import numpy as np

    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_serve_mesh
    from repro.models import init_params

    cfg = dataclasses.replace(smoke_config(bank_n=MESH_BANK_N),
                              dtype="float32")
    prompts = make_prompts(args.seed, cfg.vocab)
    with clock.phase("init params"):
        params = init_params(cfg, jax.random.PRNGKey(args.seed),
                             max_seq=MAX_SEQ)
        jax.block_until_ready(params)

    with clock.phase("single-chip PagedScheduler serve"):
        sched, solo = serve(params, cfg, prompts)
        solo_logits = first_logits(sched.engine, prompts[0])
    log(f"single chip: {len(solo)} requests, "
        f"{sched.stats['generated_tokens']} tokens, peak_bytes_in_use "
        f"{peak_bytes(devices)}")
    host_params = jax.device_get(params)
    del sched, params
    gc.collect()

    mesh = make_serve_mesh(data=1, model=len(devices))
    with clock.phase("meshed PagedScheduler serve"):
        params = jax.device_put(host_params, shd.param_specs(
            jax.eval_shape(lambda: host_params), mesh))
        del host_params
        sched, meshed = serve(params, cfg, prompts, mesh=mesh)
        meshed_logits = first_logits(sched.engine, prompts[0])
    parts = sum(1 for i in sched.engine.program.images.values()
                if i.partition)
    log(f"mesh {dict(mesh.shape)}: {parts}/"
        f"{len(sched.engine.program.images)} images partitioned, "
        f"{len(meshed)} requests, {sched.stats['generated_tokens']} tokens")
    log(f"per-device peak_bytes_in_use: {peak_bytes(devices)}")

    assert np.isfinite(meshed_logits).all()
    diff = max_diff(meshed_logits, solo_logits)
    log(f"first-step logits, prompt 0: max |mesh - single chip| {diff:.6g}, "
        f"atol {MESH_LOGIT_ATOL}, max |logit| "
        f"{float(np.abs(solo_logits).max()):.6g}")
    same = {rid: meshed[rid] == ref for rid, ref in solo.items()}
    log(f"greedy tokens equal per request: {same}")
    assert diff <= MESH_LOGIT_ATOL, \
        f"mesh vs single-chip logits differ by {diff} > {MESH_LOGIT_ATOL}"
    for rid, ref in solo.items():
        assert meshed[rid] == ref, \
            f"request {rid}: mesh {meshed[rid]} != single chip {ref}"
    log(f"greedy tokens equal the single-chip run for all {len(solo)} "
        f"requests")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the serving path on one chip; 4: sharded "
                         "serving on a 1x4 mesh vs one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = require_tpu(args.chips)
    import jax

    from repro.launch.cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    dev, count = devices[0], len(jax.devices())
    log(f"device_kind {dev.device_kind}, platform {dev.platform}, "
        f"device count {count}, using {len(devices)}")
    cfg = smoke_config()
    log(f"model {ARCH}: n_layers {cfg.n_layers}, d_model {cfg.d_model}, "
        f"n_heads {cfg.n_heads}, d_ff {cfg.d_ff}, vocab {cfg.vocab}, "
        f"dtype {cfg.dtype}")
    log(f"serving: {len(PROMPT_LENS)} requests, prompt lengths "
        f"{PROMPT_LENS}, {NEW_TOKENS} new tokens, greedy, {N_SLOTS} slots, "
        f"max_seq {MAX_SEQ}")

    clock = CompileClock()
    if args.chips == 1:
        one_chip(args, clock, devices)
    else:
        four_chips(args, clock, devices)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)


if __name__ == "__main__":
    main()
