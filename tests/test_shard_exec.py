"""Mesh-sharded ("multi-chip") CIMA execution (DESIGN.md §9).

Multi-device cases run in subprocesses under
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the main test
process stays at 1 device).  The invariants:

* sharded == single-device logits bit-for-bit on the quantized integer
  substrates (column-parallel split along M; row-parallel split along N
  with the partial-sum all-reduce after the per-device ADC epilogue —
  exact small integers make the reduction order invisible),
* ``trace()`` under shard_map reports the same total MVM count and image
  loads as the unsharded trace (records are logical, emitted once before
  shard_map — no per-shard double-counting),
* slot splicing (slice_slot/splice_slot) stays correct on sharded cache
  pytrees: the batcher is token-for-token the solo engine,
* the allocator's per-device capacity budget: streamed on 1 device can
  be resident on 8.

Numerics note (asserted as such below): with ``bank_n`` aligned to the
per-device row count, row-parallel bpbs is bit-for-bit because per-bank
ADC boundaries coincide with device boundaries.  The SSM archs' *decode*
carries a ~1e-7 wobble that is pure GSPMD fusion noise from the ambient-
mesh sharding constraints (present with a fully UNSHARDED program under
the same mesh) — the sharded matmuls themselves are exact, so decode
argmax tokens still match exactly.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=REPO)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


# ------------------------------------------------------------ unit layer

def test_shard_policy_object_and_no_global_shims():
    """ShardPolicy is an explicit value object; two policies coexist per
    call; the deprecated mutable-global shims are gone for good and the
    module default is an immutable constant."""
    out = run_py("""
        import jax
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P
        from repro.distributed import ShardPolicy
        from repro.distributed import sharding
        mesh = make_mesh((2, 4), ("data", "model"))
        p2d, pf = ShardPolicy("2d"), ShardPolicy("fsdp")
        assert p2d.dp_axes(mesh) == ("data",)
        assert pf.dp_axes(mesh) == ("data", "model")
        # a model-only serving mesh has no dp axes at all under 2d
        m1 = make_mesh((8,), ("model",))
        assert p2d.dp_axes(m1) == ()
        # the same shapes under the two policies disagree — explicitly,
        # per call, with no global mutated in between
        shapes = {"mlp": {"up": {"w": jax.ShapeDtypeStruct((8, 16),
                                                           "float32")}}}
        s2 = sharding.param_specs(shapes, mesh, p2d)["mlp"]["up"]["w"].spec
        sf = sharding.param_specs(shapes, mesh, pf)["mlp"]["up"]["w"].spec
        assert s2 == P("data", "model"), s2
        assert sf == P(("data", "model")), sf
        # the mutable-global era is over: no setter survives (ACC04), the
        # default is a frozen value, and resolve_policy prefers the arg
        for shim in ("set_policy", "get_policy"):
            assert not hasattr(sharding, shim), shim
        assert sharding.resolve_policy(None) == ShardPolicy("2d")
        assert sharding.resolve_policy(pf) is pf
        try:
            ShardPolicy("bogus")
        except ValueError:
            pass
        else:
            raise AssertionError("bad mode accepted")
        print("OK")
    """)
    assert "OK" in out


def test_cache_specs_batch1_deterministic():
    """batch_size == 1 (admission-prefill slot caches): the first size-1
    dim is the batch dim, it is excluded from model-axis candidacy, and
    the resulting layout matches the live batch cache's non-batch dims —
    the splice-compatibility contract."""
    out = run_py("""
        import jax
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P
        from repro.distributed import cache_specs
        mesh = make_mesh((8,), ("model",))
        # scanned-layer KV leaf [L, B, S, H, D] at B=1: dim 0 (L=8, which
        # IS divisible by the model axis) must NOT be claimed — dim 1 is
        # the batch, and "model" goes to the largest divisible non-batch
        # dim (S=32)
        leaf = jax.ShapeDtypeStruct((8, 1, 32, 4, 16), "float32")
        spec = jax.tree_util.tree_leaves(cache_specs(leaf, mesh, 1))[0].spec
        assert spec == P(None, None, "model"), spec
        # prefix-layer leaf [B, S, H, D] at B=1
        leaf = jax.ShapeDtypeStruct((1, 32, 4, 16), "float32")
        spec = jax.tree_util.tree_leaves(cache_specs(leaf, mesh, 1))[0].spec
        assert spec == P(None, "model"), spec
        # per-slot pos [B] at B=1: replicated scalar-ish vector
        leaf = jax.ShapeDtypeStruct((1,), "int32")
        spec = jax.tree_util.tree_leaves(cache_specs(leaf, mesh, 1))[0].spec
        assert spec == P(), spec
        # and it agrees with the live-batch layout on the non-batch dims
        live = jax.ShapeDtypeStruct((8, 4, 32, 4, 16), "float32")
        lspec = jax.tree_util.tree_leaves(cache_specs(live, mesh, 4))[0].spec
        assert lspec == P(None, None, "model"), lspec
        print("OK")
    """)
    assert "OK" in out


def test_partition_and_per_device_capacity():
    """Pure allocator layer (no devices needed): Megatron pairing of the
    partitions, divisibility fallbacks, and the per-device capacity rule
    that a projection streaming on 1 device is resident on 8."""
    import jax

    from repro.accel.program import build_program, partition_for
    from repro.configs import get_config
    from repro.models import init_params

    assert partition_for("mlp.up", 128, 256, 8) == "col"
    assert partition_for("mlp.down", 256, 128, 8) == "row"
    assert partition_for("attn.o", 128, 128, 1) is None
    # fallback to the other axis when the preferred dim is not divisible
    assert partition_for("mlp.down", 130, 128, 8) == "col"
    assert partition_for("mlp.up", 128, 130, 8) == "row"
    assert partition_for("mlp.up", 130, 130, 8) is None
    # vmap-consumed projections never partition
    assert partition_for("moe.down", 256, 128, 8) is None
    assert partition_for("cross.q", 128, 128, 8) is None

    cfg = get_config("olmo-1b").reduced().with_accel("digital_int",
                                                     ba=4, bx=4)
    params = init_params(cfg, jax.random.PRNGKey(0), max_seq=64)
    p1 = build_program(params, cfg, capacity_chips=6)
    p8 = build_program(params, cfg, capacity_chips=6, model_shards=8)
    assert p8.model_shards == 8
    streamed1 = {t for t, i in ((i.tag, i) for i in p1.images.values())
                 if not i.resident}
    streamed8 = {i.tag for i in p8.images.values() if not i.resident}
    assert streamed1, "capacity must bind on one device for this test"
    assert streamed8 < streamed1, (streamed1, streamed8)
    for img in p8.images.values():
        ref = p1.images[img.path]
        assert img.devices in (1, 8)
        if img.partition is not None:
            # per-device tiles/segments shrink with the shard
            assert img.tiles <= ref.tiles and img.segments < ref.segments
    # capacity accounting stays per-device
    assert p8.tiles_used <= 6


# ------------------------------------------------- execution parity layer

_PARITY = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.configs import get_config
    from repro.models import init_params, prefill, decode_step
    from repro.accel import build_program, install_program
    from repro.distributed import autoshard, sharding as shd

    DEVICES = {devices}
    BACKEND = "{backend}"
    mesh = make_mesh((DEVICES,), ("model",))
    for arch in ("olmo-1b", "mamba2-130m"):
        # bank_n=16 aligns per-bank ADC boundaries with device boundaries
        # for every managed N at 2/4/8 shards -> row-parallel bpbs is
        # bit-for-bit vs the single-chip run (DESIGN.md S9)
        cfg = get_config(arch).reduced().with_accel(BACKEND, ba=4, bx=4,
                                                    bank_n=16)
        params = init_params(cfg, jax.random.PRNGKey(0), max_seq=64)
        toks = jnp.asarray(np.random.default_rng(0).integers(
            1, cfg.vocab, (2, 8)), jnp.int32)

        ref_prog = build_program(params, cfg)
        ref_p = install_program(params, ref_prog, cfg)
        ref_logits, ref_cache = jax.jit(
            lambda p, t: prefill(p, t, cfg, 32))(ref_p, toks)

        prog = build_program(params, cfg, mesh=mesh)
        assert any(i.partition for i in prog.images.values()), arch
        sp = install_program(params, prog, cfg)
        sp = jax.device_put(sp, shd.param_specs(
            jax.eval_shape(lambda: sp), mesh, program=prog))
        with autoshard.use_mesh(mesh):
            logits, cache = jax.jit(
                lambda p, t: prefill(p, t, cfg, 32))(sp, toks)
        pre_diff = float(jnp.abs(logits - ref_logits).max())

        tok = jnp.argmax(ref_logits, -1).astype(jnp.int32)
        ref_dec, _ = jax.jit(
            lambda p, t, c: decode_step(p, t, c, cfg))(ref_p, tok, ref_cache)
        with autoshard.use_mesh(mesh):
            dec, _ = jax.jit(
                lambda p, t, c: decode_step(p, t, c, cfg))(sp, tok, cache)
        dec_diff = float(jnp.abs(dec - ref_dec).max())
        same_tok = bool(jnp.all(jnp.argmax(dec, -1)
                                == jnp.argmax(ref_dec, -1)))
        print(f"PARITY {{arch}} pre={{pre_diff}} dec={{dec_diff}} "
              f"tok={{same_tok}}")
"""


def _check_parity(out: str, backend: str):
    for line in out.splitlines():
        if not line.startswith("PARITY"):
            continue
        _, arch, pre, dec, tok = line.split()
        pre = float(pre.split("=")[1])
        dec = float(dec.split("=")[1])
        assert tok == "tok=True", line
        if backend == "pallas":
            assert pre < 1e-4 and dec < 1e-4, line
        else:
            # bit-for-bit prefill always; decode bit-for-bit on the
            # attention arch, ~1e-7 GSPMD fusion noise on the SSM
            assert pre == 0.0, line
            if arch == "olmo-1b":
                assert dec == 0.0, line
            else:
                assert dec < 1e-5, line


def test_sharded_logits_parity_digital_int_8dev():
    out = run_py(_PARITY.format(devices=8, backend="digital_int"))
    _check_parity(out, "digital_int")


@pytest.mark.slow
@pytest.mark.parametrize("devices", [2, 4, 8])
@pytest.mark.parametrize("backend", ["digital_int", "bpbs", "pallas"])
def test_sharded_logits_parity_matrix(devices, backend):
    if devices == 8 and backend == "digital_int":
        pytest.skip("covered by the fast test")
    out = run_py(_PARITY.format(devices=devices, backend=backend),
                 devices=devices)
    _check_parity(out, backend)


def test_sharded_trace_counts_match_unsharded():
    """Acceptance: trace() under shard_map reports the same total MVM
    count/loads as the unsharded trace for the same workload."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config
        from repro.models import init_params, prefill
        from repro import accel
        from repro.accel import build_program, install_program
        from repro.distributed import autoshard

        cfg = get_config("olmo-1b").reduced().with_accel(
            "digital_int", ba=4, bx=4, bank_n=16)
        params = init_params(cfg, jax.random.PRNGKey(0), max_seq=64)
        toks = jnp.asarray(np.random.default_rng(0).integers(
            1, cfg.vocab, (2, 8)), jnp.int32)
        mesh = make_mesh((8,), ("model",))

        def traced(prog, mesh_):
            p = install_program(params, prog, cfg)
            with accel.trace() as recs:
                if mesh_ is not None:
                    with autoshard.use_mesh(mesh_):
                        jax.jit(lambda p, t: prefill(p, t, cfg, 32))(p, toks)
                else:
                    jax.jit(lambda p, t: prefill(p, t, cfg, 32))(p, toks)
            return recs

        # capacity 0: every image streams on both sides -> loads identical
        r1 = traced(build_program(params, cfg, capacity_chips=0), None)
        r8 = traced(build_program(params, cfg, capacity_chips=0,
                                  mesh=mesh), mesh)
        assert len(r1) == len(r8), (len(r1), len(r8))
        assert sum(r.calls for r in r1) == sum(r.calls for r in r8)
        assert sum(r.loads for r in r1) == sum(r.loads for r in r8)
        sharded = [r for r in r8 if r.devices == 8]
        assert sharded and all(r.partition in ("col", "row")
                               for r in sharded)
        # logical shapes on the records, never per-shard
        by_tag1 = {(r.tag, r.n, r.m) for r in r1 if r.program}
        by_tag8 = {(r.tag, r.n, r.m) for r in r8 if r.program}
        assert by_tag1 == by_tag8
        # per-device reload segments shrink with the shard
        assert sum(r.load_segments for r in r8) < \
            sum(r.load_segments for r in r1)
        es1 = accel.energy_summary(r1)
        es8 = accel.energy_summary(r8)
        assert es8["total_cycles"] < es1["total_cycles"]   # per-device wall
        assert es8["load_cycles"] < es1["load_cycles"]
        print("OK")
    """)
    assert "OK" in out


# ---------------------------------------------------------- serving layer

def test_sharded_batcher_matches_unsharded_batcher():
    """Sharded program decode (8 chips) emits the SAME tokens as the
    single-device program path through the full slot-batching loop —
    admission prefills, splices, retirements and all (greedy,
    digital_int)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config
        from repro.models import init_params
        from repro.serve.engine import ContinuousBatcher, ServeConfig

        cfg = get_config("olmo-1b").reduced().with_accel(
            "digital_int", ba=4, bx=4, bank_n=16)
        params = init_params(cfg, jax.random.PRNGKey(0), max_seq=128)
        mesh = make_mesh((8,), ("model",))
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, cfg.vocab, (int(l),)).astype(np.int32)
                   for l in (5, 9, 17, 4)]

        def run(mesh_):
            scfg = ServeConfig(max_seq=64, max_new_tokens=8, mesh=mesh_)
            cb = ContinuousBatcher(params, cfg, scfg, n_slots=2)
            rids = [cb.submit(p) for p in prompts]
            return rids, cb.run()

        rids1, r1 = run(None)
        rids8, r8 = run(mesh)
        assert rids1 == rids8
        for rid in rids1:
            assert r1[rid] == r8[rid], (rid, r1[rid], r8[rid])
        print("OK")
    """)
    assert "OK" in out


def test_sharded_slot_splice_parity_vs_solo():
    """Slot splicing on SHARDED cache pytrees: with the mesh active and
    weights/caches TP-sharded, the batcher must still be token-for-token
    the solo engine (digital policy — projection numerics are
    batch-width independent there, so any mismatch is a splice bug)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config
        from repro.models import init_params
        from repro.serve.engine import ContinuousBatcher, Engine, ServeConfig

        cfg = get_config("olmo-1b").reduced()        # all-digital policy
        params = init_params(cfg, jax.random.PRNGKey(0), max_seq=128)
        mesh = make_mesh((8,), ("model",))
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, cfg.vocab, (int(l),)).astype(np.int32)
                   for l in (5, 9, 17, 4, 11)]
        scfg = ServeConfig(max_seq=64, max_new_tokens=8, mesh=mesh)
        cb = ContinuousBatcher(params, cfg, scfg, n_slots=2)
        rids = [cb.submit(p) for p in prompts]
        res = cb.run()
        # the live cache really is model-sharded (not silently replicated)
        eng = Engine(params, cfg, scfg)
        leaf = jax.tree_util.tree_leaves(eng.init_cache(2).layers)[0]
        assert "model" in str(leaf.sharding.spec), leaf.sharding
        for rid, p in zip(rids, prompts):
            solo = eng.generate(jnp.asarray(p[None]),
                                request_ids=np.asarray([rid]))[0].tolist()
            assert res[rid] == solo[:len(res[rid])] and \\
                len(res[rid]) == 8, (rid, res[rid], solo)
        print("OK")
    """)
    assert "OK" in out


# ------------------------------------------------- fused datapath epilogue

_FUSED_PARITY = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro import accel
    from repro.core.datapath import Postreduce
    from repro.accel.program import _compile_image, partition_for
    from repro.distributed.autoshard import use_mesh

    devices = {devices}
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256, 64)), jnp.float32)
    post = Postreduce(
        scale=jnp.asarray(rng.normal(size=(64,)), jnp.float32),
        bias=jnp.asarray(rng.normal(size=(64,)), jnp.float32),
        act="relu", saturate=True)
    mesh = make_mesh((devices,), ("model",))
    # bank_n = per-device rows so row-parallel bpbs stays bit-exact
    for tag in ("mlp.gate", "mlp.down"):        # -> col, row partitions
        for backend in ("digital_int", "bpbs", "pallas"):
            spec = accel.ExecSpec(backend=backend, ba=4, bx=4,
                                  bank_n=256 // devices, tag=tag)
            part = partition_for(tag, 256, 64, devices)
            img = _compile_image(w, spec, "p", shards=devices,
                                 partition=part)
            assert img.partition == ("row" if tag == "mlp.down" else "col")
            with use_mesh(mesh, None):
                y_f = jax.jit(lambda x: accel.matmul(
                    x, w, spec, image=img, post=post))(x)
                y_u = jax.jit(lambda x: post.apply(accel.matmul(
                    x, w, spec, image=img), spec.bx, spec.ba))(x)
            y_ref = jax.jit(lambda x: accel.matmul(x, w, spec,
                                                   post=post))(x)
            d_u = float(jnp.abs(y_f - y_u).max())
            d_r = float(jnp.abs(jnp.asarray(y_f) - y_ref).max())
            tol = 0.0 if backend != "pallas" else 1e-4
            assert d_u <= tol and d_r <= tol, (tag, backend, d_u, d_r)
    print("FUSED_OK")
"""


@pytest.mark.slow
@pytest.mark.parametrize("devices", [2, 4, 8])
def test_sharded_fused_postreduce_parity(devices):
    """Acceptance: the fused Postreduce path under shard_map (epilogue
    inside the body — local rescale+registers on "col" tiles, applied
    after the psum on "row" tiles) is bit-for-bit the unfused
    matmul-then-postreduce AND the unsharded fused path on
    digital_int/bpbs (allclose on pallas), for 2/4/8 devices."""
    out = run_py(_FUSED_PARITY.format(devices=devices), devices=devices)
    assert "FUSED_OK" in out


def test_sharded_fused_postreduce_parity_2dev_fast():
    """Tier-1-visible slice of the fused shard parity matrix."""
    out = run_py(_FUSED_PARITY.format(devices=2), devices=2)
    assert "FUSED_OK" in out
