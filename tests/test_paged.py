"""Paged serving (DESIGN.md §11): block allocator units, paged-vs-slot
token parity (ragged lengths, budgets, EOS, chunked prefill, meshes),
backpressure (deferred admission, preemption by recompute), and the
chunked-prefill resume path.

The central invariant everything here pins: unwritten pool positions
gather as exact zeros, so the dense view a paged decode block consumes is
bit-identical to the contiguous slot cache — paged output streams equal
the slot batcher's token-for-token, not just approximately.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hyp_compat import given, settings, st
from repro.configs import get_config
from repro.models import (DecodeCache, decode_step, init_params, prefill,
                          prefill_resume)
from repro.serve import (BlockAllocator, ContinuousBatcher, PagedScheduler,
                         ServeConfig, build_layout)
from repro.serve.kv import required_blocks

KEY = jax.random.PRNGKey(0)
_CACHE: dict = {}


def _setup(name="olmo-1b", max_seq=48, **scfg_kw):
    if name not in _CACHE:
        cfg = get_config(name).reduced()
        _CACHE[name] = (cfg, init_params(cfg, KEY, max_seq=64))
    cfg, params = _CACHE[name]
    scfg_kw.setdefault("kv_block_size", 8)
    return cfg, params, ServeConfig(max_seq=max_seq, **scfg_kw)


def _ragged_prompts(n, vocab, seed=1, lengths=(3, 9, 5, 13, 7, 4, 11, 6)):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, (lengths[i % len(lengths)],)
                         ).astype(np.int32) for i in range(n)]


def _run_pair(cfg, params, scfg, prompts, budgets=None, n_slots=3,
              num_blocks=None, priorities=None):
    """Same trace through the slot batcher and the paged scheduler;
    returns (slot results, paged results, paged scheduler)."""
    budgets = budgets or [None] * len(prompts)
    cb = ContinuousBatcher(params, cfg, scfg, n_slots=n_slots)
    for p, m in zip(prompts, budgets):
        cb.submit(p, max_new_tokens=m)
    ref = cb.run()
    ps = PagedScheduler(params, cfg, scfg, n_slots=n_slots,
                        num_blocks=num_blocks)
    for k, (p, m) in enumerate(zip(prompts, budgets)):
        ps.submit(p, max_new_tokens=m,
                  priority=priorities[k] if priorities else 0)
    got = ps.run()
    assert set(ref) == set(got)
    for rid in ref:
        assert ref[rid] == got[rid], (rid, ref[rid], got[rid])
    return ref, got, ps


# ----------------------------------------------------------- allocator

def test_allocator_alloc_free_cycle():
    a = BlockAllocator(6)
    x = a.alloc(4)
    assert sorted(x) == [0, 1, 2, 3] and a.available == 2
    a.free(x[:2])
    y = a.alloc(3)
    assert y is not None and a.available == 1
    assert len(set(x[2:]) | set(y)) == 5          # no id handed out twice


def test_allocator_oom_returns_none_not_partial():
    a = BlockAllocator(4)
    assert a.alloc(3) is not None
    assert a.alloc(2) is None                     # would need 5 total
    assert a.available == 1                       # nothing leaked
    assert a.alloc(1) is not None


def test_allocator_fragmentation_free():
    """Block ids are interchangeable: freeing ANY n blocks makes any
    n-block request satisfiable — no fragmentation by construction."""
    a = BlockAllocator(8)
    held = a.alloc(8)
    a.free(held[1::2])                            # free every other id
    assert a.alloc(4) is not None


def test_allocator_double_free_raises():
    a = BlockAllocator(4)
    ids = a.alloc(2)
    a.free(ids)
    with pytest.raises(ValueError):
        a.free(ids[:1])
    with pytest.raises(ValueError):
        BlockAllocator(0)


# ------------------------------------------------------ config validation

@pytest.mark.parametrize("kw", [
    dict(max_seq=0), dict(max_new_tokens=0), dict(eos_check_every=0),
    dict(eos_check_every=-2), dict(kv_block_size=0),
    dict(max_seq=48, kv_block_size=7),            # does not divide
    dict(decode_block=0), dict(prefill_chunk=0), dict(prefill_chunk=-4),
    dict(max_admit_per_step=0), dict(temperature=-0.1),
])
def test_serve_config_rejects(kw):
    base = dict(max_seq=64, max_new_tokens=8)
    base.update(kw)
    with pytest.raises(ValueError):
        ServeConfig(**base)


def test_n_slots_validated():
    cfg, params, scfg = _setup()
    with pytest.raises(ValueError):
        ContinuousBatcher(params, cfg, scfg, n_slots=0)
    with pytest.raises(ValueError):
        PagedScheduler(params, cfg, scfg, n_slots=-1)


def test_submit_rejects_impossible_request():
    cfg, params, scfg = _setup(max_new_tokens=16)
    ps = PagedScheduler(params, cfg, scfg, n_slots=2, num_blocks=2)
    with pytest.raises(ValueError):               # needs 4 blocks of 8
        ps.submit(np.arange(1, 30, dtype=np.int32))
    ps.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=8)


# ------------------------------------------------------------- layout

def test_layout_classifies_attention_and_state_leaves():
    cfg, _, _ = _setup()
    lay = build_layout(cfg, n_slots=3, s_max=48, block_size=8)
    assert lay.table_width == 48 // 8
    assert any(q is not None for q in lay.seq_axes)      # KV leaves page
    assert lay.num_blocks == 3 * lay.table_width         # full residency

    cfg_ssm = get_config("mamba2-130m").reduced()
    lay2 = build_layout(cfg_ssm, n_slots=3, s_max=48, block_size=8)
    # pure-SSM cache has NO sequence-indexed leaves: paging degenerates
    # to per-slot state copies and the allocator is never needed
    assert all(q is None for q in lay2.seq_axes)
    assert lay2.table_width == 1

    with pytest.raises(ValueError):                      # 48 % 7 != 0
        build_layout(cfg, n_slots=3, s_max=48, block_size=7)


def test_required_blocks():
    cfg, _, _ = _setup()
    lay = build_layout(cfg, 2, 48, 8)
    assert required_blocks(1, lay) == 1
    assert required_blocks(8, lay) == 1
    assert required_blocks(9, lay) == 2
    assert required_blocks(480, lay) == lay.table_width  # ring-capped


# ----------------------------------------------------------- parity

@pytest.mark.parametrize(
    "name", ["olmo-1b",
             pytest.param("mamba2-130m", marks=pytest.mark.slow),
             pytest.param("recurrentgemma-9b", marks=pytest.mark.slow)])
def test_paged_parity_greedy(name):
    """Paged == slot batcher token-for-token on ragged greedy traffic
    (attention pages, pure-SSM degenerates to state copies, rgemma
    mixes ring KV with LRU state leaves)."""
    cfg, params, scfg = _setup(name, max_new_tokens=8)
    _run_pair(cfg, params, scfg, _ragged_prompts(7, cfg.vocab))


def test_paged_parity_eos_truncation():
    """EOS mid-stream: pick a token the greedy stream actually emits so
    some requests truncate early; retired slots' in-flight block writes
    must not corrupt survivors."""
    cfg, params, scfg = _setup(max_new_tokens=10)
    prompts = _ragged_prompts(6, cfg.vocab, seed=3)
    cb = ContinuousBatcher(params, cfg, scfg, n_slots=3)
    for p in prompts:
        cb.submit(p)
    probe = cb.run()
    eos = probe[0][len(probe[0]) // 2]            # an emitted token
    scfg2 = _setup(max_new_tokens=10, eos_id=int(eos))[2]
    _run_pair(cfg, params, scfg2, prompts)


def test_paged_parity_ragged_budgets():
    cfg, params, scfg = _setup(max_new_tokens=12)
    prompts = _ragged_prompts(8, cfg.vocab, seed=5)
    budgets = [1, 12, 3, 7, 2, 12, 5, 4]
    _run_pair(cfg, params, scfg, prompts, budgets, n_slots=2)


def test_paged_parity_sampled_temperature():
    """Temperature sampling: fold_in(request_id, step) keys are batch-
    composition independent, so the paged K-step scan (sampling inside
    the jit) must reproduce the slot batcher's streams exactly."""
    cfg, params, scfg = _setup(max_new_tokens=6, temperature=0.8, seed=11)
    _run_pair(cfg, params, scfg, _ragged_prompts(5, cfg.vocab, seed=7))


def test_paged_oom_defers_admission():
    """A pool far smaller than n_slots * table_width: admissions must be
    deferred (never dropped, never crash) and every stream still matches
    the slot batcher."""
    # budget 10 > decode_block 8 keeps rows resident across blocks; a
    # 3-block pool then can't admit the next ready request while one is
    # live (a 13-token prompt + 9 decode positions is the whole pool)
    cfg, params, scfg = _setup(max_new_tokens=10)
    prompts = _ragged_prompts(6, cfg.vocab, seed=9)
    _, _, ps = _run_pair(cfg, params, scfg, prompts, n_slots=3,
                         num_blocks=3)
    assert ps.stats["deferred_admissions"] > 0


def test_paged_preemption_by_recompute():
    """Decode-time block exhaustion: growing rows must preempt the least
    urgent slot (recompute path) and the preempted request's final
    stream must still match the slot batcher exactly."""
    cfg, params, scfg = _setup(max_new_tokens=24)
    prompts = _ragged_prompts(4, cfg.vocab, seed=13)
    # prompts (<=13) admit with 1-2 blocks, but 24 generated tokens push
    # every row past 8/16 positions: concurrent rows exhaust 4 blocks
    _, _, ps = _run_pair(cfg, params, scfg, prompts, n_slots=3,
                         num_blocks=5, priorities=[0, 1, 2, 3])
    assert ps.stats["preemptions"] > 0


def test_paged_chunked_prefill_parity():
    """Chunked admission prefill (prefill_chunk=4, dense attention,
    digital float): token streams identical to the slot batcher's
    whole-prompt prefills."""
    cfg, params, scfg = _setup(max_new_tokens=8, prefill_chunk=4)
    _, _, ps = _run_pair(cfg, params, scfg,
                         _ragged_prompts(6, cfg.vocab, seed=2))
    assert ps.stats["prefill_chunks"] > ps.stats["prefills"]


def test_paged_property_parity():
    """Property: for random ragged lengths, budgets and seeds the paged
    scheduler is token-identical to the slot batcher (shared jit-warmed
    instances across examples keep this tier-1-affordable)."""
    cfg, params, scfg = _setup(max_new_tokens=6, eos_id=7)
    cb = ContinuousBatcher(params, cfg, scfg, n_slots=2)
    ps = PagedScheduler(params, cfg, scfg, n_slots=2, num_blocks=7)

    @settings(max_examples=5, deadline=None)
    @given(seed=st.integers(0, 10 ** 6),
           n=st.integers(1, 4),
           budget_hi=st.integers(1, 6))
    def prop(seed, n, budget_hi):
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(1, cfg.vocab,
                                (int(rng.integers(1, 14)),)).astype(np.int32)
                   for _ in range(n)]
        budgets = [int(rng.integers(1, budget_hi + 1)) for _ in range(n)]
        for p, m in zip(prompts, budgets):
            cb.submit(p, max_new_tokens=m)
            ps.submit(p, max_new_tokens=m)
        ref, got = cb.run(), ps.run()
        for rid in ref:
            assert ref[rid] == got[rid], (rid, ref[rid], got[rid])

    prop()


# ------------------------------------------------- admission stall (HOL)

def _event_trace(scfg_kw, burst=5):
    """One long-budget request decoding, then a burst of arrivals mid-run
    via feed; record the interleaving of prefills (p) and decodes (d)."""
    cfg, params, scfg = _setup(max_new_tokens=24, **scfg_kw)
    cb = ContinuousBatcher(params, cfg, scfg, n_slots=4)
    events = []
    orig_prefill, orig_decode = cb._prefill_request, cb.engine._decode

    def spy_prefill(req):
        events.append("p")
        return orig_prefill(req)

    def spy_decode(*a):
        events.append("d")
        return orig_decode(*a)

    cb._prefill_request = spy_prefill
    cb.engine._decode = spy_decode
    cb.submit(_ragged_prompts(1, cfg.vocab)[0])
    fed = [False]

    def feed():
        if not fed[0] and events.count("d") >= 2:   # burst mid-decode
            for p in _ragged_prompts(burst, cfg.vocab, seed=4):
                cb.submit(p, max_new_tokens=8)
            fed[0] = True
        return not fed[0]

    cb.run(feed=feed)
    assert fed[0]
    return "".join(events)


def test_admission_burst_does_not_stall_decode():
    """Regression for the head-of-line admission stall: with the default
    max_admit_per_step=1 an arrival burst admits one request per decode
    step — live slots keep making progress (no 'pp' run in the event
    trace).  The uncapped mode still exhibits the stall, proving the
    cap is what fixes it."""
    capped = _event_trace({})
    assert "pp" not in capped, capped
    uncapped = _event_trace({"max_admit_per_step": None})
    assert "pp" in uncapped, uncapped


# ------------------------------------------------------ chunked resume

def test_prefill_resume_bitwise_olmo():
    """prefill(full) == prefill(head) + prefill_resume(tail) BITWISE for
    dense attention under the digital float policy — cache, logits, and
    a subsequent decode step all exactly equal."""
    cfg, params, _ = _setup()
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(1, cfg.vocab, (2, 24)), jnp.int32)
    lg_full, c_full = prefill(params, toks, cfg, 48)
    lg_head, c_head = prefill(params, toks[:, :16], cfg, 48)
    lg_res, c_res = prefill_resume(params, toks[:, 16:], cfg, c_head)
    for a, b in zip(jax.tree_util.tree_leaves(c_full),
                    jax.tree_util.tree_leaves(c_res)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(lg_full), np.asarray(lg_res))
    tok = jnp.argmax(lg_full, -1).astype(jnp.int32)
    lg1, _ = decode_step(params, tok, c_full, cfg)
    lg2, _ = decode_step(params, tok, c_res, cfg)
    np.testing.assert_array_equal(np.asarray(lg1), np.asarray(lg2))


@pytest.mark.slow
@pytest.mark.parametrize("name", ["mamba2-130m", "recurrentgemma-9b"])
def test_prefill_resume_recurrent_argmax(name):
    """SSD/RG-LRU chunk boundaries reassociate float (documented), so the
    resume path is held to argmax agreement, not bitwise equality."""
    cfg = get_config(name).reduced()
    params = init_params(cfg, KEY, max_seq=64)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(1, cfg.vocab, (2, 24)), jnp.int32)
    lg_full, _ = prefill(params, toks, cfg, 48)
    _, c_head = prefill(params, toks[:, :16], cfg, 48)
    lg_res, c_res = prefill_resume(params, toks[:, 16:], cfg, c_head)
    assert isinstance(c_res, DecodeCache)
    np.testing.assert_array_equal(np.asarray(jnp.argmax(lg_full, -1)),
                                  np.asarray(jnp.argmax(lg_res, -1)))
    np.testing.assert_allclose(np.asarray(lg_full), np.asarray(lg_res),
                               rtol=2e-2, atol=2e-2)


def test_prefill_resume_rejects_encdec():
    cfg = get_config("whisper-tiny").reduced()
    assert cfg.is_encdec
    with pytest.raises(NotImplementedError):
        prefill_resume(None, jnp.zeros((1, 4), jnp.int32), cfg, None)
    with pytest.raises(NotImplementedError):
        PagedScheduler(None, cfg, ServeConfig(max_seq=32, max_new_tokens=4,
                                              kv_block_size=8), n_slots=1)


# ------------------------------------------------------------- 2-dev mesh

@pytest.mark.parametrize("backend", ["digital_int", "bpbs"])
def test_paged_parity_quantized_cross_scheduler(backend):
    """Cross-scheduler bitwise parity on QUANTIZING backends.

    Serving quantizes inputs per ROW (``ServeConfig.x_per_row``, the
    batch-decoupled DAC scale), so each request's logits are independent
    of which other requests happen to share its decode batch — the two
    schedulers admit with different timing, and the token streams must
    still match token-for-token."""
    cfg, params, scfg = _setup(max_seq=48, max_new_tokens=6)
    cfg = cfg.with_accel(backend, ba=4, bx=4, bank_n=16)
    params = init_params(cfg, KEY, max_seq=64)
    _run_pair(cfg, params, scfg, _ragged_prompts(4, cfg.vocab))


def test_paged_parity_2dev_mesh():
    """Paged scheduler under a 2-device "model" mesh: pools shard on
    head/latent dims, tables stay host-side.

    Every policy — the default float one and the quantizing substrates —
    is held to the same bar: meshed PagedScheduler == unsharded slot
    batcher token-for-token.  Per-row input quantization (the serving
    default) makes each row's scale a function of that row alone, so
    batch composition and admission timing cancel out even on
    ``digital_int``/``bpbs``; the old carve-out comparing quantized
    paged-vs-paged only is gone.
    """
    from test_shard_exec import run_py

    out = run_py("""
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config
        from repro.models import init_params
        from repro.serve import ContinuousBatcher, PagedScheduler, ServeConfig

        mesh = make_mesh((2,), ("model",))
        rng = np.random.default_rng(0)

        def run(server, prompts):
            for p in prompts: server.submit(p)
            return server.run()

        scfg = ServeConfig(max_seq=48, max_new_tokens=6, kv_block_size=8)
        scfg_m = ServeConfig(max_seq=48, max_new_tokens=6, kv_block_size=8,
                             mesh=mesh)
        for backend in (None, "digital_int", "bpbs"):
            cfg = get_config("olmo-1b").reduced()
            if backend:
                cfg = cfg.with_accel(backend, ba=4, bx=4, bank_n=16)
            params = init_params(cfg, jax.random.PRNGKey(0), max_seq=64)
            prompts = [rng.integers(1, cfg.vocab, (int(l),)).astype(np.int32)
                       for l in (5, 9, 12, 4)]
            ref = run(ContinuousBatcher(params, cfg, scfg, n_slots=2),
                      prompts)
            got = run(PagedScheduler(params, cfg, scfg_m, n_slots=2),
                      prompts)
            for rid in ref:
                assert ref[rid] == got[rid], (backend, rid, ref[rid],
                                              got[rid])
        print("OK")
    """, devices=2)
    assert "OK" in out
