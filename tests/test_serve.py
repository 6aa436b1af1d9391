"""Slot-level continuous batching: splice parity, per-slot retirement,
streaming, and batch-composition-independent sampling."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import (init_cache, init_params, prefill, slice_slot,
                          splice_slot)
from repro.serve.engine import ContinuousBatcher, Engine, ServeConfig

KEY = jax.random.PRNGKey(0)


def _setup(name="olmo-1b", max_seq=48, **scfg_kw):
    cfg = get_config(name).reduced()
    params = init_params(cfg, KEY, max_seq=64)
    scfg = ServeConfig(max_seq=max_seq, **scfg_kw)
    return cfg, params, scfg


def _ragged_prompts(n, vocab, seed=1, lengths=(3, 9, 5, 13, 7, 4, 11, 6)):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, (lengths[i % len(lengths)],)
                         ).astype(np.int32) for i in range(n)]


@pytest.mark.parametrize(
    "name", ["olmo-1b",
             pytest.param("mamba2-130m", marks=pytest.mark.slow),
             pytest.param("recurrentgemma-9b", marks=pytest.mark.slow)])
def test_slot_splice_parity_greedy(name):
    """N ragged prompts through the slot batcher must produce token-for-
    token what Engine.generate produces one request at a time (greedy,
    fixed seed) — the pad-masked bucketed prefill, the cache splice, and
    the shared-width decode must all be invisible to each request."""
    cfg, params, scfg = _setup(name, max_new_tokens=6)
    prompts = _ragged_prompts(6, cfg.vocab)
    cb = ContinuousBatcher(params, cfg, scfg, n_slots=3)
    rids = [cb.submit(p) for p in prompts]
    results = cb.run()
    eng = Engine(params, cfg, scfg)
    for rid, p in zip(rids, prompts):
        solo = eng.generate(jnp.asarray(p[None]),
                            request_ids=np.asarray([rid]))[0].tolist()
        assert results[rid] == solo, (rid, results[rid], solo)


def test_every_serving_path_decodes_at_the_row_tile():
    """Engine.generate, the slot batcher and the paged scheduler all run
    their decode steps at ``decode_rows(batch)`` rows, so up to
    DECODE_ROW_TILE requests decode with the same shapes on every path —
    XLA compiles a different program for each row count, which on a TPU
    let a request's stream depend on how many rows shared its step."""
    from repro.serve import PagedScheduler
    from repro.serve.engine import DECODE_ROW_TILE, decode_rows

    assert [decode_rows(b) for b in (1, 3, 8, 9)] == [8, 8, 8, 16]
    cfg, params, scfg = _setup(max_new_tokens=4, decode_block=2)
    prompts = _ragged_prompts(3, cfg.vocab)
    widths = []

    def spy(fn, row_arg):
        def wrapped(*args):
            widths.append(args[row_arg].shape[0])
            return fn(*args)
        return wrapped

    eng = Engine(params, cfg, scfg)
    eng._decode = spy(eng._decode, 1)
    solo = [eng.generate(jnp.asarray(p[None]))[0].tolist() for p in prompts]
    cb = ContinuousBatcher(params, cfg, scfg, n_slots=3)
    cb.engine._decode = spy(cb.engine._decode, 1)
    ps = PagedScheduler(params, cfg, scfg, n_slots=3)
    ps._block = spy(ps._block, 3)
    for server in (cb, ps):
        rids = [server.submit(p) for p in prompts]
        streams = server.run()
        assert [streams[r] for r in rids] == solo
    assert widths and set(widths) == {DECODE_ROW_TILE}
    assert ps.layout.num_blocks == 3 * ps.layout.table_width


def test_slot_splice_parity_with_eos_truncation():
    """Parity must hold through EOS retirement: pick a token the greedy
    run actually emits, declare it EOS, and check the batcher truncates
    exactly where the solo engine (trimmed) does — and that freed slots
    were reused (fewer decode steps than the no-EOS run)."""
    cfg, params, scfg = _setup(max_new_tokens=8)
    prompts = _ragged_prompts(5, cfg.vocab)
    cb0 = ContinuousBatcher(params, cfg, scfg, n_slots=2)
    rids0 = [cb0.submit(p) for p in prompts]
    res0 = cb0.run()
    # a token that shows up mid-sequence in some output
    eos = next(t for r in rids0 for t in res0[r][1:-1])

    scfg_eos = ServeConfig(max_seq=scfg.max_seq, max_new_tokens=8,
                           eos_id=int(eos))
    cb = ContinuousBatcher(params, cfg, scfg_eos, n_slots=2)
    rids = [cb.submit(p) for p in prompts]
    results = cb.run()
    eng = Engine(params, cfg, scfg_eos)
    truncated = 0
    for rid, p in zip(rids, prompts):
        solo = eng.generate(jnp.asarray(p[None]),
                            request_ids=np.asarray([rid]))[0].tolist()
        if int(eos) in solo:
            solo = solo[: solo.index(int(eos)) + 1]
            truncated += 1
        assert results[rid] == solo, (rid, results[rid], solo)
    assert truncated, "EOS never fired; test is vacuous"
    assert cb.stats["decode_steps"] < cb0.stats["decode_steps"]


def test_per_request_budgets_and_streaming():
    cfg, params, scfg = _setup(max_new_tokens=6)
    prompts = _ragged_prompts(6, cfg.vocab)
    budgets = (1, 3, 6, 2, 4, 5)
    cb = ContinuousBatcher(params, cfg, scfg, n_slots=2)
    rids = [cb.submit(p, max_new_tokens=m) for p, m in zip(prompts, budgets)]
    stream = []
    results = cb.run(on_token=lambda rid, tok: stream.append((rid, tok)))
    assert [len(results[r]) for r in rids] == list(budgets)
    # the stream carries every token, grouped per request in order
    per_req = {}
    for rid, tok in stream:
        per_req.setdefault(rid, []).append(tok)
    assert per_req == results


def test_slot_utilization_beats_generational_on_ragged_budgets():
    """The motivating claim: on ragged output lengths the persistent slot
    loop retires and refills slots instead of decoding a whole wave to the
    longest budget."""
    cfg, params, scfg = _setup(max_new_tokens=16)
    prompts = _ragged_prompts(6, cfg.vocab)
    budgets = (2, 16, 4, 2, 8, 4)

    gen = ContinuousBatcher(params, cfg, scfg, n_slots=2)
    [gen.submit(p, max_new_tokens=m) for p, m in zip(prompts, budgets)]
    gen.run_generational()
    slot = ContinuousBatcher(params, cfg, scfg, n_slots=2)
    [slot.submit(p, max_new_tokens=m) for p, m in zip(prompts, budgets)]
    slot.run()
    assert slot.stats["generated_tokens"] == gen.stats["generated_tokens"]
    tps_slot = slot.stats["generated_tokens"] / (
        slot.stats["decode_steps"] + slot.stats["prefills"])
    tps_gen = gen.stats["generated_tokens"] / (
        gen.stats["decode_steps"] + gen.stats["prefills"])
    assert tps_slot > tps_gen, (slot.stats, gen.stats)


def test_sampling_determinism_across_batch_composition():
    """Regression (the fold_in fix): with temperature > 0, a request's
    sampled tokens depend only on (seed, request_id), not on which batch
    or wave it landed in."""
    cfg, params, scfg = _setup(max_new_tokens=5, temperature=1.0)
    eng = Engine(params, cfg, scfg)
    rng = np.random.default_rng(3)
    p = rng.integers(1, cfg.vocab, (3, 8)).astype(np.int32)
    solo = eng.generate(jnp.asarray(p[0:1]), request_ids=np.asarray([7]))[0]
    batched = eng.generate(jnp.asarray(p), request_ids=np.asarray([7, 1, 2]))
    np.testing.assert_array_equal(solo, batched[0])
    # the same request (same id, same prompt) in a *different* composition:
    # batch slot, neighbours, and batch size all change, tokens must not
    other = eng.generate(jnp.asarray(p[[2, 0]]),
                         request_ids=np.asarray([2, 7]))
    np.testing.assert_array_equal(solo, other[1])
    np.testing.assert_array_equal(np.asarray(batched)[2],
                                  np.asarray(other)[0])


@pytest.mark.slow
def test_batcher_matches_solo_engine_at_temperature():
    """End-to-end: the slot batcher's sampled outputs equal the solo
    engine's for the same request ids, despite different slot layouts."""
    cfg, params, scfg = _setup(max_new_tokens=5, temperature=0.8)
    prompts = _ragged_prompts(4, cfg.vocab)
    cb = ContinuousBatcher(params, cfg, scfg, n_slots=2)
    rids = [cb.submit(p) for p in prompts]
    results = cb.run()
    eng = Engine(params, cfg, scfg)
    for rid, p in zip(rids, prompts):
        solo = eng.generate(jnp.asarray(p[None]),
                            request_ids=np.asarray([rid]))[0].tolist()
        assert results[rid] == solo


@pytest.mark.slow
def test_slice_splice_roundtrip_pytree_generic():
    """slice_slot/splice_slot must be exact inverses across cache families
    (KV ring caches, SSM/LRU states, prefix/scanned/suffix layouts)."""
    for name in ("recurrentgemma-9b", "mamba2-130m", "deepseek-v2-lite-16b"):
        cfg = get_config(name).reduced()
        params = init_params(cfg, KEY, max_seq=64)
        toks = jax.random.randint(KEY, (3, 8), 0, cfg.vocab)
        _, cache = prefill(params, toks, cfg, s_max=32)
        blank = init_cache(cfg, 3, 32)
        rebuilt = blank
        for i in range(3):
            rebuilt = splice_slot(rebuilt, slice_slot(cache, i), i)
        for a, b in zip(jax.tree_util.tree_leaves(cache),
                        jax.tree_util.tree_leaves(rebuilt)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_generate_stops_decoding_when_all_rows_hit_eos():
    """Engine.generate must break out of the decode loop once every row
    has emitted EOS (padding the tail with eos_id) instead of issuing
    full-width decode steps to the token budget."""
    cfg, params, scfg = _setup(max_new_tokens=12, eos_id=-1)
    eng = Engine(params, cfg, scfg)
    prompts = jnp.asarray(_ragged_prompts(1, cfg.vocab, lengths=(8,))[0]
                          )[None, :]
    base = eng.generate(prompts)
    assert eng.last_decode_steps == scfg.max_new_tokens - 1

    # greedy decode is deterministic: whatever token the model emits at
    # position 2 becomes the EOS -> the loop must stop right there
    # (eos_check_every=1: sync the done flag at every step)
    eos = int(base[0, 2])
    eng2 = Engine(params, cfg, ServeConfig(max_seq=scfg.max_seq,
                                           max_new_tokens=12, eos_id=eos,
                                           eos_check_every=1))
    out = eng2.generate(prompts)
    first = np.asarray(base[0]).tolist().index(eos)
    assert eng2.last_decode_steps == first, \
        (eng2.last_decode_steps, first)
    assert out.shape == base.shape
    # prefix matches the unconstrained run; tail is all eos padding
    row = out[0].tolist()
    np.testing.assert_array_equal(row[:first + 1], base[0, :first + 1])
    assert all(t == eos for t in row[first:])

    # default check interval K: the host sync runs every K steps, so the
    # loop may overshoot by < K forced-eos steps but must still stop
    # early — and the emitted tokens are identical for any interval
    engK = Engine(params, cfg, ServeConfig(max_seq=scfg.max_seq,
                                           max_new_tokens=12, eos_id=eos))
    outK = engK.generate(prompts)
    K = ServeConfig.eos_check_every
    assert first <= engK.last_decode_steps < min(first + K, 12)
    np.testing.assert_array_equal(outK, out)


def test_generate_eos_rows_finish_at_different_steps():
    """Mixed batch: the loop runs until the LAST row finishes, earlier
    rows hold eos — same outputs as the full-budget loop."""
    cfg, params, scfg = _setup(max_new_tokens=10, eos_id=-1)
    eng = Engine(params, cfg, scfg)
    prompts = jnp.asarray(np.stack(_ragged_prompts(2, cfg.vocab,
                                                   lengths=(8, 8))))
    base = eng.generate(prompts)
    row0, row1 = base[0].tolist(), base[1].tolist()
    common = set(row0) & set(row1)
    if common:
        # both rows emit it -> the loop stops when the LATER row finishes
        eos = min(common)
        i0, i1 = row0.index(eos), row1.index(eos)
        want_steps = max(i0, i1)
        ends = ((0, i0), (1, i1))
    else:
        # only row 0 emits it -> row 1 runs its full budget and the loop
        # must NOT stop early; row 0 holds eos from its hit onward
        eos = row0[2]
        i0 = row0.index(eos)
        want_steps = scfg.max_new_tokens - 1
        ends = ((0, i0),)
    eng2 = Engine(params, cfg, ServeConfig(max_seq=scfg.max_seq,
                                           max_new_tokens=10, eos_id=eos,
                                           eos_check_every=1))
    out = eng2.generate(prompts)
    assert eng2.last_decode_steps == want_steps
    for b, i in ends:
        row = out[b].tolist()
        np.testing.assert_array_equal(row[:i + 1], base[b, :i + 1])
        assert all(t == eos for t in row[i:])
    if not common:
        np.testing.assert_array_equal(out[1], base[1])
