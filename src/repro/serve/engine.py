"""Batched serving engine: prefill + decode with slot-level continuous
batching.

The engine keeps a fixed pool of batch slots.  Decode always runs at full
batch width, jitted with a donated cache, and ``DecodeCache.pos`` is
per-slot — so slots at different sequence lengths share one device step.
Whenever a slot finishes (EOS or token budget) it is retired and refilled
*alone*: the new request is left-padded to a power-of-two bucket,
prefilled with a pad mask (so padding never pollutes its cache), and its
batch-1 cache is spliced into the live batch cache while the other slots
keep decoding.  No generational waves, no head-of-line blocking.

Sampling keys are derived per request as ``fold_in(fold_in(key,
request_id), step)`` — a request's sampled tokens never depend on which
slots or neighbours it shared a batch with.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import decode_step, init_cache, prefill, splice_slot

from .host import host_sync

# Decode steps run at a whole number of DECODE_ROW_TILE rows (the TPU's
# sublane tile); rows past the live requests are idle padding whose
# samples are discarded.  XLA fuses and lowers a program according to
# its shapes, so one request decoded at two row counts runs two
# different programs: on a TPU, one-request Engine.generate and a
# 4-slot scheduler computed different logits for the same request and
# their greedy streams parted.  With Engine.generate, the slot batcher
# and the paged scheduler all padded to the row tile, up to
# DECODE_ROW_TILE requests decode with the same shapes on every path
# (README "Serving semantics").
DECODE_ROW_TILE = 8


def decode_rows(batch: int) -> int:
    """The row count a decode step over ``batch`` requests runs at."""
    return -(-batch // DECODE_ROW_TILE) * DECODE_ROW_TILE


@dataclasses.dataclass
class ServeConfig:
    max_seq: int = 2048
    max_new_tokens: int = 64
    temperature: float = 0.0          # 0 = greedy
    eos_id: int = -1                  # -1 = never stop early
    # how often generate() syncs the device-side all-rows-EOS flag to the
    # host to break out of the decode loop.  Each check is a blocking
    # device->host read that serializes decode dispatch, so the default
    # trades up to (eos_check_every - 1) wasted (eos-forced) steps for
    # 4x fewer pipeline stalls; 1 = check (and stop) at every step.
    eos_check_every: int = 4
    seed: int = 0
    # weight-stationary CIMA program (repro.accel.program): compile every
    # quantized projection's bit planes ONCE at engine init so decode
    # steps never re-quantize weights.  cima_chips bounds the standing
    # allocation (N x 590kb arrays PER DEVICE); None = everything resident.
    use_program: bool = True
    cima_chips: Optional[int] = None
    # double-buffered streaming (DESIGN.md §13): overlap-schedule every
    # over-capacity (streamed) image so its reload prefetches into the
    # spare bank set while the other set computes — the trace charges
    # max(compute, load) wall cycles per copy plus a once-per-pass
    # prologue instead of their sum.  Accounting only, numerics are
    # bit-identical; turn off to model a chip without the second bank
    # set's write port.
    stream_double_buffer: bool = True
    # multi-chip mesh serving (DESIGN.md §9/§13): a jax Mesh, either 1D
    # ("model",) or 2D data x model (launch.mesh.make_serve_mesh).  The
    # program compiles partitioned over "model" (column-parallel images
    # split along M, row-parallel along N with a psum after the ADC
    # epilogue); batch rows, KV pools and slot state split over "data"
    # with full image replicas per data shard;
    # params/images/caches are placed with the sharding rules, and every
    # jitted engine function traces under this mesh.  The ShardPolicy is
    # explicit — a concurrently-live trainer or second engine can hold a
    # different one (no module-global policy).
    mesh: Optional[object] = None               # jax.sharding.Mesh
    shard_policy: Optional[object] = None       # distributed.ShardPolicy
    # paged serving (serve.kv / serve.scheduler).  kv_block_size is the
    # positions-per-block granularity of the shared cache pool;
    # decode_block is how many decode steps the paged scheduler runs per
    # jitted dispatch (one host sync per block); prefill_chunk chunks
    # long admission prefills so decode interleaves between pieces
    # (None = whole-prompt prefill, exact for every arch — see
    # PagedScheduler for the chunked-exactness envelope);
    # max_admit_per_step caps admissions per ContinuousBatcher decode
    # step so an arrival burst can't stall live slots behind a
    # head-of-line run of prefills (None = admit greedily).
    kv_block_size: int = 16
    decode_block: int = 8
    prefill_chunk: Optional[int] = None
    max_admit_per_step: Optional[int] = 1
    # batch-decoupled input quantization (ExecSpec.x_per_row), ON by
    # default: every engine function traces under
    # override(x_per_row=True), so quantizing backends compute one input
    # scale per row — what a real per-vector input DAC sees — and a
    # request's token stream never depends on which other requests share
    # its batch.  This is what makes paged vs slot-batcher scheduling
    # bitwise-identical on quantizing backends (the PR 6 caveat).  Turn
    # off only to reproduce the old per-tensor batch-coupled behaviour.
    x_per_row: bool = True

    def __post_init__(self):
        def _pos(name):
            v = getattr(self, name)
            if v <= 0:
                raise ValueError(f"ServeConfig.{name} must be positive, "
                                 f"got {v}")
        for name in ("max_seq", "max_new_tokens", "eos_check_every",
                     "kv_block_size", "decode_block"):
            _pos(name)
        if self.max_seq % self.kv_block_size:
            raise ValueError(
                f"ServeConfig.kv_block_size={self.kv_block_size} must "
                f"divide the cache capacity max_seq={self.max_seq}")
        if self.prefill_chunk is not None and self.prefill_chunk <= 0:
            raise ValueError(f"ServeConfig.prefill_chunk must be positive "
                             f"or None, got {self.prefill_chunk}")
        if self.max_admit_per_step is not None and self.max_admit_per_step <= 0:
            raise ValueError(f"ServeConfig.max_admit_per_step must be "
                             f"positive or None, got {self.max_admit_per_step}")
        if self.temperature < 0:
            raise ValueError(f"ServeConfig.temperature must be >= 0, "
                             f"got {self.temperature}")
        # a policy that DECLARES data_shards must match the actual mesh
        # (a silent mismatch would place caches on an axis that doesn't
        # exist and quietly serve 1/N of the intended batch per replica)
        declared = getattr(self.shard_policy, "data_shards", 1)
        if declared > 1:
            if self.mesh is None:
                raise ValueError(
                    f"shard_policy.data_shards={declared} requires a mesh "
                    f"with a 'data' axis, got mesh=None")
            actual = int(dict(self.mesh.shape).get("data", 1))
            if actual != declared:
                raise ValueError(
                    f"shard_policy.data_shards={declared} but the mesh "
                    f"'data' axis has size {actual}")

    @classmethod
    def from_tuned(cls, tuned, mesh=None, **kw) -> "ServeConfig":
        """A ``ServeConfig`` from an auto-tuner choice (:class:`repro.
        tune.TunedConfig`): the serving-side knobs — bank capacity,
        double-buffered streaming, mesh shape — land here; the
        model-side knobs (policy, plane skip, datapath fusion) apply via
        ``tuned.apply_model(cfg)``.  Extra keywords pass through to the
        constructor (and may override the tuned values explicitly).

        A tuned mesh wider than 1x1 needs a real ``mesh`` whose
        ``data``/``model`` axis sizes match the tuned shape (e.g. from
        ``launch.mesh.make_serve_mesh``) — a silent shape mismatch
        would serve a different design point than the tuner priced.
        When the tuned data axis is wider than 1, a matching
        :class:`~repro.distributed.sharding.ShardPolicy` is attached
        unless the caller supplies one.
        """
        want = (getattr(tuned, "data_shards", 1),
                getattr(tuned, "model_shards", 1))
        if want != (1, 1):
            if mesh is None:
                raise ValueError(
                    f"tuned config {getattr(tuned, 'label', '')!r} wants a "
                    f"{want[0]}x{want[1]} data x model mesh; pass mesh= "
                    f"(e.g. launch.mesh.make_serve_mesh)")
            shape = dict(mesh.shape)
            have = (int(shape.get("data", 1)), int(shape.get("model", 1)))
            if have != want:
                raise ValueError(
                    f"mesh is {have[0]}x{have[1]} data x model but the "
                    f"tuned config was priced at {want[0]}x{want[1]}")
        if want[0] > 1 and "shard_policy" not in kw:
            from repro.distributed.sharding import ShardPolicy

            kw["shard_policy"] = ShardPolicy(data_shards=want[0])
        kw.setdefault("cima_chips", tuned.capacity_chips)
        kw.setdefault("stream_double_buffer", tuned.double_buffer)
        return cls(mesh=mesh, **kw)


class Engine:
    def __init__(self, params, cfg, serve_cfg: ServeConfig):
        self.cfg = cfg
        self.scfg = serve_cfg
        self.mesh = serve_cfg.mesh
        # program load: the paper's weight-stationary step.  For an
        # all-digital policy the program is empty and params pass through
        # untouched; otherwise every managed projection's image installs
        # into the param tree and prefill/decode/splice all reuse it.
        # With a mesh, the program compiles PARTITIONED (per-device image
        # tiles, per-device capacity budget) and params + images are
        # placed with the sharding rules before any jit traces.
        from repro.accel import build_program, install_program

        self.program = None
        if serve_cfg.use_program:
            program = build_program(
                params, cfg, capacity_chips=serve_cfg.cima_chips,
                mesh=self.mesh,
                double_buffer=serve_cfg.stream_double_buffer)
            if program:
                self.program = program
                params = install_program(params, program, cfg)
        if self.mesh is not None:
            from repro.distributed import sharding as shd

            specs = shd.param_specs(jax.eval_shape(lambda: params),
                                    self.mesh, serve_cfg.shard_policy,
                                    program=self.program)
            params = jax.device_put(params, specs)
        self.params = params
        self._prefill = jax.jit(self._meshed(
            lambda p, t, fe: prefill(p, t, cfg, serve_cfg.max_seq, fe)))
        # pad-masked variant for ragged admission (one compile per bucket
        # length — jit caches per shape)
        self._prefill_padded = jax.jit(self._meshed(
            lambda p, t, m: prefill(p, t, cfg, serve_cfg.max_seq,
                                    pad_mask=m)))
        self._decode = jax.jit(self._meshed(
            lambda p, tok, cache: decode_step(p, tok, cache, cfg)),
            donate_argnums=2)
        # writes a prefilled [B]-row cache into the first rows of a fresh
        # decode_rows(B)-row cache (generate's decode width)
        self._widen = jax.jit(self._meshed(splice_slot), donate_argnums=0)
        self._base_key = jax.random.PRNGKey(serve_cfg.seed)
        # decode steps actually issued by the last generate() call (the
        # all-rows-EOS early exit makes this < max_new_tokens - 1)
        self.last_decode_steps = 0

    def _meshed(self, fn):
        """Trace ``fn`` under the engine's execution scopes: the mesh +
        shard policy (ambient for ``cs`` constraints and the shard_map
        program dispatch) and the serving quantization discipline
        (``override(x_per_row=True)`` unless disabled).  The context
        managers are active at TRACE time, which is when dispatch and the
        sharding constraints consult them; scoping them per engine —
        rather than mutating process state at init — is what lets two
        engines (or an engine and a trainer) disagree."""
        import contextlib

        if self.mesh is None and not self.scfg.x_per_row:
            return fn

        def wrapped(*args):
            with contextlib.ExitStack() as stack:
                if self.scfg.x_per_row:
                    from repro.accel import override
                    stack.enter_context(override(x_per_row=True))
                if self.mesh is not None:
                    from repro.distributed.autoshard import use_mesh
                    stack.enter_context(
                        use_mesh(self.mesh, self.scfg.shard_policy))
                return fn(*args)
        return wrapped

    def init_cache(self, batch: int):
        """A fresh (mesh-placed) decode cache at full batch width."""
        cache = init_cache(self.cfg, batch, self.scfg.max_seq)
        if self.mesh is not None:
            from repro.distributed import sharding as shd

            specs = shd.cache_specs(jax.eval_shape(lambda: cache),
                                    self.mesh, batch,
                                    self.scfg.shard_policy)
            cache = jax.device_put(cache, specs)
        return cache

    def sample(self, logits, request_ids, steps):
        """Sample next tokens [B].  Greedy at temperature 0; otherwise each
        row uses the key ``fold_in(fold_in(key, request_id), step)`` where
        ``step`` is the row's own generated-token index — so the sampled
        sequence of a request is a pure function of (seed, request_id,
        logits) and does not depend on batch composition or arrival order.
        """
        if self.scfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def one(rid, step, lg):
            k = jax.random.fold_in(
                jax.random.fold_in(self._base_key, rid), step)
            return jax.random.categorical(k, lg / self.scfg.temperature)

        return jax.vmap(one)(jnp.asarray(request_ids, jnp.int32),
                             jnp.asarray(steps, jnp.int32),
                             logits).astype(jnp.int32)

    def prefill_single(self, prompt: np.ndarray):
        """Pad-masked batch-1 prefill at a power-of-two bucket length
        (one jit compile per bucket); returns (logits [1, V], batch-1
        cache).  The admission path of both batchers."""
        L = len(prompt)
        sb = min(max(_bucket(L), L), self.scfg.max_seq)
        toks = np.zeros((1, sb), np.int32)
        mask = np.zeros((1, sb), bool)
        toks[0, sb - L:] = prompt
        mask[0, sb - L:] = True
        return self._prefill_padded(self.params, jnp.asarray(toks),
                                    jnp.asarray(mask))

    def generate(self, prompts: jax.Array,
                 frontend_embeds: Optional[jax.Array] = None,
                 request_ids=None) -> np.ndarray:
        """prompts: [B, S] int32 -> generated tokens [B, max_new_tokens].

        Prompts must be REAL equal-length sequences, not padded (this
        convenience path passes no pad mask; ragged batching belongs in
        :class:`ContinuousBatcher`).  ``request_ids`` (default
        ``arange(B)``) seed the per-row sampling keys; pass each request's
        stable id to make sampled outputs independent of batch composition.
        """
        assert prompts.ndim == 2, "prompts must be a dense [B, S] batch"
        b = prompts.shape[0]
        w = decode_rows(b)
        eos = self.scfg.eos_id
        rids = np.arange(b) if request_ids is None else np.asarray(request_ids)
        logits, cache = self._prefill(self.params, prompts, frontend_embeds)
        if w != b:
            cache = self._widen(self.init_cache(w), cache, np.int32(0))
        tok = self.sample(logits, rids, np.zeros(b, np.int64))
        out = [tok]
        done = jnp.zeros_like(tok, dtype=bool)
        self.last_decode_steps = 0
        check = max(1, self.scfg.eos_check_every)
        for t in range(1, self.scfg.max_new_tokens):
            if eos >= 0:
                done = done | (tok == eos)
                # every row emitted EOS: stop issuing decode steps and pad
                # the remaining positions with eos_id (exactly what the
                # full loop would have produced).  The host check blocks
                # on the in-flight decode, so it runs every
                # ``eos_check_every`` steps (rows already done keep
                # emitting forced eos in between — outputs are identical
                # for any interval).
                if (t - 1) % check == 0 and bool(host_sync(
                        done, reason="eos early-exit poll, amortized over "
                        "eos_check_every decode steps").all()):
                    break
            logits, cache = self._decode(self.params,
                                         jnp.pad(tok, (0, w - b)), cache)
            logits = logits[:b]
            self.last_decode_steps += 1
            nxt = self.sample(logits, rids, np.full(b, t))
            if eos >= 0:
                nxt = jnp.where(done, eos, nxt)
            tok = nxt
            out.append(tok)
        gen = host_sync(jnp.stack(out, axis=1),
                        reason="end of generate: one batched pull of the "
                        "whole [B, T] token block")
        if gen.shape[1] < self.scfg.max_new_tokens:
            pad = np.full((b, self.scfg.max_new_tokens - gen.shape[1]),
                          eos, gen.dtype)
            gen = np.concatenate([gen, pad], axis=1)
        return gen


def _bucket(n: int) -> int:
    b = 8
    while b < n:
        b *= 2
    return b


@dataclasses.dataclass
class _Slot:
    rid: int
    budget: int
    n_gen: int


_Request = collections.namedtuple("_Request", "rid prompt budget")


class ContinuousBatcher:
    """Slot-level continuous batching over a fixed decode batch.

    ``run()`` drives one persistent decode loop: every iteration is a
    single full-width jitted decode step; finished slots (per-slot EOS or
    token budget) are retired between steps and refilled from the pending
    queue by re-prefilling ONLY that request (left-padded to a power-of-two
    bucket, pad-masked) and splicing its batch-1 cache into the live batch
    cache.  Ragged traffic therefore never idles a slot for a whole
    generational wave.

    ``stats`` after a run: ``decode_steps`` (batched model steps),
    ``slot_steps`` (sum of active slots over those steps — utilization is
    ``slot_steps / (decode_steps * n_slots)``), ``prefills``, and
    ``generated_tokens``.
    """

    def __init__(self, params, cfg, serve_cfg: ServeConfig, n_slots: int):
        if n_slots <= 0:
            raise ValueError(f"n_slots must be positive, got {n_slots}")
        self.engine = Engine(params, cfg, serve_cfg)
        # the engine's params carry the installed program images: admission
        # re-prefills and splices must reuse them, not the raw weights
        self.params, self.cfg, self.scfg = self.engine.params, cfg, serve_cfg
        self.n_slots = n_slots
        self.pending: collections.deque[_Request] = collections.deque()
        self.results: dict[int, list[int]] = {}
        self.stats = {"decode_steps": 0, "slot_steps": 0, "prefills": 0,
                      "generated_tokens": 0}
        # donated jit: splicing one slot must be an in-place scatter on the
        # live batch cache, not a full cache copy per admission.  Traced
        # under the engine's mesh so splicing a batch-1 cache into a
        # sharded live cache keeps the sharded layout (the batch dim is
        # replicated in the cache specs; the model-axis dims line up).
        self._splice = jax.jit(self.engine._meshed(splice_slot),
                               donate_argnums=0)
        self._next_id = 0

    def submit(self, prompt: np.ndarray,
               max_new_tokens: Optional[int] = None) -> int:
        """Queue a request; returns its id.  ``max_new_tokens`` overrides
        the ServeConfig budget per request (ragged output lengths)."""
        assert len(prompt) <= self.scfg.max_seq
        rid = self._next_id
        self._next_id += 1
        budget = (self.scfg.max_new_tokens if max_new_tokens is None
                  else max_new_tokens)
        self.pending.append(_Request(rid, np.asarray(prompt, np.int32),
                                     budget))
        return rid

    # ------------------------------------------------------------ slot path

    _bucket = staticmethod(_bucket)

    def _prefill_request(self, req: _Request):
        """Single-request pad-masked prefill at a bucketed length; returns
        (first sampled token, batch-1 cache)."""
        logits, cache = self.engine.prefill_single(req.prompt)
        self.stats["prefills"] += 1
        tok = self.engine.sample(logits, np.asarray([req.rid]),
                                 np.zeros(1, np.int64))
        return int(host_sync(tok, reason="admission: the first sampled "
                             "token decides retire-vs-splice")[0]), cache

    def run(self, on_token: Optional[Callable[[int, int], None]] = None,
            feed: Optional[Callable[[], bool]] = None
            ) -> dict[int, list[int]]:
        """Serve the queue to completion; returns {rid: tokens} (tokens end
        at EOS inclusive, or at the request's budget).  ``on_token(rid,
        token)`` streams every generated token as it is sampled.  ``feed``
        (if given) is called once per loop iteration to inject wall-clock
        arrivals via ``submit``; while it returns True the loop keeps
        polling instead of exiting when both queue and slots drain."""
        b = self.n_slots
        w = decode_rows(b)
        eos = self.scfg.eos_id
        cache = self.engine.init_cache(w)
        cur = np.zeros(w, np.int32)
        slots: list[Optional[_Slot]] = [None] * b
        emitted: dict[int, list[int]] = {}
        feeding = feed is not None

        def emit(rid, tok):
            emitted[rid].append(tok)
            self.stats["generated_tokens"] += 1
            if on_token is not None:
                on_token(rid, tok)

        while True:
            if feeding:
                feeding = bool(feed())
            # per-slot admission, capped at max_admit_per_step prefills per
            # decode step: an arrival burst used to stall every live slot
            # behind a head-of-line run of admission prefills
            cap = self.scfg.max_admit_per_step
            admitted = 0
            for i in range(b):
                while (slots[i] is None and self.pending
                       and (cap is None or admitted < cap)):
                    req = self.pending.popleft()
                    if req.budget <= 0:
                        self.results[req.rid] = []
                        continue
                    tok, slot_cache = self._prefill_request(req)
                    admitted += 1
                    emitted[req.rid] = []
                    emit(req.rid, tok)
                    if (eos >= 0 and tok == eos) or req.budget <= 1:
                        self.results[req.rid] = emitted.pop(req.rid)
                        continue        # retired at its first token
                    cache = self._splice(cache, slot_cache, np.int32(i))
                    cur[i] = tok
                    slots[i] = _Slot(req.rid, req.budget, 1)
            active = [i for i in range(b) if slots[i] is not None]
            if not active:
                if self.pending:
                    continue           # capped admission left work queued
                if feeding:
                    time.sleep(5e-4)   # idle but arrivals may still come
                    continue
                break

            # one fixed-width decode step for every slot (idle rows ride
            # along; their samples are discarded)
            logits, cache = self.engine._decode(self.params,
                                                jnp.asarray(cur), cache)
            self.stats["decode_steps"] += 1
            self.stats["slot_steps"] += len(active)
            idle = [0] * (w - b)
            rids = np.asarray([s.rid if s else 0 for s in slots] + idle)
            steps = np.asarray([s.n_gen if s else 0 for s in slots] + idle)
            toks = host_sync(self.engine.sample(logits, rids, steps),
                             reason="slot-batcher reference loop: one "
                             "token sync per decode step by design")
            for i in active:
                s = slots[i]
                tok = int(toks[i])
                cur[i] = tok
                s.n_gen += 1
                emit(s.rid, tok)
                if (eos >= 0 and tok == eos) or s.n_gen >= s.budget:
                    self.results[s.rid] = emitted.pop(s.rid)
                    slots[i] = None
        return self.results

    # --------------------------------------------------- generational baseline

    def run_generational(self) -> dict[int, list[int]]:
        """The pre-splice baseline, kept for utilization benchmarking:
        drain the queue in equal-length waves of ``n_slots`` (bucketed by
        prompt length so prefill stays exact without a pad mask).  Every
        wave decodes the full ``max_new_tokens`` budget even after its
        short requests finish — the idle-slot waste the slot-level loop
        removes."""
        while self.pending:
            by_len: dict[int, list[_Request]] = {}
            while self.pending:
                req = self.pending.popleft()
                by_len.setdefault(len(req.prompt), []).append(req)
            for _, group in sorted(by_len.items()):
                for j in range(0, len(group), self.n_slots):
                    wave = group[j: j + self.n_slots]
                    toks = np.stack([r.prompt for r in wave]).astype(np.int32)
                    rids = np.asarray([r.rid for r in wave])
                    gen = self.engine.generate(jnp.asarray(toks),
                                               request_ids=rids)
                    self.stats["prefills"] += 1
                    self.stats["decode_steps"] += self.engine.last_decode_steps
                    self.stats["slot_steps"] += \
                        len(wave) * self.engine.last_decode_steps
                    for r, seq in zip(wave, gen):
                        seq = seq.tolist()[: r.budget]
                        if self.scfg.eos_id >= 0 and self.scfg.eos_id in seq:
                            seq = seq[: seq.index(self.scfg.eos_id) + 1]
                        self.stats["generated_tokens"] += len(seq)
                        self.results[r.rid] = seq
        return self.results
