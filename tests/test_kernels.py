"""Per-kernel validation: sweep shapes/dtypes, allclose vs the pure-jnp
oracle (interpret mode executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hyp_compat import given, settings, st

from repro.core.bpbs import BpbsConfig, weight_planes
from repro.core.datapath import postreduce
from repro.core.quant import Coding, int_range
from repro.kernels import ref
from repro.kernels.cima_mvm import cima_mvm
from repro.kernels.flash_attention import flash_attention

rng = np.random.default_rng(0)


def _ops(coding, ba, bx, n, m, batch, sparsity=0.3, seed=0):
    r = np.random.default_rng(seed)
    lo_x, hi_x = int_range(bx, coding)
    lo_w, hi_w = int_range(ba, coding)
    if coding == Coding.XNOR:
        x = (2 * r.integers(lo_x // 2, hi_x // 2 + 1, (batch, n))
             if bx > 1 else r.choice([-1, 1], (batch, n)))
        w = (2 * r.integers(lo_w // 2, hi_w // 2 + 1, (n, m))
             if ba > 1 else r.choice([-1, 1], (n, m)))
    else:
        x = r.integers(lo_x, hi_x + 1, (batch, n))
        w = r.integers(lo_w, hi_w + 1, (n, m))
    if not (coding == Coding.XNOR and bx == 1):
        x = x * (r.random((batch, n)) > sparsity)
    return jnp.asarray(x, jnp.float32), jnp.asarray(w, jnp.float32)


def _planes(w, cfg):
    """The kernel's weight operand: the int8 planes of the layout function."""
    return weight_planes(w, cfg).astype(jnp.int8)


# tier-1 keeps one XNOR and one AND case of the shape sweep (the rest is
# `slow`), and the input-grid cases below it
CIMA_CASES = [
    # (coding, ba, bx, n, m, bank_n, block_b, block_m, fast[, extra])
    (Coding.XNOR, 4, 4, 300, 40, 2304, 8, 16, True),
    (Coding.XNOR, 1, 1, 256, 32, 2304, 16, 32, False),
    (Coding.XNOR, 2, 3, 512, 16, 256, 8, 16, False),   # multi-bank + padding
    (Coding.XNOR, 8, 8, 100, 8, 2304, 8, 8, False),
    (Coding.XNOR, 4, 2, 2400, 24, 2304, 8, 8, False),  # > one chip bank
    (Coding.AND, 4, 4, 300, 40, 2304, 8, 16, False),
    (Coding.AND, 2, 2, 512, 16, 128, 8, 16, True),
    (Coding.AND, 6, 3, 700, 12, 512, 4, 4, False),
    # The kernel's operand is the integer input grid; planes, per-bank
    # unmasked counts and plane liveness are formed in VMEM.  Network B's
    # narrow bank (27 rows in a 128-lane block), its 1152- and 2304-row
    # banks, a bank plus one row and a ragged third bank, at B_A = B_X in
    # {1, 4}.  "zero_tile": zero elements in every row (mask and nu) and an
    # all-zero second row tile (the plane-skip gate); "fused": the datapath
    # epilogue.  M = 24 over 8-column tiles holds a row tile's planes
    # across the column tiles.
    (Coding.XNOR, 1, 1, 27, 24, 2304, 8, 8, True, "zero_tile"),
    (Coding.XNOR, 1, 1, 1152, 16, 2304, 8, 16, True, "zero_tile-fused"),
    (Coding.XNOR, 1, 1, 2304, 16, 2304, 8, 16, True, "zero_tile-fused"),
    (Coding.XNOR, 1, 1, 2305, 24, 2304, 8, 8, True, "zero_tile-fused"),
    (Coding.XNOR, 4, 4, 27, 16, 2304, 8, 16, True, "zero_tile-fused"),
    (Coding.XNOR, 4, 4, 1152, 24, 2304, 8, 8, True, "zero_tile"),
    (Coding.XNOR, 4, 4, 2305, 16, 2304, 8, 16, True, "zero_tile"),
    (Coding.AND, 1, 1, 27, 16, 2304, 8, 16, True, "zero_tile-fused"),
    (Coding.AND, 1, 1, 1152, 24, 2304, 8, 8, True, "zero_tile"),
    (Coding.AND, 1, 1, 2305, 16, 2304, 8, 16, True, "zero_tile-fused"),
    (Coding.AND, 4, 4, 27, 24, 2304, 8, 8, True, "zero_tile"),
    (Coding.AND, 4, 4, 1152, 16, 2304, 8, 16, True, "zero_tile-fused"),
    (Coding.AND, 4, 4, 2305, 24, 2304, 8, 8, True, "zero_tile-fused"),
    (Coding.AND, 4, 4, 4700, 24, 2304, 8, 8, True, "zero_tile"),
]


def _cima_param(coding, ba, bx, n, m, bank_n, bb, bm, fast, extra=""):
    case = (coding, ba, bx, n, m, bank_n, bb, bm, extra)
    name = "-".join(str(v) for v in (coding.value,) + case[1:] if v != "")
    return pytest.param(*case, id=name,
                        marks=[] if fast else pytest.mark.slow)


@pytest.mark.parametrize("coding,ba,bx,n,m,bank_n,bb,bm,extra",
                         [_cima_param(*c) for c in CIMA_CASES])
def test_cima_mvm_matches_oracle(coding, ba, bx, n, m, bank_n, bb, bm,
                                 extra):
    zero_tile = "zero_tile" in extra
    x, w = _ops(coding, ba, bx, n, m, batch=2 * bb if zero_tile else 5)
    cfg = BpbsConfig(ba=ba, bx=bx, coding=coding, bank_n=bank_n)
    r = np.random.default_rng(n)
    if zero_tile:
        x = x * jnp.asarray(r.random(x.shape) > 0.2)  # zeros in every coding
        x = x.at[bb:].set(0.0)
    y_r = ref.cima_mvm_ref(x, w, cfg)                 # the bpbs fast path
    epi = {}
    if "fused" in extra:
        # powers of two and quarters keep the reference's epilogue exact
        epi = dict(escale=jnp.asarray(2.0 ** r.integers(-3, 2, m),
                                      jnp.float32),
                   pbias=jnp.asarray(r.integers(-8, 8, m) / 4, jnp.float32),
                   act="sign" if bx == 1 else "relu", by_bits=8)
        y_r = postreduce(y_r, scale=epi["escale"], bias=epi["pbias"],
                         act=epi["act"], by_bits=epi["by_bits"])
    y_k = cima_mvm(x, _planes(w, cfg), cfg, block_b=bb, block_m=bm, **epi)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))


@pytest.mark.slow
@pytest.mark.parametrize("adaptive", [False, True])
def test_cima_mvm_adaptive_range(adaptive):
    x, w = _ops(Coding.XNOR, 4, 4, 600, 16, batch=4, sparsity=0.6)
    cfg = BpbsConfig(ba=4, bx=4, bank_n=512, adaptive_range=adaptive)
    y_k = cima_mvm(x, _planes(w, cfg), cfg, block_b=4, block_m=16)
    y_r = ref.cima_mvm_ref(x, w, cfg)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=1e-3)


def test_cima_mvm_ideal_adc_is_exact_gemm():
    x, w = _ops(Coding.XNOR, 4, 4, 2400, 16, batch=4)
    cfg = BpbsConfig(ba=4, bx=4, ideal_adc=True)
    y_k = cima_mvm(x, _planes(w, cfg), cfg, block_b=4, block_m=16)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(x @ w), atol=1e-3)


@pytest.mark.slow
def test_cima_mvm_leading_batch_dims():
    x, w = _ops(Coding.XNOR, 2, 2, 128, 8, batch=6)
    x = x.reshape(2, 3, 128)
    cfg = BpbsConfig(ba=2, bx=2)
    y = cima_mvm(x, _planes(w, cfg), cfg, block_b=4, block_m=8)
    assert y.shape == (2, 3, 8)
    y_r = ref.cima_mvm_ref(x, w, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r), atol=1e-3)


@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), ba=st.integers(1, 6), bx=st.integers(1, 6),
       n=st.sampled_from([64, 255, 300]), m=st.sampled_from([8, 24]))
def test_cima_mvm_property(seed, ba, bx, n, m):
    x, w = _ops(Coding.XNOR, ba, bx, n, m, batch=3, seed=seed)
    cfg = BpbsConfig(ba=ba, bx=bx)
    y_k = cima_mvm(x, _planes(w, cfg), cfg, block_b=4, block_m=8)
    y_r = ref.cima_mvm_ref(x, w, cfg)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=1e-3)


FA_CASES = [
    # (b, h, hkv, s, d, causal, window, bq, bk, dtype)
    (2, 4, 2, 256, 64, True, None, 64, 64, jnp.float32),
    (1, 2, 2, 128, 32, False, None, 64, 64, jnp.float32),
    (1, 4, 1, 256, 64, True, 96, 64, 64, jnp.float32),     # window + MQA
    (1, 8, 4, 192, 48, True, None, 64, 64, jnp.float32),   # padded seq + d
    (2, 2, 2, 256, 128, True, None, 128, 128, jnp.bfloat16),
    (1, 6, 6, 128, 96, True, None, 64, 64, jnp.float32),   # whisper-ish dims
]


@pytest.mark.parametrize("b,h,hkv,s,d,causal,window,bq,bk,dtype", FA_CASES)
def test_flash_attention_matches_oracle(b, h, hkv, s, d, causal, window,
                                        bq, bk, dtype):
    r = np.random.default_rng(1)
    q = jnp.asarray(r.normal(size=(b, h, s, d)), dtype)
    k = jnp.asarray(r.normal(size=(b, hkv, s, d)), dtype)
    v = jnp.asarray(r.normal(size=(b, hkv, s, d)), dtype)
    o_k = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=bq, block_k=bk)
    o_r = ref.attention_ref(q, k, v, causal=causal, window=window)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32), atol=atol)


def test_flash_attention_matches_oracle_long_window():
    """window larger than seq == dense causal."""
    r = np.random.default_rng(2)
    q = jnp.asarray(r.normal(size=(1, 2, 128, 64)), jnp.float32)
    k = jnp.asarray(r.normal(size=(1, 2, 128, 64)), jnp.float32)
    v = jnp.asarray(r.normal(size=(1, 2, 128, 64)), jnp.float32)
    o_w = flash_attention(q, k, v, causal=True, window=4096,
                          block_q=64, block_k=64)
    o_c = flash_attention(q, k, v, causal=True, window=None,
                          block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(o_w), np.asarray(o_c), atol=1e-5)
