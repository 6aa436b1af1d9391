"""The main-path CIMA kernel compiled for a described TPU v5e.

Nothing runs: each case lowers ``cima_bpbs_mvm`` with ``interpret=False``
and compiles it with the TPU compiler for one chip of a described
``v5e:2x2`` topology, at olmo-1b's published widths and B_A = B_X = 4,
and at CIFAR Network B's (B_A = B_X = 1: one narrow 128-lane bank, a
1152-lane bank, a full 2304-row bank).
That is what refuses a kernel that interpret mode accepts (illegal block
tiling, layouts Mosaic cannot relayout, VMEM overuse).  Network B's conv
layers are also compiled whole, through ``accel.matmul(window=3)`` at
batch 256, to read what XLA makes of the windows on the int8 grid.  The
topology is described inside a fixture, so where no TPU compiler is
installed these tests skip, and importing this file touches no device
state.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.bpbs import BpbsConfig
from repro.kernels.cima_mvm import cima_mvm

CFG = BpbsConfig(ba=4, bx=4)          # skip_zero_planes on (the default)
DECODE_ROWS = 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described-device compile cannot be read back without the chip:
    keep it out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(one_chip, cfg, rows, n, m, fused):
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [arg((rows, n), jnp.int8),                 # quantized inputs
            arg((n, cfg.ba, m), jnp.int8)]            # CimaImage.ws planes
    if fused == "row_silu":
        # per-row escale: batch-decoupled input scales folded into the
        # datapath scale registers, then silu and B_y = 32 saturation
        args.append(arg((rows, m), jnp.float32))

        def fn(x, ws, es):
            return cima_mvm(x, ws, cfg, interpret=False, escale=es,
                            act="silu", by_bits=32)
    elif fused == "col_sign":
        # per-column escale (folded batch-norm), then the ABN's sign
        args.append(arg((m,), jnp.float32))

        def fn(x, ws, es):
            return cima_mvm(x, ws, cfg, interpret=False, escale=es,
                            act="sign", by_bits=2)
    else:
        def fn(x, ws):
            return cima_mvm(x, ws, cfg, interpret=False)

    compiled = jax.jit(fn).lower(*args).compile()
    want = sum(a.size * a.dtype.itemsize for a in args)
    return compiled, want


NET_B = BpbsConfig(ba=1, bx=1)        # XNOR, as cifar-net-b runs


@pytest.mark.parametrize("cfg,rows,n,m,fused", [
    (CFG, DECODE_ROWS, 2048, 8192, None),      # one bank: attention /
                                               # mlp.gate, mlp.up
    (CFG, DECODE_ROWS, 8192, 2048, None),      # four banks: mlp.down
                                               # (per-bank nu, fs)
    (CFG, DECODE_ROWS, 2048, 8192, "row_silu"),
    (NET_B, 4096, 27, 128, "col_sign"),        # conv 0: 128-lane block
    (NET_B, 4096, 1152, 128, "col_sign"),      # conv 1
    (NET_B, 4096, 1152, 256, None),            # conv 2
    (NET_B, 4096, 2304, 256, "col_sign"),      # conv 3-5: a whole bank
], ids=["single_bank", "multi_bank", "fused_silu_row_escale",
        "netb_n27", "netb_n1152", "netb_n1152_m256", "netb_n2304"])
def test_cima_mvm_compiles_for_v5e(one_chip, no_compile_cache, cfg, rows,
                                   n, m, fused):
    assert cfg.skip_zero_planes
    compiled, want = _compile(one_chip, cfg, rows, n, m, fused)
    size = compiled.memory_analysis().argument_size_in_bytes
    # a 27-wide operand is laid out padded to its tile
    assert size == want if n % 128 == 0 else want < size < 2 * want
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "cima_bpbs_mvm" in text


# ``%name = <shape> <opcode>(operands...)`` of a compiled module's text
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (\S+) ([\w-]+)\((.*)$")


def _instructions(text):
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = (m.group(2), m.group(3),
                               re.findall(r"%([\w.\-]+)", m.group(4)))
    return out


def _elements(shape):
    dims = shape.split("[", 1)[1].split("]", 1)[0]
    return int(np.prod([int(d) for d in dims.split(",") if d]))


@pytest.mark.parametrize("hw,cin,cout", [(32, 128, 128), (16, 256, 256)],
                         ids=["netb_conv1", "netb_conv3"])
def test_netb_conv_windows_on_the_grid_compile_for_v5e(
        one_chip, no_compile_cache, hw, cin, cout):
    """One Network B conv layer at batch 256, fused sign epilogue: no
    convolution, no float windows, no int8 relayout copy of a rank-5
    window tensor, and the kernel's ``s8[rows, 9*C]`` operand written by
    a fusion (through bitcasts only)."""
    from repro import accel

    spec = accel.ExecSpec(backend="pallas", ba=1, bx=1, interpret=False)

    def fn(x, w, s, b):
        post = accel.Postreduce(scale=s, bias=b, act="sign", saturate=True)
        return accel.matmul(x, w, spec, post=post, window=3)

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    text = jax.jit(fn).lower(arg((256, hw, hw, cin)), arg((9 * cin, cout)),
                             arg((cout,)), arg((cout,))).compile().as_text()
    assert "convolution(" not in text
    ins = _instructions(text)
    relayouts = [n for n, (shape, op, _) in ins.items()
                 if op == "copy" and shape.startswith("s8[")
                 and shape.split("]")[0].count(",") == 4]
    assert not relayouts, relayouts
    windows = 256 * hw * hw * 9 * cin
    f32_windows = [n for n, (shape, _, _) in ins.items()
                   if shape.startswith("f32[") and _elements(shape) == windows]
    assert not f32_windows, f32_windows
    operand = f"s8[{256 * hw * hw},{9 * cin}]"
    (kernel,) = [n for n, (_, op, _) in ins.items()
                 if op == "custom-call" and n.startswith("cima_bpbs_mvm")]
    (producer,) = [a for a in ins[kernel][2]
                   if ins[a][0].startswith(operand)]
    while ins[producer][1] == "bitcast":
        producer = ins[producer][2][0]
    assert ins[producer][1] == "fusion", ins[producer]
