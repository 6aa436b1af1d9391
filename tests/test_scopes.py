"""Device time by layer and phase (``bench/scopes.py``): the program's
scopes in its compiled text, the parse of that text, the per-instruction
trace reduction on the recorded trace, the join, and the readers'
guards."""
import collections
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness, scopes, trace  # noqa: E402
from bench.models import cnn as bench_cnn  # noqa: E402

DATA = ROOT / "bench" / "tests" / "data"
CELL = "cifar-net-b.batch256"

NO_WORK = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
           "after-all"}
# the plane-skip liveness is formed inside the kernel, under cima.kernel
KERNEL_PATH = {"cima.quantize_x", "cima.quantize_w", "cima.planes",
               "cima.pad", "cima.kernel", "cima.post"}


def _net_config(backend, layers):
    config = dict(harness.find_cell(CELL).config, image_hw=8, bank_n=512,
                  backend=backend, layers=layers)
    return config, bench_cnn.program_config(config, interpret=True)


@pytest.mark.parametrize("backend,layers,want", [
    ("pallas",
     [{"kind": "conv", "cin": 3, "cout": 16, "pool": True},
      {"kind": "fc", "cin": 4 * 4 * 16, "cout": 10}],
     {"cnn.layer0", "cnn.layer1", "cnn.im2col", "cnn.pool"} | KERNEL_PATH),
    ("bpbs",
     [{"kind": "conv", "cin": 3, "cout": 16}],
     {"cnn.layer0", "cnn.im2col", "cima.quantize_x", "cima.quantize_w",
      "cima.post"}),
])
def test_compiled_cnn_step_carries_every_scope(backend, layers, want):
    from repro.models.cnn import cnn_forward

    config, net = _net_config(backend, layers)
    params = bench_cnn.make_params(config, 5)
    images = jnp.zeros((4, 8, 8, 3), jnp.float32)
    text = jax.jit(lambda p, x: cnn_forward(p, x, net)).lower(
        params, images).compile().as_text()

    found = set()
    for ins in scopes.instructions(text).values():
        found.update(s for s in ins.op_name.split("/")
                     if s.startswith(scopes.PHASE_PREFIXES))
    assert want <= found, want - found

    smap = scopes.scope_map(text)
    work = [i for i in scopes.instructions(text).values()
            if i.entry and i.opcode not in NO_WORK]
    placed = [smap[i.name] for i in work]
    unscoped = [i.name for i, (_, phase) in zip(work, placed)
                if phase == scopes.UNSCOPED]
    assert len(unscoped) < 0.05 * len(work), unscoped
    # every scoped instruction lies in exactly one layer of this net
    layers_seen = {layer for layer, phase in placed
                   if phase != scopes.UNSCOPED}
    assert layers_seen == {f"cnn.layer{i}" for i in range(len(layers))}


@pytest.mark.parametrize("op_name,expected", [
    ("jit(f)/cnn.layer3/jit(_cima_mvm_planes)/cima.pad/jit(_pad)/pad",
     ("cnn.layer3", "cima.pad")),
    ("jit(f)/cnn.layer0/cnn.im2col/concatenate",
     ("cnn.layer0", "cnn.im2col")),
    ("jit(f)/cnn.layer2/cima.quantize_x/cima.post/mul",      # innermost
     ("cnn.layer2", "cima.post")),
    ("jit(f)/cnn.layer7/sub", ("cnn.layer7", scopes.LAYER_BODY)),
    ("jit(serve)/cima.planes/transpose", (None, "cima.planes")),
    ("jit(<lambda>)/dynamic_slice", (None, scopes.UNSCOPED)),
    ("", (None, scopes.UNSCOPED)),
])
def test_scope_of(op_name, expected):
    assert scopes.scope_of(op_name) == expected


HLO = """HloModule jit_f, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %abs.3 = f32[8]{0} abs(%param_0), metadata={op_name="jit(f)/cnn.layer0/cima.quantize_x/abs"}
  ROOT %neg.1 = f32[8]{0} negate(%abs.3), metadata={op_name="jit(f)/cnn.layer0/cima.planes/neg"}
}

ENTRY %main.9 (x.1: f32[8]) -> (f32[8], f32[8]) {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %copy.7 = f32[8]{0:T(128)} copy(%x.1)
  %bitcast.2 = f32[8]{0} bitcast(%copy.7)
  %fusion.88 = f32[8]{0} fusion(%bitcast.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/cnn.layer0/cima.planes/neg"}
  %cima_bpbs_mvm.3 = f32[8]{0} custom-call(%fusion.88), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/cnn.layer0/jit(_cima_mvm_planes)/cima.kernel/pallas_call"}
  %copy-start.4 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%x.1)
  %copy-done.4 = f32[8]{0} copy-done(%copy-start.4)
  ROOT %tuple.5 = (f32[8]{0}, f32[8]{0}) tuple(%cima_bpbs_mvm.3, %copy-done.4)
}
"""


def test_parse_and_scope_map_by_hand():
    ins = scopes.instructions(HLO)
    assert ins["fusion.88"].opcode == "fusion" and ins["fusion.88"].entry
    assert not ins["neg.1"].entry
    assert ins["copy-start.4"].shape.startswith("(f32[8]{0}, ")
    assert ins["copy-start.4"].opcode == "copy-start"
    assert ins["tuple.5"].operands == ("cima_bpbs_mvm.3", "copy-done.4")
    smap = scopes.scope_map(HLO)
    # a layout copy without metadata takes its consumer's scope, through
    # the bitcast between them
    assert smap["copy.7"] == smap["bitcast.2"] == ("cnn.layer0",
                                                   "cima.planes")
    assert smap["cima_bpbs_mvm.3"] == ("cnn.layer0", "cima.kernel")
    # nothing scoped consumes it: unscoped
    assert smap["copy-done.4"] == (None, scopes.UNSCOPED)
    # the fusion is placed by its root; its body also holds quantize_x
    assert scopes.fused_phases(HLO) == {
        "fusion.88": frozenset({"cima.planes", "cima.quantize_x"})}


def test_join_by_hand():
    smap = scopes.scope_map(HLO)
    inst_s = {"fusion.88": 3.0, "copy.7": 1.0, "cima_bpbs_mvm.3": 2.0,
              "copy-done.4": 0.5, "fusion.99": 0.25}      # not in the text
    attr = scopes.attribute(inst_s, smap, images=1000,
                            fused=scopes.fused_phases(HLO))
    assert attr.rows[("cnn.layer0", "cima.planes")] == {"fusion": 3.0,
                                                        "copy": 1.0}
    assert attr.rows[("cnn.layer0", "cima.kernel")] == {"cima_bpbs_mvm": 2.0}
    assert attr.rows[(None, scopes.UNSCOPED)] == {"copy-done": 0.5}
    assert attr.missing == {"fusion": 0.25}
    assert attr.coverage == pytest.approx(6.5 / 6.75)
    assert attr.phase_s(scopes.OPERAND_PREP) == 4.0
    assert attr.us_per_image(scopes.OPERAND_PREP) == pytest.approx(4000.0)
    assert attr.us_per_image(scopes.IM2COL) == 0.0
    top = attr.table()
    assert top[0][:3] == ["cnn.layer0", "cima.planes", 4.0]
    assert top[0][3] == [["fusion", 3.0], ["copy", 1.0]]
    assert attr.fused == {("cnn.layer0", "cima.planes"): {
        "fusion": {"cima.quantize_x"}}}


def test_log_names_fused_phases_and_long_gaps(capsys):
    smap = scopes.scope_map(HLO)
    attr = scopes.attribute({"fusion.88": 3.0}, smap, images=10,
                            fused=scopes.fused_phases(HLO))
    win = scopes.TracedWindow({"fusion.88": 3.0}, 4.0,
                              [(0.5, [("pjrt-tpu-tasks/1247", "Execute")])])
    scopes.log_attribution(attr, win)
    err = capsys.readouterr().err
    assert "fusion 3.000000 (+cima.quantize_x)" in err
    assert "phase cima.planes" in err and "300000.000 us/image" in err
    assert "idle gap 0.500000 s" in err and "pjrt-tpu-tasks/1247" in err


def _attr(placed, missing, phase="cima.planes"):
    return scopes.Attribution({("cnn.layer0", phase): {"fusion": placed}},
                              {"fusion": missing} if missing else {}, 100)


class _Reading:
    def __init__(self, attr):
        self._device_scopes = attr


@pytest.mark.parametrize("metric", ["operand_prep_us.cnn", "im2col_us.cnn"])
@pytest.mark.parametrize("attr,reads", [
    (_attr(0.89, 0.11), False),                   # join places 89%: nothing
    (_attr(0.9, 0.1), True),
    (_attr(1.0, 0.0), True),
    (_attr(1.0, 0.0, scopes.UNSCOPED), False),    # a program without scopes
    (None, False),                                # no jitted step to read
])
def test_readers_need_the_join(metric, attr, reads):
    v = harness.metric_reader(metric).read(metric, _Reading(attr))
    assert (v is not None) == reads
    if reads:
        want = 1e6 * (attr.found_s if metric.startswith("operand_prep")
                      else 0.0) / 100
        assert v == pytest.approx(want)


def test_instruction_seconds_on_recorded_trace():
    expected = json.loads((DATA / "cnn_window.json").read_text())
    path = str(DATA / "cnn_window.xplane.pb")
    s = trace.reduce(path, "bench.window", ("cima_bpbs_mvm",))
    w = scopes.reduce_instructions(path, "bench.window", min_gap_s=0.0)
    assert w.window_s == pytest.approx(expected["window_s"], rel=1e-9)
    assert sum(w.inst_s.values()) == pytest.approx(sum(s.op_s.values()),
                                                   rel=1e-12)
    by_key = collections.defaultdict(float)
    for name, sec in w.inst_s.items():
        by_key[trace.op_key(name)] += sec
    assert by_key.keys() == s.op_s.keys()
    for k, v in s.op_s.items():
        assert by_key[k] == pytest.approx(v, rel=1e-9)
    assert any(name.startswith("cima_bpbs_mvm.") for name in w.inst_s)
    # every idle gap, with the host events at its middle: the innermost
    # bench span trace.reduce names is among them
    assert sorted(g for g, _ in w.long_gaps) == pytest.approx(
        sorted(g for _, g in s.gaps))
    owners = collections.Counter(o for o, _ in s.gaps if o != "none")
    named = collections.Counter()
    for _, events in w.long_gaps:
        named.update({e for _, e in events if e in owners})
    assert all(named[o] >= n for o, n in owners.items())


def test_long_gaps_only_above_the_threshold():
    path = str(DATA / "cnn_window.xplane.pb")
    w = scopes.reduce_instructions(path, "bench.window", min_gap_s=1e-4)
    assert all(g >= 1e-4 for g, _ in w.long_gaps)
    every = scopes.reduce_instructions(path, "bench.window", min_gap_s=0.0)
    assert len(w.long_gaps) < len(every.long_gaps)


def test_window_runs_the_cells_step_on_the_cpu():
    """The whole attribution window at a CPU size: session, compiled text,
    traced steps, reduction and join.  The CPU trace has no TPU plane, so
    nothing is placed and the readers report nothing."""
    from bench.tests.test_faults import small_cnn_cell

    class Reading:
        cell = small_cnn_cell()

    r = Reading()
    attr = scopes.window(r, steps=2, interpret=True)
    assert attr.images == 2 * r.cell.traffic["batch"]
    assert attr.rows == {} and attr.missing == {}
    assert scopes.window(r) is attr                 # measured once
    assert harness.metric_reader("im2col_us.cnn").read(
        "im2col_us.cnn", r) is None
