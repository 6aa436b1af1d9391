"""The trace reduction, on a small trace recorded on one v5e
(``data/cnn_window.xplane.pb``: a traced window of Network A's 256-image
batch loop), and on intervals worked out by hand."""
import json
import pathlib

import pytest

from bench import trace

DATA = pathlib.Path(__file__).parent / "data"


def test_union_and_gaps_by_hand():
    iv = [(0, 10), (5, 15), (20, 30), (22, 25)]
    assert trace._union(iv) == 25
    assert trace._gaps(iv, 0, 40) == [(15, 20), (30, 40)]
    assert trace._gaps([], 3, 7) == [(3, 7)]


def test_op_key():
    assert trace.op_key("%cima_bpbs_mvm.12 = f32[8,128]{1,0} custom-call("
                        "s32[8]{0} %a)") == "cima_bpbs_mvm"
    assert trace.op_key("%copy-done.23") == "copy-done"


def test_recorded_trace():
    expected = json.loads((DATA / "cnn_window.json").read_text())
    s = trace.reduce(str(DATA / "cnn_window.xplane.pb"), "bench.window",
                     ("cima_bpbs_mvm",))
    assert s.chips == 1
    assert s.window_s == pytest.approx(expected["window_s"], rel=1e-9)
    assert s.busy_s == pytest.approx(expected["busy_s"], rel=1e-9)
    assert s.matched_s["cima_bpbs_mvm"] == pytest.approx(
        expected["kernel_s"], rel=1e-9)
    assert s.n_ops == expected["n_ops"]
    # what any reduction must satisfy
    assert 0.0 < s.busy_s <= s.window_s
    assert s.matched_s["cima_bpbs_mvm"] <= s.busy_s
    assert sum(s.op_s.values()) >= s.busy_s * (1 - 1e-9)
    assert sum(g for _, g in s.gaps) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6, abs=1e-9)
    assert s.op_s["cima_bpbs_mvm"] == pytest.approx(
        s.matched_s["cima_bpbs_mvm"])
    b = s.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(name.startswith("bench.") or name == "none"
               for name, _ in b["idle_gaps"])
