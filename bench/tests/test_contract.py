"""BENCHMARK.json against the benchmark's contract, and the files every
name in it leads to."""
import json
import os
import re
import subprocess
import sys

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def bench():
    return harness.load_json(harness.ROOT / "BENCHMARK.json")


def test_keys_names_and_units():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    for entry in b["configs"] + b["workloads"] + b["end_to_end"] + b["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])


def test_every_name_leads_to_its_files():
    b = bench()
    for c in b["configs"]:
        config = harness.load_json(harness.ROOT / c["file"])
        assert (harness.BENCH / "models" / f"{config['kind']}.py").exists()
        assert (harness.BENCH / "reference"
                / f"{config['reference']}.py").exists()
    for w in b["workloads"]:
        cell = harness.find_cell(w["name"])
        assert (harness.BENCH / "loops"
                / f"{cell.traffic['loop']}.py").exists()
        assert cell.limits
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert hasattr(harness.metric_reader(m["name"]), "read")


def test_without_a_tpu_no_result_and_nonzero_exit():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    w = bench()["workloads"][0]["name"]
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", w,
                        "--seed", str(2 ** 31 + 5), "--seconds", "1",
                        "--trace", "0"], cwd=harness.ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {line}")
