"""What every cell shares: finding a cell's files by name, the chip check,
the compile cache, compile counting, the peak table and the result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.  The
configuration's JSON file names its model ``kind`` (an adapter in
``bench/models/<kind>.py``) and its plain reference
(``bench/reference/<reference>.py``); the traffic file names its ``loop``
(``bench/loops/<loop>.py``).  A per-layer metric is read by
``bench/metrics/<name>.py``, or by ``bench/metrics/<prefix>.py`` for a
name ``<prefix>.<suffix>``.  Adding any of these is adding files.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import os
import pathlib
import sys
import time
from typing import Any, Optional

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
# fixed, inside the checkout: the cache key includes the directory
CACHE_DIR = ROOT / ".jax_cache"


def load_json(path) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict          # the BENCHMARK.json entry
    config: dict            # the configuration file's contents
    traffic: dict           # the traffic file's contents
    end_to_end: list        # metric entries this cell reports with --trace 0
    per_layer: list         # metric entries this cell reports with --trace 1
    chips: int
    limits: dict            # number compared -> its limit (checks/<cell>.json)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str) -> Cell:
    bench = load_json(ROOT / "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json; "
                         f"have {sorted(work)}")
    w = work[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(ROOT / cfg_entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in reported and _reports(m, name)]
    checks = BENCH / "checks" / f"{name}.json"
    limits = load_json(checks)["limits"] if checks.exists() else {}
    return Cell(name, w, config, traffic, e2e, per_layer, int(w["chips"]),
                limits)


def load_module(group: str, name: str):
    """``bench/<group>/<name>.py`` as a module (names may hold '-' or
    '.', so this loads by path)."""
    path = BENCH / group / f"{name}.py"
    if not path.exists():
        raise FileNotFoundError(f"bench: no {group} module {path}")
    if name.isidentifier():
        return importlib.import_module(f"bench.{group}.{name}")
    key = f"bench_{group}_{name}".replace("-", "_").replace(".", "_")
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The reader of one per-layer metric: ``metrics/<name>.py`` or, for
    ``<prefix>.<suffix>``, ``metrics/<prefix>.py``."""
    if (BENCH / "metrics" / f"{name}.py").exists():
        return load_module("metrics", name)
    return load_module("metrics", name.split(".")[0])


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ device

def require_chips(chips: int):
    """The TPU devices a cell may use; exits non-zero, printing no result,
    anywhere else."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX backend is {backend!r}")
    devices = jax.devices()
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} TPU chips, JAX "
                         f"finds {len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at the fixed in-checkout path,
    every program cached; the program's own cache helper reads the same
    variable."""
    import jax

    CACHE_DIR.mkdir(parents=True, exist_ok=True)   # JAX does not create it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


class CompileClock:
    """Backend compiles (seconds, count) and persistent-cache hits, as JAX
    reports them through ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return self.compiles, self.cache_hits

    def since(self, snap: tuple) -> int:
        """Programs compiled or loaded from the cache since ``snap``."""
        return (self.compiles - snap[0]) + (self.cache_hits - snap[1])


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown device is an error."""
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"bench: no peaks for device kind {device_kind!r}; "
                       f"peaks.json has {sorted(table['devices'])}")
    return dict(table["devices"][device_kind], source=table["source"])


def memory_peak_bytes(devices) -> Optional[int]:
    peaks_ = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks_.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_) if peaks_ else None


def device_info(devices, memory_peak: Optional[int]) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": memory_peak}


@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler's trace (idle gaps are attributed to
    these names)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


class Window:
    """The measured window on the host clock."""

    def __init__(self, seconds: float):
        self.seconds = float(seconds)
        self.t0 = self.t1 = None

    def start(self):
        self.t0 = time.perf_counter()
        return self

    def open(self) -> bool:
        return time.perf_counter() - self.t0 < self.seconds

    def close(self):
        self.t1 = time.perf_counter()
        return self.t1 - self.t0

    @property
    def length(self) -> float:
        return self.t1 - self.t0


# ------------------------------------------------------------------ result

def check_line(checks: dict) -> None:
    """Each number compared beside its limit, as the last lines on
    standard error."""
    for name, c in checks.items():
        ok = c["value"] <= c["limit"]
        print(f"[bench] check {name}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr, flush=True)


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, checks: dict, breakdown=None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)
