"""Tests for repro.analysis: the accel-lint rules and the runtime sanitizer.

Static-rule tests feed small fixture modules through
:func:`repro.analysis.lint_source` under a synthetic ``src/`` path (the
strict scope) and assert on the finding codes.  Each rule gets a
positive fixture (must flag) and a negative fixture (must stay clean) so
a rule can neither silently die nor grow false positives.
"""
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import accel
from repro.analysis import lint_paths, lint_source
from repro.analysis.findings import RULES, explain
from repro.analysis.sanitize import SanitizeError, active, sanitize
from repro.serve.host import host_sync
from repro.serve.kv import BlockAllocator

SRC = "src/repro/serve/fixture.py"     # strict scope, not ACC02-exempt
TEST = "tests/fixture.py"              # relaxed scope


def codes(source, path=SRC):
    return [f.code for f in lint_source(textwrap.dedent(source), path)]


# ----------------------------------------------------------------- JAX01

def test_jax01_item_in_traced_function():
    assert codes("""
        import jax

        @jax.jit
        def step(x):
            return x.sum().item()
        """) == ["JAX01"]


def test_jax01_asarray_in_hot_loop():
    # `drive` is not traced, but it loop-calls a jitted callable: the
    # per-step np.asarray over the device value serializes dispatch.
    assert codes("""
        import jax
        import numpy as np

        step = jax.jit(lambda x: x + 1)

        def drive(x):
            for _ in range(8):
                x = step(x)
                t = np.asarray(x)
            return x
        """) == ["JAX01"]


def test_jax01_clean_outside_hot_path():
    # identical sync in a plain function: nothing jitted anywhere near
    assert codes("""
        import numpy as np

        def plain(x):
            return np.asarray(x)
        """) == []


def test_jax01_host_sync_requires_reason():
    assert codes("""
        import jax
        from repro.serve.host import host_sync

        @jax.jit
        def step(x):
            return host_sync(x)
        """) == ["JAX01"]
    assert codes("""
        import jax
        from repro.serve.host import host_sync

        def drive(step, x):
            for _ in range(8):
                x = step(x)
                t = host_sync(x, reason="documented per-block pull")
            return x
        """) == []


def test_jax01_relaxed_in_tests_scope():
    # benchmarks/tests sync on purpose; only trace-breaking syncs flag
    assert codes("""
        import jax
        import numpy as np

        step = jax.jit(lambda x: x + 1)

        def drive(x):
            for _ in range(8):
                x = step(x)
                t = np.asarray(x)
            return x
        """, path=TEST) == []


# ----------------------------------------------------------------- JAX02

def test_jax02_key_reuse():
    assert codes("""
        import jax

        def sample():
            key = jax.random.PRNGKey(0)
            a = jax.random.normal(key)
            b = jax.random.uniform(key)
            return a + b
        """) == ["JAX02"]


def test_jax02_split_is_clean():
    assert codes("""
        import jax

        def sample():
            key = jax.random.PRNGKey(0)
            k1, k2 = jax.random.split(key)
            a = jax.random.normal(k1)
            b = jax.random.uniform(k2)
            return a + b
        """) == []


def test_jax02_loop_use_without_refresh():
    assert codes("""
        import jax

        def gen(n):
            key = jax.random.PRNGKey(0)
            out = []
            for i in range(n):
                out.append(jax.random.normal(key))
            return out
        """) == ["JAX02"]


def test_jax02_fold_in_per_iteration_is_clean():
    assert codes("""
        import jax

        def gen(n):
            key = jax.random.PRNGKey(0)
            out = []
            for i in range(n):
                key = jax.random.fold_in(key, i)
                out.append(jax.random.normal(key))
            return out
        """) == []


def test_jax02_disjoint_branches_are_clean():
    # the two consumers sit on opposite arms: only one executes
    assert codes("""
        import jax

        def pick(flag):
            key = jax.random.PRNGKey(0)
            if flag:
                return jax.random.normal(key)
            else:
                return jax.random.uniform(key)
        """) == []


# ----------------------------------------------------------------- JAX03

def test_jax03_python_branch_on_traced_value():
    assert codes("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            if jnp.any(x > 0):
                return x
            return -x
        """) == ["JAX03"]


def test_jax03_clean_when_not_traced():
    assert codes("""
        import jax.numpy as jnp

        def f(x):
            if jnp.any(x > 0):
                return x
            return -x
        """) == []


# ----------------------------------------------------------------- JAX04

def test_jax04_import_time_array():
    assert codes("""
        import jax.numpy as jnp

        SCALE = jnp.ones(3)
        """) == ["JAX04"]


def test_jax04_lazy_construction_is_clean():
    assert codes("""
        import jax.numpy as jnp

        def scale():
            return jnp.ones(3)
        """) == []
    # tests may build arrays at module scope (they own the process)
    assert codes("""
        import jax.numpy as jnp

        SCALE = jnp.ones(3)
        """, path=TEST) == []


# ----------------------------------------------------------------- ACC01

def test_acc01_trace_record_inside_shard_map():
    assert codes("""
        from jax.experimental.shard_map import shard_map
        from repro.accel.context import trace

        def launch(mesh, x):
            def body(x):
                trace(x)
                return x
            return shard_map(body, mesh=mesh, in_specs=None,
                             out_specs=None)(x)
        """) == ["ACC01"]


def test_acc01_trace_record_inside_jax_shard_map():
    """The top-level ``jax.shard_map`` spelling is recognised too."""
    assert codes("""
        import jax
        from repro.accel.context import trace

        def launch(mesh, x):
            def body(x):
                trace(x)
                return x
            return jax.shard_map(body, mesh=mesh, in_specs=None,
                                 out_specs=None, check_vma=False)(x)
        """) == ["ACC01"]


def test_acc01_record_outside_shard_map_is_clean():
    assert codes("""
        from jax.experimental.shard_map import shard_map
        from repro.accel.context import trace

        def launch(mesh, x):
            trace(x)
            def body(x):
                return x
            return shard_map(body, mesh=mesh, in_specs=None,
                             out_specs=None)(x)
        """) == []


# ----------------------------------------------------------------- ACC02

def test_acc02_backend_import_outside_accel():
    assert codes("""
        from repro.accel import backends
        """) == ["ACC02"]
    assert codes("""
        from repro.kernels import bpbs_matmul
        """) == ["ACC02"]


def test_acc02_exempt_paths():
    src = "from repro.accel import backends\n"
    assert [f.code for f in lint_source(src, TEST)] == []
    assert [f.code for f in
            lint_source(src, "src/repro/accel/fixture.py")] == []


# ----------------------------------------------------------------- ACC03

def test_acc03_frozen_spec_mutation():
    assert codes("""
        from repro.accel import ExecSpec

        def widen(spec):
            spec = ExecSpec(backend="bpbs", ba=2, bx=2)
            spec.ba = 4
            return spec
        """) == ["ACC03"]


def test_acc03_setattr_outside_post_init():
    assert codes("""
        def widen(spec):
            object.__setattr__(spec, "ba", 4)
            return spec
        """) == ["ACC03"]


def test_acc03_replace_and_post_init_are_clean():
    assert codes("""
        import dataclasses
        from repro.accel import ExecSpec

        def widen(spec):
            spec = ExecSpec(backend="bpbs", ba=2, bx=2)
            return dataclasses.replace(spec, ba=4)

        class Spec:
            def __post_init__(self):
                object.__setattr__(self, "ba", 4)
        """) == []


# ----------------------------------------------------------------- ACC04

def test_acc04_deprecated_policy_api():
    assert codes("""
        from repro.distributed.sharding import set_policy
        """) == ["ACC04"]
    assert codes("""
        def f(sharding):
            return sharding.get_policy()
        """) == ["ACC04"]


def test_acc04_threaded_policy_is_clean():
    assert codes("""
        from repro.distributed.sharding import ShardPolicy, resolve_policy

        def f(policy):
            return resolve_policy(policy)
        """) == []


# ----------------------------------------------------------- suppressions

def test_suppression_inline_with_reason():
    assert codes("""
        import jax

        @jax.jit
        def step(x):
            return x.sum().item()  # accel-lint: allow[JAX01] fixture
        """) == []


def test_suppression_standalone_covers_next_line():
    assert codes("""
        import jax

        @jax.jit
        def step(x):
            # accel-lint: allow[JAX01] fixture: documented sync
            return x.sum().item()
        """) == []


def test_suppression_standalone_covers_only_next_line():
    assert codes("""
        import jax

        @jax.jit
        def step(x):
            # accel-lint: allow[JAX01] fixture: too far away
            y = x + 1
            return y.sum().item()
        """) == ["JAX01"]


def test_suppression_without_reason_is_lnt00():
    out = codes("""
        import jax

        @jax.jit
        def step(x):
            return x.sum().item()  # accel-lint: allow[JAX01]
        """)
    # the bare allow is itself a finding AND does not suppress
    assert sorted(out) == ["JAX01", "LNT00"]


def test_suppression_unknown_code_is_lnt00():
    assert codes("""
        x = 1  # accel-lint: allow[BOGUS99] not a rule
        """) == ["LNT00"]


def test_suppression_inside_string_literal_is_ignored():
    # only real COMMENT tokens count; doc text mentioning the syntax
    # neither suppresses nor trips LNT00
    assert codes('''
        HELP = "write # accel-lint: allow[NOPE] to suppress"
        ''') == []


# ------------------------------------------------------------- call graph

def test_callgraph_traced_reaches_helpers():
    # the sync lives in a plain helper; it flags because the helper is
    # reachable from a jit entry
    assert codes("""
        import jax

        def helper(x):
            return x.item()

        @jax.jit
        def entry(x):
            return helper(x)
        """) == ["JAX01"]


def test_callgraph_unreached_helper_is_clean():
    assert codes("""
        def helper(x):
            return x.item()

        def plain(x):
            return helper(x)
        """) == []


# -------------------------------------------------------------- rule docs

def test_every_rule_has_doc_and_explain():
    for code in ("JAX01", "JAX02", "JAX03", "JAX04",
                 "ACC01", "ACC02", "ACC03", "ACC04", "LNT00"):
        assert code in RULES
        text = explain(code)
        assert RULES[code].title in text and "Fix:" in text
    assert "unknown rule code" in explain("NOPE")


def test_syntax_error_is_lnt00():
    assert codes("def broken(:\n") == ["LNT00"]


# ---------------------------------------------------------- self-run gate

def test_self_run_is_clean():
    """The linter must pass over the repo's own src/ tree: the rules ARE
    the contract, so src carries zero unsuppressed findings."""
    root = Path(__file__).resolve().parents[1]
    findings = lint_paths([str(root / "src")])
    assert findings == [], "\n".join(f.render() for f in findings)


# --------------------------------------------------------------- sanitizer

_SPEC = accel.ExecSpec(backend="bpbs", ba=2, bx=2)


def test_sanitize_scope_activation():
    outer = active()           # None, or the suite-level --sanitize scope
    with sanitize() as san:
        assert active() is san
        assert san is not outer
    assert active() is outer


def test_sanitize_nan_input_trips():
    x = jnp.ones((4, 8)).at[0, 0].set(jnp.nan)
    w = jnp.ones((8, 16)) * 0.1
    with pytest.raises(SanitizeError, match="non-finite"):
        with sanitize():
            accel.matmul(x, w, _SPEC)


def test_sanitize_host_sync_guard():
    with pytest.raises(SanitizeError, match="host_sync"):
        with sanitize():
            host_sync(jnp.array([1.0, jnp.inf]), reason="fixture")
    if active() is None:
        # outside every scope host_sync is a plain pull
        out = host_sync(jnp.array([1.0, jnp.inf]), reason="fixture")
        assert np.isinf(out[1])
    else:
        # the suite-level --sanitize scope must catch it too
        with pytest.raises(SanitizeError, match="host_sync"):
            host_sync(jnp.array([1.0, jnp.inf]), reason="fixture")


def test_sanitize_clean_dispatch_counts():
    x = jnp.ones((4, 8)) * 0.25
    w = jnp.ones((8, 16)) * 0.1
    with sanitize() as san:
        accel.matmul(x, w, _SPEC)
    assert san.stats.dispatches == 1
    assert san.stats.finite_checks == 3     # input, weight, output
    assert san.stats.adc_conversions > 0


def test_sanitize_saturation_counter_and_limit():
    # large inputs on a 1-b spec pin the charge-share range to the top
    # code: the counter sees it, and an opted-in limit fails the scope
    x = jnp.ones((4, 8)) * 3.0
    w = jnp.ones((8, 16))
    spec = accel.ExecSpec(backend="bpbs", ba=1, bx=1)
    with sanitize() as san:
        accel.matmul(x, w, spec)
    assert san.stats.adc_saturated > 0
    with pytest.raises(SanitizeError, match="saturation rate"):
        with sanitize(adc_saturation_limit=0.01):
            accel.matmul(x, w, spec)


def test_sanitize_allocator_leak_audit():
    alloc = BlockAllocator(num_blocks=8)
    held = alloc.alloc(3)
    with pytest.raises(SanitizeError, match="leaked 3 block"):
        with sanitize() as san:
            san.audit_allocator(alloc, "fixture shutdown")
    alloc.free(held)
    with sanitize() as san:
        san.audit_allocator(alloc, "fixture shutdown")
    assert san.stats.allocator_audits == 1   # fresh stats per scope


def test_sanitize_vdd_corner():
    with pytest.raises(SanitizeError, match="not a modeled supply corner"):
        with sanitize(vdd=0.7):
            pass
    x = jnp.ones((4, 8)) * 0.25
    w = jnp.ones((8, 16)) * 0.1
    with sanitize(vdd=0.85) as san:
        accel.matmul(x, w, _SPEC)        # sigma 0.0 < the 0.85V corner
    assert san.stats.corner_mismatches == 1


def test_sanitize_require_noise_key():
    noisy = accel.ExecSpec(backend="bpbs", ba=2, bx=2, adc_sigma_lsb=0.3)
    x = jnp.ones((4, 8)) * 0.25
    w = jnp.ones((8, 16)) * 0.1
    with pytest.raises(SanitizeError, match="no noise key"):
        with sanitize(require_noise_key=True):
            accel.matmul(x, w, noisy)
    with sanitize(require_noise_key=True):
        with accel.adc_noise(jax.random.PRNGKey(0)):
            accel.matmul(x, w, noisy)


def test_sanitize_survives_jit():
    # inside an active trace the checks must neither stage jnp ops nor
    # raise on tracers; closure constants are still checked eagerly
    x = jnp.ones((4, 8)) * 0.25
    w = jnp.ones((8, 16)) * 0.1
    with sanitize() as san:
        f = jax.jit(lambda x: accel.matmul(x, w, _SPEC))
        f(x).block_until_ready()
    assert san.stats.dispatches == 1
