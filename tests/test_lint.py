"""mxlint (mxnet_tpu.tools.lint): per-rule positive/negative fixture
snippets, suppression-comment and baseline mechanics, the JSON output
schema, and — as the tier-1 gate — the tree-wide run that must report
ZERO non-baselined violations inside its wall-time budget."""
import json
import textwrap
import time

import pytest

from mxnet_tpu.tools.lint import (RULES, lint_paths, lint_source,
                                  rule_names)
from mxnet_tpu.tools.lint.core import load_baseline


def run(src, path="mxnet_tpu/somemodule.py", rules=None):
    """Lint a dedented snippet; returns the list of rule names hit."""
    vs = lint_source(textwrap.dedent(src), path, rules=rules)
    return [v.rule for v in vs]


def test_rule_registry_complete():
    import mxnet_tpu.tools.lint.rules  # noqa: F401
    assert set(rule_names()) == {
        "jit-staging", "atomic-write", "counter-lock",
        "thread-hygiene", "traced-purity", "env-registry"}
    for name, fn in RULES.items():
        assert fn.rule_doc, name


# ---------------------------------------------------------------------------
# jit-staging
# ---------------------------------------------------------------------------

class TestJitStaging:
    def test_raw_jax_jit_flagged(self):
        assert run("""
            import jax
            def f(x):
                return x
            g = jax.jit(f)
        """) == ["jit-staging"]

    def test_from_import_and_alias_flagged(self):
        assert "jit-staging" in run("""
            from jax import jit
            g = jit(lambda x: x)
        """)
        assert "jit-staging" in run("""
            import jax as J
            g = J.jit(lambda x: x)
        """)

    def test_compile_watch_jit_is_clean(self):
        assert run("""
            from mxnet_tpu import compile_watch
            def f(x):
                return x
            g = compile_watch.jit(f, "site:f")
        """) == []

    def test_choke_point_file_exempt(self):
        assert run("""
            import jax
            g = jax.jit(lambda x: x)
        """, path="mxnet_tpu/compile_watch.py") == []

    def test_allowlisted_file_exempt_with_rationale(self):
        # deploy.py is the shipped allowlist entry (export-only path)
        assert run("""
            import jax
            g = jax.jit(lambda x: x)
        """, path="mxnet_tpu/deploy.py") == []
        from mxnet_tpu.tools.lint.rules import load_jit_allowlist
        allow = load_jit_allowlist()
        assert "mxnet_tpu/deploy.py" in allow
        for rationale in allow.values():
            assert len(rationale.strip()) > 10

    def test_unrelated_jit_attribute_clean(self):
        assert run("""
            import torch
            g = torch.jit(lambda x: x)
        """) == []


# ---------------------------------------------------------------------------
# atomic-write
# ---------------------------------------------------------------------------

class TestAtomicWrite:
    def test_bare_write_flagged(self):
        assert run("""
            def save(path, payload):
                with open(path, "wb") as f:
                    f.write(payload)
        """) == ["atomic-write"]

    def test_tmp_plus_replace_clean(self):
        assert run("""
            import os
            def save(path, payload):
                tmp = path + ".tmp"
                with open(tmp, "wb") as f:
                    f.write(payload)
                os.replace(tmp, path)
        """) == []

    def test_append_and_read_clean(self):
        assert run("""
            def log(path, line):
                with open(path, "a") as f:
                    f.write(line)
            def load(path):
                with open(path) as f:
                    return f.read()
        """) == []

    def test_mode_keyword_flagged(self):
        assert run("""
            def save(path, s):
                with open(path, mode="w") as f:
                    f.write(s)
        """) == ["atomic-write"]


# ---------------------------------------------------------------------------
# counter-lock
# ---------------------------------------------------------------------------

_CTR_PATH = "mxnet_tpu/telemetry.py"     # a configured counter module


class TestCounterLock:
    def test_unlocked_bump_flagged(self):
        assert run("""
            def tick(w):
                w.hits += 1
        """, path=_CTR_PATH) == ["counter-lock"]

    def test_bump_under_lock_clean(self):
        assert run("""
            import threading
            _lock = threading.Lock()
            def tick(w):
                with _lock:
                    w.hits += 1
        """, path=_CTR_PATH) == []

    def test_locked_suffix_convention_clean(self):
        assert run("""
            def tick_locked(w):
                w.hits += 1
        """, path=_CTR_PATH) == []

    def test_constructor_init_clean(self):
        assert run("""
            class W:
                def __init__(self):
                    self.hits = 0
        """, path=_CTR_PATH) == []

    def test_counters_dict_write_flagged(self):
        assert run("""
            _state = {"counters": {}}
            def bump(name):
                _state["counters"][name] = \\
                    _state["counters"].get(name, 0) + 1
        """, path="mxnet_tpu/profiler.py") == ["counter-lock"]

    def test_outside_counter_modules_clean(self):
        assert run("""
            def tick(w):
                w.hits += 1
        """, path="mxnet_tpu/ndarray/ndarray.py") == []

    def test_lock_in_caller_does_not_leak_into_nested_def(self):
        assert run("""
            import threading
            _lock = threading.Lock()
            def outer(w):
                with _lock:
                    def worker():
                        w.hits += 1
                    return worker
        """, path=_CTR_PATH) == ["counter-lock"]


# ---------------------------------------------------------------------------
# thread-hygiene
# ---------------------------------------------------------------------------

_PIPE_PATH = "mxnet_tpu/io/pipeline.py"


class TestThreadHygiene:
    def test_non_daemon_thread_flagged(self):
        assert run("""
            import threading
            def go(fn):
                t = threading.Thread(target=fn)
                t.start()
        """) == ["thread-hygiene"]

    def test_daemon_thread_clean(self):
        assert run("""
            import threading
            def go(fn):
                t = threading.Thread(target=fn, daemon=True)
                t.start()
        """) == []

    def test_unbounded_queue_in_pipeline_module_flagged(self):
        assert run("""
            import queue
            def make():
                return queue.Queue()
        """, path=_PIPE_PATH) == ["thread-hygiene"]

    def test_bounded_queue_clean(self):
        assert run("""
            import queue
            def make(depth):
                return queue.Queue(maxsize=depth)
        """, path=_PIPE_PATH) == []

    def test_unbounded_queue_outside_pipeline_modules_clean(self):
        assert run("""
            import queue
            q = queue.Queue()
        """, path="mxnet_tpu/somemodule.py") == []


# ---------------------------------------------------------------------------
# traced-purity
# ---------------------------------------------------------------------------

class TestTracedPurity:
    def test_time_in_jitted_fn_flagged(self):
        assert run("""
            import jax
            import time
            def step(x):
                return x * time.time()
            f = jax.jit(step)
        """, rules=["traced-purity"]) == ["traced-purity"]

    def test_np_random_in_jitted_fn_flagged(self):
        assert run("""
            import jax
            import numpy as np
            def step(x):
                return x + np.random.rand()
            f = jax.jit(step)
        """, rules=["traced-purity"]) == ["traced-purity"]

    def test_global_mutation_flagged(self):
        assert run("""
            import jax
            _n = 0
            def step(x):
                global _n
                _n += 1
                return x
            f = jax.jit(step)
        """, rules=["traced-purity"]) == ["traced-purity"]

    def test_pure_jitted_fn_clean(self):
        assert run("""
            import jax
            import jax.numpy as jnp
            def step(x, t):
                return jnp.sin(x) * t
            f = jax.jit(step)
        """, rules=["traced-purity"]) == []

    def test_impurity_outside_traced_fn_clean(self):
        assert run("""
            import time
            def host_loop():
                return time.time()
        """, rules=["traced-purity"]) == []

    def test_staged_compile_watch_fn_checked_too(self):
        assert run("""
            import time
            from mxnet_tpu import compile_watch
            def step(x):
                return x * time.time()
            f = compile_watch.jit(step, "site:step")
        """, rules=["traced-purity"]) == ["traced-purity"]

    def test_fused_step_fn_inner_checked(self):
        assert run("""
            import time
            class SGD:
                def fused_step_fn(self):
                    def update(p, g):
                        return p - g * time.time()
                    return update
        """, rules=["traced-purity"]) == ["traced-purity"]


# ---------------------------------------------------------------------------
# env-registry
# ---------------------------------------------------------------------------

class TestEnvRegistry:
    def test_environ_get_flagged(self):
        assert run("""
            import os
            v = os.environ.get("MXNET_FOO", "")
        """, rules=["env-registry"]) == ["env-registry"]

    def test_environ_subscript_and_getenv_flagged(self):
        assert run("""
            import os
            a = os.environ["MXNET_FOO"]
            b = os.getenv("MXNET_BAR")
        """, rules=["env-registry"]) == ["env-registry",
                                         "env-registry"]

    def test_legacy_get_env_flagged(self):
        assert run("""
            from mxnet_tpu.base import get_env
            v = get_env("MXNET_FOO", 1, int)
        """, rules=["env-registry"]) == ["env-registry"]

    def test_envs_accessor_clean(self):
        assert run("""
            from mxnet_tpu import envs
            v = envs.get_int("MXNET_TELEMETRY_RING")
        """, rules=["env-registry"]) == []

    def test_undeclared_name_through_envs_flagged(self):
        assert run("""
            from mxnet_tpu import envs
            v = envs.get_int("MXNET_DEFINITELY_NOT_DECLARED")
        """, rules=["env-registry"]) == ["env-registry"]

    def test_non_mxnet_env_reads_clean(self):
        assert run("""
            import os
            v = os.environ.get("JAX_PLATFORMS", "")
        """, rules=["env-registry"]) == []

    def test_registry_file_itself_exempt(self):
        assert run("""
            import os
            v = os.environ.get("MXNET_FOO")
        """, path="mxnet_tpu/envs.py") == []


# ---------------------------------------------------------------------------
# suppression + baseline mechanics
# ---------------------------------------------------------------------------

class TestSuppression:
    def test_inline_disable_suppresses_only_that_rule(self):
        src = textwrap.dedent("""
            import jax
            g = jax.jit(lambda x: x)  # mxlint: disable=jit-staging
        """)
        assert lint_source(src, "mxnet_tpu/m.py") == []
        # a different rule name does NOT suppress it
        src2 = src.replace("jit-staging", "atomic-write")
        assert [v.rule for v in lint_source(src2, "mxnet_tpu/m.py")] \
            == ["jit-staging"]

    def test_file_level_disable(self):
        src = textwrap.dedent("""
            # mxlint: disable-file=jit-staging
            import jax
            a = jax.jit(lambda x: x)
            b = jax.jit(lambda x: x)
        """)
        assert lint_source(src, "mxnet_tpu/m.py") == []

    def test_suppressed_findings_are_counted(self):
        collected = []
        src = textwrap.dedent("""
            import jax
            g = jax.jit(lambda x: x)  # mxlint: disable=jit-staging
        """)
        lint_source(src, "mxnet_tpu/m.py",
                    count_suppressed=collected)
        assert [v.rule for v in collected] == ["jit-staging"]


class TestBaseline:
    def _violating_file(self, tmp_path):
        f = tmp_path / "mxnet_tpu" / "baselined_mod.py"
        f.parent.mkdir()
        f.write_text("import jax\ng = jax.jit(lambda x: x)\n")
        return f

    def test_baselined_violation_absorbed(self, tmp_path):
        f = self._violating_file(tmp_path)
        entry = {"rule": "jit-staging",
                 "path": "mxnet_tpu/baselined_mod.py",
                 "context": "g = jax.jit(lambda x: x)",
                 "rationale": "fixture: grandfathered on purpose"}
        res = lint_paths([str(f)], baseline=[entry])
        assert res.ok
        assert [v.rule for v in res.baselined] == ["jit-staging"]
        assert res.stale_baseline == []

    def test_non_baselined_violation_fails(self, tmp_path):
        f = self._violating_file(tmp_path)
        res = lint_paths([str(f)], baseline=[])
        assert not res.ok
        assert [v.rule for v in res.violations] == ["jit-staging"]

    def test_stale_entry_reported(self, tmp_path):
        f = tmp_path / "mxnet_tpu" / "clean_mod.py"
        f.parent.mkdir()
        f.write_text("x = 1\n")
        entry = {"rule": "jit-staging",
                 "path": "mxnet_tpu/clean_mod.py",
                 "context": "gone = jax.jit(f)",
                 "rationale": "fixture"}
        res = lint_paths([str(f)], baseline=[entry])
        assert res.ok and len(res.stale_baseline) == 1

    def test_baseline_entry_requires_rationale(self, tmp_path):
        bad = tmp_path / "baseline.json"
        bad.write_text(json.dumps({"entries": [
            {"rule": "jit-staging", "path": "mxnet_tpu/x.py",
             "context": "y"}]}))
        with pytest.raises(ValueError, match="rationale"):
            load_baseline(str(bad))

    def test_shipped_baseline_loads_and_is_near_empty(self):
        entries = load_baseline()
        assert len(entries) <= 3
        for e in entries:
            assert e["rationale"].strip()


# ---------------------------------------------------------------------------
# output schema + CLI
# ---------------------------------------------------------------------------

class TestOutput:
    def test_json_schema(self, tmp_path):
        f = tmp_path / "mxnet_tpu" / "m.py"
        f.parent.mkdir()
        f.write_text("import jax\ng = jax.jit(lambda x: x)\n")
        d = lint_paths([str(f)], baseline=[]).to_dict()
        assert d["version"] == 1
        assert d["ok"] is False and d["files"] == 1
        assert d["counts"] == {"jit-staging": 1}
        (v,) = d["violations"]
        assert set(v) == {"rule", "path", "line", "col", "message",
                          "context"}
        assert v["path"] == "mxnet_tpu/m.py" and v["line"] == 2
        assert isinstance(d["elapsed_s"], float)
        json.dumps(d)                      # round-trips

    def test_cli_main_exit_codes(self, tmp_path, capsys):
        from mxnet_tpu.tools.lint.__main__ import main
        bad = tmp_path / "mxnet_tpu" / "m.py"
        bad.parent.mkdir()
        bad.write_text("import jax\ng = jax.jit(lambda x: x)\n")
        assert main([str(bad), "--no-baseline"]) == 1
        out = capsys.readouterr().out
        assert "jit-staging" in out
        good = tmp_path / "mxnet_tpu" / "ok.py"
        good.write_text("x = 1\n")
        assert main([str(good)]) == 0
        assert main(["--list-rules"]) == 0
        assert "jit-staging" in capsys.readouterr().out

    def test_cli_envs_reference(self, capsys):
        from mxnet_tpu.tools.lint.__main__ import main
        assert main(["--envs"]) == 0
        out = capsys.readouterr().out
        assert "MXNET_TELEMETRY_RING" in out
        assert "MXNET_COMPILE_WATCH" in out
        assert out.count("MXNET_") >= 50

    def test_parse_error_is_a_finding_not_a_crash(self, tmp_path):
        f = tmp_path / "mxnet_tpu" / "broken.py"
        f.parent.mkdir()
        f.write_text("def broken(:\n")
        res = lint_paths([str(f)], baseline=[])
        assert [v.rule for v in res.violations] == ["parse-error"]


# ---------------------------------------------------------------------------
# the tier-1 gate: the tree itself is clean, and fast
# ---------------------------------------------------------------------------

class TestTreeWide:
    def test_tree_has_zero_non_baselined_violations(self):
        t0 = time.perf_counter()
        res = lint_paths()
        wall = time.perf_counter() - t0
        assert res.ok, (
            "mxlint found non-baselined violations — fix them, "
            "suppress with a rationale, or baseline them:\n%s"
            % "\n".join(repr(v) for v in res.violations))
        assert not res.stale_baseline, (
            "stale baseline entries (the violation is gone — delete "
            "them): %r" % res.stale_baseline)
        assert res.files > 150       # the whole package was walked
        # the wall-time budget that keeps this gate tier-1-cheap; a
        # quadratic rule would blow straight through it
        assert wall < 10.0, "tree-wide lint took %.1fs" % wall

    def test_tools_stragglers_lint_clean_and_importable(self):
        # the pre-rewrite reference-era stragglers are held to the
        # same bar as the rest of the tree
        import importlib
        for mod in ("mxnet_tpu.tools.flakiness_checker",
                    "mxnet_tpu.tools.launch"):
            importlib.import_module(mod)
        from mxnet_tpu.tools.lint.core import package_root
        import os
        res = lint_paths([os.path.join(package_root(), "tools")])
        assert res.ok, res.violations


class TestJitStagingDecorators:
    # code-review finding: the bare/partial decorator idioms must not
    # bypass the gate
    def test_bare_decorator_flagged(self):
        assert run("""
            import jax
            @jax.jit
            def step(x):
                return x
        """) == ["jit-staging"]

    def test_partial_decorator_flagged(self):
        assert run("""
            from functools import partial
            import jax
            @partial(jax.jit, static_argnums=(1,))
            def step(x, n):
                return x * n
        """) == ["jit-staging"]

    def test_jit_call_decorator_flagged_once(self):
        assert run("""
            from jax import jit
            @jit
            def step(x):
                return x
        """) == ["jit-staging"]

    def test_unrelated_decorator_clean(self):
        assert run("""
            from functools import lru_cache
            @lru_cache(maxsize=8)
            def fib(n):
                return n
        """) == []


def test_relative_envs_import_is_seen_by_env_registry():
    # the tree's actual idiom is `from . import envs` — the undeclared
    # -name check must fire for it exactly as for the absolute form
    assert run("""
        from . import envs
        v = envs.get_int("MXNET_DEFINITELY_NOT_DECLARED")
    """, rules=["env-registry"]) == ["env-registry"]
    assert run("""
        from .. import envs
        v = envs.get_int("MXNET_TELEMETRY_RING")
    """, rules=["env-registry"]) == []
