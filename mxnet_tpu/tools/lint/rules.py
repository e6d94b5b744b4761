"""The mxlint rules — each encodes one convention a real bug paid for.

Every rule is AST-based (no regex-over-source except comment
handling), individually suppressible with ``# mxlint: disable=<rule>``
and baselinable with a written rationale.  False-positive philosophy:
a rule may be conservative (miss exotic constructions) but must not be
noisy — a finding the tree cannot fix or baseline honestly is a bug in
the rule, not the tree.
"""
from __future__ import annotations

import ast
import json
import os
import re

from .core import rule

# ---------------------------------------------------------------------------
# jit-staging: no raw jax.jit outside compile_watch.py
# ---------------------------------------------------------------------------

_JIT_EXEMPT_FILES = (
    # the staging choke point itself: its jax.jit twin IS the rule's
    # blessed destination
    "mxnet_tpu/compile_watch.py",
)


def _jit_allowlist_path():
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "jit_allowlist.json")


_JIT_ALLOWLIST_CACHE = None


def load_jit_allowlist():
    """Per-file allowlist for sites where staging is genuinely WRONG
    (not merely unmigrated) — each entry documents why.  Cached: the
    tree-wide run consults it once per file."""
    global _JIT_ALLOWLIST_CACHE
    if _JIT_ALLOWLIST_CACHE is not None:
        return _JIT_ALLOWLIST_CACHE
    path = _jit_allowlist_path()
    if not os.path.exists(path):
        _JIT_ALLOWLIST_CACHE = {}
        return _JIT_ALLOWLIST_CACHE
    with open(path) as f:
        data = json.load(f)
    out = {}
    for e in data.get("entries", []):
        if not str(e.get("rationale", "")).strip():
            raise ValueError(
                "jit_allowlist.json: entry %r has no rationale" % e)
        out[e["path"]] = e["rationale"]
    _JIT_ALLOWLIST_CACHE = out
    return out


@rule("jit-staging",
      "every jax.jit stages through compile_watch.jit (compile "
      "telemetry, storm detection)")
def check_jit_staging(ctx):
    if ctx.relpath in _JIT_EXEMPT_FILES:
        return
    allow = load_jit_allowlist()
    if ctx.relpath in allow:
        return
    al = ctx.aliases

    def is_raw_jit(expr):
        """True when ``expr`` references jax's jit: ``jax.jit`` /
        an alias / ``from jax import jit``."""
        if isinstance(expr, ast.Attribute) and expr.attr == "jit" \
                and isinstance(expr.value, ast.Name) \
                and al.module_is(expr.value.id, "jax"):
            return True
        return isinstance(expr, ast.Name) \
            and al.name_is(expr.id, "jax", "jit")

    msg = ("raw jax.jit — stage through compile_watch.jit("
           "fn, site=...) so this program joins compile "
           "telemetry and storm detection (or add a "
           "jit_allowlist.json entry with a rationale)")
    # decorator forms: @jax.jit / @jit / @partial(jax.jit, ...) —
    # the most common jit idiom must not bypass the gate
    dec_calls = set()
    for node in ctx.nodes:
        if not isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
            continue
        for dec in node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            args = dec.args if isinstance(dec, ast.Call) else []
            if isinstance(dec, ast.Call):
                dec_calls.add(id(dec))       # no double report below
            if is_raw_jit(target) or any(is_raw_jit(a)
                                         for a in args):
                yield ctx.violation("jit-staging", dec, msg)
    for node in ctx.nodes:
        if not isinstance(node, ast.Call) or id(node) in dec_calls:
            continue
        if is_raw_jit(node.func):
            yield ctx.violation("jit-staging", node, msg)


# ---------------------------------------------------------------------------
# atomic-write: durable writes go tmp + os.replace
# ---------------------------------------------------------------------------

_WRITE_MODES = re.compile(r"^[wx]b?\+?$")


def _open_mode(call):
    """The mode string of an ``open`` call, or None when dynamic."""
    mode = None
    if len(call.args) >= 2:
        mode = call.args[1]
    for kw in call.keywords:
        if kw.arg == "mode":
            mode = kw.value
    if mode is None:
        return "r"
    if isinstance(mode, ast.Constant) and isinstance(mode.value, str):
        return mode.value
    return None


def _scope_calls_os_replace(ctx, node):
    """True when the enclosing function (or module body, for
    module-level writes) also calls ``os.replace``/``os.rename`` —
    the write-then-rename discipline in one scope."""
    scope = ctx.enclosing_function(node) or ctx.tree
    for sub in ast.walk(scope):
        if isinstance(sub, ast.Call):
            base, attr = ctx.call_name(sub)
            if attr in ("replace", "rename") and base is not None \
                    and ctx.aliases.module_is(base, "os"):
                return True
    return False


@rule("atomic-write",
      "no bare open(..., 'w'/'wb') of durable files — write tmp then "
      "os.replace (a preempted save must leave the old file intact)")
def check_atomic_write(ctx):
    for node in ctx.nodes:
        if not isinstance(node, ast.Call):
            continue
        base, attr = ctx.call_name(node)
        if attr != "open" or base is not None:
            continue
        mode = _open_mode(node)
        if mode is None or not _WRITE_MODES.match(mode):
            continue                     # reads, appends, dynamic
        if _scope_calls_os_replace(ctx, node):
            continue
        yield ctx.violation(
            "atomic-write", node,
            "bare open(..., %r) write without os.replace in scope — "
            "write to a tmp name and os.replace() it (see "
            "base.atomic_write_bytes)" % mode)


# ---------------------------------------------------------------------------
# counter-lock: telemetry/profiler counter bumps hold their lock
# ---------------------------------------------------------------------------

# the shared-counter attribute names of the observability stack; a
# += / -= on one of these OUTSIDE a with-lock is exactly the PR 3
# racy-counter bug shape.  Bare local names are never flagged.
_COUNTER_ATTRS = frozenset({
    "compile_count", "compile_total_s", "degraded", "dispatches",
    "step_flops", "step_bytes", "step_dispatches", "step_compiles",
    "step_compile_s", "total_flops", "total_bytes", "hits", "misses",
    "errors", "saves", "failures", "records_dropped", "dropped",
    "steps", "samples",
})

# dict containers whose item-writes count as counter mutations
_COUNTER_SUBSCRIPTS = ("counters", "aggregate")

_LOCKISH = re.compile(r"lock|_mu\b|mutex|cond", re.IGNORECASE)

# modules where the counter conventions apply (the observability
# stack + its writers); elsewhere ad-hoc counters are local state
_COUNTER_MODULES = (
    "mxnet_tpu/profiler.py", "mxnet_tpu/telemetry.py",
    "mxnet_tpu/compile_watch.py", "mxnet_tpu/livemetrics.py", "mxnet_tpu/tracing.py",
    "mxnet_tpu/checkpoint.py", "mxnet_tpu/serving/",
    "mxnet_tpu/bucketing/record.py",
)


def _counter_target(node):
    """The flagged component name when ``node`` (an assignment
    target) mutates shared counter state, else None."""
    if isinstance(node, ast.Attribute):
        if node.attr in _COUNTER_ATTRS:
            return node.attr
    if isinstance(node, ast.Subscript):
        # _state["counters"][name] = ... / ["aggregate"] writes
        inner = node.value
        if isinstance(inner, ast.Subscript) and \
                isinstance(inner.slice, ast.Constant) and \
                inner.slice.value in _COUNTER_SUBSCRIPTS:
            return '["%s"]' % inner.slice.value
    return None


@rule("counter-lock",
      "observability counter mutations (+=) hold their designated "
      "lock — racy counters were PR 3's bug")
def check_counter_lock(ctx):
    if not any(ctx.relpath.startswith(m) or ctx.relpath == m
               for m in _COUNTER_MODULES):
        return
    for node in ctx.nodes:
        if isinstance(node, ast.AugAssign):
            name = _counter_target(node.target)
        elif isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Subscript):
            name = _counter_target(node.targets[0])
        else:
            continue
        if name is None:
            continue
        fn = ctx.enclosing_function(node)
        if fn is None and not isinstance(
                ctx.parents.get(node), (ast.With, ast.AsyncWith)):
            continue                 # module-level init, not mutation
        if fn is not None and fn.name in ("__init__",):
            continue                 # constructor: no concurrent view
        if fn is not None and fn.name.endswith("_locked"):
            # the tree's caller-holds-the-lock convention: the
            # ``_locked`` suffix IS the contract (and the rule checks
            # every caller site takes a lock around such calls is out
            # of scope for a lexical pass)
            continue
        if ctx.under_with_matching(node, _LOCKISH):
            continue
        yield ctx.violation(
            "counter-lock", node,
            "counter %s mutated outside a with-lock block — take "
            "the module/object lock (or suppress with a rationale "
            "if the caller provably holds it)" % name)


# ---------------------------------------------------------------------------
# thread-hygiene: daemon-or-drained threads, bounded queues
# ---------------------------------------------------------------------------

_PIPELINE_MODULES = (
    "mxnet_tpu/io/", "mxnet_tpu/serving/", "mxnet_tpu/checkpoint.py",
    "mxnet_tpu/bucketing/",
    "mxnet_tpu/kvstore_server.py", "mxnet_tpu/livemetrics.py",
)


def _kw(call, name):
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


@rule("thread-hygiene",
      "threading.Thread sites are daemon=True (or suppressed with "
      "their join/drain path named); queue.Queue() in pipeline/"
      "writer modules declares a maxsize (bounded backpressure)")
def check_thread_hygiene(ctx):
    al = ctx.aliases
    for node in ctx.nodes:
        if not isinstance(node, ast.Call):
            continue
        base, attr = ctx.call_name(node)
        # Thread(...) without daemon=True
        is_thread = (attr == "Thread" and (
            (base is not None and al.module_is(base, "threading"))
            or (base is None and al.name_is(attr, "threading",
                                            "Thread"))))
        if is_thread:
            daemon = _kw(node, "daemon")
            if not (isinstance(daemon, ast.Constant)
                    and daemon.value is True):
                yield ctx.violation(
                    "thread-hygiene", node,
                    "threading.Thread without daemon=True — a "
                    "non-daemon worker must be suppressed here with "
                    "a comment naming its join/drain path (PR 4's "
                    "blocking-put leak)")
            continue
        # unbounded queue.Queue() in pipeline/writer modules
        if not any(ctx.relpath.startswith(m) for m in
                   _PIPELINE_MODULES):
            continue
        is_queue = (attr in ("Queue", "LifoQueue",
                             "PriorityQueue") and (
            (base is not None and al.module_is(base, "queue"))
            or (base is None and al.name_is(attr, "queue", attr))))
        if is_queue:
            size = node.args[0] if node.args else _kw(node, "maxsize")
            unbounded = size is None or (
                isinstance(size, ast.Constant) and
                not size.value)
            if unbounded:
                yield ctx.violation(
                    "thread-hygiene", node,
                    "queue.Queue() without maxsize in a pipeline/"
                    "writer module — unbounded queues hide "
                    "backpressure until the host OOMs; bound it or "
                    "suppress naming the upstream bound")


# ---------------------------------------------------------------------------
# traced-purity: no host impurities inside functions handed to jit
# ---------------------------------------------------------------------------

_IMPURE_TIME = ("time", "perf_counter", "monotonic", "time_ns",
                "process_time")


def _collect_traced_functions(ctx):
    """FunctionDefs that become traced programs: (a) passed by name
    as the first argument to any ``*jit(...)`` call in the same file,
    (b) decorated with ``@jit``/``@jax.jit``/``@partial(jit, ...)``,
    (c) nested inside a function named ``fused_step_fn`` (the fused
    optimizer-update roster) and returned from it."""
    defs = {}
    for node in ctx.nodes:
        if isinstance(node, ast.FunctionDef):
            defs.setdefault(node.name, []).append(node)
    traced = []
    for node in ctx.nodes:
        if isinstance(node, ast.Call):
            _, attr = ctx.call_name(node)
            if attr == "jit" and node.args and \
                    isinstance(node.args[0], ast.Name):
                # closest preceding def wins (shadowing is rare and
                # per-scope matching would cost more than it buys)
                for cand in defs.get(node.args[0].id, ()):
                    traced.append(cand)
        if isinstance(node, ast.FunctionDef):
            for dec in node.decorator_list:
                d = dec
                if isinstance(d, ast.Call):
                    if d.args and isinstance(d.args[0], (ast.Name,
                                                         ast.Attribute)):
                        first = d.args[0]
                        if (isinstance(first, ast.Name)
                                and first.id == "jit") or \
                           (isinstance(first, ast.Attribute)
                                and first.attr == "jit"):
                            traced.append(node)
                            break
                    d = d.func
                if (isinstance(d, ast.Name) and d.id == "jit") or \
                        (isinstance(d, ast.Attribute)
                         and d.attr == "jit"):
                    traced.append(node)
                    break
            if node.name == "fused_step_fn" or \
                    node.name.startswith("fused_step_fn"):
                for sub in ast.walk(node):
                    if isinstance(sub, ast.FunctionDef) and sub is not node:
                        traced.append(sub)
    return traced


@rule("traced-purity",
      "no time.time()/np.random/global mutation/os.environ inside "
      "functions handed to jit or fused_step_fn — host impurities "
      "silently bake into the compiled program as constants")
def check_traced_purity(ctx):
    al = ctx.aliases
    seen = set()
    for fn in _collect_traced_functions(ctx):
        if id(fn) in seen:
            continue
        seen.add(id(fn))
        for node in ast.walk(fn):
            if isinstance(node, ast.Global):
                yield ctx.violation(
                    "traced-purity", node,
                    "global statement inside traced function %r — "
                    "the mutation runs at TRACE time only, then "
                    "never again" % fn.name)
            if not isinstance(node, ast.Call):
                continue
            # np.random.<fn>(...) — callee is Attribute whose value
            # is Attribute(random) on a numpy alias (checked before
            # the two-component fast path below, which cannot see it)
            f = node.func
            if isinstance(f, ast.Attribute) and \
                    isinstance(f.value, ast.Attribute) and \
                    f.value.attr == "random" and \
                    isinstance(f.value.value, ast.Name) and \
                    (al.module_is(f.value.value.id, "numpy")
                     or f.value.value.id in ("np", "numpy", "_np")):
                yield ctx.violation(
                    "traced-purity", node,
                    "np.random.%s inside traced function %r is "
                    "sampled once at trace time and frozen into the "
                    "program — use jax.random with a threaded key"
                    % (f.attr, fn.name))
                continue
            if isinstance(f, ast.Attribute) and f.attr == "get" and \
                    isinstance(f.value, ast.Attribute) and \
                    f.value.attr == "environ":
                yield ctx.violation(
                    "traced-purity", node,
                    "os.environ read inside traced function %r is "
                    "evaluated at trace time only" % fn.name)
                continue
            base, attr = ctx.call_name(node)
            if base is None:
                continue
            if al.module_is(base, "time") and attr in _IMPURE_TIME:
                yield ctx.violation(
                    "traced-purity", node,
                    "time.%s() inside traced function %r bakes the "
                    "trace-time clock into the compiled program as "
                    "a constant — pass it in as an argument"
                    % (attr, fn.name))
            elif (al.module_is(base, "random")
                  and attr in ("random", "randint", "uniform",
                               "randrange", "choice", "shuffle",
                               "gauss", "normalvariate")):
                yield ctx.violation(
                    "traced-purity", node,
                    "python random.%s() inside traced function %r "
                    "is drawn once at trace time — thread a jax PRNG "
                    "key instead" % (attr, fn.name))


# ---------------------------------------------------------------------------
# env-registry: MXNET_* reads go through mxnet_tpu.envs
# ---------------------------------------------------------------------------

_ENV_EXEMPT_FILES = (
    "mxnet_tpu/envs.py",            # the registry reads os.environ
    "mxnet_tpu/tools/lint/",        # this package (fixture strings)
)


def _mxnet_const(node):
    if isinstance(node, ast.Constant) and isinstance(node.value, str) \
            and node.value.startswith("MXNET_"):
        return node.value
    return None


@rule("env-registry",
      "every MXNET_* read goes through the typed mxnet_tpu.envs "
      "registry (declared default + doc, MXNetError naming the "
      "variable on a malformed value)")
def check_env_registry(ctx):
    if any(ctx.relpath == m or ctx.relpath.startswith(m)
           for m in _ENV_EXEMPT_FILES):
        return
    # lazily import the registry for the declared-name check; the
    # lint must still run (minus that check) if envs cannot import
    try:
        from ... import envs as _envs
        declared = set(_envs.registry())
    except Exception:
        declared = None
    al = ctx.aliases
    for node in ctx.nodes:
        # os.environ["MXNET_X"] loads
        if isinstance(node, ast.Subscript):
            v = node.value
            if isinstance(v, ast.Attribute) and v.attr == "environ":
                name = _mxnet_const(node.slice)
                if name:
                    yield ctx.violation(
                        "env-registry", node,
                        "os.environ[%r] — read it through "
                        "mxnet_tpu.envs accessors" % name)
            continue
        if not isinstance(node, ast.Call):
            continue
        base, attr = ctx.call_name(node)
        name = _mxnet_const(node.args[0]) if node.args else None
        if name is None:
            continue
        # os.environ.get("MXNET_X") / environ.get(...)
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr == "get" and (
                (isinstance(f.value, ast.Attribute)
                 and f.value.attr == "environ")
                or (isinstance(f.value, ast.Name)
                    and al.name_is(f.value.id, "os", "environ"))):
            yield ctx.violation(
                "env-registry", node,
                "os.environ.get(%r) — read it through "
                "mxnet_tpu.envs accessors" % name)
            continue
        # os.getenv("MXNET_X")
        if attr == "getenv" and base is not None \
                and al.module_is(base, "os"):
            yield ctx.violation(
                "env-registry", node,
                "os.getenv(%r) — read it through mxnet_tpu.envs "
                "accessors" % name)
            continue
        # legacy base.get_env("MXNET_X", ...)
        if attr == "get_env":
            yield ctx.violation(
                "env-registry", node,
                "legacy get_env(%r) — use the typed mxnet_tpu.envs "
                "accessor (declared default + parse errors that "
                "name the variable)" % name)
            continue
        # envs.get_*("MXNET_TYPO") — statically check declarations
        if declared is not None and attr in (
                "get_bool", "get_int", "get_float", "get_str",
                "get_path", "get_raw") and base is not None \
                and al.module_is(base, "envs") \
                and name not in declared:
            yield ctx.violation(
                "env-registry", node,
                "envs.%s(%r): variable is not declared in "
                "mxnet_tpu/envs.py — declare it (typo?) before "
                "reading it" % (attr, name))
