"""graftlint — AST-based SPMD/JAX invariant checker for the heat_tpu tree.

The framework's core design fact is SPMD: every host runs the same
Python script and collectives fire eagerly inside ops.  A whole family
of bugs therefore never shows up in a unit test and only manifests as a
hang, a silent recompile storm, or a host-transfer stall at scale:

- a per-call closure traced into ``jax.jit`` retraces on every call and
  parks a dead executable in the cache (the ``statistics.py`` max/min
  recompile bug fixed by hand in PR 2);
- an unbounded executable cache pins compiled programs plus their Mesh
  objects forever (the round-3 ADVICE leak);
- a collective dispatched under rank- or device-value-dependent control
  flow deadlocks the ranks that took the other branch (the divergence
  class ``resilience/guard`` detects at runtime — this rule catches it
  at review time);
- an implicit host sync (``np.asarray`` on a device value, ``.item()``,
  ``jax.device_get``, ``float()``/``int()`` of a jitted function's
  result) in a hot path serializes the dispatch pipeline on a device
  round-trip — and, made anywhere but ``core._hooks.fetch``, is a fetch
  that ``COMPILE_STATS["host_syncs"]`` and the trace's ``ht.fetch:*``
  spans never hear of;
- iterating a ``set`` to build collective schedules or cache keys gives
  each host its own ordering (hash randomization) — ranks dispatch
  different programs;
- a broad ``except`` that ignores the caught error swallows the
  ``ResilienceError`` hierarchy and turns detected divergence into
  silent corruption;
- a direct ``open(..., "w")`` on a durability-critical path (the
  resilience package, ``core/io.py``) bypasses ``core._atomic``'s
  temp-file + fsync + rename commit — a crash mid-write leaves a torn
  file that the checkpoint checksum layer then has to reject.

This module is **pure stdlib** (``ast`` only — no jax import) so the
CLI in ``tools/graftlint.py`` can lint without initializing a backend.
Rule reference and the failure story behind each id: ``docs/ANALYSIS.md``.

Waivers
-------
A finding is waived by a ``# graftlint: <token>`` comment on the same
line or in the contiguous comment block directly above, where
``<token>`` is the rule id
(``G004``), the rule tag (``host-sync``), or ``all``.  File-level
pragmas: ``# graftlint: skip-file`` disables the file entirely;
``# graftlint: hot-path`` opts a file into the G004 hot-path set;
``# graftlint: durable-path`` opts a file into the G007 durable-write
set (the resilience package and ``core/io.py`` are in it by location).
"""
from __future__ import annotations

import ast
import json
import os
import re
import sys
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

__all__ = [
    "RULES",
    "Finding",
    "lint_source",
    "lint_file",
    "lint_paths",
    "build_report",
    "exit_code_for",
    "iter_python_files",
]

SCHEMA_VERSION = 1


@dataclass(frozen=True)
class Rule:
    id: str
    tag: str
    bit: int
    summary: str


RULES: Dict[str, Rule] = {
    r.id: r
    for r in (
        Rule("G001", "retrace", 1,
             "per-call closure/lambda traced into jax.jit or the executable-cache layer (retrace leak)"),
        Rule("G002", "unbounded-cache", 2,
             "unbounded functools cache or module-level dict used as an executable cache"),
        Rule("G003", "divergence", 4,
             "collective dispatched under rank- or device-value-dependent control flow"),
        Rule("G004", "host-sync", 8,
             "implicit host synchronization in a hot path without a waiver"),
        Rule("G005", "nondeterminism", 16,
             "iteration over an unordered set feeds collective ordering or cache keys"),
        Rule("G006", "swallow", 32,
             "broad except ignores the caught error (swallows the ResilienceError hierarchy)"),
        Rule("G007", "durable-write", 64,
             "direct write-mode open() on a durable path bypasses core._atomic's crash-safe commit"),
    )
}

TAG_TO_ID = {r.tag: r.id for r in RULES.values()}

# G004 hot-path set: every parallel/ module, the analytics packages whose
# public calls the chip benchmark times (their device reads go through
# ``core._hooks.fetch``, which counts them and names them in a trace), plus
# the core modules on the per-op dispatch path.  Cold modules (io, printing,
# manipulations' host merges) do explicit, documented host work and are
# exempt; a new module opts in with a file-level ``# graftlint: hot-path``
# pragma.
HOT_PACKAGES = ("parallel", "cluster", "spatial", "frame", "regression")
HOT_CORE_MODULES = {
    "_operations.py", "_movement.py", "_dispatch.py", "arithmetics.py",
    "statistics.py", "relational.py", "logical.py", "rounding.py",
    "exponential.py", "trigonometrics.py",
}

COLLECTIVE_NAMES = {
    "ppermute", "psum", "pmax", "pmin", "pmean", "all_gather", "all_to_all",
    "pshuffle", "process_allgather", "ragged_process_allgather",
    "ragged_move", "reshape_via_flatmove", "strided_take",
    "broadcast_one_to_all", "sync_global_devices", "assemble_local_shards",
    "nonzero_scan", "unique_scan",
}

# NOTE: process_count()/device counts are replicated-uniform across hosts
# and therefore NOT divergence hazards; only per-rank identities are.
RANK_ATTRS = {"rank", "process_index", "local_rank"}
RANK_CALLS = {"process_index", "axis_index"}
SYNC_CALLS = {"item", "device_get", "block_until_ready"}

RESILIENCE_NAMES = {
    "ResilienceError", "DivergenceError", "CollectiveTimeout", "DegradeError",
    "NoHealthyDevicesError", "CheckpointError", "ValidationError",
}

CACHE_NAME_RE = re.compile(r"(?i)(^|_)caches?$")
WAIVER_RE = re.compile(r"#\s*graftlint:\s*([A-Za-z0-9_,\s=-]+)")


@dataclass
class Finding:
    rule: str
    path: str
    line: int
    col: int
    message: str

    def as_dict(self) -> dict:
        return {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "col": self.col,
            "message": self.message,
        }


# --------------------------------------------------------------------- waivers
def _parse_waivers(source: str) -> Tuple[Dict[int, Set[str]], Set[str]]:
    """(line -> waived rule ids, file-level pragma tokens)."""
    per_line: Dict[int, Set[str]] = {}
    pragmas: Set[str] = set()
    for i, text in enumerate(source.splitlines(), start=1):
        m = WAIVER_RE.search(text)
        if not m:
            continue
        ids: Set[str] = set()
        for token in re.split(r"[,\s]+", m.group(1).strip()):
            if not token or token == "-":
                continue
            token = token.split("=", 1)[-1]  # tolerate disable=G001 spelling
            low = token.lower()
            if low in ("skip-file", "hot-path", "durable-path"):
                pragmas.add(low)
            elif low == "all":
                ids.add("all")
            elif token.upper() in RULES:
                ids.add(token.upper())
            elif low in TAG_TO_ID:
                ids.add(TAG_TO_ID[low])
            # a comment like "# graftlint: host-sync - q is tiny" puts
            # free text after the token; unknown words are simply ignored
        if ids:
            per_line[i] = ids
    return per_line, pragmas


def _is_hot(path: str, pragmas: Set[str]) -> bool:
    if "hot-path" in pragmas:
        return True
    p = "/" + path.replace(os.sep, "/").lstrip("/")
    if any(f"/heat_tpu/{pkg}/" in p for pkg in HOT_PACKAGES):
        return True
    if "/heat_tpu/core/" in p and os.path.basename(p) in HOT_CORE_MODULES:
        return True
    return False


# G007 durable-write set: files whose writes MUST go through the
# temp-file + fsync + rename commit in core._atomic (which is itself the
# one legitimate direct writer and therefore not in the set).
def _is_durable(path: str, pragmas: Set[str]) -> bool:
    if "durable-path" in pragmas:
        return True
    p = "/" + path.replace(os.sep, "/").lstrip("/")
    if "/heat_tpu/resilience/" in p:
        return True
    return p.endswith("/heat_tpu/core/io.py")


# --------------------------------------------------------------------- helpers
def _call_name(func: ast.expr) -> Optional[str]:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _is_jit(func: ast.expr) -> bool:
    return _call_name(func) == "jit"


def _is_jit_decorator(dec: ast.expr) -> bool:
    """``@jax.jit`` / ``@jit`` / ``@partial(jax.jit, ...)``."""
    if isinstance(dec, ast.Call):
        if _call_name(dec.func) == "partial" and dec.args:
            return _is_jit(dec.args[0])
        return _is_jit(dec.func)
    return _is_jit(dec)


def _numpy_rooted(node: ast.expr) -> bool:
    """``np.inf``, ``np.iinfo(dt).max``: computed by numpy, so on the host."""
    while isinstance(node, (ast.Attribute, ast.Call, ast.UnaryOp)):
        node = (
            node.func if isinstance(node, ast.Call)
            else node.operand if isinstance(node, ast.UnaryOp)
            else node.value
        )
    return isinstance(node, ast.Name) and node.id in ("np", "numpy")


def _is_literal(node: ast.expr) -> bool:
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, (ast.List, ast.Tuple)):
        return all(_is_literal(e) for e in node.elts)
    if isinstance(node, ast.UnaryOp):
        return _is_literal(node.operand)
    return False


def _walk_no_functions(node: ast.AST) -> Iterable[ast.AST]:
    """Walk statements/expressions without descending into nested
    function/class bodies (their code does not run at this point)."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        yield n
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda, ast.ClassDef)):
            continue
        stack.extend(ast.iter_child_nodes(n))


def _exception_names(type_node: Optional[ast.expr]) -> List[str]:
    if type_node is None:
        return []
    nodes = type_node.elts if isinstance(type_node, ast.Tuple) else [type_node]
    out = []
    for n in nodes:
        name = _call_name(n) if not isinstance(n, ast.Name) else n.id
        if isinstance(n, ast.Attribute):
            name = n.attr
        if name:
            out.append(name)
    return out


# --------------------------------------------------------------------- checker
class _Checker(ast.NodeVisitor):
    def __init__(self, path: str, hot: bool, durable: bool = False):
        self.path = path
        self.hot = hot
        self.durable = durable
        self._atomic_names: Set[str] = set()
        self.findings: List[Finding] = []
        self._func_stack: List[ast.AST] = []
        self._local_defs: List[Set[str]] = []
        self._cache_decorated: List[bool] = []
        self._local_sets: List[Set[str]] = []
        self._handled_jit_ids: Set[int] = set()
        self._seen: Set[Tuple[str, int, int]] = set()
        self._parents: Dict[int, ast.AST] = {}
        # G004: the file's jitted functions, and per open function the
        # names bound to what one of them returned
        self._jitted: Set[str] = set()
        self._jit_results: List[Set[str]] = []

    # -- plumbing -------------------------------------------------------------
    def check(self, tree: ast.Module) -> List[Finding]:
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                self._parents[id(child)] = node
            # names bound by ``with atomic_write(...) as tmp`` are staged
            # temp paths: opening THEM for write is the sanctioned pattern
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    ce = item.context_expr
                    if (
                        isinstance(ce, ast.Call)
                        and _call_name(ce.func) == "atomic_write"
                        and isinstance(item.optional_vars, ast.Name)
                    ):
                        self._atomic_names.add(item.optional_vars.id)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and any(
                _is_jit_decorator(d) for d in node.decorator_list
            ):
                self._jitted.add(node.name)
        self._check_module_caches(tree)
        self.visit(tree)
        return self.findings

    def _emit(self, rule: str, node: ast.AST, message: str) -> None:
        key = (rule, getattr(node, "lineno", 0), getattr(node, "col_offset", 0))
        if key in self._seen:
            return
        self._seen.add(key)
        self.findings.append(
            Finding(rule, self.path, key[1], key[2], message)
        )

    def _enclosing_stmt(self, node: ast.AST) -> Optional[ast.stmt]:
        cur: Optional[ast.AST] = node
        while cur is not None and not isinstance(cur, ast.stmt):
            cur = self._parents.get(id(cur))
        return cur  # type: ignore[return-value]

    # -- scopes ---------------------------------------------------------------
    def _visit_function(self, node):
        local_defs = {
            n.name
            for n in ast.walk(node)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) and n is not node
        }
        cache_dec = False
        for dec in node.decorator_list:
            base = dec.func if isinstance(dec, ast.Call) else dec
            if _call_name(base) in ("lru_cache", "cache"):
                cache_dec = True
        self._check_unbounded_decorators(node)
        self._func_stack.append(node)
        self._local_defs.append(local_defs)
        self._cache_decorated.append(cache_dec)
        self._local_sets.append(set())
        self._jit_results.append(self._bound_to_jit_results(node))
        self.generic_visit(node)
        self._jit_results.pop()
        self._func_stack.pop()
        self._local_defs.pop()
        self._cache_decorated.pop()
        self._local_sets.pop()

    visit_FunctionDef = _visit_function
    visit_AsyncFunctionDef = _visit_function

    # -- G001: retrace leaks --------------------------------------------------
    def _fresh_callable(self, node: ast.expr) -> Optional[str]:
        """A callable object with per-call identity: its object is new on
        every execution of the enclosing function, so it keys every
        jit/executable cache as a miss."""
        if isinstance(node, ast.Lambda):
            return "lambda"
        if isinstance(node, ast.Call) and _call_name(node.func) == "partial":
            return "functools.partial object"
        if (
            isinstance(node, ast.Name)
            and self._local_defs
            and node.id in self._local_defs[-1]
        ):
            return f"locally-defined closure {node.id!r}"
        return None

    def visit_Call(self, node: ast.Call) -> None:
        # jit(<fresh>)(args) — jit-then-call in one expression: retraces
        # on every execution of the enclosing function
        if (
            isinstance(node.func, ast.Call)
            and _is_jit(node.func.func)
            and self._func_stack
        ):
            jit_call = node.func
            kind = self._fresh_callable(jit_call.args[0]) if jit_call.args else None
            self._handled_jit_ids.add(id(jit_call))
            if kind is not None:
                self._emit(
                    "G001", jit_call,
                    f"jax.jit of a {kind} built and invoked per call — every call "
                    "retraces; hoist the callable to module scope or key a bounded "
                    "ExecutableCache by hashable statics",
                )
        elif _is_jit(node.func) and self._func_stack and id(node) not in self._handled_jit_ids:
            kind = self._fresh_callable(node.args[0]) if node.args else None
            if kind is not None and not self._cache_decorated[-1]:
                stmt = self._enclosing_stmt(node)
                memoized = isinstance(stmt, ast.Return)
                if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                    targets = stmt.targets if isinstance(stmt, ast.Assign) else [stmt.target]
                    memoized = any(
                        isinstance(t, (ast.Subscript, ast.Attribute)) for t in targets
                    )
                if not memoized:
                    self._emit(
                        "G001", node,
                        f"jax.jit of a {kind} inside a function without memoization "
                        "(not returned, cached, or stored on self) — each call builds "
                        "a fresh traced program",
                    )
        # per-call closure handed to the cached-reduce layer: keys the
        # lru cache by a fresh identity every call (the statistics.py bug)
        fname = _call_name(node.func)
        if fname in ("_jitted_reduce", "_jitted_reduce_cached") and node.args:
            kind = self._fresh_callable(node.args[0])
            if kind is not None:
                self._emit(
                    "G001", node,
                    f"{fname} called with a {kind} as the operation — the cache keys "
                    "by object identity, so every call is a miss that compiles and "
                    "parks a dead executable; hoist it to module level",
                )
        # lambda smuggled into an executable-cache key
        self._check_sync_call(node)
        self._check_durable_open(node)
        self.generic_visit(node)

    def visit_Subscript(self, node: ast.Subscript) -> None:
        name = _call_name(node.value) if not isinstance(node.value, ast.Name) else node.value.id
        if name and CACHE_NAME_RE.search(name):
            for sub in ast.walk(node.slice):
                if isinstance(sub, ast.Lambda):
                    self._emit(
                        "G001", sub,
                        f"lambda inside the cache key of {name!r} — per-call identity "
                        "makes every lookup a miss and grows the cache monotonically",
                    )
        self.generic_visit(node)

    # -- G002: unbounded caches -----------------------------------------------
    def _check_unbounded_decorators(self, node) -> None:
        for dec in node.decorator_list:
            call = dec if isinstance(dec, ast.Call) else None
            base = dec.func if isinstance(dec, ast.Call) else dec
            name = _call_name(base)
            if name == "cache" and isinstance(base, ast.Attribute):
                # functools.cache == lru_cache(maxsize=None)
                self._emit(
                    "G002", dec,
                    "functools.cache is unbounded — compiled executables and their "
                    "Mesh objects are pinned forever; use lru_cache(maxsize=N) or "
                    "core._cache.ExecutableCache",
                )
            if name != "lru_cache":
                continue
            unbounded = False
            if call is not None:
                if call.args and isinstance(call.args[0], ast.Constant) and call.args[0].value is None:
                    unbounded = True
                for kw in call.keywords:
                    if kw.arg == "maxsize" and isinstance(kw.value, ast.Constant) and kw.value.value is None:
                        unbounded = True
            if unbounded:
                self._emit(
                    "G002", dec,
                    "lru_cache(maxsize=None) never evicts — shape-polymorphic "
                    "workloads grow it without bound; give it a maxsize",
                )

    def _check_module_caches(self, tree: ast.Module) -> None:
        bodies = [tree.body]
        bodies.extend(n.body for n in tree.body if isinstance(n, ast.ClassDef))
        for body in bodies:
            for stmt in body:
                if not isinstance(stmt, (ast.Assign, ast.AnnAssign)):
                    continue
                targets = stmt.targets if isinstance(stmt, ast.Assign) else [stmt.target]
                value = stmt.value
                if value is None:
                    continue
                is_plain_dict = isinstance(value, ast.Dict) or (
                    isinstance(value, ast.Call)
                    and _call_name(value.func) in ("dict", "OrderedDict", "defaultdict")
                )
                if not is_plain_dict:
                    continue
                for t in targets:
                    if isinstance(t, ast.Name) and CACHE_NAME_RE.search(t.id):
                        self._emit(
                            "G002", stmt,
                            f"module-level dict {t.id!r} used as a cache never evicts "
                            "— executables pinned for the process lifetime; use "
                            "core._cache.ExecutableCache (bounded LRU)",
                        )

    # -- G003: collectives under divergent control flow -----------------------
    def _divergence_kind(self, test: ast.expr) -> Optional[str]:
        for n in ast.walk(test):
            if isinstance(n, ast.Attribute) and n.attr in RANK_ATTRS:
                return f"rank-dependent ({n.attr})"
            if isinstance(n, ast.Call):
                name = _call_name(n.func)
                if name in RANK_CALLS:
                    return f"rank-dependent ({name}())"
                if name in SYNC_CALLS:
                    return f"device-value-dependent ({name}())"
        return None

    def _check_branch(self, node) -> None:
        kind = self._divergence_kind(node.test)
        if kind is None:
            return
        for n in _walk_no_functions(node):
            if isinstance(n, ast.Call) and _call_name(n.func) in COLLECTIVE_NAMES:
                self._emit(
                    "G003", n,
                    f"collective {_call_name(n.func)!r} under {kind} control flow "
                    f"(test at line {node.test.lineno}) — ranks taking different "
                    "branches dispatch different collective sequences and hang; "
                    "hoist the collective out of the branch",
                )

    def visit_If(self, node: ast.If) -> None:
        self._check_branch(node)
        self.generic_visit(node)

    def visit_While(self, node: ast.While) -> None:
        self._check_branch(node)
        self.generic_visit(node)

    # -- G004: implicit host syncs in hot paths -------------------------------
    def _is_jitted_call(self, node: ast.expr) -> bool:
        return isinstance(node, ast.Call) and _call_name(node.func) in self._jitted

    def _bound_to_jit_results(self, fn) -> Set[str]:
        """Names ``fn`` assigns from a call of one of the file's jitted
        functions (``a, b = _fit(...)``): device values, whatever they hold."""
        names: Set[str] = set()
        for n in ast.walk(fn):
            if isinstance(n, ast.Assign) and self._is_jitted_call(n.value):
                for t in n.targets:
                    elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]
                    names.update(e.id for e in elts if isinstance(e, ast.Name))
        return names

    def _check_sync_call(self, node: ast.Call) -> None:
        if not self.hot:
            return
        f = node.func
        what = None
        if (
            isinstance(f, ast.Name)
            and f.id in ("float", "int", "bool", "complex")
            and len(node.args) == 1
        ):
            arg = node.args[0]
            if self._is_jitted_call(arg) or (
                isinstance(arg, ast.Name)
                and self._jit_results
                and arg.id in self._jit_results[-1]
            ):
                what = f"{f.id}() of a jitted function's result"
        if isinstance(f, ast.Attribute):
            if f.attr == "item" and not node.args:
                what = ".item()"
            elif f.attr == "block_until_ready":
                what = ".block_until_ready()"
            elif f.attr == "device_get":
                what = "jax.device_get"
            elif (
                f.attr in ("asarray", "array")
                and isinstance(f.value, ast.Name)
                and f.value.id in ("np", "numpy")
                and node.args
                and not _is_literal(node.args[0])
                and not _numpy_rooted(node.args[0])
            ):
                what = f"np.{f.attr} on a computed value"
        elif isinstance(f, ast.Name) and f.id == "device_get":
            what = "device_get"
        if what is not None:
            self._emit(
                "G004", node,
                f"{what} in a hot path blocks dispatch on a device->host round "
                "trip; keep the value on device, read it with core._hooks.fetch "
                "(counted, and named in a trace), or waive an intentional sync "
                "with '# graftlint: host-sync'",
            )

    # -- G007: direct write-mode open() on a durable path ---------------------
    def _check_durable_open(self, node: ast.Call) -> None:
        if not self.durable:
            return
        if not (isinstance(node.func, ast.Name) and node.func.id == "open"):
            return
        mode = node.args[1] if len(node.args) > 1 else None
        for kw in node.keywords:
            if kw.arg == "mode":
                mode = kw.value
        # no/dynamic mode: default "r", or unprovable — only a literal
        # write-capable mode is a definite bypass of the atomic layer
        if not (isinstance(mode, ast.Constant) and isinstance(mode.value, str)):
            return
        if not any(c in mode.value for c in "wax+"):
            return
        target = node.args[0] if node.args else None
        if isinstance(target, ast.Name) and target.id in self._atomic_names:
            return  # staged temp path from ``with atomic_write(...) as <name>``
        self._emit(
            "G007", node,
            f"open(..., {mode.value!r}) on a durable path writes in place — a "
            "crash mid-write leaves a torn file; stage through core._atomic "
            "(atomic_write/atomic_write_bytes: temp file + fsync + rename), or "
            "waive an intentional in-place write with '# graftlint: durable-write'",
        )

    # -- G005: unordered iteration feeding collectives / cache keys -----------
    def _is_set_expr(self, node: ast.expr) -> bool:
        if isinstance(node, ast.Set) or isinstance(node, ast.SetComp):
            return True
        if isinstance(node, ast.Call) and _call_name(node.func) in ("set", "frozenset"):
            return True
        if (
            isinstance(node, ast.Name)
            and self._local_sets
            and node.id in self._local_sets[-1]
        ):
            return True
        return False

    def _check_unordered_iter(self, iter_node: ast.expr, body_scope: ast.AST) -> None:
        if not self._is_set_expr(iter_node):
            return
        for n in _walk_no_functions(body_scope):
            hazard = None
            if isinstance(n, ast.Call) and _call_name(n.func) in COLLECTIVE_NAMES:
                hazard = f"collective {_call_name(n.func)!r}"
            elif isinstance(n, ast.Subscript):
                name = n.value.id if isinstance(n.value, ast.Name) else _call_name(n.value)
                if name and CACHE_NAME_RE.search(name):
                    hazard = f"cache key for {name!r}"
            if hazard:
                self._emit(
                    "G005", iter_node,
                    f"iteration over an unordered set feeds {hazard} — set order "
                    "differs across hosts (hash randomization), so ranks disagree "
                    "on schedule/keys; iterate sorted(...) instead",
                )
                return

    def visit_Assign(self, node: ast.Assign) -> None:
        if self._local_sets and self._is_set_expr(node.value):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    self._local_sets[-1].add(t.id)
        self.generic_visit(node)

    def visit_For(self, node: ast.For) -> None:
        self._check_unordered_iter(node.iter, node)
        self.generic_visit(node)

    def _visit_comp(self, node) -> None:
        for gen in node.generators:
            self._check_unordered_iter(gen.iter, node)
        self.generic_visit(node)

    visit_ListComp = _visit_comp
    visit_SetComp = _visit_comp
    visit_GeneratorExp = _visit_comp
    visit_DictComp = _visit_comp

    # -- G006: broad except swallowing ResilienceError ------------------------
    def visit_Try(self, node: ast.Try) -> None:
        resilience_handled = False
        for handler in node.handlers:
            names = _exception_names(handler.type)
            if any(n in RESILIENCE_NAMES for n in names):
                resilience_handled = True
                continue
            broad = handler.type is None or any(
                n in ("Exception", "BaseException") for n in names
            )
            if not broad or resilience_handled:
                continue
            reraises = any(isinstance(n, ast.Raise) for n in ast.walk(handler))
            uses_exc = handler.name is not None and any(
                isinstance(n, ast.Name) and n.id == handler.name
                for stmt in handler.body
                for n in ast.walk(stmt)
            )
            if not reraises and not uses_exc:
                caught = names[0] if names else "everything (bare except)"
                self._emit(
                    "G006", handler,
                    f"broad handler catches {caught} and ignores the error — "
                    "DivergenceError/CollectiveTimeout would be swallowed into "
                    "silent corruption; narrow the type or put "
                    "'except ResilienceError: raise' first",
                )
        self.generic_visit(node)


# ------------------------------------------------------------------ public API
def lint_source(
    source: str, path: str = "<string>", select: Optional[Set[str]] = None
) -> List[Finding]:
    """Lint one source string; returns unwaived findings."""
    waivers, pragmas = _parse_waivers(source)
    if "skip-file" in pragmas:
        return []
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Finding("SYNTAX", path, e.lineno or 0, e.offset or 0, str(e.msg))]
    checker = _Checker(
        path, hot=_is_hot(path, pragmas), durable=_is_durable(path, pragmas)
    )
    findings = checker.check(tree)
    lines = source.splitlines()

    def _waived(lineno: int) -> Set[str]:
        ids = set(waivers.get(lineno, ()))
        # the contiguous comment block directly above also covers the line
        i = lineno - 1
        while 1 <= i <= len(lines) and lines[i - 1].lstrip().startswith("#"):
            ids |= waivers.get(i, set())
            i -= 1
        return ids

    out = []
    for f in findings:
        if select is not None and f.rule not in select and f.rule != "SYNTAX":
            continue
        waived = _waived(f.line)
        if f.rule in waived or "all" in waived:
            continue
        out.append(f)
    out.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return out


def lint_file(path: str, select: Optional[Set[str]] = None) -> List[Finding]:
    with open(path, "r", encoding="utf-8") as fh:
        return lint_source(fh.read(), path=path, select=select)


def iter_python_files(paths: Sequence[str]) -> List[str]:
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, names in os.walk(p):
                dirs[:] = sorted(d for d in dirs if d != "__pycache__")
                files.extend(
                    os.path.join(root, n) for n in sorted(names) if n.endswith(".py")
                )
        else:
            files.append(p)
    return files


def lint_paths(
    paths: Sequence[str], select: Optional[Set[str]] = None
) -> Tuple[List[Finding], int]:
    """(findings, files_checked) over files and/or directory trees."""
    files = iter_python_files(paths)
    findings: List[Finding] = []
    for f in files:
        findings.extend(lint_file(f, select=select))
    return findings, len(files)


def exit_code_for(findings: Iterable[Finding]) -> int:
    """Per-rule exit bitmask: G001=1, G002=2, ... G007=64; syntax errors=128."""
    code = 0
    for f in findings:
        code |= RULES[f.rule].bit if f.rule in RULES else 128
    return code


def build_report(paths: Sequence[str], findings: List[Finding], files_checked: int) -> dict:
    """The machine-readable output contract (validated in tier-1)."""
    counts = {rid: 0 for rid in RULES}
    for f in findings:
        if f.rule in counts:
            counts[f.rule] += 1
    return {
        "tool": "graftlint",
        "schema_version": SCHEMA_VERSION,
        "paths": list(paths),
        "files_checked": files_checked,
        "rules": [
            {"id": r.id, "tag": r.tag, "bit": r.bit, "summary": r.summary}
            for r in RULES.values()
        ],
        "findings": [f.as_dict() for f in findings],
        "counts": counts,
        "total": len(findings),
        "exit_code": exit_code_for(findings),
    }


def render_text(report: dict) -> str:
    lines = []
    for f in report["findings"]:
        lines.append(f"{f['path']}:{f['line']}:{f['col']}: {f['rule']} {f['message']}")
    lines.append(
        f"graftlint: {report['total']} finding(s) in {report['files_checked']} file(s)"
        + (" — clean" if report["total"] == 0 else "")
    )
    return "\n".join(lines)


def render_github(report: dict) -> str:
    """GitHub workflow-annotation lines (::error file=...,line=...)."""
    lines = []
    for f in report["findings"]:
        msg = f["message"].replace("%", "%25").replace("\n", "%0A")
        lines.append(
            f"::error file={f['path']},line={f['line']},col={f['col']},"
            f"title=graftlint {f['rule']}::{msg}"
        )
    return "\n".join(lines)


_EXIT_EPILOG = (
    "exit code is a bitmask: "
    + ", ".join(f"{r.bit}={r.id}" for r in RULES.values())
    + ", 128=syntax/internal error; 0 means clean "
    "(table: docs/ANALYSIS.md)"
)


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="graftlint",
        description="SPMD/JAX invariant checker for the heat_tpu tree "
        "(rule reference: docs/ANALYSIS.md)",
        epilog=_EXIT_EPILOG,
    )
    parser.add_argument("paths", nargs="*", default=["heat_tpu"], help="files or directories")
    parser.add_argument("--format", choices=("text", "json", "github"), default="text")
    parser.add_argument(
        "--select", default=None,
        help="comma-separated rule ids to run (default: all)",
    )
    parser.add_argument("--list-rules", action="store_true")
    args = parser.parse_args(argv)

    if args.list_rules:
        for r in RULES.values():
            print(f"{r.id}  [{r.tag}]  exit-bit {r.bit}: {r.summary}")
        return 0

    select = None
    if args.select:
        select = {s.strip().upper() for s in args.select.split(",") if s.strip()}
        unknown = select - set(RULES)
        if unknown:
            print(f"graftlint: unknown rule id(s): {sorted(unknown)}", file=sys.stderr)
            return 128
    try:
        findings, files_checked = lint_paths(args.paths, select=select)
    except OSError as e:
        print(f"graftlint: {e}", file=sys.stderr)
        return 128
    report = build_report(args.paths, findings, files_checked)
    if args.format == "json":
        print(json.dumps(report, separators=(",", ":"), sort_keys=True))
    elif args.format == "github":
        out = render_github(report)
        if out:
            print(out)
        print(f"graftlint: {report['total']} finding(s) in {report['files_checked']} file(s)")
    else:
        print(render_text(report))
    return report["exit_code"]


if __name__ == "__main__":
    sys.exit(main())
