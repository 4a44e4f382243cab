"""copsan: whole-program concurrency model (ISSUE 17).

The reference TiDB leans on ``go test -race`` and the Go runtime's
goroutine tooling; this rebuild gets neither from CPython, so the
analysis substrate models the thread plane statically the way shardflow
models the mesh plane.  Every module that imports ``threading`` joins
the model automatically — there is no hand-maintained list to drift
(``LOCK_EXCLUDES`` in lint.py is the only opt-out, and each entry must
carry a justification).

Model
-----
*Lock nodes*: every ``threading.Lock/RLock/Condition`` allocation site
becomes a named node — ``rel::Class.attr`` for instance locks (with
``Condition(self._mu)`` aliased onto the wrapped lock's node),
``rel::NAME`` for module-level locks, and dataclass
``field(default_factory=threading.Lock)`` class vars by field name.

*Acquisition edges*: ``with lock:`` nesting and paired
``lock.acquire()/release()`` calls yield directed edges held→acquired.
Call chains are followed intra-module (bounded depth) so a helper
called under a lock inherits the caller's lockset; cross-module seams
are resolved through imports, constructor-typed attributes
(``self.x = ImportedClass(...)``), and the singleton getters in
``SEAM_GETTERS`` — a call into module M while holding L conservatively
adds edges L→every lock of M, which keeps the static graph a superset
of anything the runtime sanitizer (utils/locksan) can observe.

*Thread roots*: where threads are born.  ``ROOT_ENTRIES`` pins the
known spawn points (the sched drain loop, copforge warm threads, the
ddl owner loop, status routes, weakref death callbacks, pool workers);
``threading.Thread(target=...)`` sites are auto-rooted as ``bg``; roots
propagate caller→callee to a fixpoint and any unreached function gets
its module's declared default (``MODULE_ROOTS``).  Roots in
``MULTI_ROOTS`` have many concurrent threads, so a single such root is
already a race party.

Finding families (baseline + ``# planlint: ok`` waivers like lint)
------------------------------------------------------------------
RACE-UNGUARDED-WRITE   read-modify-write of a shared attribute with an
                       empty lockset from ≥2 thread roots (or one
                       multi-thread root).  Plain assignments are
                       GIL-atomic and exempt.
RACE-GUARD-MIX         the same attribute guarded by disjoint locks at
                       different write sites — mutual exclusion in
                       name only.
LOCK-ORDER-CYCLE       a strongly-connected component in the global
                       acquisition graph (subsumes the pairwise
                       TPU-LOCK-ORDER check across modules).
LOCK-BLOCKING-HELD     file IO / flock / sleep / device sync while
                       holding a hot-path lock.
LOCK-CV-PREDICATE      ``Condition.wait()`` outside a ``while``
                       predicate loop, or ``notify`` under the lock
                       with no state write the waiter could re-check.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field
from typing import Dict, FrozenSet, List, Optional, Set, Tuple

from .lint import Finding, LOCK_EXCLUDES, module_imports_threading

RULE_UNGUARDED = "RACE-UNGUARDED-WRITE"
RULE_GUARD_MIX = "RACE-GUARD-MIX"
RULE_CYCLE = "LOCK-ORDER-CYCLE"
RULE_BLOCKING = "LOCK-BLOCKING-HELD"
RULE_CV = "LOCK-CV-PREDICATE"

CONCURRENCY_RULES = (RULE_UNGUARDED, RULE_GUARD_MIX, RULE_CYCLE,
                     RULE_BLOCKING, RULE_CV)

_WAIVER = re.compile(r"planlint:\s*ok")

# --------------------------------------------------------------------- #
# thread roots: where threads are born.  A root in MULTI_ROOTS runs
# many concurrent threads, so one such root already races with itself.
# --------------------------------------------------------------------- #

THREAD_ROOTS = {
    "statement": "session/connection statement threads (submit path, "
                 "pd coordinator tick, plan cache, catalog)",
    "drain":     "the sched-drain device launch loop (one per mesh)",
    "warm":      "copforge-group background group-program compile "
                 "threads (two alive at the most)",
    "status":    "status-server HTTP route threads",
    "owner":     "ddl owner job loop + election lease renewal",
    "timer":     "timer wheel ticks / profiler stop timers",
    "weakref":   "GC weakref death callbacks (hbm residents)",
    "pool":      "poolmgr / executor worker threads (copr chunks, "
                 "ddl backfill, dxf)",
    "bg":        "auto-discovered Thread(target=...) background sites",
}

MULTI_ROOTS = frozenset({"statement", "warm", "status", "pool"})

# declared thread spawn points: (root, module rel or "prefix/", qualname
# regex).  These are the seeds the intra-module call graph propagates.
ROOT_ENTRIES = [
    ("drain", "sched/scheduler.py", r"^DeviceScheduler\._loop$"),
    ("warm", "sched/scheduler.py",
     r"^DeviceScheduler\._group_worker$"),
    ("statement", "sched/scheduler.py",
     r"^DeviceScheduler\.(submit|configure|pause|resume|drain"
     r"|warm_groups)"),
    ("statement", "sched/scheduler.py", r"^scheduler_for$"),
    ("status", "sched/scheduler.py", r"^DeviceScheduler\.stats$"),
    ("statement", "sched/scheduler.py", r"^DeviceScheduler\.stats$"),
    ("owner", "ddl/owner.py", r"^DDLExecutor\._owner_loop"),
    ("statement", "ddl/owner.py", r"^DDLExecutor\.(run_job|close|stats)$"),
    ("owner", "ddl/election.py", r"^OwnerManager\.start_renewal\."),
    ("status", "server/status.py", r".*"),
    ("statement", "server/mysql_server.py", r".*"),
    ("pool", "utils/poolmgr.py", r"^PoolManager\.submit\."),
    ("pool", "utils/poolmgr.py", r"^PoolManager\.resize\."),
    ("weakref", "obs/hbm.py", r"^HbmLedger\._resident_dead$"),
    ("timer", "timer/", r".*"),
]

# default root sets by module prefix (first match wins): the declared
# cross-module call seams in root space — who can be on this module's
# stack.  Leaf control-plane modules are reachable from the submit path
# AND the drain (rc debit, breaker, compile cache, calibration), obs is
# additionally on the status routes and weakref callbacks, pd ticks run
# on every statement thread and render on status routes.
MODULE_ROOTS = [
    ("sched/", frozenset({"statement"})),
    ("rc/", frozenset({"statement", "drain"})),
    ("faults/", frozenset({"statement", "drain"})),
    ("compilecache/", frozenset({"statement", "drain", "warm"})),
    ("analysis/calibrate.py", frozenset({"statement", "drain", "status"})),
    ("obs/hbm.py", frozenset({"statement", "drain", "status", "weakref"})),
    ("obs/", frozenset({"statement", "drain", "status"})),
    ("pd/", frozenset({"statement", "status"})),
    ("utils/metrics.py", frozenset({"statement", "drain", "status"})),
    ("utils/poolmgr.py", frozenset({"statement", "pool", "status"})),
    ("server/status.py", frozenset({"status"})),
    ("ddl/", frozenset({"statement", "owner"})),
    ("stats/", frozenset({"statement", "owner"})),
    ("store/", frozenset({"statement", "drain"})),
    ("timer/", frozenset({"statement", "timer"})),
    ("dxf/", frozenset({"statement", "pool"})),
    ("", frozenset({"statement"})),
]

# singleton getters: imported callables whose RESULT lives in another
# module — a method call on the result while holding a lock is a seam
# into that module's locks.
SEAM_GETTERS = {
    "correction_store": "analysis/calibrate.py",
    "compile_cache": "compilecache/cache.py",
    "global_registry": "utils/metrics.py",
    "ledger_for": "obs/hbm.py",
    "scheduler_for": "sched/scheduler.py",
    "current_recorder": "obs/recorder.py",
}

# locks on the launch/admission hot path: blocking while holding one of
# these stalls the drain or every statement thread.
HOT_LOCK_PREFIXES = ("sched/", "rc/", "compilecache/", "faults/",
                     "obs/", "pd/", "analysis/calibrate.py",
                     "utils/metrics.py", "utils/poolmgr.py")

# calls that block the OS thread (sleep, file IO, device sync).
# Condition.wait is exempt — it releases the lock while sleeping.
_BLOCKING_NAMES = frozenset({
    "sleep", "flock", "lockf", "fsync", "fdatasync",
    "block_until_ready", "device_get", "urlopen",
})

_LOCK_FACTORIES = ("Lock", "RLock", "Condition")
_MUTATORS = frozenset({
    "pop", "append", "add", "remove", "discard", "clear", "update",
    "setdefault", "extend", "popitem", "insert", "appendleft",
})

_CTOR_NAMES = ("__init__", "__new__", "__post_init__")

_MAX_DEPTH = 5


def _call_name(call: ast.Call) -> str:
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


def _is_self_attr(node) -> Optional[str]:
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name) and node.value.id == "self":
        return node.attr
    return None


@dataclass(frozen=True)
class LockNode:
    name: str           # "rel::Class.attr" or "rel::NAME"
    rel: str
    line: int           # allocation-call line (locksan maps frames here)
    kind: str           # "lock" | "rlock" | "condition"
    reentrant: bool

    def hot(self) -> bool:
        return self.rel.startswith(HOT_LOCK_PREFIXES)


@dataclass
class _Write:
    cls: str
    attr: str
    line: int
    qual: str
    lockset: FrozenSet[str]
    rmw: bool


@dataclass
class ModuleModel:
    rel: str
    locks: Dict[str, LockNode] = field(default_factory=dict)
    edges: Set[Tuple[str, str]] = field(default_factory=set)
    # (held lockset, target rel or "pkg/" prefix, line) seam records
    ext_calls: List[Tuple[FrozenSet[str], str, int]] = \
        field(default_factory=list)
    writes: List[_Write] = field(default_factory=list)
    blocking: List[Tuple[str, str, int, str]] = field(default_factory=list)
    cv_issues: List[Tuple[int, str, str]] = field(default_factory=list)
    roots: Dict[str, FrozenSet[str]] = field(default_factory=dict)
    n_funcs: int = 0


class _ModuleScan:
    """One module's slice of the whole-program model."""

    def __init__(self, rel: str, src: str, tree: ast.Module,
                 all_rels: Set[str]):
        self.rel = rel
        self.lines = src.splitlines()
        self.tree = tree
        self.all_rels = all_rels
        self.m = ModuleModel(rel)
        self.imports: Dict[str, str] = {}       # local name -> rel|"pkg/"
        # (cls, attr) -> (node name, kind); "" cls = module level
        self.lock_attrs: Dict[Tuple[str, str], Tuple[str, str]] = {}
        self.alloc_index: Dict[int, str] = {}   # line -> node name
        self.attr_mod: Dict[Tuple[str, str], str] = {}
        self.meth_mod: Dict[Tuple[str, str], str] = {}
        self.units: Dict[str, Tuple[ast.AST, str]] = {}  # qual->(fn, cls)
        self.calls: Dict[str, Set[str]] = {}
        self.thread_targets: Set[str] = set()
        self._visited: Set[Tuple[str, FrozenSet[str], bool]] = set()
        self._walked: Set[str] = set()
        self._ctor_ctx = False

    def waived(self, line: int) -> bool:
        return 1 <= line <= len(self.lines) and \
            bool(_WAIVER.search(self.lines[line - 1]))

    # ----------------------------------------------------------------- #
    # imports
    # ----------------------------------------------------------------- #
    def _scan_imports(self) -> None:
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.ImportFrom):
                continue
            modparts = [p for p in (node.module or "").split(".") if p]
            if node.level == 0:
                if not modparts or modparts[0] != "tidb_tpu":
                    continue
                target = modparts[1:]
            else:
                pkg = self.rel.split("/")[:-1]
                if node.level - 1 > len(pkg):
                    continue
                base = pkg[:len(pkg) - (node.level - 1)] if node.level > 1 \
                    else pkg
                target = base + modparts
            for a in node.names:
                name = a.asname or a.name
                cand = "/".join(target + [a.name]) + ".py"
                if cand in self.all_rels:
                    self.imports[name] = cand
                    continue
                owner = "/".join(target) + ".py"
                if owner in self.all_rels:
                    self.imports[name] = owner
                elif ("/".join(target) + "/__init__.py") in self.all_rels:
                    self.imports[name] = "/".join(target) + "/"

    # ----------------------------------------------------------------- #
    # lock allocation sites
    # ----------------------------------------------------------------- #
    def _lock_kind(self, call: ast.Call) -> Optional[str]:
        name = _call_name(call)
        if name in _LOCK_FACTORIES:
            return name.lower()
        if name == "field":  # dataclass field(default_factory=threading.X)
            for kw in call.keywords:
                if kw.arg == "default_factory" and \
                        isinstance(kw.value, (ast.Attribute, ast.Name)):
                    fn = kw.value.attr if isinstance(kw.value, ast.Attribute) \
                        else kw.value.id
                    if fn in _LOCK_FACTORIES:
                        return fn.lower()
        return None

    def _add_lock(self, cls: str, attr: str, kind: str,
                  call: ast.Call) -> None:
        if (cls, attr) in self.lock_attrs:
            return
        # Condition(self._mu) / Condition(_MU) aliases the wrapped lock
        if kind == "condition" and call.args and \
                _call_name(call) == "Condition":
            arg = call.args[0]
            wrapped = _is_self_attr(arg)
            if wrapped and (cls, wrapped) in self.lock_attrs:
                node, _k = self.lock_attrs[(cls, wrapped)]
                self.lock_attrs[(cls, attr)] = (node, "condition")
                self.alloc_index.setdefault(call.lineno, node)
                return
            if isinstance(arg, ast.Name) and \
                    ("", arg.id) in self.lock_attrs:
                node, _k = self.lock_attrs[("", arg.id)]
                self.lock_attrs[(cls, attr)] = (node, "condition")
                self.alloc_index.setdefault(call.lineno, node)
                return
        name = f"{self.rel}::{cls}.{attr}" if cls else f"{self.rel}::{attr}"
        # a bare Condition() wraps an RLock internally
        reentrant = kind == "rlock" or (kind == "condition" and
                                        not call.args)
        ln = LockNode(name, self.rel, call.lineno, kind, reentrant)
        self.m.locks[name] = ln
        self.lock_attrs[(cls, attr)] = (name, kind)
        self.alloc_index[call.lineno] = name

    def _scan_locks(self) -> None:
        # module level first so Condition(_MU) aliasing resolves
        for node in self.tree.body:
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Call):
                kind = self._lock_kind(node.value)
                if kind:
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            self._add_lock("", t.id, kind, node.value)
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            for sub in ast.walk(node):
                if isinstance(sub, ast.Assign) and \
                        isinstance(sub.value, ast.Call):
                    kind = self._lock_kind(sub.value)
                    if not kind:
                        continue
                    for t in sub.targets:
                        attr = _is_self_attr(t)
                        if attr:
                            self._add_lock(node.name, attr, kind,
                                           sub.value)
                        elif isinstance(t, ast.Name) and \
                                sub in node.body:
                            self._add_lock(node.name, t.id, kind,
                                           sub.value)
                elif isinstance(sub, ast.AnnAssign) and \
                        isinstance(sub.value, ast.Call) and \
                        isinstance(sub.target, ast.Name) and \
                        sub in node.body:
                    kind = self._lock_kind(sub.value)
                    if kind:
                        self._add_lock(node.name, sub.target.id, kind,
                                       sub.value)

    # ----------------------------------------------------------------- #
    # constructor-typed attributes: self.x = ImportedClass(...) means
    # calls on self.x land in ImportedClass's module
    # ----------------------------------------------------------------- #
    def _expr_module(self, expr, local_mod: Dict[str, str],
                     cls: str = "") -> Optional[str]:
        if isinstance(expr, ast.Name):
            return self.imports.get(expr.id) or local_mod.get(expr.id)
        if isinstance(expr, ast.Call):
            f = expr.func
            if isinstance(f, ast.Name):
                if f.id in SEAM_GETTERS:
                    return SEAM_GETTERS[f.id]
                return self.imports.get(f.id)
            if isinstance(f, ast.Attribute):
                attr = _is_self_attr(f.value)
                if attr is not None and (cls, attr) in self.attr_mod:
                    return self.attr_mod[(cls, attr)]
                if attr is None and isinstance(f.value, ast.Name):
                    got = self.imports.get(f.value.id) or \
                        local_mod.get(f.value.id)
                    if got:
                        return got
                if isinstance(f.value, ast.Call):
                    return self._expr_module(f.value, local_mod, cls)
        return None

    def _scan_attr_types(self) -> None:
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            for fn in node.body:
                if not isinstance(fn, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    continue
                local_mod: Dict[str, str] = {}
                for sub in ast.walk(fn):
                    if isinstance(sub, ast.Assign) and len(sub.targets) == 1:
                        mod = self._expr_module(sub.value, local_mod,
                                                node.name)
                        if not mod:
                            continue
                        t = sub.targets[0]
                        attr = _is_self_attr(t)
                        if attr:
                            self.attr_mod[(node.name, attr)] = mod
                        elif isinstance(t, ast.Name):
                            local_mod[t.id] = mod
                    elif isinstance(sub, ast.Return) and sub.value:
                        mod = self._expr_module(sub.value, local_mod,
                                                node.name)
                        if mod:
                            self.meth_mod[(node.name, fn.name)] = mod

    # ----------------------------------------------------------------- #
    # unit collection + intra-module call graph + thread roots
    # ----------------------------------------------------------------- #
    def _collect_units(self, body, prefix: str, cls: str) -> None:
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = prefix + node.name
                self.units[qual] = (node, cls)
                self._collect_units(node.body, qual + ".", cls)
            elif isinstance(node, ast.ClassDef):
                self._collect_units(node.body, node.name + ".", node.name)

    def _scan_calls(self) -> None:
        for qual, (fn, cls) in self.units.items():
            out: Set[str] = set()
            for node in ast.walk(fn):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) and node is not fn:
                    continue
                if isinstance(node, ast.Call):
                    f = node.func
                    if isinstance(f, ast.Attribute):
                        if isinstance(f.value, ast.Name) and \
                                f.value.id == "self" and \
                                f"{cls}.{f.attr}" in self.units:
                            out.add(f"{cls}.{f.attr}")
                    elif isinstance(f, ast.Name):
                        for cand in (f"{qual}.{f.id}", f"{cls}.{f.id}",
                                     f.id, f"{f.id}.__init__"):
                            if cand in self.units:
                                out.add(cand)
                                break
                    # Thread(target=fn) / Timer(..., fn) spawn sites
                    if _call_name(node) in ("Thread", "Timer"):
                        for kw in node.keywords:
                            if kw.arg == "target" and \
                                    isinstance(kw.value, ast.Name):
                                for cand in (f"{qual}.{kw.value.id}",
                                             f"{cls}.{kw.value.id}",
                                             kw.value.id):
                                    if cand in self.units:
                                        self.thread_targets.add(cand)
                                        break
            self.calls[qual] = out

    def _assign_roots(self) -> None:
        roots: Dict[str, Set[str]] = {q: set() for q in self.units}
        for root, relpat, rx in ROOT_ENTRIES:
            if relpat.endswith("/"):
                if not self.rel.startswith(relpat):
                    continue
            elif relpat != self.rel:
                continue
            pat = re.compile(rx)
            for q in self.units:
                if pat.search(q):
                    roots[q].add(root)
        for q in self.thread_targets:
            if not roots[q]:
                roots[q].add("bg")
        # propagate caller -> callee to a fixpoint
        changed = True
        while changed:
            changed = False
            for q, callees in self.calls.items():
                for c in callees:
                    if roots[q] - roots[c]:
                        roots[c] |= roots[q]
                        changed = True
        # nested defs with no roots inherit the enclosing function's
        # (callbacks handed out by the parent run where the parent ran)
        for q in sorted(self.units, key=len):
            if roots[q]:
                continue
            parent = q.rsplit(".", 1)[0] if "." in q else ""
            if parent in self.units and roots.get(parent):
                roots[q] |= roots[parent]
        default = next(r for p, r in MODULE_ROOTS
                       if self.rel.startswith(p) or p == "")
        for q in self.units:
            self.m.roots[q] = frozenset(roots[q] or default)
        self.m.n_funcs = len(self.units)

    # ----------------------------------------------------------------- #
    # lockset traversal
    # ----------------------------------------------------------------- #
    def _resolve_lock(self, expr, cls: str) -> Optional[str]:
        attr = _is_self_attr(expr)
        if attr is not None:
            got = self.lock_attrs.get((cls, attr))
            return got[0] if got else None
        if isinstance(expr, ast.Name):
            got = self.lock_attrs.get(("", expr.id)) or \
                self.lock_attrs.get((cls, expr.id))
            return got[0] if got else None
        return None

    def _lock_kind_of(self, expr, cls: str) -> Optional[str]:
        attr = _is_self_attr(expr)
        if attr is not None:
            got = self.lock_attrs.get((cls, attr))
            return got[1] if got else None
        if isinstance(expr, ast.Name):
            got = self.lock_attrs.get(("", expr.id)) or \
                self.lock_attrs.get((cls, expr.id))
            return got[1] if got else None
        return None

    def _resolve_target(self, call: ast.Call, cls: str,
                        local_mod: Dict[str, str]) -> Optional[str]:
        f = call.func
        if isinstance(f, ast.Name):
            if f.id in SEAM_GETTERS:
                return SEAM_GETTERS[f.id]
            return self.imports.get(f.id)
        if isinstance(f, ast.Attribute):
            attr = _is_self_attr(f.value)
            if attr is not None:
                return self.attr_mod.get((cls, attr))
            if isinstance(f.value, ast.Name):
                return self.imports.get(f.value.id) or \
                    local_mod.get(f.value.id)
            if isinstance(f.value, ast.Call):
                inner = f.value.func
                if isinstance(inner, ast.Name):
                    if inner.id in SEAM_GETTERS:
                        return SEAM_GETTERS[inner.id]
                    return self.imports.get(inner.id)
                a = _is_self_attr(inner) if isinstance(inner, ast.Attribute) \
                    else None
                if isinstance(inner, ast.Attribute):
                    ia = _is_self_attr(inner.value)
                    if ia is not None:
                        return self.attr_mod.get((cls, ia))
                    if _is_self_attr(inner) is None and \
                            isinstance(inner.value, ast.Name) and \
                            inner.value.id == "self":
                        return self.meth_mod.get((cls, inner.attr))
                if a is not None:
                    return self.meth_mod.get((cls, a))
        return None

    def _record_edge(self, held: List[str], lock: str) -> None:
        for h in held:
            if h != lock:
                self.m.edges.add((h, lock))

    def _scan_exprs(self, exprs, held: List[str], cls: str, qual: str,
                    local_mod: Dict[str, str], while_depth: int) -> None:
        """Leaf-expression scan: acquire/release tracking, seam calls,
        blocking calls, cv waits."""
        for expr in exprs:
            if expr is None:
                continue
            for node in ast.walk(expr):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                name = _call_name(node)
                if isinstance(f, ast.Attribute) and \
                        name in ("acquire", "release"):
                    lk = self._resolve_lock(f.value, cls)
                    if lk:
                        if name == "acquire":
                            self._record_edge(held, lk)
                            held.append(lk)
                        elif lk in held:
                            held.remove(lk)
                        continue
                if isinstance(f, ast.Attribute) and \
                        name in ("wait", "wait_for"):
                    kind = self._lock_kind_of(f.value, cls)
                    if kind == "condition" and name == "wait" and \
                            while_depth == 0:
                        self.m.cv_issues.append((
                            node.lineno, qual,
                            "Condition.wait() outside a while predicate "
                            "loop — wakeups are advisory, re-check state"))
                    continue
                # intra-module call chain: inherit the caller's lockset
                target_unit = None
                if isinstance(f, ast.Attribute) and \
                        isinstance(f.value, ast.Name) and \
                        f.value.id == "self" and \
                        f"{cls}.{f.attr}" in self.units:
                    target_unit = f"{cls}.{f.attr}"
                elif isinstance(f, ast.Name):
                    for cand in (f"{qual}.{f.id}", f"{cls}.{f.id}", f.id,
                                 f"{f.id}.__init__"):
                        if cand in self.units:
                            target_unit = cand
                            break
                if target_unit:
                    self._walk_unit(target_unit, list(held),
                                    ctor=self._ctor_ctx)
                    continue
                if held:
                    if name in _BLOCKING_NAMES or \
                            (isinstance(f, ast.Name) and f.id == "open"):
                        hot = [h for h in held
                               if h in self.m.locks and
                               self.m.locks[h].hot()]
                        # cross-module: any held node counts (resolved
                        # at assembly); here only this module's
                        if hot:
                            self.m.blocking.append(
                                (hot[0], name or "open", node.lineno,
                                 qual))
                    target = self._resolve_target(node, cls, local_mod)
                    if target and target != self.rel:
                        self.m.ext_calls.append(
                            (frozenset(held), target, node.lineno))

    def _scan_writes(self, stmt, held: List[str], cls: str,
                     qual: str, ctor: bool) -> None:
        if not cls or ctor:
            return
        targets: List[Tuple[str, bool]] = []
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                attr = _is_self_attr(t)
                if attr:
                    rmw = any(_is_self_attr(n) == attr
                              for n in ast.walk(stmt.value))
                    targets.append((attr, rmw))
        elif isinstance(stmt, ast.AugAssign):
            attr = _is_self_attr(stmt.target)
            if attr:
                targets.append((attr, True))
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            attr = _is_self_attr(stmt.target)
            if attr:
                targets.append((attr, False))
        for attr, rmw in targets:
            if (cls, attr) in self.lock_attrs:
                continue  # the lock object itself
            self.m.writes.append(_Write(cls, attr, stmt.lineno, qual,
                                        frozenset(held), rmw))

    def _body_has_state_write(self, body) -> bool:
        for stmt in body:
            for node in ast.walk(stmt):
                if isinstance(node, (ast.Assign, ast.AugAssign,
                                     ast.AnnAssign, ast.Delete)):
                    return True
                if isinstance(node, ast.Call) and \
                        _call_name(node) in _MUTATORS:
                    return True
        return False

    def _walk_body(self, body, held: List[str], cls: str, qual: str,
                   local_mod: Dict[str, str], while_depth: int,
                   depth: int) -> None:
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue  # separate units
            if isinstance(stmt, (ast.With, ast.AsyncWith)):
                inner = list(held)
                cv_locks = []
                for item in stmt.items:
                    self._scan_exprs([item.context_expr], inner, cls,
                                     qual, local_mod, while_depth)
                    lk = self._resolve_lock(item.context_expr, cls)
                    if lk:
                        self._record_edge(inner, lk)
                        inner.append(lk)
                        if self._lock_kind_of(item.context_expr,
                                              cls) == "condition":
                            cv_locks.append((item.context_expr, lk))
                self._walk_body(stmt.body, inner, cls, qual, local_mod,
                                while_depth, depth)
                for expr, _lk in cv_locks:
                    self._check_notify(stmt, expr, cls, qual)
                continue
            if isinstance(stmt, ast.While):
                self._scan_exprs([stmt.test], held, cls, qual,
                                 local_mod, while_depth)
                self._walk_body(stmt.body, held, cls, qual, local_mod,
                                while_depth + 1, depth)
                self._walk_body(stmt.orelse, held, cls, qual, local_mod,
                                while_depth, depth)
                continue
            if isinstance(stmt, (ast.For, ast.AsyncFor)):
                self._scan_exprs([stmt.iter], held, cls, qual,
                                 local_mod, while_depth)
                self._walk_body(stmt.body, held, cls, qual, local_mod,
                                while_depth, depth)
                self._walk_body(stmt.orelse, held, cls, qual, local_mod,
                                while_depth, depth)
                continue
            if isinstance(stmt, ast.If):
                self._scan_exprs([stmt.test], held, cls, qual,
                                 local_mod, while_depth)
                self._walk_body(stmt.body, held, cls, qual, local_mod,
                                while_depth, depth)
                self._walk_body(stmt.orelse, held, cls, qual, local_mod,
                                while_depth, depth)
                continue
            if isinstance(stmt, ast.Try):
                self._walk_body(stmt.body, held, cls, qual, local_mod,
                                while_depth, depth)
                for h in stmt.handlers:
                    self._walk_body(h.body, held, cls, qual, local_mod,
                                    while_depth, depth)
                self._walk_body(stmt.orelse, held, cls, qual, local_mod,
                                while_depth, depth)
                self._walk_body(stmt.finalbody, held, cls, qual,
                                local_mod, while_depth, depth)
                continue
            # leaf statement
            self._scan_writes(stmt, held, cls, qual, self._ctor_ctx)
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name):
                mod = self._expr_module(stmt.value, local_mod, cls)
                if mod:
                    local_mod[stmt.targets[0].id] = mod
            exprs = [getattr(stmt, fld, None)
                     for fld in ("value", "test", "exc", "msg")]
            if isinstance(stmt, (ast.Assign, ast.AugAssign,
                                 ast.AnnAssign, ast.Return, ast.Expr,
                                 ast.Raise, ast.Assert, ast.Delete)):
                self._scan_exprs([e for e in exprs if e is not None],
                                 held, cls, qual, local_mod, while_depth)

    def _check_notify(self, with_stmt, cv_expr, cls: str,
                      qual: str) -> None:
        notifies = []
        for node in ast.walk(with_stmt):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr in ("notify", "notify_all"):
                notifies.append(node)
        if notifies and not self._body_has_state_write(with_stmt.body):
            self.m.cv_issues.append((
                notifies[0].lineno, qual,
                "notify without a state write under the same lock — "
                "waiters have nothing new to observe"))

    def _walk_unit(self, qual: str, held: List[str], depth: int = 0,
                   ctor: bool = False) -> None:
        # a unit reached only through a constructor runs before the
        # object is shared — its writes are initialization, not races
        ctor = ctor or qual.rsplit(".", 1)[-1] in _CTOR_NAMES
        key = (qual, frozenset(held), ctor)
        if key in self._visited or depth > _MAX_DEPTH:
            return
        self._visited.add(key)
        self._walked.add(qual)
        fn, cls = self.units[qual]
        prev = self._ctor_ctx
        self._ctor_ctx = ctor
        try:
            self._walk_body(fn.body, list(held), cls, qual, {}, 0,
                            depth + 1)
        finally:
            self._ctor_ctx = prev

    def run(self) -> ModuleModel:
        self._scan_imports()
        self._scan_locks()
        self._scan_attr_types()
        self._collect_units(self.tree.body, "", "")
        self._scan_calls()
        self._assign_roots()
        called = set()
        for callees in self.calls.values():
            called |= callees
        for qual in self.units:
            if qual not in called:
                self._walk_unit(qual, [])
        for qual in self.units:   # call-graph cycles with no entry
            if qual not in self._walked:
                self._walk_unit(qual, [])
        return self.m


# --------------------------------------------------------------------- #
# whole-program assembly
# --------------------------------------------------------------------- #

@dataclass
class ConcurrencyModel:
    modules: Dict[str, "_ModuleScan"] = field(default_factory=dict)
    locks: Dict[str, LockNode] = field(default_factory=dict)
    edges: Set[Tuple[str, str]] = field(default_factory=set)
    alloc_index: Dict[Tuple[str, int], str] = field(default_factory=dict)
    findings: List[Finding] = field(default_factory=list)
    excluded: Dict[str, str] = field(default_factory=dict)

    def summary(self) -> dict:
        return {
            "modules": len(self.modules),
            "excluded": len(self.excluded),
            "locks": len(self.locks),
            "edges": len(self.edges),
            "roots": len(THREAD_ROOTS),
            "findings": len(self.findings),
        }


def discover_threaded_modules(root: Optional[str] = None):
    """(rel -> source) for every tidb_tpu module importing threading,
    plus the excluded map.  No hand list: the import IS the contract."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    srcs: Dict[str, str] = {}
    all_rels: Set[str] = set()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames
                             if d not in ("__pycache__", "native"))
        for fname in sorted(filenames):
            if not fname.endswith(".py"):
                continue
            full = os.path.join(dirpath, fname)
            rel = os.path.relpath(full, root).replace(os.sep, "/")
            all_rels.add(rel)
            with open(full, encoding="utf-8") as f:
                srcs[rel] = f.read()
    threaded: Dict[str, str] = {}
    excluded: Dict[str, str] = {}
    for rel, src in sorted(srcs.items()):
        try:
            tree = ast.parse(src)
        except SyntaxError:
            continue  # lint reports TPU-SYNTAX
        if not module_imports_threading(tree):
            continue
        if rel in LOCK_EXCLUDES:
            excluded[rel] = LOCK_EXCLUDES[rel]
            continue
        threaded[rel] = src
    return threaded, excluded, all_rels


def _expand_target(target: str, by_rel: Dict[str, List[str]]) -> List[str]:
    if target.endswith("/"):
        out: List[str] = []
        for rel, names in by_rel.items():
            if rel.startswith(target):
                out.extend(names)
        return out
    return by_rel.get(target, [])


def _tarjan_sccs(nodes, edges) -> List[List[str]]:
    adj: Dict[str, List[str]] = {n: [] for n in nodes}
    for a, b in edges:
        if a in adj and b in adj:
            adj[a].append(b)
    index: Dict[str, int] = {}
    low: Dict[str, int] = {}
    on: Set[str] = set()
    stack: List[str] = []
    sccs: List[List[str]] = []
    counter = [0]

    def strong(v):  # iterative Tarjan
        work = [(v, 0)]
        while work:
            node, pi = work[-1]
            if pi == 0:
                index[node] = low[node] = counter[0]
                counter[0] += 1
                stack.append(node)
                on.add(node)
            recurse = False
            for i in range(pi, len(adj[node])):
                w = adj[node][i]
                if w not in index:
                    work[-1] = (node, i + 1)
                    work.append((w, 0))
                    recurse = True
                    break
                if w in on:
                    low[node] = min(low[node], index[w])
            if recurse:
                continue
            if low[node] == index[node]:
                scc = []
                while True:
                    w = stack.pop()
                    on.discard(w)
                    scc.append(w)
                    if w == node:
                        break
                if len(scc) > 1:
                    sccs.append(sorted(scc))
            work.pop()
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[node])

    for n in sorted(nodes):
        if n not in index:
            strong(n)
    return sccs


def build_model(root: Optional[str] = None) -> ConcurrencyModel:
    threaded, excluded, all_rels = discover_threaded_modules(root)
    model = ConcurrencyModel(excluded=excluded)
    scans: Dict[str, _ModuleScan] = {}
    for rel, src in threaded.items():
        scan = _ModuleScan(rel, src, ast.parse(src), all_rels)
        scan.run()
        scans[rel] = scan
        model.modules[rel] = scan
        model.locks.update(scan.m.locks)
        for line, name in scan.alloc_index.items():
            model.alloc_index[(rel, line)] = name
    by_rel: Dict[str, List[str]] = {}
    for name, ln in model.locks.items():
        by_rel.setdefault(ln.rel, []).append(name)
    for rel, scan in scans.items():
        model.edges |= scan.m.edges
        for held, target, _line in scan.m.ext_calls:
            for tgt in _expand_target(target, by_rel):
                for h in held:
                    if h != tgt:
                        model.edges.add((h, tgt))
    model.findings = _emit_findings(model)
    return model


def _emit_findings(model: ConcurrencyModel) -> List[Finding]:
    findings: List[Finding] = []
    for rel, scan in sorted(model.modules.items()):
        m = scan.m
        # RACE-UNGUARDED-WRITE / RACE-GUARD-MIX
        groups: Dict[Tuple[str, str], List[_Write]] = {}
        for w in m.writes:
            groups.setdefault((w.cls, w.attr), []).append(w)
        for (cls, attr), ws in sorted(groups.items()):
            bad = [w for w in ws if w.rmw and not w.lockset and
                   (len(m.roots.get(w.qual, frozenset())) >= 2 or
                    m.roots.get(w.qual, frozenset()) & MULTI_ROOTS)]
            bad = [w for w in bad if not scan.waived(w.line)]
            if bad:
                w = min(bad, key=lambda w: w.line)
                roots = ",".join(sorted(m.roots.get(w.qual, frozenset())))
                findings.append(Finding(
                    RULE_UNGUARDED, rel, w.line, f"{cls}.{attr}",
                    f"read-modify-write of self.{attr} with no lock "
                    f"held, reachable from thread roots [{roots}] — "
                    f"lost updates under the free-threaded interpreter "
                    f"and racy even today"))
            locked = [w for w in ws if w.lockset]
            locksets = {w.lockset for w in locked}
            if len(locksets) >= 2:
                common = frozenset.intersection(*locksets)
                if not common:
                    sites = sorted(locked, key=lambda w: w.line)
                    if not any(scan.waived(w.line) for w in sites):
                        names = " vs ".join(sorted(
                            "{" + ",".join(s.split("::")[-1]
                                           for s in sorted(ls)) + "}"
                            for ls in locksets))
                        findings.append(Finding(
                            RULE_GUARD_MIX, rel, sites[0].line,
                            f"{cls}.{attr}",
                            f"self.{attr} written under disjoint locks "
                            f"({names}) — no common guard, mutual "
                            f"exclusion in name only"))
        # LOCK-BLOCKING-HELD
        seen_b = set()
        for node, call, line, qual in sorted(m.blocking):
            if scan.waived(line) or (node, qual, call) in seen_b:
                continue
            seen_b.add((node, qual, call))
            findings.append(Finding(
                RULE_BLOCKING, rel, line, qual,
                f"{call}() while holding hot-path lock "
                f"{node.split('::')[-1]} — stalls every thread queued "
                f"on it"))
        # LOCK-CV-PREDICATE
        seen_cv = set()
        for line, qual, msg in sorted(m.cv_issues):
            if scan.waived(line) or (qual, msg) in seen_cv:
                continue
            seen_cv.add((qual, msg))
            findings.append(Finding(RULE_CV, rel, line, qual, msg))
    # LOCK-ORDER-CYCLE: global SCCs over the full acquisition graph
    for scc in _tarjan_sccs(set(model.locks), model.edges):
        first = model.locks[scc[0]]
        sig = "~".join(n.split("::")[-1] for n in scc)
        findings.append(Finding(
            RULE_CYCLE, first.rel, first.line, sig,
            f"lock-order cycle across {len(scc)} locks "
            f"({' -> '.join(scc)}) — opposite acquisition orders can "
            f"deadlock"))
    seen, out = set(), []
    for f in findings:
        k = (f.rule, f.path, f.line, f.symbol)
        if k not in seen:
            seen.add(k)
            out.append(f)
    out.sort(key=lambda f: (f.path, f.line, f.rule))
    return out


_MODEL_CACHE: Dict[str, ConcurrencyModel] = {}


def cached_model(root: Optional[str] = None) -> ConcurrencyModel:
    key = root or "<pkg>"
    if key not in _MODEL_CACHE:
        _MODEL_CACHE[key] = build_model(root)
    return _MODEL_CACHE[key]


def concurrency_findings(root: Optional[str] = None) -> List[Finding]:
    return list(cached_model(root).findings)


def analyze_source(src: str, rel: str,
                   all_rels: Optional[Set[str]] = None) -> List[Finding]:
    """Single-module analysis (tests seed violations through this):
    same extraction + rules, no cross-module seams."""
    scan = _ModuleScan(rel, src, ast.parse(src), all_rels or {rel})
    scan.run()
    model = ConcurrencyModel()
    model.modules[rel] = scan
    model.locks.update(scan.m.locks)
    model.edges |= scan.m.edges
    return _emit_findings(model)


def race_report(root: Optional[str] = None) -> str:
    """Per-module locks/edges/roots/findings table (--race-report)."""
    model = cached_model(root)
    per_mod: Dict[str, int] = {}
    for f in model.findings:
        per_mod[f.path] = per_mod.get(f.path, 0) + 1
    lines = ["copsan concurrency model — auto-discovered threading "
             "modules", ""]
    lines.append(f"{'module':<34} {'locks':>5} {'edges':>5} "
                 f"{'funcs':>5} {'finds':>5}  roots")
    for rel in sorted(model.modules):
        m = model.modules[rel].m
        roots = sorted({r for rs in m.roots.values() for r in rs})
        lines.append(f"{rel:<34} {len(m.locks):>5} {len(m.edges):>5} "
                     f"{m.n_funcs:>5} {per_mod.get(rel, 0):>5}  "
                     f"{','.join(roots)}")
    s = model.summary()
    lines.append("")
    for rel, why in sorted(model.excluded.items()):
        lines.append(f"excluded: {rel} — {why}")
    lines.append(f"total: {s['modules']} modules, {s['locks']} locks, "
                 f"{s['edges']} acquisition edges, "
                 f"{s['findings']} findings")
    return "\n".join(lines)


__all__ = [
    "CONCURRENCY_RULES", "THREAD_ROOTS", "MULTI_ROOTS", "MODULE_ROOTS",
    "ROOT_ENTRIES", "SEAM_GETTERS", "LockNode", "ConcurrencyModel",
    "discover_threaded_modules", "build_model", "cached_model",
    "concurrency_findings", "analyze_source", "race_report",
    "RULE_UNGUARDED", "RULE_GUARD_MIX", "RULE_CYCLE", "RULE_BLOCKING",
    "RULE_CV",
]
