"""TPU-hygiene linter: AST rules for the failure modes a compiled
coprocessor engine actually hits.

General-purpose linters don't know that `int(x)` inside a traced device
function forces a host sync (ConcretizationTypeError at best, a silent
recompile-per-value at worst), that `id(...)` inside a cache-key builder
makes program dedup keys die with the process, or that the admission
scheduler's drain loop must never invert the lock order the pool manager
uses.  These rules do; they are scoped to the modules where each hazard
is real, and every pre-existing accepted finding lives in
analysis/baseline.txt so only NEW findings fail the gate.

Rules
-----
- TPU-TRACE-LEAK   float()/int()/bool()/np.asarray() on non-literal
                   values inside modules whose code is traced wholesale
                   into device programs (copr/exec, copr/join,
                   parallel/spmd|shuffle|window|exchange).  These force
                   tracer concretization / host round-trips.
- TPU-DIGEST       id(...) or unordered dict iteration inside a digest
                   context (a function or assignment target whose name
                   contains key/digest/token/fingerprint/signature):
                   process-local or order-unstable values poison
                   program/task cache keys across mesh rebuilds.
- TPU-HOST-SYNC    jax.device_get(...) / .item() in hot-path modules
                   (traced modules + sched/): a host sync inside the
                   admission/launch path serializes the device pipeline.
- TPU-BROAD-EXCEPT bare `except:` or `except Exception/BaseException:`
                   whose handler does not re-raise: swallows real codec/
                   arith/driver errors.  Waived by `# noqa: BLE001` with
                   a justification or a `planlint: ok` comment.
- TPU-LOCK-ORDER   across sched/scheduler.py, utils/poolmgr.py,
                   utils/rwlock.py, store/client.py: nested acquisition
                   of the same non-reentrant lock (self-deadlock, incl.
                   Condition(lock) aliasing) and inverted acquisition
                   order between two locks observed in the same class.
- TPU-PSUM-FENCE   lax.psum in a traced module whose module does not
                   also implement the 2^31 limb-exactness fence (a
                   `*psum_limb_fence*` guard plus an OverflowError
                   raise): int/decimal SUM (hi, lo) limb states merged
                   by an UNFENCED in-program psum silently wrap past
                   2^31 contributing rows — wrong answers, no error.
- TPU-DTYPE-X64    weak-typed jnp array creation in a traced module
                   (jnp.arange/zeros/ones/full/linspace/eye with no
                   dtype, or a jnp.int64/uint64/float64 scalar
                   constructor): these produce 64-bit values only
                   because tidb_tpu/__init__ turns jax_enable_x64 on.
                   An embedder that leaves JAX's x64-disabled default in
                   place gets silently truncated int32/float32 lanes on
                   TPU — wrong join keys and sums, green CPU tests.
                   Pin dtype= explicitly.
- TPU-RETRY-BUDGET an unconditional retry loop (`while True:`) in a
                   sched/ or store/ module that SLEEPS (time.sleep or
                   any *sleep* callable) without consulting a Backoffer
                   budget: a blind sleep-and-redispatch loop retries
                   forever with no typed budget, no attempt history and
                   no RetryBudgetExceeded surfacing — route every
                   re-dispatch sleep through store/backoff.Backoffer.
- TPU-DONATE       a ``donate_argnums=``/``donate_argnames=`` keyword in
                   a traced module whose value is a non-empty literal,
                   or an expression that does not reference a
                   DonationPlan-derived symbol (a name/attribute
                   containing ``donat``): donation deletes the caller's
                   arrays, so the ONLY legitimate source of argnums is
                   the statically verified analysis/lifetime
                   DonationPlan — a hand-written literal silently
                   deletes snapshot residents or regrow inputs.
- TPU-CALIB-CLAMP  a multiplication by a measured cost-correction
                   factor (``time_factor`` / ``mem_factor`` /
                   ``*correction_factor*``) in a function that never
                   references the clamp (``clamp_factor`` /
                   ``CALIB_CLAMP_*`` — analysis/calibrate): measured
                   feedback may BEND the static LaunchCost model,
                   never replace it — an unclamped factor lets one bad
                   measurement starve or flood admission, pricing, and
                   the HBM budget.  Applies repo-wide (any module may
                   grow a calibration consumer).
- TPU-COMPILE-KEY  a serialize/deserialize/cache-write seam in
                   compilecache/ whose enclosing function does not
                   reference the persistent-key triple — a ``digest``
                   symbol, a mesh fingerprint (``mesh``/``fingerprint``)
                   and the donation plan (``donat``): an executable
                   persisted (or loaded) without the full key anatomy
                   can silently deserialize a stale or wrong-variant
                   program after a restart (mirrors TPU-DIGEST for the
                   on-disk half of the program cache).
- TPU-SHARD-CONST  a collective call (lax.all_to_all / all_gather /
                   psum / pmin / pmax / ppermute / axis_index) or a
                   PartitionSpec in a traced module whose mesh-axis
                   argument is a raw string literal instead of a
                   reference to the mesh/topology symbol
                   (parallel/topology.SHARD_AXIS): a literal axis name
                   desynchronizes silently when the topology model
                   renames or factors an axis — the program traces fine
                   and exchanges over the wrong (or a stale) axis.
- TPU-SPAN-LEAK   a time.perf_counter[_ns]() latency measurement in
                   sched/, copr/, or compilecache/ whose enclosing
                   function feeds a latency counter (an augmented
                   ``+=`` into a ``*_ns``/``*_ms``/``*_us``/``*_total``
                   /``*_seconds`` target) WITHOUT recording through the
                   copscope obs API (a span/trace reference or a
                   histogram ``observe``): a latency number that only
                   lands in an ad-hoc counter is invisible to TRACE,
                   the flight recorder, and the latency histograms —
                   route every measured duration through obs/.
- TPU-NARROW-CAST  a bit-narrowing ``.astype(...)`` (int8/16/32,
                   uint8/16/32, float16/bfloat16/float32 target) in a
                   traced module: a traced cast cannot raise on values
                   that do not fit — high bits (or mantissa digits)
                   vanish silently on device.  Every narrowing cast
                   must carry a ``# valueflow: ok - <why>`` proof
                   reference (the value-range argument that the lane's
                   interval fits the target, analysis/valueflow
                   discipline) or an explicit ``# planlint: ok``
                   waiver.  Widening casts (int64/uint64/float64) and
                   bool masks are exempt.
- TPU-PD-EPOCH     a shared-store write call (cas / txn_update /
                   delete / grant / renew / release) in pd/ whose
                   enclosing function never references the lease
                   ``epoch``: the coplace store fences dead writers
                   with lease epochs — every mutation of shared state
                   must ride a CAS carrying the member's epoch, or a
                   process whose lease expired (paused, partitioned,
                   half-dead) can clobber state the survivors already
                   repartitioned.

Inline waiver: any rule is suppressed by a `# planlint: ok` comment on
the offending line (give a reason after it).
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass
from typing import Iterable, Optional

# modules (tidb_tpu-relative, /-separated) whose function bodies are
# traced into device programs wholesale — concretization calls there are
# tracer leaks.  expr/compile.py is deliberately NOT listed: it is the
# dual-backend (np|jnp) evaluator and its host-object op implementations
# legitimately concretize when xp is numpy.
TRACED_MODULES = {
    "copr/exec.py", "copr/join.py",
    "parallel/spmd.py", "parallel/shuffle.py", "parallel/window.py",
    "parallel/exchange.py",
    # shardflow (ISSUE 12): the topology model and the sharding-flow
    # interpreter define/consume the collective axis symbols traced
    # programs bind — they obey the same hygiene rules (no stray
    # concretization, no literal axis names) so the analysis side can
    # never drift from the programs it verifies
    "parallel/topology.py", "analysis/shardflow.py",
    # coplace (ISSUE 16): the coordination plane runs on every
    # statement's tick and its payloads (quota shares, calib factors)
    # feed admission directly — same hygiene contract: no stray
    # concretization, no silent host round-trips smuggled in later
    "pd/store.py", "pd/lease.py", "pd/quota.py", "pd/registry.py",
    "pd/coordinator.py",
    # copnum (ISSUE 19): the value-range interpreter defines the
    # numeric-safety contracts traced lanes rely on (narrow SUM proofs,
    # overflow fences) — same hygiene rules as shardflow, for the same
    # reason: the analysis side must never drift from the programs it
    # verifies
    "analysis/valueflow.py",
}

# hot-path modules where a host sync stalls the launch pipeline
HOT_PATH_MODULES = TRACED_MODULES | {
    "sched/scheduler.py", "sched/task.py",
}

# copsan (ISSUE 17): the cross-layer lock-order contract is no longer
# a hand-curated module list — ANY module importing threading joins it
# automatically (module_imports_threading below; the whole-program
# model in analysis/concurrency.py consumes the same predicate).  The
# only opt-out is an explicit, justified entry here.
LOCK_EXCLUDES: dict = {
    # Add `"rel/path.py": "reason"` only when a module's thread model
    # is genuinely out of scope for the AST analysis, and say why.
    "utils/locksan.py": (
        "the sanitizer itself: it aliases the real threading factories "
        "(_REAL_LOCK = threading.Lock) and monkeypatches threading, so "
        "the AST model cannot see its _mu as a lock; its telemetry "
        "counters are deliberately approximate to keep per-acquire "
        "overhead inside the 5% budget"
    ),
}


def module_imports_threading(tree) -> bool:
    """True when the module imports threading (any form) — the auto-
    discovery predicate that retired the hand-maintained LOCK_MODULES
    set.  Importing threading IS joining the concurrency contract."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            if any(a.name == "threading" or
                   a.name.startswith("threading.") for a in node.names):
                return True
        elif isinstance(node, ast.ImportFrom):
            if (node.module or "").split(".")[0] == "threading":
                return True
    return False

# modules whose retry/re-dispatch loops must spend a typed Backoffer
# budget (TPU-RETRY-BUDGET): the device dispatch + scheduler layers
RETRY_MODULE_PREFIXES = ("sched/", "store/")

# modules whose latency measurements must flow through the copscope
# obs span/histogram API (TPU-SPAN-LEAK): the launch-path layers whose
# timings TRACE and the flight recorder attribute
SPAN_MODULE_PREFIXES = ("sched/", "copr/", "compilecache/", "pd/")
# counter targets that smell like a latency/total accumulator
_LAT_COUNTER = re.compile(r"(_ns|_ms|_us|_total|_seconds)$")
_PERF_CALL = re.compile(r"^perf_counter(_ns)?$")
# the obs API surface: span trees / TraceCtx references or a histogram
# observe — any of these in the function means the measurement is
# recorded where TRACE/recorder/histograms can see it
_OBS_REF = re.compile(r"observe|span|trace", re.IGNORECASE)

# the AOT program cache (copforge): every seam where executable bytes
# hit or leave disk must carry the digest + mesh-fingerprint +
# donation-plan triple (TPU-COMPILE-KEY)
COMPILECACHE_PREFIX = "compilecache/"

# the coplace coordination plane (TPU-PD-EPOCH): every shared-store
# mutation in pd/ must sit in a function that references the lease
# epoch — the CAS fence that refuses writes from members whose lease
# lapsed.  Call names that ARE such mutations (PdStore's write surface;
# bare `set`/`put` deliberately excluded — Gauge.set and dict puts are
# not store writes).
PD_PREFIX = "pd/"
_PD_WRITE_CALLS = re.compile(
    r"^(cas|txn_update|delete|grant|renew|release)$")
_EPOCH_REF = re.compile(r"epoch")
# receivers that are threading primitives, not the store — their
# acquire/release is lock discipline (TPU-LOCK-ORDER's concern)
_PD_LOCK_RECV = re.compile(r"mu$|mutex|lock|cond|sem", re.IGNORECASE)

# copgauge (TPU-MEM-SOURCE): modules allowed to call the raw device
# memory introspection APIs.  obs/hbm.py owns the single sanctioned
# memory_stats poll (the ledger's reconcile + the copcost auto budget
# route through it) and compilecache/ owns the compiled
# memory_analysis of served executables (the measured-watermark seam);
# a call anywhere else forks the source of memory truth away from the
# ledger.
MEM_SOURCE_MODULES = ("obs/hbm.py",)
_MEM_SOURCE_CALLS = ("memory_stats", "memory_analysis")
# call names that ARE such seams (jax.experimental.serialize_executable
# entry points plus any persist_* helper grown later)
_CACHE_WRITE_CALLS = re.compile(
    r"^(serialize|deserialize_and_load|persist\w*|_persist\w*|"
    r"write_entry\w*)$")
_KEY_TRIPLE = (("digest", re.compile(r"digest")),
               ("mesh fingerprint", re.compile(r"mesh|fingerprint")),
               ("donation plan", re.compile(r"donat")))

_DIGEST_NAME = re.compile(r"key|digest|token|fingerprint|signature",
                          re.IGNORECASE)

# measured cost-correction factors (analysis/calibrate): multiplying a
# LaunchCost term by one of these without referencing the clamp is
# unbounded feedback (TPU-CALIB-CLAMP)
_CALIB_FACTOR = re.compile(r"^(time_factor|mem_factor)$"
                           r"|correction_factor")
_CLAMP_REF = re.compile(r"clamp", re.IGNORECASE)

# collective calls whose mesh-axis argument must reference the
# mesh/topology symbol, never a raw string literal (TPU-SHARD-CONST):
# call name -> 0-based positional slot the axis may occupy
_COLLECTIVE_AXIS_CALLS = {
    "all_to_all": 1, "all_gather": 1, "psum": 1, "pmin": 1, "pmax": 1,
    "pmean": 1, "ppermute": 1, "psum_scatter": 1, "axis_index": 0,
}
# PartitionSpec constructors: every positional argument is an axis name
_PSPEC_NAMES = ("P", "PartitionSpec")

# jnp creation calls whose result dtype rides the x64 flag when no dtype
# is given, and the positional slot (0-based) a dtype may occupy.  -1 =
# dtype only arrives by keyword (arange's positionals are start/stop/
# step; linspace's are start/stop/num).
_X64_CREATORS = {"arange": -1, "zeros": 1, "ones": 1, "empty": 1,
                 "full": 2, "linspace": -1, "eye": -1}
# 64-bit scalar constructors: silently 32-bit when x64 is off
_X64_SCALARS = {"int64", "uint64", "float64"}
_WAIVER = re.compile(r"planlint:\s*ok")
_BLE_WAIVER = re.compile(r"noqa:.*BLE001|planlint:\s*ok")
# TPU-NARROW-CAST: targets that lose bits from an int64/f64 lane, and
# the proof-reference comment that clears them (a value-range argument
# in the analysis/valueflow discipline); the generic waiver also works
_NARROW_CAST_TARGETS = {"int8", "int16", "int32", "uint8", "uint16",
                        "uint32", "float16", "bfloat16", "float32"}
_NARROW_CAST_OK = re.compile(r"valueflow:\s*ok|planlint:\s*ok")


def _cast_target_name(arg: ast.AST) -> str:
    """Dtype spelled as jnp.int32 / np.int32 / int32 / 'int32'."""
    if isinstance(arg, ast.Attribute):
        return arg.attr
    if isinstance(arg, ast.Name):
        return arg.id
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value
    return ""


@dataclass
class Finding:
    rule: str
    path: str        # tidb_tpu-relative, /-separated
    line: int
    symbol: str      # enclosing Class.function qualname ('' = module)
    message: str

    def key(self) -> str:
        """Baseline identity: rule + file + enclosing symbol.  Line
        numbers are deliberately excluded so accepted findings survive
        unrelated edits to the same file."""
        return f"{self.rule} {self.path}::{self.symbol}"

    def __str__(self) -> str:
        sym = f" [{self.symbol}]" if self.symbol else ""
        return f"{self.path}:{self.line}: {self.rule}{sym} {self.message}"


# --------------------------------------------------------------------- #
# helpers
# --------------------------------------------------------------------- #

def _is_np_attr(node: ast.AST, names: Iterable[str]) -> Optional[str]:
    """node is np.<name> / numpy.<name> for name in names -> name."""
    if (isinstance(node, ast.Attribute) and node.attr in names
            and isinstance(node.value, ast.Name)
            and node.value.id in ("np", "numpy")):
        return node.attr
    return None


def _call_name(node: ast.Call) -> str:
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


def _names_in(node: ast.AST) -> set:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


class _Scoped(ast.NodeVisitor):
    """Visitor tracking the enclosing Class.function qualname and the
    per-line waiver set."""

    def __init__(self, rel: str, lines: list):
        self.rel = rel
        self.lines = lines
        self.scope: list = []
        self.findings: list = []

    def symbol(self) -> str:
        return ".".join(self.scope)

    def waived(self, lineno: int, pat=_WAIVER) -> bool:
        if 1 <= lineno <= len(self.lines):
            return bool(pat.search(self.lines[lineno - 1]))
        return False

    def add(self, rule: str, node: ast.AST, msg: str,
            pat=_WAIVER) -> None:
        if not self.waived(node.lineno, pat):
            self.findings.append(
                Finding(rule, self.rel, node.lineno, self.symbol(), msg))

    def visit_ClassDef(self, node):
        self.scope.append(node.name)
        self.generic_visit(node)
        self.scope.pop()

    def visit_FunctionDef(self, node):
        self.scope.append(node.name)
        self.generic_visit(node)
        self.scope.pop()

    visit_AsyncFunctionDef = visit_FunctionDef


# --------------------------------------------------------------------- #
# rules 1-4: expression-level
# --------------------------------------------------------------------- #

def _module_has_limb_fence(tree: ast.AST) -> bool:
    """The module implements the psum limb-exactness fence: somewhere it
    consults a `*psum_limb_fence*` guard AND raises OverflowError (the
    pre-launch capacity check of parallel/spmd.ShardedCopProgram)."""
    has_guard = False
    has_raise = False
    for node in ast.walk(tree):
        if isinstance(node, (ast.Attribute, ast.Name)):
            name = node.attr if isinstance(node, ast.Attribute) else node.id
            if "psum_limb_fence" in name:
                has_guard = True
        elif isinstance(node, ast.Raise) and node.exc is not None:
            exc = node.exc
            callee = exc.func if isinstance(exc, ast.Call) else exc
            if isinstance(callee, ast.Name) and \
                    callee.id == "OverflowError":
                has_raise = True
        if has_guard and has_raise:
            return True
    return False


class _ExprRules(_Scoped):
    def __init__(self, rel, lines, psum_fenced: bool = True):
        super().__init__(rel, lines)
        self.traced = rel in TRACED_MODULES
        self.hot = rel in HOT_PATH_MODULES
        self.retry_scope = rel.startswith(RETRY_MODULE_PREFIXES)
        self.mem_source_ok = (rel in MEM_SOURCE_MODULES
                              or rel.startswith(COMPILECACHE_PREFIX))
        self.psum_fenced = psum_fenced
        self._digest_fn = 0     # depth of digest-context functions
        self._sorted_ok: set = set()   # dict-iter calls under sorted()
        self._fn_nodes: list = []      # enclosing function AST nodes

    def visit_FunctionDef(self, node):
        # plain collection accessors named `keys`/`values`/`items` are
        # not digest builders even though the substring matches
        bump = bool(_DIGEST_NAME.search(node.name)
                    and node.name not in ("keys", "values", "items"))
        self._digest_fn += bump
        self._fn_nodes.append(node)
        super().visit_FunctionDef(node)
        self._fn_nodes.pop()
        self._digest_fn -= bump

    visit_AsyncFunctionDef = visit_FunctionDef

    # -- digest contexts also arise from `key = (...)` assignments ---- #
    def visit_Assign(self, node):
        if self._digest_fn == 0 and any(
                isinstance(t, ast.Name) and _DIGEST_NAME.search(t.id)
                for t in node.targets):
            self._scan_digest_value(node.value)
        self.generic_visit(node)

    def _note_sorted(self, node: ast.Call) -> None:
        """sorted(d.items()) neutralizes iteration order — remember the
        wrapped call so the digest rule skips it."""
        if _call_name(node) == "sorted" and isinstance(node.func, ast.Name):
            for a in node.args:
                if isinstance(a, ast.Call):
                    self._sorted_ok.add(id(a))

    def _scan_digest_value(self, value: ast.AST) -> None:
        for sub in ast.walk(value):
            if isinstance(sub, ast.Call):
                self._note_sorted(sub)
        for sub in ast.walk(value):
            if isinstance(sub, ast.Call):
                self._check_digest_call(sub)

    def _check_digest_call(self, node: ast.Call) -> None:
        name = _call_name(node)
        if isinstance(node.func, ast.Name) and name == "id":
            self.add("TPU-DIGEST", node,
                     "id(...) feeds a cache key/digest: process-local "
                     "identity does not survive object rebuilds — use a "
                     "stable fingerprint of the value instead")
        elif (isinstance(node.func, ast.Attribute)
              and name in ("items", "keys", "values") and not node.args
              # AST-node memo, not key material  # planlint: ok
              and id(node) not in self._sorted_ok):
            self.add("TPU-DIGEST", node,
                     f".{name}() iteration feeds a digest: wrap in "
                     "sorted(...) so insertion order cannot change the key")

    def visit_Call(self, node):
        name = _call_name(node)
        self._note_sorted(node)    # parents visit before children
        # TPU-TRACE-LEAK: concretization in traced modules
        if self.traced:
            if (isinstance(node.func, ast.Name)
                    and name in ("int", "float", "bool") and node.args
                    and not isinstance(node.args[0], ast.Constant)):
                self.add("TPU-TRACE-LEAK", node,
                         f"{name}(...) on a non-literal inside a traced "
                         "module concretizes the tracer (host sync / "
                         "ConcretizationTypeError); keep values as jnp "
                         "arrays or hoist to program-build time")
            if _is_np_attr(node.func, ("asarray", "array")):
                self.add("TPU-TRACE-LEAK", node,
                         "np.asarray/np.array on a traced value pulls it "
                         "to host; use jnp inside device functions")
            # TPU-PSUM-FENCE: unfenced in-program limb merges
            if name == "psum" and not self.psum_fenced:
                self.add("TPU-PSUM-FENCE", node,
                         "lax.psum in a traced module without the 2^31 "
                         "limb-exactness fence: (hi, lo) SUM limb states "
                         "wrap silently past 2^31 contributing rows — "
                         "add a *_psum_limb_fence capacity check that "
                         "raises OverflowError before launch")
            # TPU-NARROW-CAST: a traced cast cannot raise on values
            # that do not fit — bit-narrowing needs a value-range proof
            if (isinstance(node.func, ast.Attribute) and name == "astype"
                    and node.args):
                tgt = _cast_target_name(node.args[0])
                if tgt in _NARROW_CAST_TARGETS:
                    self.add(
                        "TPU-NARROW-CAST", node,
                        f".astype({tgt}) in a traced module narrows "
                        "silently on device (no data-dependent raise); "
                        "state the value-range proof in a "
                        "'# valueflow: ok - <why>' comment or waive "
                        "with '# planlint: ok'",
                        pat=_NARROW_CAST_OK)
            # TPU-DTYPE-X64: dtype decided by the x64 flag, not the code
            self._check_x64(node, name)
            # TPU-DONATE: donation argnums must come from a DonationPlan
            self._check_donate(node)
            # TPU-SHARD-CONST: collective axes must reference the
            # mesh/topology symbol
            self._check_shard_const(node, name)
        # TPU-HOST-SYNC
        if self.hot:
            if name == "device_get" and isinstance(node.func,
                                                   ast.Attribute):
                self.add("TPU-HOST-SYNC", node,
                         "jax.device_get in a hot launch path blocks on "
                         "the device; move the sync to the result seam")
            elif (name == "item" and isinstance(node.func, ast.Attribute)
                  and not node.args):
                self.add("TPU-HOST-SYNC", node,
                         ".item() forces a device->host transfer in a "
                         "hot path")
        # TPU-MEM-SOURCE: raw device-memory introspection outside the
        # ledger (obs/hbm) + compile cache forks the memory truth
        if (not self.mem_source_ok and name in _MEM_SOURCE_CALLS
                and isinstance(node.func, ast.Attribute)):
            self.add("TPU-MEM-SOURCE", node,
                     f"{name}() outside obs/hbm.py + compilecache/: "
                     "the HBM ledger is the single source of device-"
                     "memory truth — route polls through "
                     "obs.hbm.device_memory_stats and measured "
                     "watermarks through the compile cache's "
                     "memory seam")
        # TPU-DIGEST inside digest-named functions
        if self._digest_fn > 0:
            self._check_digest_call(node)
        self.generic_visit(node)

    def _check_x64(self, node: ast.Call, name: str) -> None:
        """Weak-typed jnp creation in a traced module: the value is
        int64/float64 only while jax_enable_x64 stays on (tidb_tpu
        enables it at import); under JAX's default it silently narrows
        to 32 bits on TPU while CPU tests (same flag) stay green."""
        f = node.func
        if not (isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name)
                and f.value.id == "jnp"):
            return
        if name in _X64_SCALARS:
            self.add("TPU-DTYPE-X64", node,
                     f"jnp.{name}(...) yields a 32-bit value when "
                     "jax_enable_x64 is off — construct via jnp.asarray"
                     "(x, dtype=...) with an explicit np dtype")
            return
        slot = _X64_CREATORS.get(name)
        if slot is None:
            return
        if any(kw.arg == "dtype" for kw in node.keywords):
            return
        if 0 <= slot < len(node.args):
            return                      # dtype passed positionally
        self.add("TPU-DTYPE-X64", node,
                 f"jnp.{name}(...) without an explicit dtype is "
                 "x64-flag-dependent: int64/float64 only because "
                 "tidb_tpu enables jax_enable_x64 — pin dtype= so an "
                 "embedder's x64-off default cannot silently narrow "
                 "device lanes to 32 bits")

    def _check_shard_const(self, node: ast.Call, name: str) -> None:
        """A collective (or PartitionSpec) whose mesh-axis argument is a
        raw string literal: the axis name must reference the topology
        symbol (parallel/topology.SHARD_AXIS or a parameter derived
        from it) so a topology rename/refactor cannot silently leave a
        traced program exchanging over a stale axis."""
        def flag(what):
            self.add("TPU-SHARD-CONST", node,
                     f"{what} in {name}(...): collective mesh-axis "
                     "names must reference the mesh/topology symbol "
                     "(parallel/topology.SHARD_AXIS), not a raw string "
                     "literal — a topology rename would silently "
                     "desynchronize this program from the analysis")

        if name in _PSPEC_NAMES:
            for a in node.args:
                if isinstance(a, ast.Constant) and isinstance(a.value, str):
                    flag(f"literal axis {a.value!r}")
                    return
            return
        slot = _COLLECTIVE_AXIS_CALLS.get(name)
        if slot is None:
            return
        cand = None
        if slot < len(node.args):
            cand = node.args[slot]
        for kw in node.keywords:
            if kw.arg in ("axis_name", "axis"):
                cand = kw.value
        if isinstance(cand, ast.Constant) and isinstance(cand.value, str):
            flag(f"literal axis {cand.value!r}")

    def _check_donate(self, node: ast.Call) -> None:
        """donate_argnums/donate_argnames in a traced module: jax bakes
        the aliasing into the executable and DELETES the caller's
        arrays, so the value must be derived from the statically
        verified DonationPlan (analysis/lifetime) — a literal (or any
        expression not referencing a donation-plan symbol) is a
        hand-rolled lifetime claim the gate refuses."""
        for kw in node.keywords:
            if kw.arg not in ("donate_argnums", "donate_argnames"):
                continue
            v = kw.value
            if isinstance(v, (ast.Tuple, ast.List)) and not v.elts:
                continue          # donating nothing is always safe
            literal = isinstance(v, ast.Constant) or (
                isinstance(v, (ast.Tuple, ast.List))
                and all(isinstance(e, ast.Constant) for e in v.elts))
            if literal:
                self.add("TPU-DONATE", node,
                         f"literal {kw.arg}= in a traced module: "
                         "donation argnums must come from a verified "
                         "analysis/lifetime DonationPlan, not a "
                         "hand-written position list")
                continue
            names = {n.id for n in ast.walk(v) if isinstance(n, ast.Name)}
            names |= {a.attr for a in ast.walk(v)
                      if isinstance(a, ast.Attribute)}
            if not any("donat" in s for s in names):
                self.add("TPU-DONATE", node,
                         f"{kw.arg}= value does not reference a "
                         "DonationPlan-derived symbol; route donation "
                         "through analysis/lifetime so the slot "
                         "lifetimes are verified pre-trace")

    # -- TPU-CALIB-CLAMP: unclamped measured-correction feedback ------- #

    @staticmethod
    def _refs_calib_factor(node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name) and _CALIB_FACTOR.search(sub.id):
                return True
            if isinstance(sub, ast.Attribute) \
                    and _CALIB_FACTOR.search(sub.attr):
                return True
        return False

    def _check_calib_clamp(self, node: ast.AST) -> None:
        scope = self._fn_nodes[-1] if self._fn_nodes else node
        for sub in ast.walk(scope):
            name = None
            if isinstance(sub, ast.Name):
                name = sub.id
            elif isinstance(sub, ast.Attribute):
                name = sub.attr
            if name is not None and _CLAMP_REF.search(name):
                return
        self.add("TPU-CALIB-CLAMP", node,
                 "multiplies by a measured cost-correction factor "
                 "without referencing the clamp (clamp_factor / "
                 "CALIB_CLAMP_MIN/MAX, analysis/calibrate): unclamped "
                 "feedback lets one bad measurement starve or flood "
                 "admission — clamp every factor to [1/8, 8]")

    def visit_BinOp(self, node):
        if isinstance(node.op, ast.Mult) and (
                self._refs_calib_factor(node.left)
                or self._refs_calib_factor(node.right)):
            self._check_calib_clamp(node)
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        if isinstance(node.op, ast.Mult) and (
                self._refs_calib_factor(node.value)
                or self._refs_calib_factor(node.target)):
            self._check_calib_clamp(node)
        self.generic_visit(node)

    def visit_While(self, node):
        # TPU-RETRY-BUDGET: a `while True:` re-dispatch loop in the
        # sched/store layers that sleeps blind retries forever; the
        # Backoffer is the only sanctioned sleep (typed curve, total
        # budget, attempt history, RetryBudgetExceeded surfacing)
        if self.retry_scope and isinstance(node.test, ast.Constant) \
                and bool(node.test.value):
            self._check_retry_budget(node)
        self.generic_visit(node)

    def _check_retry_budget(self, node: ast.While) -> None:
        sleep_call = None
        consults_budget = False
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                f = sub.func
                nm = f.attr if isinstance(f, ast.Attribute) else \
                    (f.id if isinstance(f, ast.Name) else "")
                if "sleep" in nm and sleep_call is None:
                    sleep_call = sub
            if isinstance(sub, ast.Name) and "backoff" in sub.id.lower():
                consults_budget = True
            elif isinstance(sub, ast.Attribute) \
                    and "backoff" in sub.attr.lower():
                consults_budget = True
        if sleep_call is not None and not consults_budget:
            self.add("TPU-RETRY-BUDGET", sleep_call,
                     "unbounded retry loop sleeps without a Backoffer "
                     "budget: blind sleep-and-redispatch retries "
                     "forever — back off through store/backoff."
                     "Backoffer so the attempt history and total sleep "
                     "budget are enforced")

    def visit_ExceptHandler(self, node):
        broad = node.type is None
        if isinstance(node.type, ast.Name):
            broad = node.type.id in ("Exception", "BaseException")
        elif isinstance(node.type, ast.Tuple):
            broad = any(isinstance(e, ast.Name)
                        and e.id in ("Exception", "BaseException")
                        for e in node.type.elts)
        if broad and not self._reraises(node):
            what = "bare except" if node.type is None else \
                f"except {ast.unparse(node.type)}"
            self.add("TPU-BROAD-EXCEPT", node,
                     f"{what} without re-raise swallows unexpected "
                     "errors (driver faults, codec bugs); catch the "
                     "specific exceptions and re-raise the rest",
                     pat=_BLE_WAIVER)
        self.generic_visit(node)

    @staticmethod
    def _reraises(handler: ast.ExceptHandler) -> bool:
        """Handler re-raises (bare `raise`, or raises a new error built
        from the caught one) somewhere in its body."""
        for sub in ast.walk(handler):
            if isinstance(sub, ast.Raise):
                return True
        return False


# --------------------------------------------------------------------- #
# rule: TPU-SPAN-LEAK (latency measurements must reach the obs API)
# --------------------------------------------------------------------- #

class _SpanLeakRules(_Scoped):
    """Per-function analysis: a function that measures wall time with
    time.perf_counter[_ns]() AND feeds a latency counter (``+=`` into
    a *_ns/*_ms/*_us/*_total/*_seconds target) must also reference the
    obs span/trace surface or a histogram ``observe`` — otherwise the
    measurement is invisible to TRACE, the flight recorder, and the
    latency histograms (copscope, ISSUE 13)."""

    def visit_FunctionDef(self, node):
        self.scope.append(node.name)
        self._check_fn(node)
        self.generic_visit(node)
        self.scope.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def _check_fn(self, fn) -> None:
        has_perf = False
        obs_ref = False
        feeds: list = []
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Call) and \
                    _PERF_CALL.match(_call_name(sub)):
                has_perf = True
            name = None
            if isinstance(sub, ast.Name):
                name = sub.id
            elif isinstance(sub, ast.Attribute):
                name = sub.attr
            if name is not None and _OBS_REF.search(name):
                obs_ref = True
            if isinstance(sub, ast.AugAssign) \
                    and isinstance(sub.op, ast.Add):
                t = sub.target
                tn = t.attr if isinstance(t, ast.Attribute) else \
                    (t.id if isinstance(t, ast.Name) else "")
                if tn and _LAT_COUNTER.search(tn):
                    feeds.append((sub, tn))
        if not has_perf or obs_ref:
            return
        for node, tn in feeds:
            self.add("TPU-SPAN-LEAK", node,
                     f"perf_counter latency measurement feeds `{tn}` "
                     "without recording through the obs span/histogram "
                     "API: the duration is invisible to TRACE, the "
                     "flight recorder, and the latency histograms — "
                     "record a span (obs.trace) or observe() a "
                     "histogram next to the counter")


# --------------------------------------------------------------------- #
# rule: TPU-COMPILE-KEY (compilecache/ persistence seams)
# --------------------------------------------------------------------- #

class _CompileKeyRules(_Scoped):
    """Every serialize/deserialize/persist call in compilecache/ must
    sit in a function that references the persistent-key triple: a
    digest, a mesh fingerprint, and the donation plan.  Identifier
    check covers names, attributes, AND string constants (the header
    field names the loader re-verifies count as references)."""

    def __init__(self, rel, lines):
        super().__init__(rel, lines)
        self._fn_nodes: list = []

    def visit_FunctionDef(self, node):
        self._fn_nodes.append(node)
        super().visit_FunctionDef(node)
        self._fn_nodes.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Call(self, node):
        name = _call_name(node)
        if _CACHE_WRITE_CALLS.match(name) and self._fn_nodes:
            fn = self._fn_nodes[-1]
            blob = " ".join(self._identifiers(fn)).lower()
            missing = [lbl for lbl, pat in _KEY_TRIPLE
                       if not pat.search(blob)]
            if missing:
                self.add("TPU-COMPILE-KEY", node,
                         f"{name}(...) in a cache-write seam whose "
                         "enclosing function never references "
                         f"{' / '.join(missing)}: a persisted "
                         "executable keyed without the full digest + "
                         "mesh-fingerprint + donation-plan triple can "
                         "silently deserialize the wrong program "
                         "variant after a restart")
        self.generic_visit(node)

    @staticmethod
    def _identifiers(fn: ast.AST) -> set:
        out = set()
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Name):
                out.add(sub.id)
            elif isinstance(sub, ast.Attribute):
                out.add(sub.attr)
            elif isinstance(sub, ast.Constant) and \
                    isinstance(sub.value, str):
                out.add(sub.value)
        return out


# --------------------------------------------------------------------- #
# rule: TPU-PD-EPOCH (pd/ shared-store mutation seams)
# --------------------------------------------------------------------- #

class _PdEpochRules(_Scoped):
    """Every shared-store write call in pd/ must sit in a function that
    references the lease epoch.  The coplace store's liveness contract
    is epoch-fenced CAS: a mutation path that never mentions the epoch
    is one a dead member (expired lease, paused process, partition
    survivor) could drive — the store would have no way to refuse it.
    Identifier check mirrors TPU-COMPILE-KEY: names, attributes, AND
    string constants (the ``"epoch"`` doc fields the backends
    round-trip count as references)."""

    def __init__(self, rel, lines):
        super().__init__(rel, lines)
        self._fn_nodes: list = []

    def visit_FunctionDef(self, node):
        self._fn_nodes.append(node)
        super().visit_FunctionDef(node)
        self._fn_nodes.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    @staticmethod
    def _lock_receiver(node: ast.Call) -> bool:
        recv = node.func.value if isinstance(node.func, ast.Attribute) \
            else None
        if isinstance(recv, ast.Attribute):
            return bool(_PD_LOCK_RECV.search(recv.attr))
        if isinstance(recv, ast.Name):
            return bool(_PD_LOCK_RECV.search(recv.id))
        return False

    def visit_Call(self, node):
        name = _call_name(node)
        if _PD_WRITE_CALLS.match(name) and self._fn_nodes \
                and not self._lock_receiver(node):
            fn = self._fn_nodes[-1]
            blob = " ".join(
                _CompileKeyRules._identifiers(fn)).lower()
            if not _EPOCH_REF.search(blob):
                self.add("TPU-PD-EPOCH", node,
                         f"{name}(...) mutates the shared pd store "
                         "from a function that never references the "
                         "lease epoch: without the epoch-fenced CAS a "
                         "member whose lease expired can clobber "
                         "state the surviving members already "
                         "repartitioned — thread the member epoch "
                         "through every write path")
        self.generic_visit(node)


# --------------------------------------------------------------------- #
# rule 5: lock acquisition order
# --------------------------------------------------------------------- #

class _LockRules(_Scoped):
    """Per-class lock-order analysis.

    Collects lock attributes (threading.Lock/RLock/Condition assigned to
    self._x in any method), resolves Condition(self._y) aliasing, then
    walks each function recording `with self._x:` nesting — directly and
    one call level deep within the class (with self._a: self.meth() where
    meth acquires self._b counts as a->b).  Findings: nested acquisition
    of one underlying non-reentrant lock, and any (a,b) order observed
    together with (b,a)."""

    def __init__(self, rel, lines, tree):
        super().__init__(rel, lines)
        self.tree = tree

    def run(self) -> list:
        for cls in [n for n in ast.walk(self.tree)
                    if isinstance(n, ast.ClassDef)]:
            self._check_class(cls)
        return self.findings

    def _check_class(self, cls: ast.ClassDef) -> None:
        locks: dict = {}     # attr -> canonical (aliased) attr
        reentrant: set = set()
        for sub in ast.walk(cls):
            if not (isinstance(sub, ast.Assign)
                    and isinstance(sub.value, ast.Call)):
                continue
            kind = _call_name(sub.value)
            if kind not in ("Lock", "RLock", "Condition"):
                continue
            for t in sub.targets:
                if (isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"):
                    canon = t.attr
                    if kind == "Condition" and sub.value.args:
                        a0 = sub.value.args[0]
                        if (isinstance(a0, ast.Attribute)
                                and isinstance(a0.value, ast.Name)
                                and a0.value.id == "self"):
                            canon = a0.attr   # Condition wraps that lock
                    locks[t.attr] = canon
                    if kind == "RLock":
                        reentrant.add(canon)
        if not locks:
            return
        # per-method: ordered list of (outer-lock-stack, acquired lock)
        per_method: dict = {}
        for fn in [n for n in cls.body
                   if isinstance(n, (ast.FunctionDef,
                                     ast.AsyncFunctionDef))]:
            per_method[fn.name] = self._acquisitions(fn, locks)
        edges: dict = {}     # (a, b) -> lineno of first observation
        for fname, acqs in per_method.items():
            for held, lock, node in acqs:
                for h in held:
                    if h == lock and h not in reentrant:
                        self.add(
                            "TPU-LOCK-ORDER", node,
                            f"{cls.name}.{fname} re-acquires "
                            f"self.{lock} while already holding it "
                            "(non-reentrant: self-deadlock)")
                    elif h != lock:
                        edges.setdefault((h, lock), node)
                # one call level deep: self.meth() under a held lock
                for sub in ast.walk(node):
                    if (isinstance(sub, ast.Call)
                            and isinstance(sub.func, ast.Attribute)
                            and isinstance(sub.func.value, ast.Name)
                            and sub.func.value.id == "self"
                            and sub.func.attr in per_method):
                        for _h2, l2, _n2 in per_method[sub.func.attr]:
                            if lock == l2 and l2 not in reentrant:
                                self.add(
                                    "TPU-LOCK-ORDER", sub,
                                    f"{cls.name}.{fname} holds "
                                    f"self.{lock} and calls "
                                    f"self.{sub.func.attr}() which "
                                    "re-acquires it (self-deadlock)")
                            elif lock != l2:
                                edges.setdefault((lock, l2), sub)
        for (a, b), node in edges.items():
            if (b, a) in edges and a < b:    # report each cycle once
                self.add("TPU-LOCK-ORDER", node,
                         f"{cls.name} acquires self.{a} before self.{b} "
                         f"here but self.{b} before self.{a} at line "
                         f"{edges[(b, a)].lineno}: lock-order inversion")

    def _acquisitions(self, fn, locks) -> list:
        """All lock acquisitions in fn as (held-before, lock, with-node),
        via the with-statement nesting structure."""
        out: list = []

        def lock_of(item) -> Optional[str]:
            e = item.context_expr
            if isinstance(e, ast.Call):       # .acquire() is not a ctx mgr
                return None
            if (isinstance(e, ast.Attribute)
                    and isinstance(e.value, ast.Name)
                    and e.value.id == "self" and e.attr in locks):
                return locks[e.attr]
            return None

        def walk(stmts, held):
            for node in stmts:
                if isinstance(node, ast.With):
                    acquired = []
                    for item in node.items:
                        lk = lock_of(item)
                        if lk is not None:
                            out.append((tuple(held + acquired), lk, node))
                            acquired.append(lk)
                    walk(node.body, held + acquired)
                    continue
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    continue                 # nested defs run elsewhere
                for field in ("body", "orelse", "finalbody"):
                    sub = getattr(node, field, None)
                    if isinstance(sub, list):
                        walk(sub, held)
                for h in getattr(node, "handlers", None) or []:
                    walk(h.body, held)

        walk(fn.body, [])
        return out


# --------------------------------------------------------------------- #
# entry points
# --------------------------------------------------------------------- #

def lint_source(src: str, rel: str) -> list:
    """Lint one module's source; `rel` is its tidb_tpu-relative path
    (/-separated) — rules scope on it."""
    tree = ast.parse(src)
    lines = src.splitlines()
    fenced = rel not in TRACED_MODULES or _module_has_limb_fence(tree)
    v = _ExprRules(rel, lines, psum_fenced=fenced)
    v.visit(tree)
    findings = v.findings
    if rel.startswith(COMPILECACHE_PREFIX):
        ck = _CompileKeyRules(rel, lines)
        ck.visit(tree)
        findings += ck.findings
    if rel.startswith(PD_PREFIX):
        pe = _PdEpochRules(rel, lines)
        pe.visit(tree)
        findings += pe.findings
    if rel.startswith(SPAN_MODULE_PREFIXES):
        sl = _SpanLeakRules(rel, lines)
        sl.visit(tree)
        findings += sl.findings
    if rel not in LOCK_EXCLUDES and module_imports_threading(tree):
        findings += _LockRules(rel, lines, tree).run()
    # collapse repeats on one line (e.g. three id() calls in one tuple)
    seen, out = set(), []
    for f in findings:
        k = (f.rule, f.path, f.line)
        if k not in seen:
            seen.add(k)
            out.append(f)
    out.sort(key=lambda f: (f.path, f.line, f.rule))
    return out


def lint_tree(root: Optional[str] = None) -> list:
    """Lint every .py file under the tidb_tpu package."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings: list = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames
                             if d not in ("__pycache__", "native"))
        for fname in sorted(filenames):
            if not fname.endswith(".py"):
                continue
            full = os.path.join(dirpath, fname)
            rel = os.path.relpath(full, root).replace(os.sep, "/")
            with open(full, encoding="utf-8") as f:
                try:
                    findings += lint_source(f.read(), rel)
                except SyntaxError as e:
                    findings.append(Finding(
                        "TPU-SYNTAX", rel, e.lineno or 0, "",
                        f"file does not parse: {e.msg}"))
    return findings


def load_baseline(path: Optional[str] = None) -> set:
    """Accepted-findings allowlist: one `RULE path::symbol` key per line
    (comments with #).  Pre-existing findings listed here pass the gate;
    new ones fail it."""
    if path is None:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "baseline.txt")
    keys = set()
    if os.path.exists(path):
        with open(path, encoding="utf-8") as f:
            for line in f:
                line = line.split("#", 1)[0].strip()
                if line:
                    keys.add(line)
    return keys


def new_findings(findings: list, baseline: set) -> list:
    return [f for f in findings if f.key() not in baseline]


__all__ = ["Finding", "lint_source", "lint_tree", "load_baseline",
           "new_findings", "TRACED_MODULES", "HOT_PATH_MODULES",
           "LOCK_EXCLUDES", "module_imports_threading",
           "RETRY_MODULE_PREFIXES",
           "COMPILECACHE_PREFIX", "PD_PREFIX",
           "SPAN_MODULE_PREFIXES", "MEM_SOURCE_MODULES"]
