"""Resource-lifecycle & exception-contract lint over the runtime (PWA201–205).

The reference engine leans on Rust ownership and typed-error discipline to stay
leak-free under failure; this Python runtime reproduces that discipline only by
convention — and the review-hardening history shows the recurring bug class: a
cancelled REST client permanently leaking its admission slot (PR 6), parked
leaver continuations that were write-only state (PR 11), broad ``except``
blocks one refactor away from swallowing ``PeerShutdownError`` and wedging the
fence ladder. These passes mechanize that audit over the same parsed-module
substrate the concurrency lint (PWA101–104) built:

- **PWA201 — acquire/release pairing.** Registered resource acquisitions
  (socket/file/tempfile/process constructors, admission-slot container stores)
  must have their release dominate every exit: a ``with``, a ``finally``, a
  provably-exception-free tail, or an ownership transfer (returned, stored on
  ``self``/a container, passed onward). Class-attribute resources are checked
  interprocedurally: SOME method of the class (a teardown helper called from a
  ``finally`` qualifies) must release the attribute. Error.
- **PWA202 — typed-error swallowing.** A ``try`` whose body can raise a typed
  protocol error (``PeerShutdownError``/``PeerTimeoutError``/
  ``ClusterFenceError``/``MembershipMismatchError``/``AutoscaleRefusedError``/
  ``EmbedOverloadError``…, discovered from the analyzed modules; raise sets
  propagate interprocedurally through resolvable calls) guarded by a bare or
  ``except Exception`` handler that neither re-raises nor isinstance-triages
  swallows the failure model's control flow. Any non-re-raising
  ``except BaseException`` is flagged unconditionally — it can eat
  ``GraphCaptureInterrupt`` (and ``KeyboardInterrupt``). Error.
- **PWA203 — write-only / dead attribute state.** An attribute of a runtime
  class that is written outside constructor-only code but never read anywhere
  (any analyzed module, plus the tests/benchmarks read index in tree mode) is the
  parked-continuation bug class: state that silently stops meaning anything.
  Constructor-reachability and the ``# noqa: PWA2xx (<why>)`` escape reuse the
  PWA103 machinery. Warning.
- **PWA204 — exception-masking cleanup.** A ``raise``, ``return``/``break``/
  ``continue``, or an unguarded call that can raise a typed error inside a
  ``finally`` block replaces the in-flight (typed) exception with a generic
  one — recovery then routes on the wrong type. Error.
- **PWA205 — telemetry-contract drift.** Every ``stage_add``/``stage_timer``/
  ``stage_add_many``/``record_event`` string literal must parse against the
  registered namespace prefixes (``engine/telemetry.py:STAGE_NAMESPACES``) and
  flight-event kinds (``FLIGHT_EVENT_KINDS``), so counters cannot silently
  fork from ``/metrics`` dashboards. Error.

Surfaces mirror PWA10x exactly: folded into ``cli analyze --runtime`` (same
0/1/2 exit-code contract and JSON format, per-pass ``checked`` flags), a
``PATHWAY_RESOURCE_LINT=off|warn|error`` gate on ``pw.run`` (default ``off`` —
CI carries the clean-tree gate), ``lint.diag.PWA20x`` stage counters + the
``lint`` flight event, and ``# noqa: PWA20x (<reason>)`` suppression through
the shared noqa machinery.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterator, List, Optional, Set, Tuple

from pathway_tpu.analysis.concurrency import (
    _REPO_ROOT,
    RUNTIME_MODULES,
    ConcurrencyPass,
    _ModuleInfo,
    _ModuleParser,
    _load_modules,
    _self_attr,
)
from pathway_tpu.analysis.framework import (
    AnalysisReport,
    Diagnostic,
    Severity,
)

#: the modules the resource/exception passes police: the threaded runtime set
#: plus the engine commit loop, persistence, the REST plane, and chaos — the
#: layers that hold slots, sockets, file handles, and typed-error contracts.
RESOURCE_MODULES: Tuple[str, ...] = RUNTIME_MODULES + (
    "pathway_tpu/engine/runner.py",
    "pathway_tpu/engine/profile.py",
    "pathway_tpu/engine/fusion.py",
    "pathway_tpu/persistence/engine.py",
    "pathway_tpu/persistence/backends.py",
    "pathway_tpu/persistence/replica_feed.py",
    "pathway_tpu/io/http/_server.py",
    "pathway_tpu/internals/chaos.py",
)

#: files scanned (regex, not AST) for attribute reads in tree mode: an attr
#: consumed only by tests/benchmarks/examples is observability state, not dead
_EXTERNAL_READ_GLOBS: Tuple[str, ...] = ("tests", "examples", "benchmarks", "chip_smoke.py")

# -- PWA201 resource registry -------------------------------------------------

#: terminal constructor name -> (resource kind, release-method names). The
#: Attribute form (``socket.socket``/``tempfile.NamedTemporaryFile``) only
#: matches when the receiver is an imported-module alias, so a method merely
#: NAMED ``open`` on some object never reads as a file constructor.
_RESOURCE_CTORS: Dict[str, Tuple[str, Tuple[str, ...]]] = {
    "socket": ("socket", ("close", "detach")),
    "create_connection": ("socket", ("close", "detach")),
    "socketpair": ("socket", ("close", "detach")),
    "open": ("file", ("close",)),
    "fdopen": ("file", ("close",)),
    "NamedTemporaryFile": ("file", ("close",)),
    "TemporaryFile": ("file", ("close",)),
    "TemporaryDirectory": ("tempdir", ("cleanup",)),
    "Popen": ("process", ("wait", "communicate", "kill", "terminate")),
}

#: ``self.<attr>[key] = value`` admission-slot containers: a function that both
#: stores AND pops a slot must pop on the ``finally`` path (the PR-6 cancelled-
#: client wedge). Release method names that undo a slot store.
_SLOT_CONTAINERS: Set[str] = {"futures"}
_SLOT_RELEASES: Set[str] = {"pop", "discard", "remove"}

#: mutator methods whose receiver is a WRITE, not a read, for PWA203: only the
#: grow-a-collection family — ``.add(1)`` on an OTel counter or ``.pop()`` on
#: a queue consumes the object, a bare ``.append`` into a never-read list does
#: not (the parked-continuation shape)
_WRITE_ONLY_MUTATORS: Set[str] = {
    "append", "extend", "insert", "appendleft", "extendleft", "setdefault",
}

#: typed protocol errors every tree carries even when the defining module is
#: not in the analyzed set (framework.py defines the capture interrupt)
_SEED_TYPED_ERRORS: Dict[str, Tuple[str, ...]] = {
    "GraphCaptureInterrupt": ("BaseException",),
    "GraphLintError": ("Exception",),
}

_BROAD = {"Exception"}
_BROADEST = {"BaseException"}

#: builtin exception hierarchy the name-level subclass test walks through
#: (typed errors derive from these; ast gives us names, not classes)
_BUILTIN_BASES: Dict[str, Tuple[str, ...]] = {
    "Exception": ("BaseException",),
    "ArithmeticError": ("Exception",),
    "AssertionError": ("Exception",),
    "AttributeError": ("Exception",),
    "LookupError": ("Exception",),
    "KeyError": ("LookupError",),
    "IndexError": ("LookupError",),
    "OSError": ("Exception",),
    "IOError": ("OSError",),
    "ConnectionError": ("OSError",),
    "TimeoutError": ("OSError",),
    "RuntimeError": ("Exception",),
    "NotImplementedError": ("RuntimeError",),
    "TypeError": ("Exception",),
    "ValueError": ("Exception",),
    "StopIteration": ("Exception",),
    "SystemExit": ("BaseException",),
    "KeyboardInterrupt": ("BaseException",),
}


def _terminal_name(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _exc_names(node: "ast.expr | None") -> List[str]:
    """The exception class names an ``except <type>`` clause matches."""
    if node is None:
        return []
    if isinstance(node, ast.Tuple):
        out: List[str] = []
        for el in node.elts:
            out.extend(_exc_names(el))
        return out
    name = _terminal_name(node)
    return [name] if name else []


def _walk_skip_nested(node: ast.AST) -> Iterator[ast.AST]:
    """``ast.walk`` that does not descend into nested function/class defs —
    their statements execute on a different activation (or not at all)."""
    stack: List[ast.AST] = list(ast.iter_child_nodes(node))
    while stack:
        sub = stack.pop()
        yield sub
        if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(sub))


def _cannot_raise(stmt: ast.stmt) -> bool:
    """True only for statements that provably cannot raise: simple assignments
    of names/constants (the "exception-free tail" a release may ride)."""
    if isinstance(stmt, ast.Pass):
        return True
    if isinstance(stmt, (ast.Assign, ast.AugAssign)):
        value = stmt.value
        simple = (ast.Name, ast.Constant)
        if isinstance(value, ast.Tuple):
            ok = all(isinstance(el, simple) for el in value.elts)
        else:
            ok = isinstance(value, simple)
        targets = stmt.targets if isinstance(stmt, ast.Assign) else [stmt.target]
        return ok and all(isinstance(t, ast.Name) for t in targets)
    return False


# ---------------------------------------------------------------------------
# context
# ---------------------------------------------------------------------------


class _FuncRef:
    """One function/method with its AST node and resolution coordinates."""

    __slots__ = ("module", "cls", "name", "node")

    def __init__(self, module: _ModuleInfo, cls: Optional[str], name: str, node: ast.AST):
        self.module = module
        self.cls = cls
        self.name = name
        self.node = node

    @property
    def qual(self) -> str:
        return f"{self.cls}.{self.name}" if self.cls else self.name


class ResourceAnalysisContext:
    """Parsed view of the resource modules shared by all five passes: function
    AST index, typed-error hierarchy, interprocedural raise closures, and the
    external attribute-read index (tree mode)."""

    def __init__(self, modules: List[_ModuleInfo], *, external_reads: "Optional[Set[str]]" = None):
        self.modules = modules
        self.funcs: List[_FuncRef] = []
        self.class_defs: Dict[str, Tuple[_ModuleInfo, ast.ClassDef]] = {}
        self.class_methods: Dict[str, Dict[str, _FuncRef]] = {}
        self.module_funcs: Dict[Tuple[str, str], _FuncRef] = {}
        self.method_index: Dict[str, List[_FuncRef]] = {}
        for mod in modules:
            for node in mod.tree.body:
                if isinstance(node, ast.ClassDef):
                    self.class_defs.setdefault(node.name, (mod, node))
                    methods = self.class_methods.setdefault(node.name, {})
                    for item in node.body:
                        if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                            ref = _FuncRef(mod, node.name, item.name, item)
                            methods[item.name] = ref
                            self.funcs.append(ref)
                            self.method_index.setdefault(item.name, []).append(ref)
                elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    ref = _FuncRef(mod, None, node.name, node)
                    self.module_funcs[(mod.short, node.name)] = ref
                    self.funcs.append(ref)
        # nested defs (closures, thread bodies, async handlers) are analyzed as
        # their own functions — the REST handler's slot store and the acceptor
        # thread's except live in closures, not methods
        for ref in list(self.funcs):
            seen_nodes: Set[int] = {id(ref.node)}
            for sub in ast.walk(ref.node):
                if (
                    isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and id(sub) not in seen_nodes
                ):
                    seen_nodes.add(id(sub))
                    self.funcs.append(
                        _FuncRef(
                            ref.module, ref.cls,
                            f"{ref.name}.<locals>.{sub.name}", sub,
                        )
                    )
        # typed-error hierarchy: ClassDef names ending in Error/Interrupt whose
        # bases chain to builtin exceptions or other typed errors
        self.error_bases: Dict[str, Tuple[str, ...]] = {
            **_BUILTIN_BASES,
            **_SEED_TYPED_ERRORS,
        }
        self.typed_errors: Set[str] = set(_SEED_TYPED_ERRORS)
        changed = True
        while changed:
            changed = False
            for name, (mod, node) in self.class_defs.items():
                if name in self.typed_errors:
                    continue
                if not (name.endswith("Error") or name.endswith("Interrupt")):
                    continue
                bases = tuple(b for b in (_terminal_name(x) for x in node.bases) if b)
                if any(b in self.error_bases or b.endswith("Error") for b in bases):
                    self.error_bases[name] = bases
                    self.typed_errors.add(name)
                    changed = True
        self.external_reads: Set[str] = external_reads if external_reads is not None else set()
        self._raise_cache: Dict[Tuple[str, str, str], Set[str]] = {}

    # -- resolution ----------------------------------------------------------

    def resolve_method(self, cls_name: str, method: str) -> Optional[_FuncRef]:
        seen: Set[str] = set()
        stack = [cls_name]
        while stack:
            name = stack.pop()
            if name in seen:
                continue
            seen.add(name)
            got = self.class_methods.get(name, {}).get(method)
            if got is not None:
                return got
            entry = self.class_defs.get(name)
            if entry is not None:
                stack.extend(
                    b for b in (_terminal_name(x) for x in entry[1].bases) if b
                )
        return None

    def resolve_call(self, call: ast.Call, mod: _ModuleInfo, cls: Optional[str]) -> Optional[_FuncRef]:
        """Resolve a call to an analyzed function: local/imported functions,
        ``self.m()`` methods (through analyzed bases), ``module.f()`` through
        import aliases, and — for ``other.m()`` receivers — the terminal-
        attribute heuristic when exactly one analyzed class defines ``m``."""
        fn = call.func
        if isinstance(fn, ast.Name):
            imported = mod.import_funcs.get(fn.id)
            if imported is not None:
                return self.module_funcs.get(imported)
            return self.module_funcs.get((mod.short, fn.id))
        if isinstance(fn, ast.Attribute):
            recv = fn.value
            if isinstance(recv, ast.Name):
                if recv.id in ("self", "cls") and cls is not None:
                    return self.resolve_method(cls, fn.attr)
                target_mod = mod.import_modules.get(recv.id)
                if target_mod is not None:
                    return self.module_funcs.get((target_mod, fn.attr))
            cands = self.method_index.get(fn.attr, [])
            if len(cands) == 1:
                return cands[0]
        return None

    # -- interprocedural raise closure ---------------------------------------

    def raise_closure(self, ref: _FuncRef, _depth: int = 0) -> Set[str]:
        """Typed-error names ``ref`` may raise, directly or through resolvable
        calls (depth-bounded, cycle-guarded)."""
        key = (ref.module.short, ref.cls or "", ref.name)
        got = self._raise_cache.get(key)
        if got is not None:
            return got
        self._raise_cache[key] = set()  # cycle guard
        out: Set[str] = set()
        for sub in _walk_skip_nested(ref.node):
            if isinstance(sub, ast.Raise) and sub.exc is not None:
                target = sub.exc
                if isinstance(target, ast.Call):
                    target = target.func
                name = _terminal_name(target)
                if name in self.typed_errors:
                    out.add(name)
            elif isinstance(sub, ast.Call) and _depth < 8:
                callee = self.resolve_call(sub, ref.module, ref.cls)
                if callee is not None and callee.node is not ref.node:
                    out |= self.raise_closure(callee, _depth + 1)
        self._raise_cache[key] = out
        return out

    def stmt_raises(self, stmts: List[ast.stmt], mod: _ModuleInfo, cls: Optional[str]) -> Set[str]:
        """Typed errors the statement list may raise (direct + call closure)."""
        out: Set[str] = set()
        for stmt in stmts:
            for sub in [stmt, *_walk_skip_nested(stmt)]:
                if isinstance(sub, ast.Raise) and sub.exc is not None:
                    target = sub.exc
                    if isinstance(target, ast.Call):
                        target = target.func
                    name = _terminal_name(target)
                    if name in self.typed_errors:
                        out.add(name)
                elif isinstance(sub, ast.Call):
                    callee = self.resolve_call(sub, mod, cls)
                    if callee is not None:
                        out |= self.raise_closure(callee)
        return out

    def is_subclass(self, name: str, ancestor: str) -> bool:
        """Name-level subclass test over the discovered hierarchy (plus the
        builtin bases recorded for each typed error)."""
        seen: Set[str] = set()
        stack = [name]
        while stack:
            cur = stack.pop()
            if cur == ancestor:
                return True
            if cur in seen:
                continue
            seen.add(cur)
            stack.extend(self.error_bases.get(cur, ()))
        return False


def _scan_external_reads(root: str) -> Set[str]:
    """Attribute names read by tests/benchmarks/examples (regex scan: ``.name``
    loads plus getattr/hasattr string literals). Coarse on purpose — an over-
    wide read index only makes PWA203 quieter, never noisier."""
    attr_re = re.compile(r"\.\s*([A-Za-z_]\w*)")
    getattr_re = re.compile(r"(?:getattr|hasattr|setattr)\(\s*[^,]+,\s*['\"](\w+)['\"]")
    out: Set[str] = set()
    for rel in _EXTERNAL_READ_GLOBS:
        path = os.path.join(root, rel)
        files: List[str] = []
        if os.path.isfile(path):
            files = [path]
        elif os.path.isdir(path):
            for base, _dirs, names in os.walk(path):
                files.extend(
                    os.path.join(base, n) for n in names if n.endswith(".py")
                )
        for fpath in files:
            try:
                with open(fpath, "r", encoding="utf-8") as f:
                    source = f.read()
            except OSError:
                continue
            out.update(attr_re.findall(source))
            out.update(getattr_re.findall(source))
    return out


def build_resource_context(
    paths: "Optional[List[str]]" = None, *, with_external_reads: bool = True
) -> ResourceAnalysisContext:
    modules = _load_modules(paths if paths is not None else list(RESOURCE_MODULES))
    external = _scan_external_reads(_REPO_ROOT) if with_external_reads else set()
    return ResourceAnalysisContext(modules, external_reads=external)


# ---------------------------------------------------------------------------
# pass base
# ---------------------------------------------------------------------------


class ResourcePass(ConcurrencyPass):
    """One resource/exception-contract pass. Shares the Diagnostic + noqa
    machinery with the concurrency passes (different context type)."""

    code = "PWA200"

    def run(self, ctx: ResourceAnalysisContext) -> List[Diagnostic]:  # type: ignore[override]
        raise NotImplementedError


def _iter_funcs(ctx: ResourceAnalysisContext) -> Iterator[_FuncRef]:
    yield from ctx.funcs


# ---------------------------------------------------------------------------
# PWA201 — acquire/release pairing
# ---------------------------------------------------------------------------


class _Acquire:
    __slots__ = ("var", "kind", "releases", "lineno", "stmt")

    def __init__(self, var: str, kind: str, releases: Tuple[str, ...], lineno: int, stmt: ast.stmt):
        self.var = var
        self.kind = kind
        self.releases = releases
        self.lineno = lineno
        self.stmt = stmt


class AcquireReleasePass(ResourcePass):
    """PWA201: a registered resource acquisition whose release does not
    dominate every exit — not in a ``with``, not in a ``finally``, not in a
    provably-exception-free tail, and never transferred to another owner.

    Known precision limit: escape analysis is flow-INsensitive — a ``return s``
    (or store/call-arg) on ANY path blesses the variable on every path, so a
    conditional ownership transfer followed by raising statements on the other
    branch is not caught. Full dominance analysis over the CFG would close
    this; the pass trades it for zero false positives on ownership-transfer
    idioms (dial → tune → store) that pervade the mesh wiring."""

    code = "PWA201"
    title = "resource release does not dominate every exit"

    def run(self, ctx: ResourceAnalysisContext) -> List[Diagnostic]:
        out: List[Diagnostic] = []
        for ref in _iter_funcs(ctx):
            out.extend(self._check_function(ctx, ref))
        out.extend(self._check_class_attrs(ctx))
        return out

    # -- helpers -------------------------------------------------------------

    @staticmethod
    def _ctor_of(call: ast.AST, mod: _ModuleInfo) -> Optional[Tuple[str, Tuple[str, ...]]]:
        if not isinstance(call, ast.Call):
            return None
        fn = call.func
        if isinstance(fn, ast.Name):
            if fn.id == "open":
                return _RESOURCE_CTORS["open"]
            if fn.id in _RESOURCE_CTORS and fn.id != "open":
                # `from socket import socket` / `from subprocess import Popen`
                if fn.id in mod.import_funcs:
                    return _RESOURCE_CTORS[fn.id]
            return None
        if isinstance(fn, ast.Attribute) and isinstance(fn.value, ast.Name):
            # module-alias receivers only: `store.open()` is a method, not a fd
            if fn.value.id in mod.import_modules and fn.attr in _RESOURCE_CTORS:
                return _RESOURCE_CTORS[fn.attr]
        return None

    def _check_function(self, ctx: ResourceAnalysisContext, ref: _FuncRef) -> List[Diagnostic]:
        mod, node = ref.module, ref.node
        acquires: List[_Acquire] = []
        attr_acquires: List[Tuple[str, int]] = []  # (attr, lineno) — checked class-wide
        local_to_attr: Dict[str, str] = {}

        # withitem context expressions and attribute receivers never count as
        # escapes; collect their Name ids up front (AST has no parent links)
        non_escape: Set[int] = set()
        with_managed: Set[str] = set()
        for sub in _walk_skip_nested(node):
            if isinstance(sub, ast.With) or isinstance(sub, ast.AsyncWith):
                for item in sub.items:
                    for inner in ast.walk(item.context_expr):
                        if isinstance(inner, ast.Name):
                            non_escape.add(id(inner))
                    if isinstance(item.context_expr, ast.Name):
                        with_managed.add(item.context_expr.id)
            elif isinstance(sub, ast.Attribute) and isinstance(sub.value, ast.Name):
                non_escape.add(id(sub.value))
            elif isinstance(sub, ast.Subscript) and isinstance(sub.value, ast.Name):
                non_escape.add(id(sub.value))
            elif isinstance(sub, ast.Compare):
                for inner in ast.walk(sub):
                    if isinstance(inner, ast.Name):
                        non_escape.add(id(inner))

        # acquisitions
        for sub in _walk_skip_nested(node):
            if isinstance(sub, (ast.With, ast.AsyncWith)):
                continue  # `with open(...) as f` is release-by-construction
            if isinstance(sub, ast.Assign):
                got = self._ctor_of(sub.value, mod)
                if got is None:
                    continue
                kind, releases = got
                for target in sub.targets:
                    if isinstance(target, ast.Name):
                        acquires.append(
                            _Acquire(target.id, kind, releases, sub.lineno, sub)
                        )
                    else:
                        attr = _self_attr(target)
                        if attr is not None:
                            attr_acquires.append((attr, sub.lineno))
        if not acquires and not attr_acquires:
            slot = self._check_slot_stores(ctx, ref)
            return slot
        # the `with ctor()` case: the ctor Call sits in a withitem — drop
        # acquisitions whose ctor call is managed (detected above by walking
        # With items first; Assign-in-with is not a python shape, so only
        # plain `x = ctor()` reaches here)

        # escapes + releases
        escaped: Set[str] = set(with_managed)
        released_finally: Set[str] = set()
        released_lines: Dict[str, List[ast.Call]] = {}
        for name in [a.var for a in acquires]:
            released_lines.setdefault(name, [])

        def note_escapes(expr: "ast.expr | None") -> None:
            if expr is None:
                return
            for inner in ast.walk(expr):
                if isinstance(inner, ast.Name) and id(inner) not in non_escape:
                    escaped.add(inner.id)

        acquire_ids = {id(a.stmt) for a in acquires}
        for sub in _walk_skip_nested(node):
            if isinstance(sub, (ast.Return, ast.Yield, ast.YieldFrom)):
                note_escapes(sub.value)
            elif isinstance(sub, ast.Assign) and id(sub) not in acquire_ids:
                note_escapes(sub.value)
                for target in sub.targets:
                    attr = _self_attr(target)
                    if attr is not None and isinstance(sub.value, ast.Name):
                        local_to_attr[sub.value.id] = attr
            elif isinstance(sub, ast.Call):
                for arg in list(sub.args) + [kw.value for kw in sub.keywords]:
                    note_escapes(arg)
                fn = sub.func
                if isinstance(fn, ast.Attribute) and isinstance(fn.value, ast.Name):
                    for a in acquires:
                        if fn.value.id == a.var and fn.attr in a.releases:
                            released_lines[a.var].append(sub)

        # which release calls sit under a finally?
        finally_calls: Set[int] = set()
        for sub in _walk_skip_nested(node):
            if isinstance(sub, ast.Try) and sub.finalbody:
                for stmt in sub.finalbody:
                    for inner in [stmt, *ast.walk(stmt)]:
                        if isinstance(inner, ast.Call):
                            finally_calls.add(id(inner))
        for a in acquires:
            if any(id(c) in finally_calls for c in released_lines[a.var]):
                released_finally.add(a.var)

        out: List[Diagnostic] = []
        for a in acquires:
            if a.var in escaped or a.var in released_finally:
                continue
            if a.var in local_to_attr:
                continue  # ownership moved to the object; class-wide check below
            if self._released_in_safe_tail(node, a):
                continue
            d = self.diag(
                Severity.ERROR,
                f"{a.kind} acquired into {a.var!r} in {ref.qual} is not "
                "released on every exit: no `with`, no `finally`-path "
                f"{'/'.join(a.releases)}(), and no ownership transfer — an "
                "exception between acquire and release leaks the "
                f"{a.kind} (wrap in `with`, or release in `finally`)",
                module=mod, lineno=a.lineno, function=ref.qual,
                resource=a.kind, variable=a.var,
            )
            if d is not None:
                out.append(d)
        out.extend(self._check_slot_stores(ctx, ref))
        return out

    @staticmethod
    def _released_in_safe_tail(fn_node: ast.AST, acq: _Acquire) -> bool:
        """Release follows the acquire in the same statement block with only
        provably-exception-free statements between them."""

        def block_check(body: List[ast.stmt]) -> bool:
            for i, stmt in enumerate(body):
                if stmt is not acq.stmt:
                    continue
                for later in body[i + 1:]:
                    if (
                        isinstance(later, ast.Expr)
                        and isinstance(later.value, ast.Call)
                        and isinstance(later.value.func, ast.Attribute)
                        and isinstance(later.value.func.value, ast.Name)
                        and later.value.func.value.id == acq.var
                        and later.value.func.attr in acq.releases
                    ):
                        return True
                    if not _cannot_raise(later):
                        return False
                return False
            return False

        for sub in [fn_node, *_walk_skip_nested(fn_node)]:
            for field in ("body", "orelse", "finalbody"):
                body = getattr(sub, field, None)
                if isinstance(body, list) and block_check(body):
                    return True
        return False

    def _check_slot_stores(self, ctx: ResourceAnalysisContext, ref: _FuncRef) -> List[Diagnostic]:
        """Admission-slot containers: a function that stores AND pops a slot
        must pop on the finally path — a success-only pop is the PR-6
        cancelled-client wedge."""
        mod, node = ref.module, ref.node
        stores: List[Tuple[str, int]] = []
        pops: List[ast.Call] = []
        for sub in _walk_skip_nested(node):
            if isinstance(sub, ast.Assign):
                for target in sub.targets:
                    if isinstance(target, ast.Subscript):
                        attr = _self_attr(target.value)
                        if attr in _SLOT_CONTAINERS:
                            stores.append((attr, sub.lineno))
            elif isinstance(sub, ast.Call):
                fn = sub.func
                if (
                    isinstance(fn, ast.Attribute)
                    and fn.attr in _SLOT_RELEASES
                    and _self_attr(fn.value) in _SLOT_CONTAINERS
                ):
                    pops.append(sub)
        if not stores or not pops:
            return []
        finally_calls: Set[int] = set()
        for sub in _walk_skip_nested(node):
            if isinstance(sub, ast.Try) and sub.finalbody:
                for stmt in sub.finalbody:
                    for inner in [stmt, *ast.walk(stmt)]:
                        if isinstance(inner, ast.Call):
                            finally_calls.add(id(inner))
        if any(id(p) in finally_calls for p in pops):
            return []
        attr, lineno = stores[0]
        d = self.diag(
            Severity.ERROR,
            f"admission slot stored into self.{attr}[...] in {ref.qual} is "
            "released only on the success path: a cancelled/raising request "
            "leaks its slot and wedges the admission cap — pop it in a "
            "`finally`",
            module=mod, lineno=lineno, function=ref.qual, container=attr,
        )
        return [d] if d is not None else []

    def _check_class_attrs(self, ctx: ResourceAnalysisContext) -> List[Diagnostic]:
        """Class-attribute resources: SOME method of the class must release the
        attribute (``self.a.close()``, or through a local alias — the teardown
        helper called from a ``finally`` is the interprocedural corner)."""
        out: List[Diagnostic] = []
        for cls_name, (mod, cls_node) in ctx.class_defs.items():
            resource_attrs: Dict[str, Tuple[str, Tuple[str, ...], int, str]] = {}
            for method in ctx.class_methods.get(cls_name, {}).values():
                for sub in _walk_skip_nested(method.node):
                    if not isinstance(sub, ast.Assign):
                        continue
                    got = self._ctor_of(sub.value, mod)
                    direct_attr = None
                    for target in sub.targets:
                        a = _self_attr(target)
                        if a is not None:
                            direct_attr = a
                    if got is not None and direct_attr is not None:
                        resource_attrs.setdefault(
                            direct_attr, (got[0], got[1], sub.lineno, method.qual)
                        )
                    elif direct_attr is not None and isinstance(sub.value, ast.Name):
                        # `self.attr = local` where local held a resource
                        for inner in _walk_skip_nested(method.node):
                            if (
                                isinstance(inner, ast.Assign)
                                and any(
                                    isinstance(t, ast.Name) and t.id == sub.value.id
                                    for t in inner.targets
                                )
                            ):
                                got2 = self._ctor_of(inner.value, mod)
                                if got2 is not None:
                                    resource_attrs.setdefault(
                                        direct_attr,
                                        (got2[0], got2[1], sub.lineno, method.qual),
                                    )
            if not resource_attrs:
                continue
            for attr, (kind, releases, lineno, qual) in sorted(resource_attrs.items()):
                if self._class_releases_attr(ctx, cls_name, attr, releases):
                    continue
                d = self.diag(
                    Severity.ERROR,
                    f"{cls_name}.{attr} holds a {kind} but no method of the "
                    f"class ever calls {'/'.join(releases)}() on it: the "
                    "object's teardown path cannot release the resource",
                    module=mod, lineno=lineno, function=qual,
                    cls=cls_name, attr=attr, resource=kind,
                )
                if d is not None:
                    out.append(d)
        return out

    @staticmethod
    def _class_releases_attr(
        ctx: ResourceAnalysisContext, cls_name: str, attr: str, releases: Tuple[str, ...]
    ) -> bool:
        seen: Set[str] = set()
        stack = [cls_name]
        while stack:
            name = stack.pop()
            if name in seen:
                continue
            seen.add(name)
            for method in ctx.class_methods.get(name, {}).values():
                aliases: Set[str] = set()
                for sub in _walk_skip_nested(method.node):
                    if isinstance(sub, ast.Assign):
                        # x = self.attr  /  x, self.attr = self.attr, None
                        values = (
                            list(sub.value.elts)
                            if isinstance(sub.value, ast.Tuple)
                            else [sub.value]
                        )
                        targets = sub.targets
                        if (
                            len(targets) == 1
                            and isinstance(targets[0], ast.Tuple)
                            and len(targets[0].elts) == len(values)
                        ):
                            pairs = list(zip(targets[0].elts, values))
                        elif len(values) == 1:
                            pairs = [(t, values[0]) for t in targets]
                        else:
                            pairs = []
                        for tgt, val in pairs:
                            if (
                                isinstance(tgt, ast.Name)
                                and _self_attr(val) == attr
                            ):
                                aliases.add(tgt.id)
                for sub in _walk_skip_nested(method.node):
                    if isinstance(sub, ast.Call) and isinstance(sub.func, ast.Attribute):
                        if sub.func.attr not in releases:
                            continue
                        recv = sub.func.value
                        if _self_attr(recv) == attr:
                            return True
                        if isinstance(recv, ast.Name) and recv.id in aliases:
                            return True
            entry = ctx.class_defs.get(name)
            if entry is not None:
                stack.extend(
                    b for b in (_terminal_name(x) for x in entry[1].bases) if b
                )
        return False


# ---------------------------------------------------------------------------
# PWA202 — typed-error swallowing
# ---------------------------------------------------------------------------


class TypedErrorSwallowPass(ResourcePass):
    """PWA202: broad handlers that can eat the failure model's typed errors.
    ``except BaseException`` without re-raise is flagged unconditionally (it
    can eat ``GraphCaptureInterrupt``); bare/``except Exception`` is flagged
    when the try body's interprocedural raise set carries a typed protocol
    error the handler neither re-raises nor isinstance-triages."""

    code = "PWA202"
    title = "broad except swallows typed protocol errors"

    def run(self, ctx: ResourceAnalysisContext) -> List[Diagnostic]:
        out: List[Diagnostic] = []
        for ref in _iter_funcs(ctx):
            for sub in _walk_skip_nested(ref.node):
                if isinstance(sub, ast.Try):
                    out.extend(self._check_try(ctx, ref, sub))
        return out

    #: methods that STORE their argument for another consumer — shipping the
    #: exception object onward, not discarding it. Deliberately narrow: a
    #: ``log.warning("...", exc)`` is log-and-continue, i.e. exactly the
    #: swallow this pass exists to catch.
    _TRANSFER_METHODS = frozenset({
        "append", "add", "put", "put_nowait", "set_exception", "set_result",
        "send", "extend",
    })

    @classmethod
    def _handler_triages(cls, handler: ast.ExceptHandler) -> bool:
        """Re-raise, isinstance triage, or capture-for-transfer: a handler that
        STORES the bound exception somewhere another thread reads it
        (``t.exception = exc``, ``errors.append(exc)``, ``fut.set_exception(exc)``)
        is shipping the failure, not swallowing it. Storing means an attribute/
        subscript assignment target or a transfer-method call — a plain local
        (``msg = str(exc)``) or a logging call does NOT count."""
        exc_name = handler.name
        for sub in ast.walk(handler):
            if isinstance(sub, ast.Raise):
                return True
            if (
                isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Name)
                and sub.func.id == "isinstance"
            ):
                return True
            if exc_name is None:
                continue
            stored: "List[ast.expr]" = []
            if isinstance(sub, ast.Assign) and any(
                isinstance(t, (ast.Attribute, ast.Subscript)) for t in sub.targets
            ):
                stored = [sub.value]
            elif (
                isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Attribute)
                and sub.func.attr in cls._TRANSFER_METHODS
            ):
                stored = list(sub.args)
            for value in stored:
                if any(
                    isinstance(inner, ast.Name) and inner.id == exc_name
                    for inner in ast.walk(value)
                ):
                    return True
        return False

    def _check_try(
        self, ctx: ResourceAnalysisContext, ref: _FuncRef, node: ast.Try
    ) -> List[Diagnostic]:
        out: List[Diagnostic] = []
        body_raises: "Optional[Set[str]]" = None  # computed lazily (closure walk)
        caught_before: List[str] = []
        for handler in node.handlers:
            names = _exc_names(handler.type)
            broadest = handler.type is None or any(n in _BROADEST for n in names)
            broad = broadest or any(n in _BROAD for n in names)
            if not broad:
                caught_before.extend(names)
                continue
            if self._handler_triages(handler):
                caught_before.extend(names)
                continue
            if broadest:
                d = self.diag(
                    Severity.ERROR,
                    f"{'bare except' if handler.type is None else 'except BaseException'} "
                    f"in {ref.qual} neither re-raises nor triages: it can eat "
                    "GraphCaptureInterrupt (and KeyboardInterrupt), so the "
                    "capture/abort protocol silently dies here — catch "
                    "Exception, or re-raise after cleanup",
                    module=ref.module, lineno=handler.lineno, function=ref.qual,
                )
                if d is not None:
                    out.append(d)
                caught_before.extend(names)
                continue
            if body_raises is None:
                body_raises = ctx.stmt_raises(node.body, ref.module, ref.cls)
            # Exception-derived only: BaseException-derived typed errors
            # (GraphCaptureInterrupt) fly PAST an `except Exception` anyway
            residual = {
                e
                for e in body_raises
                if ctx.is_subclass(e, "Exception")
                and not any(ctx.is_subclass(e, c) for c in caught_before)
            }
            if residual:
                listed = ", ".join(sorted(residual))
                d = self.diag(
                    Severity.ERROR,
                    f"broad except in {ref.qual} can swallow typed protocol "
                    f"error(s) {listed} raised in the try body: the failure "
                    "model routes recovery on these types — triage with "
                    "isinstance/a narrower except, or re-raise",
                    module=ref.module, lineno=handler.lineno, function=ref.qual,
                    swallows=sorted(residual),
                )
                if d is not None:
                    out.append(d)
            caught_before.extend(names)
        return out


# ---------------------------------------------------------------------------
# PWA203 — write-only / dead attribute state
# ---------------------------------------------------------------------------


class DeadStatePass(ResourcePass):
    """PWA203: runtime-class attributes written outside constructor-only code
    but read nowhere (any analyzed module + the external read index): the
    parked-continuation bug class — state that no longer means anything."""

    code = "PWA203"
    title = "write-only attribute state"

    def run(self, ctx: ResourceAnalysisContext) -> List[Diagnostic]:
        # global read index: any `x.attr` load in the analyzed modules
        reads: Set[str] = set(ctx.external_reads)
        not_read_nodes: Set[int] = set()
        for mod in ctx.modules:
            for sub in ast.walk(mod.tree):
                if isinstance(sub, ast.Call) and isinstance(sub.func, ast.Attribute):
                    if sub.func.attr in _WRITE_ONLY_MUTATORS:
                        # `self.x.append(v)`: the self.x load is the WRITE's
                        # receiver, not a read of the value
                        not_read_nodes.add(id(sub.func.value))
                elif isinstance(sub, ast.Subscript) and isinstance(sub.ctx, (ast.Store, ast.Del)):
                    not_read_nodes.add(id(sub.value))
                elif (
                    isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Name)
                    and sub.func.id in ("getattr", "hasattr")
                    and len(sub.args) >= 2
                    and isinstance(sub.args[1], ast.Constant)
                    and isinstance(sub.args[1].value, str)
                ):
                    reads.add(sub.args[1].value)
        for mod in ctx.modules:
            for sub in ast.walk(mod.tree):
                if (
                    isinstance(sub, ast.Attribute)
                    and isinstance(sub.ctx, ast.Load)
                    and id(sub) not in not_read_nodes
                ):
                    reads.add(sub.attr)

        out: List[Diagnostic] = []
        for cls_name, (mod, cls_node) in ctx.class_defs.items():
            cls_info = mod.classes.get(cls_name)
            if cls_info is None:
                continue
            from pathway_tpu.analysis.concurrency import UnlockedSharedWritePass

            exempt = UnlockedSharedWritePass._constructor_only(cls_info)
            writes: Dict[str, Tuple[str, int]] = {}
            for method in ctx.class_methods.get(cls_name, {}).values():
                if method.name.split(".")[0] in exempt:
                    continue
                for sub in _walk_skip_nested(method.node):
                    attr: Optional[str] = None
                    if isinstance(sub, (ast.Assign, ast.AugAssign)):
                        targets = (
                            sub.targets if isinstance(sub, ast.Assign) else [sub.target]
                        )
                        for t in targets:
                            a = _self_attr(t)
                            if a is None and isinstance(t, ast.Subscript):
                                a = _self_attr(t.value)
                            if a is not None:
                                attr = a
                    elif isinstance(sub, ast.Call) and isinstance(sub.func, ast.Attribute):
                        if sub.func.attr in _WRITE_ONLY_MUTATORS:
                            attr = _self_attr(sub.func.value)
                    if attr is None or attr.startswith("__"):
                        continue
                    writes.setdefault(attr, (method.qual, sub.lineno))
            for attr, (qual, lineno) in sorted(writes.items()):
                if attr in reads:
                    continue
                d = self.diag(
                    Severity.WARNING,
                    f"{cls_name}.{attr} is written in {qual} but never read "
                    "anywhere (analyzed modules + tests/benchmarks): write-only "
                    "state is the parked-continuation bug class — delete it, "
                    "or wire the consumer it was meant for (`# noqa: PWA203 "
                    "(<why>)` if it is intentionally export-only)",
                    module=mod, lineno=lineno, function=qual,
                    cls=cls_name, attr=attr,
                )
                if d is not None:
                    out.append(d)
        return out


# ---------------------------------------------------------------------------
# PWA204 — exception-masking finally/cleanup
# ---------------------------------------------------------------------------


class FinallyMaskPass(ResourcePass):
    """PWA204: a ``raise``/``return``/``break``/``continue`` or an unguarded
    typed-error-raising call inside ``finally`` replaces the in-flight
    exception — the fence ladder then routes recovery on the wrong type."""

    code = "PWA204"
    title = "finally block can mask the in-flight exception"

    def run(self, ctx: ResourceAnalysisContext) -> List[Diagnostic]:
        out: List[Diagnostic] = []
        for ref in _iter_funcs(ctx):
            for sub in _walk_skip_nested(ref.node):
                if isinstance(sub, ast.Try) and sub.finalbody:
                    out.extend(self._check_finally(ctx, ref, sub.finalbody))
        return out

    def _check_finally(
        self, ctx: ResourceAnalysisContext, ref: _FuncRef, finalbody: List[ast.stmt]
    ) -> List[Diagnostic]:
        out: List[Diagnostic] = []
        guarded: Set[int] = set()  # nodes under a try/except INSIDE the finally

        def scan(stmts: List[ast.stmt]) -> None:
            for stmt in stmts:
                for sub in [stmt, *_walk_skip_nested(stmt)]:
                    if isinstance(sub, ast.Try) and sub.handlers:
                        for inner_stmt in sub.body:
                            for inner in [inner_stmt, *ast.walk(inner_stmt)]:
                                guarded.add(id(inner))

        scan(finalbody)
        for stmt in finalbody:
            for sub in [stmt, *_walk_skip_nested(stmt)]:
                if id(sub) in guarded:
                    continue
                if isinstance(sub, ast.Raise):
                    d = self.diag(
                        Severity.ERROR,
                        f"raise inside finally in {ref.qual} replaces the "
                        "in-flight exception: a typed protocol error unwinding "
                        "through here becomes this one — re-raise outside the "
                        "finally, or guard the cleanup",
                        module=ref.module, lineno=sub.lineno, function=ref.qual,
                    )
                    if d is not None:
                        out.append(d)
                elif isinstance(sub, (ast.Return, ast.Break, ast.Continue)):
                    kind = type(sub).__name__.lower()
                    d = self.diag(
                        Severity.ERROR,
                        f"{kind} inside finally in {ref.qual} silently "
                        "swallows any in-flight exception (including typed "
                        "protocol errors) — move it out of the finally",
                        module=ref.module, lineno=sub.lineno, function=ref.qual,
                    )
                    if d is not None:
                        out.append(d)
                elif isinstance(sub, ast.Call):
                    callee = ctx.resolve_call(sub, ref.module, ref.cls)
                    if callee is None:
                        continue
                    raised = ctx.raise_closure(callee)
                    if raised:
                        listed = ", ".join(sorted(raised))
                        d = self.diag(
                            Severity.ERROR,
                            f"call to {callee.qual} inside finally in "
                            f"{ref.qual} can raise {listed}: an error thrown "
                            "from cleanup masks the in-flight exception — "
                            "guard the call with its own try/except",
                            module=ref.module, lineno=sub.lineno,
                            function=ref.qual, raises=sorted(raised),
                        )
                        if d is not None:
                            out.append(d)
        return out


# ---------------------------------------------------------------------------
# PWA205 — telemetry-contract drift
# ---------------------------------------------------------------------------


class TelemetryContractPass(ResourcePass):
    """PWA205: stage-counter and flight-event string literals must parse
    against the registered namespaces (``telemetry.STAGE_NAMESPACES`` /
    ``telemetry.FLIGHT_EVENT_KINDS``) so counters can't silently fork from the
    ``/metrics`` dashboards built on them."""

    code = "PWA205"
    title = "unregistered telemetry namespace"

    def run(self, ctx: ResourceAnalysisContext) -> List[Diagnostic]:
        from pathway_tpu.engine.telemetry import (
            FLIGHT_EVENT_KINDS,
            STAGE_NAMESPACES,
            TRACE_SPAN_KINDS,
        )

        out: List[Diagnostic] = []
        for ref in _iter_funcs(ctx):
            out.extend(
                self._check_function(
                    ref, STAGE_NAMESPACES, FLIGHT_EVENT_KINDS, TRACE_SPAN_KINDS
                )
            )
        # module-level calls (rare) ride the module "function"
        return out

    @staticmethod
    def _literal_head(node: ast.AST) -> "Optional[Tuple[str, bool]]":
        """``(name, is_partial)``: a literal stage name, or the literal head of
        an f-string (partial — the tail is dynamic)."""
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return node.value, False
        if isinstance(node, ast.JoinedStr) and node.values:
            head = node.values[0]
            if isinstance(head, ast.Constant) and isinstance(head.value, str):
                return head.value, True
        return None

    def _check_name(
        self,
        ref: _FuncRef,
        node: ast.AST,
        name: str,
        namespaces: Tuple[str, ...],
        *,
        partial: bool,
    ) -> Optional[Diagnostic]:
        # a COMPLETE literal must carry a full registered prefix; only an
        # f-string head may be shorter than its namespace (f"embed{x}")
        ok = any(
            name.startswith(ns) or (partial and ns.startswith(name))
            for ns in namespaces
        )
        if ok:
            return None
        return self.diag(
            Severity.ERROR,
            f"stage counter {name!r} in {ref.qual} is outside every "
            "registered namespace "
            f"({', '.join(n.rstrip('.') for n in namespaces)}): it would fork "
            "from /metrics silently — register the prefix in "
            "telemetry.STAGE_NAMESPACES or fix the name",
            module=ref.module, lineno=node.lineno, function=ref.qual,
            stage=name,
        )

    def _check_function(
        self,
        ref: _FuncRef,
        namespaces: Tuple[str, ...],
        event_kinds: "frozenset[str]",
        trace_kinds: "frozenset[str]" = frozenset(),
    ) -> List[Diagnostic]:
        out: List[Diagnostic] = []
        many_vars: Set[str] = set()
        for sub in _walk_skip_nested(ref.node):
            if isinstance(sub, ast.Call):
                callee = _terminal_name(sub.func)
                if callee == "stage_add_many" and sub.args:
                    if isinstance(sub.args[0], ast.Name):
                        many_vars.add(sub.args[0].id)
        for sub in _walk_skip_nested(ref.node):
            if isinstance(sub, ast.Call):
                callee = _terminal_name(sub.func)
                if callee in ("stage_add", "stage_timer") and sub.args:
                    got = self._literal_head(sub.args[0])
                    if got is not None:
                        d = self._check_name(
                            ref, sub.args[0], got[0], namespaces, partial=got[1]
                        )
                        if d is not None:
                            out.append(d)
                elif callee == "stage_add_many" and sub.args:
                    if isinstance(sub.args[0], ast.Dict):
                        for key in sub.args[0].keys:
                            got = self._literal_head(key) if key is not None else None
                            if got is not None:
                                d = self._check_name(
                                    ref, key, got[0], namespaces, partial=got[1]
                                )
                                if d is not None:
                                    out.append(d)
                elif callee == "record_event" and sub.args:
                    got = self._literal_head(sub.args[0])
                    head = got[0] if got is not None else None
                    if (
                        head is not None
                        and isinstance(sub.args[0], ast.Constant)
                        and head not in event_kinds
                    ):
                        d = self.diag(
                            Severity.ERROR,
                            f"flight event kind {head!r} in {ref.qual} is not "
                            "in telemetry.FLIGHT_EVENT_KINDS: post-mortem "
                            "tooling keyed on registered kinds will not see "
                            "it — register the kind or fix the name",
                            module=ref.module, lineno=sub.lineno,
                            function=ref.qual, event=head,
                        )
                        if d is not None:
                            out.append(d)
                elif (
                    callee in ("trace_span", "record_span", "start")
                    and trace_kinds
                    and sub.args
                    and isinstance(sub.args[0], ast.Constant)
                    and isinstance(sub.args[0].value, str)
                ):
                    # span kinds are closed-set literals: the merger and the
                    # critical-path walk key on them. ``.start`` is scoped to
                    # literal-string first args, so Thread.start() (no args)
                    # never matches
                    kind_lit = sub.args[0].value
                    if kind_lit not in trace_kinds:
                        d = self.diag(
                            Severity.ERROR,
                            f"trace span kind {kind_lit!r} in {ref.qual} is "
                            "not in telemetry.TRACE_SPAN_KINDS: the trace "
                            "merger and critical-path analysis key on "
                            "registered kinds — register the kind or fix "
                            "the name",
                            module=ref.module, lineno=sub.lineno,
                            function=ref.qual, span_kind=kind_lit,
                        )
                        if d is not None:
                            out.append(d)
            elif isinstance(sub, ast.Assign):
                # updates["exchange.x"] = 1 on a dict later fed to
                # stage_add_many: literal keys checked too
                for target in sub.targets:
                    if (
                        isinstance(target, ast.Subscript)
                        and isinstance(target.value, ast.Name)
                        and target.value.id in many_vars
                    ):
                        got = self._literal_head(target.slice)
                        if got is not None:
                            d = self._check_name(
                                ref, target, got[0], namespaces, partial=got[1]
                            )
                            if d is not None:
                                out.append(d)
        return out


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def default_resource_passes() -> List[ResourcePass]:
    return [
        AcquireReleasePass(),
        TypedErrorSwallowPass(),
        DeadStatePass(),
        FinallyMaskPass(),
        TelemetryContractPass(),
    ]


def analyze_resources(
    paths: "Optional[List[str]]" = None,
    *,
    passes: "Optional[List[ResourcePass]]" = None,
    ctx: "Optional[ResourceAnalysisContext]" = None,
) -> AnalysisReport:
    """Run the PWA201–205 pipeline over the resource modules (or ``paths``).
    Same report type and exit-code contract as the other lint families."""
    from pathway_tpu.analysis.framework import run_runtime_passes

    if ctx is None:
        ctx = build_resource_context(paths)
    if passes is None:
        passes = default_resource_passes()
    return run_runtime_passes(
        passes, ctx, family="resource", node_count=len(ctx.funcs)
    )


def analyze_resource_source(source: str, name: str = "planted") -> AnalysisReport:
    """Lint one in-memory module (tests plant violations this way). No
    external read index: the planted module is the whole world."""
    info = _ModuleParser(name, f"<{name}>", source).parse()
    return analyze_resources(ctx=ResourceAnalysisContext([info]))


def analyze_runtime_full(paths: "Optional[List[str]]" = None) -> AnalysisReport:
    """The full runtime lint: PWA101–104 (concurrency) + PWA201–205 (resource/
    exception contracts) folded into ONE report — what ``cli analyze
    --runtime`` surfaces. The modules are parsed ONCE and shared: the
    concurrency context is built over the RUNTIME_MODULES subset of the same
    parse the resource context uses."""
    from pathway_tpu.analysis.concurrency import (
        RuntimeAnalysisContext,
        analyze_runtime,
    )

    if paths is not None:
        concurrency_report = analyze_runtime()
        resource_report = analyze_resources(paths)
    else:
        modules = _load_modules(list(RESOURCE_MODULES))
        runtime_rel = set(RUNTIME_MODULES)
        runtime_mods = [
            m
            for m in modules
            if os.path.relpath(m.path, _REPO_ROOT).replace(os.sep, "/") in runtime_rel
        ]
        concurrency_report = analyze_runtime(ctx=RuntimeAnalysisContext(runtime_mods))
        resource_report = analyze_resources(
            ctx=ResourceAnalysisContext(
                modules, external_reads=_scan_external_reads(_REPO_ROOT)
            )
        )
    diagnostics = concurrency_report.diagnostics + resource_report.diagnostics
    diagnostics.sort(key=lambda d: (-int(d.severity), d.code, d.file or "", d.line or 0))
    return AnalysisReport(
        diagnostics,
        node_count=max(concurrency_report.node_count, resource_report.node_count),
        pass_seconds={
            **concurrency_report.pass_seconds,
            **resource_report.pass_seconds,
        },
        pass_checked={
            **concurrency_report.pass_checked,
            **resource_report.pass_checked,
        },
    )


_cached_report: "Optional[AnalysisReport]" = None


def resource_gate() -> None:
    """``PATHWAY_RESOURCE_LINT=off|warn|error`` (default ``off``): lint the
    runtime's resource/exception contracts before a run. ``warn`` logs and
    mirrors counters; ``error`` refuses the run on any PWA201–205 error. The
    report is cached process-wide — the runtime source cannot change under a
    live process."""
    from pathway_tpu.analysis.framework import enforce_gate, gate_mode

    mode = gate_mode("PATHWAY_RESOURCE_LINT")
    if mode is None:
        return
    global _cached_report
    if _cached_report is None:
        _cached_report = analyze_resources()
    enforce_gate(_cached_report, mode)
