"""Concurrency lint over the RUNTIME's own threaded code (PWA101–PWA104).

The graph-lint passes (PWA001–005) analyze USER dataflow graphs; the failure
model underneath them — fence/quiesce/rejoin, the aligned checkpoint protocol,
the recovery ladder — is itself a hand-written distributed protocol built from
Python threads, sockets, locks, and condition variables, and until now its only
correctness guard was chaos testing (whatever interleavings the OS scheduler
happened to produce). This module lints that runtime source statically, so
lock-order and lifecycle bugs surface at review time instead of as a wedged
cluster:

- **PWA101 — lock-order cycle.** A lock-acquisition graph is built over the
  threaded modules (``RUNTIME_MODULES``): every ``with <lock>:`` nested inside
  another — directly or through calls resolved interprocedurally (self-method
  and cross-module, e.g. the telemetry stage-counter lock taken by
  ``stage_add`` calls made under an exchange lock) — adds an edge. A cycle
  means two threads can acquire the same locks in opposite orders and
  deadlock; a self-edge means re-acquiring a non-reentrant lock. Error.
- **PWA102 — unbounded wait.** ``Condition.wait``/``Event.wait``/``Queue.get``
  with no timeout on runtime paths: the fence deadline, the supervisor's
  stall-killer, and teardown can only abort waits that wake up. Error.
- **PWA103 — unlocked shared write.** An attribute mutated under a lock in one
  method and with no lock in another (the RacerD-style inconsistent-locking
  heuristic). Constructor-only code is exempt (no peer threads exist yet —
  methods reachable ONLY from ``__init__`` and never escaping as callbacks are
  proven single-threaded); single-owner attributes (never locked anywhere)
  are not flagged. Warning — the heuristic cannot see ownership conventions,
  so confirmed-benign sites carry ``# noqa: PWA103`` with a reason.
- **PWA104 — thread-lifecycle hygiene.** A ``threading.Thread`` that is
  neither daemon nor joined in its creating scope outlives ``pw.run`` /
  server teardown and wedges interpreter shutdown. Error.

Surfaces mirror the graph lint: ``pathway_tpu.cli analyze --runtime`` (same
JSON format and 0/1/2 exit-code contract), an optional
``PATHWAY_RUNTIME_LINT=off|warn|error`` gate on ``pw.run`` (default ``off`` —
the runtime tree changes with the package, not the user program, so CI runs
the cli gate instead of every run paying a re-parse), and ``lint.diag.PWA10x``
stage counters + the ``lint`` flight event via
:meth:`~pathway_tpu.analysis.framework.AnalysisReport.emit_telemetry`.

Any finding can be suppressed inline with ``# noqa: PWA1xx`` (a bare
``# noqa`` suppresses all four); suppressions should say why.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Set, Tuple

from pathway_tpu.analysis.framework import (
    AnalysisReport,
    Diagnostic,
    Severity,
)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: the threaded runtime layers the concurrency passes police. Relative to the
#: repo root; ``internals/sched.py`` + ``internals/protocol_models.py`` are the
#: model-checking harness itself — it eats its own dog food.
RUNTIME_MODULES: Tuple[str, ...] = (
    "pathway_tpu/parallel/cluster.py",
    "pathway_tpu/parallel/supervisor.py",
    "pathway_tpu/parallel/membership.py",
    "pathway_tpu/parallel/autoscaler.py",
    "pathway_tpu/parallel/replica.py",
    "pathway_tpu/parallel/threads.py",
    "pathway_tpu/engine/brownout.py",
    "pathway_tpu/models/embed_pipeline.py",
    "pathway_tpu/models/encoder_service.py",
    "pathway_tpu/models/device_worker.py",
    "pathway_tpu/models/generation_service.py",
    "pathway_tpu/ops/knn_tiers.py",
    "pathway_tpu/ops/knn_quant.py",
    "pathway_tpu/engine/http_server.py",
    "pathway_tpu/engine/telemetry.py",
    "pathway_tpu/engine/tracing.py",
    "pathway_tpu/internals/sched.py",
    "pathway_tpu/internals/protocol_models.py",
)

# threading-primitive constructors, by terminal callee name
_LOCK_CTORS = {"Lock": "lock", "RLock": "rlock", "Semaphore": "lock", "BoundedSemaphore": "lock"}
_COND_CTORS = {"Condition": "condition"}
_EVENT_CTORS = {"Event": "event"}
_QUEUE_CTORS = {"Queue": "queue", "SimpleQueue": "queue", "LifoQueue": "queue", "PriorityQueue": "queue"}
_ALL_CTORS = {**_LOCK_CTORS, **_COND_CTORS, **_EVENT_CTORS, **_QUEUE_CTORS}

# methods that block on each primitive kind (PWA102 scope)
_BLOCKING_METHODS = {
    "condition": {"wait", "wait_for"},
    "event": {"wait"},
    "queue": {"get", "join"},
}

# container-mutating method names (shared shape with passes.py's PWA001 set)
_MUTATOR_METHODS: Set[str] = {
    "append", "extend", "insert", "add", "update", "pop", "popitem",
    "remove", "discard", "clear", "setdefault", "sort", "reverse",
    "appendleft", "extendleft", "popleft",
}

_NOQA_RE = re.compile(r"#\s*noqa(?::\s*(?P<codes>[A-Z0-9, ]+))?", re.IGNORECASE)


# ---------------------------------------------------------------------------
# module model
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class LockDef:
    """One lock-ish attribute or global: identity is ``scope.attr``."""

    scope: str  # class name, or module short name for globals
    attr: str  # attribute/global name; container locks carry a "[]" suffix
    kind: str  # lock | rlock | condition | event | queue
    module: str
    lineno: int

    @property
    def ident(self) -> str:
        return f"{self.scope}.{self.attr}"


@dataclass
class _CallSite:
    held: Tuple[str, ...]  # lock idents held at the call
    callee: Tuple[str, str, str]  # ("method", Class, name) | ("func", module, name)
    lineno: int


@dataclass
class _Mutation:
    attr: str
    lineno: int
    locked: bool


@dataclass
class _WaitSite:
    lock: LockDef
    method: str
    lineno: int
    has_timeout: bool


@dataclass
class _ThreadSite:
    lineno: int
    daemon: bool
    joined: bool
    assigned_to: Optional[str]


@dataclass
class _FuncInfo:
    module: str
    cls: Optional[str]
    name: str
    lineno: int
    acquires: Set[str] = field(default_factory=set)  # lock idents taken directly
    edges: List[Tuple[str, str, int]] = field(default_factory=list)  # (outer, inner, line)
    calls: List[_CallSite] = field(default_factory=list)
    mutations: List[_Mutation] = field(default_factory=list)
    waits: List[_WaitSite] = field(default_factory=list)
    threads: List[_ThreadSite] = field(default_factory=list)
    has_any_join: bool = False
    joined_names: Set[str] = field(default_factory=set)  # `x.join(...)` receivers
    daemon_names: Set[str] = field(default_factory=set)  # `x.daemon = True` targets

    @property
    def qual(self) -> str:
        return f"{self.cls}.{self.name}" if self.cls else self.name


@dataclass
class _ClassInfo:
    name: str
    module: str
    bases: List[str]
    lock_attrs: Dict[str, LockDef] = field(default_factory=dict)
    cond_alias: Dict[str, str] = field(default_factory=dict)  # cond attr -> lock attr
    methods: Dict[str, _FuncInfo] = field(default_factory=dict)
    escaped_methods: Set[str] = field(default_factory=set)  # passed as callbacks
    nonlock_attrs: Set[str] = field(default_factory=set)  # assigned non-primitives


@dataclass
class _ModuleInfo:
    short: str  # e.g. "cluster"
    path: str
    tree: ast.Module
    source_lines: List[str]
    classes: Dict[str, _ClassInfo] = field(default_factory=dict)
    global_locks: Dict[str, LockDef] = field(default_factory=dict)
    functions: Dict[str, _FuncInfo] = field(default_factory=dict)
    import_funcs: Dict[str, Tuple[str, str]] = field(default_factory=dict)  # local name -> (module short, func)
    import_modules: Dict[str, str] = field(default_factory=dict)  # local alias -> module short

    def noqa_codes(self, lineno: int) -> Optional[Set[str]]:
        """Codes suppressed on ``lineno`` (empty set = suppress everything)."""
        if not (1 <= lineno <= len(self.source_lines)):
            return None
        m = _NOQA_RE.search(self.source_lines[lineno - 1])
        if m is None:
            return None
        codes = m.group("codes")
        if not codes:
            return set()
        return {c.strip().upper() for c in codes.split(",") if c.strip()}


class RuntimeAnalysisContext:
    """Parsed view of the runtime modules shared by all four passes."""

    def __init__(self, modules: List[_ModuleInfo]):
        self.modules = modules
        # attr name -> every LockDef carrying it (the terminal-attribute
        # heuristic for `other.event.wait()` receivers)
        self.attr_index: Dict[str, List[LockDef]] = {}
        for mod in modules:
            for cls in mod.classes.values():
                for ld in cls.lock_attrs.values():
                    self.attr_index.setdefault(ld.attr, []).append(ld)
            for ld in mod.global_locks.values():
                self.attr_index.setdefault(ld.attr, []).append(ld)
        # attr names ALSO assigned non-primitive values somewhere: the
        # terminal-attribute heuristic must not fire on those (a model's
        # `cv = sched.condition(...)` is not ThreadExchangeHub's real one)
        self.ambiguous_attrs: Set[str] = set()
        for mod in modules:
            for cls in mod.classes.values():
                self.ambiguous_attrs |= cls.nonlock_attrs & set(self.attr_index)
        self._closure_cache: Dict[Tuple[str, str, str], Set[str]] = {}

    # -- resolution ----------------------------------------------------------

    def find_class(self, name: str) -> Optional[_ClassInfo]:
        for mod in self.modules:
            if name in mod.classes:
                return mod.classes[name]
        return None

    def resolve_method(self, cls_name: str, method: str) -> Optional[_FuncInfo]:
        """Look up a method on a class or (by name) its analyzed bases."""
        seen: Set[str] = set()
        stack = [cls_name]
        while stack:
            name = stack.pop()
            if name in seen:
                continue
            seen.add(name)
            cls = self.find_class(name)
            if cls is None:
                continue
            if method in cls.methods:
                return cls.methods[method]
            stack.extend(cls.bases)
        return None

    def class_lock(self, cls_name: str, attr: str) -> Optional[LockDef]:
        """A lock attr on a class or its analyzed bases, condition aliases
        canonicalized to the underlying lock (one identity per mutex)."""
        seen: Set[str] = set()
        stack = [cls_name]
        while stack:
            name = stack.pop()
            if name in seen:
                continue
            seen.add(name)
            cls = self.find_class(name)
            if cls is None:
                continue
            attr = cls.cond_alias.get(attr, attr)
            if attr in cls.lock_attrs:
                return cls.lock_attrs[attr]
            stack.extend(cls.bases)
        return None

    def resolve_func(self, module: str, name: str) -> Optional[_FuncInfo]:
        for mod in self.modules:
            if mod.short == module:
                return mod.functions.get(name)
        return None

    def acquire_closure(self, fn: _FuncInfo, _depth: int = 0) -> Set[str]:
        """Every lock ``fn`` may take, directly or through resolvable calls."""
        key = (fn.module, fn.cls or "", fn.name)
        got = self._closure_cache.get(key)
        if got is not None:
            return got
        self._closure_cache[key] = set(fn.acquires)  # cycle guard
        out = set(fn.acquires)
        if _depth < 12:
            for call in fn.calls:
                callee = self._callee_info(call)
                if callee is not None and callee is not fn:
                    out |= self.acquire_closure(callee, _depth + 1)
        self._closure_cache[key] = out
        return out

    def _callee_info(self, call: _CallSite) -> Optional[_FuncInfo]:
        kind, scope, name = call.callee
        if kind == "method":
            return self.resolve_method(scope, name)
        return self.resolve_func(scope, name)


# ---------------------------------------------------------------------------
# parsing
# ---------------------------------------------------------------------------


def _ctor_kind(call: ast.AST) -> Optional[str]:
    """'lock'/'condition'/… when ``call`` constructs a threading primitive."""
    if not isinstance(call, ast.Call):
        return None
    fn = call.func
    name = None
    if isinstance(fn, ast.Attribute):
        name = fn.attr
    elif isinstance(fn, ast.Name):
        name = fn.id
    return _ALL_CTORS.get(name or "")


def _self_attr(node: ast.AST) -> Optional[str]:
    """``self.X`` → ``"X"``."""
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id in ("self", "cls")
    ):
        return node.attr
    return None


def _is_thread_ctor(call: ast.Call) -> bool:
    fn = call.func
    if isinstance(fn, ast.Attribute) and fn.attr == "Thread":
        return True
    return isinstance(fn, ast.Name) and fn.id == "Thread"


class _ModuleParser:
    """Builds a :class:`_ModuleInfo` from one source file."""

    def __init__(self, short: str, path: str, source: str):
        self.info = _ModuleInfo(
            short=short,
            path=path,
            tree=ast.parse(source, filename=path),
            source_lines=source.splitlines(),
        )

    def parse(self) -> _ModuleInfo:
        info = self.info
        for node in info.tree.body:
            if isinstance(node, ast.ImportFrom) and node.module:
                tail = node.module.rsplit(".", 1)[-1]
                for alias in node.names:
                    info.import_funcs[alias.asname or alias.name] = (tail, alias.name)
                    # `from pathway_tpu.engine import telemetry` also binds a
                    # MODULE name: register it as a module alias too, so
                    # `telemetry.stage_add(...)` resolves cross-module
                    info.import_modules[alias.asname or alias.name] = alias.name
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    tail = alias.name.rsplit(".", 1)[-1]
                    info.import_modules[alias.asname or tail] = tail
            elif isinstance(node, ast.Assign):
                kind = _ctor_kind(node.value)
                if kind is not None:
                    for target in node.targets:
                        if isinstance(target, ast.Name):
                            info.global_locks[target.id] = LockDef(
                                scope=info.short, attr=target.id, kind=kind,
                                module=info.short, lineno=node.lineno,
                            )
            elif isinstance(node, ast.ClassDef):
                self._parse_class(node)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fn = self._parse_function(node, cls=None)
                info.functions[fn.name] = fn
        return info

    # -- class level ---------------------------------------------------------

    def _parse_class(self, node: ast.ClassDef) -> None:
        cls = _ClassInfo(
            name=node.name,
            module=self.info.short,
            bases=[b.id for b in node.bases if isinstance(b, ast.Name)]
            + [b.attr for b in node.bases if isinstance(b, ast.Attribute)],
        )
        self.info.classes[node.name] = cls
        # first sweep: every `self.X = <primitive>()` anywhere in the class
        # (locks are usually born in __init__ but rejoin paths mint them late)
        for sub in ast.walk(node):
            if isinstance(sub, ast.Assign):
                kind = _ctor_kind(sub.value)
                for target in sub.targets:
                    attr = _self_attr(target)
                    if attr is not None and kind is None:
                        cls.nonlock_attrs.add(attr)
                    if attr is None:
                        # self._locks[k] = threading.Lock() → container of locks
                        if (
                            isinstance(target, ast.Subscript)
                            and kind is not None
                            and _self_attr(target.value) is not None
                        ):
                            container = _self_attr(target.value)
                            cls.lock_attrs.setdefault(
                                container + "[]",
                                LockDef(
                                    scope=node.name, attr=container + "[]", kind=kind,
                                    module=self.info.short, lineno=sub.lineno,
                                ),
                            )
                        continue
                    if kind is not None:
                        cls.lock_attrs.setdefault(
                            attr,
                            LockDef(
                                scope=node.name, attr=attr, kind=kind,
                                module=self.info.short, lineno=sub.lineno,
                            ),
                        )
                        # Condition(self._lock) shares the mutex with _lock:
                        # one identity, or PWA101 would see phantom 2-cycles
                        if (
                            kind == "condition"
                            and isinstance(sub.value, ast.Call)
                            and sub.value.args
                        ):
                            inner = _self_attr(sub.value.args[0])
                            if inner is not None:
                                cls.cond_alias[attr] = inner
            elif isinstance(sub, ast.Call):
                # self._locks.setdefault(k, threading.Lock())
                if (
                    isinstance(sub.func, ast.Attribute)
                    and sub.func.attr == "setdefault"
                    and len(sub.args) == 2
                    and _ctor_kind(sub.args[1]) is not None
                ):
                    container = _self_attr(sub.func.value)
                    if container is not None:
                        cls.lock_attrs.setdefault(
                            container + "[]",
                            LockDef(
                                scope=node.name, attr=container + "[]",
                                kind=_ctor_kind(sub.args[1]) or "lock",
                                module=self.info.short, lineno=sub.lineno,
                            ),
                        )
        for item in node.body:
            if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fn = self._parse_function(item, cls=node.name)
                cls.methods[item.name] = fn
        # escaped methods: `self.m` referenced outside a direct call position
        # (Thread targets, callbacks) run on other threads — never
        # constructor-exempt for PWA103. AST has no parent links, so first
        # collect the Attribute nodes that ARE the func of a direct call.
        called_direct: Set[int] = set()
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call) and isinstance(sub.func, ast.Attribute):
                called_direct.add(id(sub.func))
        for sub in ast.walk(node):
            if (
                isinstance(sub, ast.Attribute)
                and _self_attr(sub) in cls.methods
                and id(sub) not in called_direct
            ):
                cls.escaped_methods.add(sub.attr)

    # -- function level ------------------------------------------------------

    def _parse_function(
        self, node: "ast.FunctionDef | ast.AsyncFunctionDef", cls: Optional[str]
    ) -> _FuncInfo:
        fn = _FuncInfo(module=self.info.short, cls=cls, name=node.name, lineno=node.lineno)
        local_waitables: Dict[str, str] = {}  # local var -> primitive kind
        local_locks: Dict[str, str] = {}  # local var -> lock ident
        thread_assigns: Dict[int, str] = {}  # id(Thread ctor Call) -> var name

        for sub in ast.walk(node):
            if isinstance(sub, ast.Assign):
                kind = _ctor_kind(sub.value)
                if kind is not None:
                    for target in sub.targets:
                        if isinstance(target, ast.Name):
                            local_waitables[target.id] = kind
                            local_locks[target.id] = (
                                f"{self.info.short}.{node.name}.{target.id}"
                            )
                if (
                    len(sub.targets) == 1
                    and isinstance(sub.targets[0], ast.Name)
                    and isinstance(sub.value, ast.Call)
                    and _is_thread_ctor(sub.value)
                ):
                    thread_assigns[id(sub.value)] = sub.targets[0].id

        def lock_at(expr: ast.AST) -> Optional[str]:
            """Resolve an acquisition expression to a lock identity."""
            if isinstance(expr, ast.Call):
                # `with self._cond:` vs `cond.acquire()` handled by callers;
                # also `with self._lock_for(x):` — unresolvable
                return None
            if isinstance(expr, ast.Name):
                if expr.id in self.info.global_locks:
                    return self.info.global_locks[expr.id].ident
                return local_locks.get(expr.id)
            if isinstance(expr, ast.Subscript):
                base = _self_attr(expr.value)
                if base is not None and cls is not None:
                    return f"{cls}.{base}[]"
                return None
            if isinstance(expr, ast.Attribute):
                attr = _self_attr(expr)
                if attr is not None and cls is not None:
                    # alias-canonicalize through the class chain at report
                    # time; here use the raw (cls, attr) — the context
                    # resolves it in _canon below
                    return ("%s.%s" % (cls, attr))
                # other.cv / self._hub.cv: terminal-attribute heuristic,
                # resolved later by the context (needs the global attr index)
                return f"?attr.{expr.attr}"
            return None

        held: List[Tuple[str, int]] = []

        def visit(stmt: ast.AST) -> None:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)) and stmt is not node:
                # nested defs (closures, Thread bodies) analyzed separately
                # under the parent's scope name; they don't inherit held locks
                inner = self._parse_function(stmt, cls=cls)
                inner.name = f"{node.name}.<locals>.{stmt.name}"
                if cls is not None:
                    self.info.classes[cls].methods[inner.name] = inner
                else:
                    self.info.functions[inner.name] = inner
                return
            if isinstance(stmt, ast.With):
                acquired: List[str] = []
                for item in stmt.items:
                    ident = lock_at(item.context_expr)
                    if ident is not None:
                        fn.acquires.add(ident)
                        for outer, _ln in held:
                            fn.edges.append((outer, ident, item.context_expr.lineno))
                        acquired.append(ident)
                        held.append((ident, item.context_expr.lineno))
                    else:
                        # `with telemetry.stage_timer(...):` — the context
                        # manager call itself may take locks; record it as a
                        # call site under whatever is currently held
                        visit(item.context_expr)
                for child in stmt.body:
                    visit(child)
                for _ in acquired:
                    held.pop()
                return
            if isinstance(stmt, ast.Call):
                self._record_call(fn, stmt, held, cls)
                self._record_wait(fn, stmt, local_waitables, cls)
                if _is_thread_ctor(stmt):
                    fn.threads.append(
                        _ThreadSite(
                            lineno=stmt.lineno,
                            daemon=any(
                                kw.arg == "daemon"
                                and isinstance(kw.value, ast.Constant)
                                and kw.value.value is True
                                for kw in stmt.keywords
                            ),
                            joined=False,
                            assigned_to=thread_assigns.get(id(stmt)),
                        )
                    )
                if isinstance(stmt.func, ast.Attribute) and stmt.func.attr == "join":
                    fn.has_any_join = True
                    if isinstance(stmt.func.value, ast.Name):
                        fn.joined_names.add(stmt.func.value.id)
                self._record_mutation_call(fn, stmt, bool(held))
            if isinstance(stmt, (ast.Assign, ast.AugAssign, ast.Delete)):
                self._record_mutations(fn, stmt, bool(held))
            for child in ast.iter_child_nodes(stmt):
                visit(child)

        for stmt in node.body:
            visit(stmt)

        # `x.daemon = True` before start() upgrades that variable's sites
        for sub in ast.walk(node):
            if (
                isinstance(sub, ast.Assign)
                and len(sub.targets) == 1
                and isinstance(sub.targets[0], ast.Attribute)
                and sub.targets[0].attr == "daemon"
                and isinstance(sub.value, ast.Constant)
                and sub.value.value is True
            ):
                recv = sub.targets[0].value
                if isinstance(recv, ast.Name):
                    fn.daemon_names.add(recv.id)
                else:  # `self._t.daemon = True` — attribute the whole scope
                    for site in fn.threads:
                        site.daemon = True
        # join/daemon attribution: per-variable when the thread is bound to a
        # name (an unrelated join must not mask a leaked sibling thread);
        # scope-wide fallback only for unnamed creations (comprehensions,
        # `threads = [...]` lists joined through a loop variable)
        for site in fn.threads:
            if site.assigned_to is not None:
                site.joined = site.assigned_to in fn.joined_names
                site.daemon = site.daemon or site.assigned_to in fn.daemon_names
            else:
                site.joined = fn.has_any_join
        return fn

    def _record_call(
        self,
        fn: _FuncInfo,
        call: ast.Call,
        held: List[Tuple[str, int]],
        cls: Optional[str],
    ) -> None:
        func = call.func
        callee: Optional[Tuple[str, str, str]] = None
        if isinstance(func, ast.Attribute):
            if (
                isinstance(func.value, ast.Name)
                and func.value.id in ("self", "cls")
                and cls is not None
            ):
                callee = ("method", cls, func.attr)
            elif isinstance(func.value, ast.Name):
                mod = self.info.import_modules.get(func.value.id)
                if mod is not None:
                    callee = ("func", mod, func.attr)
        elif isinstance(func, ast.Name):
            if func.id in self.info.import_funcs:
                mod, name = self.info.import_funcs[func.id]
                callee = ("func", mod, name)
            else:
                callee = ("func", self.info.short, func.id)
        if callee is not None:
            fn.calls.append(
                _CallSite(
                    held=tuple(ident for ident, _ in held),
                    callee=callee,
                    lineno=call.lineno,
                )
            )

    def _record_wait(
        self,
        fn: _FuncInfo,
        call: ast.Call,
        local_waitables: Dict[str, str],
        cls: Optional[str],
    ) -> None:
        func = call.func
        if not isinstance(func, ast.Attribute):
            return
        method = func.attr
        if method not in {"wait", "wait_for", "get", "join"}:
            return
        recv = func.value
        lock: Optional[LockDef] = None
        if isinstance(recv, ast.Name) and recv.id in local_waitables:
            lock = LockDef(
                scope=fn.qual, attr=recv.id, kind=local_waitables[recv.id],
                module=self.info.short, lineno=call.lineno,
            )
        elif isinstance(recv, ast.Attribute):
            attr = recv.attr
            self_attr = _self_attr(recv)
            if self_attr is not None and cls is not None:
                lock = LockDef(
                    scope=cls, attr=self_attr, kind="?", module=self.info.short,
                    lineno=call.lineno,
                )
            else:
                # `req.event.wait()`: terminal-attribute, resolved by the pass
                lock = LockDef(
                    scope="?", attr=attr, kind="?", module=self.info.short,
                    lineno=call.lineno,
                )
        if lock is None:
            return
        has_timeout = False
        # positional timeout slots: wait(timeout) is first; wait_for(pred,
        # timeout) and get(block, timeout) are SECOND — `q.get(True)` is the
        # block flag, still an unbounded wait; Queue.join() takes none
        if method in ("wait_for", "get"):
            pos = call.args[1:2]
        elif method == "wait":
            pos = call.args[:1]
        else:
            pos = []
        has_timeout = any(
            not (isinstance(a, ast.Constant) and a.value is None) for a in pos
        )
        for kw in call.keywords:
            if kw.arg == "timeout" and not (
                isinstance(kw.value, ast.Constant) and kw.value.value is None
            ):
                has_timeout = True
        fn.waits.append(
            _WaitSite(lock=lock, method=method, lineno=call.lineno, has_timeout=has_timeout)
        )

    def _record_mutations(
        self,
        fn: _FuncInfo,
        stmt: "ast.Assign | ast.AugAssign | ast.Delete",
        locked: bool,
    ) -> None:
        targets: List[ast.AST]
        if isinstance(stmt, ast.Assign):
            targets = list(stmt.targets)
        elif isinstance(stmt, ast.AugAssign):
            targets = [stmt.target]
        else:
            targets = list(stmt.targets)

        def hit(target: ast.AST) -> None:
            if isinstance(target, ast.Tuple):
                for el in target.elts:
                    hit(el)
                return
            attr = _self_attr(target)
            if attr is None and isinstance(target, ast.Subscript):
                attr = _self_attr(target.value)
            if attr is None and isinstance(target, ast.Name):
                # module-global mutation inside a module-level function
                if fn.cls is None:
                    attr = f"<global>{target.id}"
            if attr is not None:
                fn.mutations.append(_Mutation(attr=attr, lineno=stmt.lineno, locked=locked))

        for target in targets:
            hit(target)

    def _record_mutation_call(self, fn: _FuncInfo, call: ast.Call, locked: bool) -> None:
        func = call.func
        if not isinstance(func, ast.Attribute) or func.attr not in _MUTATOR_METHODS:
            return
        recv = func.value
        attr = _self_attr(recv)
        if attr is None and isinstance(recv, ast.Subscript):
            attr = _self_attr(recv.value)
        if attr is None and isinstance(recv, ast.Name) and fn.cls is None:
            attr = f"<global>{recv.id}"
        if attr is not None:
            fn.mutations.append(_Mutation(attr=attr, lineno=call.lineno, locked=locked))


# ---------------------------------------------------------------------------
# context construction
# ---------------------------------------------------------------------------


def _load_modules(paths: "Optional[List[str]]" = None) -> List[_ModuleInfo]:
    out: List[_ModuleInfo] = []
    for rel in paths if paths is not None else RUNTIME_MODULES:
        path = rel if os.path.isabs(rel) else os.path.join(_REPO_ROOT, rel)
        if not os.path.exists(path):
            continue  # optional modules (sched lands with this PR; stay robust)
        short = os.path.splitext(os.path.basename(path))[0]
        with open(path, "r", encoding="utf-8") as f:
            source = f.read()
        out.append(_ModuleParser(short, path, source).parse())
    return out


def build_runtime_context(paths: "Optional[List[str]]" = None) -> RuntimeAnalysisContext:
    return RuntimeAnalysisContext(_load_modules(paths))


def _canon(ctx: RuntimeAnalysisContext, ident: str, module: _ModuleInfo) -> Optional[str]:
    """Canonicalize a raw acquisition identity: condition aliases collapse to
    their mutex, `?attr.X` terminal-attribute refs resolve when unambiguous,
    unknown class attrs (non-lock `with`s, e.g. files) drop out."""
    if ident.startswith("?attr."):
        attr = ident[len("?attr."):]
        defs = [d for d in ctx.attr_index.get(attr, []) if d.kind != "event"]
        if len({d.ident for d in defs}) == 1:
            d = defs[0]
            canon = ctx.class_lock(d.scope, d.attr)
            return canon.ident if canon is not None else d.ident
        return None
    scope, _, attr = ident.partition(".")
    if scope == module.short or "." in attr:
        # module-global or local lock: already canonical
        return ident
    ld = ctx.class_lock(scope, attr)
    if ld is not None:
        return ld.ident
    if attr.endswith("[]"):
        return ident
    return None  # `with self.something:` that is not a known lock


# ---------------------------------------------------------------------------
# passes
# ---------------------------------------------------------------------------


class ConcurrencyPass:
    """One runtime-source lint pass (mirrors AnalysisPass, different ctx)."""

    code = "PWA100"
    title = ""

    def run(self, ctx: RuntimeAnalysisContext) -> List[Diagnostic]:
        raise NotImplementedError

    def diag(
        self,
        severity: Severity,
        message: str,
        *,
        module: _ModuleInfo,
        lineno: int,
        function: str = "",
        **details: Any,
    ) -> Optional[Diagnostic]:
        noqa = module.noqa_codes(lineno)
        if noqa is not None and (not noqa or self.code in noqa):
            return None
        line_text = (
            module.source_lines[lineno - 1]
            if 1 <= lineno <= len(module.source_lines)
            else None
        )
        return Diagnostic(
            code=self.code,
            severity=severity,
            message=message,
            node_kind="runtime",
            node_name=function,
            file=os.path.relpath(module.path, _REPO_ROOT)
            if module.path.startswith(_REPO_ROOT)
            else module.path,
            line=lineno,
            function=function,
            line_text=line_text,
            details=details,
        )


def _iter_funcs(ctx: RuntimeAnalysisContext) -> Iterator[Tuple[_ModuleInfo, Optional[_ClassInfo], _FuncInfo]]:
    for mod in ctx.modules:
        for fn in mod.functions.values():
            yield mod, None, fn
        for cls in mod.classes.values():
            for fn in cls.methods.values():
                yield mod, cls, fn


class LockOrderPass(ConcurrencyPass):
    """PWA101: cycles (and non-reentrant self-loops) in the lock-acquisition
    graph built from nested ``with`` blocks and interprocedural call closure."""

    code = "PWA101"
    title = "lock-order cycle"

    def build_graph(
        self, ctx: RuntimeAnalysisContext
    ) -> Dict[Tuple[str, str], List[Tuple[str, int, str]]]:
        """(outer, inner) -> [(file module, line, function)] acquisition edges."""
        edges: Dict[Tuple[str, str], List[Tuple[str, int, str]]] = {}

        def add(outer: str, inner: str, mod: _ModuleInfo, line: int, qual: str) -> None:
            edges.setdefault((outer, inner), []).append((mod.short, line, qual))

        for mod, _cls, fn in _iter_funcs(ctx):
            for outer, inner, line in fn.edges:
                o = _canon(ctx, outer, mod)
                i = _canon(ctx, inner, mod)
                if o is not None and i is not None:
                    add(o, i, mod, line, fn.qual)
            for call in fn.calls:
                if not call.held:
                    continue
                callee = ctx._callee_info(call)
                if callee is None:
                    continue
                callee_mod = next(
                    (m for m in ctx.modules if m.short == callee.module), mod
                )
                inner_locks = {
                    _canon(ctx, a, callee_mod)
                    for a in ctx.acquire_closure(callee)
                }
                for outer in call.held:
                    o = _canon(ctx, outer, mod)
                    if o is None:
                        continue
                    for i in inner_locks:
                        # i == o is kept: calling a method that re-acquires a
                        # held non-reentrant lock is the self-deadlock case
                        if i is not None:
                            add(o, i, mod, call.lineno, fn.qual)
        return edges

    def run(self, ctx: RuntimeAnalysisContext) -> List[Diagnostic]:
        edges = self.build_graph(ctx)
        adj: Dict[str, Set[str]] = {}
        for (outer, inner), _sites in edges.items():
            adj.setdefault(outer, set()).add(inner)
        out: List[Diagnostic] = []
        # self-loops: re-acquiring a non-reentrant lock deadlocks immediately
        for (outer, inner), sites in sorted(edges.items()):
            if outer != inner:
                continue
            if self._is_rlock(ctx, outer):
                continue
            mod = next((m for m in ctx.modules if m.short == sites[0][0]), ctx.modules[0])
            d = self.diag(
                Severity.ERROR,
                f"non-reentrant lock {outer} is re-acquired while already held "
                "(direct or through the call chain): the thread deadlocks "
                "against itself",
                module=mod, lineno=sites[0][1], function=sites[0][2],
                lock=outer,
            )
            if d is not None:
                out.append(d)
        # cycles of length >= 2
        for cycle in self._cycles(adj):
            pairs = list(zip(cycle, cycle[1:] + cycle[:1]))
            sites = [edges.get(p, [("?", 0, "?")])[0] for p in pairs]
            mod = next(
                (m for m in ctx.modules if m.short == sites[0][0]), ctx.modules[0]
            )
            where = "; ".join(
                f"{a}→{b} at {s[0]}.py:{s[1]} ({s[2]})" for (a, b), s in zip(pairs, sites)
            )
            d = self.diag(
                Severity.ERROR,
                "lock-order cycle: " + " → ".join(cycle + [cycle[0]]) + " — two "
                "threads taking these locks in opposite orders deadlock under "
                f"the wrong interleaving [{where}]",
                module=mod, lineno=sites[0][1], function=sites[0][2],
                cycle=cycle,
            )
            if d is not None:
                out.append(d)
        return out

    @staticmethod
    def _is_rlock(ctx: RuntimeAnalysisContext, ident: str) -> bool:
        scope, _, attr = ident.partition(".")
        for mod in ctx.modules:
            cls = mod.classes.get(scope)
            if cls is not None and attr in cls.lock_attrs:
                return cls.lock_attrs[attr].kind == "rlock"
            if mod.short == scope and attr in mod.global_locks:
                return mod.global_locks[attr].kind == "rlock"
        return False

    @staticmethod
    def _cycles(adj: Dict[str, Set[str]]) -> List[List[str]]:
        """Simple cycles (each reported once, rotated to its min node)."""
        seen: Set[Tuple[str, ...]] = set()
        out: List[List[str]] = []

        def dfs(start: str, node: str, path: List[str], visited: Set[str]) -> None:
            for nxt in sorted(adj.get(node, ())):
                if nxt == start and len(path) >= 2:
                    lo = path.index(min(path))
                    canon = tuple(path[lo:] + path[:lo])
                    if canon not in seen:
                        seen.add(canon)
                        out.append(list(canon))
                elif nxt not in visited and nxt > start:
                    visited.add(nxt)
                    dfs(start, nxt, path + [nxt], visited)
                    visited.discard(nxt)

        for start in sorted(adj):
            dfs(start, start, [start], {start})
        return out


class UnboundedWaitPass(ConcurrencyPass):
    """PWA102: ``Condition.wait``/``Event.wait``/``Queue.get`` with no timeout.
    The fence deadline, the supervisor's stall-killer, and teardown can only
    abort waits that periodically wake; an untimed wait is a wedge."""

    code = "PWA102"
    title = "unbounded blocking wait"

    def run(self, ctx: RuntimeAnalysisContext) -> List[Diagnostic]:
        out: List[Diagnostic] = []
        for mod, cls, fn in _iter_funcs(ctx):
            for site in fn.waits:
                if site.has_timeout:
                    continue
                kind = self._waitable_kind(ctx, site, cls)
                if kind is None or site.method not in _BLOCKING_METHODS.get(kind, ()):
                    continue
                d = self.diag(
                    Severity.ERROR,
                    f"{kind} {site.lock.scope}.{site.lock.attr}.{site.method}() "
                    "has no timeout: the epoch-fence deadline, the supervisor's "
                    "stall-killer, and shutdown cannot abort a wait that never "
                    "wakes — wait in a bounded loop and re-check the abort "
                    "condition",
                    module=mod, lineno=site.lineno, function=fn.qual,
                    primitive=kind, method=site.method,
                )
                if d is not None:
                    out.append(d)
        return out

    @staticmethod
    def _waitable_kind(
        ctx: RuntimeAnalysisContext, site: _WaitSite, cls: Optional[_ClassInfo]
    ) -> Optional[str]:
        lock = site.lock
        if lock.kind != "?":
            return lock.kind if lock.kind in _BLOCKING_METHODS else None
        if lock.scope != "?" and cls is not None:
            ld = ctx.class_lock(lock.scope, lock.attr)
            if ld is not None:
                return ld.kind if ld.kind in _BLOCKING_METHODS else None
            return None
        # terminal-attribute heuristic: `req.event.wait()` — the attr name
        # must resolve to primitives EVERYWHERE it is assigned, or the
        # receiver may be something else entirely
        if lock.attr in ctx.ambiguous_attrs:
            return None
        defs = ctx.attr_index.get(lock.attr, [])
        kinds = {d.kind for d in defs if d.kind in _BLOCKING_METHODS}
        if len(kinds) == 1:
            return next(iter(kinds))
        return None


class UnlockedSharedWritePass(ConcurrencyPass):
    """PWA103: an attribute written under a lock in one method and with no
    lock in another (inconsistent locking). Constructor-reachable-only code is
    exempt — no peer thread exists before ``__init__`` returns."""

    code = "PWA103"
    title = "shared-mutable write outside the owning lock"

    def run(self, ctx: RuntimeAnalysisContext) -> List[Diagnostic]:
        out: List[Diagnostic] = []
        for mod in ctx.modules:
            for cls in mod.classes.values():
                out.extend(self._check_class(ctx, mod, cls))
            # module-global equivalent over module-level functions
            guarded: Set[str] = set()
            for fn in mod.functions.values():
                for m in fn.mutations:
                    if m.attr.startswith("<global>") and m.locked:
                        guarded.add(m.attr)
            for fn in mod.functions.values():
                for m in fn.mutations:
                    if m.attr in guarded and not m.locked:
                        d = self.diag(
                            Severity.WARNING,
                            f"module global {m.attr[8:]!r} is written under a "
                            f"lock elsewhere but without one in {fn.qual}: "
                            "either every writer holds the lock or none "
                            "meaningfully does",
                            module=mod, lineno=m.lineno, function=fn.qual,
                            attr=m.attr[8:],
                        )
                        if d is not None:
                            out.append(d)
        return out

    def _check_class(
        self, ctx: RuntimeAnalysisContext, mod: _ModuleInfo, cls: _ClassInfo
    ) -> List[Diagnostic]:
        exempt = self._constructor_only(cls)
        guarded: Set[str] = set()
        for name, fn in cls.methods.items():
            if name.split(".")[0] in exempt:
                continue
            for m in fn.mutations:
                if m.locked:
                    guarded.add(m.attr)
        out: List[Diagnostic] = []
        for name, fn in cls.methods.items():
            if name.split(".")[0] in exempt:
                continue
            for m in fn.mutations:
                if m.attr in guarded and not m.locked:
                    d = self.diag(
                        Severity.WARNING,
                        f"{cls.name}.{m.attr} is written under a lock in other "
                        f"methods but without one in {fn.qual}: a concurrent "
                        "reader/writer can observe a torn update — hold the "
                        "owning lock here too (or mark the single-owner "
                        "convention with `# noqa: PWA103 (<why>)`)",
                        module=mod, lineno=m.lineno, function=fn.qual,
                        attr=m.attr, cls=cls.name,
                    )
                    if d is not None:
                        out.append(d)
        return out

    @staticmethod
    def _constructor_only(cls: _ClassInfo) -> Set[str]:
        """Methods reachable ONLY from ``__init__`` (and ``__init__`` itself):
        they run before any peer thread can exist, so unlocked writes there are
        single-threaded by construction. A method that escapes as a callback
        (``target=self._reader``) is never exempt."""
        callers: Dict[str, Set[str]] = {}
        for name, fn in cls.methods.items():
            base = name.split(".")[0]
            for call in fn.calls:
                if call.callee[0] == "method" and call.callee[1] == cls.name:
                    callers.setdefault(call.callee[2], set()).add(base)
        exempt: Set[str] = {"__init__"}
        changed = True
        while changed:
            changed = False
            for name in cls.methods:
                base = name.split(".")[0]
                if base in exempt or base in cls.escaped_methods:
                    continue
                who = callers.get(base)
                if who and who <= exempt:
                    exempt.add(base)
                    changed = True
        return exempt


class ThreadLifecyclePass(ConcurrencyPass):
    """PWA104: a thread that is neither daemon nor joined in its creating
    scope survives ``pw.run``/server teardown and wedges interpreter exit
    (non-daemon threads block process shutdown)."""

    code = "PWA104"
    title = "non-daemon thread with no join on the shutdown path"

    def run(self, ctx: RuntimeAnalysisContext) -> List[Diagnostic]:
        out: List[Diagnostic] = []
        for mod, _cls, fn in _iter_funcs(ctx):
            for site in fn.threads:
                if site.daemon or site.joined:
                    continue
                d = self.diag(
                    Severity.ERROR,
                    f"thread created in {fn.qual} is neither daemon=True nor "
                    "joined in this scope: it outlives run/teardown, holds its "
                    "resources, and blocks interpreter shutdown — pass "
                    "daemon=True (and make its loop abort-checked) or join it "
                    "on the shutdown path",
                    module=mod, lineno=site.lineno, function=fn.qual,
                )
                if d is not None:
                    out.append(d)
        return out


def default_concurrency_passes() -> List[ConcurrencyPass]:
    return [
        LockOrderPass(),
        UnboundedWaitPass(),
        UnlockedSharedWritePass(),
        ThreadLifecyclePass(),
    ]


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def analyze_runtime(
    paths: "Optional[List[str]]" = None,
    *,
    passes: "Optional[List[ConcurrencyPass]]" = None,
    ctx: "Optional[RuntimeAnalysisContext]" = None,
) -> AnalysisReport:
    """Run the PWA101–104 pipeline over the runtime modules (or ``paths``).
    Same report type as the graph lint: JSON shape, exit-code contract, and
    ``emit_telemetry`` all carry over."""
    from pathway_tpu.analysis.framework import run_runtime_passes

    if ctx is None:
        ctx = build_runtime_context(paths)
    if passes is None:
        passes = default_concurrency_passes()
    return run_runtime_passes(
        passes, ctx, family="concurrency",
        node_count=sum(1 for _ in _iter_funcs(ctx)),
    )


def analyze_source(source: str, name: str = "planted") -> AnalysisReport:
    """Lint one in-memory module (tests plant violations this way)."""
    info = _ModuleParser(name, f"<{name}>", source).parse()
    return analyze_runtime(ctx=RuntimeAnalysisContext([info]))


_cached_report: "Optional[AnalysisReport]" = None


def runtime_gate() -> None:
    """``PATHWAY_RUNTIME_LINT=off|warn|error`` (default ``off``): lint the
    runtime's own concurrency before a run. ``warn`` logs and mirrors counters;
    ``error`` refuses the run on any PWA101–104 error. The report is cached
    process-wide — the runtime source cannot change under a live process."""
    from pathway_tpu.analysis.framework import enforce_gate, gate_mode

    mode = gate_mode("PATHWAY_RUNTIME_LINT")
    if mode is None:
        return
    global _cached_report
    if _cached_report is None:
        _cached_report = analyze_runtime()
    enforce_gate(_cached_report, mode)
