"""Whole-commit fusion: compile operator chains into single programs.

The execution half of the fusion compiler (planning lives in
``pathway_tpu/analysis/fusion.py``). A :class:`ChainProgram` executes one
maximal run of consecutive ``rowwise``/``filter`` nodes as a single unit
instead of one evaluator dispatch per node:

- **composed evaluation** — the chain's column environment flows node to node
  with no intermediate ``Delta`` objects, no per-node state-table traffic, and
  dead-column elimination (an interior column nothing downstream reads is
  never computed, provided its expression is pure — see ``PURE_EXPRS``);
- **XLA lowering** — a run of map steps whose expressions are built from
  device-friendly scalar ops lowers to ONE jitted JAX program; shapes are
  padded to pow2 buckets (``internals/shapes.py``) so ragged commit sizes hit
  a bounded jit cache, and the padded operand buffers are donated so XLA may
  write outputs in place;
- **bitwise honesty** — the first batch through every lowered program is ALSO
  evaluated by the stock interpreter and compared byte-for-byte (dtypes
  included). Any deviation (e.g. FMA contraction on float chains — XLA:CPU
  contracts ``a*b+c`` where numpy rounds twice) permanently downgrades that
  program to the interpreter and bumps ``fuse.jit_parity_rejects``. Fused
  output is bit-identical to unfused BY CONSTRUCTION, not by hope.

Stateful members of a fused region (join/groupby/concat) keep executing
through their own incremental evaluators — their arrangements ARE the carried
state, held across commits rather than re-materialized per substep — while the
chains around them fuse. Counters ride ``engine/telemetry.py`` under
``fuse.*``; the region plan is logged as a ``fusion`` flight-recorder event.

Env knobs: ``PATHWAY_FUSION=off|on`` (runner gate, default on);
``PATHWAY_FUSION_JIT_ROWS`` — minimum batch rows before a lowered program
dispatches to XLA (default 32768; below it the interpreter wins on host);
``PATHWAY_FUSION_JIT=0`` — disable the XLA path, keep composed evaluation.
"""

from __future__ import annotations

import operator
import os
import time as time_mod
from typing import Any, Dict, List, Optional, Set, Tuple

import numpy as np

from pathway_tpu.analysis.fusion import ChainSpec, expr_pure
from pathway_tpu.engine import expression_evaluator as ee
from pathway_tpu.engine import telemetry
from pathway_tpu.engine.columnar import Delta
from pathway_tpu.internals import expression as expr
from pathway_tpu.internals import parse_graph as pg
from pathway_tpu.internals.shapes import next_pow2

# operators that lower 1:1 onto jnp arrays through their dunder dispatch and
# are bit-exact per op (integer ops exact; float add/sub/mul/cmp exact PER OP —
# cross-op contraction is what the parity probe exists to catch)
_LOWER_OPS: Set[Any] = {
    operator.add, operator.sub, operator.mul,
    operator.gt, operator.lt, operator.ge, operator.le,
    operator.eq, operator.ne,
    operator.and_, operator.or_, operator.xor,
    operator.lshift, operator.rshift,
}
# division family lowers only with a CONSTANT nonzero right operand: the
# interpreter's zero-divisor path poisons cells with host Error objects,
# which no device program can reproduce
_DIV_OPS: Set[Any] = {operator.truediv, operator.floordiv, operator.mod}
_LOWER_UNARY: Set[Any] = {operator.neg, operator.not_}

_JIT_FLOOR = 8  # minimum pow2 pad bucket (lane alignment; shared convention)


def _jit_threshold() -> int:
    try:
        return max(1, int(os.environ.get("PATHWAY_FUSION_JIT_ROWS", str(1 << 15))))
    except ValueError:
        return 1 << 15


def _jit_enabled() -> bool:
    return os.environ.get("PATHWAY_FUSION_JIT", "").lower() not in (
        "0", "false", "no", "off",
    )


def _lowerable(e: expr.ColumnExpression) -> bool:
    """True when the whole tree maps onto the jnp op whitelist (static check;
    runtime dtypes are verified per batch, and the parity probe has the final
    word)."""
    if isinstance(e, expr.ColumnConstExpression):
        v = e._value
        return isinstance(v, (bool, int, float, np.bool_, np.integer, np.floating))
    if isinstance(e, expr.ColumnReference):
        return e.name != "id"  # key pointers are host objects
    if isinstance(e, expr.ColumnBinaryOpExpression):
        op = e._operator
        if op in _DIV_OPS:
            right = e._right
            if not (
                isinstance(right, expr.ColumnConstExpression)
                and isinstance(right._value, (int, float, np.integer, np.floating))
                and not isinstance(right._value, bool)
                and right._value != 0
            ):
                return False
            return _lowerable(e._left)
        return op in _LOWER_OPS and _lowerable(e._left) and _lowerable(e._right)
    if isinstance(e, expr.ColumnUnaryOpExpression):
        return e._operator in _LOWER_UNARY and _lowerable(e._expr)
    if isinstance(e, expr.IfElseExpression):
        return _lowerable(e._if) and _lowerable(e._then) and _lowerable(e._else)
    return False


def _expr_ref_names(e: expr.ColumnExpression) -> Set[str]:
    return {ref.name for ref in e._column_refs}


def _to_host_view(out: Any, rows: int) -> np.ndarray:
    """Host ndarray over a program output, zero-copy where the backend allows.

    On the CPU backend the XLA output buffer IS host memory: ``np.from_dlpack``
    wraps it without the ~1 ms/MB copy ``np.asarray`` pays per column. The
    returned view keeps the producing buffer alive (dlpack capsule ref), and
    deltas are immutable once emitted, so sharing is safe. Any failure (older
    jax, non-CPU backend layouts) falls back to the copying path."""
    try:
        arr = np.from_dlpack(out)
    except Exception:
        arr = np.asarray(out)
    return arr[:rows]


class _RunStep:
    """One map node inside a lowered run, split into *computed* columns (these
    lower to XLA) and *aliases* — bare column renames/pass-throughs, which stay
    host-side array references exactly like the interpreter's resolver returns
    them (a string key threading through an arithmetic chain must neither
    block lowering nor round-trip through the device)."""

    __slots__ = ("node", "compute", "aliases")

    def __init__(self, node: pg.Node, live: List[str]):
        self.node = node
        self.compute: Dict[str, expr.ColumnExpression] = {}
        self.aliases: Dict[str, str] = {}
        exprs = node.config["exprs"]
        for name in live:
            e = exprs[name]
            if isinstance(e, expr.ColumnReference) and e.name != "id":
                self.aliases[name] = e.name
            else:
                self.compute[name] = e


class _LoweredRun:
    """One maximal run of consecutive map steps (plus, optionally, the mask of
    the filter immediately after) lowered to a single jitted XLA program.

    ``steps`` is a list of :class:`_RunStep` — every *computed* expression
    statically lowerable; aliases propagate host-side. ``outputs`` lists the
    externally visible computed columns as ``(step_index, name)``; the mask,
    when present, rides as one extra output. The jit cache is keyed by the
    pow2 row bucket; input buffers are fresh pad copies owned by this run, so
    they are donated where the backend supports it (XLA may write outputs
    into the input storage instead of allocating)."""

    def __init__(
        self,
        steps: List[_RunStep],
        in_names: List[str],
        outputs: List[Tuple[int, str]],
        mask_node: "pg.Node | None",
    ):
        self.steps = steps
        self.in_names = in_names
        self.outputs = outputs
        self.mask_node = mask_node
        self._fns: Dict[int, Any] = {}  # pow2 bucket -> jitted fn
        self.compiles = 0
        # pow2 buckets whose compiled program passed the first-batch bitwise
        # parity check. Verification is PER BUCKET, matching the compile
        # granularity: each bucket is a distinct XLA program and the backend
        # may make different codegen choices per shape (a verified 64k-bucket
        # program says nothing about the 256k one).
        self.verified: Set[int] = set()
        self.disabled = not _jit_enabled()
        self.hits = 0

    @property
    def mask_expr(self) -> "expr.ColumnExpression | None":
        return None if self.mask_node is None else self.mask_node.config["expression"]

    # -- tracing --------------------------------------------------------------

    def _lower_expr(self, e: Any, env: Dict[str, Any], n: int, jnp: Any) -> Any:
        if isinstance(e, expr.ColumnConstExpression):
            v = e._value
            if isinstance(v, (bool, np.bool_)):
                return jnp.full((n,), bool(v), dtype=np.bool_)
            if isinstance(v, (int, np.integer)):
                return jnp.full((n,), int(v), dtype=np.int64)
            return jnp.full((n,), float(v), dtype=np.float64)
        if isinstance(e, expr.ColumnReference):
            return env[e.name]
        if isinstance(e, expr.ColumnBinaryOpExpression):
            left = self._lower_expr(e._left, env, n, jnp)
            right = self._lower_expr(e._right, env, n, jnp)
            op = e._operator
            # mirror ExpressionEvaluator._eval_ColumnBinaryOpExpression's
            # numeric path: bool coercion for the bitwise trio, nothing else
            if op in (operator.and_, operator.or_, operator.xor) and (
                left.dtype == np.bool_ or right.dtype == np.bool_
            ):
                return op(left.astype(np.bool_), right.astype(np.bool_))
            return op(left, right)
        if isinstance(e, expr.ColumnUnaryOpExpression):
            val = self._lower_expr(e._expr, env, n, jnp)
            if e._operator is operator.not_:
                return ~val.astype(np.bool_)
            return e._operator(val)
        if isinstance(e, expr.IfElseExpression):
            cond = self._lower_expr(e._if, env, n, jnp)
            then = self._lower_expr(e._then, env, n, jnp)
            other = self._lower_expr(e._else, env, n, jnp)
            if then.dtype != other.dtype:
                common = np.promote_types(then.dtype, other.dtype)
                then = then.astype(common)
                other = other.astype(common)
            return jnp.where(cond, then, other)
        raise NotImplementedError(type(e).__name__)

    def _fn_for(self, bucket: int) -> Any:
        fn = self._fns.get(bucket)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        def traced(*arrays: Any) -> tuple:
            env = dict(zip(self.in_names, arrays))
            step_envs: List[Dict[str, Any]] = []
            for step in self.steps:
                new_env = {
                    out: env[src] for out, src in step.aliases.items() if src in env
                }
                for name, e in step.compute.items():
                    new_env[name] = self._lower_expr(e, env, bucket, jnp)
                env = new_env
                step_envs.append(env)
            outs = [step_envs[idx][name] for idx, name in self.outputs]
            if self.mask_node is not None:
                outs.append(self._lower_expr(self.mask_expr, env, bucket, jnp))
            return tuple(outs)

        # padded operand buffers are fresh copies owned by the caller: donate
        # them so XLA may write outputs into the input storage. The CPU
        # backend does not implement donation (warns and copies) — donate only
        # where it is real.
        donate: tuple = ()
        if jax.default_backend() != "cpu":
            donate = tuple(range(len(self.in_names)))
        fn = jax.jit(traced, donate_argnums=donate)
        self._fns[bucket] = fn
        self.compiles += 1
        telemetry.stage_add("fuse.jit_compiles")
        return fn

    # -- dispatch -------------------------------------------------------------

    def run_device(
        self, env: Dict[str, np.ndarray], rows: int
    ) -> "Optional[Tuple[Dict[Tuple[int, str], np.ndarray], Optional[np.ndarray], int]]":
        """Execute on device; returns ``(outputs by (step, name), mask,
        bucket)`` or None when ineligible (dtypes, compile failure) — the
        caller falls back to the interpreter."""
        if self.disabled:
            return None
        arrays = []
        for name in self.in_names:
            col = env[name]
            if col.dtype == object or col.dtype.kind not in "bif":
                telemetry.stage_add("fuse.jit_dtype_fallbacks")
                return None
            arrays.append(col)
        from jax import enable_x64

        bucket = next_pow2(rows, _JIT_FLOOR)
        padded = []
        for col in arrays:
            # empty + explicit tail zero: one pass over the buffer instead of
            # zeros-then-overwrite (the pad region only feeds pad outputs,
            # which are sliced away; zeroing keeps it deterministic anyway)
            buf = np.empty(bucket, dtype=col.dtype)
            buf[:rows] = col
            buf[rows:] = 0
            padded.append(buf)
        try:
            with enable_x64():
                fn = self._fn_for(bucket)
                outs = fn(*padded)
        except Exception:
            # any tracing/compile/runtime failure: interpreter takes over for
            # the rest of this run's lifetime — never the commit's
            self.disabled = True
            telemetry.stage_add("fuse.jit_errors")
            return None
        self.hits += 1
        telemetry.stage_add("fuse.jit_hits")
        host = [_to_host_view(o, rows) for o in outs]
        mask: "Optional[np.ndarray]" = None
        if self.mask_node is not None:
            mask = host.pop().astype(bool)
        return dict(zip(self.outputs, host)), mask, bucket


class ChainProgram:
    """Executable form of one planned :class:`ChainSpec`.

    Per commit, the program pulls the head's input delta from the substep's
    ``deltas`` dict, streams the column environment through its steps (maps
    compose; filters compact eagerly so error-poisoning/row-set semantics stay
    identical to per-node dispatch), and materializes real ``Delta`` objects
    only for *exported* nodes — nodes some consumer outside the chain (or the
    state/undo machinery) actually observes. Bookkeeping (step counts, state
    application, undo capture, profiler attribution) mirrors
    ``GraphRunner._substep`` exactly — the bitwise-parity contract is with the
    per-node dispatch path, commit by commit."""

    def __init__(self, runner: Any, spec: ChainSpec, consumers: Dict[int, List[pg.Node]]):
        node_by_id = {n.id: n for n in runner._nodes}
        self.spec = spec
        self.nodes: List[pg.Node] = [node_by_id[nid] for nid in spec.node_ids]
        self.input_id = spec.input_id
        self._input_table = self.nodes[0].inputs[0]
        chain_ids = set(spec.node_ids)
        self.name = f"fuse:{self.nodes[0].name}+{len(self.nodes) - 1}"

        # exported = observable outside the fused program: an outside consumer
        # reads deltas[id], or the node's state table is materialized (state
        # application must happen delta-by-delta for checkpoint/undo parity)
        self.export: Dict[int, bool] = {}
        for i, node in enumerate(self.nodes):
            outside = any(c.id not in chain_ids for c in consumers.get(node.id, []))
            self.export[node.id] = (
                outside or node.id in runner._materialized or i == len(self.nodes) - 1
            )

        # live-column analysis, back to front: an exported node needs every
        # output column; an interior node needs the columns the next step
        # references, plus any non-pure column (whose evaluation could raise —
        # skipping it would be observable on error paths)
        self.live: Dict[int, List[str]] = {}
        needed_next: Set[str] = set()
        for i in range(len(self.nodes) - 1, -1, -1):
            node = self.nodes[i]
            all_cols = runner.output_columns_of(node)
            if node.kind == "filter":
                live = list(all_cols) if self.export[node.id] else [
                    c for c in all_cols if c in needed_next
                ]
                self.live[node.id] = live
                needed_next = set(live) | _expr_ref_names(node.config["expression"])
            else:
                exprs = node.config["exprs"]
                if self.export[node.id]:
                    live = list(all_cols)
                else:
                    live = [
                        c
                        for c in all_cols
                        if c in needed_next or not expr_pure(exprs[c])
                    ]
                self.live[node.id] = live
                needed_next = set()
                for c in live:
                    needed_next |= _expr_ref_names(exprs[c])

        self._build_runs()
        telemetry.stage_add_many({
            "fuse.chains_built": 1.0,
            "fuse.ops_fused": float(len(self.nodes)),
        })

    # -- jit run construction -------------------------------------------------

    def _build_runs(self) -> None:
        """Group consecutive lowerable map steps (optionally capped by the next
        filter's mask) into lowered runs. A run is lowered atomically: every
        live column of every step must be statically lowerable, else the run
        ends there (earlier lowerable steps still form a run; the rest stays
        on the interpreter — composed, just not on device)."""
        self.runs: Dict[int, _LoweredRun] = {}  # start step index -> run
        i = 0
        n_nodes = len(self.nodes)
        while i < n_nodes:
            node = self.nodes[i]
            if node.kind == "filter":
                if _lowerable(node.config["expression"]):
                    run = self._make_run(i, i - 1, mask_idx=i)  # mask-only
                    if run is not None:
                        self.runs[i] = run
                i += 1
                continue
            if not all(
                _lowerable(node.config["exprs"][c]) for c in self.live[node.id]
            ):
                i += 1
                continue
            j = i
            while (
                j + 1 < n_nodes
                and self.nodes[j + 1].kind == "rowwise"
                and all(
                    _lowerable(self.nodes[j + 1].config["exprs"][c])
                    for c in self.live[self.nodes[j + 1].id]
                )
            ):
                j += 1
            mask_idx = None
            if (
                j + 1 < n_nodes
                and self.nodes[j + 1].kind == "filter"
                and _lowerable(self.nodes[j + 1].config["expression"])
            ):
                mask_idx = j + 1
            run = self._make_run(i, j, mask_idx=mask_idx)
            if run is not None:
                self.runs[i] = run
            i = j + 1 if mask_idx is None else j + 2

    def _make_run(
        self, start: int, end: int, mask_idx: "int | None"
    ) -> "Optional[_LoweredRun]":
        steps: List[_RunStep] = []
        in_names: Set[str] = set()
        outputs: List[Tuple[int, str]] = []
        # origin[name] = the run-INPUT column a name aliases back to, or None
        # for computed values: a compute expression referencing an alias chain
        # pulls the underlying input column into the traced program's operands.
        # The run's input level is the PREVIOUS chain node's output (the chain
        # input only for a run starting at the head).
        if start == 0:
            base_cols = self._input_table.column_names()
        else:
            prev = self.nodes[start - 1]
            base_cols = prev.output.column_names() if prev.output is not None else []
        origin: Dict[str, "str | None"] = {c: c for c in base_cols}

        def need_refs(e: expr.ColumnExpression) -> None:
            for name in _expr_ref_names(e):
                src = origin.get(name)
                if src is not None:
                    in_names.add(src)

        for k in range(start, end + 1):
            step = _RunStep(self.nodes[k], self.live[self.nodes[k].id])
            for e in step.compute.values():
                need_refs(e)
            new_origin: Dict[str, "str | None"] = {
                out: origin.get(src) for out, src in step.aliases.items()
            }
            for name in step.compute:
                new_origin[name] = None
            origin = new_origin
            steps.append(step)
            # run outputs, for steps whose env the host observes (the last
            # step, and exported mid-run nodes whose full deltas must
            # materialize): every live column that does NOT alias back to a
            # run input — computed columns and aliases of computed columns
            # ride the device; input-origin aliases propagate host-side as
            # the same array references the interpreter would return
            if k == end or self.export[step.node.id]:
                outputs.extend(
                    (k - start, c)
                    for c in self.live[step.node.id]
                    if origin.get(c) is None
                )
        mask_node = self.nodes[mask_idx] if mask_idx is not None else None
        if mask_node is not None:
            need_refs(mask_node.config["expression"])
        if not in_names:
            return None  # constant-only program: not worth a device dispatch
        if not outputs and mask_node is None:
            return None
        return _LoweredRun(steps, sorted(in_names), outputs, mask_node)

    # -- interpreter building blocks (exact per-node parity) ------------------

    def _interp_exprs(
        self,
        node: pg.Node,
        exprs: Dict[str, expr.ColumnExpression],
        keys: np.ndarray,
        env: Dict[str, np.ndarray],
        rows: int,
        runtime: Dict[str, Any],
    ) -> Dict[str, np.ndarray]:
        from pathway_tpu.engine.evaluators import id_pointer_column

        runtime["node"] = node
        id_cache: List[Any] = []

        def resolver(ref: expr.ColumnReference) -> np.ndarray:
            if ref.name == "id":
                if not id_cache:
                    id_cache.append(id_pointer_column(keys))
                return id_cache[0]
            return env[ref.name]

        try:
            return {name: ee.evaluate(e, rows, resolver) for name, e in exprs.items()}
        except Exception as exc:
            from pathway_tpu.internals.trace import add_error_context

            raise add_error_context(exc, node) from exc

    def _mask_of(
        self,
        node: pg.Node,
        keys: np.ndarray,
        env: Dict[str, np.ndarray],
        rows: int,
        runtime: Dict[str, Any],
    ) -> np.ndarray:
        from pathway_tpu.engine.evaluators import filter_mask_to_bool

        mask = self._interp_exprs(
            node, {"__mask__": node.config["expression"]}, keys, env, rows, runtime
        )["__mask__"]
        # the SHARED coercion rule (poisoned predicate cells drop the row):
        # bitwise lockstep with FilterEvaluator by construction
        return filter_mask_to_bool(mask)

    def _probe_parity(
        self,
        run: _LoweredRun,
        keys: np.ndarray,
        env: Dict[str, np.ndarray],
        rows: int,
        runtime: Dict[str, Any],
        out_map: Dict[Tuple[int, str], np.ndarray],
        mask: "Optional[np.ndarray]",
        bucket: int,
    ) -> bool:
        """First-batch honesty check, PER POW2 BUCKET (each bucket is its own
        compiled program): interpreter vs device, byte-for-byte and
        dtype-for-dtype. A reject permanently downgrades the whole run — one
        divergent bucket means the lowering cannot be trusted."""
        ref_env = dict(env)
        step_envs: List[Dict[str, np.ndarray]] = []
        for step in run.steps:
            exprs = {
                c: step.node.config["exprs"][c] for c in self.live[step.node.id]
            }
            ref_env = self._interp_exprs(step.node, exprs, keys, ref_env, rows, runtime)
            step_envs.append(ref_env)
        ok = True
        for (idx, name), got in out_map.items():
            want = step_envs[idx][name]
            if got.dtype != want.dtype or got.tobytes() != want.tobytes():
                ok = False
                break
        if ok and mask is not None:
            want_mask = self._mask_of(
                run.mask_node, keys, step_envs[-1] if step_envs else env, rows, runtime
            )
            if mask.tobytes() != want_mask.tobytes():
                ok = False
        if not ok:
            run.disabled = True
            telemetry.stage_add("fuse.jit_parity_rejects")
            return False
        run.verified.add(bucket)
        telemetry.stage_add("fuse.jit_parity_verified")
        return True

    # -- execution ------------------------------------------------------------

    def execute(
        self,
        runner: Any,
        deltas: Dict[int, Delta],
        neu: bool,
        profile_ops: "List[tuple] | None",
        runtime: Dict[str, Any],
    ) -> bool:
        t0 = time_mod.perf_counter() if profile_ops is not None else 0.0
        self._profiling = profile_ops is not None
        in_delta = deltas.get(
            self.input_id, Delta.empty(self._input_table.column_names())
        )
        rows = len(in_delta)
        rowcounts: List[Tuple[pg.Node, int, int]] = []  # (node, rows, retractions)
        if rows == 0:
            # per-node dispatch would skip every chain node (empty input, no
            # pending state, no cluster barrier) and emit Delta.empty
            for node in self.nodes:
                if self.export[node.id]:
                    deltas[node.id] = Delta.empty(runner.output_columns_of(node))
            self._profile(profile_ops, t0, rowcounts, neu)
            return False
        threshold = _jit_threshold()
        keys, diffs = in_delta.keys, in_delta.diffs
        env: Dict[str, np.ndarray] = dict(in_delta.columns)
        any_output = False
        i = 0
        n_nodes = len(self.nodes)
        while i < n_nodes:
            node = self.nodes[i]
            if rows == 0:
                # a filter dropped everything: downstream chain nodes see empty
                # inputs and skip, exactly like per-node dispatch
                if self.export[node.id]:
                    deltas[node.id] = Delta.empty(runner.output_columns_of(node))
                i += 1
                continue
            run = self.runs.get(i)
            device_mask: "Optional[np.ndarray]" = None
            if run is not None and rows >= threshold and not run.disabled:
                got = run.run_device(env, rows)
                if got is not None and got[2] not in run.verified:
                    if not self._probe_parity(
                        run, keys, env, rows, runtime, got[0], got[1], got[2]
                    ):
                        got = None  # parity reject: interpreter from here on
                if got is not None:
                    out_map, device_mask, _bucket = got
                    for k, step in enumerate(run.steps):
                        # host-side env: alias propagation (same array refs the
                        # interpreter's resolver would return) + device outputs
                        new_env = {
                            out: env[src]
                            for out, src in step.aliases.items()
                            if src in env
                        }
                        for (kk, name), arr in out_map.items():
                            if kk == k:
                                new_env[name] = arr
                        env = new_env
                        self._after_map(
                            step.node, keys, diffs, env, rows, deltas, runner,
                            neu, rowcounts,
                        )
                        any_output = True
                    i += len(run.steps)
                    if device_mask is None:
                        continue
                    node = self.nodes[i]  # the filter the mask belongs to
            if node.kind == "rowwise":
                exprs = {c: node.config["exprs"][c] for c in self.live[node.id]}
                env = self._interp_exprs(node, exprs, keys, env, rows, runtime)
                self._after_map(
                    node, keys, diffs, env, rows, deltas, runner, neu, rowcounts
                )
                any_output = True
                i += 1
                continue
            # filter
            mask = (
                device_mask
                if device_mask is not None
                else self._mask_of(node, keys, env, rows, runtime)
            )
            keys = keys[mask]
            diffs = diffs[mask]
            env = {c: env[c][mask] for c in self.live[node.id]}
            rows = len(keys)
            if self.export[node.id]:
                delta = Delta(keys, diffs, dict(env))
                delta.neu = in_delta.neu
                if neu and rows:
                    delta.neu = True
                self._book(node, delta, deltas, runner, rowcounts)
            elif rows:
                runner._step_counts[node.id] = (
                    runner._step_counts.get(node.id, 0) + rows
                )
                rowcounts.append((node, rows, self._retr(diffs)))
            if rows:
                any_output = True
            i += 1
        self._profile(profile_ops, t0, rowcounts, neu)
        return any_output

    # -- bookkeeping (mirrors GraphRunner._substep per-node accounting) -------

    def _retr(self, diffs: np.ndarray) -> int:
        return int(np.count_nonzero(diffs < 0)) if self._profiling else 0

    def _after_map(
        self,
        node: pg.Node,
        keys: np.ndarray,
        diffs: np.ndarray,
        env: Dict[str, np.ndarray],
        rows: int,
        deltas: Dict[int, Delta],
        runner: Any,
        neu: bool,
        rowcounts: List[tuple],
    ) -> None:
        if self.export[node.id]:
            delta = Delta(
                keys, diffs, {c: env[c] for c in runner.output_columns_of(node)}
            )
            if neu and rows:
                delta.neu = True
            self._book(node, delta, deltas, runner, rowcounts)
        elif rows:
            runner._step_counts[node.id] = runner._step_counts.get(node.id, 0) + rows
            rowcounts.append((node, rows, self._retr(diffs)))

    def _book(
        self,
        node: pg.Node,
        delta: Delta,
        deltas: Dict[int, Delta],
        runner: Any,
        rowcounts: List[tuple],
    ) -> None:
        if (
            runner._undo_current is not None
            and node.id not in runner._undo_current["evals"]
        ):
            runner._capture_undo_state(node, runner.evaluators[node.id])
        deltas[node.id] = delta
        n = len(delta)
        if not n:
            return
        runner._step_counts[node.id] = runner._step_counts.get(node.id, 0) + n
        rowcounts.append((node, n, self._retr(delta.diffs)))
        if node.output is not None and node.id in runner._materialized:
            if runner._undo_current is not None:
                runner._undo_current["applied"].append((node.id, delta))
            runner.states[node.id].apply(delta)

    def _profile(
        self,
        profile_ops: "List[tuple] | None",
        t0: float,
        rowcounts: List[tuple],
        neu: bool,
    ) -> None:
        """Region row + per-member estimates (PR-5 metrics plane): the region's
        wall time is real; member seconds are attributed proportionally to
        their output rows so the ``/metrics`` operator families stay live."""
        if profile_ops is None:
            return
        elapsed = time_mod.perf_counter() - t0
        total_rows = sum(r for _n, r, _ret in rowcounts)
        head = self.nodes[0]
        profile_ops.append(
            (head.id, self.name, "fused_chain", elapsed, total_rows,
             sum(ret for _n, _r, ret in rowcounts), neu)
        )
        counted = {n.id: (r, ret) for n, r, ret in rowcounts}
        for node in self.nodes:
            r, ret = counted.get(node.id, (0, 0))
            est = (
                elapsed * (r / total_rows) if total_rows else elapsed / len(self.nodes)
            )
            profile_ops.append((node.id, node.name, node.kind, est, r, ret, neu))

    # -- introspection --------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "nodes": [n.id for n in self.nodes],
            "runs": len(self.runs),
            "jit_compiles": sum(r.compiles for r in self.runs.values()),
            "jit_buckets": sorted({b for r in self.runs.values() for b in r._fns}),
            "jit_hits": sum(r.hits for r in self.runs.values()),
            "jit_verified": sum(1 for r in self.runs.values() if r.verified),
            "jit_disabled": sum(1 for r in self.runs.values() if r.disabled),
        }


def build_schedule(runner: Any, plan: Any) -> "Optional[List[Any]]":
    """Turn a :class:`FusionPlan` into the runner's substep schedule: the node
    list with every planned chain collapsed into a :class:`ChainProgram` at the
    position of its first member. Returns None when nothing fuses (the runner
    then keeps the stock loop — zero new code on that path)."""
    if not plan.chains:
        return None
    consumers: Dict[int, List[pg.Node]] = {}
    for node in runner._nodes:
        for table in node.inputs:
            consumers.setdefault(table._node.id, []).append(node)
    head_of: Dict[int, ChainSpec] = {c.node_ids[0]: c for c in plan.chains}
    in_chain: Set[int] = {nid for c in plan.chains for nid in c.node_ids}
    schedule: List[Any] = []
    for node in runner._nodes:
        spec = head_of.get(node.id)
        if spec is not None:
            schedule.append(ChainProgram(runner, spec, consumers))
        elif node.id not in in_chain:
            schedule.append(node)
    telemetry.stage_add_many({
        "fuse.regions": float(len(plan.regions)),
        "fuse.schedules_built": 1.0,
    })
    return schedule
