"""Incremental operator evaluators — the differential-dataflow replacement.

Each parse-graph node kind gets an evaluator that consumes input ``Delta`` batches and emits an
output ``Delta`` per commit, maintaining whatever keyed state incrementality requires. This
mirrors the reference's DD operator implementations in ``src/engine/dataflow.rs`` (joins,
groupby, ix, concat, flatten, sort via prev/next) at batch granularity. Dense numeric work
inside a batch (expression trees, reducer sums, KNN search) is delegated to vectorized
numpy/JAX kernels.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import numpy as np

from pathway_tpu.engine import expression_evaluator as ee
from pathway_tpu.engine import telemetry
from pathway_tpu.engine.columnar import ERROR, Delta, Error, StateTable, empty_keys, objarray
from pathway_tpu.internals import expression as expr
from pathway_tpu.internals import parse_graph as pg
from pathway_tpu.internals.keys import (
    KEY_DTYPE,
    Pointer,
    broadcast_key,
    key_bytes,
    combine_keys,
    hash_upsert,
    keys_from_values,
    keys_to_pointers,
    pointer_from,
    pointers_to_keys,
)
from pathway_tpu.internals.reducers import _IdMarker, _SeqMarker


class UnpicklableStateError(Exception):
    """Operator state can't be checkpointed; the journal must keep full history."""


def _collect_nondet_exprs(value: Any, found: List[Any], seen: set) -> None:
    """Deterministic walk over a node config collecting non-deterministic apply
    expressions (dicts by sorted key, sequences in order, expression trees by
    ``_deps`` order) — the walk order IS the expressions' stable identity across
    process restarts, so memoized replay state can live in operator snapshots."""
    if isinstance(value, expr.ColumnExpression):
        if id(value) in seen:
            return
        seen.add(id(value))
        if isinstance(value, expr.ApplyExpression) and not value._deterministic:
            found.append(value)
        for dep in value._deps():
            _collect_nondet_exprs(dep, found, seen)
    elif isinstance(value, dict):
        for k in sorted(value, key=repr):
            _collect_nondet_exprs(value[k], found, seen)
    elif isinstance(value, (list, tuple)):
        for v in value:
            _collect_nondet_exprs(v, found, seen)


def _to_host(v: Any) -> Any:
    if type(v).__module__.startswith("jax"):
        return np.asarray(v)
    return v


def filter_mask_to_bool(mask: np.ndarray) -> np.ndarray:
    """Filter predicate column → boolean row mask: poisoned (Error) cells drop
    the row. ONE home for the rule — FilterEvaluator and the fusion compiler's
    composed filters (``engine/fusion.py``) must stay in bitwise lockstep."""
    if mask.dtype == object:
        mask = np.frompyfunc(
            lambda v: bool(v) if not isinstance(v, Error) else False, 1, 1
        )(mask).astype(bool)
    return mask.astype(bool)


def id_pointer_column(keys: np.ndarray) -> np.ndarray:
    """The materialized ``id`` pseudo-column: row-key Pointers boxed in an
    object array — shared by ``Evaluator._resolver_for`` and the fusion
    chain resolver so both paths box identically."""
    out = np.empty(len(keys), dtype=object)
    out[:] = keys_to_pointers(keys)
    return out


class Evaluator:
    def __init__(self, node: pg.Node, runner: Any):
        self.node = node
        self.runner = runner
        self.output_columns: List[str] = (
            node.output.column_names() if node.output is not None else []
        )
        found: List[Any] = []
        _collect_nondet_exprs(node.config, found, set())
        # id(expr) -> stable token; the token keys _udf_memo so replay state
        # survives a checkpoint/restore round-trip (id() does not)
        self._memo_tokens: Dict[int, str] = {
            id(e): f"nd{i}" for i, e in enumerate(found)
        }

    def process(self, input_deltas: List[Delta]) -> Delta:
        raise NotImplementedError

    # -- multi-process placement (parallel/cluster.py) ------------------------
    #
    # Per-input routing policy applied by the runner before ``process`` when a
    # spawn cluster is active (reference: timely Exchange pacts per operator,
    # ``shard.rs`` routing; centralization ``time_column.rs:48-51``):
    #   None        — rows stay where they were produced (stateless / row-local)
    #   "rowkey"    — hash-exchange by row key: same-key rows of every such
    #                 input meet on the key's owner process
    #   "custom"    — hash-exchange by ``cluster_route_keys(idx, delta)``
    #   "root"      — centralize the input on process 0 (global-order state)
    #   "broadcast" — replicate the input on every process (replicated state)
    # An evaluator with ANY non-None policy participates in the all-to-all
    # barrier every commit, even with no local rows.

    CLUSTER_POLICIES: Dict[int, str] = {}
    _cluster_policies: tuple = ()  # resolved per-instance by GraphRunner.setup
    _cluster_barrier: bool = False
    # incremental rewind (GraphRunner._capture_undo_state): True means a
    # pre-commit state_dict()/load_state_dict() round-trip exactly restores
    # this operator, so a fenced survivor may undo an interrupted commit in
    # place. Set False on operators whose per-commit state snapshot is
    # unreasonable (huge or externally mutated in place) — the graph then
    # skips the rewind rung and fences use checkpoint + tail replay.
    # The PWA002 graph-lint pass (pathway_tpu/analysis) reports every
    # REWIND_SAFE=False operator at build time; any evaluator that flushes on
    # ``runner.draining`` (a live-only signal replay cannot reproduce) MUST set
    # this False — tests/test_analysis.py audits that invariant by source scan.
    REWIND_SAFE = True
    # False when this operator's state sits outside the snapshot protocol
    # (device-resident / externally mutated): state_dict() would abort the
    # checkpoint or restore an empty shell. The PWA005 lint pass reports such
    # operators in persistence-enabled graphs at build time.
    SNAPSHOT_CAPTURE = True

    def cluster_input_policy(self, idx: int) -> str | None:
        return self.CLUSTER_POLICIES.get(idx)

    def cluster_route_keys(self, idx: int, delta: Delta) -> np.ndarray:
        raise NotImplementedError  # required for "custom" policies only

    # -- operator snapshots (reference ``operator_snapshot.rs``) -------------

    _NON_STATE_ATTRS = (
        "node", "runner", "output_columns", "_memo_tokens",
        "_cluster_policies", "_cluster_barrier",
    )

    def state_dict(self) -> Dict[str, bytes]:
        """Picklable per-attribute snapshot of this operator's incremental state.
        Graph-config attributes (expressions, callbacks) are excluded by name via
        ``_NON_STATE_ATTRS`` — they are rebuilt identically from the (sig-checked) graph
        on restore. A *state* attribute that fails to pickle aborts the checkpoint
        (``UnpicklableStateError``): silently dropping it would compact away journal
        history the restore then cannot reconstruct."""
        import pickle

        out: Dict[str, bytes] = {}
        for name, value in self.__dict__.items():
            if name in self._NON_STATE_ATTRS:
                continue
            if name == "_udf_memo":
                # replay values may be device arrays (the serving path keeps
                # query embeddings on the TPU) — snapshot their host mirror so
                # post-restore retractions still replay the exact value
                value = {
                    tok: {kb: _to_host(v) for kb, v in store.items()}
                    for tok, store in value.items()
                }
            try:
                out[name] = pickle.dumps(value, protocol=pickle.HIGHEST_PROTOCOL)
            except Exception as exc:
                raise UnpicklableStateError(
                    f"{type(self).__name__}.{name} is not picklable ({exc}); "
                    "operator checkpointing is unavailable for this pipeline"
                ) from exc
        return out

    def load_state_dict(self, state: Dict[str, bytes]) -> None:
        import pickle

        for name, blob in state.items():
            self.__dict__[name] = pickle.loads(blob)

    # -- elastic membership handoff (parallel/membership.py) -----------------
    #
    # A membership change re-partitions key-owned state across the new
    # topology. The base protocol covers the common cases generically:
    # stateless evaluators export nothing, and the non-deterministic-apply
    # replay memo (``_udf_memo``: token -> {row-key bytes -> value}) is keyed
    # by row key, so it partitions exactly. Evaluators holding other keyed
    # state implement their own export/import (GroupbyEvaluator, the
    # key-presence family); evaluators whose state is NOT key-partitionable
    # return a reason from ``reshard_check`` and the whole transition is
    # refused loudly before anything mutates.

    #: state-shaped instance attrs that are really graph config, rebuilt
    #: identically from the (sig-checked) graph on every rank
    RESHARD_CONFIG_ATTRS: tuple = ()

    @staticmethod
    def _reshard_empty(value: Any) -> bool:
        if value is None or value is False:
            return True
        if isinstance(value, np.ndarray):
            return value.size == 0
        if isinstance(value, (dict, list, tuple, set, frozenset, str, bytes)):
            return len(value) == 0
        if isinstance(value, (int, float)) and value == 0:
            return True
        return False

    def _reshard_state_attrs(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for name, value in self.__dict__.items():
            if name in self._NON_STATE_ATTRS or name in self.RESHARD_CONFIG_ATTRS:
                continue
            if self._reshard_empty(value):
                continue
            out[name] = value
        return out

    def reshard_check(self) -> "str | None":
        """None when this evaluator's live state can ride the membership
        handoff; else a human-readable refusal reason."""
        extra = [n for n in self._reshard_state_attrs() if n != "_udf_memo"]
        if extra:
            return (
                f"{type(self).__name__} holds state ({', '.join(sorted(extra))}) "
                "this build cannot re-partition by key"
            )
        return None

    def reshard_export(self, owner_of: Any, new_n: int) -> Dict[int, Any]:
        """Partition this evaluator's keyed state by new owner rank. The
        export is COMPLETE (includes the keys this rank keeps): fragments
        double as the new topology's checkpoint, so the importer always
        starts from a fresh instance."""
        reason = self.reshard_check()
        if reason is not None:
            # defense in depth: the preflight vote refuses such graphs before
            # anything mutates — reaching here means the plan and the export
            # disagree, which must fail loudly, never silently drop state
            from pathway_tpu.parallel.membership import MembershipUnsupportedError

            raise MembershipUnsupportedError(reason)
        memo = self.__dict__.get("_udf_memo") or {}
        if not memo:
            return {}
        from pathway_tpu.internals.keys import KEY_DTYPE

        out: Dict[int, Any] = {}
        for tok, store in memo.items():
            for kb, val in store.items():
                keys = np.frombuffer(kb, dtype=KEY_DTYPE)
                dest = int(np.asarray(owner_of(keys))[0])
                out.setdefault(dest, {}).setdefault("_udf_memo", {}).setdefault(
                    tok, {}
                )[kb] = _to_host(val)
        return out

    def reshard_import(self, payload: Any) -> None:
        memo = self.__dict__.setdefault("_udf_memo", {})
        for tok, store in (payload or {}).get("_udf_memo", {}).items():
            memo.setdefault(tok, {}).update(store)

    # -- helpers ------------------------------------------------------------

    def _resolver_for(self, table: Any, delta: Delta) -> Callable[[expr.ColumnReference], np.ndarray]:
        """Resolve column refs against a delta of ``table``; cross-table refs hit state.

        Retraction rows resolve cross-table refs against the *retracted* values: when the
        referenced table replaced a key this commit (a -1/+1 pair on the same key), the
        materialized state already holds the new value, but a retraction must carry what
        was originally emitted (DD value-matched semantics — ``dataflow.rs`` joins match
        on values, not on current state)."""

        def resolver(ref: expr.ColumnReference) -> np.ndarray:
            if ref.table is table:
                if ref.name == "id":
                    return id_pointer_column(delta.keys)
                return delta.columns[ref.name]
            # cross-table reference: same-universe lookup by key in materialized state
            state = self.runner.state_of(ref.table._node)
            if ref.name == "id":
                return id_pointer_column(delta.keys)
            slots = state.lookup(delta.keys)
            hit = slots >= 0
            if hit.all() and len(state):
                out = state.gather(ref.name, slots)  # fancy indexing already copied
            else:
                # a same-universe reference must hit: a miss means the tables' key sets
                # genuinely differ (e.g. select over a reindexed table referencing the
                # pre-reindex table) — poison instead of silently yielding None
                out = np.empty(len(delta), dtype=object)
                out[:] = ERROR
                if hit.any():
                    out[hit] = state.gather(ref.name, slots[hit])
            if np.any(delta.diffs < 0):
                # retraction rows resolve against the *retracted* upstream values when
                # the referenced table replaced the key this commit (see docstring)
                ref_delta = self.runner.current_delta_of(ref.table._node)
                if ref_delta is not None and len(ref_delta):
                    neg = np.nonzero(ref_delta.diffs < 0)[0]
                    ref_col = ref_delta.columns.get(ref.name)
                    if len(neg) and ref_col is not None:
                        from pathway_tpu.engine.index import KeyIndex

                        ret_idx = KeyIndex(len(neg))
                        ret_slots, _ = ret_idx.upsert(ref_delta.keys[neg])
                        slot_values = np.empty(ret_idx.slot_bound(), dtype=ref_col.dtype)
                        slot_values[ret_slots] = ref_col[neg]
                        mine = np.nonzero(delta.diffs < 0)[0]
                        found = ret_idx.lookup(delta.keys[mine])
                        use = found >= 0
                        if use.any():
                            if out.dtype != object and out.dtype != slot_values.dtype:
                                out = out.astype(object)
                            out[mine[use]] = slot_values[found[use]]
            return ee._tidy(out) if out.dtype == object else out

        return resolver

    def _eval_expr(
        self, e: expr.ColumnExpression, delta: Delta, resolver: Callable
    ) -> np.ndarray:
        """Evaluate with non-deterministic-apply replay wired in: retraction rows
        reuse the value computed at insert time (see EvalContext docstring)."""
        return ee.evaluate(
            e,
            len(delta),
            resolver,
            keys=delta.keys,
            diffs=delta.diffs,
            memo=self.__dict__.setdefault("_udf_memo", {}),
            memo_tokens=self._memo_tokens,
        )

    def _eval_exprs(
        self, exprs: Dict[str, expr.ColumnExpression], table: Any, delta: Delta
    ) -> Dict[str, np.ndarray]:
        resolver = self._resolver_for(table, delta)
        return {name: self._eval_expr(e, delta, resolver) for name, e in exprs.items()}


class InputEvaluator(Evaluator):
    """Source node: pulls batches from its DataSource each commit."""

    def process(self, input_deltas: List[Delta]) -> Delta:
        source = self.node.config["source"]
        delta = source.next_batch(self.output_columns)
        if len(delta) == 0:
            return delta
        # a keyed upsert stream (e.g. Debezium CDC) can retract and re-add the same key
        # within one commit; net the multiplicities so state application is order-free
        # (reference UpsertSession semantics, adaptors.rs:67)
        return delta.consolidated()


class RowwiseEvaluator(Evaluator):
    """select/with_columns. Cross-table column references are LIVE dependencies
    (reference: a read of another same-universe table is a dataflow edge — DD
    re-derives downstream rows when the referenced arrangement changes): when a
    referenced table emits a delta this commit, the affected rows of THIS table
    re-evaluate and re-emit even though the primary input saw no delta."""

    # cross-ref node list is derived from the graph config, not run state
    RESHARD_CONFIG_ATTRS = ("_cross_nodes",)

    def __init__(self, node: pg.Node, runner: Any):
        super().__init__(node, runner)
        own = node.inputs[0]
        cross: Dict[int, Any] = {}
        for e in node.config["exprs"].values():
            for ref in e._column_refs:
                if ref.table is not own:
                    cross[ref.table._node.id] = ref.table._node
        self._cross_nodes = list(cross.values())

    def process(self, input_deltas: List[Delta]) -> Delta:
        (delta,) = input_deltas
        table = self.node.inputs[0]
        parts: List[Delta] = []
        if len(delta):
            columns = self._eval_exprs(self.node.config["exprs"], table, delta)
            parts.append(Delta(delta.keys, delta.diffs, columns))
        if self._cross_nodes:
            refreshed = self._cross_refresh(delta)
            if refreshed is not None:
                parts.append(refreshed)
        if not parts:
            return Delta.empty(self.output_columns)
        if len(parts) == 1:
            return parts[0]
        return Delta.concat(parts, self.output_columns)

    def _cross_refresh(self, own_delta: Delta) -> Delta | None:
        """Retract+reinsert rows whose cross-referenced values changed this
        commit (keys from the referenced tables' deltas, restricted to this
        table's universe, minus rows the primary delta already covers)."""
        runner = self.runner
        key_parts = []
        for ref_node in self._cross_nodes:
            d = runner.current_delta_of(ref_node)
            if d is not None and len(d):
                key_parts.append(d.keys)
        if not key_parts:
            return None
        seen: set = set()
        own_keys = set(key_bytes(own_delta.keys)) if len(own_delta) else set()
        kept: List[np.void] = []
        for arr in key_parts:
            for i, kb in enumerate(key_bytes(arr)):
                if kb in seen or kb in own_keys:
                    continue
                seen.add(kb)
                kept.append(arr[i])
        if not kept:
            return None
        keys = np.array(kept, dtype=KEY_DTYPE)
        in_state = runner.state_of(self.node.inputs[0]._node)
        slots = in_state.lookup(keys)
        present = slots >= 0
        if not present.any():
            return None
        keys = keys[present]
        slots = slots[present]
        in_cols = self.node.inputs[0].column_names()
        synth = Delta(
            keys,
            np.ones(len(keys), dtype=np.int64),
            {c: in_state.gather(c, slots) for c in in_cols},
        )
        new_cols = self._eval_exprs(self.node.config["exprs"], self.node.inputs[0], synth)
        out_state = runner.state_of(self.node)
        oslots = out_state.lookup(keys)
        had = oslots >= 0
        # suppress no-op rows: only emit where some output value actually moved
        changed = ~had  # rows never emitted always emit
        if had.any():
            idx = np.nonzero(had)[0]
            neq = np.zeros(len(idx), dtype=bool)
            for name in self.output_columns:
                old = out_state.gather(name, oslots[idx])
                neq |= _col_neq(old, new_cols[name][idx])
            changed[idx] |= neq
        if not changed.any():
            return None
        ch = np.nonzero(changed)[0]
        # batch-gather old values once per column, then assemble rows
        ret_idx = ch[had[ch]]
        old_cols = {
            c: out_state.gather(c, oslots[ret_idx]) for c in self.output_columns
        }
        old_pos = {int(i): p for p, i in enumerate(ret_idx.tolist())}
        out_keys: List[np.void] = []
        out_diffs: List[int] = []
        rows: List[dict] = []
        for i in ch.tolist():
            if had[i]:
                p = old_pos[i]
                rows.append({c: old_cols[c][p] for c in self.output_columns})
                out_keys.append(keys[i])
                out_diffs.append(-1)
            rows.append({c: new_cols[c][i] for c in self.output_columns})
            out_keys.append(keys[i])
            out_diffs.append(1)
        return _delta_from_rows(out_keys, out_diffs, rows, self.output_columns)


class FilterEvaluator(Evaluator):
    def process(self, input_deltas: List[Delta]) -> Delta:
        (delta,) = input_deltas
        if len(delta) == 0:
            return Delta.empty(self.output_columns)
        table = self.node.inputs[0]
        resolver = self._resolver_for(table, delta)
        mask = ee.evaluate(self.node.config["expression"], len(delta), resolver)
        return delta.select(filter_mask_to_bool(mask))


class _DerivedKeyMixin(Evaluator):
    """Provenance machinery for key-DERIVING evaluators (reindex, flatten,
    concat-reindex).

    These nodes change the row key without an exchange, so an output row
    resides wherever its INPUT row lived — the membership planner composes
    their owner function as ``upstream_owner(prov[out_key])``. The provenance
    map is tracked only under a cluster and is monotonic: derivation is
    deterministic, so a retracted-then-re-added row maps identically, and
    keeping retired entries lets late retractions route to the rank that
    still holds the matching downstream state. Growth is bounded by the
    number of DISTINCT derived keys ever produced on this rank.
    """

    def __init__(self, node: pg.Node, runner: Any):
        super().__init__(node, runner)
        self._reshard_prov: Dict[bytes, bytes] = {}

    def _track_prov(self, out_keys: Any, in_keys: Any) -> None:
        if getattr(self.runner, "_cluster", None) is None:
            return
        prov = self._reshard_prov
        for j in range(len(out_keys)):
            kb = out_keys[j].tobytes()
            if kb not in prov:
                prov[kb] = in_keys[j].tobytes()

    # -- elastic membership handoff: the provenance map itself partitions by
    # the DERIVED key's (composed) owner so the new topology can re-plan later

    def reshard_check(self) -> "str | None":
        return None

    def reshard_export(self, owner_of: Any, new_n: int) -> Dict[int, Any]:
        out: Dict[int, Any] = {}
        for kb, src in self._reshard_prov.items():
            keys = np.frombuffer(kb, dtype=KEY_DTYPE)
            dest = int(np.asarray(owner_of(keys))[0])
            out.setdefault(dest, {"prov": {}})["prov"][kb] = src
        memo = Evaluator.reshard_export(self, owner_of, new_n)
        for dest, payload in memo.items():
            out.setdefault(dest, {"prov": {}})["_udf_memo"] = payload["_udf_memo"]
        return out

    def reshard_export_parts(
        self, owner_of: Any, new_n: int, budget_rows: int
    ) -> "Iterable[tuple]":
        # streamed: never materialize the full per-dest export — buffer at
        # most ``budget_rows`` provenance entries per open destination, so the
        # donor's peak is O(budget x dests), not O(prov map)
        step = max(1, int(budget_rows))
        memo = Evaluator.reshard_export(self, owner_of, new_n)
        extras: Dict[int, dict] = {
            dest: {"_udf_memo": payload["_udf_memo"]}
            for dest, payload in memo.items()
        }
        open_parts: Dict[int, dict] = {}
        for kb, src in self._reshard_prov.items():
            keys = np.frombuffer(kb, dtype=KEY_DTYPE)
            dest = int(np.asarray(owner_of(keys))[0])
            part = open_parts.get(dest)
            if part is None:
                part = open_parts[dest] = {"prov": {}}
                part.update(extras.pop(dest, {}))
            part["prov"][kb] = src
            if len(part["prov"]) >= step:
                yield dest, open_parts.pop(dest)
        for dest in sorted(open_parts):
            yield dest, open_parts[dest]
        for dest in sorted(extras):
            # a dest owed memo state but no provenance rows
            yield dest, {"prov": {}, **extras[dest]}

    def reshard_import(self, payload: Any) -> None:
        self._reshard_prov.update((payload or {}).get("prov", {}))
        Evaluator.reshard_import(self, payload)


class ReindexEvaluator(_DerivedKeyMixin):
    def process(self, input_deltas: List[Delta]) -> Delta:
        (delta,) = input_deltas
        if len(delta) == 0:
            return Delta.empty(self.output_columns)
        table = self.node.inputs[0]
        resolver = self._resolver_for(table, delta)
        new_ids = ee.evaluate(self.node.config["expression"], len(delta), resolver)
        keys = pointers_to_keys(
            [p if isinstance(p, Pointer) else pointer_from(p) for p in new_ids]
        )
        self._track_prov(keys, delta.keys)
        return Delta(keys, delta.diffs, dict(delta.columns))


class ConcatEvaluator(_DerivedKeyMixin):
    def __init__(self, node: pg.Node, runner: Any):
        super().__init__(node, runner)
        # net live multiplicity per key: concat is a DISJOINT union, so a key
        # reaching multiplicity 2 is a collision and fails the run (reference
        # raises on duplicate keys; reindex mode cannot collide)
        self.live: Dict[bytes, int] = {}

    def process(self, input_deltas: List[Delta]) -> Delta:
        reindex = self.node.config.get("reindex", False)
        parts = []
        net: Dict[bytes, tuple] = {}  # kb -> (net diff this commit, sample key)
        for i, delta in enumerate(input_deltas):
            if len(delta) == 0:
                continue
            if reindex:
                new_keys = np.empty(len(delta), dtype=KEY_DTYPE)
                for j in range(len(delta)):
                    p = pointer_from(Pointer(int(delta.keys[j]["hi"]), int(delta.keys[j]["lo"])), i)
                    new_keys[j]["hi"], new_keys[j]["lo"] = p.hi, p.lo
                self._track_prov(new_keys, delta.keys)
                delta = Delta(new_keys, delta.diffs, delta.columns)
            else:
                for j in range(len(delta)):
                    kb = delta.keys[j].tobytes()
                    prev = net.get(kb)
                    net[kb] = (
                        (prev[0] if prev else 0) + int(delta.diffs[j]),
                        delta.keys[j],
                    )
            parts.append(delta)
        # collision check on the NET per-commit count: a same-commit key handoff
        # between inputs (one retracts, another inserts, any row order) is legal
        for kb, (d, key) in net.items():
            cnt = self.live.get(kb, 0) + d
            if cnt > 1:
                from pathway_tpu.internals.keys import keys_to_pointers

                raise ValueError(
                    "concat: duplicate key "
                    f"{keys_to_pointers(np.array([key], dtype=KEY_DTYPE))[0]!r} — "
                    "input universes must be disjoint (use concat_reindex for "
                    "overlapping tables)"
                )
            if cnt:
                self.live[kb] = cnt
            else:
                self.live.pop(kb, None)
        return Delta.concat(parts, self.output_columns)

    # -- elastic membership handoff: the collision tracker is keyed by the
    # OUTPUT key in both modes (pass-through or derived), so it partitions
    # under the same (possibly composed) owner function as the provenance map

    def reshard_export(self, owner_of: Any, new_n: int) -> Dict[int, Any]:
        out = _DerivedKeyMixin.reshard_export(self, owner_of, new_n)
        for kb, cnt in self.live.items():
            keys = np.frombuffer(kb, dtype=KEY_DTYPE)
            dest = int(np.asarray(owner_of(keys))[0])
            out.setdefault(dest, {"prov": {}}).setdefault("live", {})[kb] = cnt
        return out

    def reshard_import(self, payload: Any) -> None:
        for kb, cnt in (payload or {}).get("live", {}).items():
            self.live[kb] = self.live.get(kb, 0) + cnt
        _DerivedKeyMixin.reshard_import(self, payload)


def _col_neq(old: np.ndarray, new: np.ndarray) -> np.ndarray:
    """Elementwise inequality tolerant of object cells (ndarray values, exceptions).

    NaN compares unequal to itself, matching the previous per-row tuple compare —
    a group whose aggregate stays NaN re-emits, which is harmless."""
    try:
        res = np.asarray(old != new)
        if res.dtype == np.bool_ and res.shape == old.shape:
            return res
        # object != produced non-scalar cells (ndarray values): per-cell fallback
    except (TypeError, ValueError):
        pass

    def cell_neq(a: Any, b: Any) -> bool:
        if a is b:
            return False
        if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
            return not (
                isinstance(a, np.ndarray)
                and isinstance(b, np.ndarray)
                and np.array_equal(a, b)
            )
        try:
            return not (a == b)
        except Exception:
            return True

    return np.frompyfunc(cell_neq, 2, 1)(old, new).astype(bool)


def _group_stable(e: expr.ColumnExpression) -> bool:
    """True when the expression is a deterministic function of grouping values
    only — no reducer leaves, no non-deterministic applies anywhere in the tree."""
    if isinstance(e, expr.ReducerExpression):
        return False
    if isinstance(e, expr.ApplyExpression) and not e._deterministic:
        return False
    return all(_group_stable(d) for d in e._deps())


class GroupbyEvaluator(Evaluator):
    """Incremental groupby-reduce (reference ``reduce.rs`` + DD reduce), fully columnar.

    Group state is struct-of-arrays indexed by dense slots from the native ``KeyIndex``
    (group key -> slot): signed row counts, grouping values, one ``ColumnarState`` per
    reducer leaf (``internals/reducers.py``), and the last-emitted output row per group
    for change detection. A commit is a handful of vectorized passes — hash, upsert,
    segment-reduce, gather — with per-group Python only inside non-semigroup reducer
    fallbacks (the reference's recompute-style reducers)."""

    # reducer_leaves is graph config: checkpoints must not replace it — identity (id())
    # keys the leaf-value mapping
    _NON_STATE_ATTRS = Evaluator._NON_STATE_ATTRS + ("reducer_leaves",)

    def __init__(self, node: pg.Node, runner: Any):
        super().__init__(node, runner)
        from pathway_tpu.engine.index import KeyIndex

        self.gindex = KeyIndex()
        self._capacity = 0
        self.gkeys = np.zeros(0, dtype=KEY_DTYPE)
        self.counts = np.zeros(0, dtype=np.int64)
        self.last_valid = np.zeros(0, dtype=bool)
        self.gvals: Dict[str, np.ndarray] = {
            name: np.empty(0, dtype=object) for name in node.config["grouping_names"]
        }
        self.last_cols: Dict[str, np.ndarray] = {
            name: np.empty(0, dtype=object) for name in self.output_columns
        }
        self.reducer_leaves: List[expr.ReducerExpression] = []
        self._collect_reducers(node.config["out_exprs"])
        self.leaf_states = [leaf._reducer.make_state() for leaf in self.reducer_leaves]
        self.seq = 0
        # output columns that are pure functions of the grouping values (no
        # reducer, no non-deterministic apply) CANNOT change while a group is
        # alive — change detection skips comparing them (group keys fingerprint
        # the grouping values, so equal key implies equal value)
        self._stable_cols = {
            name
            for name, e in node.config["out_exprs"].items()
            if _group_stable(e)
        }

    def load_state_dict(self, state: Dict[str, bytes]) -> None:
        super().load_state_dict(state)
        if "groups" in self.__dict__:
            # dict-of-groups checkpoints predate the columnar state; restoring them
            # silently-empty would corrupt aggregates — fail loudly instead
            raise RuntimeError(
                "checkpoint was written by an incompatible (pre-columnar) build; "
                "clear the persistence directory and re-run"
            )

    # -- elastic membership handoff ------------------------------------------
    #
    # Group state is columnar keyed by the group key (= output row key), so
    # the reshard is exactly an array redistribution: gather the moved
    # groups' slots (counts, grouping values, last-emitted rows, every
    # reducer leaf's accumulator columns), ship per new owner, scatter into
    # freshly upserted slots on the importer.

    def reshard_check(self) -> "str | None":
        # columnar group state partitions exactly by group key — but the
        # non-deterministic-UDF replay memo is keyed by INPUT row key while
        # future retractions route by GROUP key, so a populated memo cannot
        # be re-partitioned (the row->group mapping is not recoverable from
        # the memo): refuse loudly rather than silently drop replay values
        if self.__dict__.get("_udf_memo"):
            return (
                "GroupbyEvaluator holds non-deterministic-UDF replay state "
                "(_udf_memo) that cannot be re-partitioned by group key"
            )
        return None

    def reshard_export(self, owner_of: Any, new_n: int) -> Dict[int, Any]:
        reason = self.reshard_check()
        if reason is not None:
            from pathway_tpu.parallel.membership import MembershipUnsupportedError

            raise MembershipUnsupportedError(reason)
        gkeys, slots = self.gindex.items()
        if len(gkeys) == 0:
            return {}
        owners = np.asarray(owner_of(gkeys))
        out: Dict[int, Any] = {}
        for dest in np.unique(owners):
            sel = owners == dest
            dslots = slots[sel]
            out[int(dest)] = {
                "gkeys": gkeys[sel].copy(),
                "counts": self.counts[dslots].copy(),
                "last_valid": self.last_valid[dslots].copy(),
                "gvals": {n: a[dslots].copy() for n, a in self.gvals.items()},
                "last_cols": {
                    n: a[dslots].copy() for n, a in self.last_cols.items()
                },
                "leaves": [st.reshard_take(dslots) for st in self.leaf_states],
                "seq": int(self.seq),
            }
        return out

    def reshard_import(self, payload: Any) -> None:
        from pathway_tpu.engine.columnar import set_cells

        gkeys = payload["gkeys"]
        if len(gkeys) == 0:
            return
        slots, is_new = self.gindex.upsert(gkeys)
        if not is_new.all():
            raise RuntimeError(
                "membership handoff fragment re-imported a group key that is "
                "already present — fragments must be disjoint; the store is "
                "inconsistent"
            )
        self._ensure_capacity()
        self.gkeys[slots] = gkeys
        self.counts[slots] = payload["counts"]
        self.last_valid[slots] = payload["last_valid"]
        for name in self.gvals:
            self.gvals[name] = set_cells(
                self.gvals[name], slots, payload["gvals"][name]
            )
        for name in self.last_cols:
            self.last_cols[name] = set_cells(
                self.last_cols[name], slots, payload["last_cols"][name]
            )
        for st, blob in zip(self.leaf_states, payload["leaves"]):
            st.reshard_put(slots, blob)
        # seq continues past every donor's counter: the sequence reducer's
        # per-rank monotonicity survives the move
        self.seq = max(self.seq, int(payload.get("seq", 0)))

    def _collect_reducers(self, out_exprs: Dict[str, expr.ColumnExpression]) -> None:
        seen: set[int] = set()

        def walk(e: expr.ColumnExpression) -> None:
            if isinstance(e, expr.ReducerExpression):
                if id(e) not in seen:
                    seen.add(id(e))
                    self.reducer_leaves.append(e)
                return
            for d in e._deps():
                walk(d)

        for e in out_exprs.values():
            walk(e)

    def _ensure_capacity(self) -> None:
        bound = self.gindex.slot_bound()
        if bound <= self._capacity:
            return
        cap = max(16, 2 * self._capacity, bound)
        gkeys = np.zeros(cap, dtype=KEY_DTYPE)
        gkeys[: self._capacity] = self.gkeys
        self.gkeys = gkeys
        self.counts = np.concatenate(
            [self.counts, np.zeros(cap - len(self.counts), dtype=np.int64)]
        )
        valid = np.zeros(cap, dtype=bool)
        valid[: self._capacity] = self.last_valid
        self.last_valid = valid
        from pathway_tpu.engine.columnar import grow_column

        for name in self.gvals:
            self.gvals[name] = grow_column(self.gvals[name], cap)
        for name in self.last_cols:
            self.last_cols[name] = grow_column(self.last_cols[name], cap)
        for st in self.leaf_states:
            st.ensure(cap)
        self._capacity = cap

    def _eval_out(self, slots: np.ndarray) -> Dict[str, np.ndarray]:
        """Output expressions over the given group slots, vectorized, with reducer
        leaves bound to their columnar aggregates."""
        leaf_value_arrays = {
            id(leaf): st.values(slots)
            for leaf, st in zip(self.reducer_leaves, self.leaf_states)
        }
        gval_arrays = {name: self.gvals[name][slots] for name in self.gvals}

        class _GroupEval(ee.ExpressionEvaluator):
            def _eval_ReducerExpression(self, re: expr.ReducerExpression) -> np.ndarray:
                return leaf_value_arrays[id(re)]

            def _eval_ColumnReference(self, ref: expr.ColumnReference) -> np.ndarray:
                return gval_arrays[ref.name]

        evaluator = _GroupEval(ee.EvalContext(len(slots), lambda ref: None))
        out_exprs = self.node.config["out_exprs"]
        return {name: evaluator.eval(out_exprs[name]) for name in self.output_columns}

    def _group_keys(self, grouping_vals: List[np.ndarray], n: int, set_id: bool) -> np.ndarray:
        if not grouping_vals:
            # global reduce: every row lands in the single salt-only group
            return broadcast_key(pointer_from(), n)
        if not set_id:
            return keys_from_values(grouping_vals)
        col = grouping_vals[0]
        out = np.empty(n, dtype=KEY_DTYPE)
        for i in range(n):
            p = col[i]
            if not isinstance(p, Pointer):
                p = pointer_from(*(g[i] for g in grouping_vals))
            out[i]["hi"], out[i]["lo"] = p.hi, p.lo
        return out

    def process(self, input_deltas: List[Delta]) -> Delta:
        (delta,) = input_deltas
        set_id = self.node.config.get("set_id", False)
        cluster = getattr(self.runner, "_cluster", None)
        if cluster is not None:
            # hash-route rows to their group key's owner process (all-to-all
            # barrier; participates even with no local rows — peers block on our
            # partitions). Reference: DD reduce's exchange over the Cluster
            # allocator, shard.rs routing.
            n0 = len(delta)
            resolver0 = self._resolver_for(self.node.inputs[0], delta)
            gvals0 = [ee.evaluate(g, n0, resolver0) for g in self.node.config["grouping"]]
            gkeys0 = self._group_keys(gvals0, n0, set_id)
            tag = f"{self.runner.current_time}:{self.node.id}:g".encode()
            delta = cluster.exchange_delta(tag, delta, gkeys0)
        if len(delta) == 0:
            return Delta.empty(self.output_columns)
        table = self.node.inputs[0]
        resolver = self._resolver_for(table, delta)
        n = len(delta)
        diffs = delta.diffs

        grouping_vals = [
            ee.evaluate(g, n, resolver) for g in self.node.config["grouping"]
        ]

        # reducer argument values per leaf (vectorized)
        leaf_args: List[List[np.ndarray]] = []
        for leaf in self.reducer_leaves:
            arrays = []
            for a in leaf._args:
                if isinstance(a, _IdMarker):
                    ids = np.empty(n, dtype=object)
                    ids[:] = keys_to_pointers(delta.keys)
                    arrays.append(ids)
                elif isinstance(a, _SeqMarker):
                    seqs = np.arange(self.seq, self.seq + n, dtype=np.int64)
                    arrays.append(seqs.astype(object))
                else:
                    arrays.append(self._eval_expr(a, delta, resolver))
            leaf_args.append(arrays)
        self.seq += n

        if grouping_vals and not set_id:
            # fused native fingerprint + upsert: one crossing for the hot pair
            gkeys, slots, is_new = hash_upsert(self.gindex, grouping_vals)
        else:
            gkeys = self._group_keys(grouping_vals, n, set_id)
            slots, is_new = self.gindex.upsert(gkeys)
        self._ensure_capacity()
        new_slots = slots[is_new]
        if len(new_slots):
            # recycled slots start pristine
            self.counts[new_slots] = 0
            self.last_valid[new_slots] = False
            self.gkeys[new_slots] = gkeys[is_new]
            for st in self.leaf_states:
                st.reset(new_slots)
            from pathway_tpu.engine.columnar import set_cells

            for gi, name in enumerate(self.gvals):
                self.gvals[name] = set_cells(
                    self.gvals[name], new_slots, np.asarray(grouping_vals[gi])[is_new]
                )

        from pathway_tpu.ops.segment import segment_count

        # dense batch segmentation: an O(n + slot_bound) bitmap pass when the batch
        # is comparable to the live slot space; an O(n log n) sort when a small
        # commit touches a huge accumulated group space (bitmap would scan it all)
        bound = self.gindex.slot_bound()
        if bound <= 4 * n + 1024:
            seen = np.zeros(bound, dtype=bool)
            seen[slots] = True
            uniq_slots = np.nonzero(seen)[0]
            pos_of_slot = np.empty(bound, dtype=np.int64)
            pos_of_slot[uniq_slots] = np.arange(len(uniq_slots), dtype=np.int64)
            inverse = pos_of_slot[slots]
        else:
            uniq_slots, inverse = np.unique(slots, return_inverse=True)
        m = len(uniq_slots)
        cnt_delta = segment_count(inverse, m, weights=diffs)
        counts_after = self.counts[uniq_slots] + cnt_delta

        for st, arrays in zip(self.leaf_states, leaf_args):
            st.update(
                slots, uniq_slots, inverse, arrays, diffs, cnt_delta, counts_after,
                key_lo=gkeys["lo"],
            )
        self.counts[uniq_slots] = counts_after

        # -- emission: retract old rows, insert new rows, per changed group ----
        alive_mask = counts_after > 0
        alive_slots = uniq_slots[alive_mask]
        dead_slots = uniq_slots[~alive_mask]

        new_cols = self._eval_out(alive_slots) if len(alive_slots) else {}
        had_row_alive = self.last_valid[alive_slots]
        changed = ~had_row_alive  # groups without a cached row always emit
        if had_row_alive.any():
            idx = np.nonzero(had_row_alive)[0]
            neq = np.zeros(len(idx), dtype=bool)
            for name in self.output_columns:
                if name in self._stable_cols:
                    continue  # pure grouping function: equal by construction
                old = self.last_cols[name][alive_slots[idx]]
                neq |= _col_neq(old, new_cols[name][idx])
            changed[idx] |= neq

        # retracts: dead groups with a cached row + changed alive groups with one
        r_uniq = np.zeros(m, dtype=bool)
        r_uniq[~alive_mask] = self.last_valid[dead_slots]
        alive_pos = np.nonzero(alive_mask)[0]
        r_uniq[alive_pos] = had_row_alive & changed
        i_uniq = np.zeros(m, dtype=bool)
        i_uniq[alive_pos] = changed

        if not r_uniq.any() and not i_uniq.any():
            if len(dead_slots):
                self._bury(dead_slots)
            return Delta.empty(self.output_columns)

        # interleave so each group's retract immediately precedes its insert
        r_idx = np.nonzero(r_uniq)[0]
        i_idx = np.nonzero(i_uniq)[0]
        seqd = np.sort(np.concatenate([r_idx * 2, i_idx * 2 + 1]))
        is_ins = (seqd % 2) == 1
        group_pos = seqd // 2
        ev_slots = uniq_slots[group_pos]
        out_keys = self.gkeys[ev_slots]
        out_diffs = np.where(is_ins, 1, -1).astype(np.int64)

        # map uniq position -> position in alive_slots (for gathering new values)
        alive_rel = np.full(m, -1, dtype=np.int64)
        alive_rel[alive_pos] = np.arange(len(alive_slots))
        ins_rel = alive_rel[group_pos[is_ins]]

        from pathway_tpu.engine.columnar import set_cells

        columns: Dict[str, np.ndarray] = {}
        for name in self.output_columns:
            old_part = self.last_cols[name][ev_slots[~is_ins]]
            new_part = new_cols[name][ins_rel] if len(ins_rel) else np.empty(0, dtype=object)
            if not is_ins.any():
                columns[name] = old_part
            elif not (~is_ins).any():
                columns[name] = new_part
            else:
                out = None
                if old_part.dtype == new_part.dtype and old_part.dtype != object:
                    out = np.empty(len(is_ins), dtype=old_part.dtype)
                else:
                    out = np.empty(len(is_ins), dtype=object)
                try:
                    out[~is_ins] = old_part
                    out[is_ins] = new_part
                except (TypeError, ValueError):
                    out = np.empty(len(is_ins), dtype=object)
                    out[~is_ins] = old_part
                    out[is_ins] = new_part
                columns[name] = out

        # update the last-emitted cache
        changed_slots = alive_slots[changed]
        if len(changed_slots):
            for name in self.output_columns:
                self.last_cols[name] = set_cells(
                    self.last_cols[name], changed_slots, new_cols[name][changed]
                )
            self.last_valid[changed_slots] = True
        if len(dead_slots):
            self._bury(dead_slots)

        return Delta(out_keys, out_diffs, columns)

    def _bury(self, dead_slots: np.ndarray) -> None:
        """A group's multiset emptied: drop it from the index (slot recycles) and
        release cached object references."""
        self.last_valid[dead_slots] = False
        self.gindex.remove(self.gkeys[dead_slots])
        for name in self.last_cols:
            col = self.last_cols[name]
            if col.dtype == object:
                col[dead_slots] = None
        for name in self.gvals:
            col = self.gvals[name]
            if col.dtype == object:
                col[dead_slots] = None


class DeduplicateEvaluator(Evaluator):
    # state is per INSTANCE: route rows to their instance's owner process
    # (within-commit arrival order across processes is rank-merged, the same
    # nondeterminism timely's exchange has). The route key IS the instance's
    # OUTPUT row key (``pointer_from(inst, "dedup")``), so the rank owning an
    # instance's state also owns its emitted rows — the reshard planner
    # treats this "custom" exchange as plain ``bykey`` (RESHARD_ROUTE_BYKEY).
    CLUSTER_POLICIES = {0: "custom"}
    RESHARD_ROUTE_BYKEY = True

    @staticmethod
    def _instance_out_key(inst: Any) -> Pointer:
        return pointer_from(
            inst if not isinstance(inst, np.void) else int(inst["lo"]), "dedup"
        )

    def cluster_route_keys(self, idx: int, delta: Delta) -> np.ndarray:
        instance_e = self.node.config.get("instance")
        if instance_e is None:
            # global dedup: a single logical instance — one owner (the
            # process owning the output key of instance 0)
            return broadcast_key(self._instance_out_key(0), len(delta))
        resolver = self._resolver_for(self.node.inputs[0], delta)
        instances = ee.evaluate(instance_e, len(delta), resolver)
        return pointers_to_keys(
            [self._instance_out_key(inst) for inst in instances]
        )

    def __init__(self, node: pg.Node, runner: Any):
        super().__init__(node, runner)
        self.current: Dict[bytes, Tuple[np.void, dict, Any]] = {}  # instance -> (key,row,value)
        # instance -> output row-key bytes: the reshard partition key for
        # ``current`` (an instance repr is not invertible)
        self._okeys: Dict[bytes, bytes] = {}

    def process(self, input_deltas: List[Delta]) -> Delta:
        (delta,) = input_deltas
        if len(delta) == 0:
            return Delta.empty(self.output_columns)
        table = self.node.inputs[0]
        resolver = self._resolver_for(table, delta)
        n = len(delta)
        value_e = self.node.config.get("value")
        instance_e = self.node.config.get("instance")
        acceptor = self.node.config.get("acceptor")
        values = ee.evaluate(value_e, n, resolver) if value_e is not None else delta.keys
        instances = (
            ee.evaluate(instance_e, n, resolver)
            if instance_e is not None
            else np.zeros(n, dtype=object)
        )
        # emission is consolidated PER INSTANCE per call: several accepted rows
        # for one instance in a single delta must not emit chained retract/add
        # pairs for the same output key — StateTable.apply replays retractions
        # before insertions, so an intra-delta chain would retract a row whose
        # add rides the very same delta. One retraction of the pre-call row
        # (if any) plus one add of the final winner keeps every retraction
        # pointing at an already-applied row.
        pre: Dict[bytes, Any] = {}  # ib -> (ikey, pre-call entry | None)
        for i in range(n):
            if delta.diffs[i] < 0:
                continue  # append-only semantics (reference deduplicate is streaming-only)
            inst = instances[i]
            ib = repr(inst).encode()
            row = {c: delta.columns[c][i] for c in delta.column_names}
            val = values[i]
            cur = self.current.get(ib)
            if cur is None:
                accept = True
            else:
                accept = bool(acceptor(val, cur[2])) if acceptor is not None else True
            if not accept:
                continue
            ikey = self._instance_out_key(inst)
            if ib not in pre:
                pre[ib] = (ikey, cur)
            self.current[ib] = (delta.keys[i], row, val)
            if ib not in self._okeys:
                self._okeys[ib] = pointers_to_keys([ikey])[0].tobytes()
        if not pre:
            return Delta.empty(self.output_columns)
        out_keys, out_diffs, out_rows = [], [], []
        for ib, (ikey, cur) in pre.items():
            if cur is not None:
                out_keys.append(ikey)
                out_diffs.append(-1)
                out_rows.append(cur[1])
            out_keys.append(ikey)
            out_diffs.append(1)
            out_rows.append(self.current[ib][1])
        columns = {
            name: ee._tidy(objarray([r[name] for r in out_rows]))
            for name in self.output_columns
        }
        return Delta(pointers_to_keys(out_keys), np.array(out_diffs, dtype=np.int64), columns)

    # -- elastic membership handoff: instances partition by OUTPUT key -------

    def reshard_check(self) -> "str | None":
        if self.__dict__.get("_udf_memo"):
            return (
                "DeduplicateEvaluator holds a non-deterministic replay memo "
                "keyed by pre-exchange row keys — re-partitioning by instance "
                "output key cannot place it"
            )
        if len(self._okeys) < len(self.current):
            # a pre-upgrade checkpoint restored `current` without the output
            # key sidecar: those instances cannot be placed — refuse loudly
            return (
                "DeduplicateEvaluator state predates output-key tracking "
                f"({len(self.current) - len(self._okeys)} instance(s) without "
                "a recorded output key) — cannot re-partition this checkpoint"
            )
        return None

    def reshard_export(self, owner_of: Any, new_n: int) -> Dict[int, Any]:
        reason = self.reshard_check()
        if reason is not None:
            from pathway_tpu.parallel.membership import MembershipUnsupportedError

            raise MembershipUnsupportedError(reason)
        if not self.current:
            return {}
        from pathway_tpu.internals.keys import KEY_DTYPE

        out: Dict[int, Any] = {}
        for ib, entry in self.current.items():
            kb = self._okeys[ib]
            dest = int(np.asarray(owner_of(np.frombuffer(kb, dtype=KEY_DTYPE)))[0])
            bucket = out.setdefault(dest, {"current": {}, "okeys": {}})
            bucket["current"][ib] = entry
            bucket["okeys"][ib] = kb
        return out

    def reshard_export_parts(
        self, owner_of: Any, new_n: int, budget_rows: int
    ) -> "Iterable[tuple]":
        # streamed: never materialize the full per-dest export — buffer at
        # most ``budget_rows`` instances per open destination, so the donor's
        # peak is O(budget x dests), not O(instances)
        reason = self.reshard_check()
        if reason is not None:
            from pathway_tpu.parallel.membership import MembershipUnsupportedError

            raise MembershipUnsupportedError(reason)
        from pathway_tpu.internals.keys import KEY_DTYPE

        step = max(1, int(budget_rows))
        open_parts: Dict[int, dict] = {}
        for ib, entry in self.current.items():
            kb = self._okeys[ib]
            dest = int(np.asarray(owner_of(np.frombuffer(kb, dtype=KEY_DTYPE)))[0])
            part = open_parts.setdefault(dest, {"current": {}, "okeys": {}})
            part["current"][ib] = entry
            part["okeys"][ib] = kb
            if len(part["current"]) >= step:
                yield dest, open_parts.pop(dest)
        for dest in sorted(open_parts):
            yield dest, open_parts[dest]

    def reshard_import(self, payload: Any) -> None:
        cur = (payload or {}).get("current", {})
        overlap = self.current.keys() & cur.keys()
        if overlap:
            raise RuntimeError(
                "dedup reshard import found an instance already present — "
                "handoff fragments overlap"
            )
        self.current.update(cur)
        self._okeys.update((payload or {}).get("okeys", {}))


class _JoinSide:
    """Columnar arrangement for one join side on native structures: a ``KeyIndex``
    (row key -> slot), a ``MultiMap`` (join key -> row slots), and slot-indexed value
    arrays. The DD-arrangement stand-in for the join's build state (reference
    ``dataflow.rs`` join over arranged collections) — inserts, removals, and probes
    are O(batch) native calls."""

    def __init__(self, names: Iterable[str]):
        from pathway_tpu.engine.index import KeyIndex, MultiMap

        self.names = list(names)
        self.row_index = KeyIndex()
        self.jkmap = MultiMap()
        self._capacity = 0
        self.keys = np.zeros(0, dtype=KEY_DTYPE)
        self.jk = np.zeros(0, dtype=KEY_DTYPE)
        self.cols: Dict[str, np.ndarray] = {c: np.empty(0, dtype=object) for c in self.names}

    def _ensure_capacity(self, bound: int | None = None) -> None:
        if bound is None:
            bound = self.row_index.slot_bound()
        if bound <= self._capacity:
            return
        from pathway_tpu.engine.columnar import grow_column

        cap = max(16, 2 * self._capacity, bound)
        keys = np.empty(cap, dtype=KEY_DTYPE)
        keys[: self._capacity] = self.keys
        self.keys = keys
        jk = np.empty(cap, dtype=KEY_DTYPE)
        jk[: self._capacity] = self.jk
        self.jk = jk
        for c in self.names:
            self.cols[c] = grow_column(self.cols[c], cap)
        self._capacity = cap

    def insert_batch(
        self, row_keys: np.ndarray, jkeys: np.ndarray, values: Dict[str, np.ndarray]
    ) -> np.ndarray:
        from pathway_tpu.engine.columnar import set_cells
        from pathway_tpu.engine.index import _NativeKeyIndex, _NativeMultiMap

        n = len(row_keys)
        if self._capacity == 0:
            # first allocation: value-column dtypes come from the first batch
            # through (StateTable does the same) — downstream gathers then stay
            # typed int64/float64 instead of object, which keeps the groupby
            # reducers fed by this join on their vectorized segment kernels
            # (an object `net` column was a per-row Python sum, ~40x slower);
            # set_cells/adopt_dtype still demote to object on any conflict
            for c in self.names:
                self.cols[c] = np.empty(0, dtype=np.asarray(values[c]).dtype)
        if isinstance(self.row_index, _NativeKeyIndex) and isinstance(
            self.jkmap, _NativeMultiMap
        ):
            # fused native pass: upsert + duplicate-replace + slot writes + jk-map
            import ctypes

            u64p = ctypes.POINTER(ctypes.c_uint64)
            i64p = ctypes.POINTER(ctypes.c_int64)
            self._ensure_capacity(self.row_index.slot_bound() + n)
            rk = np.ascontiguousarray(row_keys)
            jkc = np.ascontiguousarray(jkeys)
            slots = np.empty(n, dtype=np.int64)
            self.row_index._lib.pwtpu_side_insert(
                self.row_index._h, self.jkmap._h,
                rk.ctypes.data_as(u64p), jkc.ctypes.data_as(u64p), n,
                self.keys.ctypes.data_as(u64p), self.jk.ctypes.data_as(u64p),
                slots.ctypes.data_as(i64p),
            )
        else:
            # pure-Python fallback: sequential, mirroring the fused native pass
            # exactly (within-batch duplicate row keys replace the earlier row,
            # including its join-key bucket entry)
            self._ensure_capacity(self.row_index.slot_bound() + n)
            slots = np.empty(n, dtype=np.int64)
            one = np.empty(1, dtype=np.int64)
            for i in range(n):
                s_arr, new_arr = self.row_index.upsert(row_keys[i : i + 1])
                s = int(s_arr[0])
                if not new_arr[0]:
                    one[0] = s
                    self.jkmap.remove(self.jk[s : s + 1], one)
                self.keys[s] = row_keys[i]
                self.jk[s] = jkeys[i]
                one[0] = s
                self.jkmap.insert(jkeys[i : i + 1], one)
                slots[i] = s
        for c in self.names:
            self.cols[c] = set_cells(self.cols[c], slots, values[c])
        return slots

    def reshard_export(self, owner_of: Any) -> Dict[int, dict]:
        """Partition the live arrangement by the JOIN key's new owner:
        per-dest parallel arrays (row keys, join keys, value columns) a fresh
        side rebuilds from via :meth:`reshard_import`. Complete — includes
        the rows this rank keeps."""
        keys, slots = self.row_index.items()
        if not len(keys):
            return {}
        jk = self.jk[slots]
        owners = np.asarray(owner_of(jk))
        out: Dict[int, dict] = {}
        for dest in np.unique(owners):
            sel = slots[owners == dest]
            out[int(dest)] = {
                "keys": self.keys[sel].copy(),
                "jk": self.jk[sel].copy(),
                "cols": {c: self.cols[c][sel].copy() for c in self.names},
            }
        return out

    def reshard_export_chunks(
        self, owner_of: Any, budget_rows: int
    ) -> "Iterable[tuple]":
        """Bounded variant of :meth:`reshard_export`: yields ``(dest, piece)``
        with ≤``budget_rows`` rows per piece, copying only one piece at a
        time (the O(rows) owner metadata is ints, never row payload)."""
        keys, slots = self.row_index.items()
        if not len(keys):
            return
        owners = np.asarray(owner_of(self.jk[slots]))
        step = max(1, int(budget_rows))
        for dest in np.unique(owners):
            sel = slots[owners == dest]
            for s in range(0, len(sel), step):
                sl = sel[s : s + step]
                yield int(dest), {
                    "keys": self.keys[sl].copy(),
                    "jk": self.jk[sl].copy(),
                    "cols": {c: self.cols[c][sl].copy() for c in self.names},
                }

    def reshard_import(self, payload: dict) -> None:
        keys = payload.get("keys")
        if keys is None or not len(keys):
            return
        present = self.row_index.lookup(keys)
        if (present >= 0).any():
            # two old ranks both claimed a row key: the partitions were not
            # disjoint — corrupt handoff, never merge silently
            raise RuntimeError(
                "join-side reshard import found a row key already present — "
                "handoff fragments overlap"
            )
        self.insert_batch(keys, payload["jk"], payload["cols"])

    def remove_batch(self, row_keys: np.ndarray) -> np.ndarray:
        """Slots removed per key (-1 when the key was absent)."""
        from pathway_tpu.engine.index import _NativeKeyIndex, _NativeMultiMap

        n = len(row_keys)
        if isinstance(self.row_index, _NativeKeyIndex) and isinstance(
            self.jkmap, _NativeMultiMap
        ):
            import ctypes

            u64p = ctypes.POINTER(ctypes.c_uint64)
            i64p = ctypes.POINTER(ctypes.c_int64)
            rk = np.ascontiguousarray(row_keys)
            slots = np.empty(n, dtype=np.int64)
            self.row_index._lib.pwtpu_side_remove(
                self.row_index._h, self.jkmap._h,
                rk.ctypes.data_as(u64p), n,
                self.jk.ctypes.data_as(u64p), slots.ctypes.data_as(i64p),
            )
        else:
            slots = self.row_index.remove(row_keys)
            present = np.nonzero(slots >= 0)[0]
            if len(present):
                self.jkmap.remove(self.jk[slots[present]], slots[present])
        present = np.nonzero(slots >= 0)[0]
        if len(present):
            live = slots[present]
            for c in self.names:
                col = self.cols[c]
                if col.dtype == object:
                    col[live] = None
        return slots


class JoinEvaluator(Evaluator):
    """Symmetric incremental hash join (reference DD join replacement).

    Hot path is fully columnar: per commit, each side's join keys hash in one
    vectorized pass, the other side's matches come back as one CSR probe from the
    native multimap, and emission gathers own-side values straight from the delta
    (retraction rows carry their retracted values) and other-side values from slot
    arrays. Outer-join null-row bookkeeping runs per distinct join key, not per row.
    """

    def __init__(self, node: pg.Node, runner: Any):
        super().__init__(node, runner)
        from pathway_tpu.internals.joins import JoinKind

        self.kind = node.config["kind"]
        self.JoinKind = JoinKind
        self.left = _JoinSide(node.inputs[0].column_names())
        self.right = _JoinSide(node.inputs[1].column_names())

    def load_state_dict(self, state: Dict[str, bytes]) -> None:
        super().load_state_dict(state)
        if "left_map" in self.__dict__ or "right_map" in self.__dict__:
            raise RuntimeError(
                "checkpoint was written by an incompatible (pre-columnar) build; "
                "clear the persistence directory and re-run"
            )

    # -- elastic membership handoff: arrangements partition by JOIN key ------
    #
    # In cluster mode both input sides exchange by join key, so this rank's
    # arrangements hold exactly the rows whose join key it owns — they
    # re-partition under shard_of(join_key, new_n). The join's OUTPUT is
    # exchanged by output row key (see process), so the planner treats the
    # node as "bykey": the owner function it hands reshard_export is the
    # plain new-topology hash, which this export applies to JOIN keys.

    def reshard_check(self) -> "str | None":
        if self.__dict__.get("_udf_memo"):
            return (
                "JoinEvaluator holds a non-deterministic replay memo keyed by "
                "pre-exchange row keys — re-partitioning by join key cannot "
                "place it"
            )
        return None

    def reshard_export(self, owner_of: Any, new_n: int) -> Dict[int, Any]:
        reason = self.reshard_check()
        if reason is not None:
            from pathway_tpu.parallel.membership import MembershipUnsupportedError

            raise MembershipUnsupportedError(reason)
        out: Dict[int, Any] = {}
        for side_name, side in (("left", self.left), ("right", self.right)):
            for dest, payload in side.reshard_export(owner_of).items():
                out.setdefault(dest, {})[side_name] = payload
        return out

    def reshard_export_parts(
        self, owner_of: Any, new_n: int, budget_rows: int
    ) -> "Iterable[tuple]":
        """Bounded-transport export: the same partitions as
        :meth:`reshard_export`, sliced into ≤``budget_rows``-row pieces so the
        chunked fragment stream never materializes a whole side at once.
        Pieces merge on import (insert_batch is incremental)."""
        reason = self.reshard_check()
        if reason is not None:
            from pathway_tpu.parallel.membership import MembershipUnsupportedError

            raise MembershipUnsupportedError(reason)
        for side_name, side in (("left", self.left), ("right", self.right)):
            for dest, piece in side.reshard_export_chunks(owner_of, budget_rows):
                yield dest, {side_name: piece}

    def reshard_import(self, payload: Any) -> None:
        for side_name, side in (("left", self.left), ("right", self.right)):
            p = (payload or {}).get(side_name)
            if p:
                side.reshard_import(p)

    def _join_keys(self, side: str, delta: Delta) -> np.ndarray:
        table = self.node.inputs[0 if side == "left" else 1]
        exprs = self.node.config["left_on" if side == "left" else "right_on"]
        if not exprs:
            # no on-condition: every row shares the salt-only bucket (cross join)
            return broadcast_key(pointer_from(), len(delta))
        resolver = self._resolver_for(table, delta)
        arrays = [self._eval_expr(e, delta, resolver) for e in exprs]
        return keys_from_values(arrays)

    def process(self, input_deltas: List[Delta]) -> Delta:
        left_delta, right_delta = input_deltas
        cluster = getattr(self.runner, "_cluster", None)
        parts: List[Delta] = []
        JK = self.JoinKind
        for delta, side_name in ((left_delta, "left"), (right_delta, "right")):
            if len(delta) == 0 and cluster is None:
                continue
            # Frontier optimization: own-side rows are arranged only so FUTURE
            # other-side deltas can probe them (and, for outer kinds, so null-row
            # bookkeeping can see past own-side counts). When the other side's
            # subtree is closed — no delta this commit and none ever again — and
            # the other side never emits null rows, arranging this side buys
            # nothing: skip it. This is the static-build-side join fast path.
            is_left = side_name == "left"
            other_delta = right_delta if is_left else left_delta
            other_null = self.kind in ((JK.RIGHT, JK.OUTER) if is_left else (JK.LEFT, JK.OUTER))
            other_table = self.node.inputs[1 if is_left else 0]
            skip_arrange = (
                not other_null
                and len(other_delta) == 0
                and self.runner.subtree_closed(other_table._node)
            )
            part = self._run_side(delta, side_name, skip_arrange=skip_arrange)
            if part is not None and len(part):
                parts.append(part)
        if not parts:
            out = Delta.empty(self.output_columns)
        else:
            out = Delta.concat(parts, self.output_columns).consolidated()
        if cluster is not None and self.runner._persistence is not None:
            # replies re-route by OUTPUT row key: post-join rows land on their
            # output key's owner, so this node's materialized output and every
            # downstream key-preserving chain is plain "bykey" state for the
            # reshard planner — the join's arrangements (keyed by join key)
            # are the only state that partitions by shard_of(join_key)
            # (all-to-all barrier; runs even when empty). Only reshard-capable
            # runs (persistence on — membership handoffs write through it)
            # need the invariant; ephemeral runs keep rows where the join-key
            # exchange computed them and skip the extra hop.
            tag = f"{self.runner.current_time}:{self.node.id}:out".encode()
            out = cluster.exchange_delta(tag, out, out.keys)
        return out

    def _run_side(
        self, delta: Delta, side_name: str, *, skip_arrange: bool = False
    ) -> Delta | None:
        JK = self.JoinKind
        is_left = side_name == "left"
        own = self.left if is_left else self.right
        other = self.right if is_left else self.left
        own_null = self.kind in ((JK.LEFT, JK.OUTER) if is_left else (JK.RIGHT, JK.OUTER))
        other_null = self.kind in ((JK.RIGHT, JK.OUTER) if is_left else (JK.LEFT, JK.OUTER))

        cluster = getattr(self.runner, "_cluster", None)
        if cluster is not None:
            # both sides hash-route by JOIN key, so every join key's rows meet on
            # one owner process (all-to-all barrier; runs even with no local rows)
            jkeys0 = self._join_keys(side_name, delta)
            tag = f"{self.runner.current_time}:{self.node.id}:{side_name}".encode()
            delta = cluster.exchange_delta(tag, delta, jkeys0)
        if len(delta) == 0:
            return None

        n = len(delta)
        diffs = delta.diffs
        jkeys = self._join_keys(side_name, delta)

        # one CSR probe against the other side (static during this side's pass)
        offsets, match_slots = other.jkmap.probe(jkeys)
        counts = np.diff(offsets)

        # matched events: row i of the delta x each matching other-side slot.
        # Unique-key build sides (the common case) probe to exactly one match
        # per row — the repeats collapse to identity/copy, skip them.
        own_identity = False
        if len(match_slots) == n and counts[-1] == 1 and (counts == 1).all():
            ev_row = np.arange(n, dtype=np.int64)
            ev_d = diffs
            own_identity = True
        else:
            ev_row = np.repeat(np.arange(n, dtype=np.int64), counts)
            ev_d = np.repeat(diffs, counts)
        ev_other = match_slots

        null_rows = np.zeros(0, dtype=np.int64)
        null_d = np.zeros(0, dtype=np.int64)
        flip_slots = np.zeros(0, dtype=np.int64)
        flip_d = np.zeros(0, dtype=np.int64)
        if own_null:
            # unmatched rows of a LEFT/OUTER side emit with the other side null
            unmatched = np.nonzero(counts == 0)[0]
            null_rows = unmatched
            null_d = diffs[unmatched]
        if other_null and len(match_slots):
            # other-side rows flip between "null row" and "matched": when this side's
            # distinct join key goes 0 -> >0 rows, retract the other side's null rows;
            # on >0 -> 0, re-emit them. Tracked per distinct join key.
            from pathway_tpu.engine.index import KeyIndex

            uidx = KeyIndex(n)
            uslot, first = uidx.upsert(jkeys)
            n_keys = uidx.slot_bound()
            base = np.zeros(n_keys, dtype=np.int64)
            own_counts, _ = own.jkmap.counts(jkeys[first])
            base[uslot[first]] = own_counts
            net = np.zeros(n_keys, dtype=np.int64)
            np.add.at(net, uslot, diffs)
            flips: List[tuple] = []
            went_up = np.nonzero((base == 0) & (net > 0))[0]
            went_down = np.nonzero((base > 0) & (base + net == 0))[0]
            if len(went_up) or len(went_down):
                first_rows = np.nonzero(first)[0]
                row_of_uslot = np.zeros(n_keys, dtype=np.int64)
                row_of_uslot[uslot[first_rows]] = first_rows
                for uj, d in [(j, -1) for j in went_up] + [(j, 1) for j in went_down]:
                    r = int(row_of_uslot[uj])
                    s, e = offsets[r], offsets[r + 1]
                    flips.append((match_slots[s:e], d))
            if flips:
                flip_slots = np.concatenate([f[0] for f in flips])
                flip_d = np.concatenate(
                    [np.full(len(f[0]), f[1], dtype=np.int64) for f in flips]
                )

        # mutate own-side state AFTER all probes/gathers that read it.
        # Retractions ALWAYS apply (rows arranged before the other side closed
        # must still evict, or they leak for the run's lifetime); only new
        # inserts are skipped under the frontier fast path.
        ret_rows = np.nonzero(diffs < 0)[0]
        if len(ret_rows):
            own.remove_batch(delta.keys[ret_rows])
        if not skip_arrange:
            ins_rows = np.nonzero(diffs > 0)[0]
            if len(ins_rows):
                own.insert_batch(
                    delta.keys[ins_rows],
                    jkeys[ins_rows],
                    {c: delta.columns[c][ins_rows] for c in own.names},
                )

        total = len(ev_row) + len(null_rows) + len(flip_slots)
        if total == 0:
            return None
        return self._emit_side(
            delta, side_name, other,
            ev_d, ev_row, ev_other,
            null_d, null_rows,
            flip_d, flip_slots,
            own_identity=own_identity
            and len(null_rows) == 0
            and len(flip_slots) == 0,
        )

    def _emit_side(
        self,
        delta: Delta,
        side_name: str,
        other: _JoinSide,
        ev_d: np.ndarray,
        ev_row: np.ndarray,
        ev_other: np.ndarray,
        null_d: np.ndarray,
        null_rows: np.ndarray,
        flip_d: np.ndarray,
        flip_slots: np.ndarray,
        own_identity: bool = False,
    ) -> Delta:
        """Assemble one side-pass's output: matched events, own-null rows, and
        other-side null-row flips, in that order. ``own_identity`` marks the
        unique-match inner pass where ``ev_row`` is the identity permutation:
        own-side gathers collapse to the delta's own arrays (no copy — delta
        columns are immutable once emitted, like every evaluator treats them)."""
        is_left = side_name == "left"
        left_table, right_table = self.node.inputs
        n_ev = len(ev_d) + len(null_d) + len(flip_d)
        n_m, n_nu = len(ev_d), len(null_d)

        # per-event row index into the delta (own side) / slot into other side; -1 null
        if n_nu == 0 and len(flip_d) == 0:
            # inner-match-only pass (the common case): no null segments to
            # splice — reuse the event arrays and a shared all-true mask
            own_rows = ev_row
            other_slots = ev_other
            out_d = ev_d
            own_mask = other_mask = np.ones(n_ev, dtype=bool)
        else:
            own_rows = np.concatenate(
                [ev_row, null_rows, np.full(len(flip_d), -1, dtype=np.int64)]
            )
            other_slots = np.concatenate(
                [ev_other, np.full(len(null_d), -1, dtype=np.int64), flip_slots]
            )
            out_d = np.concatenate([ev_d, null_d, flip_d])
            own_mask = own_rows >= 0
            other_mask = other_slots >= 0

        cache: Dict[str, np.ndarray] = {}

        def own_col(name: str) -> np.ndarray:
            key = "own:" + name
            if key not in cache:
                src = delta.columns[name]
                if own_identity:
                    out = src  # identity permutation: the delta's array as-is
                elif own_mask.all():
                    out = src[own_rows]
                else:
                    out = np.empty(n_ev, dtype=object)
                    out[own_mask] = src[own_rows[own_mask]]
                    out[~own_mask] = None
                cache[key] = out
            return cache[key]

        def other_col(name: str) -> np.ndarray:
            key = "other:" + name
            if key not in cache:
                src = other.cols[name]
                if other_mask.all():
                    out = src[other_slots]
                else:
                    out = np.empty(n_ev, dtype=object)
                    out[other_mask] = src[other_slots[other_mask]]
                    out[~other_mask] = None
                cache[key] = out
            return cache[key]

        def own_ids() -> np.ndarray:
            key = "own:id"
            if key not in cache:
                out = np.empty(n_ev, dtype=object)
                rows = np.nonzero(own_mask)[0]
                ptrs = keys_to_pointers(delta.keys[own_rows[rows]])
                for a, p in zip(rows, ptrs):
                    out[a] = p
                out[~own_mask] = None
                cache[key] = out
            return cache[key]

        def other_ids() -> np.ndarray:
            key = "other:id"
            if key not in cache:
                out = np.empty(n_ev, dtype=object)
                rows = np.nonzero(other_mask)[0]
                ptrs = keys_to_pointers(other.keys[other_slots[rows]])
                for a, p in zip(rows, ptrs):
                    out[a] = p
                out[~other_mask] = None
                cache[key] = out
            return cache[key]

        def resolver(ref: expr.ColumnReference) -> np.ndarray:
            own_side = (ref.table is left_table) == is_left
            if ref.table is not left_table and ref.table is not right_table:
                raise ValueError(f"join select references foreign table: {ref!r}")
            if ref.name == "id":
                return own_ids() if own_side else other_ids()
            return own_col(ref.name) if own_side else other_col(ref.name)

        exprs = self.node.config["exprs"]
        columns = {name: ee.evaluate(e, n_ev, resolver) for name, e in exprs.items()}

        # output keys: hash (left_key, right_key, "join"); id_expr overrides where
        # the left side is present
        if own_identity:
            own_keys = delta.keys
        else:
            own_keys = np.zeros(n_ev, dtype=KEY_DTYPE)
            own_keys[own_mask] = delta.keys[own_rows[own_mask]]
        oth_keys = np.zeros(n_ev, dtype=KEY_DTYPE)
        oth_keys[other_mask] = other.keys[other_slots[other_mask]]
        lkeys, lmask = (own_keys, own_mask) if is_left else (oth_keys, other_mask)
        rkeys, rmask = (oth_keys, other_mask) if is_left else (own_keys, own_mask)
        keys = combine_keys(lkeys, rkeys, lmask, rmask)
        id_expr = self.node.config.get("id_expr")
        if id_expr is not None and lmask.any():
            id_vals = ee.evaluate(id_expr, n_ev, resolver)
            for i in np.nonzero(lmask)[0]:
                p = id_vals[i]
                if isinstance(p, Pointer):
                    keys[i]["hi"], keys[i]["lo"] = p.hi, p.lo
        return Delta(keys, out_d, columns)


class UpdateRowsEvaluator(Evaluator):
    # base and patch relate rows BY ROW KEY: exchanging both means every key's
    # base/patch pair meets on its owner process (exact under spawn -n N)
    CLUSTER_POLICIES = {0: "rowkey", 1: "rowkey"}

    def __init__(self, node: pg.Node, runner: Any):
        super().__init__(node, runner)
        self.base = StateTable(self.output_columns)
        self.patch = StateTable(self.output_columns)

    def process(self, input_deltas: List[Delta]) -> Delta:
        base_delta, patch_delta = input_deltas
        out_keys, out_diffs, out_rows = [], [], []

        for i in range(len(base_delta)):
            kb = base_delta.keys[i].tobytes()
            d = int(base_delta.diffs[i])
            row = {c: base_delta.columns[c][i] for c in self.output_columns}
            if self.patch.get_row(kb) is None:
                out_keys.append(base_delta.keys[i])
                out_diffs.append(d)
                out_rows.append(row)
        self.base.apply(base_delta)

        for i in range(len(patch_delta)):
            kb = patch_delta.keys[i].tobytes()
            d = int(patch_delta.diffs[i])
            row = {c: patch_delta.columns[c][i] for c in self.output_columns}
            base_row = self.base.get_row(kb)
            if d > 0:
                if base_row is not None and self.patch.get_row(kb) is None:
                    out_keys.append(patch_delta.keys[i])
                    out_diffs.append(-1)
                    out_rows.append(base_row)
                out_keys.append(patch_delta.keys[i])
                out_diffs.append(1)
                out_rows.append(row)
            else:
                out_keys.append(patch_delta.keys[i])
                out_diffs.append(-1)
                out_rows.append(row)
                if base_row is not None:
                    out_keys.append(patch_delta.keys[i])
                    out_diffs.append(1)
                    out_rows.append(base_row)
        self.patch.apply(patch_delta)

        return _delta_from_rows(
            out_keys, out_diffs, out_rows, self.output_columns
        ).consolidated()


class UpdateCellsEvaluator(Evaluator):
    CLUSTER_POLICIES = {0: "rowkey", 1: "rowkey"}

    def __init__(self, node: pg.Node, runner: Any):
        super().__init__(node, runner)
        patch_cols = [
            c for c in node.inputs[1].column_names() if c in node.inputs[0].column_names()
        ]
        self.patch_cols = patch_cols
        self.base = StateTable(self.output_columns)
        self.patch = StateTable(patch_cols)

    def _merged(self, kb: bytes, base_row: dict) -> dict:
        patch_row = self.patch.get_row(kb)
        if patch_row is None:
            return base_row
        merged = dict(base_row)
        merged.update(patch_row)
        return merged

    def process(self, input_deltas: List[Delta]) -> Delta:
        base_delta, patch_delta = input_deltas
        out_keys, out_diffs, out_rows = [], [], []

        # patch first so base rows arriving same commit see it
        self.patch.apply(
            Delta(
                patch_delta.keys,
                patch_delta.diffs,
                {c: patch_delta.columns[c] for c in self.patch_cols},
            )
        )
        for i in range(len(base_delta)):
            kb = base_delta.keys[i].tobytes()
            row = {c: base_delta.columns[c][i] for c in self.output_columns}
            out_keys.append(base_delta.keys[i])
            out_diffs.append(int(base_delta.diffs[i]))
            out_rows.append(self._merged(kb, row))
        self.base.apply(base_delta)

        # patch changes for keys NOT in this commit's base delta
        seen = {base_delta.keys[i].tobytes() for i in range(len(base_delta))}
        handled: set[bytes] = set()
        for i in range(len(patch_delta)):
            kb = patch_delta.keys[i].tobytes()
            if kb in seen or kb in handled:
                continue
            handled.add(kb)
            base_row = self.base.get_row(kb)
            if base_row is None:
                continue
            # old merged (reconstruct patch state before this commit's patch delta)
            old_patch: dict | None = None
            for j in range(len(patch_delta)):
                if patch_delta.keys[j].tobytes() == kb and patch_delta.diffs[j] < 0:
                    old_patch = {c: patch_delta.columns[c][j] for c in self.patch_cols}
            old_row = dict(base_row)
            if old_patch is not None:
                old_row.update(old_patch)
            new_row = self._merged(kb, base_row)
            if old_row != new_row:
                out_keys.append(patch_delta.keys[i])
                out_diffs.append(-1)
                out_rows.append(old_row)
                out_keys.append(patch_delta.keys[i])
                out_diffs.append(1)
                out_rows.append(new_row)
        return _delta_from_rows(out_keys, out_diffs, out_rows, self.output_columns).consolidated()


class _KeyPresenceMixin(Evaluator):
    """Shared machinery for intersect/difference/restrict/having."""

    def cluster_input_policy(self, idx: int) -> str | None:
        # presence is tested key-by-key: co-partition every input by row key
        return "rowkey"

    def __init__(self, node: pg.Node, runner: Any):
        super().__init__(node, runner)
        self.base = StateTable(self.output_columns)
        self.presence: List[set[bytes]] = [set() for _ in node.inputs[1:]]

    # -- elastic membership handoff: base rows and presence sets are both
    # keyed by the row key (every input is rowkey-exchanged), so they
    # partition exactly

    def reshard_check(self) -> "str | None":
        return None

    def reshard_export(self, owner_of: Any, new_n: int) -> Dict[int, Any]:
        out: Dict[int, Any] = {}

        def bucket(dest: int) -> dict:
            return out.setdefault(
                dest,
                {"base": None, "presence": [set() for _ in self.presence]},
            )

        for dest, part in self.base.reshard_partition(owner_of).items():
            bucket(dest)["base"] = part
        for idx, pres in enumerate(self.presence):
            for kb in pres:
                keys = np.frombuffer(kb, dtype=KEY_DTYPE)
                dest = int(np.asarray(owner_of(keys))[0])
                bucket(dest)["presence"][idx].add(kb)
        memo = Evaluator.reshard_export(self, owner_of, new_n)
        for dest, payload in memo.items():
            bucket(dest)["_udf_memo"] = payload["_udf_memo"]
        return out

    def reshard_import(self, payload: Any) -> None:
        part = payload.get("base")
        if part is not None:
            keys, diffs, columns = part
            self.base.apply(Delta(keys, diffs, columns))
        for idx, pres in enumerate(payload.get("presence", ())):
            if idx < len(self.presence):
                self.presence[idx] |= set(pres)
        Evaluator.reshard_import(self, payload)

    def _emit_row(self, kb: bytes, key: np.void, diff: int, row: dict, out: list) -> None:
        out.append((key, diff, row))

    def _condition(self, kb: bytes) -> bool:
        raise NotImplementedError

    def process(self, input_deltas: List[Delta]) -> Delta:
        base_delta = input_deltas[0]
        out: List[tuple] = []

        # update presence sets, recording transitions
        transitions: Dict[bytes, np.void] = {}
        for idx, delta in enumerate(input_deltas[1:]):
            for i in range(len(delta)):
                kb = delta.keys[i].tobytes()
                before = self._condition(kb)
                if delta.diffs[i] > 0:
                    self.presence[idx].add(kb)
                else:
                    self.presence[idx].discard(kb)
                after = self._condition(kb)
                if before != after:
                    transitions[kb] = delta.keys[i]

        for i in range(len(base_delta)):
            kb = base_delta.keys[i].tobytes()
            transitions.pop(kb, None)
        # base rows: emit if condition currently holds
        for i in range(len(base_delta)):
            kb = base_delta.keys[i].tobytes()
            if self._condition(kb):
                row = {c: base_delta.columns[c][i] for c in self.output_columns}
                out.append((base_delta.keys[i], int(base_delta.diffs[i]), row))
        self.base.apply(base_delta)

        for kb, key in transitions.items():
            row = self.base.get_row(kb)
            if row is None:
                continue
            diff = 1 if self._condition(kb) else -1
            out.append((key, diff, row))

        keys = [o[0] for o in out]
        diffs = [o[1] for o in out]
        rows = [o[2] for o in out]
        return _delta_from_rows(keys, diffs, rows, self.output_columns)


class IntersectEvaluator(_KeyPresenceMixin):
    def _condition(self, kb: bytes) -> bool:
        return all(kb in p for p in self.presence)


class DifferenceEvaluator(_KeyPresenceMixin):
    def _condition(self, kb: bytes) -> bool:
        return kb not in self.presence[0]


class RestrictEvaluator(_KeyPresenceMixin):
    def _condition(self, kb: bytes) -> bool:
        return kb in self.presence[0]


class HavingEvaluator(Evaluator):
    """Keep base rows whose key appears among the indexer pointer column's values."""

    _NON_STATE_ATTRS = Evaluator._NON_STATE_ATTRS + ("indexers",)

    # custom routes carry the base ROW KEY itself (the pointer value each
    # indexer row asserts), so state partitions exactly under shard_of(row key)
    RESHARD_ROUTE_BYKEY = True

    def cluster_input_policy(self, idx: int) -> str | None:
        # indexer rows route by the POINTER VALUE they carry (the key whose
        # presence they assert), meeting the base row they reference
        return "rowkey" if idx == 0 else "custom"

    def cluster_route_keys(self, idx: int, delta: Delta) -> np.ndarray:
        vals = delta.columns[self.indexers[idx - 1].name]
        out = delta.keys.copy()  # non-pointer cells: route arbitrarily (ignored)
        for i in range(len(delta)):
            if isinstance(vals[i], Pointer):
                out[i] = pointers_to_keys([vals[i]])[0]
        return out

    def __init__(self, node: pg.Node, runner: Any):
        super().__init__(node, runner)
        self.base = StateTable(self.output_columns)
        self.indexers: List[expr.ColumnReference] = node.config["indexers"]
        self.counts: List[Dict[bytes, int]] = [defaultdict(int) for _ in self.indexers]

    def _condition(self, kb: bytes) -> bool:
        return all(c.get(kb, 0) > 0 for c in self.counts)

    def process(self, input_deltas: List[Delta]) -> Delta:
        base_delta = input_deltas[0]
        out: List[tuple] = []
        transitions: Dict[bytes, np.void] = {}
        for idx, delta in enumerate(input_deltas[1:]):
            ref = self.indexers[idx]
            if len(delta) == 0:
                continue
            vals = delta.columns[ref.name]
            for i in range(len(delta)):
                p = vals[i]
                if not isinstance(p, Pointer):
                    continue
                kb = pointers_to_keys([p]).tobytes()
                before = self._condition(kb)
                self.counts[idx][kb] += int(delta.diffs[i])
                after = self._condition(kb)
                if before != after:
                    transitions[kb] = pointers_to_keys([p])[0]

        for i in range(len(base_delta)):
            kb = base_delta.keys[i].tobytes()
            transitions.pop(kb, None)
            if self._condition(kb):
                row = {c: base_delta.columns[c][i] for c in self.output_columns}
                out.append((base_delta.keys[i], int(base_delta.diffs[i]), row))
        self.base.apply(base_delta)

        for kb, key in transitions.items():
            row = self.base.get_row(kb)
            if row is None:
                continue
            diff = 1 if self._condition(kb) else -1
            out.append((key, diff, row))
        return _delta_from_rows(
            [o[0] for o in out], [o[1] for o in out], [o[2] for o in out], self.output_columns
        )

    # -- elastic membership handoff: base rows and indexer reference counts
    # are both keyed by the base row key, so they partition exactly

    def reshard_check(self) -> "str | None":
        return None

    def reshard_export(self, owner_of: Any, new_n: int) -> Dict[int, Any]:
        out: Dict[int, Any] = {}

        def bucket(dest: int) -> dict:
            return out.setdefault(
                dest,
                {"base": None, "counts": [dict() for _ in self.indexers]},
            )

        for dest, part in self.base.reshard_partition(owner_of).items():
            bucket(dest)["base"] = part
        for idx, cnt in enumerate(self.counts):
            for kb, c in cnt.items():
                if not c:
                    continue
                keys = np.frombuffer(kb, dtype=KEY_DTYPE)
                dest = int(np.asarray(owner_of(keys))[0])
                bucket(dest)["counts"][idx][kb] = c
        memo = Evaluator.reshard_export(self, owner_of, new_n)
        for dest, payload in memo.items():
            bucket(dest)["_udf_memo"] = payload["_udf_memo"]
        return out

    def reshard_import(self, payload: Any) -> None:
        part = payload.get("base")
        if part is not None:
            keys, diffs, columns = part
            self.base.apply(Delta(keys, diffs, columns))
        for idx, cnt in enumerate(payload.get("counts", ())):
            if idx < len(self.counts):
                for kb, c in cnt.items():
                    self.counts[idx][kb] += c
        Evaluator.reshard_import(self, payload)


class WithUniverseOfEvaluator(Evaluator):
    """Runtime enforcement of the promised universe equality (the reference's
    engine rekeys onto the other universe and fails on mismatch; here both key
    sets are tracked and verified once the stream is final)."""

    CLUSTER_POLICIES = {0: "rowkey", 1: "rowkey"}

    def __init__(self, node: pg.Node, runner: Any):
        super().__init__(node, runner)
        from pathway_tpu.engine.index import KeyIndex

        self.self_keys = KeyIndex()
        self.other_keys = KeyIndex()

    def process(self, input_deltas: List[Delta]) -> Delta:
        self_delta, other_delta = input_deltas
        for delta, idx in ((self_delta, self.self_keys), (other_delta, self.other_keys)):
            if not len(delta):
                continue
            # removals first: an in-place update (-1 old, +1 new on one key in one
            # delta) must leave the key PRESENT regardless of row order
            ins = delta.diffs > 0
            if (~ins).any():
                idx.remove(delta.keys[~ins])
            if ins.any():
                idx.upsert(delta.keys[ins])
        return self_delta

    def verify_universes(self) -> None:
        """Called at stream end: the promised key-set equality must actually hold."""
        from pathway_tpu.internals.keys import keys_to_pointers

        a_keys, _ = self.self_keys.items()
        b_keys, _ = self.other_keys.items()
        only_a = self.other_keys.lookup(a_keys) < 0 if len(a_keys) else np.zeros(0, bool)
        only_b = self.self_keys.lookup(b_keys) < 0 if len(b_keys) else np.zeros(0, bool)
        if only_a.any() or only_b.any():
            sample_a = keys_to_pointers(a_keys[only_a][:3]) if only_a.any() else []
            sample_b = keys_to_pointers(b_keys[only_b][:3]) if only_b.any() else []
            raise RuntimeError(
                "with_universe_of: promised universe equality violated at runtime — "
                f"{int(only_a.sum())} key(s) only in the table (e.g. {sample_a}), "
                f"{int(only_b.sum())} only in the other (e.g. {sample_b})"
            )


class FlattenEvaluator(_DerivedKeyMixin):
    def process(self, input_deltas: List[Delta]) -> Delta:
        (delta,) = input_deltas
        if len(delta) == 0:
            return Delta.empty(self.output_columns)
        flat_name = self.node.config["flat_name"]
        origin_id = self.node.config.get("origin_id")
        out_keys, out_diffs, out_rows, in_idx = [], [], [], []
        ptrs = keys_to_pointers(delta.keys)
        for i in range(len(delta)):
            value = delta.columns[flat_name][i]
            items = _iter_flatten(value)
            for j, item in enumerate(items):
                row = {c: delta.columns[c][i] for c in delta.column_names}
                row[flat_name] = item
                if origin_id:
                    row[origin_id] = ptrs[i]
                out_keys.append(pointer_from(ptrs[i], j, "flatten"))
                out_diffs.append(int(delta.diffs[i]))
                out_rows.append(row)
                in_idx.append(i)
        keys = pointers_to_keys(out_keys) if out_keys else []
        if len(keys):
            self._track_prov(keys, delta.keys[np.asarray(in_idx, dtype=np.int64)])
        return _delta_from_rows(keys, out_diffs, out_rows, self.output_columns)


def _iter_flatten(value: Any) -> list:
    from pathway_tpu.internals.json import Json

    if isinstance(value, Json):
        return [Json(v) if isinstance(v, (dict, list)) else v for v in value.value]
    if isinstance(value, (list, tuple)):
        return list(value)
    if isinstance(value, np.ndarray):
        return list(value)
    if isinstance(value, str):
        return list(value)
    raise TypeError(f"cannot flatten value of type {type(value).__name__}")


class IxEvaluator(Evaluator):
    """source-keyed lookup into target (reference ``ix``/``ix_ref``).

    Multi-process: the TARGET side replicates (broadcast) into a private state
    replica, so a lookup of any pointer answers locally wherever the source row
    lives — the same replicated-state pattern as the external index. Source
    rows (and therefore output rows) stay where they were produced."""

    CLUSTER_POLICIES = {1: "broadcast"}

    def __init__(self, node: pg.Node, runner: Any):
        super().__init__(node, runner)
        self.src_keys: Dict[bytes, bytes] = {}  # source key -> target key
        self.reverse: Dict[bytes, set[bytes]] = defaultdict(set)
        self.src_rows: Dict[bytes, np.void] = {}
        self.emitted: Dict[bytes, dict] = {}  # source key -> last emitted output row
        self._replica: Any = (
            StateTable(node.inputs[1].column_names())
            if getattr(runner, "_cluster", None) is not None
            else None
        )

    def process(self, input_deltas: List[Delta]) -> Delta:
        source_delta, target_delta = input_deltas
        source_table, target_table = self.node.inputs
        optional = self.node.config.get("optional", False)
        if self._replica is not None:
            # broadcast target deltas feed the replica BEFORE lookups, matching
            # the single-process ordering (target materializes before ix runs)
            self._replica.apply(target_delta)
            target_state = self._replica
        else:
            target_state = self.runner.state_of(target_table._node)
        out_keys, out_diffs, out_rows = [], [], []

        handled_sources: set[bytes] = set()
        if len(source_delta):
            resolver = self._resolver_for(source_table, source_delta)
            ixptrs = ee.evaluate(
                self.node.config["key_expression"], len(source_delta), resolver
            )
            for i in range(len(source_delta)):
                skb = source_delta.keys[i].tobytes()
                handled_sources.add(skb)
                d = int(source_delta.diffs[i])
                p = ixptrs[i]
                tkb = pointers_to_keys([p]).tobytes() if isinstance(p, Pointer) else None
                if d > 0:
                    self.src_keys[skb] = tkb
                    self.src_rows[skb] = source_delta.keys[i]
                    if tkb is not None:
                        self.reverse[tkb].add(skb)
                    row = None if tkb is None else target_state.get_row(tkb)
                    if row is None:
                        if not optional and tkb is not None:
                            raise KeyError(f"ix: missing key {p!r} in target table")
                        row = {c: None for c in self.output_columns}
                    self.emitted[skb] = row
                else:
                    self.src_keys.pop(skb, None)
                    self.src_rows.pop(skb, None)
                    if tkb is not None:
                        self.reverse[tkb].discard(skb)
                    # retraction replays what was last emitted, regardless of target state
                    row = self.emitted.pop(skb, {c: None for c in self.output_columns})
                out_keys.append(source_delta.keys[i])
                out_diffs.append(d)
                out_rows.append(row)

        # target-side changes re-emit affected source rows, preserving row-per-key:
        # optional sources flip between the real row and an all-None row
        none_row = {c: None for c in self.output_columns}
        for i in range(len(target_delta)):
            tkb = target_delta.keys[i].tobytes()
            d = int(target_delta.diffs[i])
            row = {c: target_delta.columns[c][i] for c in self.output_columns}
            for skb in self.reverse.get(tkb, set()):
                if skb in handled_sources:
                    continue
                prev = self.emitted.get(skb)
                if d > 0:
                    if prev is not None:
                        out_keys.append(self.src_rows[skb])
                        out_diffs.append(-1)
                        out_rows.append(prev)
                    out_keys.append(self.src_rows[skb])
                    out_diffs.append(1)
                    out_rows.append(row)
                    self.emitted[skb] = row
                else:
                    out_keys.append(self.src_rows[skb])
                    out_diffs.append(-1)
                    out_rows.append(prev if prev is not None else row)
                    if optional:
                        out_keys.append(self.src_rows[skb])
                        out_diffs.append(1)
                        out_rows.append(none_row)
                        self.emitted[skb] = none_row
                    else:
                        self.emitted.pop(skb, None)
        return _delta_from_rows(
            out_keys, out_diffs, out_rows, self.output_columns
        ).consolidated()


class SortEvaluator(Evaluator):
    """prev/next pointers per instance (reference ``prev_next.rs:770``)."""

    # global per-instance ordering: centralize on process 0 (the reference routes
    # such operators to one worker, ``time_column.rs:48-51``)
    CLUSTER_POLICIES = {0: "root"}

    def __init__(self, node: pg.Node, runner: Any):
        super().__init__(node, runner)
        self.rows: Dict[bytes, tuple] = {}  # key -> (sort_val, instance, Pointer)
        self.emitted: Dict[bytes, tuple] = {}  # key -> (prev, next)

    def process(self, input_deltas: List[Delta]) -> Delta:
        (delta,) = input_deltas
        if len(delta) == 0:
            return Delta.empty(self.output_columns)
        table = self.node.inputs[0]
        resolver = self._resolver_for(table, delta)
        n = len(delta)
        keys_vals = ee.evaluate(self.node.config["key"], n, resolver)
        instance_e = self.node.config.get("instance")
        instances = (
            ee.evaluate(instance_e, n, resolver) if instance_e is not None else np.zeros(n, dtype=object)
        )
        ptrs = keys_to_pointers(delta.keys)
        touched_instances = set()
        for i in range(n):
            kb = delta.keys[i].tobytes()
            if delta.diffs[i] > 0:
                self.rows[kb] = (keys_vals[i], instances[i], ptrs[i], delta.keys[i])
            else:
                self.rows.pop(kb, None)
            touched_instances.add(_hashable_scalar(instances[i]))

        # recompute orders for touched instances
        out_keys, out_diffs, out_rows = [], [], []
        by_instance: Dict[Any, list] = defaultdict(list)
        for kb, (sv, inst, ptr, key) in self.rows.items():
            hi = _hashable_scalar(inst)
            if hi in touched_instances:
                by_instance[hi].append((sv, ptr, kb, key))
        new_links: Dict[bytes, tuple] = {}
        for inst, rows in by_instance.items():
            rows.sort(key=lambda r: (r[0], r[1]))
            for idx, (sv, ptr, kb, key) in enumerate(rows):
                prev_ptr = rows[idx - 1][1] if idx > 0 else None
                next_ptr = rows[idx + 1][1] if idx < len(rows) - 1 else None
                new_links[kb] = (prev_ptr, next_ptr, key)
        # diff against emitted
        for kb, (pv, nv) in list(self.emitted.items()):
            if kb not in self.rows:
                # row gone: retract
                out_keys.append(self._key_of(kb))
                out_diffs.append(-1)
                out_rows.append({"prev": pv, "next": nv})
                del self.emitted[kb]
        for kb, (pv, nv, key) in new_links.items():
            old = self.emitted.get(kb)
            if old == (pv, nv):
                continue
            if old is not None:
                out_keys.append(key)
                out_diffs.append(-1)
                out_rows.append({"prev": old[0], "next": old[1]})
            out_keys.append(key)
            out_diffs.append(1)
            out_rows.append({"prev": pv, "next": nv})
            self.emitted[kb] = (pv, nv)
        return _delta_from_rows(out_keys, out_diffs, out_rows, self.output_columns)

    def _key_of(self, kb: bytes) -> np.void:
        arr = np.frombuffer(kb, dtype=KEY_DTYPE)
        return arr[0]


def _hashable_scalar(v: Any) -> Any:
    if isinstance(v, np.ndarray):
        return (v.tobytes(), v.shape)
    return v


class SortedIndexEvaluator(Evaluator):
    """Sorted binary tree per instance (reference ``stdlib/indexing/sorting.py:92``).

    The reference grows a treap through ``pw.iterate`` rounds of ix/groupby; here
    the engine holds each instance's rows sorted and rebuilds the tree for touched
    instances per commit as a CARTESIAN TREE (one O(n) stack pass): in-order =
    key order, heap order = per-row priority. Priorities are the rows' xxh3 key
    fingerprints — deterministic, uniform, independent of arrival order, matching
    the reference's hash-as-priority treap shape."""

    CLUSTER_POLICIES = {0: "root"}  # global per-instance ordering, like sort

    def __init__(self, node: pg.Node, runner: Any):
        super().__init__(node, runner)
        self.rows: Dict[bytes, tuple] = {}  # kb -> (sort_val, instance, ptr, key)
        self.emitted: Dict[bytes, tuple] = {}  # kb -> row tuple
        # per-instance membership so a commit touches only its instances'
        # rows, not the whole table (incrementality)
        self.members: Dict[Any, Dict[bytes, tuple]] = defaultdict(dict)

    @staticmethod
    def _tree_links(ordered: List[tuple]) -> List[tuple]:
        """(left, right, parent) per position for the cartesian tree of
        ``ordered`` = [(priority, ptr), ...] in key order; min-priority root."""
        n = len(ordered)
        left = [None] * n
        right = [None] * n
        parent = [None] * n
        stack: List[int] = []
        for i in range(n):
            dethroned = None
            while stack and ordered[stack[-1]][0] > ordered[i][0]:
                dethroned = stack.pop()
            if dethroned is not None:
                left[i] = ordered[dethroned][1]
                parent[dethroned] = ordered[i][1]
            if stack:
                right[stack[-1]] = ordered[i][1]
                parent[i] = ordered[stack[-1]][1]
            stack.append(i)
        return list(zip(left, right, parent))

    def process(self, input_deltas: List[Delta]) -> Delta:
        (delta,) = input_deltas
        if len(delta) == 0:
            return Delta.empty(self.output_columns)
        table = self.node.inputs[0]
        resolver = self._resolver_for(table, delta)
        n = len(delta)
        keys_vals = ee.evaluate(self.node.config["key"], n, resolver)
        instance_e = self.node.config.get("instance")
        instances = (
            ee.evaluate(instance_e, n, resolver)
            if instance_e is not None
            else np.zeros(n, dtype=object)
        )
        ptrs = keys_to_pointers(delta.keys)
        touched = set()
        for i in range(n):
            kb = delta.keys[i].tobytes()
            old = self.rows.get(kb)
            if old is not None:
                self.members[_hashable_scalar(old[1])].pop(kb, None)
                touched.add(_hashable_scalar(old[1]))
            if delta.diffs[i] > 0:
                entry = (keys_vals[i], instances[i], ptrs[i], delta.keys[i])
                self.rows[kb] = entry
                self.members[_hashable_scalar(instances[i])][kb] = entry
            else:
                self.rows.pop(kb, None)
            touched.add(_hashable_scalar(instances[i]))

        fresh: Dict[bytes, tuple] = {}
        for hi in touched:
            members = [
                (sv, ptr, kb, key, inst)
                for kb, (sv, inst, ptr, key) in self.members.get(hi, {}).items()
            ]
            members.sort(key=lambda r: (r[0], r[1]))
            # priority = xxh3 fingerprint already inside the row key (lo word)
            links = self._tree_links(
                [(np.frombuffer(kb, dtype=KEY_DTYPE)[0]["lo"].item(), ptr) for _sv, ptr, kb, _k, _i in members]
            )
            for (sv, ptr, kb, key, inst), (lf, rt, par) in zip(members, links):
                fresh[kb] = (key, {"key": sv, "left": lf, "right": rt, "parent": par, "instance": inst})

        out_keys, out_diffs, out_rows = [], [], []
        # removals come from the delta's negative rows, not a full emitted scan
        for i in range(n):
            if delta.diffs[i] >= 0:
                continue
            kb = delta.keys[i].tobytes()
            if kb in self.rows:
                continue  # replaced within this commit, not removed
            old_row = self.emitted.pop(kb, None)
            if old_row is not None:
                out_keys.append(delta.keys[i])
                out_diffs.append(-1)
                out_rows.append(old_row)
        for kb, (key, row) in fresh.items():
            old = self.emitted.get(kb)
            if old == row:
                continue
            if old is not None:
                out_keys.append(key)
                out_diffs.append(-1)
                out_rows.append(old)
            out_keys.append(key)
            out_diffs.append(1)
            out_rows.append(row)
            self.emitted[kb] = row
        return _delta_from_rows(out_keys, out_diffs, out_rows, self.output_columns)


class RemoveErrorsEvaluator(Evaluator):
    def process(self, input_deltas: List[Delta]) -> Delta:
        (delta,) = input_deltas
        if len(delta) == 0:
            return delta
        mask = np.ones(len(delta), dtype=bool)
        for col in delta.columns.values():
            if col.dtype == object:
                mask &= ~np.frompyfunc(lambda v: isinstance(v, Error), 1, 1)(col).astype(bool)
        return delta.select(mask)


class AsofNowEvaluator(Evaluator):
    """``_forget_immediately`` / ``_filter_out_results_of_forgetting``.

    Forget mode passes each commit's rows through unchanged and schedules a retraction of
    every insert; the runner drains those in the same commit's *neu* phase (the
    reference's odd-time forgetting, ``dataflow.rs:3447``): downstream state shrinks, but
    the forgetting filter drops neu deltas so delivered results stay. An upstream
    retraction of a still-scheduled key cancels the schedule (no double retraction).
    """

    def __init__(self, node: pg.Node, runner: Any):
        super().__init__(node, runner)
        self.pending: Dict[bytes, tuple] = {}  # kb -> (key, row)

    def process(self, input_deltas: List[Delta]) -> Delta:
        (delta,) = input_deltas
        mode = self.node.config["mode"]
        if mode == "filter_forgotten":
            if delta.neu:
                return Delta.empty(self.output_columns)
            return delta
        # forget mode
        for i in range(len(delta)):
            kb = delta.keys[i].tobytes()
            if delta.diffs[i] > 0:
                self.pending[kb] = (
                    delta.keys[i],
                    {c: delta.columns[c][i] for c in delta.column_names},
                )
            else:
                # genuine upstream retraction passes through; cancel the scheduled one
                self.pending.pop(kb, None)
        return delta

    def neu_pending(self) -> bool:
        return self.node.config["mode"] == "forget" and bool(self.pending)

    def drain_neu(self, input_deltas: List[Delta]) -> Delta:
        parts = []
        if self.pending:
            keys = [p[0] for p in self.pending.values()]
            rows = [p[1] for p in self.pending.values()]
            self.pending = {}
            parts.append(
                _delta_from_rows(keys, [-1] * len(keys), rows, self.output_columns)
            )
        if any(len(d) for d in input_deltas):
            parts.append(self.process(input_deltas))
        return Delta.concat(parts, self.output_columns)

    def has_pending(self) -> bool:
        return bool(self.pending)


class _TimeThresholdEvaluator(Evaluator):
    """Shared machinery for buffer/forget/freeze (reference ``time_column.rs``).

    Tracks ``now`` = the max value of the time column observed so far; a row is *ripe*
    once its threshold column value is ≤ ``now`` (the commit-granularity stand-in for
    the reference's frontier comparison). Ripeness scans use a min-heap on threshold so
    each commit pops only the ripe prefix (no full rescan of buffered state).
    """

    # ``now`` is a GLOBAL watermark (max time over the whole stream): centralize
    # on process 0, as the reference does for time-column operators
    # (``time_column.rs:48-51`` — "we need to process all data in one worker")
    CLUSTER_POLICIES = {0: "root"}

    # drain-sensitive: these operators flush on ``runner.draining``, a
    # live-only signal that a rejoining rank's journal replay does not
    # reproduce (``_ready`` is forced False during replay) — a rung-1 survivor
    # keeping post-flush state while the replacement replays without the flush
    # would diverge per rank, so graphs holding one skip the rewind rung
    REWIND_SAFE = False

    def __init__(self, node: pg.Node, runner: Any):
        super().__init__(node, runner)
        self.now: Any = None
        self._heap: List[tuple] = []  # (threshold, seq, kb)
        self._heap_seq = 0

    def _thresholds_times(self, delta: Delta) -> Tuple[np.ndarray, np.ndarray]:
        table = self.node.inputs[0]
        resolver = self._resolver_for(table, delta)
        n = len(delta)
        thr = ee.evaluate(self.node.config["threshold"], n, resolver)
        tim = ee.evaluate(self.node.config["time"], n, resolver)
        return thr, tim

    def _advance_now(self, tim: np.ndarray, diffs: np.ndarray) -> None:
        for i in range(len(tim)):
            if diffs[i] > 0 and tim[i] is not None:
                if self.now is None or tim[i] > self.now:
                    self.now = tim[i]

    def _ripe(self, threshold: Any) -> bool:
        return self.now is not None and threshold <= self.now

    def _heap_push(self, threshold: Any, kb: bytes) -> None:
        import heapq

        heapq.heappush(self._heap, (threshold, self._heap_seq, kb))
        self._heap_seq += 1

    def _heap_pop_ripe(self, *, all_: bool = False):
        """Yield (threshold, kb) for entries whose threshold passed ``now`` (or all,
        when draining). Entries are lazily validated by the caller."""
        import heapq

        while self._heap and (all_ or self._ripe(self._heap[0][0])):
            threshold, _, kb = heapq.heappop(self._heap)
            yield threshold, kb


class BufferEvaluator(_TimeThresholdEvaluator):
    """Postpone emission until the stream's time passes each row's threshold
    (reference ``TimeColumnBuffer`` / ``postpone_core``, ``time_column.rs:255,380``).
    At stream close every buffered row flushes, as when the frontier empties."""

    def __init__(self, node: pg.Node, runner: Any):
        super().__init__(node, runner)
        # kb -> [key, row, threshold, accumulated diff]
        self.pending: Dict[bytes, list] = {}
        self.emitted: set[bytes] = set()

    def process(self, input_deltas: List[Delta]) -> Delta:
        (delta,) = input_deltas
        out_keys: List[Any] = []
        out_diffs: List[int] = []
        out_rows: List[dict] = []
        if len(delta):
            thr, tim = self._thresholds_times(delta)
            self._advance_now(tim, delta.diffs)
            for i in range(len(delta)):
                kb = delta.keys[i].tobytes()
                d = int(delta.diffs[i])
                row = {c: delta.columns[c][i] for c in delta.column_names}
                if d < 0 and kb in self.emitted:
                    # retraction of an already-emitted row passes straight through
                    out_keys.append(delta.keys[i])
                    out_diffs.append(-1)
                    out_rows.append(row)
                    self.emitted.discard(kb)
                    continue
                cur = self.pending.get(kb)
                if cur is None:
                    self.pending[kb] = [delta.keys[i], row, thr[i], d]
                    self._heap_push(thr[i], kb)
                else:
                    cur[3] += d
                    if d > 0:
                        cur[1] = row
                        if cur[2] != thr[i]:
                            cur[2] = thr[i]
                            self._heap_push(thr[i], kb)
                    if cur[3] == 0:
                        del self.pending[kb]
        draining = getattr(self.runner, "draining", False)
        for threshold, kb in self._heap_pop_ripe(all_=draining):
            cur = self.pending.get(kb)
            if cur is None or cur[2] != threshold:
                continue  # stale heap entry (row cancelled or rescheduled)
            del self.pending[kb]
            key, row, _, acc = cur
            if acc == 0:
                continue
            out_keys.append(key)
            out_diffs.append(acc)
            out_rows.append(row)
            if acc > 0:
                self.emitted.add(kb)
        return _delta_from_rows(
            out_keys, out_diffs, out_rows, self.output_columns
        ).consolidated()

    def has_pending(self) -> bool:
        return bool(self.pending)


class FreezeEvaluator(_TimeThresholdEvaluator):
    """Drop late rows — updates arriving after the stream's time passed their threshold
    (reference ``TimeColumnFreeze`` / ``ignore_late``, ``time_column.rs:631,677``)."""

    def process(self, input_deltas: List[Delta]) -> Delta:
        (delta,) = input_deltas
        if len(delta) == 0:
            return Delta.empty(self.output_columns)
        thr, tim = self._thresholds_times(delta)
        mask = np.ones(len(delta), dtype=bool)
        for i in range(len(delta)):
            if self._ripe(thr[i]):
                mask[i] = False
        self._advance_now(tim, delta.diffs)
        return delta.select(mask)


class ForgetEvaluator(_TimeThresholdEvaluator):
    """Retract rows once the stream's time passes their threshold (reference
    ``TimeColumnForget``, ``time_column.rs:556``). The retractions drain in the same
    commit's *neu* phase; with keep_results=True a downstream forgetting filter drops
    them so state is bounded but delivered results stay, and with keep_results=False
    there is no filter, so results are genuinely removed."""

    def __init__(self, node: pg.Node, runner: Any):
        super().__init__(node, runner)
        self.live: Dict[bytes, tuple] = {}  # kb -> (key, row, threshold)
        self.pending_forget: Dict[bytes, tuple] = {}  # kb -> (key, row)

    def process(self, input_deltas: List[Delta]) -> Delta:
        (delta,) = input_deltas
        if len(delta) == 0:
            return Delta.empty(self.output_columns)
        thr, tim = self._thresholds_times(delta)
        self._advance_now(tim, delta.diffs)
        for i in range(len(delta)):
            kb = delta.keys[i].tobytes()
            if delta.diffs[i] > 0:
                row = {c: delta.columns[c][i] for c in delta.column_names}
                self.live[kb] = (delta.keys[i], row, thr[i])
                self._heap_push(thr[i], kb)
            else:
                # genuine upstream retraction: cancel any scheduled forgetting
                self.live.pop(kb, None)
                self.pending_forget.pop(kb, None)
        for threshold, kb in self._heap_pop_ripe():
            cur = self.live.get(kb)
            if cur is None or cur[2] != threshold:
                continue  # stale heap entry
            del self.live[kb]
            self.pending_forget[kb] = (cur[0], cur[1])
        return delta

    def neu_pending(self) -> bool:
        return bool(self.pending_forget)

    def drain_neu(self, input_deltas: List[Delta]) -> Delta:
        parts = []
        if self.pending_forget:
            keys = [p[0] for p in self.pending_forget.values()]
            rows = [p[1] for p in self.pending_forget.values()]
            self.pending_forget = {}
            parts.append(
                _delta_from_rows(keys, [-1] * len(keys), rows, self.output_columns)
            )
        if any(len(d) for d in input_deltas):
            parts.append(self.process(input_deltas))
        return Delta.concat(parts, self.output_columns)

    def has_pending(self) -> bool:
        return bool(self.pending_forget)


class ExternalIndexEvaluator(Evaluator):
    """External index operator (reference ``external_index.rs:38``).

    In as-of-now mode (the default, reference ``use_external_index_as_of_now``) a query is
    answered once against the index state at arrival and never revisited. With
    ``asof_now=False`` live queries are *re-answered* whenever the index changes: the old
    reply is retracted and the fresh one emitted (reference full differential semantics of
    ``DataIndex.query``).

    With ``index_payload_columns`` in the config (``DataIndex.query_as_of_now`` with
    ``collapse_rows=True``) the operator also assembles the reply the user reads: one row
    per query holding ``_pw_index_reply_score`` and each payload (data) column as a tuple,
    best score first, equal scores in the order the matches joined the reply. It keeps
    every data row's payload beside its vector, so such a query needs no flatten / ix /
    groupby / join_left downstream, and its row is the one that chain gives a live
    query: a matched data row that changes while the reply is live re-emits the reply
    with the new values and the old scores (that match moves behind its equal scores),
    a deleted one reads ``ERROR``, and a matched key that is no ``Pointer`` (a row
    installed through the index's descriptor contract) reads ``None``. Live queries
    keep the chain: their replies are re-answered, and the chain's differential update
    is what tracks that."""

    # the data side replicates to every process (each holds the FULL index);
    # queries stay local and answer exactly against the replicated state —
    # the replicated-index pattern (queries never cross processes)
    CLUSTER_POLICIES = {0: "broadcast"}
    # the index mutates in place (possibly device-resident pages); pickling it
    # every commit for an undo record would dwarf the tail replay it avoids
    REWIND_SAFE = False
    # checkpoints cannot capture the device-resident index either: a restore
    # rebuilds it only through journal replay (PWA005 flags this under
    # persistence so the weaker recovery contract is visible at build time)
    SNAPSHOT_CAPTURE = False

    def __init__(self, node: pg.Node, runner: Any):
        super().__init__(node, runner)
        self.index = node.config["index_factory"].make_instance()
        # the last row emitted per query key: a retraction replays it
        self.replies = StateTable(self.output_columns)
        self.asof_now: bool = bool(self.node.config.get("asof_now", True))
        # kb -> (key, qvec, limit, filter) for re-answering mode
        self.live_queries: Dict[bytes, tuple] = {}
        # assembled replies: data key -> payload values, and per live reply its
        # query key and matches [(data key, score)] in the order they joined it
        self.payload: Dict[Any, tuple] = {}
        self.reply_order: Dict[bytes, tuple] = {}
        self.watchers: Dict[Pointer, set] = {}

    # -- elastic membership: the rebuildable-descriptor contract -------------
    #
    # The index's data side is broadcast (every rank holds the FULL index),
    # so its content is identical everywhere and a membership transition can
    # replicate it to the new topology from ONE export instead of refusing —
    # when the index can export one. The query side (replies, live_queries)
    # is keyed by query row key and partitions like any keyed state.

    def rebuild_supported(self) -> bool:
        """True when the backing index exports a rebuildable descriptor
        (keys + host vectors + filter data) — the membership preflight's
        alternative to the blanket device-resident refusal."""
        index = self.index
        return (
            getattr(index, "rebuild_descriptor", None) is not None
            and getattr(getattr(index, "store", None), "export_rows", None)
            is not None
        )

    def rebuild_descriptor(self) -> "Any | None":
        if not self.rebuild_supported():
            return None
        desc = self.index.rebuild_descriptor()
        if desc is not None and self._payload_refs() is not None:
            # the payload replicates with the index: a rank that joins answers
            # with the data rows, not with None
            desc = dict(desc, payload=dict(self.payload))
        return desc

    def install_rebuild_descriptor(self, desc: Any) -> None:
        if desc is not None:
            desc = dict(desc)
            payload = desc.pop("payload", None)
            self.index.install_rebuild_descriptor(desc)
            if payload is not None:
                self.payload = dict(payload)

    def reshard_check(self) -> "str | None":
        if self.rebuild_supported():
            return None
        return (
            "external index state lives outside the snapshot protocol "
            "(device-resident) and this index type exports no rebuildable "
            "descriptor"
        )

    def reshard_export(self, owner_of: Any, new_n: int) -> Dict[int, Any]:
        """Partition the QUERY-side state by row key (the index content
        itself rides the replicated descriptor, not the keyed export)."""
        from pathway_tpu.internals.keys import KEY_DTYPE

        out: Dict[int, Any] = {}
        for dest, part in self.replies.reshard_partition(owner_of).items():
            out.setdefault(dest, {})["replies"] = part
        for kb, (key, qvec, limit, flt) in self.live_queries.items():
            keys = np.frombuffer(kb, dtype=KEY_DTYPE)
            dest = int(np.asarray(owner_of(keys))[0])
            out.setdefault(dest, {}).setdefault("live_queries", {})[kb] = (
                key, _to_host(qvec), limit, flt,
            )
        for kb, entry in self.reply_order.items():
            dest = int(np.asarray(owner_of(np.frombuffer(kb, dtype=KEY_DTYPE)))[0])
            out.setdefault(dest, {}).setdefault("reply_order", {})[kb] = entry
        return out

    def reshard_import(self, payload: Any) -> None:
        payload = payload or {}
        part = payload.get("replies")
        if part is not None:
            keys, diffs, columns = part
            if len(keys):
                self.replies.apply(Delta(keys, diffs, columns))
        self.live_queries.update(payload.get("live_queries", {}))
        for kb, entry in payload.get("reply_order", {}).items():
            self.reply_order[kb] = entry
            self._watch(kb, entry[1])

    def _search_batch(
        self, vecs: List[Any], limits: List[int], filters: List[Any]
    ) -> List[List[tuple]]:
        if not vecs:
            return []
        if hasattr(self.index, "search_many"):
            return self.index.search_many(vecs, limits, filters)
        return [
            self.index.search(v, l, f) for v, l, f in zip(vecs, limits, filters)
        ]

    # -- the assembled reply (as-of-now, collapsed) ----------------------------

    def _payload_refs(self) -> "Dict[str, expr.ColumnReference] | None":
        return self.node.config.get("index_payload_columns")

    def _visible(self, key: Any, width: int) -> tuple:
        """What a reply shows of a matched key: its payload; ``None`` in every
        column for a key that is no ``Pointer`` (no data row); ``ERROR`` for a
        data row deleted while a reply holds it."""
        if not isinstance(key, Pointer):
            return (None,) * width
        row = self.payload.get(key)
        return (ERROR,) * width if row is None else row

    def _apply_payload(
        self, refs: Dict[str, Any], index_delta: Delta, resolver: Callable, ptrs: List[Any]
    ) -> Dict[Any, tuple]:
        """Keep each data row's payload as the index keeps its vector. Returns, for
        the touched keys a live reply holds, what they showed before this commit,
        in order of first appearance."""
        cols = [self._eval_expr(ref, index_delta, resolver) for ref in refs.values()]
        before: Dict[Any, tuple] = {}
        watchers, payload = self.watchers, self.payload
        for i, key in enumerate(ptrs):
            if key in watchers and key not in before:
                before[key] = self._visible(key, len(cols))
            if index_delta.diffs[i] > 0:
                payload[key] = tuple(c[i] for c in cols)
            else:
                payload.pop(key, None)
        return before

    def _watch(self, kb: bytes, order: List[tuple]) -> None:
        for data_key, _ in order:
            if isinstance(data_key, Pointer):
                self.watchers.setdefault(data_key, set()).add(kb)

    def _forget_reply(self, kb: bytes) -> None:
        entry = self.reply_order.pop(kb, None)
        if entry is None:
            return
        for data_key, _ in entry[1]:
            held = self.watchers.get(data_key)
            if held is not None:
                held.discard(kb)
                if not held:
                    del self.watchers[data_key]

    def _assemble(self, order: List[tuple], names: List[str]) -> dict:
        """One reply row: scores and payload tuples, best score first; equal scores
        keep the order the matches joined the reply (the rule of
        ``reducers.tuple(..., sort_by=-score)``, which orders the live path's reply)."""
        ranked = sorted(range(len(order)), key=lambda j: (-order[j][1], j))
        values = [self._visible(order[j][0], len(names)) for j in ranked]
        # float64 scores, as the live path's score column holds them
        row = {"_pw_index_reply_score": tuple(np.float64(order[j][1]) for j in ranked)}
        for c, name in enumerate(names):
            row[name] = tuple(v[c] for v in values)
        return row

    def _answer(self, key: Any, kb: bytes, matches: List[tuple], names: List[str]) -> dict:
        for data_key, _ in matches:
            if isinstance(data_key, Pointer) and data_key not in self.payload:
                raise KeyError(f"index reply: matched key {data_key!r} is not in the data table")
        order = list(matches)
        self.reply_order[kb] = (key, order)
        self._watch(kb, order)
        return self._assemble(order, names)

    def _reemit(
        self, before: Dict[Any, tuple], answered: set, names: List[str],
        out_keys: list, out_diffs: list, out_rows: list,
    ) -> int:
        """A matched data row that changed while its reply is live re-emits the
        reply with the new values and the old scores; the changed match moves
        behind its equal scores, as ix / groupby move it in the live path."""
        touched: Dict[bytes, None] = {}
        for data_key, old in before.items():
            if not _cells_differ(old, self._visible(data_key, len(names))):
                continue
            for kb in self.watchers.get(data_key, ()):
                if kb in answered:
                    continue
                order = self.reply_order[kb][1]
                j = next(j for j, m in enumerate(order) if m[0] == data_key)
                order.append(order.pop(j))
                touched[kb] = None
        n = 0
        for kb in touched:
            key, order = self.reply_order[kb]
            row = self._assemble(order, names)
            stored = self.replies.get_row(kb)
            if stored is not None:
                if not _cells_differ(
                    tuple(stored[c] for c in row), tuple(row.values())
                ):
                    continue
                out_keys.append(key)
                out_diffs.append(-1)
                out_rows.append(stored)
            out_keys.append(key)
            out_diffs.append(1)
            out_rows.append(row)
            n += 1
        return n

    def process(self, input_deltas: List[Delta]) -> Delta:
        index_delta, query_delta = input_deltas
        index_table, query_table = self.node.inputs
        index_changed = len(index_delta) > 0
        refs = self._payload_refs()
        names = list(refs) if refs is not None else []
        before: Dict[Any, tuple] = {}

        if len(index_delta):
            resolver = self._resolver_for(index_table, index_delta)
            vec_ref = self.node.config["index_column"]
            vectors = self._eval_expr(vec_ref, index_delta, resolver)
            filter_col = self.node.config.get("index_filter_data_column")
            filters = (
                self._eval_expr(filter_col, index_delta, resolver)
                if filter_col is not None
                else None
            )
            ptrs = keys_to_pointers(index_delta.keys)
            add_mask = index_delta.diffs > 0
            bulk_add = getattr(self.index, "add_many", None)
            if bulk_add is not None and add_mask.all():
                # pure-insert commit: one staged batch + one capacity jump
                bulk_add(
                    ptrs,
                    list(vectors),
                    list(filters) if filters is not None else None,
                )
            else:
                for i in range(len(index_delta)):
                    if add_mask[i]:
                        self.index.add(
                            ptrs[i], vectors[i], filters[i] if filters is not None else None
                        )
                    else:
                        self.index.remove(ptrs[i])
            if refs is not None:
                before = self._apply_payload(refs, index_delta, resolver, ptrs)

        out_keys, out_diffs, out_rows = [], [], []
        q_kbs: List[bytes] = []
        assembled = 0
        if len(query_delta):
            resolver = self._resolver_for(query_table, query_delta)
            qvecs = self._eval_expr(
                self.node.config["query_column"], query_delta, resolver
            )
            limit_col = self.node.config.get("query_responses_limit_column")
            limits = (
                self._eval_expr(limit_col, query_delta, resolver)
                if limit_col is not None
                else None
            )
            qfilter_col = self.node.config.get("query_filter_column")
            qfilters = (
                self._eval_expr(qfilter_col, query_delta, resolver)
                if qfilter_col is not None
                else None
            )
            q_kbs = key_bytes(query_delta.keys)
            ins = [i for i in range(len(query_delta)) if query_delta.diffs[i] > 0]
            ins_replies = self._search_batch(
                [qvecs[i] for i in ins],
                [int(limits[i]) if limits is not None else 1 for i in ins],
                [qfilters[i] if qfilters is not None else None for i in ins],
            )
            reply_of = dict(zip(ins, ins_replies))
            for i in range(len(query_delta)):
                kb = q_kbs[i]
                if query_delta.diffs[i] > 0:
                    limit = int(limits[i]) if limits is not None else 1
                    flt = qfilters[i] if qfilters is not None else None
                    out_keys.append(query_delta.keys[i])
                    out_diffs.append(1)
                    if refs is not None:
                        out_rows.append(
                            self._answer(query_delta.keys[i], kb, reply_of[i], names)
                        )
                        assembled += 1
                    else:
                        out_rows.append({"_pw_index_reply": tuple(reply_of[i])})
                    if not self.asof_now:
                        self.live_queries[kb] = (
                            query_delta.keys[i],
                            qvecs[i],
                            limit,
                            flt,
                        )
                else:
                    self.live_queries.pop(kb, None)
                    self._forget_reply(kb)
                    stored = self.replies.get_row(kb)
                    if stored is not None:
                        out_keys.append(query_delta.keys[i])
                        out_diffs.append(-1)
                        out_rows.append(stored)

        if not self.asof_now and index_changed and self.live_queries:
            answered = set(key_bytes(query_delta.keys))
            live = [
                (kb, entry)
                for kb, entry in self.live_queries.items()
                if kb not in answered
            ]
            live_replies = self._search_batch(
                [entry[1] for _, entry in live],
                [entry[2] for _, entry in live],
                [entry[3] for _, entry in live],
            )
            for (kb, (key, qvec, limit, flt)), matches in zip(live, live_replies):
                reply = tuple(matches)
                stored = self.replies.get_row(kb)
                if stored is not None and stored["_pw_index_reply"] == reply:
                    continue
                if stored is not None:
                    out_keys.append(key)
                    out_diffs.append(-1)
                    out_rows.append(stored)
                out_keys.append(key)
                out_diffs.append(1)
                out_rows.append({"_pw_index_reply": reply})
        reemits = (
            self._reemit(before, set(q_kbs), names, out_keys, out_diffs, out_rows)
            if before
            else 0
        )
        if assembled or reemits:
            telemetry.stage_add_many(
                {"index.replies_assembled": assembled, "index.reply_reemits": reemits}
            )
        delta = _delta_from_rows(out_keys, out_diffs, out_rows, self.output_columns)
        self.replies.apply(delta)
        return delta


class GradualBroadcastEvaluator(Evaluator):
    """Broadcast a (lower, value, upper) threshold to every row with per-key
    staggering and hysteresis (reference ``gradual_broadcast.rs``): each row's
    ``apx_value`` sits at its own point of the band — apx(k) = lower +
    (upper - lower) * frac(key) — and only re-emits when a threshold update moves
    the band past the row's stored value, so a drifting threshold updates rows
    progressively instead of retracting the whole table each tick."""

    # rows are row-local (apx derives from the row's own key), but the
    # threshold band typically comes from a GLOBAL reduce living on one owner
    # process — replicate it so every process applies the same band
    CLUSTER_POLICIES = {1: "broadcast"}

    def __init__(self, node: pg.Node, runner: Any):
        super().__init__(node, runner)
        self.rows = StateTable(node.inputs[0].column_names())
        self.apx: Dict[bytes, Any] = {}
        self.threshold: tuple | None = None

    @staticmethod
    def _frac(keys: np.ndarray) -> np.ndarray:
        return keys["lo"].astype(np.float64) / float(2**64)

    def _candidate(self, keys: np.ndarray) -> np.ndarray:
        lower, _value, upper = self.threshold
        return lower + (upper - lower) * self._frac(keys)

    def process(self, input_deltas: List[Delta]) -> Delta:
        from pathway_tpu.internals.keys import key_bytes

        rows_delta, thr_delta = input_deltas
        out_parts: List[tuple] = []  # (keys, diffs, cols dict incl apx)

        new_threshold = self.threshold
        if len(thr_delta):
            ins = np.nonzero(thr_delta.diffs > 0)[0]
            if len(ins):
                i = int(ins[-1])
                cfg = self.node.config
                new_threshold = (
                    thr_delta.columns[cfg["lower"]][i],
                    thr_delta.columns[cfg["value"]][i],
                    thr_delta.columns[cfg["upper"]][i],
                )

        def emit(delta: Delta, apx_vals: np.ndarray, sign: int) -> None:
            cols = {c: delta.columns[c] for c in self.rows.column_names}
            cols["apx_value"] = apx_vals
            out_parts.append(
                Delta(delta.keys, np.full(len(delta), sign, dtype=np.int64), cols)
            )

        if len(rows_delta):
            ret = rows_delta.select(rows_delta.diffs < 0)
            if len(ret):
                kbs = key_bytes(ret.keys)
                olds = np.array([self.apx.pop(kb, None) for kb in kbs], dtype=object)
                emit(ret, olds, -1)
            self.rows.apply(rows_delta)
            ins = rows_delta.select(rows_delta.diffs > 0)
            if len(ins):
                if self.threshold is None and new_threshold is None:
                    apx = np.zeros(len(ins), dtype=np.float64)
                else:
                    save, self.threshold = self.threshold, (
                        new_threshold or self.threshold
                    )
                    apx = self._candidate(ins.keys)
                    self.threshold = save
                for kb, a in zip(key_bytes(ins.keys), apx):
                    self.apx[kb] = a
                emit(ins, np.asarray(apx, dtype=np.float64), 1)

        if new_threshold is not None and new_threshold != self.threshold:
            self.threshold = new_threshold
            lower, _value, upper = new_threshold
            snap = self.rows.snapshot()
            if len(snap):
                kbs = key_bytes(snap.keys)
                stored = np.array([self.apx.get(kb) for kb in kbs], dtype=np.float64)
                cand = self._candidate(snap.keys)
                # hysteresis: rows whose stored value still sits inside the new
                # band keep it; only rows the band moved past re-emit
                move = (stored < lower) | (stored > upper)
                move &= stored != cand
                idx = np.nonzero(move)[0]
                if len(idx):
                    moving = snap.select(idx)
                    emit(moving, stored[idx], -1)
                    emit(moving, cand[idx], 1)
                    for i in idx.tolist():
                        self.apx[kbs[i]] = cand[i]

        if not out_parts:
            return Delta.empty(self.output_columns)
        return Delta.concat(out_parts, self.output_columns)


class OutputEvaluator(Evaluator):
    def __init__(self, node: pg.Node, runner: Any):
        super().__init__(node, runner)
        self.callback = node.config.get("callback")
        self.batch_callback = node.config.get("batch_callback")
        self.on_end = node.config.get("on_end")
        self.on_time_end = node.config.get("on_time_end")
        self.on_error = node.config.get("on_error")
        self.input_columns = node.inputs[0].column_names()

    def notify_failure(self, exc: BaseException) -> None:
        """The run is failing: sinks distinguishing failure from a clean end
        (ExportedTable) hear about it before finish() fires their on_end."""
        if self.on_error is not None and not getattr(self, "_on_error_fired", False):
            self._on_error_fired = True
            self.on_error(exc)

    def process(self, input_deltas: List[Delta]) -> Delta:
        (delta,) = input_deltas
        if (
            getattr(self.runner, "_inject", None) is not None
            and not getattr(self.runner, "replay_outputs", True)
        ):
            return Delta.empty([])  # journal replay with silent sinks
        if self.batch_callback is not None and len(delta):
            # vectorized delivery: one call per commit, raw columnar arrays
            self.batch_callback(
                delta.keys,
                delta.diffs,
                {c: delta.columns[c] for c in self.input_columns},
                self.runner.current_time,
            )
        if self.callback is not None and len(delta):
            ptrs = keys_to_pointers(delta.keys)
            time = self.runner.current_time
            names = self.input_columns
            from pathway_tpu.io._utils import columns_to_pylists

            col_map = columns_to_pylists(delta.columns, names)
            cols = [col_map[c] for c in names]
            additions = (delta.diffs > 0).tolist()
            callback = self.callback
            for ptr, is_add, *vals in zip(ptrs, additions, *cols):
                callback(
                    key=ptr, row=dict(zip(names, vals)), time=time, is_addition=is_add
                )
        if self.on_time_end is not None and len(delta):
            # the commit's batch is fully delivered: its time is closed (reference
            # on_time_end markers — AsyncTransformer flushes at time boundaries)
            self.on_time_end(self.runner.current_time)
        return Delta.empty([])

    def notify_stream_end(self) -> None:
        if self.on_end is not None and not getattr(self, "_on_end_fired", False):
            self._on_end_fired = True
            self.on_end()

    def finish(self) -> None:
        self.notify_stream_end()


def _cells_differ(old: tuple, new: tuple) -> bool:
    """Whether two rows of cells differ (ndarray and ``ERROR`` cells allowed)."""
    return bool(_col_neq(objarray(list(old)), objarray(list(new))).any())


def _delta_from_rows(
    keys: Any, diffs: List[int], rows: List[dict], column_names: List[str]
) -> Delta:
    if len(rows) == 0:
        return Delta.empty(column_names)
    if isinstance(keys, list):
        if keys and isinstance(keys[0], Pointer):
            keys = pointers_to_keys(keys)
        else:
            arr = np.empty(len(keys), dtype=KEY_DTYPE)
            for i, k in enumerate(keys):
                arr[i] = k
            keys = arr
    columns = {
        name: ee._tidy(objarray([r[name] for r in rows]))
        for name in column_names
    }
    return Delta(keys, np.array(diffs, dtype=np.int64), columns)


def wire_cluster_defaults(cls: type, policy: "str | None" = None) -> None:
    """Install the Evaluator cluster protocol on an evaluator class defined
    outside this module (iterate, row transformers): plumbing defaults plus an
    optional constant input policy for every input (e.g. ``"root"`` to
    centralize). One place to extend when the protocol grows."""
    cls._cluster_policies = ()
    cls._cluster_barrier = False
    cls.CLUSTER_POLICIES = {}
    if policy is None:
        cls.cluster_input_policy = Evaluator.cluster_input_policy
    else:
        cls.cluster_input_policy = lambda self, idx, _p=policy: _p


EVALUATORS: Dict[type, type] = {
    pg.InputNode: InputEvaluator,
    pg.RowwiseNode: RowwiseEvaluator,
    pg.FilterNode: FilterEvaluator,
    pg.ReindexNode: ReindexEvaluator,
    pg.ConcatNode: ConcatEvaluator,
    pg.GroupbyNode: GroupbyEvaluator,
    pg.DeduplicateNode: DeduplicateEvaluator,
    pg.JoinNode: JoinEvaluator,
    pg.UpdateRowsNode: UpdateRowsEvaluator,
    pg.UpdateCellsNode: UpdateCellsEvaluator,
    pg.IntersectNode: IntersectEvaluator,
    pg.DifferenceNode: DifferenceEvaluator,
    pg.RestrictNode: RestrictEvaluator,
    pg.HavingNode: HavingEvaluator,
    pg.WithUniverseOfNode: WithUniverseOfEvaluator,
    pg.FlattenNode: FlattenEvaluator,
    pg.IxNode: IxEvaluator,
    pg.SortNode: SortEvaluator,
    pg.SortedIndexNode: SortedIndexEvaluator,
    pg.RemoveErrorsNode: RemoveErrorsEvaluator,
    pg.AsofNowUpdateNode: AsofNowEvaluator,
    pg.BufferNode: BufferEvaluator,
    pg.ForgetNode: ForgetEvaluator,
    pg.FreezeNode: FreezeEvaluator,
    pg.ExternalIndexNode: ExternalIndexEvaluator,
    pg.GradualBroadcastNode: GradualBroadcastEvaluator,
    pg.OutputNode: OutputEvaluator,
}


def _register_iterate() -> None:
    from pathway_tpu.internals.iterate import IterateEvaluator, IterateResultEvaluator

    EVALUATORS[pg.IterateNode] = IterateEvaluator
    EVALUATORS[pg.IterateResultNode] = IterateResultEvaluator


_register_iterate()
