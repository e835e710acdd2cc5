"""The commit loop — graph execution driver.

Parity: reference ``pw.run`` path (``internals/run.py`` → ``GraphRunner`` →
``run_with_new_dataflow_graph``'s worker loop ``dataflow.rs:5596-5650``). Instead of timely's
``step_or_park``, each commit gathers one batch per source, pushes deltas through the operator
DAG in topological order, and delivers outputs. Timestamps are even integers (data times), as in
the reference's alt/neu scheme (``timestamp.rs:20``).
"""

from __future__ import annotations

import os
import time as time_mod
from typing import Any, Dict, List, Optional

import numpy as np

from pathway_tpu.engine import tracing as _tracing
from pathway_tpu.engine.columnar import Delta, StateTable
from pathway_tpu.engine.profile import CommitProfile
from pathway_tpu.engine.profile import autoscale_signals as _autoscale_signals
from pathway_tpu.internals import parse_graph as pg


class GraphRunner:
    def __init__(self, graph: Any = None):
        self.graph = graph if graph is not None else pg.G
        self.states: Dict[int, StateTable] = {}
        self.evaluators: Dict[int, Any] = {}
        self.current_time = 0
        self._commit = 0
        self._sources: List[tuple] = []
        self._nodes: List[pg.Node] = []
        self._monitor: Any = None
        self._ready = False
        self.draining = False
        self._step_counts: Dict[int, int] = {}
        self._persistence: Any = None
        self._inject: Optional[Dict[int, Delta]] = None  # journal replay injection
        self._input_deltas: Dict[int, Delta] = {}
        self._graph_sig = ""
        self._snapshot_interval_s = 0.0
        self._last_checkpoint = time_mod.monotonic()
        self._warned_unpicklable = False
        self.prober_stats: Any = None
        self._output_rows_this_commit = 0
        self._http_server: Any = None
        self.replay_outputs = True
        self._shared_nonroot = False  # transparent-threads worker with rank > 0
        self._substep_deltas: Dict[int, Delta] = {}
        self._materialized: set = set()
        self._materialize_all = False  # nested iterate runners read states directly
        self._cluster: Any = None  # multi-process exchange (parallel/cluster.py)
        self._metrics: Any = None  # OTel MetricsRecorder (engine/telemetry.py)
        self._chaos: Any = None  # fault injection (internals/chaos.py), None when off
        self._rank = 0
        self._supervise_dir: Any = None  # PATHWAY_SUPERVISE_DIR (spawn supervisor)
        self._last_status_write = 0.0
        # surgical single-rank restart (epoch fencing; parallel/cluster.py)
        self._surgical = False  # PATHWAY_RESTART_MODE=surgical (spawn supervisor)
        self._rejoin_carry: Dict[int, Delta] = {}  # in-flight inputs saved over a fence
        self._input_deltas_commit = -1  # commit the current _input_deltas belong to
        self._rejoins = 0
        self._last_rejoin_s: "float | None" = None
        self._rejoin_state = "running"  # "running" | "fencing" | "rejoining"
        # metrics plane (engine/profile.py): per-operator commit profiles +
        # the crash/stall flight recorder; None in nested iterate runners
        self._profiler: Any = None
        self._recorder: Any = None
        self._profile_ops: "List[tuple] | None" = None
        self._last_commit_profile: "CommitProfile | None" = None
        # whole-commit fusion (engine/fusion.py): the substep schedule with
        # operator chains collapsed into compiled ChainPrograms; None = stock
        # per-node dispatch (PATHWAY_FUSION=off, nested runners, nothing fuses)
        self._fusion_schedule: "List[Any] | None" = None
        # one AnalysisContext per runner, shared by the lint gate and the
        # fusion planner (building it twice = two full DAG walks per pw.run)
        self._analysis_ctx: Any = None
        # coordinated cluster checkpoints (persistence/engine.py manifest
        # protocol) + incremental rewind (undo record + mesh serve log)
        self._ckpt_interval_s = 0.0  # 0 = coordinated checkpoints off
        self._ckpt_compact = True  # PATHWAY_CHECKPOINT_COMPACT=0 disables
        self._ckpt_disabled_reason: "str | None" = None
        self._manifest_commit: "int | None" = None  # last durable manifest
        self._undo_depth = 0  # PATHWAY_UNDO_RING_DEPTH; 0 = rewind rung off
        self._undo_max_bytes = 0  # PATHWAY_UNDO_MAX_STATE_BYTES; 0 = unbounded
        self._undo_current: "Dict[str, Any] | None" = None  # in-flight record
        # adaptive rewind-cost guard: EWMA of per-commit undo-capture seconds
        # vs whole-commit seconds — state_dict() re-pickles every touched
        # operator's state each commit, so a large-state graph under the byte
        # cap could still pay more for the rung than the tail replay it avoids
        self._undo_capture_ewma = 0.0
        self._undo_commit_ewma = 0.0
        self._undo_armed_commits = 0
        self._rewind_safe = True  # graph has no drain-sensitive operators
        # elastic mesh membership (parallel/membership.py): grow/shrink the
        # cluster under traffic via an epoch-fenced MEMBERSHIP_CHANGE
        # transition at a quiesced commit boundary
        self._membership_state = "stable"  # stable|joining|draining|resharding
        self._target_workers: "int | None" = None
        self._member_pending: Any = None  # agreed directive awaiting readiness
        self._member_all_ready = False
        self._member_done_gen = -1  # newest applied/refused/failed generation
        self._member_refused: "tuple | None" = None  # (gen, reason)
        # structured per-node preflight refusals ({"node","kind","reason"})
        # from the last plan this rank computed — /healthz + status file
        self._member_refusal_nodes: "list[dict]" = []
        self._member_committed_gen: "int | None" = None  # rank-0 manifest marker
        self._member_attempts = 0  # transient-abort retries of the pending gen
        self._member_in_flight = False  # transition running (no surgical rejoin)
        self._membership_left = False  # this rank drained away (leaver)
        self._member_join_gen: "int | None" = None  # joiner: generation joined
        self._mismatch_workers: "int | None" = None  # store-vs-run worker count
        # autoscale observability (parallel/autoscaler.py): the supervisor
        # exports its controller state to the supervise dir; workers mirror it
        # into /healthz + the flight recorder so flap-locks and decisions are
        # visible from inside the cluster
        self._autoscale_state: "Dict[str, Any] | None" = None
        self._autoscale_seen_gen = -1
        self._autoscale_last_read = 0.0

    def state_of(self, node: pg.Node) -> StateTable:
        if node.id not in self._materialized:
            raise KeyError(
                f"state of node {node.id} ({node.kind}) was not materialized; "
                "the static reference analysis in _compute_materialized missed a "
                "consumer — please report"
            )
        return self.states[node.id]

    def _compute_materialized(self) -> set:
        """Node ids whose output state must be kept materialized.

        The reference arranges every collection inside DD; here a node's StateTable
        is upkept only when something reads it: cross-table column references
        (``Evaluator._resolver_for``), ``ix`` targets, checkpoint snapshots (any
        persistence), and ``iterate`` graphs (nested runners read states directly).
        Everything else flows through as deltas only.
        """
        all_ids = {n.id for n in self._nodes}
        if self._persistence is not None or self._materialize_all:
            return all_ids
        needed: set = set()
        from pathway_tpu.internals.expression import ColumnExpression

        def walk_value(value: Any, input_tables: list) -> None:
            if isinstance(value, ColumnExpression):
                for ref in value._column_refs:
                    if all(ref.table is not t for t in input_tables):
                        needed.add(ref.table._node.id)
            elif isinstance(value, dict):
                for v in value.values():
                    walk_value(v, input_tables)
            elif isinstance(value, (list, tuple)):
                for v in value:
                    walk_value(v, input_tables)

        def has_cross_ref(node: pg.Node) -> bool:
            found = [False]

            def walk(value: Any) -> None:
                if found[0]:
                    return
                if isinstance(value, ColumnExpression):
                    for ref in value._column_refs:
                        if all(ref.table is not t for t in node.inputs):
                            found[0] = True
                            return
                elif isinstance(value, dict):
                    for v in value.values():
                        walk(v)
                elif isinstance(value, (list, tuple)):
                    for v in value:
                        walk(v)

            walk(node.config)
            return found[0]

        for node in self._nodes:
            if isinstance(node, (pg.IterateNode, pg.IterateResultNode)):
                return all_ids
            input_tables = list(node.inputs)
            walk_value(node.config, input_tables)
            if isinstance(node, pg.RowwiseNode) and has_cross_ref(node):
                # cross-table refs make this a LIVE dependency: the evaluator
                # re-derives affected rows from its input's state and suppresses
                # no-ops against its own output state — both must materialize
                # (checked per node: the referenced table may already be in
                # `needed` from another consumer)
                needed.add(node.inputs[0]._node.id)
                needed.add(node.id)
            if isinstance(node, pg.IxNode) and len(node.inputs) > 1:
                needed.add(node.inputs[1]._node.id)
        return needed & all_ids

    def current_delta_of(self, node: pg.Node) -> Optional[Delta]:
        """The delta ``node`` emitted in the current substep (None before it ran).
        Lets evaluators resolve retraction rows against retracted upstream values."""
        return self._substep_deltas.get(node.id)

    # The cluster blocklist is EMPTY: every operator kind runs multi-process.
    # Kinds either exchange (rowkey/custom routing), centralize on process 0
    # (sort, time behaviors, and — since r5 — iterate's nested fixpoint and
    # row transformers' pointer-chasing context, which recompute from full
    # state that cannot be co-partitioned), or replicate (ix/external_index
    # broadcast their lookup side) — see ``Evaluator.CLUSTER_POLICIES``.
    _CLUSTER_UNSUPPORTED: set = set()

    def setup(self, monitoring_level: Any = None, persistence_config: Any = None) -> None:
        # hot-path modules load now, not inside the first timed commit
        from pathway_tpu.engine import index as _index  # noqa: F401
        from pathway_tpu.ops import segment as _segment  # noqa: F401
        from pathway_tpu.engine.evaluators import EVALUATORS
        from pathway_tpu.internals.chaos import get_chaos
        from pathway_tpu.internals.config import get_pathway_config as _get_cfg
        from pathway_tpu.parallel.cluster import get_cluster

        self._cluster = None if self._materialize_all else get_cluster()
        self._chaos = None if self._materialize_all else get_chaos()
        self._rank = _get_cfg().process_id
        import os as _os

        self._supervise_dir = None if self._materialize_all else _os.environ.get(
            "PATHWAY_SUPERVISE_DIR"
        )
        self._surgical = (
            not self._materialize_all
            and _os.environ.get("PATHWAY_RESTART_MODE") == "surgical"
        )
        if not self._materialize_all:
            # nested iterate runners share the outer commit's clock; profiling
            # them would double-count their wall time under the outer operator
            from pathway_tpu.engine import profile as _profile

            if _profile.profiling_enabled():
                self._profiler = _profile.get_profiler()
            self._recorder = _profile.get_flight_recorder()
            self._recorder.configure(
                rank=self._rank, default_dir=self._supervise_dir
            )
            # the tracing plane shares the recorder's rank/dump-dir config so
            # trace-rank-N.jsonl lands beside flight-rank-N.json
            _tracing.get_tracer().configure(
                rank=self._rank, default_dir=self._supervise_dir
            )
        if self._cluster is not None:
            bad = sorted(
                {n.kind for n in self.graph.nodes if n.kind in self._CLUSTER_UNSUPPORTED}
            )
            if bad:
                raise NotImplementedError(
                    f"operators {bad} keep per-key state that is not co-partitioned "
                    "across spawn processes; run this pipeline single-process "
                    "(spawn -n 1) or restructure around groupby/join"
                )
            from pathway_tpu.internals.expression import ColumnExpression

            def refs_in(node: pg.Node, value: Any) -> list:
                found: list = []

                def walk(v: Any) -> None:
                    if isinstance(v, ColumnExpression):
                        for ref in v._column_refs:
                            if all(ref.table is not t for t in node.inputs):
                                found.append(ref.table)
                    elif isinstance(v, dict):
                        for x in v.values():
                            walk(x)
                    elif isinstance(v, (list, tuple)):
                        for x in v:
                            walk(x)

                walk(value)
                return found

            # PLACEMENT analysis: which process holds each node's rows. Cross-
            # table references resolve against locally materialized state, so a
            # reference is legal exactly when both sides are co-located:
            #   ("own",)     — rows live at shard_of(row_key): outputs of
            #                  row-key / group-key exchanges through
            #                  key-preserving chains (two such tables with the
            #                  same universe are co-located by construction)
            #   ("ingest",)  — never exchanged: rows sit where they entered
            #   ("root",)    — centralized on process 0
            #   ("at", id)   — produced at exchange/key-derivation point `id`
            #   ("mixed",id) — inputs disagree; matches nothing but itself
            from pathway_tpu.engine.evaluators import EVALUATORS, Evaluator

            _dummy_cache: dict = {}

            def class_policies(node: pg.Node) -> tuple:
                cls = EVALUATORS.get(type(node))
                if cls is None:
                    return tuple(None for _ in node.inputs)
                import types as _types

                dummy = _dummy_cache.get(cls)
                if dummy is None:
                    dummy = _types.SimpleNamespace(CLUSTER_POLICIES=cls.CLUSTER_POLICIES)
                    _dummy_cache[cls] = dummy
                out = []
                for i in range(len(node.inputs)):
                    try:
                        out.append(cls.cluster_input_policy(dummy, i))
                    except Exception:
                        out.append("custom")  # stateful override: assume it routes
                return tuple(out)

            _KEY_PRESERVING = {
                "rowwise", "filter", "update_rows", "update_cells", "intersect",
                "difference", "restrict", "having", "with_universe_of",
                "remove_errors", "concat", "output", "asof_now_update",
            }
            _placement_cache: dict = {}

            def placement(node: pg.Node) -> tuple:
                got = _placement_cache.get(node.id)
                if got is not None:
                    return got
                if isinstance(node, pg.InputNode):
                    p: tuple = ("ingest",)
                else:
                    pol = class_policies(node)
                    if "root" in pol:
                        p = ("root",)
                    elif node.kind == "groupby":
                        # routed by group key == output row key
                        p = ("own",)
                    elif node.kind == "join" or "custom" in pol:
                        # exchanged by a non-output key (join key, instance):
                        # rows land at that key's owner, a place all its own
                        p = ("at", node.id)
                    elif "rowkey" in pol:
                        p = ("own",)
                    else:
                        contrib = [
                            placement(inp._node)
                            for i, inp in enumerate(node.inputs)
                            if pol[i] != "broadcast"
                        ] or [placement(inp._node) for inp in node.inputs]
                        if not contrib:
                            p = ("ingest",)
                        elif all(c == contrib[0] for c in contrib):
                            p = contrib[0]
                        else:
                            p = ("mixed", node.id)
                        if p == ("own",) and node.kind not in _KEY_PRESERVING:
                            # key-changing op over key-owned rows: rows stay put
                            # but no longer sit at their (new) key's owner
                            p = ("at", node.id)
                _placement_cache[node.id] = p
                return p

            # nested-graph kinds hold inner-table expressions in their config;
            # the whole nested graph runs where the evaluator runs (root), so
            # those are not cross-process references
            _NESTED_KINDS = {"iterate", "iterate_result", "row_transformer", "row_transformer_result"}
            for node in self.graph.nodes:
                if node.kind in _NESTED_KINDS:
                    continue
                if node.kind == "groupby":
                    # the two expression sites evaluate in DIFFERENT frames:
                    # grouping expressions run PRE-exchange (rows still at the
                    # input's placement), reducer args run POST-exchange (rows
                    # at the group key's owner, where no foreign table's shard
                    # can be assumed present)
                    config_no_grouping = {
                        k: v for k, v in node.config.items() if k != "grouping"
                    }
                    if refs_in(node, config_no_grouping):
                        raise NotImplementedError(
                            f"node {node.id} (groupby) reducer arguments reference "
                            "another table's state, which is evaluated after the "
                            "group-key exchange where that state is not resident — "
                            "inline the referenced columns before the groupby "
                            "(select/join them onto the input) or run single-process"
                        )
                    refs = refs_in(node, node.config.get("grouping"))
                    own = placement(node.inputs[0]._node)
                else:
                    refs = refs_in(node, node.config)
                    own = placement(node)
                for ref_table in refs:
                    if placement(ref_table._node) != own:
                        raise NotImplementedError(
                            f"node {node.id} ({node.kind}) cross-references table "
                            f"{ref_table._node.id}, whose rows are partitioned "
                            f"differently across spawn processes "
                            f"({placement(ref_table._node)} vs {own}); the "
                            "referenced state cannot be resolved remotely — "
                            "inline the referenced columns before the exchange "
                            "(select/join them onto the input) or run "
                            "single-process"
                        )

        self._nodes = list(self.graph.nodes)
        for node in self._nodes:
            if node.id in self.evaluators:
                continue
            evaluator_cls = EVALUATORS.get(type(node))
            if evaluator_cls is None:
                raise NotImplementedError(f"no evaluator for node kind {node.kind!r}")
            self.evaluators[node.id] = evaluator_cls(node, self)
            columns = node.output.column_names() if node.output is not None else []
            self.states[node.id] = StateTable(columns)
        shared_threads = self._bind_cluster_policies()
        self._sources = [
            (node, self.evaluators[node.id])
            for node in self._nodes
            if isinstance(node, pg.InputNode)
        ]
        self._shared_nonroot = shared_threads and self._cluster.me != 0
        if self._shared_nonroot:
            # transparent-threads mode, rank > 0: the ONE shared set of source
            # objects is polled by rank 0 alone (rows reach this rank through
            # the key exchange); touching them here would double-ingest
            self._sources = []
        replay_frames = []
        ckpt_floor = 0
        if persistence_config is not None and persistence_config.backend is not None:
            from pathway_tpu.persistence.engine import PersistenceManager

            self._persistence = PersistenceManager(persistence_config)
            # "silent_replay" keeps external sinks from re-receiving already-delivered
            # rows on resume (in-process subscribers then rebuild state themselves)
            self.replay_outputs = persistence_config.persistence_mode != "silent_replay"
            sig = self.graph.sig()
            self._graph_sig = sig
            self._snapshot_interval_s = (
                getattr(persistence_config, "snapshot_interval_ms", 0) or 0
            ) / 1000.0
            if self._cluster is not None:
                if self._persistence.load_checkpoint(sig) is not None:
                    # an UNVERSIONED per-shard snapshot can only come from a
                    # single-process run whose journal was compacted at an
                    # unsynchronized commit; resuming it under spawn would
                    # silently double-count exchanged rows. (Worker-count
                    # mismatches are also refused by the store-wide meta.)
                    raise NotImplementedError(
                        "this persistence store contains an operator snapshot "
                        "(written by a single-process run); resuming it under "
                        "spawn -n N is not supported — restart single-process or "
                        "start the cluster from a fresh store"
                    )
                # coordinated cluster checkpoints: cadence from
                # PATHWAY_CHECKPOINT_INTERVAL_S (fallback: the config's
                # snapshot interval); the checkpoint marker rides the
                # per-commit neu allgather so all ranks snapshot at ONE commit
                from pathway_tpu.internals.config import env_float as _env_float

                if self._persistence.supports_cluster_checkpoints:
                    self._ckpt_interval_s = max(
                        0.0,
                        _env_float(
                            "PATHWAY_CHECKPOINT_INTERVAL_S", self._snapshot_interval_s
                        ),
                    )
                self._ckpt_compact = (
                    _os.environ.get("PATHWAY_CHECKPOINT_COMPACT", "1") != "0"
                )
                self._snapshot_interval_s = 0.0  # the single-process path stays off
                checkpoint = None
                joiner = _os.environ.get("PATHWAY_MEMBERSHIP_JOIN") == "1"
                if joiner:
                    # a grow-transition joiner: its catch-up basis is the
                    # membership manifest + handoff fragments + journal tail
                    # (never a full-history replay) — wait for the members to
                    # commit it
                    self._membership_state = "joining"
                    self._target_workers = self._cluster.n
                    manifest = self._await_membership_manifest(sig)
                else:
                    manifest = self._persistence.load_cluster_manifest(sig)
                # (a joiner's manifest comes from _await_membership_manifest,
                # which returns only membership manifests or raises typed —
                # the never-committed case is reported there)
                if manifest is not None:
                    base = int(manifest["commit_id"])
                    self._manifest_commit = base
                    membership = manifest.get("membership")
                    if joiner:
                        self._member_join_info = membership
                    if membership:
                        # membership manifest: the per-rank "snapshot" is the
                        # set of handoff fragments addressed to this rank
                        frags = self._persistence.load_reshard_fragments(
                            sig, base, self._rank, int(membership["from_n"])
                        )
                        checkpoint = (base, ("fragments", frags, membership))
                    else:
                        checkpoint = (
                            base,
                            self._persistence.load_cluster_snapshot(sig, base),
                        )
                    ckpt_floor = base + 1
            else:
                checkpoint = self._persistence.load_checkpoint(sig)
            if (
                self._surgical
                and self._cluster is not None
                and getattr(self._cluster, "supports_rejoin", False)
            ):
                # incremental rewind (fence rung 1): keep per-commit undo
                # records + the mesh serve log so a fenced survivor undoes only
                # the interrupted commit. Drain-sensitive operators emit on a
                # live-only signal replay cannot reproduce, so graphs holding
                # them skip the rewind rung (rung 2 stays exact).
                self._undo_depth = getattr(self._cluster, "commit_log_depth", 0)
                self._undo_max_bytes = int(
                    _env_float("PATHWAY_UNDO_MAX_STATE_BYTES", 64 * 1024 * 1024)
                )
                self._rewind_safe = all(
                    getattr(ev, "REWIND_SAFE", True)
                    for ev in self.evaluators.values()
                )
            replay_frames = self._persistence.load_journal(sig)
            self._persistence.open_for_append(sig)
            restore_frames = list(replay_frames)
            if checkpoint is not None:
                base_commit, blob = checkpoint
                if isinstance(blob, tuple) and blob[0] == "fragments":
                    # membership-manifest restore: merge the handoff
                    # fragments addressed to this rank (they are complete,
                    # disjoint partitions — together they ARE this rank's
                    # snapshot at the transition commit)
                    from pathway_tpu.parallel.membership import (
                        import_fragments,
                        merge_fragment_sources,
                    )

                    _frags = blob[1]
                    import_fragments(self, _frags)
                    self._deliver_sink_snapshots()
                    src_offsets, src_deltas = merge_fragment_sources(_frags)
                    park = self._persistence.load_source_park(sig)
                    if park:
                        # a drained leaver's rank-local source continuation:
                        # this joiner reuses its rank id and must not
                        # re-ingest what the old incarnation contributed
                        for nid, offs in park.get("offsets", {}).items():
                            src_offsets.setdefault(int(nid), {}).update(offs)
                    blob_sources = {
                        "source_offsets": src_offsets,
                        "source_deltas": src_deltas,
                    }
                else:
                    self._load_checkpoint_state(blob)
                    blob_sources = {
                        "source_offsets": blob["source_offsets"],
                        "source_deltas": blob["source_deltas"],
                    }
                self._commit = base_commit + 1
                # frames ≤ the checkpointed commit are subsumed by it (compaction may
                # have crashed before truncating the journal)
                replay_frames = [f for f in replay_frames if f[0] > base_commit]
                if self._cluster is not None:
                    import logging

                    # the bounded-recovery claim made observable: a replacement
                    # rank names its base manifest + the tail it still replays
                    logging.getLogger("pathway_tpu").warning(
                        "rank %d: cold-starting from %s "
                        "at commit %d (+%d journal tail frame(s))",
                        self._rank,
                        "membership manifest + handoff fragments"
                        if isinstance(blob, tuple)
                        else "cluster checkpoint manifest",
                        base_commit, len(replay_frames),
                    )
                synthetic = (
                    base_commit,
                    {},
                    {
                        nid: {
                            **blob_sources["source_offsets"].get(nid, {}),
                            **(
                                {"state_deltas": blob_sources["source_deltas"][nid]}
                                if blob_sources["source_deltas"].get(nid)
                                else {}
                            ),
                        }
                        for nid in set(blob_sources["source_offsets"])
                        | set(blob_sources["source_deltas"])
                    },
                )
                restore_frames = [synthetic, *replay_frames]
            if restore_frames:
                self._restore_sources(restore_frames)
        self._materialized = self._compute_materialized()
        self._build_fusion()
        for node, evaluator in self._sources:
            node.config["source"].on_start()
        self._monitor = _make_monitor(monitoring_level, self._nodes)
        self._ready = True
        # replay journaled input deltas through the (deterministic) graph to rebuild
        # every operator's state, before any realtime stepping
        from pathway_tpu.internals.config import get_pathway_config

        if self._cluster is not None and self._persistence is not None:
            join_info = getattr(self, "_member_join_info", None)
            if join_info is not None:
                # joiner: no replay (the fragments ARE the state at the
                # transition commit) — synchronize with the members' install
                # barrier and enter the lockstep loop at commit C+1
                gen = int(join_info.get("generation", 0))
                self._cluster.allgather(f"member:install:{gen}".encode(), None)
                self._membership_state = "stable"
                self._member_done_gen = gen
                self._target_workers = self._cluster.n
                import logging

                logging.getLogger("pathway_tpu").warning(
                    "rank %d: joined the cluster at epoch %d (n=%d, "
                    "generation %d) from the membership manifest — no "
                    "journal replay",
                    self._rank, getattr(self._cluster, "epoch", 0),
                    self._cluster.n, gen,
                )
            else:
                self._cluster_replay(replay_frames, floor=ckpt_floor)
        else:
            if replay_frames and get_pathway_config().persistence_mode == "batch":
                # replay the whole recording as ONE commit (reference PersistenceMode::Batch)
                merged: Dict[int, List[Delta]] = {}
                for _cid, input_deltas, _offs in replay_frames:
                    for nid, delta in input_deltas.items():
                        merged.setdefault(nid, []).append(delta)
                combined = {
                    nid: Delta.concat(ds, list(ds[0].columns)) for nid, ds in merged.items()
                }
                replay_frames = [(replay_frames[-1][0], combined, replay_frames[-1][2])]
            for commit_id, input_deltas, _offsets in replay_frames:
                self._inject = input_deltas
                self.step()
            self._inject = None
            if replay_frames:
                # future frame ids must exceed every journaled id (checkpoint subsumption
                # filters by id)
                self._commit = max(self._commit, replay_frames[-1][0] + 1)

    def _analysis_context(self, *, persistence: "bool | None" = None) -> Any:
        """The ONE AnalysisContext of this runner (DAG walk + consumer maps +
        dtype propagation), built lazily and shared by the lint gate and the
        fusion planner — a regression test asserts a single construction per
        ``pw.run``."""
        if self._analysis_ctx is None:
            from pathway_tpu.analysis import AnalysisContext

            if persistence is None:
                persistence = self._persistence is not None
            self._analysis_ctx = AnalysisContext(self.graph, persistence=persistence)
        return self._analysis_ctx

    def _fusion_mode(self) -> str:
        mode = os.environ.get("PATHWAY_FUSION", "on").strip().lower()
        if mode in ("off", "0", "false", "no", "none"):
            return "off"
        if mode not in ("on", "1", "true", "yes", ""):
            import logging

            # a typo (PATHWAY_FUSION=fast) must not silently flip the default
            logging.getLogger("pathway_tpu").warning(
                "unrecognized PATHWAY_FUSION=%r (expected off|on); keeping the "
                "default 'on'",
                mode,
            )
        return "on"

    def _build_fusion(self) -> None:
        """Plan whole-commit fusion and compile the substep schedule
        (``PATHWAY_FUSION=off`` or a plan with no chains leaves the stock
        per-node dispatch untouched). Runs inside ``setup`` after evaluators
        and the materialization set exist — journal replay already executes
        fused."""
        self._fusion_schedule = None
        if self._materialize_all or self._fusion_mode() == "off":
            # nested iterate runners share the outer commit's substep; fusing
            # them would double-attribute and complicate the inner fixpoint
            return
        from pathway_tpu.analysis.fusion import plan_fusion
        from pathway_tpu.engine.fusion import build_schedule

        plan = plan_fusion(self._analysis_context())
        self._fusion_schedule = build_schedule(self, plan)
        if self._fusion_schedule is not None and self._recorder is not None:
            # the region plan rides the flight recorder so a post-mortem dump
            # names what was fused at crash time
            self._recorder.record_event("fusion", **plan.to_event())

    def _bind_cluster_policies(self) -> bool:
        """Stamp every evaluator with its per-input cluster routing policies and
        barrier participation (re-run after a surgical-rejoin state reset — the
        fresh evaluators need the same stamps the originals got in setup).
        Returns the transparent-threads flag."""
        shared_threads = self._cluster is not None and getattr(
            self._cluster, "shared_inputs", False
        )
        if self._cluster is not None:
            for node in self._nodes:
                ev = self.evaluators[node.id]
                ev._cluster_policies = tuple(
                    ev.cluster_input_policy(i) for i in range(len(node.inputs))
                )
                # exchange/centralize/broadcast points are lockstep barriers:
                # they participate in every commit even with no local rows
                ev._cluster_barrier = node.kind in ("groupby", "join") or any(
                    p is not None for p in ev._cluster_policies
                )
                if shared_threads and isinstance(node, pg.OutputNode):
                    # transparent-threads mode: sinks live on rank 0 only, so
                    # every worker ships its output partition to the root —
                    # callbacks stay single-threaded and see ALL rows, in the
                    # same per-commit batches a 1-thread run delivers
                    ev._cluster_policies = tuple("root" for _ in node.inputs)
                    ev._cluster_barrier = True
        return shared_threads

    def _load_checkpoint_state(self, blob: dict) -> None:
        """Restore operator + state-table snapshots (reference operator persistence,
        ``dataflow/persist.rs``); live sinks then receive the restored state as one
        snapshot delivery (they cannot re-hear the compacted history)."""
        from pathway_tpu.engine.evaluators import OutputEvaluator

        for nid, sblob in blob["states"].items():
            if nid in self.states:
                self.states[nid].load_state_blob(sblob)
        for nid, estate in blob["evaluators"].items():
            evaluator = self.evaluators.get(nid)
            if evaluator is not None:
                evaluator.load_state_dict(estate)
        self._deliver_sink_snapshots()

    def _deliver_sink_snapshots(self) -> None:
        """Live sinks receive the restored/imported state as one snapshot
        delivery (they cannot re-hear compacted history; after a membership
        import this also hands a rank its newly-gained rows)."""
        from pathway_tpu.engine.evaluators import OutputEvaluator

        if not self.replay_outputs:
            return
        for node in self._nodes:
            evaluator = self.evaluators[node.id]
            if isinstance(evaluator, OutputEvaluator):
                snapshot = self.states[node.inputs[0]._node.id].snapshot()
                if len(snapshot):
                    evaluator.process([snapshot])

    def _await_membership_manifest(self, sig: str) -> dict:
        """Joiner-side wait for the members to commit the membership
        manifest (bounded by the fence timeout; a refused/aborted transition
        leaves the joiner to die typed and the supervisor cleans up).
        Worker-count mismatches against OLDER manifests are expected while
        the transition is still in flight — keep polling."""
        from pathway_tpu.internals.config import env_float as _env_float
        from pathway_tpu.parallel.cluster import PeerTimeoutError
        from pathway_tpu.parallel.membership import MembershipMismatchError

        deadline = time_mod.monotonic() + _env_float(
            "PATHWAY_MEMBERSHIP_DEADLINE_S",
            _env_float("PATHWAY_FENCE_TIMEOUT_S", 180.0),
        )
        while True:
            try:
                manifest = self._persistence.load_cluster_manifest(sig)
            except MembershipMismatchError:
                manifest = None  # pre-transition manifest still newest
            if manifest is not None and manifest.get("membership"):
                return manifest
            if time_mod.monotonic() > deadline:
                raise PeerTimeoutError(
                    f"joiner rank {self._rank}: no membership manifest "
                    "appeared within the deadline — the transition aborted "
                    "or never started"
                )
            self._publish_status(force=True)
            time_mod.sleep(0.25)

    def _snapshot_blob(self) -> "tuple[dict | None, str]":
        """Build the full engine snapshot (operator + state-table + source
        state). Returns ``(blob, "ok")``, ``(None, "defer")`` while any source
        is mid-segment (a segment's pre-checkpoint events would be baked into
        state while its tail stays in the journal, making a changed-segment
        undo impossible), or ``(None, "permanent: ...")`` for unpicklable
        operator state."""
        from pathway_tpu.engine.evaluators import (
            InputEvaluator,
            OutputEvaluator,
            UnpicklableStateError,
        )

        offsets = {
            # per-frame marker payloads don't belong in the checkpoint snapshot
            n.id: {k: v for k, v in n.config["source"].offset_state().items() if k != "state_deltas"}
            for n, _ in self._sources
        }
        if any(o.get("in_progress") for o in offsets.values()):
            return None, "defer"
        deltas = {
            n.id: n.config["source"].checkpoint_state_deltas() for n, _ in self._sources
        }
        try:
            blob = {
                "states": {nid: st.state_blob() for nid, st in self.states.items()},
                "evaluators": {
                    nid: ev.state_dict()
                    for nid, ev in self.evaluators.items()
                    if not isinstance(ev, (InputEvaluator, OutputEvaluator))
                },
                "source_offsets": offsets,
                "source_deltas": deltas,
            }
        except UnpicklableStateError as exc:
            return None, f"permanent: {exc}"
        return blob, "ok"

    def _take_checkpoint(self) -> bool:
        """Single-process checkpoint: snapshot, then compact the journal."""
        blob, why = self._snapshot_blob()
        if blob is None:
            if why.startswith("permanent"):
                if not self._warned_unpicklable:
                    self._warned_unpicklable = True
                    import logging

                    logging.getLogger("pathway_tpu").warning(
                        "operator checkpointing disabled: %s — falling back to "
                        "full journal replay on resume",
                        why,
                    )
                self._snapshot_interval_s = 0.0  # stop retrying every commit
            return False
        self._persistence.dump_checkpoint(self._graph_sig, self._commit, blob)
        return True

    def _coordinated_checkpoint(self) -> None:
        """Cluster-coordinated checkpoint at ONE lockstep commit id (the
        decision rode this commit's neu allgather, so every rank is here).

        Barrier sequence: (1) every rank writes its versioned snapshot; (2)
        durability acks are allgathered — any non-ok rank aborts the attempt
        cluster-wide and the previous checkpoint stands; (3) rank 0 commits the
        manifest (read-back verified) and the outcome is allgathered; (4) only
        then does every rank compact its journal shard and prune old
        snapshots/manifests + the mesh serve log. A crash at ANY point leaves
        the previous checkpoint + uncompacted journal recoverable
        (chaos-tested: post-snapshot kill, torn manifest, snapshot error)."""
        from pathway_tpu.engine import telemetry
        from pathway_tpu.engine.profile import histogram

        cluster = self._cluster
        t0 = time_mod.monotonic()
        epoch = getattr(cluster, "epoch", 0)
        if self._chaos is not None:
            self._chaos.begin_checkpoint_attempt()
            # plain rank death scheduled after N completed checkpoints (the
            # acceptance headline) — before anything of THIS attempt is written
            self._chaos.maybe_checkpoint_kill(
                self._rank, self._commit, epoch=epoch, op="pre_snapshot_kill"
            )
        blob, status = self._snapshot_blob()
        size = 0
        if blob is not None:
            try:
                size = self._persistence.dump_cluster_snapshot(
                    self._graph_sig, self._commit, blob
                )
            except (ConnectionError, OSError) as exc:
                status = f"transient: {exc}"
        if self._chaos is not None:
            # fault window: this rank's snapshot is durable, the manifest is not
            self._chaos.maybe_checkpoint_kill(self._rank, self._commit, epoch=epoch)
        statuses = cluster.allgather(f"ckptack:{self._commit}".encode(), status)
        if any(s.startswith("permanent") for s in statuses):
            self._ckpt_disabled_reason = next(
                s for s in statuses if s.startswith("permanent")
            )
            self._ckpt_interval_s = 0.0
            import logging

            logging.getLogger("pathway_tpu").warning(
                "coordinated checkpoints disabled cluster-wide (%s) — rejoin "
                "falls back to full journal replay",
                self._ckpt_disabled_reason,
            )
            return
        if any(s != "ok" for s in statuses):
            # transient backend error or a mid-segment defer somewhere: no
            # manifest, previous checkpoint stands, retry at the next commit
            telemetry.stage_add("persist.checkpoint_retries")
            if self._recorder is not None:
                self._recorder.record_event(
                    "checkpoint_deferred", commit=self._commit,
                    statuses=[s.split(":")[0] for s in statuses],
                )
            return
        ok = True
        if self._rank == 0:
            ok = self._persistence.commit_cluster_manifest(
                self._graph_sig, self._commit, epoch=epoch
            )
        oks = cluster.allgather(f"ckptdone:{self._commit}".encode(), bool(ok))
        if not all(oks):
            # torn/failed manifest: every rank keeps its journal intact; the
            # orphan snapshots are pruned by the next successful checkpoint
            telemetry.stage_add("persist.checkpoint_manifest_failures")
            return
        tail_frames = 0
        if self._ckpt_compact:
            tail_frames = self._persistence.compact_journal(self._graph_sig)
        self._persistence.cleanup_cluster_checkpoints(self._commit)
        # a parked leaver source continuation (restored if this rank rejoined
        # after a scale-down) is superseded once a durable snapshot carries
        # the live offsets
        self._persistence.clear_source_park()
        cluster.prune_commit_log(self._commit)
        self._manifest_commit = self._commit
        self._last_checkpoint = time_mod.monotonic()
        duration = self._last_checkpoint - t0
        # recovery-SLO instrumentation (PR 5 metrics plane): checkpoint
        # cadence/size/duration and the journal-tail length it compacted away
        histogram("pathway_checkpoint_duration_seconds").observe(duration)
        telemetry.stage_add_many({
            "persist.checkpoints": 1.0,
            "persist.checkpoint_bytes": float(size),
            "persist.checkpoint_s": duration,
            "persist.journal_frames_compacted": float(tail_frames),
        })
        if self._recorder is not None:
            self._recorder.record_event(
                "checkpoint",
                commit=self._commit,
                epoch=epoch,
                bytes=size,
                duration_s=round(duration, 4),
                journal_frames_compacted=tail_frames,
            )

    def _restore_sources(self, frames: List[tuple]) -> None:
        """Fold journaled segment-state deltas and the unmarked tail back into each
        source (reference ``Connector::read_snapshot`` + ``OffsetValue`` seek)."""
        from pathway_tpu.internals.keys import keys_to_pointers

        last_offsets = frames[-1][2]
        for node, _ in self._sources:
            nid = node.id
            offsets = last_offsets.get(nid, {})
            rehydrate = getattr(
                getattr(node.config["source"], "subject", None),
                "rehydrate_state_deltas",
                None,
            )
            # journal-frame markers are slim (no row payload): re-derive
            # each marker's rows from the input deltas journaled up to its
            # frame (row keys are content-addressed, the lookup is exact).
            # Checkpoint/fragment deltas arrive hydrated and pass through.
            row_values: Dict[bytes, Any] = {}
            fed_until = 0

            def _feed_rows(up_to: int) -> None:
                nonlocal fed_until
                for f_idx in range(fed_until, up_to):
                    delta = frames[f_idx][1].get(nid)
                    if delta is None or len(delta) == 0:
                        continue
                    for i in range(len(delta)):
                        if delta.diffs[i] > 0:
                            row_values[delta.keys[i].tobytes()] = {
                                n: c[i] for n, c in delta.columns.items()
                            }
                fed_until = max(fed_until, up_to)

            state_deltas: List[Any] = []
            last_marker_idx = -1
            for idx, (_cid, _deltas, offs) in enumerate(frames):
                deltas = offs.get(nid, {}).get("state_deltas")
                if deltas:
                    if rehydrate is not None and any(
                        "rows" not in d and not d.get("deleted") for d in deltas
                    ):
                        _feed_rows(idx + 1)
                        deltas = rehydrate(deltas, row_values)
                    state_deltas.extend(deltas)
                    last_marker_idx = idx
            tail: Optional[dict] = None
            if offsets.get("consumed", 0) > 0 or offsets.get("done"):
                tail_rows: List[tuple] = []
                for _cid, input_deltas, _offs in frames[last_marker_idx + 1 :]:
                    delta = input_deltas.get(nid)
                    if delta is None or len(delta) == 0:
                        continue
                    pointers = keys_to_pointers(delta.keys)
                    for i in range(len(delta)):
                        values = {n: c[i] for n, c in delta.columns.items()}
                        tail_rows.append((pointers[i], values, int(delta.diffs[i])))
                in_progress = offsets.get("in_progress") or {}
                covered = 0
                if last_marker_idx >= 0:
                    covered = frames[last_marker_idx][2].get(nid, {}).get("consumed", 0)
                tail = {
                    "token": in_progress.get("token"),
                    "fp": in_progress.get("fp"),
                    "count": in_progress.get("emitted", 0),
                    "rows": tail_rows,
                    # events up to `covered` are accounted for by segment markers; only
                    # a marker-less subject re-pushes its whole history
                    "covered": covered,
                    "has_markers": last_marker_idx >= 0,
                }
            node.config["source"].restore(offsets, state_deltas, tail)

    def _cluster_replay(self, replay_frames: List[tuple], floor: int = 0) -> None:
        """Lockstep journal replay across the cluster: journals differ after a
        mid-commit kill (one process recorded commit N, its peer died first),
        and a commit with data on only one process writes a frame only there.
        Exchange tags carry the commit id, so every process must replay the
        UNION of recorded ids at their ORIGINAL numbering — injecting an empty
        frame where it has no local data — or the all-to-all deadlocks.
        (Reference: timely workers replay a shared total order of timestamps.)
        Runs at initial setup AND after a surgical-rejoin state reset; either
        way every rank leaves with the same ``_commit`` counter, so post-replay
        barrier tags line up. ``floor`` is the post-replay commit counter when
        nothing is journaled (manifest commit + 1 under a cluster checkpoint —
        every rank computes the same floor from the same manifest)."""
        local_frames = {cid: deltas for cid, deltas, _offs in replay_frames}
        all_ids = self._cluster_replay_ids(local_frames)
        # rung-coordination barrier (see _attempt_surgical_rejoin): a fresh or
        # replacement rank has no retained state, so it votes "no interrupted
        # commit" and always step-replays — the vote only keeps its barrier
        # tag sequence aligned with fenced survivors deciding serve-vs-step
        self._cluster.allgather(b"replay:mode", None)
        self._cluster_replay_steps(local_frames, all_ids, floor)

    def _cluster_replay_ids(self, local_frames: Dict[int, Any]) -> List[int]:
        """The union of journaled commit ids across the cluster (one allgather;
        both the step-replay and the serve-from-log rejoin paths start here, so
        a rank may decide its mode AFTER learning the union without skewing the
        barrier tag sequence)."""
        id_lists = self._cluster.allgather(b"replay:ids", sorted(local_frames))
        return sorted(set().union(*id_lists))

    def _cluster_replay_steps(
        self, local_frames: Dict[int, Any], all_ids: List[int], floor: int = 0
    ) -> None:
        from pathway_tpu.internals.config import get_pathway_config

        if all_ids and get_pathway_config().persistence_mode == "batch":
            # batch mode, cluster flavor: collapse every local frame into ONE
            # replay commit pinned at the globally-last journaled id, so the
            # single replayed commit carries the same exchange tags everywhere
            merged: Dict[int, List[Delta]] = {}
            for deltas in local_frames.values():
                for nid, delta in deltas.items():
                    merged.setdefault(nid, []).append(delta)
            combined = {
                nid: Delta.concat(ds, list(ds[0].columns))
                for nid, ds in merged.items()
            }
            local_frames = {all_ids[-1]: combined}
            all_ids = [all_ids[-1]]
        for cid in all_ids:
            self._commit = cid
            self._inject = local_frames.get(cid, {})
            self.step()
        self._inject = None
        # nothing journaled anywhere: every rank aligns at the floor (0 on a
        # fresh store; manifest commit + 1 under a cluster checkpoint — a
        # fenced survivor may arrive here mid-commit-N; leaving its counter
        # ahead of the replacement's would skew every post-rejoin barrier tag)
        self._commit = all_ids[-1] + 1 if all_ids else floor

    def step(self) -> bool:
        """Run one commit; returns True if any node produced output.

        Each commit runs in two phases mirroring the reference's alt/neu timestamps
        (``dataflow.rs:3447``): the even ("alt") phase moves normal data; the odd ("neu")
        phase moves *forgetting* retractions drained from Forget/AsofNow operators. Keeping
        the phases separate guarantees a delta is never a mix of real updates and
        forgetting updates, so ``_filter_out_results_of_forgetting`` can drop whole neu
        deltas without losing genuine data.

        The commit is the root of the commit-plane trace: its trace id is a
        pure function of ``(epoch, commit)``, so every rank's commit span is a
        sibling in ONE trace without anything riding the wire, and barrier /
        checkpoint spans opened below become its children via the
        context-local parent. REST queries whose rows this commit took link
        in, and each gets its ``queue`` span: from its push to this commit's
        start. Operator child spans are synthesized AFTER the commit closes,
        and only for sampled/promoted commits — nothing on the operator hot
        path.
        """
        tracer = _tracing.get_tracer()
        if not tracer.recording() or self._materialize_all:
            return self._step_inner()
        epoch = (
            getattr(self._cluster, "epoch", 0) if self._cluster is not None else 0
        )
        tracer.set_epoch(epoch)
        commit = self._commit
        ctx = _tracing.commit_trace_context(epoch, commit, self._rank)
        with tracer.trace_span(
            "commit",
            f"commit {commit}",
            self_ctx=ctx,
            attrs={"commit": commit, "epoch": epoch},
        ) as span:
            any_output = self._step_inner()
            if span is not None:
                self._trace_commit_queries(tracer, span)
        if span is not None and span.sampled:
            self._trace_commit_ops(tracer, span)
        return any_output

    def _trace_commit_queries(self, tracer: Any, span: Any) -> None:
        """Link the REST queries whose rows this commit took (matched by row
        key among its input deltas) and record, for each, how long the row
        waited: a ``queue`` span from its push to the commit's start, a child
        of the query's own ``rest`` span, naming the commit that took it."""
        taken = tracer.take_commit_links(
            keys[i : i + size]
            for keys, size in (
                (d.keys.tobytes(), d.keys.itemsize)
                for d in self._input_deltas.values()
            )
            for i in range(0, len(keys), size)
        )
        span.attrs["queries"] = len(taken)
        for query_ctx, pushed in taken:
            span.add_link(query_ctx)
            if not query_ctx.sampled:
                continue
            waited = max(0.0, span.ts_mono - pushed)
            tracer.record_span(
                "queue",
                f"queue for commit {span.attrs['commit']}",
                parent=query_ctx,
                ts=span.ts - waited,
                ts_mono=pushed,
                duration_s=waited,
                attrs={"commit": span.attrs["commit"]},
            )

    def _trace_commit_ops(self, tracer: Any, span: Any) -> None:
        """Lift the commit profile's per-evaluator rows into child spans of
        the (sampled or slow-promoted) commit span. Start offsets partition
        the commit window cumulatively — durations are what the critical-path
        walk consumes; only the slowest rows survive the cap. A commit that
        moved no row (the loop wakes and finds nothing: most commits of a
        serving process) gets none: its rows are all noise, and at one per
        operator they would push a traced span's requests out of the ring."""
        commit_profile = self._last_commit_profile
        self._last_commit_profile = None
        if commit_profile is None or not commit_profile.ops:
            return
        if not (commit_profile.input_rows or commit_profile.output_rows):
            return
        ops = commit_profile.ops
        if len(ops) > 48:
            ops = sorted(ops, key=lambda op: op[3], reverse=True)[:48]
        parent = span.context()
        offset = 0.0
        for node_id, name, kind, seconds, rows, retractions, neu in ops:
            span_kind = "fused_region" if kind == "fused_chain" else "operator"
            tracer.record_span(
                span_kind,
                name,
                parent=parent,
                ts=span.ts + offset,
                ts_mono=span.ts_mono + offset,
                duration_s=seconds,
                attrs={
                    "node": node_id,
                    "op_kind": kind,
                    "rows": rows,
                    "retractions": retractions,
                    "neu": neu,
                },
            )
            offset += seconds

    def _step_inner(self) -> bool:
        commit_t0 = time_mod.monotonic()
        if self._inject is None:
            # fresh drain: these deltas belong to THIS commit (the surgical
            # fence must only carry over input rows of the interrupted commit,
            # never re-ingest an earlier, already-journaled batch)
            self._input_deltas = {}
            self._input_deltas_commit = self._commit
        if self._chaos is not None and self._inject is None:
            # fault injection: a scheduled kill fires at a LIVE commit
            # boundary — the previous commit is fully journaled, this one is
            # mid-flight everywhere else in the cluster (peers block in its
            # barriers). Journal replay (restart-all resume or a fenced
            # survivor's rollback) must never re-fire a kill, or the schedule
            # would loop forever.
            self._chaos.maybe_kill(
                self._rank,
                self._commit,
                epoch=getattr(self._cluster, "epoch", 0)
                if self._cluster is not None
                else 0,
            )
        self.current_time = self._commit * 2  # even data times, as in the reference
        self.draining = self._ready and self.sources_finished()
        undo_armed = (
            self._undo_depth > 0
            and self._rewind_safe
            and self._inject is None
            and self._cluster is not None
        )
        if undo_armed:
            # incremental rewind bookkeeping for THIS commit: the undo record
            # (inverted on a fence) and the mesh serve-log entry (served to a
            # replacement's tail replay). Both are discarded if the commit
            # completes/fails respectively — see _undo_interrupted_commit.
            self._undo_current = {
                "commit": self._commit, "applied": [], "evals": {}, "bytes": 0,
                "capture_s": 0.0,
            }
            self._cluster.begin_commit_log(self._commit)
        ckpt_due = False
        any_output = self._substep(neu=False)
        neu = any(
            getattr(self.evaluators[n.id], "neu_pending", _no_pending)()
            for n in self._nodes
        )
        if self._cluster is not None:
            # the neu phase is part of the lockstep commit protocol: every process
            # must agree whether it runs (exchange points fire inside it). The
            # coordinated-checkpoint marker RIDES this same barrier: barriers are
            # already lockstep, so every rank learns at the same commit id that a
            # checkpoint is due — aligned Chandy–Lamport for free.
            member_vote = self._membership_vote() if self._inject is None else None
            want_ckpt = (
                self._inject is None
                and self._ckpt_interval_s > 0
                and self._persistence is not None
                and time_mod.monotonic() - self._last_checkpoint
                >= self._ckpt_interval_s
                # a pending membership change writes its OWN manifest at the
                # transition commit; a racing checkpoint would be redundant
                and self._member_pending is None
            )
            votes = self._cluster.allgather(
                f"neu:{self._commit}".encode(), (neu, want_ckpt, member_vote)
            )
            neu = any(v[0] for v in votes)
            ckpt_due = any(v[1] for v in votes)
            if self._inject is None:
                self._membership_votes_seen([v[2] for v in votes])
        if neu:
            self.current_time = self._commit * 2 + 1
            any_output = self._substep(neu=True) or any_output
        if undo_armed:
            # mutations for this commit are final: seal the serve-log entry and
            # drop the undo record — a fence from here on (journaling has no
            # barriers; the checkpoint barriers come after) must NOT undo a
            # completed commit
            self._cluster.end_commit_log()
            rec_done, self._undo_current = self._undo_current, None
            if rec_done is not None and rec_done["evals"]:
                alpha = 0.2
                self._undo_capture_ewma += alpha * (
                    rec_done["capture_s"] - self._undo_capture_ewma
                )
                self._undo_commit_ewma += alpha * (
                    time_mod.monotonic() - commit_t0 - self._undo_commit_ewma
                )
                self._undo_armed_commits += 1
                if (
                    self._undo_armed_commits >= 8
                    # 1 ms absolute floor: below it the rung is cheap in wall
                    # terms and µs-level timer noise could trip the ratio
                    and self._undo_capture_ewma > 1e-3
                    and self._undo_capture_ewma > 0.25 * self._undo_commit_ewma
                ):
                    self._disable_rewind(
                        f"undo capture averages "
                        f"{self._undo_capture_ewma * 1e3:.1f} ms/commit "
                        f"({self._undo_capture_ewma / self._undo_commit_ewma:.0%} "
                        "of commit time); re-pickling this much operator state "
                        "every commit costs more than the tail replay it avoids"
                    )
        if self._persistence is not None and self._inject is None:
            offsets = {n.id: n.config["source"].offset_state() for n, _ in self._sources}
            # a frame is needed for data AND for data-less segment markers (a marker can
            # close a segment whose rows all rode earlier frames)
            if any(len(d) for d in self._input_deltas.values()) or any(
                o.get("state_deltas") for o in offsets.values()
            ):
                self._persistence.record_commit(self._commit, self._input_deltas, offsets)
                if (
                    self._snapshot_interval_s > 0
                    # single-process operator snapshots are wall-clock-driven;
                    # under a cluster the COORDINATED protocol below replaces
                    # them (an unsynchronized checkpoint would subsume commits
                    # whose exchanges a peer still needs to replay)
                    and self._cluster is None
                    and time_mod.monotonic() - self._last_checkpoint
                    >= self._snapshot_interval_s
                ):
                    with _tracing.trace_span(
                        "checkpoint", f"checkpoint {self._commit}"
                    ):
                        if self._take_checkpoint():
                            self._last_checkpoint = time_mod.monotonic()
            if ckpt_due:
                # every rank reaches this point for a due checkpoint (the
                # decision was allgathered), including ranks with no data this
                # commit — the protocol is a barrier sequence of its own
                with _tracing.trace_span(
                    "checkpoint", f"checkpoint {self._commit}"
                ):
                    self._coordinated_checkpoint()
        input_rows = sum(len(d) for d in self._input_deltas.values())
        if self.prober_stats is not None:
            self.prober_stats.record_commit(
                input_rows,
                self._output_rows_this_commit,
                self._step_counts,
                self.sources_finished(),
            )
            if self._metrics is not None:
                self._metrics.record_commit(
                    input_rows,
                    self._output_rows_this_commit,
                    time_mod.monotonic() - commit_t0,
                )
        if self._profiler is not None:
            commit_profile = CommitProfile(
                commit=self._commit,
                rank=self._rank,
                duration_s=time_mod.monotonic() - commit_t0,
                input_rows=input_rows,
                output_rows=self._output_rows_this_commit,
                neu=neu,
                ops=self._profile_ops or [],
            )
            self._profiler.record_commit(commit_profile)
            if self._recorder is not None:
                self._recorder.record_commit(commit_profile)
            self._last_commit_profile = commit_profile
            self._profile_ops = None
        if self._monitor is not None:
            self._monitor.update(self._commit, self._step_counts, self.states)
        if self._supervise_dir is not None:
            # liveness for the spawn supervisor: written from THIS loop (not a
            # helper thread) so staleness means the commit loop stopped turning
            self._publish_status()
        self._commit += 1
        if self._member_all_ready and self._inject is None:
            # every rank voted ready for the same generation at THIS commit:
            # the cluster is quiesced — run the epoch-fenced transition
            self._run_membership_transition()
        return any_output

    def _publish_status(self, force: bool = False) -> None:
        """Atomically publish this rank's liveness record for the supervisor
        (throttled; ``force`` bypasses the throttle — the fence path publishes
        on every poll so a quiesced-but-healthy survivor is never shot for
        staleness, and so operators can watch the rejoin progress)."""
        if self._supervise_dir is None:
            return
        now = time_mod.monotonic()
        if not force and now - self._last_status_write < 0.25:
            return
        from pathway_tpu.parallel.supervisor import write_status

        self._mirror_autoscale_state(now)
        health = self.health()
        write_status(
            self._supervise_dir,
            self._rank,
            commit=self._commit,
            persistence=self._persistence is not None,
            peers=health["peers"],
            epoch=health["epoch"],
            state=health["state"],
            restarts=health["restarts"],
            last_rejoin_s=health["last_rejoin_s"],
            checkpoint_commit=health["checkpoint_commit"],
            journal_tail_frames=health["journal_tail_frames"],
            extra={
                k: health[k]
                for k in (
                    "membership_state",
                    "current_workers",
                    "target_workers",
                    "membership_committed",
                    "membership_refused",
                    "membership_refusals",
                    "manifest_workers",
                    "autoscale",
                )
            },
        )
        self._last_status_write = now

    def _mirror_autoscale_state(self, now: float) -> None:
        """Mirror the supervisor's autoscale-controller state file into this
        worker's observability surfaces (throttled to ~1/s): ``/healthz``
        shows the controller state + last decision, decision changes bump
        ``autoscale.decisions``, and a flap-lock engaging lands an
        ``autoscale`` flight event — post-mortems then carry the controller's
        story next to the commit timeline."""
        if self._supervise_dir is None or now - self._autoscale_last_read < 1.0:
            return
        self._autoscale_last_read = now
        from pathway_tpu.engine import telemetry
        from pathway_tpu.parallel.autoscaler import read_state

        state = read_state(self._supervise_dir)
        if state is None:
            return
        gen = int(state.get("generation", 0) or 0)
        prev = self._autoscale_state
        self._autoscale_state = state
        if gen == self._autoscale_seen_gen:
            return
        self._autoscale_seen_gen = gen
        # the generation bumps on EVERY controller state change (issue,
        # refusal, completion, recovery re-arm) — count a DECISION only when
        # the last-decision record itself changed
        if state.get("last_decision") != (prev or {}).get("last_decision"):
            telemetry.stage_add("autoscale.decisions")
        was_locked = bool(prev and prev.get("flap_locked"))
        if state.get("flap_locked") and not was_locked:
            telemetry.stage_add("autoscale.flap_locks")
        if self._recorder is not None:
            last = state.get("last_decision") or {}
            self._recorder.record_event(
                "autoscale",
                state=state.get("state"),
                flap_locked=bool(state.get("flap_locked")),
                decision=last.get("kind"),
                target_n=last.get("target_n"),
                reason=str(last.get("reason", ""))[:160],
            )

    def _substep(self, *, neu: bool) -> bool:
        if not neu:
            self._step_counts = {}
            self._output_rows_this_commit = 0
            self._profile_ops = [] if self._profiler is not None else None
        deltas: Dict[int, Delta] = {}
        self._substep_deltas = deltas
        any_output = False
        from pathway_tpu.engine import expression_evaluator as ee_mod

        profile_ops = self._profile_ops
        runtime = ee_mod.get_runtime()
        schedule = self._fusion_schedule
        if schedule is None:
            # stock per-node dispatch (PATHWAY_FUSION=off reproduces this path
            # exactly: the schedule is never built)
            for node in self._nodes:
                if self._run_node(node, deltas, neu, profile_ops, runtime):
                    any_output = True
        else:
            for item in schedule:
                if isinstance(item, pg.Node):
                    ran = self._run_node(item, deltas, neu, profile_ops, runtime)
                else:
                    # a compiled ChainProgram covering several operators
                    ran = item.execute(self, deltas, neu, profile_ops, runtime)
                if ran:
                    any_output = True
        return any_output

    def _run_node(
        self,
        node: pg.Node,
        deltas: Dict[int, Delta],
        neu: bool,
        profile_ops: "List[tuple] | None",
        runtime: Dict[str, Any],
    ) -> bool:
        """One operator's substep turn (the pre-fusion per-node dispatch body,
        shared verbatim by the unfused loop and fused-region member nodes).
        Returns whether the node emitted rows."""
        any_output = False
        evaluator = self.evaluators[node.id]
        runtime["node"] = node
        # commit identity for UDFs that read live process-global state
        # (the /v1/statistics engine snapshot): re-derivations WITHIN one
        # commit must see the same value (a value that moved between two
        # evaluations churns nondeterministic update pairs), while the
        # next commit reads fresh — retraction rows of later commits are
        # covered by the evaluator's memoize-on-retraction, not by this.
        # Set per node because nested iterate runners share this
        # thread-local and overwrite it mid-substep.
        runtime["commit_token"] = (id(self), self._commit)
        _t_op = time_mod.perf_counter() if profile_ops is not None else 0.0
        if (
            isinstance(node, pg.OutputNode)
            and not neu
            and (self._inject is None or self.replay_outputs)
        ):
            # count only rows actually delivered to sinks (not forgetting-phase
            # retractions, not silently-replayed history)
            self._output_rows_this_commit += sum(
                len(deltas.get(inp._node.id, ())) for inp in node.inputs
            )
        if isinstance(node, pg.InputNode):
            if neu or self._shared_nonroot:
                delta = Delta.empty(self.output_columns_of(node))
            elif self._inject is not None:
                # journal replay: feed the persisted delta instead of the source
                delta = self._inject.get(
                    node.id, Delta.empty(self.output_columns_of(node))
                )
            else:
                delta = evaluator.process([])
                carry = self._rejoin_carry.pop(node.id, None)
                if carry is not None and len(carry):
                    # input rows drained by the commit a fence interrupted,
                    # never journaled: re-ingest them exactly once with the
                    # first post-rejoin batch (they journal normally now)
                    delta = (
                        Delta.concat(
                            [carry, delta], self.output_columns_of(node)
                        )
                        if len(delta)
                        else carry
                    )
            if not neu:
                self._input_deltas[node.id] = delta
            if self._cluster is not None and getattr(
                self._cluster, "shared_inputs", False
            ):
                # transparent-threads mode: scatter the freshly ingested rows
                # by row key so rowwise/filter/join work downstream runs on
                # ALL ranks, not just the ingesting rank 0 (stateful ops
                # re-exchange by their own keys as usual). Lockstep: every
                # rank reaches this exchange each commit (rank > 0 with an
                # empty delta).
                tag = f"{self.current_time}:{node.id}:scatter".encode()
                delta = self._cluster.exchange_delta(tag, delta, delta.keys)
        else:
            inputs = [
                deltas.get(inp._node.id, Delta.empty(inp.column_names()))
                for inp in node.inputs
            ]
            originates = neu and getattr(evaluator, "neu_pending", _no_pending)()
            cross_nodes = getattr(evaluator, "_cross_nodes", None)
            if (
                all(len(d) == 0 for d in inputs)
                and not originates
                and not (not neu and _has_pending(evaluator))
                and node.kind != "iterate_result"
                # a rowwise node's cross-table references are live deps:
                # run when any referenced table emitted this substep
                and not (
                    cross_nodes
                    and any(len(deltas.get(n.id, ())) for n in cross_nodes)
                )
                # lockstep: exchange-point operators participate in every
                # commit's all-to-all even with no local rows (peers block on
                # our partitions)
                and not (self._cluster is not None and evaluator._cluster_barrier)
            ):
                delta = Delta.empty(self.output_columns_of(node))
            else:
                if (
                    self._undo_current is not None
                    and node.id not in self._undo_current["evals"]
                ):
                    # pre-mutation snapshot, taken the FIRST time this
                    # operator runs in the commit (the neu phase re-runs
                    # nodes; the undo target is the pre-commit state)
                    self._capture_undo_state(node, evaluator)
                if self._cluster is not None and any(
                    p is not None for p in evaluator._cluster_policies
                ):
                    inputs = self._route_cluster_inputs(node, evaluator, inputs)
                if originates:
                    delta = evaluator.drain_neu(inputs)
                else:
                    try:
                        delta = evaluator.process(inputs)
                    except Exception as exc:
                        from pathway_tpu.internals.trace import add_error_context
                        from pathway_tpu.parallel.cluster import (
                            PeerShutdownError,
                            PeerTimeoutError,
                        )

                        if isinstance(exc, (PeerShutdownError, PeerTimeoutError)):
                            # a peer death inside this node's exchange is an
                            # infrastructure failure, not an operator bug:
                            # keep it TYPED so the surgical-rejoin fence (and
                            # isinstance-based failure triage) can catch it
                            raise
                        raise add_error_context(exc, node) from exc
            if neu and len(delta):
                delta.neu = True
        deltas[node.id] = delta
        if len(delta):
            any_output = True
            self._step_counts[node.id] = self._step_counts.get(node.id, 0) + len(delta)
            if node.output is not None and node.id in self._materialized:
                if self._undo_current is not None:
                    # applied-delta record: Delta.negated() of each entry
                    # (in reverse) is the exact state-table undo
                    self._undo_current["applied"].append((node.id, delta))
                self.states[node.id].apply(delta)
        if profile_ops is not None:
            rows = len(delta)
            # count_nonzero: ONE pass over diffs (a min() pre-check reads
            # the array twice on the update-heavy deltas that dominate
            # steady state, doubling the per-op profiling cost)
            retractions = (
                int(np.count_nonzero(delta.diffs < 0)) if rows else 0
            )
            profile_ops.append((
                node.id,
                node.name,
                node.kind,
                time_mod.perf_counter() - _t_op,
                rows,
                retractions,
                neu,
            ))
        return any_output

    def _route_cluster_inputs(
        self, node: pg.Node, evaluator: Any, inputs: List[Delta]
    ) -> List[Delta]:
        """Apply the evaluator's per-input cluster policies (all-to-all barriers;
        every process reaches this point each commit — ``_cluster_barrier``)."""
        routed: List[Delta] = []
        for idx, delta in enumerate(inputs):
            policy = evaluator._cluster_policies[idx]
            tag = f"{self.current_time}:{node.id}:i{idx}".encode()
            if policy is None:
                routed.append(delta)
            elif policy == "rowkey":
                routed.append(self._cluster.exchange_delta(tag, delta, delta.keys))
            elif policy == "custom":
                route_keys = (
                    delta.keys if len(delta) == 0
                    else evaluator.cluster_route_keys(idx, delta)
                )
                routed.append(self._cluster.exchange_delta(tag, delta, route_keys))
            elif policy == "root":
                routed.append(self._cluster.exchange_to_root(tag, delta))
            elif policy == "broadcast":
                routed.append(self._cluster.broadcast_merge(tag, delta))
            else:
                raise AssertionError(f"unknown cluster policy {policy!r}")
        return routed

    def health(self) -> Dict[str, Any]:
        """One liveness payload, two consumers: the ``/healthz`` endpoint and
        the supervisor's per-rank status file (``parallel/supervisor.py``)."""
        peers: Dict[str, float] = {}
        dead: Dict[str, str] = {}
        if self._cluster is not None:
            ages = getattr(self._cluster, "heartbeat_ages", None)
            if ages is not None:
                peers = {str(p): round(a, 3) for p, a in ages().items()}
            dead_fn = getattr(self._cluster, "dead_peers", None)
            if dead_fn is not None:
                dead = {str(p): r for p, r in dead_fn().items()}
        return {
            "rank": self._rank,
            "commit": self._commit,
            "persistence": self._persistence is not None,
            "peers": peers,
            "dead_peers": dead,
            # surgical-restart observability: which mesh incarnation this rank
            # is on, how often it (or its cluster) was relaunched, and whether
            # it is currently quiesced at an epoch fence
            "epoch": getattr(self._cluster, "epoch", 0)
            if self._cluster is not None
            else 0,
            "restarts": int(os.environ.get("PATHWAY_RESTART_COUNT", "0") or 0),
            "rejoins": self._rejoins,
            "last_rejoin_s": self._last_rejoin_s,
            "state": self._rejoin_state,
            # recovery-SLO observability: the commit the last durable cluster
            # checkpoint covers, and how many journal frames a recovery would
            # still replay past it — together they bound the next rejoin
            "checkpoint_commit": self._manifest_commit,
            "journal_tail_frames": (
                self._persistence.frames_since_compact
                if self._persistence is not None
                else None
            ),
            # elastic-membership observability: where the topology is and
            # where it is going (stable|joining|draining|resharding|drained)
            "membership_state": self._membership_state,
            "current_workers": (
                getattr(self._cluster, "n", None)
                if self._cluster is not None
                else 1
            ),
            "target_workers": (
                self._member_pending.target_n
                if self._member_pending is not None
                else self._target_workers
            ),
            "membership_committed": self._member_committed_gen,
            "membership_refused": self._member_refused,
            "membership_refusals": self._member_refusal_nodes,
            "manifest_workers": self._mismatch_workers,
            # autoscale observability: this rank's published load signals and
            # the mirrored controller state (flap-lock visible in /healthz)
            "autoscale": _autoscale_signals(
                input_rows=(
                    self.prober_stats.input_rows
                    if self.prober_stats is not None
                    else None
                )
            ),
            "autoscaler": self._autoscale_state,
        }

    # -- elastic mesh membership (MEMBERSHIP_CHANGE; parallel/membership.py) ---

    def _membership_vote(self) -> "tuple | None":
        """Per-commit membership vote riding the neu allgather: the directive
        this rank has seen (so peers that have not read the file yet learn it
        FROM the vote) plus this rank's quiesce readiness."""
        cluster = self._cluster
        if (
            cluster is None
            or not getattr(cluster, "supports_rejoin", False)
            or self._supervise_dir is None
            or self._persistence is None
            or not self._persistence.supports_cluster_checkpoints
        ):
            return None
        now = time_mod.monotonic()
        if now - getattr(self, "_member_poll_at", 0.0) >= 0.25:
            self._member_poll_at = now
            from pathway_tpu.parallel.membership import read_directive

            d = read_directive(self._supervise_dir)
            if (
                d is not None
                and d.generation > self._member_done_gen
                and d.target_n != cluster.n
                and (
                    self._member_pending is None
                    or d.generation > self._member_pending.generation
                )
            ):
                self._member_pending = d
                self._member_attempts = 0
        if self._member_pending is None:
            return None
        return (self._member_pending.as_tuple(), self._membership_ready())

    def _membership_ready(self) -> bool:
        """Quiesce check: every reshardable live source paused at a scan
        boundary with nothing buffered and no segment in flight. Rank-local
        sources keep flowing — their rows stay where they are ingested."""
        self._membership_state = (
            "draining"
            if self._member_pending is not None
            and self._rank >= self._member_pending.target_n
            else "resharding"
        )
        ready = True
        for node, _ev in self._sources:
            source = node.config["source"]
            if source.is_finished():
                continue
            subject = getattr(source, "subject", None)
            if getattr(subject, "reshard_exports", None) is None:
                continue
            subject.reshard_pause()
            if not subject.reshard_idle(0.05):
                ready = False
                continue
            if not source.reshard_ready():
                ready = False
        return ready

    def _membership_unpause(self) -> None:
        for node, _ev in self._sources:
            subject = getattr(node.config["source"], "subject", None)
            resume = getattr(subject, "reshard_resume", None)
            if resume is not None:
                resume()

    def _membership_votes_seen(self, mvotes: "List[tuple | None]") -> None:
        """Fold the allgathered membership votes: adopt the newest directive
        and arm the transition when every rank is ready for the same
        generation."""
        from pathway_tpu.parallel.membership import MembershipDirective

        self._member_all_ready = False
        best: "tuple | None" = None
        for mv in mvotes:
            if mv is not None and (best is None or mv[0][0] > best[0]):
                best = mv[0]
        if best is None:
            return
        gen = int(best[0])
        if gen > self._member_done_gen and (
            self._member_pending is None
            or self._member_pending.generation < gen
        ):
            self._member_pending = MembershipDirective.from_tuple(best)
            self._member_attempts = 0
        if (
            self._member_pending is not None
            and self._member_pending.generation == gen
            and all(mv is not None and mv[0][0] == gen and mv[1] for mv in mvotes)
        ):
            self._member_all_ready = True

    def _membership_abort(
        self, directive: Any, reason: str, *, permanent: bool
    ) -> None:
        import logging

        from pathway_tpu.engine import telemetry
        from pathway_tpu.internals.config import env_float as _env_float

        telemetry.stage_add("cluster.reshard_aborts")
        log = logging.getLogger("pathway_tpu")
        if permanent or self._member_attempts >= max(
            1,
            int(_env_float("PATHWAY_MEMBERSHIP_MAX_ATTEMPTS", 3)),
        ):
            log.error(
                "rank %d: membership change to n=%d REFUSED (generation %d): %s",
                self._rank, directive.target_n, directive.generation, reason,
            )
            self._member_refused = (directive.generation, reason)
            self._member_done_gen = directive.generation
            self._member_pending = None
        else:
            log.warning(
                "rank %d: membership attempt %d to n=%d aborted (%s); will retry",
                self._rank, self._member_attempts, directive.target_n, reason,
            )
        self._membership_state = "stable"
        self._membership_unpause()
        self._publish_status(force=True)

    def _run_membership_transition(self) -> None:
        """The MEMBERSHIP_CHANGE state machine at a fully quiesced commit
        boundary (modeled first as ``membership_model`` in
        ``internals/protocol_models.py`` — the phases and their order follow
        the model exactly): preflight capability vote → handoff fragments
        (read-back verified) → durability-ack barrier → rank 0 commits the
        membership manifest (the atomic commit point) → journal compaction →
        final old-topology barrier → leavers release / members rewire +
        reset + import → install barrier with the joiners. A crash at ANY
        point either aborts cleanly (pre-manifest: the previous topology
        stands) or completes via restart-all at the new topology (the
        supervisor adapts -n off the typed mismatch reports)."""
        import logging

        from pathway_tpu.engine import telemetry
        from pathway_tpu.engine.profile import histogram
        from pathway_tpu.parallel import membership as ms

        directive = self._member_pending
        self._member_all_ready = False
        if directive is None:
            return
        cluster = self._cluster
        log = logging.getLogger("pathway_tpu")
        commit = self._commit - 1  # the just-completed, fully journaled commit
        gen = directive.generation
        old_n, new_n = cluster.n, directive.target_n
        leaving = self._rank >= new_n
        t0 = time_mod.monotonic()
        self._member_attempts += 1
        self._member_in_flight = True
        self._membership_state = "draining" if leaving else "resharding"
        telemetry.stage_add("cluster.reshard_attempts")
        # quiesce window: the commit loop is paused from here until resume —
        # the REST plane sheds with 429 + the expected remaining pause as an
        # honest Retry-After instead of letting clients hang on a paused
        # engine (engine/brownout.py; chaos-tested)
        from pathway_tpu.engine.brownout import get_brownout
        from pathway_tpu.engine.profile import histograms as _histograms

        _reshard_hist = _histograms().get("pathway_reshard_duration_seconds")
        get_brownout().enter_quiesce(
            _reshard_hist.quantile(0.5)
            if _reshard_hist is not None and _reshard_hist.count
            else 1.0
        )
        if self._recorder is not None:
            self._recorder.record_event(
                "membership",
                phase="begin",
                generation=gen,
                from_n=old_n,
                to_n=new_n,
                commit=commit,
                epoch=getattr(cluster, "epoch", 0),
            )
        self._publish_status(force=True)
        if self._chaos is not None:
            self._chaos.begin_scale_attempt()
            # a donor/leaver killed after the quiesce vote, before its
            # fragments are durable — the headline mid-handoff crash
            self._chaos.maybe_scale_kill(
                self._rank, "scale_drain_kill", generation=gen, commit=commit
            )
        try:
            # 1. preflight capability vote: can every rank re-partition all
            #    of its state? Any refusal aborts BEFORE anything mutates.
            plan = ms.compute_reshard_plan(self)
            refusals = list(plan.refusals)
            refusal_nodes = list(plan.refused_nodes)
            for sref in ms.preflight_sources(self, new_n, self._rank):
                refusals.append(sref)
                refusal_nodes.append(
                    {"node": None, "kind": "input", "reason": sref}
                )
            if self._chaos is not None and self._chaos.scale_fault(
                "scale_refused", self._rank
            ):
                # deterministic refusal injection: the autoscaler's typed
                # refusal-backoff path is exercised without needing a
                # non-reshardable graph in the test program
                refusals.append(
                    "chaos: injected preflight refusal (scale_refused)"
                )
                refusal_nodes.append(
                    {"node": None, "kind": "chaos", "reason": "scale_refused"}
                )
            # refusal observability: per-node reasons on /healthz + the
            # status file, a counter, and a flight event naming the kinds
            self._member_refusal_nodes = refusal_nodes
            if refusals:
                telemetry.stage_add("cluster.preflight_refuse")
                if self._recorder is not None:
                    self._recorder.record_event(
                        "preflight_refuse",
                        generation=gen,
                        kinds=sorted(
                            {str(r.get("kind")) for r in refusal_nodes}
                        ),
                        refusals=len(refusals),
                    )
            ok_votes = cluster.allgather(
                f"member:ready:{gen}:{commit}".encode(),
                refusals[0] if refusals else None,
            )
            bad = [r for r in ok_votes if r is not None]
            if bad:
                self._membership_abort(directive, bad[0], permanent=True)
                return
            # 2. handoff fragments: the reshard as an array redistribution —
            #    every keyed state array partitioned by its owner function
            #    and written per new owner, read-back verified. The default
            #    CHUNKED transport streams bounded mini-fragments (composed
            #    collective steps), keeping a donor's peak handoff memory
            #    O(chunk x peers); PATHWAY_RESHARD_TRANSPORT=gather restores
            #    the whole-fragment path (escape hatch + bench baseline).
            status = "ok"
            stats: Dict[str, int] = {"rows_handed_off": 0}
            frag_bytes = 0
            transport = (
                os.environ.get("PATHWAY_RESHARD_TRANSPORT", "chunked")
                .strip()
                .lower()
            )
            try:
                if transport == "gather":
                    fragments, stats = ms.build_fragments(
                        self, plan, new_n, commit, gen
                    )
                    frag_bytes = self._persistence.dump_reshard_fragments(
                        self._graph_sig, commit, fragments
                    )
                else:
                    chunk_iter, stats = ms.build_fragment_chunks(
                        self, plan, new_n, commit, gen
                    )
                    frag_bytes = self._persistence.dump_reshard_chunks(
                        self._graph_sig, commit, chunk_iter
                    )
            except (ConnectionError, OSError, ValueError) as exc:
                status = f"transient: {exc}"
            acks = cluster.allgather(f"member:ack:{gen}".encode(), status)
            if any(a != "ok" for a in acks):
                self._membership_abort(
                    directive,
                    next(a for a in acks if a != "ok"),
                    permanent=False,
                )
                return
            # 3. the atomic commit point: rank 0 commits the membership
            #    manifest (workers = new_n), read-back verified
            ok0 = True
            if self._rank == 0:
                ok0 = self._persistence.commit_membership_manifest(
                    self._graph_sig,
                    commit,
                    epoch=directive.epoch,
                    from_n=old_n,
                    to_n=new_n,
                    generation=gen,
                )
                if ok0:
                    # supervisor-visible commit marker: a crash from here on
                    # recovers at the NEW topology
                    self._member_committed_gen = gen
                    self._publish_status(force=True)
            oks = cluster.allgather(f"member:done:{gen}".encode(), bool(ok0))
            if not all(oks):
                self._membership_abort(
                    directive, "membership manifest commit failed (torn write)",
                    permanent=False,
                )
                return
            # 4. committed: adopt the new worker count for every later
            #    journal header/snapshot/manifest, and compact this shard
            #    (frames <= C are subsumed by the fragments; compaction is
            #    FORCED — the manifest+tail handoff contract depends on it)
            self._manifest_commit = commit
            self._member_committed_gen = gen
            self._persistence.set_workers(new_n)
            self._persistence.compact_journal(self._graph_sig)
            self._persistence.cleanup_cluster_checkpoints(commit)
            # any previously restored park is superseded by the fragments
            # (leavers write their NEW park after this point, at release)
            self._persistence.clear_source_park()
            cluster.prune_commit_log(commit)
            self._undo_current = None
            self._last_checkpoint = time_mod.monotonic()
            # 5. final old-topology barrier: nobody tears down or rewires
            #    until every old rank is past the commit point
            cluster.allgather(f"member:cut:{gen}".encode(), None)
            rows_out = int(stats.get("rows_handed_off", 0))
            telemetry.stage_add_many({
                "cluster.reshard_rows_handed_off": float(rows_out),
                "cluster.reshard_fragment_bytes": float(frag_bytes),
            })
            if leaving:
                # 6L. leaver release: fragments durable + manifest committed
                #     (the model's release-after-drain invariant). Park the
                #     rank-local source continuation for a future joiner
                #     reusing this rank id, retract delivered rows from the
                #     live sinks, and leave the mesh.
                park = {
                    nid: {
                        k: v
                        for k, v in offs.items()
                        if k != "state_deltas"
                    }
                    for nid, offs in (
                        (node.id, node.config["source"].offset_state())
                        for node, _ev in self._sources
                    )
                }
                self._persistence.dump_source_park(
                    self._graph_sig, commit, {"offsets": park}
                )
                self._deliver_sink_retractions()
                self._membership_state = "drained"
                self._membership_left = True
                self._publish_status(force=True)
                cluster.leave_membership()
                duration = time_mod.monotonic() - t0
                telemetry.stage_add("cluster.reshard_drained")
                log.warning(
                    "rank %d: drained for scale-down to n=%d (generation %d) "
                    "in %.2fs — %d row(s) handed off",
                    self._rank, new_n, gen, duration, rows_out,
                )
                if self._recorder is not None:
                    self._recorder.record_event(
                        "membership", phase="drained", generation=gen,
                        to_n=new_n, duration_s=round(duration, 3),
                    )
                return
            # 6S. survivor: retract EVERYTHING previously delivered while the
            #     old state is still present — step 9 re-delivers the full
            #     imported snapshot, so sinks see one clean retract/re-add
            #     cycle (diff-folding consumers net exactly; retracting only
            #     the moved rows would double-deliver the kept ones)
            self._deliver_sink_retractions()
            # 7. rewire the mesh: install joiner links / cut leaver links,
            #    adopt the new epoch (stale frames purge; future-epoch frames
            #    from faster members deliver — the model's install step)
            cluster.apply_membership(
                new_n,
                directive.epoch,
                on_wait=lambda: self._publish_status(force=True),
            )
            # 8. flip the process-wide topology: connectors and late
            #    PersistenceManager readers see the new count
            os.environ["PATHWAY_PROCESSES"] = str(new_n)
            # 9. sources adopt the new shard map (moved scan state dropped
            #    WITHOUT retractions, gained scan state absorbed), then
            #    evaluator/state-table state resets and re-imports this
            #    rank's fragments — the live path and the crash-recovery
            #    path share one loader
            my_frags = self._persistence.load_reshard_fragments(
                self._graph_sig, commit, self._rank, old_n
            )
            _offs, gained = ms.merge_fragment_sources(my_frags)
            for node, _ev in self._sources:
                source = node.config["source"]
                subject = getattr(source, "subject", None)
                if getattr(subject, "reshard_apply", None) is not None:
                    subject.reshard_apply(new_n, self._rank)
                    source.reshard_scrub(new_n, self._rank)
                deltas = gained.get(node.id)
                if deltas:
                    source.reshard_absorb(deltas)
            self._reset_operator_state()
            ms.import_fragments(self, my_frags)
            self._deliver_sink_snapshots()
            self._membership_unpause()
            # 10. install barrier with the joiners (their setup blocks on it)
            cluster.allgather(f"member:install:{gen}".encode(), None)
            self._commit = commit + 1
            self._member_done_gen = gen
            self._member_pending = None
            self._membership_state = "stable"
            self._target_workers = new_n
            # loop realignment: this transition ran INSIDE step(C); a joiner's
            # first action is a full step(C+1), so this member must go
            # straight to step(C+1) too — the run loop skips its done-vote
            # for this iteration
            self._member_resumed = True
            duration = time_mod.monotonic() - t0
            histogram("pathway_reshard_duration_seconds").observe(duration)
            telemetry.stage_add("cluster.reshard_applied")
            if self._recorder is not None:
                self._recorder.record_event(
                    "membership",
                    phase="applied",
                    generation=gen,
                    from_n=old_n,
                    to_n=new_n,
                    epoch=getattr(cluster, "epoch", 0),
                    duration_s=round(duration, 3),
                    rows_handed_off=rows_out,
                )
            log.warning(
                "rank %d: membership transition to n=%d complete (generation "
                "%d, epoch %d) in %.2fs — %d row(s) handed off, %d fragment "
                "byte(s)",
                self._rank, new_n, gen, getattr(cluster, "epoch", 0),
                duration, rows_out, frag_bytes,
            )
            self._publish_status(force=True)
        finally:
            import sys as _sys

            get_brownout().exit_quiesce()
            if _sys.exc_info()[0] is None:
                self._member_in_flight = False
            else:
                # an exception is unwinding: LEAVE the in-flight flag set so
                # _surgical_rejoin declines (a mid-transition peer death must
                # reach the supervisor typed — it restarts all at whichever
                # topology committed), and leave a visible trace first
                self._publish_status(force=True)

    def _deliver_sink_retractions(self) -> None:
        """Feed each live sink a retraction of EVERY row it was delivered
        (its input's full pre-transition state). Paired with the
        post-import snapshot delivery this gives sinks one clean
        retract/re-add cycle across the reshard: diff-folding consumers net
        exactly, rows that moved re-appear at their new owner, and rows
        that stayed are re-asserted — the same contract restored
        checkpoints already give sinks."""
        from pathway_tpu.engine.evaluators import OutputEvaluator

        if not self.replay_outputs:
            return
        for node in self._nodes:
            evaluator = self.evaluators.get(node.id)
            if not isinstance(evaluator, OutputEvaluator):
                continue
            inp = node.inputs[0]._node
            state = self.states.get(inp.id)
            if state is None or inp.id not in self._materialized:
                continue
            snap = state.snapshot()
            if not len(snap):
                continue
            retraction = Delta(
                snap.keys,
                -np.ones(len(snap), dtype=np.int64),
                dict(snap.columns),
            )
            evaluator.process([retraction])

    # -- surgical single-rank restart (epoch fence; parallel/cluster.py) -------

    def _surgical_rejoin(self, exc: BaseException) -> bool:
        """Recover from a typed peer failure without dying: quiesce at the
        epoch fence, wait for the supervisor's replacement rank to re-dial,
        roll this rank's operator state back to its own journal shard, and
        lockstep-replay the union of journaled commit ids so every rank —
        survivors and replacement alike — converges on the last cluster-wide
        committed state. Output stays bit-identical to a failure-free run: the
        interrupted commit's drained-but-unjournaled input rows are carried
        across the fence and re-ingested exactly once.

        Returns False when surgical recovery is off or impossible — no
        persistence journal (nothing to roll back to: refused loudly, the
        caller re-raises the typed error within the barrier deadline), a
        thread-mode exchange, replay in progress — or when the fence itself
        fails (second death, no replacement in time): the caller re-raises and
        the supervisor escalates to restart-all, then loud teardown."""
        cluster = self._cluster
        if (
            not self._surgical
            or cluster is None
            or not getattr(cluster, "supports_rejoin", False)
            or self._supervise_dir is None
            or self._persistence is None
            or self._inject is not None
            # a peer death INSIDE a membership transition cannot be healed by
            # a single-rank rejoin (the topology itself is in flight): die
            # typed, the supervisor restarts all at whichever topology the
            # membership manifest committed
            or self._member_in_flight
        ):
            return False
        import logging

        log = logging.getLogger("pathway_tpu")
        t0 = time_mod.monotonic()
        self._rejoin_state = "fencing"
        log.warning(
            "rank %d: peer failure at commit %d (%s); quiescing at the epoch "
            "fence for a surgical rejoin",
            self._rank,
            self._commit,
            exc,
        )
        if self._recorder is not None:
            # the interrupted commit is the post-mortem's subject: dump before
            # the rollback resets state (a failed rejoin dies typed after this)
            self._recorder.record_event(
                "fence",
                commit=self._commit,
                epoch=getattr(cluster, "epoch", 0),
                error=str(exc),
            )
            self._recorder.dump("fence")
        # preserve the interrupted commit's drained input rows IFF its journal
        # frame never made it to disk — journaled rows replay from the journal,
        # carrying them too would double-ingest
        if (
            self._input_deltas_commit == self._commit
            and getattr(self._persistence, "last_commit_id", None) != self._commit
        ):
            for nid, delta in self._input_deltas.items():
                if len(delta):
                    prev = self._rejoin_carry.get(nid)
                    self._rejoin_carry[nid] = (
                        Delta.concat([prev, delta], list(delta.columns.keys()))
                        if prev is not None and len(prev)
                        else delta
                    )
            for node, _ in self._sources:
                rewind = getattr(node.config["source"], "rewind_frame_state", None)
                if rewind is not None:
                    # segment markers drained by the aborted commit re-ride the
                    # next journaled frame
                    rewind()
        # the interrupted commit's partial serve-log entry must never be
        # replayed to a peer (its tags are regenerated live after recovery)
        discard_log = getattr(cluster, "discard_open_commit_log", None)
        if discard_log is not None:
            discard_log()
        from pathway_tpu.parallel.cluster import PeerShutdownError, PeerTimeoutError

        try:
            cluster.begin_fence()
            cluster.await_rejoin(on_wait=lambda: self._publish_status(force=True))
        except (PeerShutdownError, PeerTimeoutError, OSError) as fence_exc:
            self._rejoin_state = "running"
            log.error(
                "rank %d: surgical rejoin failed (%s); dying typed so the "
                "supervisor can degrade to restart-all or tear down",
                self._rank,
                fence_exc,
            )
            return False
        self._rejoin_state = "rejoining"
        self._publish_status(force=True)
        # Recovery rungs, cheapest first (escalation: rewind → checkpoint+tail
        # replay → full journal replay; the supervisor's restart-all and loud
        # teardown sit below). The journal was compacted at the last cluster
        # checkpoint, so reload() and the replay union are bounded by the tail.
        frames = self._persistence.reload(self._graph_sig)
        manifest = self._persistence.load_cluster_manifest(self._graph_sig)
        base: "int | None" = None
        if manifest is not None:
            base = int(manifest["commit_id"])
            self._manifest_commit = base
            # belt and braces: a crash after the manifest barrier but before
            # this rank's compaction leaves subsumed frames behind
            frames = [f for f in frames if f[0] > base]
        floor = base + 1 if base is not None else 0
        local_frames = {cid: deltas for cid, deltas, _offs in frames}
        all_ids = self._cluster_replay_ids(local_frames)
        from pathway_tpu.engine import telemetry
        from pathway_tpu.internals.config import get_pathway_config

        # Rung coordination. Serving logged barrier parts is only equivalent to
        # step-replaying a tail commit when every rank's live inputs for that
        # commit made it into a journal frame. A survivor interrupted mid-commit
        # BEFORE journaling carries its drained rows across the fence instead —
        # if a peer still journaled that commit (barrier skew of one commit is
        # possible: the dead rank's last sends can reach one survivor and not
        # another), the replayed commit diverges from the logged one, and
        # everyone must step-replay from a reset. Each rank votes the id of its
        # unjournaled interrupted commit (None when clean); any vote naming a
        # journaled tail commit forces rung 2 cluster-wide. The vote is a
        # dedicated barrier so replacements (which always step) stay aligned.
        interrupted = (
            self._commit
            if (
                self._input_deltas_commit == self._commit
                and getattr(self._persistence, "last_commit_id", None)
                != self._commit
            )
            else None
        )
        mode_votes = cluster.allgather(b"replay:mode", interrupted)
        tail_clean = all(
            v is None or not all_ids or v > all_ids[-1] for v in mode_votes
        )
        rewound = (
            self._undo_depth > 0
            and self._rewind_safe
            and tail_clean
            # a live in-flight record must be for THIS commit (a mismatch means
            # bookkeeping drifted — reset rather than mis-undo); None is fine:
            # the failure hit between commits, state is complete as-is
            and (
                self._undo_current is None
                or self._undo_current["commit"] == self._commit
            )
            # batch-mode replay collapses frames into one renumbered commit —
            # a shape the per-commit serve log cannot reproduce
            and get_pathway_config().persistence_mode != "batch"
            and cluster.commit_log_covers(all_ids)
        )
        if rewound:
            # rung 1 — incremental rewind: this rank's state is current except
            # for the interrupted commit, which is undone IN PLACE from the
            # retained undo record; the replacement's tail replay is then
            # served from the logged barriers instead of re-stepping anything
            self._undo_interrupted_commit()
            for cid in all_ids:
                cluster.serve_commit_log(cid)
            self._commit = all_ids[-1] + 1 if all_ids else floor
            telemetry.stage_add("cluster.rejoin_rewinds")
        else:
            # rung 2/3 — the interrupted commit left partially-applied state
            # that (here) cannot be unwound in place: reset, restore this
            # rank's snapshot from the latest cluster checkpoint (rung 2; full
            # journal replay when none exists — rung 3), and lockstep-replay
            # the union of journaled tail ids, exactly like a relaunched
            # process — minus the process launch, imports, and source re-scan
            self._undo_current = None
            self._reset_operator_state()
            if base is not None:
                if manifest.get("membership"):
                    # the newest checkpoint is a membership manifest: this
                    # rank's snapshot is its handoff-fragment set
                    from pathway_tpu.parallel.membership import import_fragments

                    import_fragments(
                        self,
                        self._persistence.load_reshard_fragments(
                            self._graph_sig, base, self._rank,
                            int(manifest["membership"]["from_n"]),
                        ),
                    )
                    self._deliver_sink_snapshots()
                else:
                    self._load_checkpoint_state(
                        self._persistence.load_cluster_snapshot(
                            self._graph_sig, base
                        )
                    )
                self._commit = base + 1
            was_ready, self._ready = self._ready, False  # replay parity with setup
            try:
                self._cluster_replay_steps(local_frames, all_ids, floor)
            finally:
                self._ready = was_ready
            telemetry.stage_add("cluster.rejoin_resets")
        self._rejoins += 1
        self._last_rejoin_s = time_mod.monotonic() - t0
        self._rejoin_state = "running"
        from pathway_tpu.engine.profile import histogram

        # recovery-SLO instrumentation: rejoin latency distribution + the
        # journal-tail length this recovery had to cover
        histogram("pathway_rejoin_duration_seconds").observe(self._last_rejoin_s)
        telemetry.stage_add("cluster.rejoin_tail_commits", float(len(all_ids)))
        if self._recorder is not None:
            self._recorder.record_event(
                "rejoin",
                epoch=getattr(cluster, "epoch", 0),
                duration_s=self._last_rejoin_s,
                mode="rewind" if rewound else (
                    "checkpoint+tail" if base is not None else "full-replay"
                ),
                tail_commits=len(all_ids),
            )
        self._publish_status(force=True)
        log.warning(
            "rank %d: rejoined the cluster at epoch %d in %.2fs via %s "
            "(resuming at commit %d, %d tail commit(s))",
            self._rank,
            getattr(cluster, "epoch", 0),
            self._last_rejoin_s,
            "incremental rewind" if rewound else (
                "checkpoint+tail replay" if base is not None else "full journal replay"
            ),
            self._commit,
            len(all_ids),
        )
        return True

    def _capture_undo_state(self, node: Any, evaluator: Any) -> None:
        """Pre-mutation operator snapshot for the incremental-rewind undo
        record. Input evaluators are excluded (a source cannot un-consume;
        the fence's carry re-ingests the interrupted commit's drained rows)
        and output evaluators are stateless sinks — matching the checkpoint
        snapshot's exclusions. Unpicklable or oversized state disables the
        rewind rung permanently for this run; rung 2 (checkpoint + tail
        replay) stays exact."""
        from pathway_tpu.engine.evaluators import (
            InputEvaluator,
            OutputEvaluator,
            UnpicklableStateError,
        )

        if isinstance(evaluator, (InputEvaluator, OutputEvaluator)):
            return
        rec = self._undo_current
        _t0 = time_mod.perf_counter()
        try:
            state = evaluator.state_dict()
        except UnpicklableStateError as exc:
            self._disable_rewind(str(exc))
            return
        rec["capture_s"] += time_mod.perf_counter() - _t0
        rec["evals"][node.id] = state
        rec["bytes"] += sum(len(b) for b in state.values())
        if self._undo_max_bytes and rec["bytes"] > self._undo_max_bytes:
            self._disable_rewind(
                f"per-commit undo state hit PATHWAY_UNDO_MAX_STATE_BYTES "
                f"({rec['bytes']} > {self._undo_max_bytes}); re-pickling this "
                "much state every commit would cost more than the tail replay "
                "it avoids"
            )

    def _disable_rewind(self, reason: str) -> None:
        """Turn the rewind rung off for the rest of this run (the condition —
        unpicklable or oversized operator state — recurs every commit). The
        serve log is dropped too: a rank that must reset on a fence recomputes
        its barrier parts live, so logging them is dead weight."""
        import logging

        logging.getLogger("pathway_tpu").warning(
            "incremental rewind disabled for this run: %s — fences fall back "
            "to checkpoint + journal-tail replay",
            reason,
        )
        self._rewind_safe = False
        self._undo_depth = 0
        self._undo_current = None
        cluster = self._cluster
        if cluster is not None and hasattr(cluster, "discard_open_commit_log"):
            cluster.discard_open_commit_log()
            cluster.prune_commit_log(self._commit)
            cluster.commit_log_depth = 0
        from pathway_tpu.engine import telemetry

        telemetry.stage_add("cluster.rewind_disabled")

    def _undo_interrupted_commit(self) -> None:
        """Rung-1 rollback: invert the interrupted commit's applied state-table
        deltas (in reverse order) and restore the pre-mutation evaluator
        snapshots captured before each operator ran. Exact by construction —
        ``Delta.negated()`` of an applied delta removes precisely the rows it
        inserted and re-inserts the rows it retracted (retraction rows carry
        their values). A COMPLETED commit never reaches here: its record is
        dropped the moment its mutations become final (see ``step``)."""
        rec, self._undo_current = self._undo_current, None
        if rec is None or rec["commit"] != self._commit:
            return  # the failure hit between commits: nothing was applied
        for nid, delta in reversed(rec["applied"]):
            self.states[nid].apply(delta.negated())
        for nid, blobs in rec["evals"].items():
            self.evaluators[nid].load_state_dict(blobs)
        self._substep_deltas = {}
        self._input_deltas = {}
        self._input_deltas_commit = -1
        self._step_counts = {}
        from pathway_tpu.engine import telemetry

        telemetry.stage_add("cluster.commits_rewound")

    def _reset_operator_state(self) -> None:
        """Discard every evaluator and state table and rebuild them pristine
        from the graph (the rejoin rollback: in-memory state from the
        interrupted epoch is unrecoverable once a commit half-applied).
        Sources are NOT reset — a survivor's connectors are live and correctly
        positioned; everything they ever emitted is either journaled (replays)
        or carried in ``_rejoin_carry``."""
        from pathway_tpu.engine.evaluators import EVALUATORS

        self.evaluators = {}
        self.states = {}
        for node in self._nodes:
            self.evaluators[node.id] = EVALUATORS[type(node)](node, self)
            columns = node.output.column_names() if node.output is not None else []
            self.states[node.id] = StateTable(columns)
        self._bind_cluster_policies()
        self._sources = [(node, self.evaluators[node.id]) for node, _ in self._sources]
        self._materialized = self._compute_materialized()
        self._substep_deltas = {}
        self._input_deltas = {}
        self._input_deltas_commit = -1
        self._step_counts = {}

    def output_columns_of(self, node: pg.Node) -> List[str]:
        return node.output.column_names() if node.output is not None else []

    def sources_finished(self) -> bool:
        return all(node.config["source"].is_finished() for node, _ in self._sources)

    def primary_sources_finished(self) -> bool:
        return all(
            node.config["source"].is_finished()
            for node, _ in self._sources
            if not getattr(node.config["source"], "loopback", False)
        )

    def subtree_closed(self, node: pg.Node) -> bool:
        """Frontier check: True when ``node``'s operator subtree can emit no further
        delta in any future commit (all ancestor sources finished, no pending operator
        state anywhere in the subtree). The TPU-native stand-in for the reference's
        frontier tracking (timely progress; ``TotalFrontier``, ``src/engine/frontier.rs``):
        downstream operators use it to stop maintaining state that can never be probed
        again. Conservative: returns False under journal replay, persistence, cluster
        mode, and nested iterate runners, where closure is not locally decidable."""
        if (
            self._materialize_all
            or self._inject is not None
            or self._persistence is not None
            or self._cluster is not None
        ):
            return False
        cache = getattr(self, "_closed_cache", None)
        if cache is None or cache[0] != self._commit:
            cache = (self._commit, {})
            self._closed_cache = cache
        memo = cache[1]
        if node.id in memo:
            return memo[node.id]
        memo[node.id] = False  # cycle guard (loop-back chains stay open)
        closed = True
        if isinstance(node, pg.InputNode):
            closed = node.config["source"].is_finished()
        else:
            evaluator = self.evaluators.get(node.id)
            if evaluator is not None and (
                _has_pending(evaluator)
                or getattr(evaluator, "neu_pending", _no_pending)()
            ):
                closed = False
            else:
                closed = all(self.subtree_closed(inp._node) for inp in node.inputs)
        memo[node.id] = closed
        return closed

    def _ancestor_inputs(self, node: pg.Node) -> list:
        """Transitive InputNodes feeding ``node`` (memoized)."""
        cache = getattr(self, "_ancestor_cache", None)
        if cache is None:
            cache = self._ancestor_cache = {}
        if node.id in cache:
            return cache[node.id]
        cache[node.id] = []  # cycle guard (loop-back chains)
        out: list = []
        if isinstance(node, pg.InputNode):
            out.append(node)
        for inp in node.inputs:
            out.extend(self._ancestor_inputs(inp._node))
        cache[node.id] = out
        return out

    def _notify_stream_end(self) -> None:
        """Deliver on_end to each subscriber whose ENTIRE input ancestry is final —
        including loop-back sources, so a subscriber downstream of an
        AsyncTransformer hears the end only after in-flight invocations drained
        (and a chained transformer closes cascade-style). Re-checked every idle
        iteration; each evaluator fires once."""
        from pathway_tpu.engine.evaluators import OutputEvaluator

        for node in self._nodes:
            evaluator = self.evaluators.get(node.id)
            if not isinstance(evaluator, OutputEvaluator):
                continue
            if all(
                a.config["source"].is_finished() for a in self._ancestor_inputs(node)
            ):
                evaluator.notify_stream_end()

    def has_pending(self) -> bool:
        return any(_has_pending(e) for e in self.evaluators.values())

    def finish(self) -> None:
        from pathway_tpu.engine.evaluators import OutputEvaluator, WithUniverseOfEvaluator

        for node, _ in self._sources:
            # graceful producer shutdown (streaming subjects poll this between
            # refresh cycles — e.g. the airbyte sync loop)
            subject = getattr(node.config["source"], "subject", None)
            stop = getattr(subject, "stop", None)
            if stop is not None:
                stop()
        for node in self._nodes:
            evaluator = self.evaluators.get(node.id)
            if isinstance(evaluator, OutputEvaluator):
                if not self._shared_nonroot:
                    # transparent-threads rank > 0 shares rank 0's sink objects;
                    # only rank 0 may fire their on_end notifications
                    evaluator.finish()
            elif isinstance(evaluator, WithUniverseOfEvaluator):
                evaluator.verify_universes()
        if self._persistence is not None:
            self._persistence.close()
        if self._monitor is not None:
            self._monitor.close()
        if self._http_server is not None:
            self._http_server.close()
            self._http_server = None
        # stop idle device-service workers (drain + join): teardown must not
        # leave a device-owning thread behind a finished run — services stay
        # usable, the worker respawns lazily on the next submit. Module never
        # imported = no services exist = nothing to stop.
        import sys as _sys

        svc_mod = _sys.modules.get("pathway_tpu.models.device_worker")
        if svc_mod is not None:
            try:
                svc_mod.stop_all_workers()
            except Exception:
                pass
        # final trace flush (no-op when tracing is off or no dir is known);
        # crash/fence/chaos paths flush via the flight recorder's dump instead
        trace_path = _tracing.get_tracer().flush(reason="finish")
        if trace_path is not None and self._recorder is not None:
            self._recorder.record_event("trace_flush", path=trace_path)

    def _lint_gate(self, *, persistence: bool) -> None:
        """Automatic graph lint before the first commit, gated by
        ``PATHWAY_LINT=off|warn|error`` (default ``warn``). Diagnostics are
        logged, mirrored into the stage counters + flight recorder, and under
        ``error`` an error-severity finding refuses the run (GraphLintError)."""
        import logging

        # the runtime's OWN concurrency (PWA101-104) and resource/exception
        # (PWA201-205) gates ride here too but are independent knobs:
        # PATHWAY_LINT=off must not disarm them. Both default off — the
        # runtime tree changes with the package, not the user program, so CI
        # runs `cli analyze --runtime` instead of every pw.run paying a
        # re-parse
        from pathway_tpu.analysis import resource_gate, runtime_gate

        runtime_gate()
        resource_gate()
        mode = os.environ.get("PATHWAY_LINT", "warn").strip().lower()
        if mode in ("off", "0", "false", "no", "none", ""):
            return
        if mode not in ("warn", "error"):
            # a typo (PATHWAY_LINT=errors) must not silently disarm the gate
            logging.getLogger("pathway_tpu.analysis").warning(
                "unrecognized PATHWAY_LINT=%r (expected off|warn|error); "
                "falling back to 'warn' — errors will NOT refuse the run",
                mode,
            )
            mode = "warn"
        if getattr(self, "_lint_done", False):
            return
        self._lint_done = True
        from pathway_tpu.analysis import GraphLintError, analyze_graph

        # one DAG walk per runner: the same AnalysisContext feeds the fusion
        # planner in setup() (building two contexts per pw.run was a full
        # duplicate walk of consumer maps + upstream sets)
        report = analyze_graph(
            self.graph,
            persistence=persistence,
            ctx=self._analysis_context(persistence=persistence),
        )
        report.emit_telemetry()
        if report.diagnostics:
            log = logging.getLogger("pathway_tpu.analysis")
            for d in report.errors + report.warnings:
                log.warning("%s", d.format())
            for d in report.infos:
                log.info("%s", d.format())
        if mode == "error" and report.errors:
            raise GraphLintError(report)

    def run(
        self,
        *,
        monitoring_level: Any = None,
        with_http_server: bool = False,
        terminate_on_error: bool = True,
        max_commits: int | None = None,
        persistence_config: Any = None,
        **kwargs: Any,
    ) -> None:
        from pathway_tpu.internals.config import get_pathway_config

        env_cfg = get_pathway_config()
        # persistence may also arrive via the record/replay env contract
        # (PATHWAY_REPLAY_STORAGE, applied below) — the persistence-gated lint
        # passes (PWA002 severity, PWA005) must see it either way
        lint_persistence = persistence_config is not None or bool(
            env_cfg.replay_storage
        )
        lint_exempt = getattr(self, "lint_exempt", False)
        if not lint_exempt and os.environ.get("PATHWAY_LINT_CAPTURE", "") not in (
            "",
            "0",
        ):
            # `cli analyze` build-only mode: the graph is complete, hand it to
            # the analyzer without executing a single commit (debug capture
            # helpers are exempt so the analyzed program runs past them to its
            # real ``pw.run``)
            from pathway_tpu.analysis import GraphCaptureInterrupt

            raise GraphCaptureInterrupt(self.graph, persistence=lint_persistence)
        if not lint_exempt and not self._ready and not self._materialize_all:
            from pathway_tpu.parallel.cluster import (
                in_thread_worker,
                thread_worker_rank,
                thread_worker_shared_inputs,
            )

            if not in_thread_worker():
                self._lint_gate(persistence=lint_persistence)
            elif not thread_worker_shared_inputs() and thread_worker_rank() == 0:
                # run_shared_graph workers re-run the one graph the parent
                # already linted — skip. run_threads workers each build and run
                # their OWN graph with no parent run: rank 0's graph is
                # representative, lint it once instead of N times
                self._lint_gate(persistence=lint_persistence)
        if env_cfg.threads > 1 and not self._ready:
            from pathway_tpu.parallel.cluster import in_thread_worker

            if not in_thread_worker():
                # PATHWAY_THREADS lane: fan this run out over worker threads
                # (one shared graph; sources rank 0, compute key-partitioned,
                # outputs centralized — identical output to a 1-thread run)
                if env_cfg.processes > 1:
                    raise NotImplementedError(
                        "PATHWAY_THREADS > 1 combined with PATHWAY_PROCESSES > 1 "
                        "(thread workers inside each spawned process) needs a "
                        "hierarchical exchange that is not built; use spawn -n "
                        "for multi-process or -t for multi-thread"
                    )
                from pathway_tpu.parallel.threads import run_shared_graph

                run_shared_graph(
                    self.graph,
                    env_cfg.threads,
                    dict(
                        monitoring_level=monitoring_level,
                        with_http_server=with_http_server,
                        terminate_on_error=terminate_on_error,
                        max_commits=max_commits,
                        persistence_config=persistence_config,
                        **kwargs,
                    ),
                )
                return
        if env_cfg.processes > 1:
            from pathway_tpu.parallel.mesh import require_cpu_platform

            require_cpu_platform(f"rank {env_cfg.process_id} of {env_cfg.processes}")
        if persistence_config is None and env_cfg.replay_storage:
            # `pathway_tpu spawn --record` / `replay` contract (reference cli.py:166-284)
            from pathway_tpu import persistence as _pers

            persistence_config = _pers.Config(
                _pers.Backend.filesystem(env_cfg.replay_storage)
            )
        from pathway_tpu.engine.http_server import ProberStats, maybe_start_http_server

        self.prober_stats = ProberStats()
        self._http_server = maybe_start_http_server(self.prober_stats, with_http_server)
        from pathway_tpu.engine.telemetry import MetricsRecorder, span

        self._metrics = MetricsRecorder.get(self.prober_stats)

        try:
            if not self._ready:
                with span("graph_runner.build", nodes=len(self.graph.nodes)):
                    self.setup(monitoring_level, persistence_config=persistence_config)
        except BaseException as exc:
            from pathway_tpu.parallel.membership import MembershipMismatchError

            if isinstance(exc, MembershipMismatchError):
                # the store committed a membership transition this launch does
                # not match: publish manifest_n so the supervisor adapts -n
                self._mismatch_workers = exc.manifest_n
                self._membership_state = "membership_mismatch"
                self._publish_status(force=True)
            # a failed build must not leak the just-bound monitoring listener:
            # the caller may fix the config and rerun in this same process
            if self._http_server is not None:
                self._http_server.close()
                self._http_server = None
            raise
        if self._http_server is not None:
            self._http_server.health_source = self.health
        if env_cfg.snapshot_access == "replay" and not env_cfg.continue_after_replay:
            # replay-only run: the journal has been fed through the graph in setup();
            # stop without consuming realtime connector data
            self.finish()
            return
        from pathway_tpu.engine import expression_evaluator as ee_mod

        runtime = ee_mod.get_runtime()
        prev_runtime = dict(runtime)
        runtime["terminate_on_error"] = terminate_on_error
        # fallback sink for operators with no local log; nested iterate runners run on
        # this thread and inherit it, while their inner node objects route precisely
        runtime["global_source"] = getattr(self.graph, "_error_log_source", None)
        from pathway_tpu.engine.datasource import StreamingDataSource

        # idle pacing: a producer's push wakes the loop at once (latency = wake + one
        # commit), events a source holds back inside its autocommit window are taken
        # when the window ends, and otherwise the loop looks again every 10 ms. It
        # does not tick at the smallest autocommit interval: an idle step is most of
        # a millisecond of Python under the interpreter lock, and at the REST
        # connector's 1 ms it took the lock from the generation service's thread
        # while a request generated outside any commit (a decode step's host time
        # 1.0 -> 2.2 ms on the chip, PERF.md section 6, PR 29). The wake event is
        # per-runner so concurrent loops never consume each other's signals.
        idle_wait = 0.010
        streams = [
            node.config["source"]
            for node, _ in self._sources
            if isinstance(node.config["source"], StreamingDataSource)
        ]

        def idle_timeout() -> float:
            held = [at for at in (s.release_at() for s in streams) if at is not None]
            if not held:
                return idle_wait
            # never 0: a step that leaves queued events where they are must not spin
            return min(idle_wait, max(0.0005, min(held) - time_mod.monotonic()))

        import threading as _threading

        wake = _threading.Event()
        StreamingDataSource.register_runner(wake)
        from pathway_tpu.parallel.cluster import PeerShutdownError, PeerTimeoutError

        # flight-recorder SIGTERM hook: a supervisor stall-kill (SIGTERM grace
        # before SIGKILL) or operator shutdown leaves a dump behind. Main
        # thread only — signal.signal raises ValueError elsewhere.
        import signal as _signal

        _prev_term: Any = None
        _installed_term = False
        if self._recorder is not None and self._recorder.enabled:
            def _on_term(signum: int, frame: Any) -> None:
                self._recorder.dump("sigterm")
                # chain: restore whatever was there — including SIG_IGN (a
                # process that deliberately ignored SIGTERM must keep
                # ignoring it) — and re-raise so the previous disposition
                # (default termination, operator handler, or ignore) applies
                _signal.signal(
                    _signal.SIGTERM,
                    _prev_term if _prev_term is not None else _signal.SIG_DFL,
                )
                os.kill(os.getpid(), _signal.SIGTERM)

            try:
                _prev_term = _signal.signal(_signal.SIGTERM, _on_term)
                _installed_term = True
            except ValueError:
                pass  # not the main thread

        commits = 0
        try:
            with span("graph_runner.run"):
                while True:
                    wake.clear()
                    try:
                        any_output = self.step()
                    except (PeerShutdownError, PeerTimeoutError) as exc:
                        # a peer died mid-commit: with surgical mode on, quiesce
                        # at the epoch fence, take the relaunched rank back in,
                        # roll back the interrupted commit, and keep running —
                        # otherwise die typed (PR 2 restart-all/teardown)
                        if self._surgical_rejoin(exc):
                            continue
                        raise
                    if self._membership_left:
                        # this rank drained away in a scale-down: its handoff
                        # is durable, its journal shard compacted empty — a
                        # clean exit the supervisor expects
                        break
                    commits += 1
                    if getattr(self, "_member_resumed", False):
                        # a membership transition completed inside that step:
                        # joiners enter the lockstep loop with a full step at
                        # C+1, so skip this iteration's done-vote and step
                        # again immediately — every member's barrier tag
                        # sequence realigns at commit C+1
                        self._member_resumed = False
                        continue
                    if max_commits is not None and commits >= max_commits:
                        break
                    if (
                        self.primary_sources_finished()
                        and not any_output
                        and not self.has_pending()
                        # cluster peers may still route rows here; finish() notifies
                        and self._cluster is None
                    ):
                        self._notify_stream_end()
                    local_done = (
                        self.sources_finished() and not any_output and not self.has_pending()
                    )
                    if self._cluster is not None:
                        # lockstep shutdown: stop only when EVERY process drained
                        # (a peer's data may still route rows to us)
                        try:
                            done_votes = self._cluster.allgather(
                                f"done:{self._commit}".encode(), local_done
                            )
                        except (PeerShutdownError, PeerTimeoutError) as exc:
                            if self._surgical_rejoin(exc):
                                continue
                            raise
                        if all(done_votes):
                            break
                        if not any_output:
                            # keep stepping (peers may exchange into us), but pace
                            # the idle spin — barriers resume inside the next step
                            wake.wait(timeout=idle_timeout())
                        continue
                    if local_done:
                        break
                    if not any_output and not self.sources_finished():
                        # nothing to do until a source pushes: its own span, so
                        # that device idle under it reads "no work", not "the
                        # host was busy with something unnamed"
                        with _tracing.trace_span("loop_wait"):
                            wake.wait(timeout=idle_timeout())
        except BaseException as exc:
            # a failing run must be distinguishable from a clean close by sinks
            # that hand state to OTHER graphs (ExportedTable._fail) — finish()
            # in the finally block fires their on_end either way
            from pathway_tpu.engine.evaluators import OutputEvaluator
            from pathway_tpu.parallel.membership import MembershipMismatchError

            if isinstance(exc, MembershipMismatchError):
                # report the store's worker count through the status file so
                # the supervisor can ADAPT -n (a membership transition
                # committed before a crash) instead of tearing down
                self._mismatch_workers = exc.manifest_n
                self._membership_state = "membership_mismatch"
                self._publish_status(force=True)
            if self._recorder is not None:
                self._recorder.dump(f"crash: {type(exc).__name__}")
            for evaluator in self.evaluators.values():
                if isinstance(evaluator, OutputEvaluator):
                    evaluator.notify_failure(exc)
            raise
        finally:
            if _installed_term:
                try:
                    _signal.signal(_signal.SIGTERM, _prev_term)
                except (ValueError, TypeError):
                    pass
            StreamingDataSource.unregister_runner(wake)
            runtime.update(prev_runtime)
            if max_commits is None:
                self.finish()
            elif self._http_server is not None:
                # stepped runs keep engine state but must not leak the
                # monitoring listener port across back-to-back runs
                self._http_server.close()
                self._http_server = None


def _has_pending(evaluator: Any) -> bool:
    has = getattr(evaluator, "has_pending", None)
    return bool(has()) if has is not None else False


def _no_pending() -> bool:
    return False


def _make_monitor(level: Any, nodes: List[pg.Node]) -> Any:
    if level is None:
        return None
    from pathway_tpu.internals.monitoring import MonitoringLevel, StatsMonitor

    if level in (MonitoringLevel.NONE, "none"):
        return None
    if isinstance(level, str):
        level = MonitoringLevel(level)
    return StatsMonitor(nodes, level=level)


def run(**kwargs: Any) -> None:
    """Execute the global dataflow graph (parity: ``pw.run``, reference ``run.py:12``)."""
    GraphRunner(pg.G).run(**kwargs)


def run_all(**kwargs: Any) -> None:
    run(**kwargs)
