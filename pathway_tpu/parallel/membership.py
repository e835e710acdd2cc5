"""Elastic mesh membership: epoch-fenced grow/shrink of a spawn cluster.

ROADMAP item 1: a ``MEMBERSHIP_CHANGE(target_n)`` transition that rides the
existing fence/quiesce machinery. The supervisor publishes a *directive*
(generation, target worker count, next epoch) into the supervise dir; the
workers agree on it through the per-commit neu allgather, quiesce at one
commit boundary, partition their state into per-new-owner *handoff fragments*
(the reshard treated as an array redistribution — every keyed state array is
gathered by ``shard_of(key, new_n)`` and scattered to its new owner, the
DrJAX MapReduce-primitives view of the reshard), commit a *membership
manifest* through the PR-6 checkpoint machinery, and only then rewire the
mesh: joiners install, leavers drain and release. A joiner's catch-up is the
manifest + fragments + journal tail — never a full-history replay.

The state machine was modeled FIRST (``membership_model`` in
``internals/protocol_models.py``) and explored under ``internals/sched.py``;
the invariants proven there (single owner per key range at every epoch, no
row lost or duplicated across the handoff, leavers drained before release,
no stale-epoch delivery, no deadlock) are the contract this module
implements against real sockets and stores.

This module owns the pieces that are neither mesh (``parallel/cluster.py``)
nor engine (``engine/runner.py``): the typed errors, the directive file
protocol between supervisor and workers, the per-node reshard-policy
analysis, and the fragment build/import helpers.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

# directive file written atomically by the supervisor into the supervise dir;
# workers poll it at commit boundaries and agree on it via the neu allgather
DIRECTIVE_FILE = "membership.json"


class MembershipMismatchError(ValueError):
    """A persisted store (journal header, store meta, checkpoint manifest)
    names a different worker count than this run. Carries enough to triage:
    was the cluster scaled with ``--scale`` (relaunch with ``-n manifest_n``,
    or let the supervisor adapt) or is the store from another deployment?

    Subclasses ``ValueError`` so pre-elastic ``except ValueError`` refusal
    handling keeps working."""

    def __init__(
        self,
        what: str,
        *,
        manifest_n: "int | None",
        current_n: int,
        epoch: int = 0,
    ):
        self.manifest_n = manifest_n
        self.current_n = current_n
        self.epoch = epoch
        super().__init__(
            f"persisted {what} was written by a run with {manifest_n} worker "
            f"process(es) but this run uses {current_n} (store epoch "
            f"{epoch}): the journal and checkpoints are sharded per worker. "
            f"If the cluster was resized with `spawn --scale`, relaunch with "
            f"-n {manifest_n} (the supervisor does this automatically when "
            "adapting after a mid-transition crash); if you never scaled, "
            "the store belongs to a different deployment — clear the "
            "persistence directory to start fresh"
        )


class MembershipUnsupportedError(RuntimeError):
    """The running graph (or its sources) holds state this build cannot
    re-partition across a membership change. The scale request is REFUSED —
    loudly, with the reason — and the cluster keeps running at its current
    size."""


@dataclass(frozen=True)
class MembershipDirective:
    """One requested membership change, written by the supervisor."""

    generation: int  # monotonically increasing per supervise dir
    target_n: int
    epoch: int  # the epoch the new topology will run at
    from_n: int  # worker count when the directive was issued
    # who asked: "operator" (--scale / control endpoint / plan) or
    # "autoscaler" (the closed control loop) — refusal feedback and
    # post-mortems attribute the decision. NOT part of as_tuple(): the
    # per-commit vote payload stays the stable 4-tuple.
    origin: str = "operator"

    def as_tuple(self) -> tuple:
        return (self.generation, self.target_n, self.epoch, self.from_n)

    @classmethod
    def from_tuple(cls, t: "tuple | list | None") -> "Optional[MembershipDirective]":
        if not t:
            return None
        g, n, e, f = t
        return cls(int(g), int(n), int(e), int(f))


def directive_path(supervise_dir: str) -> str:
    return os.path.join(supervise_dir, DIRECTIVE_FILE)


def write_directive(supervise_dir: str, directive: MembershipDirective) -> None:
    path = directive_path(supervise_dir)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(
            {
                "generation": directive.generation,
                "target_n": directive.target_n,
                "epoch": directive.epoch,
                "from_n": directive.from_n,
                "origin": directive.origin,
            },
            f,
        )
    os.replace(tmp, path)


def read_directive(supervise_dir: "str | None") -> "Optional[MembershipDirective]":
    if not supervise_dir:
        return None
    try:
        with open(directive_path(supervise_dir)) as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return None
    try:
        return MembershipDirective(
            int(raw["generation"]), int(raw["target_n"]),
            int(raw["epoch"]), int(raw["from_n"]),
            origin=str(raw.get("origin", "operator")),
        )
    except (KeyError, TypeError, ValueError):
        return None


def clear_directive(supervise_dir: "str | None") -> None:
    if not supervise_dir:
        return
    try:
        os.unlink(directive_path(supervise_dir))
    except OSError:
        pass


# ---------------------------------------------------------------------------
# reshard-policy analysis
# ---------------------------------------------------------------------------
#
# Which new rank owns each piece of a node's state after the transition?
#
#   "bykey"     — rows live at their row/group key's owner (outputs of
#                 row-key and group-key exchanges through key-preserving
#                 chains, plus join/dedup/having whose exchange or instance
#                 key equals the output row key): partition every keyed
#                 state array by shard_of(key, new_n).
#   "source"    — never exchanged: rows sit where they were ingested, so
#                 they move exactly when their *source shard* moves (fs file
#                 ownership is hash-of-path mod n). Partitioned by the key ->
#                 new-owner map the reshardable sources export; keys outside
#                 the map (rank-local sources) stay on a surviving donor and
#                 fall back to shard_of on a leaver (their streams are final
#                 — the preflight refuses live rank-local streams on
#                 leavers).
#   "root"      — centralized on rank 0 (sort, temporal behaviors, iterate,
#                 row transformers): rank 0 survives every transition, so
#                 the full state ships to rank 0 (a no-op move for live
#                 rank 0).
#   "derived:N" — key-DERIVING node N (reindex/flatten/concat-reindex): an
#                 output row resides wherever its input row lived, so the
#                 owner function composes as base_owner(prov[out_key])
#                 through node N's provenance map (``plan.derived_base``
#                 holds the base placement per derived node).
#   "replicate" — replicated index content (every rank already holds
#                 identical state by the broadcast construction): rank 0's
#                 rebuild descriptor ships to every new rank.
#
# Every graph kind maps to an explicit policy class in
# ``RESHARD_KIND_POLICIES``; a kind missing from the table is a loud typed
# refusal ("no declared reshard policy"), never a silent guess — a new
# evaluator must declare how its state rides the handoff before graphs
# using it can scale.

# key-preserving kinds (mirror of GraphRunner.setup's placement analysis):
# output row keys equal input row keys, so ownership flows through unchanged
_KEY_PRESERVING = {
    "rowwise", "filter", "update_rows", "update_cells", "intersect",
    "difference", "restrict", "having", "with_universe_of",
    "remove_errors", "concat", "output", "asof_now", "ix",
}

_NESTED_KINDS = {
    "iterate", "iterate_result", "row_transformer", "row_transformer_result",
}

#: every graph node kind -> reshard policy class. "inherit" means ownership
#: flows from the (non-broadcast) inputs, still subject to the
#: key-preservation check and the evaluator's own ``reshard_check``;
#: "derived" composes the owner through the node's provenance map. A kind
#: absent from this table refuses loudly (see ``compute_reshard_plan``).
RESHARD_KIND_POLICIES: Dict[str, str] = {
    "input": "source",
    # nested subgraphs centralize on rank 0 (rank 0 survives every transition)
    "iterate": "root",
    "iterate_result": "root",
    "row_transformer": "root",
    "row_transformer_result": "root",
    # exchanged/keyed by a key equal to the OUTPUT row key
    "groupby": "bykey",       # routed by group key == output row key
    "join": "bykey",          # arrangements partition by join key; outputs
                              # re-exchange by output row key after the join
    "deduplicate": "bykey",   # instance route key == output row key
    "having": "bykey",        # indexer routes carry the base row key
    # key-DERIVING: owner composes through the tracked provenance map
    "reindex": "derived",
    "flatten": "derived",
    "concat": "inherit",      # promoted to "derived" in reindex mode
    "external_index": "replicate",
    # key-preserving / policy-declaring pass-through kinds: ownership flows
    # from inputs, or the evaluator declares "root"/"rowkey" itself
    "rowwise": "inherit",
    "filter": "inherit",
    "update_rows": "inherit",
    "update_cells": "inherit",
    "intersect": "inherit",
    "difference": "inherit",
    "restrict": "inherit",
    "with_universe_of": "inherit",
    "remove_errors": "inherit",
    "output": "inherit",
    "asof_now": "inherit",
    "ix": "inherit",
    "sort": "inherit",
    "sorted_index": "inherit",
    "gradual_broadcast": "inherit",
    "buffer": "inherit",
    "forget": "inherit",
    "freeze": "inherit",
    "stateful_reduce": "inherit",
}


@dataclass
class ReshardPlan:
    """Per-node reshard policies, or the reasons the transition is refused.

    ``refused_nodes`` carries the structured per-node view ({"node", "kind",
    "reason"}) for /healthz and supervisor post-mortems; ``refusals`` is the
    same information formatted for logs and the preflight vote payload.
    ``derived_base`` maps a key-deriving node id to the placement string its
    provenance resolves into (possibly another ``derived:M`` — chains
    compose)."""

    policies: Dict[int, str]
    refusals: List[str]
    refused_nodes: List[Dict[str, Any]] = None  # type: ignore[assignment]
    derived_base: Dict[int, str] = None  # type: ignore[assignment]

    def __post_init__(self) -> None:
        if self.refused_nodes is None:
            self.refused_nodes = []
        if self.derived_base is None:
            self.derived_base = {}

    @property
    def ok(self) -> bool:
        return not self.refusals


def compute_reshard_plan(runner: Any) -> ReshardPlan:
    """Classify every node of the running graph for the handoff. Pure
    analysis — no state is touched. Conservative: anything the fragment
    builder cannot partition exactly is a refusal, never a silent guess."""
    from pathway_tpu.engine.evaluators import InputEvaluator, OutputEvaluator
    from pathway_tpu.internals import parse_graph as pg

    policies: Dict[int, str] = {}
    refusals: List[str] = []
    refused_nodes: List[Dict[str, Any]] = []
    derived_base: Dict[int, str] = {}
    memo: Dict[int, str] = {}
    reasons: Dict[int, str] = {}

    def refuse(node: Any, reason: str) -> str:
        reasons.setdefault(node.id, reason)
        return "refuse"

    def placement(node: Any) -> str:
        got = memo.get(node.id)
        if got is not None:
            return got
        memo[node.id] = "refuse"  # cycle guard (loop-back chains)
        p = _place(node)
        memo[node.id] = p
        return p

    def _place(node: Any) -> str:
        if isinstance(node, pg.InputNode):
            return "source"
        kind_policy = RESHARD_KIND_POLICIES.get(node.kind)
        if kind_policy is None:
            return refuse(
                node,
                f"kind {node.kind!r} declares no reshard policy — a new "
                "evaluator must be added to RESHARD_KIND_POLICIES (with an "
                "export path for its state) before graphs using it can "
                "change membership",
            )
        ev = runner.evaluators.get(node.id)
        pol = tuple(getattr(ev, "_cluster_policies", ()) or ())
        if kind_policy == "root" or "root" in pol:
            return "root"
        if kind_policy == "replicate":
            return "replicate"
        if kind_policy == "bykey":
            return "bykey"
        if kind_policy == "derived" or (
            node.kind == "concat" and node.config.get("reindex", False)
        ):
            bases = {placement(inp._node) for inp in node.inputs}
            if len(bases) != 1:
                return refuse(
                    node,
                    "key-deriving node over inputs with mixed placements "
                    f"({', '.join(sorted(bases))}) — the provenance map "
                    "cannot name a single base owner per derived key",
                )
            base = bases.pop()
            if base == "refuse":
                return refuse(node, "an input of this node already refuses")
            if base == "root":
                return "root"  # all input rows sit on rank 0; so do outputs
            derived_base[node.id] = base
            return f"derived:{node.id}"
        # inherit: ownership flows from the (non-broadcast) inputs
        if "custom" in pol and not getattr(ev, "RESHARD_ROUTE_BYKEY", False):
            return refuse(
                node,
                "exchanged by a custom route key that is not the output "
                "row key — its keyed state cannot be placed by "
                "shard_of(output key) (declare RESHARD_ROUTE_BYKEY if the "
                "route IS the output key)",
            )
        if "rowkey" in pol or "custom" in pol:
            return "bykey"
        contrib = [
            placement(inp._node)
            for i, inp in enumerate(node.inputs)
            if not (i < len(pol) and pol[i] == "broadcast")
        ] or [placement(inp._node) for inp in node.inputs]
        # only the index content replicates: an external index's replies are
        # keyed by query row and partition by key, as its own query side does
        contrib = ["bykey" if c == "replicate" else c for c in contrib]
        if not contrib:
            return "source"
        if any(c == "refuse" for c in contrib):
            return refuse(node, "an input of this node already refuses")
        if not all(c == contrib[0] for c in contrib):
            return refuse(
                node,
                "inputs have mixed placements "
                f"({', '.join(sorted(set(contrib)))}) — rows of this node "
                "have no single owner function",
            )
        p = contrib[0]
        if (
            (p in ("bykey", "source") or p.startswith("derived:"))
            and node.kind not in _KEY_PRESERVING
            and node.kind != "external_index"
        ):
            # key-changing op without provenance tracking: output keys are
            # neither the exchange key nor the preserved input key.
            # external_index is exempt: its output universe IS the query
            # input's universe (replies keyed by query key).
            return refuse(
                node,
                "output keys are a derivation this build does not track "
                "provenance for — state keyed by them cannot be placed",
            )
        return p

    def record_refusal(node: Any, reason: str) -> None:
        refusals.append(f"node {node.id} ({node.kind}): {reason}")
        refused_nodes.append(
            {"node": node.id, "kind": node.kind, "reason": reason}
        )

    for node in runner._nodes:
        ev = runner.evaluators.get(node.id)
        if isinstance(ev, (InputEvaluator, OutputEvaluator)):
            # sources hand off through the source-state path; sinks are
            # rank-local delivery bookkeeping (retraction/snapshot replay
            # handles them at the transition)
            continue
        p = placement(node)
        if p == "refuse":
            record_refusal(
                node,
                reasons.get(
                    node.id,
                    "state cannot be re-partitioned across a membership "
                    "change",
                ),
            )
            continue
        if node.kind == "external_index":
            # an index that exports a rebuildable descriptor replicates to
            # the new topology (its data side is broadcast — every rank
            # already holds identical content); the typed refusal is KEPT
            # for index types that cannot export a descriptor
            reason = ev.reshard_check() if ev is not None else "no evaluator"
            if reason is not None:
                record_refusal(node, reason)
                continue
            policies[node.id] = "replicate"
            continue
        if not getattr(ev, "SNAPSHOT_CAPTURE", True):
            record_refusal(
                node,
                "state lives outside the snapshot protocol "
                "(device-resident) and cannot ride the handoff fragments",
            )
            continue
        if p == "bykey" or p == "source" or p.startswith("derived:"):
            reason = ev.reshard_check() if ev is not None else None
            if reason is not None:
                record_refusal(node, reason)
                continue
        policies[node.id] = p
    return ReshardPlan(policies, refusals, refused_nodes, derived_base)


def preflight_sources(runner: Any, new_n: int, me: int) -> List[str]:
    """Source-side capability check. A leaver's live streams must be
    transferable (fs scans reshard; finished streams and loopbacks are
    inert); a rank-local live stream on a leaver would silently stop
    ingesting — refuse instead."""
    refusals: List[str] = []
    leaving = me >= new_n
    for node, _ev in runner._sources:
        source = node.config["source"]
        subject = getattr(source, "subject", None)
        reshardable = subject is not None and hasattr(subject, "reshard_exports")
        if reshardable:
            continue
        if getattr(source, "loopback", False):
            continue
        if leaving and not source.is_finished():
            refusals.append(
                f"source node {node.id}: rank {me} is draining but this "
                "live stream is rank-local (no reshard support) — its "
                "future rows would be lost; finish or reshard the source "
                "before scaling this rank away"
            )
    return refusals


# ---------------------------------------------------------------------------
# handoff fragments
# ---------------------------------------------------------------------------


def _owner_fn_bykey(new_n: int) -> Callable[[Any], Any]:
    from pathway_tpu.internals.keys import shard_of

    def owner_of(keys: Any) -> Any:
        return shard_of(keys, new_n)

    return owner_of


def _owner_fn_source(
    key_owner_map: Dict[bytes, int], default_owner: "int | None", new_n: int
) -> Callable[[Any], Any]:
    """Per-row owners for ingest-placed state: the source-exported key map
    decides; unmapped keys stay on the donor (survivor) or hash out
    (leaver — their streams are final by preflight)."""
    import numpy as np

    from pathway_tpu.internals.keys import shard_of

    def owner_of(keys: Any) -> Any:
        fallback = (
            shard_of(keys, new_n)
            if default_owner is None
            else np.full(len(keys), default_owner, dtype=np.int64)
        )
        out = fallback.copy()
        for i in range(len(keys)):
            got = key_owner_map.get(keys[i].tobytes())
            if got is not None:
                out[i] = got
        return out

    return owner_of


def _owner_fn_derived(ev: Any, base_fn: Callable[[Any], Any]) -> Callable[[Any], Any]:
    """Owner for key-DERIVED state: map each derived key through the
    evaluator's provenance to the input key whose placement decides
    residence, then ask the base owner. Keys without a provenance entry
    (never produced on this rank — e.g. replay-memo entries keyed by
    pre-derivation keys) fall through to the base owner unmapped."""
    import numpy as np

    from pathway_tpu.internals.keys import KEY_DTYPE

    def owner_of(keys: Any) -> Any:
        prov = getattr(ev, "_reshard_prov", None) or {}
        if not prov:
            return base_fn(keys)
        mapped = np.empty(len(keys), dtype=KEY_DTYPE)
        for i in range(len(keys)):
            kb = keys[i].tobytes()
            src = prov.get(kb, kb)
            mapped[i] = np.frombuffer(src, dtype=KEY_DTYPE)[0]
        return base_fn(mapped)

    return owner_of


def _make_owner_resolver(
    runner: Any,
    plan: ReshardPlan,
    new_n: int,
    key_map: Dict[bytes, int],
    me: int,
    leaving: bool,
) -> Callable[[str], Callable[[Any], Any]]:
    """Memoized placement-string -> owner-function resolver. ``derived:N``
    placements compose recursively through their base placement (chains of
    reindex/flatten over reindex compose all the way down to bykey/source)."""
    bykey = _owner_fn_bykey(new_n)
    bysource = _owner_fn_source(key_map, None if leaving else me, new_n)
    fns: Dict[str, Callable[[Any], Any]] = {"bykey": bykey, "source": bysource}

    def owner_for(policy: str) -> Callable[[Any], Any]:
        fn = fns.get(policy)
        if fn is None:
            if not policy.startswith("derived:"):
                raise MembershipUnsupportedError(
                    f"no owner function for reshard policy {policy!r}"
                )
            nid = int(policy.split(":", 1)[1])
            base = plan.derived_base.get(nid, "bykey")
            fn = _owner_fn_derived(runner.evaluators[nid], owner_for(base))
            fns[policy] = fn
        return fn

    return owner_for


def build_source_exports(
    runner: Any, new_n: int
) -> Tuple[Dict[int, Dict[int, list]], Dict[bytes, int]]:
    """Ask every reshardable source to partition its durable scan state by
    new owner. Returns ``(per_dest {rank: {node_id: [state deltas]}},
    key_owner_map {row-key bytes -> new owner})`` — the map also drives the
    "source"-policy state-table partition. Pure read: nothing is removed
    from the live sources until the transition commits."""
    per_dest: Dict[int, Dict[int, list]] = {}
    key_map: Dict[bytes, int] = {}
    for node, _ev in runner._sources:
        source = node.config["source"]
        subject = getattr(source, "subject", None)
        exports = getattr(subject, "reshard_exports", None)
        if exports is None:
            continue
        by_owner = exports(new_n)
        for dest, deltas in by_owner.items():
            if not deltas:
                continue
            per_dest.setdefault(dest, {}).setdefault(node.id, []).extend(deltas)
        key_owners = getattr(subject, "reshard_key_owners", None)
        if key_owners is not None:
            for kb, owner in key_owners(new_n):
                key_map[kb] = owner
    return per_dest, key_map


def build_fragments(
    runner: Any,
    plan: ReshardPlan,
    new_n: int,
    commit: int,
    generation: int,
    source_state: "Tuple[dict, dict] | None" = None,
) -> Tuple[Dict[int, dict], Dict[str, int]]:
    """Partition this rank's entire engine state into one fragment per new
    rank (including one addressed to itself — crash recovery reloads the
    full set, so fragments must be complete, not deltas against live
    state). ``source_state`` is a precomputed :func:`build_source_exports`
    result (the caller reuses it for the sink retractions — rebuilding it
    copies every emitted row again). Returns ``(fragments, stats)``."""
    import numpy as np  # noqa: F401  (vectorized owners)

    from pathway_tpu.internals.config import get_pathway_config

    me = get_pathway_config().process_id
    leaving = me >= new_n
    source_exports, key_map = (
        source_state
        if source_state is not None
        else build_source_exports(runner, new_n)
    )
    owner_for = _make_owner_resolver(runner, plan, new_n, key_map, me, leaving)
    bykey = owner_for("bykey")

    fragments: Dict[int, dict] = {
        dest: {
            "format": 1,
            "from_rank": me,
            "commit": commit,
            "generation": generation,
            "states": {},
            "evals": {},
            "evals_full": {},
            "evals_rebuild": {},
            "source_offsets": {},
            "source_deltas": {},
        }
        for dest in range(new_n)
    }
    rows_moved = 0
    for nid, policy in plan.policies.items():
        ev = runner.evaluators[nid]
        state = runner.states.get(nid)
        if policy == "replicate":
            # replicated index content: identical on every old rank by the
            # broadcast construction, so rank 0's descriptor is authoritative
            # and ships to EVERY new rank; the keyed query-side state
            # partitions by row key like any bykey evaluator
            if me == 0:
                desc = ev.rebuild_descriptor()
                for dest in range(new_n):
                    fragments[dest]["evals_rebuild"][nid] = desc
            for dest, payload in ev.reshard_export(bykey, new_n).items():
                fragments[dest]["evals"][nid] = payload
            if state is not None and nid in runner._materialized:
                for dest, part in state.reshard_partition(bykey).items():
                    fragments[dest]["states"][nid] = part
                    if dest != me:
                        rows_moved += len(part[0])
            continue
        if policy == "root":
            # centralized state lives at rank 0 ONLY — rank 0's copy is
            # authoritative, and a non-root rank's empty mirror must never
            # clobber it at import
            if me == 0:
                fragments[0]["evals_full"][nid] = ev.state_dict()
                if state is not None and nid in runner._materialized:
                    snap = state.snapshot()
                    if len(snap):
                        fragments[0]["states"][nid] = (
                            snap.keys, snap.diffs, dict(snap.columns)
                        )
            continue
        owner_of = owner_for(policy)
        payloads = ev.reshard_export(owner_of, new_n)
        for dest, payload in payloads.items():
            fragments[dest]["evals"][nid] = payload
        if state is not None and nid in runner._materialized:
            for dest, part in state.reshard_partition(owner_of).items():
                fragments[dest]["states"][nid] = part
                if dest != me:
                    rows_moved += len(part[0])
    # source continuation offsets ride the self-addressed fragment (a crash
    # recovery of THIS rank resumes its own counters); moved scan state is
    # addressed to its new owner
    if not leaving:
        for node, _ev in runner._sources:
            offsets = node.config["source"].offset_state()
            offsets.pop("state_deltas", None)
            fragments[me]["source_offsets"][node.id] = offsets
    for dest, by_node in source_exports.items():
        if dest >= new_n:
            continue
        for nid, deltas in by_node.items():
            fragments[dest]["source_deltas"].setdefault(nid, []).extend(deltas)
    stats = {"rows_handed_off": rows_moved}
    return fragments, stats


#: default per-chunk budget for the streamed handoff (bytes of payload per
#: mini-fragment). Overridden by PATHWAY_RESHARD_CHUNK_BYTES.
DEFAULT_RESHARD_CHUNK_BYTES = 1 << 22


def reshard_chunk_bytes() -> int:
    raw = os.environ.get("PATHWAY_RESHARD_CHUNK_BYTES", "")
    try:
        got = int(raw)
    except ValueError:
        got = 0
    return got if got > 0 else DEFAULT_RESHARD_CHUNK_BYTES


def _approx_nbytes(obj: Any) -> int:
    """Cheap recursive payload-size estimate for chunk budgeting. Exactness
    does not matter — it only decides where chunk boundaries fall."""
    import numpy as np

    if obj is None:
        return 8
    if isinstance(obj, np.ndarray):
        return int(obj.nbytes)
    if isinstance(obj, (bytes, bytearray, str)):
        return len(obj)
    if isinstance(obj, dict):
        return 64 + sum(
            _approx_nbytes(k) + _approx_nbytes(v) for k, v in obj.items()
        )
    if isinstance(obj, (list, tuple, set, frozenset)):
        return 64 + sum(_approx_nbytes(v) for v in obj)
    return 64


def build_fragment_chunks(
    runner: Any,
    plan: ReshardPlan,
    new_n: int,
    commit: int,
    generation: int,
    source_state: "Tuple[dict, dict] | None" = None,
    chunk_bytes: "int | None" = None,
) -> Tuple[Any, Dict[str, int]]:
    """Streamed counterpart of :func:`build_fragments`: yields
    ``(dest, chunk)`` mini-fragments whose payload stays under the chunk
    budget, so a donor's peak handoff memory is O(chunk x peers) instead of
    O(state). Each chunk has the full format-1 fragment shape and imports
    independently through :func:`import_fragments` (state-table parts apply
    as incremental deltas; evaluator exports are merge-disjoint by
    construction), carrying at most one payload per (section, node) — plus a
    ``kinds`` list naming the node kinds aboard, which the chaos harness
    gates its chunk-level faults on.

    Returns ``(chunk_iterator, stats)``; ``stats`` is populated AS the
    iterator is drained (read it only after the dump loop finishes).
    Unsplittable payloads (root state dicts, rebuild descriptors) ride a
    single chunk whatever their size — the budget bounds the partitionable
    state, which is what grows with the workload."""
    from pathway_tpu.internals.config import get_pathway_config

    me = get_pathway_config().process_id
    leaving = me >= new_n
    source_exports, key_map = (
        source_state
        if source_state is not None
        else build_source_exports(runner, new_n)
    )
    owner_for = _make_owner_resolver(runner, plan, new_n, key_map, me, leaving)
    bykey = owner_for("bykey")
    budget = int(chunk_bytes) if chunk_bytes else reshard_chunk_bytes()
    budget = max(1, budget)
    # row budget for export-side slicing: conservative rows-per-chunk guess;
    # the byte accounting below is what actually seals chunks
    budget_rows = max(64, budget // 512)
    kinds_of = {n.id: n.kind for n in runner._nodes}
    stats: Dict[str, int] = {"rows_handed_off": 0, "chunks": 0}

    def pieces():
        """(dest, section, nid, payload, moved_rows) in node order."""
        for nid, policy in plan.policies.items():
            ev = runner.evaluators[nid]
            state = runner.states.get(nid)
            if policy == "replicate":
                if me == 0:
                    desc = ev.rebuild_descriptor()
                    for dest in range(new_n):
                        yield dest, "evals_rebuild", nid, desc, 0
                for dest, payload in ev.reshard_export(bykey, new_n).items():
                    yield dest, "evals", nid, payload, 0
                if state is not None and nid in runner._materialized:
                    for dest, part in state.reshard_partition_chunks(
                        bykey, budget_rows
                    ):
                        yield dest, "states", nid, part, (
                            len(part[0]) if dest != me else 0
                        )
                continue
            if policy == "root":
                if me == 0:
                    yield 0, "evals_full", nid, ev.state_dict(), 0
                    if state is not None and nid in runner._materialized:
                        snap = state.snapshot()
                        if len(snap):
                            yield 0, "states", nid, (
                                snap.keys, snap.diffs, dict(snap.columns)
                            ), 0
                continue
            owner_of = owner_for(policy)
            parts_fn = getattr(ev, "reshard_export_parts", None)
            if parts_fn is not None:
                for dest, piece in parts_fn(owner_of, new_n, budget_rows):
                    yield dest, "evals", nid, piece, 0
            else:
                for dest, payload in ev.reshard_export(owner_of, new_n).items():
                    yield dest, "evals", nid, payload, 0
            if state is not None and nid in runner._materialized:
                for dest, part in state.reshard_partition_chunks(
                    owner_of, budget_rows
                ):
                    yield dest, "states", nid, part, (
                        len(part[0]) if dest != me else 0
                    )
        if not leaving:
            for node, _ev in runner._sources:
                offsets = node.config["source"].offset_state()
                offsets.pop("state_deltas", None)
                yield me, "source_offsets", node.id, offsets, 0
        for dest, by_node in source_exports.items():
            if dest >= new_n:
                continue
            for nid, deltas in by_node.items():
                yield dest, "source_deltas", nid, list(deltas), 0

    def new_chunk() -> dict:
        return {
            "format": 1,
            "from_rank": me,
            "commit": commit,
            "generation": generation,
            "states": {},
            "evals": {},
            "evals_full": {},
            "evals_rebuild": {},
            "source_offsets": {},
            "source_deltas": {},
            "kinds": [],
        }

    def seal(chunk: dict) -> dict:
        chunk["kinds"] = sorted(set(chunk["kinds"]))
        stats["chunks"] += 1
        return chunk

    def chunks():
        open_chunks: Dict[int, list] = {}  # dest -> [chunk, approx bytes]
        touched: set = set()
        for dest, section, nid, payload, moved in pieces():
            touched.add(dest)
            ent = open_chunks.get(dest)
            if ent is None:
                ent = open_chunks[dest] = [new_chunk(), 0]
            if nid in ent[0][section]:
                # one payload per (section, node) per chunk: importing a
                # chunk must never see two payloads collide under one id
                yield dest, seal(ent[0])
                ent = open_chunks[dest] = [new_chunk(), 0]
            ent[0][section][nid] = payload
            ent[0]["kinds"].append(kinds_of.get(nid, "input"))
            ent[1] += _approx_nbytes(payload)
            stats["rows_handed_off"] += moved
            if ent[1] >= budget:
                yield dest, seal(ent[0])
                del open_chunks[dest]
        for dest in sorted(open_chunks):
            yield dest, seal(open_chunks[dest][0])
        # every destination gets at least one chunk: the per-dest manifest
        # must exist for the loader to tell "empty handoff" from "torn write"
        for dest in range(new_n):
            if dest not in touched:
                yield dest, seal(new_chunk())

    return chunks(), stats


def import_fragments(runner: Any, frags: List[dict]) -> Dict[str, int]:
    """Merge handoff fragments addressed to this rank into FRESH evaluator /
    state-table instances (the caller reset them). Order-independent: key
    partitions are disjoint by construction; root/full states appear in
    exactly one fragment. Accepts both whole fragments (gather transport)
    and streamed chunks (:func:`build_fragment_chunks`) — a chunk is just a
    small fragment."""
    from pathway_tpu.engine.columnar import Delta
    from pathway_tpu.internals.chaos import get_chaos
    from pathway_tpu.internals.config import get_pathway_config

    chaos = get_chaos()
    rows = 0
    for frag in frags:
        if chaos is not None and "deduplicate" in (frag.get("kinds") or ()):
            # dedup_install_kill: die right before applying a chunk that
            # carries dedup instance state — the install barrier must fail,
            # the previous topology's state must stand, and the recovery
            # ladder must replay the transition bit-identically
            chaos.maybe_scale_kill(
                get_pathway_config().process_id, "dedup_install_kill",
                commit=int(frag.get("commit", -1)),
            )
        for nid, (keys, diffs, columns) in frag.get("states", {}).items():
            nid = int(nid)
            state = runner.states.get(nid)
            if state is not None and len(keys):
                state.apply(Delta(keys, diffs, columns))
                rows += len(keys)
        for nid, payload in frag.get("evals", {}).items():
            ev = runner.evaluators.get(int(nid))
            if ev is not None:
                ev.reshard_import(payload)
        for nid, blobs in frag.get("evals_full", {}).items():
            ev = runner.evaluators.get(int(nid))
            if ev is not None:
                ev.load_state_dict(blobs)
        for nid, desc in frag.get("evals_rebuild", {}).items():
            ev = runner.evaluators.get(int(nid))
            if ev is not None and desc is not None:
                ev.install_rebuild_descriptor(desc)
    return {"rows_imported": rows}


def merge_fragment_sources(frags: List[dict]) -> Tuple[Dict[int, dict], Dict[int, list]]:
    """Collect the source continuation offsets + scan-state deltas addressed
    to this rank across all fragments (cold-start restore path)."""
    offsets: Dict[int, dict] = {}
    deltas: Dict[int, list] = {}
    for frag in frags:
        for nid, offs in frag.get("source_offsets", {}).items():
            offsets[int(nid)] = offs
        for nid, entries in frag.get("source_deltas", {}).items():
            deltas.setdefault(int(nid), []).extend(entries)
    return offsets, deltas
