"""Input sources feeding the commit loop.

Parity: reference connector framework (``src/connectors/mod.rs`` — input thread + poller +
commit ticks). Host-side by design: TPU engines keep IO on the host CPU and ship batched
columns to the device.
"""

from __future__ import annotations

import queue
import threading
import time as time_mod
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence

import numpy as np

from pathway_tpu.engine.columnar import Delta
from pathway_tpu.internals.keys import KEY_DTYPE, Pointer, keys_from_values, pointers_to_keys, sequential_keys


def _auto_key_base() -> int:
    """Start of this process's autogenerated-key sequence. Under ``spawn -n N``
    every process hashes the same dense ints otherwise — colliding row keys that
    corrupt exchanged/centralized operator state once deltas from two processes
    meet (the reference stripes per-worker key spaces the same way). Stable
    across restarts for a given process id, so persistence replay reproduces
    identical keys."""
    from pathway_tpu.internals.config import get_pathway_config

    cfg = get_pathway_config()
    if int(getattr(cfg, "processes", 1) or 1) <= 1:
        return 0
    return int(getattr(cfg, "process_id", 0) or 0) << 44


class DataSource:
    """One input's event feed; ``next_batch`` is called once per commit."""

    def _key_base(self) -> int:
        """Per-source cache of :func:`_auto_key_base` (stable for the process
        lifetime; the env parse must not run per row)."""
        base = getattr(self, "_auto_key_base_cached", None)
        if base is None:
            base = _auto_key_base()
            self._auto_key_base_cached = base
        return base

    def next_batch(self, column_names: List[str]) -> Delta:
        raise NotImplementedError

    def is_finished(self) -> bool:
        raise NotImplementedError

    def on_start(self) -> None:
        pass

    def on_stop(self) -> None:
        pass

    # -- persistence hooks (reference ``OffsetValue``, ``offset.rs:37``) ----

    def offset_state(self) -> dict:
        """Light resumable position + this frame's segment-state deltas, journaled every
        commit."""
        return {}

    def checkpoint_state_deltas(self) -> list | None:
        """Drained segment markers for operator checkpoints (compaction drops the
        journal frames that carried them)."""
        return None

    def restore(self, offset: dict, state_deltas: list, tail: dict | None) -> None:
        """Reposition so already-journaled events are not re-emitted after replay.

        ``state_deltas``: every segment-completion marker journaled so far, in order —
        the subject folds them back into its scan state. ``tail`` describes the segment
        whose processing straddled the crash: ``{"token", "fp", "count", "rows"}`` with
        ``rows`` = the journaled ``(key, values, diff)`` events not yet covered by any
        marker. On the matching segment's re-arrival the source either skips ``count``
        re-pushed events (fingerprint unchanged — deterministic re-push) or retracts
        ``rows`` first (segment changed while down)."""


class StaticDataSource(DataSource):
    """All rows present at time 0 (batch mode)."""

    def __init__(
        self,
        rows: List[tuple],
        keys: np.ndarray | None = None,
        column_names: List[str] | None = None,
        columns: Dict[str, np.ndarray] | None = None,
    ):
        # rows: list of dicts column->value OR tuples following column_names;
        # columns: pre-columnarized arrays built at graph construction (off the
        # run clock), taking precedence over rows
        self._rows = rows
        self._keys = keys
        self._column_names = column_names
        self._columns = columns
        self._done = False

    def on_start(self) -> None:
        # a fresh GraphRunner re-runs the whole graph (debug captures, repeated pw.run),
        # unless a persistence restore marked the rows as replayed — a one-shot flag so
        # later runs of the same graph without persistence still re-emit
        if getattr(self, "_restored_done", False):
            self._restored_done = False
        else:
            self._done = False

    def offset_state(self) -> dict:
        return {"done": self._done}

    def restore(self, offset: dict, state_deltas: list, tail: dict | None) -> None:
        # replayed journal already carries the rows; don't emit them again
        if offset.get("done"):
            self._done = True
            self._restored_done = True

    def next_batch(self, column_names: List[str]) -> Delta:
        if self._done:
            return Delta.empty(column_names)
        self._done = True
        n = len(self._rows)
        columns: Dict[str, np.ndarray] = {}
        for name in column_names:
            if self._columns is not None and name in self._columns:
                columns[name] = self._columns[name]
                continue
            col = np.empty(n, dtype=object)
            for i, row in enumerate(self._rows):
                col[i] = row[name] if isinstance(row, dict) else row[self._column_names.index(name)]
            columns[name] = _tidy_col(col)
        if self._keys is None:
            keys = sequential_keys(self._key_base(), n)
        else:
            keys = self._keys
        return Delta(keys, np.ones(n, dtype=np.int64), columns)

    def is_finished(self) -> bool:
        return self._done


class StreamingDataSource(DataSource):
    """Queue-fed source; a producer thread pushes (key, row, diff) events.

    Mirrors the reference's per-connector input thread + mpsc channel + poller drain
    (``connectors/mod.rs:461-529``). Draining is NON-blocking — the commit loop wakes
    on a per-runner event when any producer pushes, so end-to-end latency is wake-up +
    one commit rather than a serial per-source poll window — while ``autocommit_ms``
    keeps its reference meaning as the commit-tick interval: a source releases its
    queued events at most once per window, so steady streams still coalesce into
    window-sized batches instead of commit-per-event.
    """

    _MAX_EVENTS_PER_COMMIT = 100_000  # reference drains <=100k entries/iteration

    # one process-wide wake signal plus per-runner events: a producer push wakes
    # EVERY registered commit loop (each clears only its own event, so concurrent
    # runners never consume each other's wakeups)
    WAKE = threading.Event()
    _RUNNER_EVENTS: "list[threading.Event]" = []
    _REG_LOCK = threading.Lock()

    @classmethod
    def register_runner(cls, event: "threading.Event") -> None:
        with cls._REG_LOCK:
            cls._RUNNER_EVENTS.append(event)

    @classmethod
    def unregister_runner(cls, event: "threading.Event") -> None:
        with cls._REG_LOCK:
            if event in cls._RUNNER_EVENTS:
                cls._RUNNER_EVENTS.remove(event)

    @classmethod
    def _wake_all(cls) -> None:
        cls.WAKE.set()
        for ev in list(cls._RUNNER_EVENTS):
            ev.set()

    def __init__(
        self,
        subject: Any = None,
        autocommit_ms: float | None = None,
        loopback: bool = False,
    ):
        self.events: "queue.Queue[tuple]" = queue.Queue()
        self._finished = threading.Event()
        self._started = False
        self.subject = subject
        # loop-back sources (AsyncTransformer) are fed by results of THIS graph:
        # they do not gate the primary end-of-input signal (runner fires stream-end
        # notifications once every non-loopback source drained)
        self.loopback = loopback
        self._thread: threading.Thread | None = None
        self._autocommit_ms = autocommit_ms
        self._next_commit_at = 0.0  # the end of the open autocommit window (time.monotonic)
        self._seq = 0
        # persistence: events consumed so far (journaled events count as consumed on
        # resume); deterministically re-pushed events dedup via segment-scoped skips
        self._consumed = 0
        self._skip = 0
        # segment bookkeeping. Markers ride the event queue, so each is ordered after
        # exactly the events it accounts for — no cross-thread snapshot races.
        self._in_progress: dict | None = None  # {"token", "fp", "emitted"}
        self._frame_state_deltas: List[Any] = []  # drained this frame, journaled with it
        self._requeued_state: List[Any] = []  # markers whose journal frame was aborted
        self._drained_state_deltas: List[Any] = []  # full drained marker history
        # armed at restore when the crash straddled a segment
        self._pending_resume: dict | None = None  # {"token", "fp", "count", "rows"}

    # producer API ----------------------------------------------------------

    def push(self, values: dict, key: Pointer | None = None, diff: int = 1) -> None:
        self.events.put(("data", key, values, diff))
        StreamingDataSource._wake_all()

    def push_begin(self, token: Any, fingerprint: Any) -> None:
        """Producer marks the start of a replayable segment (e.g. one file): ``token``
        identifies it, ``fingerprint`` changes iff a re-push of the segment would produce
        a different event sequence."""
        self.events.put(("begin", token, fingerprint))
        StreamingDataSource._wake_all()

    def push_state(self, state_delta: Any) -> None:
        """Producer checkpoints the just-finished segment in-band (after its events).
        The delta is journaled with the commit frame; on resume all deltas are folded
        back through ``subject.restore``. Ends the current engine batch so journal
        frames align with segment boundaries."""
        self.events.put(("state", state_delta))
        StreamingDataSource._wake_all()

    def push_barrier(self) -> None:
        """Producer signals one full scan pass: any still-unmatched crash-straddled
        segment is gone — its journaled tail events get retracted."""
        self.events.put(("barrier",))
        StreamingDataSource._wake_all()

    def close(self) -> None:
        self.events.put(("eof",))
        StreamingDataSource._wake_all()

    # engine API ------------------------------------------------------------

    def on_start(self) -> None:
        if self.subject is not None and not self._started:
            self._started = True

            from pathway_tpu.internals import config as _config_mod

            # worker-thread runs install a per-thread config override (rank,
            # shard count); threading.local does NOT inherit, so the connector
            # thread re-installs it — otherwise partition-sharded readers on
            # every worker would read EVERY file and double-ingest
            _cfg_override = _config_mod.current_thread_config_override()

            def runner() -> None:
                # a connector-thread failure must surface in the engine loop, not
                # die silently with the thread (reference: connector errors
                # terminate the run or hit the error log per terminate_on_error)
                if _cfg_override is not None:
                    _config_mod.set_thread_config(_cfg_override)
                try:
                    self.subject.run(self)
                except BaseException as exc:  # noqa: BLE001
                    self.events.put(("error", exc))
                finally:
                    self.close()

            self._thread = threading.Thread(target=runner, daemon=True, name="pathway:connector")
            self._thread.start()

    def rewind_frame_state(self) -> None:
        """Surgical-rejoin support: the commit that drained these segment
        markers was fenced off before its journal frame was written — re-emit
        them with the next frame so the journal's marker history stays complete
        (they are already in the drained history, so checkpoints are
        unaffected)."""
        if self._frame_state_deltas:
            self._requeued_state = self._frame_state_deltas + self._requeued_state
            self._frame_state_deltas = []

    def next_batch(self, column_names: List[str]) -> Delta:
        rows: List[tuple] = []
        # markers rescued from a fenced-off commit ride the next frame (ahead
        # of anything drained now, preserving marker-after-its-events order)
        self._frame_state_deltas = self._requeued_state
        self._requeued_state = []
        now = time_mod.monotonic()
        if (
            now < self._next_commit_at
            and not self._finished.is_set()
            and self.events.qsize() < self._MAX_EVENTS_PER_COMMIT
        ):
            # inside the autocommit window: let events coalesce (the reference's
            # commit tick); eof and overfull queues release immediately. Serving
            # latency is bounded by the tick — the rest connector runs a 1 ms
            # tick so per-request overhead is wake + <=1 ms.
            return Delta.empty(column_names)
        deadline = now + (self._autocommit_ms or 10) / 1000.0
        while len(rows) < self._MAX_EVENTS_PER_COMMIT:
            try:
                event = self.events.get_nowait()
            except queue.Empty:
                break
            if event[0] == "eof":
                self._finished.set()
                break
            if event[0] == "error":
                # re-raise the connector thread's failure on the engine loop
                # (reference Connector error propagation; terminate_on_error and
                # error-log routing are applied by the evaluator/runner above us)
                self._finished.set()
                raise event[1]
            if event[0] == "begin":
                _, token, fp = event
                self._in_progress = {"token": token, "fp": fp, "emitted": 0}
                pending = self._pending_resume
                if pending is not None and token == pending["token"]:
                    self._pending_resume = None
                    if fp == pending["fp"]:
                        # unchanged segment: the re-push repeats the journaled tail.
                        # emitted continues from the journaled count so a second crash
                        # before the marker journals the full skip width
                        self._skip += pending["count"]
                        self._in_progress["emitted"] = pending["count"]
                    else:
                        # segment changed while down: undo its journaled partial events
                        rows.extend(
                            (key, values, -diff)
                            for key, values, diff in pending["rows"]
                        )
                        self._consumed += len(pending["rows"])
                continue
            if event[0] == "state":
                self._in_progress = None
                self._frame_state_deltas.append(event[1])
                self._drained_state_deltas.append(event[1])
                if len(self._drained_state_deltas) > 256:
                    fold = getattr(self.subject, "fold_state_deltas", None)
                    if fold is not None:
                        # lossless compaction keeps memory bounded by live state even
                        # when checkpointing is off
                        self._drained_state_deltas = list(
                            fold(self._drained_state_deltas)
                        )
                # end the batch: journal frames align with segment boundaries, so the
                # resume tail never spans more than one segment
                break
            if event[0] == "barrier":
                pending, self._pending_resume = self._pending_resume, None
                if pending is not None:
                    # straddled segment never re-appeared (deleted while down)
                    rows.extend(
                        (key, values, -diff) for key, values, diff in pending["rows"]
                    )
                    self._consumed += len(pending["rows"])
                continue
            _, key, values, diff = event
            if self._skip > 0:
                self._skip -= 1
                continue
            self._consumed += 1
            if self._in_progress is not None:
                self._in_progress["emitted"] += 1
            rows.append((key, values, diff))
            if time_mod.monotonic() > deadline and rows:
                break
        if not rows:
            return Delta.empty(column_names)
        # a released batch opens the next coalescing window: sustained streams
        # batch at the autocommit tick (reference commit_duration semantics)
        self._next_commit_at = time_mod.monotonic() + (self._autocommit_ms or 10) / 1000.0
        n = len(rows)
        keys = np.empty(n, dtype=KEY_DTYPE)
        key_base = None  # config parse hoisted out of the per-row loop
        for i, (key, values, diff) in enumerate(rows):
            if key is None:
                if key_base is None:
                    key_base = self._key_base()
                key_arr = sequential_keys(key_base + self._seq, 1)
                self._seq += 1
                keys[i] = key_arr[0]
            else:
                keys[i] = pointers_to_keys([key])[0]
        diffs = np.array([r[2] for r in rows], dtype=np.int64)
        columns = {}
        for name in column_names:
            col = np.empty(n, dtype=object)
            for i, (_, values, _) in enumerate(rows):
                col[i] = values.get(name)
            columns[name] = _tidy_col(col)
        return Delta(keys, diffs, columns)

    def is_finished(self) -> bool:
        return self._finished.is_set() and self.events.empty()

    def release_at(self) -> "float | None":
        """When ``next_batch`` lets the queued events go (``time.monotonic``; in the
        past once the autocommit window is over); None while nothing is queued."""
        return None if self.events.empty() else self._next_commit_at

    # -- elastic membership (parallel/membership.py) --------------------------

    def reshard_ready(self) -> bool:
        """True when this source is quiesced enough for a membership
        transition: nothing buffered, no segment straddling the boundary.
        Rank-local sources (no reshard support on the subject) are always
        ready — their rows stay where they are ingested."""
        if getattr(self.subject, "reshard_exports", None) is None:
            return True
        return self.events.empty() and self._in_progress is None

    def reshard_scrub(self, new_n: int, me: int) -> None:
        """Drop scan-state history for shards that moved away (the new owner
        carries it forward in ITS checkpoints; keeping it here would make two
        ranks both claim the files on a later restore)."""
        keeps = getattr(self.subject, "reshard_keeps", None)
        if keeps is None:
            return
        self._drained_state_deltas = [
            d for d in self._drained_state_deltas if keeps(d, new_n, me)
        ]
        self._frame_state_deltas = [
            d for d in self._frame_state_deltas if keeps(d, new_n, me)
        ]
        self._requeued_state = [
            d for d in self._requeued_state if keeps(d, new_n, me)
        ]

    def reshard_absorb(self, deltas: list) -> None:
        """Adopt scan state handed off by a donor rank: fold it into the
        subject (so the scanner will not re-ingest those shards) and into the
        drained history (so this rank's next checkpoint carries it)."""
        if not deltas:
            return
        self._drained_state_deltas.extend(deltas)
        sub_restore = getattr(self.subject, "restore", None)
        if sub_restore is not None:
            sub_restore(deltas)

    # -- persistence ---------------------------------------------------------

    def checkpoint_state_deltas(self) -> list | None:
        if not self._drained_state_deltas:
            return None
        fold = getattr(self.subject, "fold_state_deltas", None)
        if fold is None:
            return list(self._drained_state_deltas)
        folded = fold(self._drained_state_deltas)
        # folding is lossless: prune the history so memory stays bounded by live state
        self._drained_state_deltas = list(folded)
        # journal-frame markers are slim (no row payload — the frame carries
        # the rows); a CHECKPOINT outlives its frames, so the export hydrates
        # rows back in from the subject's live scan state
        hydrate = getattr(self.subject, "hydrate_state_deltas", None)
        if hydrate is not None:
            return hydrate(folded)
        return folded

    def offset_state(self) -> dict:
        out: dict = {"consumed": self._consumed, "seq": self._seq}
        if self._frame_state_deltas:
            out["state_deltas"] = list(self._frame_state_deltas)
        if self._in_progress is not None:
            out["in_progress"] = dict(self._in_progress)
        return out

    def restore(self, offset: dict, state_deltas: list, tail: dict | None) -> None:
        self._seq = offset.get("seq", 0)
        consumed = offset.get("consumed", 0)
        self._consumed = consumed
        self._drained_state_deltas = list(state_deltas)
        sub_restore = getattr(self.subject, "restore", None)
        if sub_restore is not None and state_deltas:
            sub_restore(state_deltas)
        if tail is None:
            return
        if tail.get("token") is not None:
            # segment-aware subject: dedup/undo decided when the segment re-arrives
            # (or provably never does — see push_barrier)
            self._pending_resume = tail
        elif tail.get("has_markers"):
            # segment-aware subject with no in-flight segment at crash time: completed
            # segments won't be re-pushed (the folded state skips them); nothing to dedup
            self._skip = max(consumed - tail.get("covered", 0), 0)
        else:
            # markerless subject: the whole journaled history is deterministically
            # re-pushed from the start; skip all of it
            self._skip = consumed


def _tidy_col(col: np.ndarray) -> np.ndarray:
    from pathway_tpu.engine.expression_evaluator import _tidy

    return _tidy(col)
