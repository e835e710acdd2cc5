"""Cross-process exchange backend — the reference's ``CommunicationConfig::Cluster``.

The reference scales past one process with timely's TCP allocator: every worker runs
the same dataflow and rows hash-route to their key's owner
(``src/engine/dataflow/config.rs:73-84``,
``external/timely-dataflow/communication/src/initialize.rs:25-31``, shard routing
``src/engine/dataflow/shard.rs:15-20``). Here the equivalent is a full-mesh TCP
exchange between the ``pathway_tpu spawn -n N`` processes: key-partitioned stateful
operators (groupby, join) partition each commit's input delta by the low bits of the
routing key and swap partitions all-to-all, so every group/join key lives on exactly
one owner process and global aggregates are exact. Commits run in lockstep — each
exchange is a barrier — mirroring timely's bulk-synchronous progress model (and the
mesh collectives the same operators use across TPU chips, ``groupby_sharded.py``).

Environment contract (set by ``pathway_tpu spawn``): ``PATHWAY_PROCESSES``,
``PATHWAY_PROCESS_ID``, ``PATHWAY_FIRST_PORT``; addresses default to
``127.0.0.1:first_port+i`` like the reference (``dataflow/config.rs:111-114``).
"""

from __future__ import annotations

import os
import pickle
import random
import socket
import struct
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from pathway_tpu.engine.profile import get_flight_recorder as _flight_recorder
from pathway_tpu.engine.tracing import (
    current_context as _trace_current,
    format_trace_header as _format_trace_header,
    get_tracer as _get_tracer,
    parse_trace_header as _parse_trace_header,
)
from pathway_tpu.engine.telemetry import (
    stage_add as _stage_add,
    stage_add_many as _stage_add_many,
)
from pathway_tpu.internals.config import env_float as _env_float

# control frame: liveness beacon, never enters the inbox (and never counts
# toward the chaos harness's per-peer data-frame streams)
HEARTBEAT_TAG = b"\x00hb"
# control frame: "these ranks died — quiesce at the epoch fence" (payload is a
# pickled sorted list of dead ranks; exempt from chaos like heartbeats so the
# recovery protocol itself stays deterministic under frame-fault plans)
FENCE_TAG = b"\x00fence"
# rejoin hello, sent by a relaunched rank dialing back into a live cluster:
# magic + rank(4, little) + epoch(4, little)
_REJOIN_MAGIC = b"PWRJ"
# membership hello, sent by a JOINER of an elastic grow transition dialing
# the existing members (and lower-ranked fellow joiners):
# magic + rank(4, little) + epoch(4, little) + target_n(4, little)
_MEMBER_MAGIC = b"PWMB"
# sanity bound on hello ranks: parked dial-ins are validated again at
# install, but a garbage rank must not grow the pending map unboundedly
_MAX_RANK = 4096


class ClusterExchange:
    """Full-mesh, length-prefixed-frame TCP exchange between spawn processes.

    Frames are tagged; ``exchange_parts`` is an all-to-all barrier: it sends one
    payload per peer under a tag and blocks until the same tag arrived from every
    peer. Deterministic tag sequences (commit id x node id x purpose) keep the
    processes in lockstep without a coordinator.

    Failure model (the supervised-runtime contract): every peer link carries
    heartbeat frames, every barrier wait has a deadline, and a dead or wedged
    peer surfaces as a typed ``PeerShutdownError`` (its socket closed) or
    ``PeerTimeoutError`` (barrier deadline / heartbeat staleness) instead of an
    infinite ``Condition.wait`` — a SIGKILLed worker fails its survivors loudly
    within the deadline, never hangs them. Knobs (env):

    - ``PATHWAY_BARRIER_TIMEOUT_S`` — per-barrier recv deadline (default 300);
    - ``PATHWAY_HEARTBEAT_INTERVAL_S`` — beacon period (default 1.0);
    - ``PATHWAY_HEARTBEAT_TIMEOUT_S`` — staleness bound while waiting on a peer
      (default 60; 0 disables);
    - ``PATHWAY_CONNECT_TIMEOUT_S`` — connect budget PER PEER dialed, and
      again for the dial-in accept join (default 60; worst-case wiring time
      for rank r is ``(n - r) x`` this bound);
    - ``PATHWAY_EXCHANGE_INBOX_FRAMES`` — per-peer inbox bound (default 1024);
      a full inbox parks the reader thread (TCP backpressure), it never grows
      without bound when one process runs ahead of its peers.

    Epoch fencing (surgical single-rank restart): every frame header carries
    the cluster epoch (``PATHWAY_CLUSTER_EPOCH``, bumped by the supervisor on
    every relaunch). When a rank dies, survivors broadcast a ``FENCE`` control
    frame, abort their in-flight barriers with :class:`ClusterFenceError`, and
    quiesce in :meth:`await_rejoin`; the supervisor relaunches ONLY the dead
    rank with ``PATHWAY_CLUSTER_REJOIN=1`` and the next epoch, and that
    replacement dials back into every survivor's still-open listener. On
    install the survivors adopt the new epoch and drop every stale-epoch data
    frame (in the inbox and still in flight on the wire) instead of letting it
    corrupt post-rejoin barriers that reuse the same commit tags. Knobs:
    ``PATHWAY_FENCE_TIMEOUT_S`` — how long a fenced survivor waits for the
    replacement to re-dial before giving up typed (default 180).
    """

    _HDR = struct.Struct("<III")  # tag_len, payload_len, cluster_epoch

    #: real socket mesh supports the fence/rejoin protocol (the in-process
    #: ThreadExchange does not — a thread peer cannot be relaunched)
    supports_rejoin = True

    def __init__(self, n_processes: int, process_id: int, first_port: int):
        self.n = n_processes
        self.me = process_id
        self.first_port = first_port
        self._conns: Dict[int, socket.socket] = {}
        self._conn_gen: Dict[int, int] = {}  # bumped when a peer link is replaced
        self._send_locks: Dict[int, threading.Lock] = {}
        self._inbox: Dict[tuple, bytes] = {}  # (peer, tag) -> payload
        self._inbox_count: Dict[int, int] = {}  # buffered frames per peer
        self._cv = threading.Condition()
        self._closed = False
        self._dead: Dict[int, str] = {}  # peer -> reason its link died
        self._last_heard: Dict[int, float] = {}
        # EWMA of peer_wall - local_wall per peer, estimated from the wall
        # stamp every heartbeat beacon carries (the trace merger aligns
        # per-rank span files with these; see clock_offsets())
        self._clock_offsets: Dict[int, float] = {}
        self._listener: Optional[socket.socket] = None
        self._stop = threading.Event()
        self.epoch = max(0, int(_env_float("PATHWAY_CLUSTER_EPOCH", 0)))
        self._rejoin_mode = os.environ.get("PATHWAY_CLUSTER_REJOIN") == "1"
        # elastic membership: a JOINER process of a grow transition
        # (PATHWAY_MEMBERSHIP_JOIN=1, PATHWAY_MEMBERSHIP_FROM=<old n>) wires
        # into the live mesh and waits for the members' install; existing
        # members park joiner hellos (which may arrive before their engines
        # have even read the directive) until apply_membership installs them
        self._membership_join = os.environ.get("PATHWAY_MEMBERSHIP_JOIN") == "1"
        self._membership_from = max(
            0, int(_env_float("PATHWAY_MEMBERSHIP_FROM", 0))
        )
        self._pending_rejoin: Dict[int, tuple] = {}  # rank -> (socket, epoch)
        self._fence_dead: "set[int]" = set()  # ranks peers told us died
        self._fence_pending = False
        # frames from an epoch we have not adopted YET: a survivor that
        # installed the rejoin first may talk to us before our own install
        # (parked here, delivered at install — dropping them would wedge the
        # post-rejoin replay until the barrier deadline)
        self._future_inbox: Dict[tuple, tuple] = {}  # (peer, tag) -> (payload, epoch)
        self.stale_frames_dropped = 0
        # incremental-rewind serve log: per-commit ring of every barrier this
        # rank sent (tag -> per-peer parts), in order. A fenced survivor that
        # rewound only the interrupted commit SERVES a replacement's tail
        # replay from this log instead of resetting + replaying its own
        # journal — the replayed commits regenerate the same deterministic tag
        # sequence, so replaying the logged parts is indistinguishable from
        # recomputing them. Bounded by PATHWAY_UNDO_RING_DEPTH commits and
        # pruned at every coordinated checkpoint (replays never reach behind
        # the manifest commit).
        self._commit_log: "OrderedDict[int, List[tuple]]" = OrderedDict()
        self._commit_log_open: Optional[int] = None
        self.commit_log_depth = max(
            0, int(_env_float("PATHWAY_UNDO_RING_DEPTH", 64))
        )
        self.barrier_timeout_s = _env_float("PATHWAY_BARRIER_TIMEOUT_S", 300.0)
        self.heartbeat_interval_s = _env_float("PATHWAY_HEARTBEAT_INTERVAL_S", 1.0)
        self.heartbeat_timeout_s = _env_float("PATHWAY_HEARTBEAT_TIMEOUT_S", 60.0)
        self.fence_timeout_s = _env_float("PATHWAY_FENCE_TIMEOUT_S", 180.0)
        self._inbox_limit = max(
            1, int(_env_float("PATHWAY_EXCHANGE_INBOX_FRAMES", 1024))
        )
        from pathway_tpu.internals.chaos import get_chaos

        self._chaos = get_chaos()
        if self._membership_join and self.n > 1:
            self._connect_membership()
        elif self._rejoin_mode and self.n > 1:
            self._connect_rejoin()
        else:
            self._connect_all()
        now = time.monotonic()
        for peer in self._conns:
            self._last_heard[peer] = now
            self._inbox_count[peer] = 0
            self._conn_gen[peer] = 0
        for peer, conn in self._conns.items():
            self._start_reader(peer, conn)
        if self.heartbeat_interval_s > 0:
            # one beacon thread PER PEER: a send stalled on one backpressured
            # link (full socket buffer) must not starve beacons to the others —
            # that would read as a false cluster-wide wedge
            for peer in self._conns:
                self._start_heartbeat(peer)
        # the listener stays open for the cluster's lifetime: a surgically
        # relaunched rank rejoins by dialing it (parked until the engine
        # reaches the fence and installs the link)
        if self._listener is not None:
            threading.Thread(
                target=self._rejoin_acceptor, daemon=True,
                name="pathway:cluster-rejoin-accept",
            ).start()

    def _start_reader(self, peer: int, conn: socket.socket) -> None:
        threading.Thread(
            target=self._reader, args=(peer, conn), daemon=True,
            name=f"pathway:cluster-rx-{peer}",
        ).start()

    def _start_heartbeat(self, peer: int) -> None:
        threading.Thread(
            target=self._heartbeat_loop, args=(peer, self._conn_gen.get(peer, 0)),
            daemon=True, name=f"pathway:cluster-hb-{peer}",
        ).start()

    # -- wiring --------------------------------------------------------------

    def _connect_all(self) -> None:
        listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        listener.bind(("127.0.0.1", self.first_port + self.me))
        listener.listen(self.n)
        self._listener = listener

        accepted: Dict[int, socket.socket] = {}
        accept_errors: List[BaseException] = []

        def accept_loop() -> None:
            try:
                for _ in range(self.me):  # lower-ranked peers dial us
                    conn, _addr = listener.accept()
                    peer = int.from_bytes(self._recv_exact(conn, 4), "little")
                    accepted[peer] = conn
            except BaseException as exc:  # surfaced after join: silent partial
                accept_errors.append(exc)  # wiring would drop peers' data

        acceptor = threading.Thread(target=accept_loop, daemon=True)
        acceptor.start()
        connect_budget = _env_float("PATHWAY_CONNECT_TIMEOUT_S", 60.0)
        try:
            rng = random.Random((self.me << 16) ^ self.first_port)
            for peer in range(self.me + 1, self.n):
                s = self._dial_peer(peer, connect_budget, rng)
                s.sendall(self.me.to_bytes(4, "little"))
                self._conns[peer] = s
            acceptor.join(timeout=connect_budget)
            if acceptor.is_alive():
                raise PeerTimeoutError(
                    f"cluster process {self.me} timed out waiting for dial-ins"
                )
            if accept_errors:
                raise ConnectionError(
                    f"cluster process {self.me} failed accepting dial-ins"
                ) from accept_errors[0]
            if len(accepted) != self.me:
                raise ConnectionError(
                    f"cluster process {self.me} expected {self.me} dial-ins, got "
                    f"{sorted(accepted)}"
                )
        except BaseException:
            # failed wiring must not strand fds: a stranded listener wedges the
            # retry (and the restarted rank) on "Address already in use"
            for s in list(self._conns.values()) + list(accepted.values()):
                try:
                    s.close()
                except OSError:
                    pass
            self._conns.clear()
            try:
                listener.close()
            except OSError:
                pass
            self._listener = None
            raise
        self._conns.update(accepted)
        for peer, conn in self._conns.items():
            self._tune_socket(conn)
            self._send_locks[peer] = threading.Lock()

    def _dial_peer(
        self, peer: int, connect_budget: float, rng: random.Random
    ) -> socket.socket:
        """Dial one peer with exponential backoff + jitter: the peer may not be
        up yet, and N processes hammering one listener at a fixed 50 ms period
        synchronize into accept-queue bursts. Raises :class:`PeerTimeoutError`
        past the budget."""
        deadline = time.monotonic() + connect_budget
        delay = 0.05
        while True:
            try:
                s = socket.create_connection(
                    ("127.0.0.1", self.first_port + peer), timeout=5
                )
                break
            except OSError as exc:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise PeerTimeoutError(
                        f"cluster process {self.me} could not reach peer "
                        f"{peer} on port {self.first_port + peer} within "
                        f"{connect_budget:.0f}s"
                    ) from exc
                time.sleep(min(remaining, delay * (1.0 + 0.25 * rng.random())))
                delay = min(delay * 2, 2.0)
        # back to fully blocking: create_connection's dial timeout must not
        # linger on the socket, or every later sendall/recv on this link
        # spuriously times out after 5s of quiet (SO_SNDTIMEO and the
        # recv-side deadlines own timeout behavior from here on)
        s.settimeout(None)
        return s

    def _tune_socket(self, conn: socket.socket) -> None:
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        if self.barrier_timeout_s > 0:
            # send-side deadline (SO_SNDTIMEO is send-ONLY, so the reader
            # thread's blocking recv is untouched): a peer that stopped
            # reading must surface as a typed error from _send, not hang
            # sendall forever once the TCP buffers fill — _recv's deadlines
            # can't fire if we never get there
            conn.setsockopt(
                socket.SOL_SOCKET,
                socket.SO_SNDTIMEO,
                struct.pack(
                    "ll",
                    int(self.barrier_timeout_s),
                    int(self.barrier_timeout_s % 1 * 1_000_000),
                ),
            )

    def _connect_rejoin(self) -> None:
        """Relaunched-rank wiring: dial EVERY survivor's still-open listener and
        introduce ourselves with the rejoin hello (rank + new epoch). The
        survivors' acceptor threads park the links until their engines reach
        the epoch fence and install them — no accept phase on our side."""
        if self._chaos is not None and self._chaos.drop_rejoin(self.me):
            # deterministic fault injection: the rejoin handshake is "lost".
            # Failing the wiring loudly (instead of silently half-joining)
            # exercises the surgical -> restart-all escalation in the supervisor.
            raise PeerTimeoutError(
                f"chaos: rejoin handshake of rank {self.me} (epoch {self.epoch}) "
                "dropped by plan"
            )
        listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        listener.bind(("127.0.0.1", self.first_port + self.me))
        listener.listen(self.n)
        self._listener = listener
        connect_budget = _env_float("PATHWAY_CONNECT_TIMEOUT_S", 60.0)
        hello = (
            _REJOIN_MAGIC
            + self.me.to_bytes(4, "little")
            + (self.epoch & 0xFFFFFFFF).to_bytes(4, "little")
        )
        rng = random.Random((self.me << 16) ^ self.first_port ^ self.epoch)
        try:
            for peer in range(self.n):
                if peer == self.me:
                    continue
                # a second dead rank (double failure) makes a survivor
                # unreachable: _dial_peer's typed timeout fails the rejoin
                # loudly so the supervisor degrades to restart-all
                s = self._dial_peer(peer, connect_budget, rng)
                s.sendall(hello)
                self._conns[peer] = s
        except BaseException:
            for s in list(self._conns.values()):
                try:
                    s.close()
                except OSError:
                    pass
            self._conns.clear()
            try:
                listener.close()
            except OSError:
                pass
            self._listener = None
            raise
        for peer, conn in self._conns.items():
            self._tune_socket(conn)
            self._send_locks[peer] = threading.Lock()

    def _membership_hello(self) -> bytes:
        return (
            _MEMBER_MAGIC
            + self.me.to_bytes(4, "little")
            + (self.epoch & 0xFFFFFFFF).to_bytes(4, "little")
            + self.n.to_bytes(4, "little")
        )

    def _connect_membership(self) -> None:
        """Joiner wiring for an elastic grow transition: ``self.n`` is the
        TARGET topology and ``self.epoch`` the transition's epoch. The joiner
        dials every existing member (ranks < PATHWAY_MEMBERSHIP_FROM) and
        every lower-ranked fellow joiner, and accepts dial-ins from
        higher-ranked joiners — members park our hello until their engines
        reach the membership quiesce point and install (``apply_membership``).
        """
        if self._chaos is not None:
            # deterministic fault injection: a joiner killed before it ever
            # installs — the headline join-side crash of the transition
            self._chaos.maybe_scale_kill(
                self.me, "scale_join_kill", epoch=self.epoch
            )
        if self._chaos is not None and self._chaos.scale_fault(
            "dropped_scale_handshake", self.me
        ):
            # deterministic fault injection: the joiner's hello is "lost" —
            # failing the wiring loudly exercises the supervisor's
            # joiner-relaunch / restart-all escalation
            raise PeerTimeoutError(
                f"chaos: membership handshake of joiner rank {self.me} "
                f"(epoch {self.epoch}) dropped by plan"
            )
        from_n = self._membership_from
        listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        listener.bind(("127.0.0.1", self.first_port + self.me))
        listener.listen(self.n)
        self._listener = listener
        connect_budget = _env_float("PATHWAY_CONNECT_TIMEOUT_S", 60.0)
        higher_joiners = self.n - 1 - self.me
        accepted: Dict[int, socket.socket] = {}
        accept_errors: List[BaseException] = []

        def accept_loop() -> None:
            try:
                while len(accepted) < higher_joiners:
                    conn, _addr = listener.accept()
                    conn.settimeout(10.0)
                    hello = self._recv_exact(conn, len(_MEMBER_MAGIC) + 12)
                    conn.settimeout(None)
                    if not hello.startswith(_MEMBER_MAGIC):
                        conn.close()
                        continue
                    peer = int.from_bytes(hello[4:8], "little")
                    if not (self.me < peer < self.n):
                        conn.close()
                        continue
                    accepted[peer] = conn
            except BaseException as exc:  # surfaced after join
                accept_errors.append(exc)

        acceptor = threading.Thread(target=accept_loop, daemon=True)
        acceptor.start()
        hello = self._membership_hello()
        rng = random.Random((self.me << 16) ^ self.first_port ^ self.epoch)
        try:
            # every existing member (< from_n) and every lower-ranked joiner
            for peer in range(self.me):
                s = self._dial_peer(peer, connect_budget, rng)
                s.sendall(hello)
                self._conns[peer] = s
            if higher_joiners:
                acceptor.join(timeout=connect_budget)
                if acceptor.is_alive():
                    raise PeerTimeoutError(
                        f"joiner rank {self.me} timed out waiting for "
                        f"{higher_joiners} higher-ranked joiner dial-in(s) "
                        f"(got {sorted(accepted)})"
                    )
                if accept_errors:
                    raise ConnectionError(
                        f"joiner rank {self.me} failed accepting fellow "
                        "joiners"
                    ) from accept_errors[0]
        except BaseException:
            for s in list(self._conns.values()) + list(accepted.values()):
                try:
                    s.close()
                except OSError:
                    pass
            self._conns.clear()
            try:
                listener.close()
            except OSError:
                pass
            self._listener = None
            raise
        self._conns.update(accepted)
        for peer, conn in self._conns.items():
            self._tune_socket(conn)
            self._send_locks[peer] = threading.Lock()

    def _rejoin_acceptor(self) -> None:
        """Post-wiring accept loop: park dial-ins from relaunched ranks until
        the engine's fence path installs them (``await_rejoin``). Runs for the
        exchange's lifetime; exits when the listener closes."""
        listener = self._listener
        while not self._closed:
            try:
                conn, _addr = listener.accept()
            except OSError:
                return  # listener closed (teardown)
            try:
                conn.settimeout(10.0)
                hello = self._recv_exact(conn, len(_REJOIN_MAGIC) + 8)
                if hello.startswith(_MEMBER_MAGIC):
                    hello += self._recv_exact(conn, 4)  # + target_n
                conn.settimeout(None)
            except (ConnectionError, OSError):
                try:
                    conn.close()
                except OSError:
                    pass
                continue
            rank = int.from_bytes(hello[4:8], "little")
            epoch = int.from_bytes(hello[8:12], "little")
            stale_conn: Optional[socket.socket] = None
            with self._cv:
                if hello.startswith(_MEMBER_MAGIC):
                    # joiner hello of an elastic grow: the rank may exceed the
                    # CURRENT n (that is the point) and may arrive before this
                    # member's engine has even read the directive — park it;
                    # apply_membership validates against the real target
                    ok = (
                        not self._closed
                        and 0 <= rank < _MAX_RANK
                        and rank != self.me
                        and epoch > self.epoch
                    )
                else:
                    ok = (
                        not self._closed
                        and hello.startswith(_REJOIN_MAGIC)
                        and 0 <= rank < self.n
                        and rank != self.me
                        # stale-epoch rejoins (a zombie replacement from an
                        # abandoned attempt) are refused, not installed
                        and epoch > self.epoch
                    )
                if ok:
                    old = self._pending_rejoin.pop(rank, None)
                    if old is not None:
                        stale_conn = old[0]
                    self._pending_rejoin[rank] = (conn, epoch)
                    self._cv.notify_all()
            if not ok:
                stale_conn = conn
            if stale_conn is not None:
                try:
                    stale_conn.close()
                except OSError:
                    pass

    @staticmethod
    def _recv_exact(conn: socket.socket, n: int) -> bytes:
        buf = b""
        while len(buf) < n:
            chunk = conn.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("cluster peer closed the connection")
            buf += chunk
        return buf

    def _reader(self, peer: int, conn: socket.socket) -> None:
        try:
            while True:
                hdr = self._recv_exact(conn, self._HDR.size)
                tag_len, payload_len, frame_epoch = self._HDR.unpack(hdr)
                tag = self._recv_exact(conn, tag_len)
                payload = self._recv_exact(conn, payload_len) if payload_len else b""
                if tag == HEARTBEAT_TAG and payload:
                    # outside _cv: the tracer push takes its own lock and must
                    # not nest under the mesh condition
                    self._note_peer_clock(peer, payload)
                if tag != HEARTBEAT_TAG:
                    _stage_add_many({
                        f"exchange.peer{peer}.bytes_received": float(
                            self._HDR.size + tag_len + payload_len
                        ),
                        f"exchange.peer{peer}.frames_received": 1.0,
                    })
                with self._cv:
                    self._last_heard[peer] = time.monotonic()
                    if tag == HEARTBEAT_TAG:
                        # beacons prove liveness whatever the epoch — a peer
                        # mid-fence is alive, not stale
                        self._cv.notify_all()
                        continue
                    if tag == FENCE_TAG:
                        if frame_epoch >= self.epoch:
                            try:
                                ranks = pickle.loads(payload)
                            except Exception:
                                ranks = []
                            self._fence_dead.update(int(r) for r in ranks)
                            self._fence_pending = True
                            _stage_add("cluster.fences_received")
                            _flight_recorder().record_event(
                                "fence_received",
                                from_peer=peer,
                                dead_ranks=sorted(self._fence_dead),
                                epoch=self.epoch,
                            )
                            self._cv.notify_all()
                        continue
                    # bounded inbox: park until the consumer drains (the unread
                    # backlog itself proves the peer is alive, so keep the
                    # heartbeat clock fresh while parked — the peer's beacons
                    # queue behind the data we are not reading)
                    while (
                        self._inbox_count[peer] >= self._inbox_limit
                        and not self._closed
                        and frame_epoch >= self.epoch
                    ):
                        self._last_heard[peer] = time.monotonic()
                        self._cv.wait(timeout=0.2)
                    if self._closed:
                        return
                    if frame_epoch < self.epoch:
                        # stale-epoch data frame (sent before the sender
                        # fenced): DROPPED, never delivered — post-rejoin
                        # barriers replay the same commit tags, and a stale
                        # payload under a reused tag would silently corrupt
                        # them
                        self.stale_frames_dropped += 1
                        continue
                    if frame_epoch > self.epoch:
                        # a peer that installed the rejoin BEFORE us is already
                        # talking at the new epoch: park the frame (it still
                        # counts toward the inbox bound) and deliver it when
                        # our own install adopts that epoch — dropping it would
                        # lose a barrier part nobody retransmits
                        self._future_inbox[(peer, tag)] = (payload, frame_epoch)
                        self._inbox_count[peer] += 1
                        self._cv.notify_all()
                        continue
                    self._inbox[(peer, tag)] = payload
                    self._inbox_count[peer] += 1
                    self._cv.notify_all()
        except (ConnectionError, OSError) as exc:
            with self._cv:
                # a replaced link (rejoin installed a fresh socket for this
                # peer) dying late must not re-mark the NEW link dead
                if self._conns.get(peer) is conn:
                    self._dead.setdefault(peer, str(exc) or type(exc).__name__)
                self._cv.notify_all()

    def _send(self, peer: int, tag: bytes, payload: bytes) -> None:
        conn = self._conns.get(peer)
        if conn is None:
            # link removed by a membership shrink: a stale heartbeat thread
            # racing the install must simply stop, not KeyError
            return
        lock = self._send_locks.get(peer)
        if lock is None:
            return
        frame = (
            self._HDR.pack(len(tag), len(payload), self.epoch & 0xFFFFFFFF)
            + tag
            + payload
        )
        if self._chaos is not None and tag not in (HEARTBEAT_TAG, FENCE_TAG):
            action = self._chaos.frame_action(self.me, peer)
            if action.kind == "drop":
                return  # peer's barrier deadline turns this into PeerTimeoutError
            if action.kind == "delay":
                time.sleep(action.delay_s)
            elif action.kind == "truncate":
                # torn write + dead link, as a crash mid-send would leave it
                with lock:
                    try:
                        conn.sendall(frame[: max(1, len(frame) // 2)])
                        conn.shutdown(socket.SHUT_RDWR)
                    except OSError:
                        pass
                with self._cv:
                    self._dead.setdefault(peer, "chaos: link truncated")
                    self._cv.notify_all()
                return
        try:
            with lock:
                conn.sendall(frame)
            if tag != HEARTBEAT_TAG:
                # per-peer traffic accounting (heartbeats excluded — 1 Hz
                # beacons would drown the data-frame signal)
                _stage_add_many({
                    f"exchange.peer{peer}.bytes_sent": float(len(frame)),
                    f"exchange.peer{peer}.frames_sent": 1.0,
                })
        except OSError as exc:
            timed_out = isinstance(exc, (socket.timeout, BlockingIOError))
            with self._cv:
                # the stream may have a torn partial frame on it now — the
                # link is unusable either way, so the peer is dead to us
                # (unless the link was already replaced by a rejoin: a stale
                # heartbeat thread failing on the OLD socket must not poison
                # the freshly installed one)
                if self._conns.get(peer) is conn:
                    self._dead.setdefault(peer, str(exc) or type(exc).__name__)
                self._cv.notify_all()
            if timed_out:
                raise PeerTimeoutError(
                    f"cluster process {self.me} send of {tag!r} to peer {peer} "
                    f"stalled past the {self.barrier_timeout_s:.0f}s deadline "
                    "— peer stopped reading"
                ) from exc
            raise PeerShutdownError(
                f"cluster process {self.me} failed sending {tag!r} to peer "
                f"{peer}: {exc}"
            ) from exc

    def _recv(self, peer: int, tag: bytes, timeout: Optional[float] = None) -> bytes:
        if timeout is None:
            timeout = self.barrier_timeout_s
        deadline = time.monotonic() + timeout
        with self._cv:
            while (peer, tag) not in self._inbox:
                if self._fence_pending:
                    raise ClusterFenceError(
                        f"cluster peer requested an epoch fence (ranks "
                        f"{sorted(self._fence_dead)} died) while process "
                        f"{self.me} waited for {tag!r} at epoch {self.epoch}"
                    )
                if peer in self._dead:
                    raise PeerShutdownError(
                        f"cluster peer {peer} disconnected while process "
                        f"{self.me} waited for {tag!r}: {self._dead[peer]}"
                    )
                if self._closed:
                    raise PeerShutdownError(
                        f"cluster exchange closed while waiting for {tag!r} "
                        f"from peer {peer}"
                    )
                now = time.monotonic()
                heard = self._last_heard.get(peer)
                if (
                    self.heartbeat_timeout_s > 0
                    # without beacons, silence between barriers is normal —
                    # staleness is only meaningful while heartbeats flow
                    and self.heartbeat_interval_s > 0
                    and heard is not None
                    and now - heard > self.heartbeat_timeout_s
                ):
                    _stage_add("cluster.peer_stale_trips")
                    _flight_recorder().record_event(
                        "peer_stale",
                        peer=peer,
                        tag=tag.decode("utf-8", "replace"),
                        stale_s=round(now - heard, 3),
                    )
                    raise PeerTimeoutError(
                        f"cluster peer {peer} heartbeat is {now - heard:.1f}s "
                        f"stale (> {self.heartbeat_timeout_s:.0f}s) while process "
                        f"{self.me} waited for {tag!r} — peer is wedged"
                    )
                remaining = deadline - now
                if remaining <= 0:
                    _stage_add("cluster.barrier_timeouts")
                    _flight_recorder().record_event(
                        "barrier_timeout",
                        peer=peer,
                        tag=tag.decode("utf-8", "replace"),
                        timeout_s=timeout,
                    )
                    raise PeerTimeoutError(
                        f"cluster process {self.me} timed out after "
                        f"{timeout:.0f}s waiting for {tag!r} from peer {peer}"
                    )
                self._cv.wait(timeout=min(remaining, 0.5))
            payload = self._inbox.pop((peer, tag))
            self._inbox_count[peer] -= 1
            self._cv.notify_all()  # unpark a backpressured reader
            return payload

    # -- liveness -------------------------------------------------------------

    def _heartbeat_loop(self, peer: int, gen: int = 0) -> None:
        while not self._stop.wait(self.heartbeat_interval_s):
            with self._cv:
                # _dead/_conn_gen are _cv-owned state; reading them unlocked
                # raced the rejoin install (PWA103 — a torn read could keep a
                # stale beacon thread alive against a replaced link)
                stale = (
                    self._closed
                    or peer in self._dead
                    # the link was replaced by a rejoin; its NEW heartbeat
                    # thread owns the beacons now
                    or self._conn_gen.get(peer, 0) != gen
                )
            if stale:
                return
            try:
                # beacons carry the sender's wall clock: receivers estimate
                # per-peer clock offsets for the trace merger's alignment
                self._send(peer, HEARTBEAT_TAG, struct.pack("<d", time.time()))
            except (PeerShutdownError, OSError):
                return  # _send already recorded the death

    def _note_peer_clock(self, peer: int, payload: bytes) -> None:
        """A heartbeat beacon carried the sender's wall clock: EWMA the
        ``peer_wall - local_wall`` offset (biased by one-way latency — good to
        ~ms on a LAN, plenty to causally order cross-rank spans) and publish
        the table to the tracer so every flush's ``_meta`` carries it."""
        try:
            (sender_wall,) = struct.unpack("<d", payload)
        except struct.error:
            return  # malformed beacon: liveness already counted, skip the clock
        sample = sender_wall - time.time()
        with self._cv:
            prev = self._clock_offsets.get(peer)
            self._clock_offsets[peer] = (
                sample if prev is None else prev + 0.2 * (sample - prev)
            )
            offsets = dict(self._clock_offsets)
        _get_tracer().set_clock_offsets(offsets)

    def clock_offsets(self) -> Dict[int, float]:
        """Heartbeat-estimated ``peer_wall - local_wall`` seconds per peer
        (the trace merger aligns per-rank span files with these)."""
        with self._cv:
            return dict(self._clock_offsets)

    def heartbeat_ages(self) -> Dict[int, float]:
        """Seconds since each peer was last heard from (any frame). The shared
        liveness signal: served by ``/healthz`` and written to the supervisor's
        per-rank status file."""
        now = time.monotonic()
        with self._cv:
            return {peer: now - t for peer, t in self._last_heard.items()}

    def dead_peers(self) -> Dict[int, str]:
        with self._cv:
            return dict(self._dead)

    # -- epoch fence / surgical rejoin ----------------------------------------

    def begin_fence(self) -> None:
        """Tell every live peer this rank observed a death and is quiescing at
        the epoch fence. Peers abort their in-flight barriers with
        :class:`ClusterFenceError` within socket latency instead of sitting out
        the full barrier deadline. Best-effort: a peer whose link also died
        learns about the fence from its own typed error."""
        with self._cv:
            dead = sorted(set(self._dead) | self._fence_dead)
        _stage_add("cluster.fence_broadcasts")
        _flight_recorder().record_event(
            "fence_broadcast", dead_ranks=dead, epoch=self.epoch
        )
        payload = pickle.dumps(dead, protocol=pickle.HIGHEST_PROTOCOL)
        for peer in list(self._conns):
            if peer in dead:
                continue
            try:
                self._send(peer, FENCE_TAG, payload)
            except (PeerShutdownError, PeerTimeoutError, OSError):
                pass

    def await_rejoin(
        self,
        timeout: Optional[float] = None,
        on_wait: "Optional[Callable[[], None]]" = None,
    ) -> int:
        """Quiesce at the epoch fence until the supervisor's replacement
        rank(s) re-dial, then install the new link(s) and adopt their epoch.

        Returns the new cluster epoch. ``on_wait`` (if given) is called every
        poll interval WITHOUT the exchange lock held — the engine uses it to
        keep publishing liveness status so the supervisor's staleness monitor
        doesn't shoot a healthy, fenced survivor. Raises
        :class:`PeerTimeoutError` when no replacement arrives in time (second
        failure, exhausted restart budget — the caller escalates)."""
        if timeout is None:
            timeout = self.fence_timeout_s
        deadline = time.monotonic() + timeout
        while True:
            installed: Dict[int, tuple] = {}
            old_conns: List[socket.socket] = []
            with self._cv:
                # parked MEMBERSHIP hellos (rank >= n, a pending grow) are
                # not replacements: they stay parked for apply_membership
                replacements = {
                    r: v for r, v in self._pending_rejoin.items() if r < self.n
                }
                waiting = (set(self._dead) | self._fence_dead) - set(
                    replacements
                )
                if not waiting and replacements:
                    installed = replacements
                    for r in replacements:
                        self._pending_rejoin.pop(r, None)
                    new_epoch = max(e for (_c, e) in installed.values())
                    for rank, (conn, _e) in installed.items():
                        old = self._conns.get(rank)
                        if old is not None and old is not conn:
                            old_conns.append(old)
                        self._conns[rank] = conn
                        self._conn_gen[rank] = self._conn_gen.get(rank, 0) + 1
                        self._dead.pop(rank, None)
                        self._last_heard[rank] = time.monotonic()
                        # minted under _cv: _send reads this dict from
                        # heartbeat threads concurrently with the install
                        self._send_locks.setdefault(rank, threading.Lock())
                    # the aborted epoch's frames must never meet the replayed
                    # barriers that reuse their tags: purge the whole inbox
                    # (parked readers wake, re-check the epoch, and drop)
                    self.stale_frames_dropped += len(self._inbox)
                    self._inbox.clear()
                    for p in self._inbox_count:
                        self._inbox_count[p] = 0
                    # deliver frames peers already sent at the epoch we are
                    # adopting (they installed first and raced ahead of us)
                    future, self._future_inbox = self._future_inbox, {}
                    for (peer, tag), (payload, ep) in future.items():
                        if ep == new_epoch and peer in self._conns:
                            self._inbox[(peer, tag)] = payload
                            self._inbox_count[peer] = (
                                self._inbox_count.get(peer, 0) + 1
                            )
                        else:
                            self.stale_frames_dropped += 1
                    self._fence_dead.clear()
                    self._fence_pending = False
                    self.epoch = new_epoch
                    self._cv.notify_all()
                elif self._closed:
                    raise PeerShutdownError(
                        f"cluster exchange closed while process {self.me} "
                        "fenced for a rejoin"
                    )
                else:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise PeerTimeoutError(
                            f"process {self.me} fenced at epoch {self.epoch} "
                            f"but no replacement rank re-dialed within "
                            f"{timeout:.0f}s (waiting on {sorted(waiting)})"
                        )
                    self._cv.wait(timeout=min(remaining, 0.25))
            if installed:
                for old in old_conns:
                    try:
                        old.close()
                    except OSError:
                        pass
                for rank, (conn, _e) in installed.items():
                    self._tune_socket(conn)
                    self._start_reader(rank, conn)
                    if self.heartbeat_interval_s > 0:
                        self._start_heartbeat(rank)
                _stage_add("cluster.rejoins_installed")
                _flight_recorder().record_event(
                    "rejoin_installed",
                    ranks=sorted(installed),
                    epoch=self.epoch,
                )
                return self.epoch
            if on_wait is not None:
                on_wait()

    # -- elastic membership (grow/shrink the live mesh) ------------------------

    def apply_membership(
        self,
        new_n: int,
        new_epoch: int,
        timeout: Optional[float] = None,
        on_wait: "Optional[Callable[[], None]]" = None,
    ) -> int:
        """Install the new topology on an EXISTING member at the membership
        quiesce point: wait for every joiner's parked dial-in (grow), or cut
        the draining ranks' links (shrink), then atomically adopt ``new_n``
        and ``new_epoch`` — purging the old epoch's inbox and delivering
        frames peers already sent at the new epoch (members that applied
        first race ahead exactly like staggered rejoin installs).

        Returns the new epoch. Raises :class:`PeerTimeoutError` when a
        joiner never dials in (killed/dropped handshake — the caller dies
        typed and the supervisor escalates)."""
        if timeout is None:
            timeout = self.fence_timeout_s
        deadline = time.monotonic() + timeout
        joiner_ranks = {r for r in range(new_n) if r >= self.n}
        while True:
            installed: Dict[int, tuple] = {}
            removed_conns: List[socket.socket] = []
            with self._cv:
                ready = {
                    r
                    for r, (_c, ep) in self._pending_rejoin.items()
                    if r in joiner_ranks and ep == new_epoch
                }
                if ready >= joiner_ranks:
                    for rank in sorted(joiner_ranks):
                        conn, _ep = self._pending_rejoin.pop(rank)
                        installed[rank] = (conn, new_epoch)
                        self._conns[rank] = conn
                        self._conn_gen[rank] = self._conn_gen.get(rank, 0) + 1
                        self._send_locks.setdefault(rank, threading.Lock())
                        self._last_heard[rank] = time.monotonic()
                        self._inbox_count.setdefault(rank, 0)
                    # shrink: cut the draining ranks' links (their readers see
                    # the conn replaced/absent and never mark them dead)
                    for rank in [r for r in self._conns if r >= new_n]:
                        removed_conns.append(self._conns.pop(rank))
                        self._conn_gen[rank] = self._conn_gen.get(rank, 0) + 1
                        self._send_locks.pop(rank, None)
                        self._last_heard.pop(rank, None)
                        self._inbox_count.pop(rank, None)
                        self._dead.pop(rank, None)
                        self._fence_dead.discard(rank)
                    # zombie hellos of abandoned attempts: refuse, never keep
                    for rank in [
                        r
                        for r, (_c, ep) in self._pending_rejoin.items()
                        if ep <= new_epoch
                    ]:
                        removed_conns.append(self._pending_rejoin.pop(rank)[0])
                    # the old epoch's frames must never meet the new
                    # topology's barriers (same discipline as a rejoin
                    # install): purge, then deliver parked new-epoch frames
                    self.stale_frames_dropped += len(self._inbox)
                    self._inbox.clear()
                    for p in self._inbox_count:
                        self._inbox_count[p] = 0
                    future, self._future_inbox = self._future_inbox, {}
                    for (peer, tag), (payload, ep) in future.items():
                        if ep == new_epoch and peer in self._conns:
                            self._inbox[(peer, tag)] = payload
                            self._inbox_count[peer] = (
                                self._inbox_count.get(peer, 0) + 1
                            )
                        else:
                            self.stale_frames_dropped += 1
                    self.n = new_n
                    self.epoch = new_epoch
                    self._cv.notify_all()
                elif self._closed:
                    raise PeerShutdownError(
                        f"cluster exchange closed while process {self.me} "
                        "waited to apply the membership change"
                    )
                else:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise PeerTimeoutError(
                            f"process {self.me} waited {timeout:.0f}s for "
                            f"joiner rank(s) {sorted(joiner_ranks - ready)} "
                            f"to dial in at epoch {new_epoch} — membership "
                            "change cannot complete"
                        )
                    self._cv.wait(timeout=min(remaining, 0.25))
            if installed or not joiner_ranks:
                for conn in removed_conns:
                    try:
                        conn.close()
                    except OSError:
                        pass
                for rank, (conn, _e) in installed.items():
                    self._tune_socket(conn)
                    self._start_reader(rank, conn)
                    if self.heartbeat_interval_s > 0:
                        self._start_heartbeat(rank)
                _stage_add("cluster.membership_applied")
                _flight_recorder().record_event(
                    "membership_applied",
                    n=self.n,
                    epoch=self.epoch,
                    joined=sorted(installed),
                )
                return self.epoch
            if on_wait is not None:
                on_wait()

    def leave_membership(self) -> None:
        """A draining leaver's mesh teardown (after the final old-topology
        barrier): just the idempotent close — survivors have already stopped
        addressing this rank, and their readers ignore links no longer in
        ``_conns``."""
        _stage_add("cluster.membership_left")
        _flight_recorder().record_event(
            "membership_left", rank=self.me, epoch=self.epoch
        )
        self.close()

    # -- incremental-rewind serve log -----------------------------------------

    def begin_commit_log(self, commit_id: int) -> None:
        """Open the serve-log entry for one live commit: every barrier sent
        until :meth:`end_commit_log` is recorded under this id. Called from the
        single engine thread only."""
        if self.commit_log_depth <= 0:
            return
        self._commit_log.pop(commit_id, None)
        self._commit_log[commit_id] = []
        self._commit_log_open = commit_id

    def end_commit_log(self) -> None:
        """Seal the open entry (the commit completed) and evict the oldest
        entries past the depth bound."""
        self._commit_log_open = None
        while len(self._commit_log) > self.commit_log_depth:
            self._commit_log.popitem(last=False)

    def discard_open_commit_log(self) -> None:
        """Drop the in-flight entry: an interrupted commit's partial barrier
        stream must never be served (its tags will be regenerated live after
        the rewind)."""
        if self._commit_log_open is not None:
            self._commit_log.pop(self._commit_log_open, None)
            self._commit_log_open = None

    def commit_log_covers(self, commit_ids: "List[int]") -> bool:
        return all(cid in self._commit_log for cid in commit_ids)

    def serve_commit_log(self, commit_id: int) -> int:
        """Re-run every logged barrier of one commit with the ORIGINAL parts,
        discarding what peers send back (a serving survivor already holds the
        results in its live state). Returns the number of barriers served."""
        entries = self._commit_log.get(commit_id, ())
        for tag, parts in entries:
            self.exchange_parts(tag, parts)
        return len(entries)

    def prune_commit_log(self, through_commit: int) -> None:
        """Drop sealed entries ≤ ``through_commit`` (a durable checkpoint
        manifest guarantees no replay will ever reach behind it)."""
        for cid in [c for c in self._commit_log if c <= through_commit]:
            if cid != self._commit_log_open:
                del self._commit_log[cid]

    # -- collectives ----------------------------------------------------------

    def exchange_parts(self, tag: bytes, parts: Dict[int, bytes]) -> Dict[int, bytes]:
        """All-to-all: send ``parts[peer]`` to each peer, receive theirs. Barrier.

        Raises :class:`PeerShutdownError` when a peer's link died, or
        :class:`PeerTimeoutError` when a peer missed the barrier deadline or
        went heartbeat-stale — never blocks forever on a dead peer.

        Straggler attribution: the peer whose frame this process BLOCKED on
        longest arrived last (frames already inboxed cost ~0), so per-barrier
        wait seconds and a per-peer straggler count land in the stage
        counters; the flight recorder's ``note_barrier`` marks the tag in
        flight so a death mid-barrier names it in the dump."""
        recorder = _flight_recorder()
        if self._commit_log_open is not None:
            # live commit under the rewind contract: remember exactly what this
            # barrier sent, so a post-fence serve can replay it verbatim
            self._commit_log[self._commit_log_open].append((tag, dict(parts)))
        for peer in self._conns:
            self._send(peer, tag, parts.get(peer, b""))
        recorder.note_barrier(tag)
        t0 = time.perf_counter()
        out: Dict[int, bytes] = {}
        slowest_peer = -1
        slowest_wait = 0.0
        for peer in self._conns:
            w0 = time.perf_counter()
            out[peer] = self._recv(peer, tag)
            wait = time.perf_counter() - w0
            if wait > slowest_wait:
                slowest_wait = wait
                slowest_peer = peer
        barrier_wait = time.perf_counter() - t0
        updates = {
            "exchange.barriers": 1.0,
            "exchange.barrier_wait_s": barrier_wait,
        }
        if slowest_peer >= 0 and slowest_wait > 0.001:
            # only meaningful blocking attributes a straggler: an inboxed
            # frame's ~µs pop must not smear the attribution
            updates[f"exchange.straggler.peer{slowest_peer}"] = 1.0
            updates[f"exchange.peer{slowest_peer}.straggler_wait_s"] = slowest_wait
        _stage_add_many(updates)
        tracer = _get_tracer()
        if tracer.recording() and _trace_current() is not None:
            # a barrier inside a traced scope (the commit span's context-local
            # parent) becomes a child span carrying the SAME straggler
            # attribution the stage counters got — "barrier held 41 ms by
            # rank 3" in the merged critical path
            span = tracer.start(
                "barrier", f"barrier {tag.decode('utf-8', 'replace')}"
            )
            if span is not None:
                span.ts -= barrier_wait  # stamp the barrier's START
                span.ts_mono -= barrier_wait
                span.duration_s = max(barrier_wait, 1e-9)
                if slowest_peer >= 0 and slowest_wait > 0.001:
                    span.attrs["straggler_rank"] = slowest_peer
                    span.attrs["straggler_wait_s"] = slowest_wait
                tracer.finish(span)
        # cleared on SUCCESS only: when a recv raises (peer death, barrier
        # timeout) the mark must survive the unwind — the fence/crash dump's
        # summary names this tag as the pending barrier, and the next
        # successful barrier overwrites it anyway
        recorder.note_barrier(None)
        return out

    def allgather(self, tag: bytes, value: Any) -> List[Any]:
        """Every process contributes ``value``; all receive the full list (by rank)."""
        blob = pickle.dumps(value, protocol=pickle.HIGHEST_PROTOCOL)
        got = self.exchange_parts(tag, {p: blob for p in self._conns})
        out: List[Any] = [None] * self.n
        out[self.me] = value
        for peer, payload in got.items():
            out[peer] = pickle.loads(payload)
        return out

    def close(self) -> None:
        """Idempotent teardown — safe to call again from the fence path when a
        rejoin aborts mid-handshake (never double-closes peer sockets, parked
        rejoin dial-ins, or the listener)."""
        self._stop.set()
        with self._cv:
            if self._closed:
                return
            self._closed = True
            pending = list(self._pending_rejoin.values())
            self._pending_rejoin = {}
            conns = list(self._conns.values())
            listener, self._listener = self._listener, None
            self._cv.notify_all()  # release parked readers and waiting recvs
        for conn, _epoch in pending:
            try:
                conn.close()
            except OSError:
                pass
        for conn in conns:
            try:
                conn.close()
            except OSError:
                pass
        if listener is not None:
            try:
                # shutdown BEFORE close: the rejoin acceptor blocks in
                # accept() on this fd, and a plain close would leave that
                # in-flight syscall holding the open file description — the
                # port would stay bound and wedge a relaunched rank on
                # EADDRINUSE. shutdown wakes the acceptor with an error first.
                listener.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                listener.close()
            except OSError:
                pass

    # -- delta routing ---------------------------------------------------------

    def exchange_delta(self, tag: bytes, delta: Any, route_keys: np.ndarray) -> Any:
        """Hash-route a commit's delta rows to their owner process and merge what
        this process owns (reference shard routing, ``shard.rs:15-20``): owner =
        key.lo % n. Returns the merged delta (own partition + received rows)."""
        from pathway_tpu.engine.columnar import Delta
        from pathway_tpu.internals.keys import shard_of

        owners = shard_of(route_keys, self.n)
        # the sender's trace context rides each frame (5th tuple slot,
        # length-tolerant on receive): receivers link the sender's span into
        # their own commit trace, making the routed delta a causal edge
        ctx = _trace_current()
        rider = _format_trace_header(ctx) if ctx is not None else None
        parts: Dict[int, bytes] = {}
        for peer in range(self.n):
            if peer == self.me:
                continue
            rows = np.nonzero(owners == peer)[0]
            if len(rows):
                sub = delta.select(rows)
                parts[peer] = pickle.dumps(
                    (sub.keys, sub.diffs, sub.columns, sub.neu, rider),
                    protocol=pickle.HIGHEST_PROTOCOL,
                )
            else:
                parts[peer] = b""
        received = self.exchange_parts(tag, parts)
        mine = delta.select(np.nonzero(owners == self.me)[0])
        merged = [mine]
        link_ctxs = []
        for peer in sorted(received):
            payload = received[peer]
            if payload:
                unpacked = pickle.loads(payload)
                keys, diffs, columns, neu = unpacked[:4]
                if len(unpacked) > 4 and unpacked[4]:
                    peer_ctx = _parse_trace_header(unpacked[4])
                    if peer_ctx is not None:
                        link_ctxs.append(peer_ctx)
                merged.append(Delta(keys, diffs, columns, neu=neu))
        tracer = _get_tracer()
        if link_ctxs and tracer.recording() and ctx is not None:
            span = tracer.start(
                "exchange",
                f"exchange {tag.decode('utf-8', 'replace')}",
                links=tuple(link_ctxs),
            )
            if span is not None:
                span.duration_s = 1e-9  # a causal edge, not a timed wait
                tracer.finish(span)
        if len(merged) == 1:
            return mine
        return Delta.concat(merged, list(delta.columns))

    @staticmethod
    def _pack(delta: Any) -> bytes:
        return pickle.dumps(
            (delta.keys, delta.diffs, delta.columns, delta.neu),
            protocol=pickle.HIGHEST_PROTOCOL,
        )

    def exchange_to_root(self, tag: bytes, delta: Any) -> Any:
        """Centralize: every process ships its whole delta to process 0 (the
        reference routes temporal-behavior input to one worker,
        ``time_column.rs:48-51``). Process 0 returns the rank-ordered merge;
        everyone else returns an empty delta. Barrier."""
        from pathway_tpu.engine.columnar import Delta

        columns = list(delta.columns)
        parts: Dict[int, bytes] = {p: b"" for p in self._conns}
        if self.me != 0 and len(delta):
            parts[0] = self._pack(delta)
        received = self.exchange_parts(tag, parts)
        if self.me != 0:
            return Delta.empty(columns)
        merged = [delta]
        for peer in sorted(received):
            payload = received[peer]
            if payload:
                keys, diffs, cols, neu = pickle.loads(payload)
                merged.append(Delta(keys, diffs, cols, neu=neu))
        if len(merged) == 1:
            return delta
        return Delta.concat(merged, columns)

    def broadcast_merge(self, tag: bytes, delta: Any) -> Any:
        """Replicate: every process contributes its delta; ALL processes return the
        same rank-ordered merge (replicated-state operators, e.g. the external
        index's data side — every process holds the full index, queries answer
        locally). Barrier."""
        from pathway_tpu.engine.columnar import Delta

        columns = list(delta.columns)
        blob = self._pack(delta) if len(delta) else b""
        received = self.exchange_parts(tag, {p: blob for p in self._conns})
        by_rank: List[Any] = [None] * self.n
        by_rank[self.me] = delta
        for peer, payload in received.items():
            if payload:
                keys, diffs, cols, neu = pickle.loads(payload)
                by_rank[peer] = Delta(keys, diffs, cols, neu=neu)
        merged = [d for d in by_rank if d is not None and len(d)]
        if not merged:
            return Delta.empty(columns)
        if len(merged) == 1:
            return merged[0]
        return Delta.concat(merged, columns)


class ThreadExchangeHub:
    """Shared mailbox for the in-process worker-thread exchange: the timely
    shared-memory allocator's slot, where ``spawn -n``'s TCP mesh is its
    process allocator (``external/timely-dataflow/communication/src/initialize.rs:25-31``
    distinguishes exactly these two)."""

    def __init__(self, n: int):
        self.n = n
        self.boxes: Dict[tuple, bytes] = {}  # (dst, src, tag) -> payload
        self.cv = threading.Condition()
        self.closed = False
        # transparent-threads mode (one shared graph): sources ingest on rank 0
        # and outputs centralize there; compute partitions across all ranks
        self.shared_inputs = False

    def close(self) -> None:
        with self.cv:
            self.closed = True
            self.cv.notify_all()


class PeerShutdownError(ConnectionError):
    """A peer worker shut down while this worker waited on it — a SECONDARY
    failure (the peer's own exception is the root cause)."""


class PeerTimeoutError(TimeoutError):
    """Timed out waiting on a peer worker — secondary, like
    :class:`PeerShutdownError` (typed so failure triage classifies by
    ``isinstance`` instead of matching message text)."""


class ClusterFenceError(PeerShutdownError):
    """A peer observed a rank death and broadcast the epoch fence: this rank
    must abort its in-flight barriers and quiesce for a surgical rejoin (or,
    with surgical mode off, fail fast exactly like any other peer loss — it IS
    a :class:`PeerShutdownError`)."""


def _freeze_delta(payload: Any) -> Any:
    """Mark a delta's arrays read-only before handing the LIVE object to peer
    threads: the zero-serialization lane shares one address space, and the
    engine-wide convention that deltas are never mutated in place is otherwise
    unenforced — a violation must fail fast in the mutating worker, not corrupt
    its peers nondeterministically."""
    if payload is None:
        return payload
    for arr in (payload.keys, payload.diffs, *payload.columns.values()):
        if isinstance(arr, np.ndarray):
            arr.setflags(write=False)
    return payload


class ThreadExchange(ClusterExchange):
    """``ClusterExchange``'s collectives and delta routing over an in-memory
    transport: worker THREADS in one process instead of spawned processes.
    All the lockstep/barrier semantics are inherited — only ``_send``/``_recv``
    change (a dict handoff under one condition variable; no sockets, no
    serializing between address spaces beyond the pickle the routing layer
    already does)."""

    #: thread peers cannot be relaunched into a live hub — no fence protocol
    supports_rejoin = False

    def __init__(self, hub: ThreadExchangeHub, me: int):
        # deliberately NOT calling super().__init__ — no sockets to wire
        self.n = hub.n
        self.me = me
        self._hub = hub
        self.epoch = 0
        self._conns = {p: None for p in range(hub.n) if p != me}  # peer ranks
        # same barrier-deadline knob as the TCP lane (no heartbeats here: a
        # thread peer cannot vanish silently, only wedge — which this catches)
        self.barrier_timeout_s = _env_float("PATHWAY_BARRIER_TIMEOUT_S", 300.0)
        # no rejoin protocol -> no serve log (inherited exchange_parts reads these)
        self._commit_log = OrderedDict()
        self._commit_log_open = None
        self.commit_log_depth = 0

    def _send(self, peer: int, tag: bytes, payload: Any) -> None:
        if payload is not None and hasattr(payload, "columns"):
            _freeze_delta(payload)  # object handoff: enforce the no-mutation contract
        with self._hub.cv:
            self._hub.boxes[(peer, self.me, tag)] = payload
            self._hub.cv.notify_all()

    def _recv(self, peer: int, tag: bytes, timeout: Optional[float] = None) -> bytes:
        if timeout is None:
            timeout = self.barrier_timeout_s
        deadline = time.monotonic() + timeout
        key = (self.me, peer, tag)
        with self._hub.cv:
            while key not in self._hub.boxes:
                if self._hub.closed:
                    raise PeerShutdownError(
                        f"worker thread {peer} shut down while waiting for {tag!r}"
                    )
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise PeerTimeoutError(
                        f"worker thread {self.me} timed out waiting for {tag!r} "
                        f"from worker {peer}"
                    )
                self._hub.cv.wait(timeout=min(remaining, 1.0))
            return self._hub.boxes.pop(key)

    def close(self) -> None:
        self._hub.close()

    def heartbeat_ages(self) -> Dict[int, float]:
        return {}  # one address space: a peer thread cannot vanish silently

    def dead_peers(self) -> Dict[int, str]:
        return {}

    @property
    def shared_inputs(self) -> bool:
        return self._hub.shared_inputs

    # -- zero-serialization delta collectives --------------------------------
    # Worker threads share one address space: deltas cross the exchange as
    # OBJECT handoffs (the partition slice the routing already makes), not
    # pickled bytes. This is the in-memory allocator's whole advantage — the
    # TCP lane pays serialization because it must, this lane must not.

    def exchange_delta(self, tag: bytes, delta: Any, route_keys: np.ndarray) -> Any:
        from pathway_tpu.engine.columnar import Delta
        from pathway_tpu.internals.keys import shard_of

        owners = shard_of(route_keys, self.n)
        for peer in self._conns:
            rows = np.nonzero(owners == peer)[0]
            self._send(peer, tag, delta.select(rows) if len(rows) else None)
        mine = delta.select(np.nonzero(owners == self.me)[0])
        merged = [mine]
        for peer in sorted(self._conns):
            part = self._recv(peer, tag)
            if part is not None and len(part):
                merged.append(part)
        if len(merged) == 1:
            return mine
        return Delta.concat(merged, list(delta.columns))

    def exchange_to_root(self, tag: bytes, delta: Any) -> Any:
        from pathway_tpu.engine.columnar import Delta

        columns = list(delta.columns)
        if self.me != 0:
            self._send(0, tag, delta if len(delta) else None)
            for peer in self._conns:
                if peer != 0:
                    self._send(peer, tag, None)
        else:
            for peer in self._conns:
                self._send(peer, tag, None)
        received = {peer: self._recv(peer, tag) for peer in self._conns}
        if self.me != 0:
            return Delta.empty(columns)
        merged = [delta]
        for peer in sorted(received):
            part = received[peer]
            if part is not None and len(part):
                merged.append(part)
        if len(merged) == 1:
            return delta
        return Delta.concat(merged, columns)

    def broadcast_merge(self, tag: bytes, delta: Any) -> Any:
        from pathway_tpu.engine.columnar import Delta

        columns = list(delta.columns)
        payload = delta if len(delta) else None
        for peer in self._conns:
            self._send(peer, tag, payload)
        by_rank: List[Any] = [None] * self.n
        by_rank[self.me] = delta if len(delta) else None
        for peer in self._conns:
            by_rank[peer] = self._recv(peer, tag)
        merged = [d for d in by_rank if d is not None and len(d)]
        if not merged:
            return Delta.empty(columns)
        if len(merged) == 1:
            return merged[0]
        return Delta.concat(merged, columns)


_thread_ctx = threading.local()


def in_thread_worker() -> bool:
    """True on a thread already bound to a worker exchange (prevents nested
    fan-out when a worker's own ``pw.run`` consults PATHWAY_THREADS)."""
    return getattr(_thread_ctx, "hub", None) is not None


def thread_worker_rank() -> int:
    """This thread's worker rank (0 when not a worker thread)."""
    return int(getattr(_thread_ctx, "me", 0) or 0)


def thread_worker_shared_inputs() -> bool:
    """True on a ``run_shared_graph`` worker (the ``pw.run`` PATHWAY_THREADS
    fan-out over ONE already-built graph, which the parent runner already
    linted); False on a ``run_threads`` worker, where each rank builds and
    runs its own graph with no parent run."""
    hub = getattr(_thread_ctx, "hub", None)
    return bool(getattr(hub, "shared_inputs", False))


def set_thread_exchange(hub: "ThreadExchangeHub | None", me: int = 0) -> None:
    """Bind this thread to a worker-thread exchange (``run_threads`` launcher);
    None unbinds."""
    _thread_ctx.hub = hub
    _thread_ctx.me = me
    _thread_ctx.exchange = None


_cluster: Optional[ClusterExchange] = None
_cluster_tried = False


def get_cluster() -> Optional[ClusterExchange]:
    """Process-wide exchange, created from the spawn env on first use; None when
    running single-process. Worker threads bound to a ThreadExchangeHub get
    their in-memory exchange instead."""
    global _cluster, _cluster_tried
    hub = getattr(_thread_ctx, "hub", None)
    if hub is not None:
        ex = getattr(_thread_ctx, "exchange", None)
        if ex is None:
            ex = ThreadExchange(hub, _thread_ctx.me)
            _thread_ctx.exchange = ex
        return ex
    if _cluster_tried:
        return _cluster
    from pathway_tpu.internals.config import get_pathway_config

    cfg = get_pathway_config()
    n = int(getattr(cfg, "processes", 1) or 1)
    if n <= 1:
        _cluster_tried = True
        return None
    # mark as tried only on SUCCESS: a failed wiring attempt must raise again on
    # retry, never silently degrade to single-process partial results
    cluster = ClusterExchange(
        n, int(getattr(cfg, "process_id", 0) or 0), int(getattr(cfg, "first_port", 10000) or 10000)
    )
    _cluster = cluster
    _cluster_tried = True
    return _cluster
