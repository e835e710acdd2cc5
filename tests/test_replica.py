"""Read-replica serving fleet (``parallel/replica.py`` +
``persistence/replica_feed.py``).

What the suite proves, layer by layer:

- **feed round-trip** — a replica bootstrapped from the primary's
  read-back-verified export and caught up through the frame tail answers
  BITWISE-identically to the primary at the same commit id;
- **bounded bootstrap** — the export streams in bounded row fragments, so
  a replica's peak install memory is one fragment, never the corpus;
- **typed refusal** — a torn bootstrap (chaos ``replica_torn_bootstrap``)
  refuses with ``ReplicaBootstrapError`` and stays OUT of rotation; it
  never serves from a half-installed index;
- **exactly-once apply** — a frame re-listed across polls is skipped (the
  double-apply guard ``replica_follow_model`` explores interleavings of);
- **bounded staleness** — ``max_staleness_s`` sheds typed in-process and as
  HTTP 429 with an RFC-9110 integer ``Retry-After`` over the wire;
- **kill-invisible failover** — the router absorbs dead/refusing/stale
  replicas and falls back to the primary: zero client-visible errors, even
  with a chaos-SIGKILL'd replica in the fleet (the spawn acceptance);
- **fleet supervision** — post-mortems attribute replica deaths (exit
  cause, last applied commit, staleness at death) and flight dumps survive
  supervise-dir cleanup;
- **independent autoscaling** — ``_fleet_signals`` + the replica-flavored
  pure controller grow the fleet on query load without touching ingest.

Spawn-convergence acceptances budget 240 s (CI worst case); they converge
in seconds on an idle machine.
"""

import json
import os
import re
import shutil
import sys
import tempfile
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from pathway_tpu.ops.knn import BruteForceKnnIndex
from pathway_tpu.parallel.replica import (
    ReplicaFleet,
    ReplicaFollower,
    ReplicaNotServingError,
    ReplicaRouter,
    ReplicaServer,
    ReplicaStaleError,
    ReplicaUnavailableError,
    default_index_factory,
    read_replica_statuses,
)
from pathway_tpu.persistence.replica_feed import (
    ReplicaBootstrapError,
    ReplicaFeed,
)

pytestmark = pytest.mark.replicas

DIM = 8


class FakeClock:
    def __init__(self, t: float = 100.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += float(dt)


def _vectors(n: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, DIM)).astype(np.float32)


def _primary(n: int = 12, seed: int = 0) -> BruteForceKnnIndex:
    index = BruteForceKnnIndex(DIM)
    vecs = _vectors(n, seed)
    index.add_many([f"k{i}" for i in range(n)], vecs)
    for i in range(n):
        index.filter_data[f"k{i}"] = {"tag": "even" if i % 2 == 0 else "odd"}
    return index


def _assert_bitwise_parity(primary, follower, queries, k=4, filters=None):
    want = primary.search_many(list(queries), [k] * len(queries), filters)
    _, got = follower.search_many(list(queries), [k] * len(queries), filter_exprs=filters)
    assert got == want  # keys AND float scores, exact equality


# -- feed round-trip + parity ---------------------------------------------------


def test_bootstrap_and_follow_bitwise_parity(tmp_path):
    """Bootstrap at commit 3, tail frames 4 (upsert) and 5 (removal +
    re-upsert): the replica answers bitwise-identically to the primary."""
    primary = _primary(12)
    feed = ReplicaFeed(str(tmp_path / "feed"))
    feed.export_bootstrap(3, primary)

    extra = _vectors(3, seed=7)
    primary.add_many(["n0", "n1", "n2"], extra)
    feed.record_commit(4, ["n0", "n1", "n2"], extra)

    primary.remove("k1")
    moved = _vectors(1, seed=9)
    primary.add_many(["k2"], moved)  # upsert: k2 moves
    primary.filter_data["k2"] = {"tag": "moved"}
    feed.record_commit(
        5, ["k2"], moved, removals=["k1"], filter_data={"k2": {"tag": "moved"}}
    )

    follower = ReplicaFollower(feed, default_index_factory)
    assert follower.bootstrap() == 3
    assert follower.state == "following"
    assert follower.poll_frames() == 2
    assert follower.applied_commit == 5

    queries = _vectors(5, seed=3)
    _assert_bitwise_parity(primary, follower, queries)
    commit, rows = follower.search_many(list(queries[:1]), [12])
    assert commit == 5
    keys = {key for key, _ in rows[0]}
    assert "k1" not in keys and "n0" in keys
    # filter data survives bootstrap + frame apply (k2's tag moved)
    _assert_bitwise_parity(
        primary, follower, queries[:2], filters=["tag == 'moved'"] * 2
    )


def test_bootstrap_streams_bounded_fragments(tmp_path):
    """A 10-row export at rows_per_fragment=4 lands as 3 fragments and every
    install call stays within the bound — flat peak memory by construction."""
    primary = _primary(10)
    feed = ReplicaFeed(str(tmp_path / "feed"))
    manifest = feed.export_bootstrap(1, primary, rows_per_fragment=4)
    assert len(manifest["fragments"]) == 3
    assert manifest["rows"] == 10
    assert [f["rows"] for f in manifest["fragments"]] == [4, 4, 2]

    sizes = []
    holder = {}

    def install_header(header):
        index = default_index_factory(header)
        index.install_descriptor_header(header)
        holder["index"] = index

    def install_fragment(keys, vectors):
        sizes.append(len(keys))
        holder["index"].install_descriptor_rows(keys, vectors)

    assert (
        feed.load_bootstrap(
            install_header=install_header, install_fragment=install_fragment
        )
        == 1
    )
    assert sizes == [4, 4, 2]
    want = primary.search_many(list(_vectors(3, 5)), [3] * 3)
    assert holder["index"].search_many(list(_vectors(3, 5)), [3] * 3) == want


@pytest.mark.chaos
def test_torn_bootstrap_is_typed_refusal(tmp_path, monkeypatch):
    """Chaos-torn bootstrap: a TYPED ``ReplicaBootstrapError`` refusal; the
    replica reports ``refused`` and every query raises
    ``ReplicaNotServingError`` — it never serves a half-installed index."""
    from pathway_tpu.internals.chaos import reset_chaos

    primary = _primary(8)
    feed = ReplicaFeed(str(tmp_path / "feed"))
    feed.export_bootstrap(1, primary)
    monkeypatch.setenv(
        "PATHWAY_CHAOS_PLAN",
        json.dumps(
            {"replica": [{"op": "replica_torn_bootstrap", "replica": 0}]}
        ),
    )
    reset_chaos()
    try:
        follower = ReplicaFollower(feed, default_index_factory)
        with pytest.raises(ReplicaBootstrapError, match="checksum mismatch"):
            follower.bootstrap()
        assert follower.state == "refused"
        snap = follower.snapshot()
        assert snap["state"] == "refused"
        assert "checksum" in snap["refusal"]
        with pytest.raises(ReplicaNotServingError) as exc_info:
            follower.search_many(list(_vectors(1)), [3])
        assert exc_info.value.state == "refused"
        # a refusal is sticky but not fatal: the same process can re-bootstrap
        # once the fault clears (operator repaired / re-exported)
        monkeypatch.setenv("PATHWAY_CHAOS_PLAN", "{}")
        reset_chaos()
        assert follower.bootstrap() == 1
        assert follower.state == "following"
    finally:
        reset_chaos()


def test_double_apply_guard_skips_relisted_frame(tmp_path, monkeypatch):
    """A frame re-listed by a stale directory scan is a no-op: the applied
    commit id never regresses and results stay bitwise-stable (the
    ``replica_follow_model`` invariant, exercised live)."""
    primary = _primary(6)
    feed = ReplicaFeed(str(tmp_path / "feed"))
    feed.export_bootstrap(1, primary)
    extra = _vectors(2, seed=11)
    primary.add_many(["a0", "a1"], extra)
    feed.record_commit(2, ["a0", "a1"], extra)

    follower = ReplicaFollower(feed, default_index_factory)
    follower.bootstrap()
    assert follower.poll_frames() == 1
    assert follower.applied_commit == 2
    queries = list(_vectors(3, seed=2))
    _, before = follower.search_many(queries, [8] * 3)

    # an idle re-poll applies nothing
    assert follower.poll_frames() == 0

    # simulate a stale listing that re-offers the already-applied frame
    real_frames_after = feed.frames_after
    monkeypatch.setattr(
        feed, "frames_after", lambda floor: real_frames_after(floor - 1)
    )
    assert follower.poll_frames() == 0
    assert follower.applied_commit == 2
    _, after = follower.search_many(queries, [8] * 3)
    assert after == before


# -- bounded staleness ----------------------------------------------------------


def test_staleness_shed_typed_and_recovery(tmp_path):
    clock = FakeClock()
    primary = _primary(6)
    feed = ReplicaFeed(str(tmp_path / "feed"))
    feed.export_bootstrap(1, primary)
    follower = ReplicaFollower(feed, default_index_factory, clock=clock)
    assert follower.staleness_s() == float("inf")  # before bootstrap
    follower.bootstrap()
    assert follower.staleness_s() == 0.0

    clock.advance(5.0)
    with pytest.raises(ReplicaStaleError) as exc_info:
        follower.search_many(list(_vectors(1)), [3], max_staleness_s=1.0)
    err = exc_info.value
    assert err.staleness_s == pytest.approx(5.0)
    assert err.retry_after_s > 0.0
    assert follower.snapshot()["shed_total"] == 1

    # a generous bound (and no bound at all) still serves
    commit, _ = follower.search_many(
        list(_vectors(1)), [3], max_staleness_s=10.0
    )
    assert commit == 1
    follower.search_many(list(_vectors(1)), [3])

    # catching up with the tail resets freshness: the tight bound serves again
    extra = _vectors(1, seed=4)
    primary.add_many(["z0"], extra)
    feed.record_commit(2, ["z0"], extra)
    follower.poll_frames()
    assert follower.staleness_s() == 0.0
    commit, _ = follower.search_many(
        list(_vectors(1)), [3], max_staleness_s=1.0
    )
    assert commit == 2


def test_retry_estimate_scales_with_backlog(tmp_path):
    primary = _primary(4)
    feed = ReplicaFeed(str(tmp_path / "feed"))
    feed.export_bootstrap(1, primary)
    follower = ReplicaFollower(feed, default_index_factory, poll_s=0.5)
    follower.bootstrap()
    idle = follower.retry_estimate_s()
    assert idle == pytest.approx(0.5)  # one poll in flight, no backlog
    for commit in (2, 3, 4):
        feed.record_commit(commit, ["b"], _vectors(1, seed=commit))
    assert follower.pending_frames() == 3
    assert follower.retry_estimate_s() == pytest.approx(2.0)  # (3 + 1) polls


# -- the HTTP surface -----------------------------------------------------------


def _post_retrieve(port, payload, timeout=10.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/retrieve",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, dict(resp.headers), json.loads(resp.read())


def test_server_serves_sheds_429_integer_retry_after(tmp_path):
    """The live shed is RFC-9110 honest: HTTP 429 with ``Retry-After`` a
    base-10 non-negative integer (no float, no units) — satellite audit's
    live leg for the replica path."""
    clock = FakeClock()
    primary = _primary(6)
    feed = ReplicaFeed(str(tmp_path / "feed"))
    feed.export_bootstrap(1, primary)
    follower = ReplicaFollower(feed, default_index_factory, clock=clock)
    follower.bootstrap()
    server = ReplicaServer(follower)
    try:
        queries = [[float(x) for x in v] for v in _vectors(2, seed=6)]
        status, _, body = _post_retrieve(
            server.port, {"vectors": queries, "k": 3}
        )
        assert status == 200
        assert body["commit"] == 1
        want = primary.search_many(list(_vectors(2, seed=6)), [3, 3])
        got = [[(key, score) for key, score in row] for row in body["results"]]
        assert got == want

        clock.advance(30.0)
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            _post_retrieve(
                server.port,
                {"vectors": queries, "k": 3, "max_staleness_s": 0.5},
            )
        err = exc_info.value
        assert err.code == 429
        retry_after = err.headers.get("Retry-After")
        assert re.fullmatch(r"[0-9]+", retry_after), retry_after
        assert int(retry_after) >= 1
        assert json.loads(err.read())["error"] == "stale"

        # healthz carries the serving state + applied commit + staleness
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/healthz", timeout=10
        ) as resp:
            health = json.loads(resp.read())
        assert health["state"] == "following"
        assert health["applied_commit"] == 1
        assert health["staleness_s"] == pytest.approx(30.0)
        assert health["alive"] is True
    finally:
        server.close()


def test_server_503_before_bootstrap(tmp_path):
    feed = ReplicaFeed(str(tmp_path / "feed"))
    follower = ReplicaFollower(feed, default_index_factory)
    server = ReplicaServer(follower)
    try:
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            _post_retrieve(server.port, {"vectors": [[0.0] * DIM], "k": 1})
        assert exc_info.value.code == 503
        body = json.loads(exc_info.value.read())
        assert body == {"error": "not_serving", "state": "init"}
    finally:
        server.close()


@pytest.mark.trace
def test_replica_serve_links_originating_commit_trace(tmp_path, monkeypatch):
    """ISSUE 20 acceptance, replica leg: the primary's commit-span context
    rides the feed frame, the replica's ``replica_apply`` span joins the
    commit's trace as a CHILD, and a served read parents to the CALLER's
    header while LINKING the applied commit span — `cli trace` can walk from
    a client query back to the ingest commit whose data answered it."""
    from pathway_tpu.engine.tracing import (
        TRACE_HEADER,
        commit_trace_context,
        format_trace_header,
        get_tracer,
        new_trace_context,
        parse_trace_header,
        reset_tracing,
    )

    monkeypatch.setenv("PATHWAY_TRACE", "on")
    monkeypatch.setenv("PATHWAY_TRACE_SAMPLE", "1.0")
    reset_tracing()
    tracer = get_tracer()
    try:
        primary = _primary(6)
        feed = ReplicaFeed(str(tmp_path / "feed"))
        feed.export_bootstrap(1, primary)
        extra = _vectors(2, seed=5)
        primary.add_many(["n0", "n1"], extra)
        commit_ctx = commit_trace_context(0, 2, rank=0)
        with tracer.trace_span("commit", "commit 2", self_ctx=commit_ctx):
            feed.record_commit(2, ["n0", "n1"], extra)

        follower = ReplicaFollower(feed, default_index_factory)
        assert follower.bootstrap() == 1
        assert follower.poll_frames() == 1
        spans = tracer.recent_spans(limit=256)
        apply_span = next(s for s in spans if s["kind"] == "replica_apply")
        # the rider made the apply a CHILD of the primary's commit span
        assert apply_span["trace_id"] == commit_ctx.trace_id
        assert apply_span["parent_id"] == commit_ctx.span_id

        server = ReplicaServer(follower)
        try:
            caller = new_trace_context(sampled=True)
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/v1/retrieve",
                data=json.dumps(
                    {"vectors": [[0.0] * DIM], "k": 2}
                ).encode(),
                headers={
                    "Content-Type": "application/json",
                    TRACE_HEADER: format_trace_header(caller),
                },
                method="POST",
            )
            with urllib.request.urlopen(req, timeout=10) as resp:
                assert resp.status == 200
                echoed = parse_trace_header(resp.headers.get(TRACE_HEADER))
            # response echoes the caller's trace with the serve span's id
            assert echoed is not None
            assert echoed.trace_id == caller.trace_id
            assert echoed.span_id != caller.span_id
            serve = next(
                s for s in tracer.recent_spans(limit=256)
                if s["kind"] == "replica_serve"
            )
            assert serve["trace_id"] == caller.trace_id
            assert serve["parent_id"] == caller.span_id
            assert serve["attrs"]["status"] == 200
            assert serve["attrs"]["commit"] == 2
            # ... and LINKS the applied commit span: query -> ingest edge
            linked = {link["span_id"] for link in serve["links"]}
            assert commit_ctx.span_id in linked, serve["links"]
        finally:
            server.close()
    finally:
        # env is still monkeypatched "on" here, so a bare reset would leave
        # the process-wide tracer live for the rest of the suite
        reset_tracing()
        get_tracer().enabled = False


# -- the router: kill-invisible failover ---------------------------------------


def _primary_closure(primary, tip_commit):
    def serve(vectors, k, filters):
        return tip_commit, primary.search_many(
            list(vectors), [k] * len(vectors), filters
        )

    return serve


def test_router_failover_is_client_invisible(tmp_path):
    """Kill one replica server, then both: every query still succeeds —
    first via the surviving replica, then via the primary fallback. The
    client never sees an error."""
    primary = _primary(8)
    feed = ReplicaFeed(str(tmp_path / "feed"))
    feed.export_bootstrap(1, primary)
    followers = [
        ReplicaFollower(feed, default_index_factory, replica_id=i)
        for i in range(2)
    ]
    for f in followers:
        f.bootstrap()
    servers = [ReplicaServer(f) for f in followers]
    try:
        router = ReplicaRouter(
            [f"http://127.0.0.1:{s.port}" for s in servers],
            primary=_primary_closure(primary, 1),
        )
        queries = [[float(x) for x in v] for v in _vectors(2, seed=8)]
        want = primary.search_many(list(_vectors(2, seed=8)), [3, 3])
        for _ in range(4):
            commit, results = router.retrieve(queries, 3)
            assert commit == 1 and results == want
        assert router.stats["replica_served"] == 4

        servers[0].close()  # half the fleet vanishes mid-traffic
        for _ in range(6):
            commit, results = router.retrieve(queries, 3)
            assert commit == 1 and results == want
        assert router.stats["failovers"] >= 1
        assert router.stats["primary_served"] == 0  # fleet still covered it

        servers[1].close()  # whole fleet gone: the primary absorbs
        for _ in range(3):
            commit, results = router.retrieve(queries, 3)
            assert commit == 1 and results == want
        assert router.stats["primary_served"] == 3
    finally:
        for s in servers:
            s.close()


def test_router_stale_fleet_sheds_with_min_retry_after(tmp_path):
    """With no primary, an all-stale fleet surfaces a typed
    ``ReplicaStaleError`` carrying the smallest advertised backoff; an
    all-dead fleet surfaces ``ReplicaUnavailableError``."""
    clock = FakeClock()
    primary = _primary(6)
    feed = ReplicaFeed(str(tmp_path / "feed"))
    feed.export_bootstrap(1, primary)
    follower = ReplicaFollower(feed, default_index_factory, clock=clock)
    follower.bootstrap()
    clock.advance(60.0)
    server = ReplicaServer(follower)
    try:
        router = ReplicaRouter([f"http://127.0.0.1:{server.port}"])
        queries = [[float(x) for x in v] for v in _vectors(1)]
        with pytest.raises(ReplicaStaleError) as exc_info:
            router.retrieve(queries, 3, max_staleness_s=0.5)
        assert exc_info.value.retry_after_s >= 1.0  # the advertised integer
        assert router.stats["sheds_seen"] == 1
    finally:
        server.close()
    router = ReplicaRouter([f"http://127.0.0.1:{server.port}"])
    with pytest.raises(ReplicaUnavailableError):
        router.retrieve(queries, 3)


# -- fleet autoscaling (pure) ---------------------------------------------------


def test_fleet_signals_fold_served_and_shed_rates():
    from pathway_tpu.parallel.replica import _fleet_signals

    statuses0 = {
        0: {"served_total": 100, "shed_total": 0},
        1: {"served_total": 50, "shed_total": 2},
    }
    signals, carry = _fleet_signals(statuses0, None, 10.0, 2)
    assert signals.stable and signals.current_n == 2
    assert signals.ingest_rate == 0.0  # first sample: no window yet
    statuses1 = {
        0: {"served_total": 600, "shed_total": 0},
        1: {"served_total": 250, "shed_total": 12},
    }
    signals, carry = _fleet_signals(statuses1, carry, 12.0, 2)
    assert signals.ingest_rate == pytest.approx(350.0)  # +700 served / 2 s
    assert signals.shed_rate == pytest.approx(5.0)
    # a missing status file (replica mid-relaunch) reads as unstable
    signals, _ = _fleet_signals({0: statuses1[0]}, carry, 13.0, 2)
    assert not signals.stable


def test_replica_policy_scales_up_on_query_load(monkeypatch):
    """The replica-flavored pure controller (QPS-per-replica capacity, shed
    escalates immediately) grows the fleet after a sustained overload — no
    ingest signal involved."""
    from pathway_tpu.parallel.autoscaler import (
        AutoscaleController,
        AutoscalePolicy,
        AutoscaleSignals,
    )

    monkeypatch.delenv("PATHWAY_REPLICA_AUTOSCALE_QPS", raising=False)
    policy = AutoscalePolicy.replica_from_env()
    assert policy.min_workers == 1 and policy.max_workers == 4
    assert policy.rows_per_worker == 200.0  # queries/s per replica
    controller = AutoscaleController(policy, 1)
    target = None
    for tick in range(20):
        decision = controller.sample(
            float(tick * 2),
            AutoscaleSignals(ingest_rate=700.0, stable=True, current_n=1),
        )
        if decision is not None:
            target = decision
            break
    assert target == 4  # ceil(700/200) = 4, within the fleet ceiling


# -- fleet spawn acceptances ----------------------------------------------------


def _spawn_env(tmp_path, **extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PATHWAY_REPLICA_POLL_S"] = "0.05"
    env.update(extra)
    return env


@pytest.mark.chaos
def test_fleet_chaos_kill_zero_client_errors(tmp_path):
    """THE acceptance: n=2 replicas + primary fallback, chaos SIGKILLs
    replica 0 at its first applied frame — 20 straight client queries all
    succeed (zero visible errors), the death is attributed (exit cause,
    last applied commit, staleness at death), the flight dump survives
    supervise-dir cleanup, and the relaunched replica rejoins."""
    primary = _primary(10)
    feed_root = str(tmp_path / "feed")
    supervise_dir = str(tmp_path / "supervise")
    os.makedirs(supervise_dir)
    feed = ReplicaFeed(feed_root)
    feed.export_bootstrap(1, primary)

    plan = {"replica": [{"op": "replica_kill", "replica": 0, "commit": 2}]}
    fleet = ReplicaFleet(
        feed_root=feed_root,
        supervise_dir=supervise_dir,
        run_id="test-kill",
        n=2,
        base_env=_spawn_env(tmp_path, PATHWAY_CHAOS_PLAN=json.dumps(plan)),
        autoscale=False,
    )
    preserved = None
    try:
        fleet.start()
        endpoints = fleet.wait_serving(2, deadline_s=240.0)
        assert len(endpoints) == 2

        # move the primary forward: re-export FIRST so the relaunched
        # replica bootstraps PAST the killing frame (the prune discipline),
        # then publish the frame the chaos plan is armed on
        extra = _vectors(2, seed=21)
        primary.add_many(["x0", "x1"], extra)
        feed.export_bootstrap(2, primary)
        feed.record_commit(2, ["x0", "x1"], extra)

        router = ReplicaRouter(
            endpoints, primary=_primary_closure(primary, 2), timeout_s=10.0
        )
        queries = [[float(x) for x in v] for v in _vectors(2, seed=22)]
        want = primary.search_many(list(_vectors(2, seed=22)), [3, 3])
        deadline = time.monotonic() + 240.0
        served = 0
        while served < 20:
            assert time.monotonic() < deadline, "kill acceptance timed out"
            _, results = router.retrieve(queries, 3)  # must NEVER raise
            assert results == want
            served += 1
            fleet.watch_once()
            time.sleep(0.02)
        assert served == 20  # zero client-visible errors

        # the SIGKILL happened and was attributed
        deadline = time.monotonic() + 240.0
        while not fleet.post_mortems and time.monotonic() < deadline:
            fleet.watch_once()
            time.sleep(0.05)
        assert fleet.post_mortems, "replica 0 was never reaped"
        line = fleet.post_mortems[0]
        assert "replica 0" in line
        assert "killed by signal SIGKILL" in line
        assert "last applied commit" in line
        assert "staleness at death" in line
        # chaos dumps the flight recorder before the kill; the fleet
        # preserved it outside the supervise dir
        match = re.search(r"flight dump preserved at (\S+)", line)
        assert match, line
        preserved = match.group(1)
        assert os.path.exists(preserved)

        # the relaunch converges back to a full fleet at the NEW bootstrap
        fleet.wait_serving(2, deadline_s=240.0)
        statuses = read_replica_statuses(supervise_dir, 2)
        assert statuses[0]["applied_commit"] == 2
    finally:
        fleet.stop()
        shutil.rmtree(supervise_dir, ignore_errors=True)
    # preservation outlives the supervise dir
    assert preserved is not None and os.path.exists(preserved)
    os.unlink(preserved)


def test_fleet_stop_preserves_flight_dumps(tmp_path):
    """Even without a chaos kill, ``stop()`` copies whatever flight dumps
    the replicas wrote out of the doomed supervise dir first."""
    fleet = ReplicaFleet(
        feed_root=str(tmp_path / "feed"),
        supervise_dir=str(tmp_path / "supervise"),
        run_id="test-preserve",
        n=0,
        autoscale=False,
    )
    replicas_dir = os.path.join(str(tmp_path / "supervise"), "replicas")
    os.makedirs(replicas_dir)
    with open(os.path.join(replicas_dir, "flight-rank-3.json"), "w") as f:
        json.dump({"events": []}, f)
    fleet.procs[3] = type(  # a stub "already exited" process handle
        "P", (), {"poll": lambda self: 0, "terminate": lambda self: None,
                  "wait": lambda self, timeout=None: 0}
    )()
    fleet.stop()
    shutil.rmtree(str(tmp_path / "supervise"))
    preserved = os.path.join(
        tempfile.gettempdir(), "pathway-flight-test-preserve-replica-3.json"
    )
    assert os.path.exists(preserved)
    os.unlink(preserved)


def test_replica_process_refuses_typed_on_torn_bootstrap_spawn(tmp_path):
    """A spawned replica whose bootstrap is chaos-torn stays UP, publishes
    ``refused`` (out of rotation), and answers 503 — a typed refusal an
    operator can see, not a crash loop."""
    primary = _primary(6)
    feed_root = str(tmp_path / "feed")
    supervise_dir = str(tmp_path / "supervise")
    os.makedirs(supervise_dir)
    ReplicaFeed(feed_root).export_bootstrap(1, primary)
    plan = {"replica": [{"op": "replica_torn_bootstrap", "replica": 0}]}
    fleet = ReplicaFleet(
        feed_root=feed_root,
        supervise_dir=supervise_dir,
        run_id="test-torn",
        n=1,
        base_env=_spawn_env(tmp_path, PATHWAY_CHAOS_PLAN=json.dumps(plan)),
        autoscale=False,
    )
    try:
        fleet.start()
        deadline = time.monotonic() + 240.0
        status = None
        while time.monotonic() < deadline:
            status = read_replica_statuses(supervise_dir, 1).get(0)
            if status and status.get("state") == "refused":
                break
            time.sleep(0.05)
        assert status is not None and status["state"] == "refused", status
        assert "checksum" in (status.get("refusal") or "")
        assert fleet.procs[0].poll() is None  # up, just out of rotation
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            _post_retrieve(
                int(status["port"]), {"vectors": [[0.0] * DIM], "k": 1}
            )
        assert exc_info.value.code == 503
        assert json.loads(exc_info.value.read())["state"] == "refused"
    finally:
        fleet.stop()
