"""Closed-loop autoscaler + brownout ladder (ISSUE 13).

Five layers under test:

- controller policy (``parallel/autoscaler.py``): rate-based targets through
  hysteresis bands, per-direction cooldowns, one-transition-in-flight,
  TYPED refusal backoff (at most one retry per window), and the flap lock
  under the chaos ``oscillating_load`` profile;
- brownout ladder (``engine/brownout.py``): occupancy-driven rungs with
  hysteresis, admission/n_probe degradation factors, the quiesce
  window, and the REST plane shedding 429 + honest Retry-After on both;
- supervisor wiring: the hardened control endpoint (``err <reason>`` for
  malformed commands, the read-only ``status`` command, concurrent ``scale``
  requests), refusal feedback into the controller, and the typed
  ``AutoscaleRefusedError`` in post-mortems;
- chaos (``internals/chaos.py``): the ``load_spike`` / ``oscillating_load``
  / ``noisy_neighbor`` load profiles and the ``scale_refused`` preflight op;
- spawn acceptance: an ``--autoscale`` cluster at n=2 under a ramping
  synthetic load scales to 4 and back to 2 with NO operator input, final
  output bit-identical to a static run.
"""

from __future__ import annotations

import itertools
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import pytest

import pathway_tpu as pw
from pathway_tpu.engine.brownout import BrownoutState, get_brownout, reset_brownout
from pathway_tpu.internals.chaos import Chaos
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.parallel.autoscaler import (
    AutoscaleController,
    AutoscalePolicy,
    AutoscaleRefusedError,
    AutoscaleSignals,
    aggregate_signals,
    read_state,
    write_state,
)
from pathway_tpu.parallel.membership import MembershipDirective
from pathway_tpu.parallel.supervisor import Supervisor

pytestmark = pytest.mark.autoscale

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PORT_SLOT = itertools.count()


def _port_base() -> int:
    return 31000 + os.getpid() % 150 * 30 + next(_PORT_SLOT) * 6


def _steady(rate: float, n: int = 2, **kw) -> AutoscaleSignals:
    return AutoscaleSignals(ingest_rate=rate, stable=True, current_n=n, **kw)


# -- controller policy --------------------------------------------------------


def test_policy_env_knobs(monkeypatch):
    monkeypatch.setenv("PATHWAY_AUTOSCALE_MAX", "6")
    monkeypatch.setenv("PATHWAY_AUTOSCALE_ROWS_PER_WORKER", "42")
    monkeypatch.setenv("PATHWAY_AUTOSCALE_FLAP_REVERSALS", "5")
    policy = AutoscalePolicy.from_env()
    assert policy.max_workers == 6
    assert policy.rows_per_worker == 42.0
    assert policy.flap_reversals == 5
    assert policy.min_workers == 2  # untouched default


def test_scale_up_needs_consecutive_samples_and_respects_cooldown():
    policy = AutoscalePolicy(
        rows_per_worker=100, up_samples=3, up_cooldown_s=10, max_workers=8
    )
    ctrl = AutoscaleController(policy, 2)
    # two samples above the band: not yet
    assert ctrl.sample(0.0, _steady(1000)) is None
    assert ctrl.sample(1.0, _steady(1000)) is None
    target = ctrl.sample(2.0, _steady(1000))
    assert target == 8  # ceil(1000/100) clamped to max
    ctrl.on_issued(target, 2.0)
    ctrl.on_complete(target, 3.0)
    # overload persists, but the up cooldown holds the next transition
    for t in (4.0, 5.0, 6.0, 7.0):
        assert ctrl.sample(t, _steady(10_000, n=8)) is None


def test_scale_down_is_slower_and_banded():
    policy = AutoscalePolicy(
        rows_per_worker=100, down_samples=3, down_cooldown_s=0, min_workers=2
    )
    ctrl = AutoscaleController(policy, 4)
    # inside the band (4 workers * 100 * 0.75 = 300): no decision
    for t in range(5):
        assert ctrl.sample(float(t), _steady(350, n=4)) is None
    # well below: needs down_samples consecutive, then targets the rate
    assert ctrl.sample(5.0, _steady(120, n=4)) is None
    assert ctrl.sample(6.0, _steady(120, n=4)) is None
    assert ctrl.sample(7.0, _steady(120, n=4)) == 2

def test_one_transition_in_flight_and_resume_after_stable():
    policy = AutoscalePolicy(rows_per_worker=10, up_samples=1, up_cooldown_s=0)
    ctrl = AutoscaleController(policy, 2)
    target = ctrl.sample(0.0, _steady(1000))
    assert target is not None
    ctrl.on_issued(target, 0.0)
    # in flight: no further decisions whatever the signals say
    assert ctrl.sample(1.0, _steady(10_000)) is None
    # the transition dies mid-flight: controller holds until stable again
    ctrl.on_aborted("crash", 2.0)
    assert ctrl.sample(3.0, AutoscaleSignals(ingest_rate=10_000, stable=False)) is None
    # the recovery ladder owns the cluster while unstable; the first STABLE
    # sample re-arms the controller (matching the model's stable-gate)
    assert ctrl.sample(4.0, _steady(10_000)) is not None


def test_refusal_backs_off_typed_and_retries_at_most_once_per_window():
    policy = AutoscalePolicy(
        rows_per_worker=10, up_samples=1, up_cooldown_s=0, refusal_backoff_s=10,
        shed_first_s=0,
    )
    ctrl = AutoscaleController(policy, 2)
    target = ctrl.sample(0.0, _steady(1000))
    ctrl.on_issued(target, 0.0)
    ctrl.on_refused(target, "join state is not reshardable", 1.0)
    # typed surface for post-mortems/tests
    assert isinstance(ctrl.last_refusal, AutoscaleRefusedError)
    assert ctrl.last_refusal.target_n == target
    assert "preflight" in str(ctrl.last_refusal)
    # inside the backoff window: never retried, however hot the signals
    for t in range(2, 11):
        assert ctrl.sample(float(t), _steady(10_000)) is None
    # after the window: exactly one retry is allowed
    retry = ctrl.sample(11.5, _steady(10_000))
    assert retry is not None
    ctrl.on_issued(retry, 11.5)
    ctrl.on_refused(retry, "still not reshardable", 12.0)
    for t in range(13, 22):
        assert ctrl.sample(float(t), _steady(10_000)) is None


def test_oscillating_load_flap_locks_with_bounded_transition_rate():
    """THE oscillating-load scenario (chaos ``oscillating_load`` profile
    drives the offered rate): at most one transition per cooldown window,
    and after ``flap_reversals`` direction reversals the controller locks
    into hold-and-alert instead of thrashing the reshard path."""
    load = Chaos(0, {"load": {
        "op": "oscillating_load", "period_s": 8.0, "low": 0.0, "high": 100.0,
    }})
    policy = AutoscalePolicy(
        min_workers=2, max_workers=4, rows_per_worker=20,
        up_samples=2, down_samples=2, up_cooldown_s=2, down_cooldown_s=2,
        flap_window_s=100, flap_reversals=3, shed_first_s=0,
    )
    ctrl = AutoscaleController(policy, 2)
    issued = []
    for t in range(80):
        rate = load.load_rate(float(t))
        target = ctrl.sample(float(t), _steady(rate, n=ctrl.current_n))
        if target is not None:
            issued.append((t, target))
            ctrl.on_issued(target, float(t))
            ctrl.on_complete(target, float(t))  # transitions land instantly
    assert ctrl.flap_locked, "oscillating load never engaged the flap lock"
    assert ctrl.state == "flap_locked"
    # at most one transition per cooldown window
    for (t1, _a), (t2, _b) in zip(issued, issued[1:]):
        assert t2 - t1 >= 2, f"two transitions inside one cooldown: {issued}"
    # the lock shows up in the decision log and the exported state
    kinds = [d.kind for d in ctrl.decisions]
    assert "flap_lock" in kinds
    locked_at = kinds.index("flap_lock")
    # ...and the lock HOLDS: nothing is issued after it
    assert all(
        d.kind not in ("scale_up", "scale_down")
        for d in ctrl.decisions[locked_at + 1:]
    )
    assert ctrl.as_dict()["flap_locked"] is True


def test_overload_scales_only_after_shed_window():
    """Shed-before-scale: a shed storm alone does not scale until the
    brownout/shed signal has been engaged for shed_first_s — cheap
    degradation is spent before a reshard pause."""
    policy = AutoscalePolicy(
        rows_per_worker=1000, up_samples=99, up_cooldown_s=0, shed_first_s=5
    )
    ctrl = AutoscaleController(policy, 2)
    # rate is modest (never crosses the band) but requests are shedding
    sig = lambda: _steady(100, shed_rate=4.0, brownout_level=1)
    for t in range(5):
        assert ctrl.sample(float(t), sig()) is None
    got = ctrl.sample(6.0, sig())
    assert got == 3  # current + 1 under overload
    decision = ctrl.last_decision()
    assert decision is not None and "overload" in decision.reason


def test_aggregate_signals_rates_and_reset_clamp():
    def status(rows, shed, state="running", mstate="stable"):
        return {
            "state": state,
            "membership_state": mstate,
            "autoscale": {
                "input_rows": rows, "shed": shed, "barrier_wait_s": 0.0,
                "commit_p99_s": 0.02, "brownout_level": 1,
            },
        }

    sig, carry = aggregate_signals(
        {0: status(100, 0), 1: status(100, 0)}, None, 10.0, 2
    )
    assert sig.stable and sig.ingest_rate == 0.0  # first sample: no rate yet
    sig, carry = aggregate_signals(
        {0: status(200, 3), 1: status(200, 1)}, carry, 12.0, 2
    )
    assert sig.ingest_rate == pytest.approx(100.0)  # +200 rows over 2 s
    assert sig.shed_rate == pytest.approx(2.0)
    assert sig.brownout_level == 1
    assert sig.commit_p99_s == pytest.approx(0.02)
    # a relaunched worker resets its counters: the delta clamps at 0
    sig, carry = aggregate_signals(
        {0: status(0, 0), 1: status(0, 0)}, carry, 14.0, 2
    )
    assert sig.ingest_rate == 0.0 and sig.shed_rate == 0.0
    # a missing or mid-transition rank makes the sample unstable
    sig, _ = aggregate_signals({0: status(0, 0)}, carry, 16.0, 2)
    assert not sig.stable
    sig, _ = aggregate_signals(
        {0: status(0, 0), 1: status(0, 0, mstate="resharding")}, carry, 18.0, 2
    )
    assert not sig.stable


def test_state_file_roundtrip(tmp_path):
    ctrl = AutoscaleController(AutoscalePolicy(), 2)
    ctrl.sample(0.0, _steady(10))
    write_state(str(tmp_path), ctrl)
    state = read_state(str(tmp_path))
    assert state is not None
    assert state["state"] == "watching"
    assert state["current_n"] == 2
    assert state["flap_locked"] is False
    assert read_state(str(tmp_path / "nope")) is None


def test_health_payload_carries_signals_and_controller_mirror(tmp_path):
    """Satellite: /healthz (via GraphRunner.health) exposes this rank's
    published load signals AND the mirrored controller state, and a flap
    lock appearing in the state file bumps the autoscale counters."""
    from pathway_tpu.engine import telemetry
    from pathway_tpu.engine.runner import GraphRunner
    from pathway_tpu.internals.parse_graph import ParseGraph

    runner = GraphRunner(ParseGraph())
    runner._supervise_dir = str(tmp_path)
    health = runner.health()
    assert "input_rows" in health["autoscale"]
    assert health["autoscaler"] is None  # no state file yet
    ctrl = AutoscaleController(AutoscalePolicy(), 2)
    ctrl.flap_locked = True
    ctrl.state = "flap_locked"
    ctrl._bump()
    write_state(str(tmp_path), ctrl)
    before = telemetry.stage_snapshot("autoscale.").get("autoscale.flap_locks", 0.0)
    runner._mirror_autoscale_state(time.monotonic() + 10)
    health = runner.health()
    assert health["autoscaler"]["flap_locked"] is True
    assert health["autoscaler"]["state"] == "flap_locked"
    after = telemetry.stage_snapshot("autoscale.").get("autoscale.flap_locks", 0.0)
    assert after == before + 1


# -- chaos load profiles ------------------------------------------------------


def test_chaos_load_profiles_are_deterministic():
    spike = Chaos(0, {"load": {
        "op": "load_spike", "at_s": 5, "duration_s": 10, "low": 50, "high": 400,
    }})
    assert spike.load_rate(0.0) == 50
    assert spike.load_rate(5.0) == 400
    assert spike.load_rate(14.9) == 400
    assert spike.load_rate(15.0) == 50
    osc = Chaos(0, {"load": {
        "op": "oscillating_load", "period_s": 4, "low": 10, "high": 90,
    }})
    assert osc.load_rate(0.0) == 90
    assert osc.load_rate(1.9) == 90
    assert osc.load_rate(2.0) == 10
    assert osc.load_rate(4.0) == 90
    assert Chaos(0, {}).load_rate(1.0) is None
    noisy = Chaos(0, {"load": {
        "op": "noisy_neighbor", "client": "tenant-7", "rps": 25, "rows": 2,
    }})
    assert noisy.noisy_neighbor() == {"client": "tenant-7", "rps": 25.0, "rows": 2}
    assert noisy.load_rate(1.0) is None
    assert spike.noisy_neighbor() is None


def test_chaos_scale_refused_gating():
    chaos = Chaos(0, {"scale": [{"op": "scale_refused", "rank": 0, "at": 0}]})
    assert chaos.scale_fault("scale_refused", 0)
    assert not chaos.scale_fault("scale_refused", 1)
    chaos2 = Chaos(0, {"scale": [{"op": "scale_refused", "rank": 0, "at": 1}]})
    assert not chaos2.scale_fault("scale_refused", 0)
    chaos2.begin_scale_attempt()
    chaos2.begin_scale_attempt()
    assert chaos2.scale_fault("scale_refused", 0)


# -- brownout ladder ----------------------------------------------------------


def test_brownout_rungs_engage_and_release_with_hysteresis():
    bo = BrownoutState(enabled=True, hold_s=0.5)
    t0 = 100.0
    assert bo.observe_occupancy(0.3, now=t0) == 0
    assert bo.admission_scale() == 1.0
    assert bo.observe_occupancy(0.7, now=t0 + 1) == 1
    assert bo.admission_scale() == 0.5
    assert bo.nprobe_shift() == 0
    assert bo.observe_occupancy(0.9, now=t0 + 2) == 2
    assert bo.admission_scale() == 0.25
    assert bo.nprobe_shift() == 1
    # oscillating just below the threshold does NOT release inside hold_s
    assert bo.observe_occupancy(0.5, now=t0 + 2.1) == 2
    # quiet past hold_s: rungs release
    assert bo.observe_occupancy(0.1, now=t0 + 10) == 0
    snap = bo.snapshot()
    assert snap["engages"] == 2 and snap["releases"] == 2


def test_brownout_disabled_stays_level_zero(monkeypatch):
    assert BrownoutState(enabled=False).observe_occupancy(0.99) == 0
    monkeypatch.setenv("PATHWAY_BROWNOUT", "off")
    reset_brownout()
    try:
        assert not get_brownout().enabled
        assert get_brownout().observe_occupancy(0.99) == 0
    finally:
        monkeypatch.delenv("PATHWAY_BROWNOUT")
        reset_brownout()


def test_brownout_quiesce_window_retry_after():
    bo = BrownoutState(enabled=True)
    assert bo.quiesce_retry_after() is None
    bo.enter_quiesce(2.0)
    retry = bo.quiesce_retry_after()
    assert retry is not None and 0.4 <= retry <= 2.0
    assert bo.snapshot()["quiesced"] is True
    bo.exit_quiesce()
    assert bo.quiesce_retry_after() is None


def test_ivf_n_probe_degrades_under_brownout(monkeypatch):
    import numpy as np

    from pathway_tpu.ops.knn_ivf import IvfKnnStore

    reset_brownout()
    try:
        store = IvfKnnStore(dim=8, n_clusters=16, n_probe=8)
        rng = np.random.default_rng(0)
        store.add_many(
            list(range(64)), rng.standard_normal((64, 8)).astype(np.float32)
        )
        assert store._effective_n_probe() == store.n_probe
        get_brownout().observe_occupancy(0.9)  # rung 2: n_probe halves
        assert store._effective_n_probe() == max(1, store.n_probe >> 1)
        # serving still works at the degraded rung
        scores, slots, valid = store.search_batch(
            rng.standard_normal((4, 8), dtype=np.float32), k=3
        )
        assert scores.shape == (4, 3)
    finally:
        reset_brownout()


# -- supervisor: control endpoint + refusal feedback --------------------------


def _mini_supervisor(**kw) -> Supervisor:
    return Supervisor(
        processes=2, threads=1, first_port=_port_base(), program="true",
        arguments=[], env_base={}, **kw,
    )


def _control(port: int, line: str) -> str:
    with socket.create_connection(("127.0.0.1", port), timeout=5) as conn:
        conn.sendall(line.encode() + b"\n")
        buf = b""
        while not buf.endswith(b"\n"):
            chunk = conn.recv(4096)
            if not chunk:
                break
            buf += chunk
    return buf.decode()


def test_control_endpoint_commands_and_errors():
    sup = _mini_supervisor(control_port=0, autoscale=True)
    sup._start_control_endpoint()
    try:
        port = sup.control_port
        assert port
        assert _control(port, "scale 3") == "ok\n"
        assert sup._scale_requests == [3]
        # malformed commands answer err <reason> instead of being dropped
        assert _control(port, "scale x").startswith("err scale target must be")
        assert _control(port, "scale").startswith("err usage")
        assert _control(port, "resize 9").startswith("err unknown command")
        assert _control(port, "").startswith("err empty command")
        # read-only status: topology + controller state + last decision
        status = json.loads(_control(port, "status"))
        assert status["n"] == 2
        assert status["transition_in_flight"] is False
        assert status["autoscaler"]["state"] == "watching"
        assert status["autoscaler"]["current_n"] == 2
    finally:
        sup._control_listener.close()


def test_control_endpoint_concurrent_scale_requests():
    sup = _mini_supervisor(control_port=0)
    sup._start_control_endpoint()
    try:
        port = sup.control_port
        replies = []
        lock = threading.Lock()

        def ask(n):
            reply = _control(port, f"scale {n}")
            with lock:
                replies.append(reply)

        threads = [
            threading.Thread(target=ask, args=(3 + i % 2,)) for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert replies == ["ok\n"] * 8
        with sup._scale_lock:
            assert len(sup._scale_requests) == 8
    finally:
        sup._control_listener.close()


def test_supervisor_refusal_feeds_controller_and_post_mortem(tmp_path, capsys):
    """An autoscaler-issued scale-up refused by the preflight vote reaches
    the controller as a TYPED AutoscaleRefusedError, and the post-mortem
    names it."""
    sup = _mini_supervisor(autoscale=True)
    sup._supervise_dir = str(tmp_path)
    directive = MembershipDirective(1, 4, 1, 2, origin="autoscaler")
    sup._transition = (directive, time.monotonic())
    sup.autoscaler.on_issued(4, time.monotonic())
    statuses = {0: {"membership_refused": [1, "join state is not reshardable"]}}
    assert sup._watch_transition(statuses) is None
    assert sup._transition is None  # unwound, cluster keeps running
    refusal = sup.autoscaler.last_refusal
    assert isinstance(refusal, AutoscaleRefusedError)
    assert refusal.target_n == 4
    assert "join state is not reshardable" in str(refusal)
    # the controller is back to watching (not stuck in-flight), but the
    # refused direction is under backoff
    assert sup.autoscaler.state == "watching"
    assert sup.autoscaler.sample(
        time.monotonic(), _steady(1e9)
    ) is None
    sup._post_mortem((0, "exit code 1"), {}, "budget exhausted")
    err = capsys.readouterr().err
    assert "post-mortem autoscaler" in err
    assert "AutoscaleRefusedError" in err


def test_operator_origin_refusal_skips_controller(tmp_path):
    """A refusal of an OPERATOR-issued transition must not arm the
    autoscaler's backoff — the controller only owns its own decisions."""
    sup = _mini_supervisor(autoscale=True)
    sup._supervise_dir = str(tmp_path)
    directive = MembershipDirective(1, 4, 1, 2, origin="operator")
    sup._transition = (directive, time.monotonic())
    statuses = {0: {"membership_refused": [1, "nope"]}}
    assert sup._watch_transition(statuses) is None
    assert sup.autoscaler.last_refusal is None


def test_directive_file_carries_origin(tmp_path):
    from pathway_tpu.parallel.membership import read_directive, write_directive

    directive = MembershipDirective(3, 4, 2, 2, origin="autoscaler")
    write_directive(str(tmp_path), directive)
    got = read_directive(str(tmp_path))
    assert got is not None and got.origin == "autoscaler"
    # the vote payload stays the stable 4-tuple
    assert got.as_tuple() == (3, 4, 2, 2)


# -- spawn acceptance: capacity follows load, no operator ---------------------

AUTOSCALE_PROG = """
import json, os
import pathway_tpu as pw

tmp = os.environ["PATHWAY_TPU_TEST_DIR"]
pid = int(os.environ.get("PATHWAY_PROCESS_ID", "0"))

class WordSchema(pw.Schema):
    word: str

t = pw.io.fs.read(
    os.path.join(tmp, "in"), format="csv", schema=WordSchema, mode="streaming",
)
counts = t.groupby(t.word).reduce(t.word, total=pw.reducers.count())

out_path = os.path.join(tmp, f"out_{pid}.json")
rows = {}
def on_change(key, row, time, is_addition):
    if is_addition:
        rows[repr(key)] = {"word": row["word"], "total": int(row["total"])}
    else:
        rows.pop(repr(key), None)
    with open(out_path + ".tmp", "w") as f:
        json.dump(list(rows.values()), f)
    os.replace(out_path + ".tmp", out_path)

pw.io.subscribe(counts, on_change)
cfg = pw.persistence.Config(
    pw.persistence.Backend.filesystem(os.path.join(tmp, "store"))
)
pw.run(persistence_config=cfg, monitoring_level=pw.MonitoringLevel.NONE)
"""


def _read_merged(tmp_path, n: int) -> dict:
    merged: dict = {}
    for p in range(n):
        path = tmp_path / f"out_{p}.json"
        if not path.exists():
            continue
        try:
            for r in json.loads(path.read_text()):
                merged[r["word"]] = r["total"]
        except ValueError:
            pass
    return merged


def _static_reference_counts(tmp_path) -> dict:
    """The bit-identity baseline: the same pipeline run statically in-process
    over everything the feeder wrote."""
    G.clear()

    class WordSchema(pw.Schema):
        word: str

    t = pw.io.fs.read(
        str(tmp_path / "in"), format="csv", schema=WordSchema, mode="static"
    )
    counts = t.groupby(t.word).reduce(t.word, total=pw.reducers.count())
    rows: dict = {}

    def on_change(key, row, time, is_addition):
        if is_addition:
            rows[key] = {"word": row["word"], "total": int(row["total"])}
        else:
            rows.pop(key, None)

    pw.io.subscribe(counts, on_change)
    pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    G.clear()
    return {r["word"]: r["total"] for r in rows.values()}


@pytest.mark.chaos
def test_autoscale_cycle_under_ramping_load_no_operator_input(tmp_path):
    """THE acceptance scenario: ``spawn -n 2 --autoscale`` under a ramping
    synthetic load (the chaos ``load_spike`` profile) scales to 4 and back
    to 2 with NO operator input — no scale plan, no control commands — and
    the final merged output is bit-identical to a static run. Exactly one
    transition per direction (no flap), never a restart-all."""
    (tmp_path / "in").mkdir()
    load = Chaos(0, {"load": {
        "op": "load_spike", "at_s": 3.0, "duration_s": 8.0,
        "low": 60.0, "high": 600.0,
    }})
    env = os.environ.copy()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PATHWAY_TPU_TEST_DIR"] = str(tmp_path)
    env["JAX_PLATFORMS"] = "cpu"
    env["PATHWAY_HEARTBEAT_INTERVAL_S"] = "0.2"
    env["PATHWAY_BARRIER_TIMEOUT_S"] = "60"
    env["PATHWAY_FENCE_TIMEOUT_S"] = "60"
    env["PATHWAY_MEMBERSHIP_DEADLINE_S"] = "90"
    env["PATHWAY_AUTOSCALE"] = "on"
    env["PATHWAY_AUTOSCALE_MIN"] = "2"
    env["PATHWAY_AUTOSCALE_MAX"] = "4"
    env["PATHWAY_AUTOSCALE_ROWS_PER_WORKER"] = "150"
    env["PATHWAY_AUTOSCALE_SAMPLE_S"] = "0.5"
    env["PATHWAY_AUTOSCALE_UP_SAMPLES"] = "2"
    env["PATHWAY_AUTOSCALE_DOWN_SAMPLES"] = "4"
    env["PATHWAY_AUTOSCALE_UP_COOLDOWN_S"] = "2"
    env["PATHWAY_AUTOSCALE_DOWN_COOLDOWN_S"] = "4"
    env["PATHWAY_AUTOSCALE_FLAP_WINDOW_S"] = "60"
    env["PATHWAY_AUTOSCALE_FLAP_REVERSALS"] = "3"
    prog = tmp_path / "prog.py"
    prog.write_text(AUTOSCALE_PROG)
    control_port = _port_base() + 5
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "pathway_tpu.cli", "spawn",
            "-n", "2", "--first-port", str(_port_base()),
            "--max-restarts", "2", "--autoscale",
            "--control-port", str(control_port),
            sys.executable, str(prog),
        ],
        env=env, cwd=str(tmp_path), start_new_session=True,
        stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True,
    )
    err = ""
    expected: dict = {}
    try:
        # feed at the chaos load profile (rows/s follow the spike), tallying
        # the expected counts as we write
        t0 = time.monotonic()
        carry = 0.0
        last = 0.0
        i = 0
        while True:
            elapsed = time.monotonic() - t0
            if elapsed >= 15.0:
                break
            if proc.poll() is not None:
                _, err = proc.communicate()
                raise AssertionError(
                    f"spawn exited early (rc={proc.returncode}): {err}"
                )
            carry += (load.load_rate(elapsed) or 0.0) * max(0.0, elapsed - last)
            last = elapsed
            rows = int(carry)
            if rows > 0:
                carry -= rows
                word = f"w{i % 17}"
                (tmp_path / "in" / f"f{i:06d}.csv").write_text(
                    "word\n" + f"{word}\n" * rows
                )
                expected[word] = expected.get(word, 0) + rows
                i += 1
            time.sleep(0.1)
        # convergence: everything fed is delivered exactly once AND the
        # supervisor reports the cluster stable back at n=2 (the read-only
        # status command — still no operator INPUT). 240 s, the suite-wide
        # spawn-convergence discipline: a full out-and-back cycle (two
        # membership transitions) under full-suite load legitimately takes
        # minutes, and a tight wait reads as spurious row loss
        deadline = time.monotonic() + 240
        merged: dict = {}
        back_at_2 = False
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                _, err = proc.communicate()
                raise AssertionError(
                    f"spawn exited early (rc={proc.returncode}): {err}"
                )
            merged = _read_merged(tmp_path, 4)
            try:
                status = json.loads(_control(control_port, "status"))
                back_at_2 = (
                    status.get("n") == 2
                    and not status.get("transition_in_flight")
                )
            except (OSError, ValueError):
                back_at_2 = False
            if merged == expected and back_at_2:
                break
            time.sleep(0.3)
        assert merged == expected, f"got {merged}, want {expected}"
        assert back_at_2, "cluster never reported stable at n=2"
    finally:
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            _, err = proc.communicate(timeout=20)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            _, err = proc.communicate()
        err = err or ""
    assert "autoscaler: scaling n=2 -> n=4" in err, (
        f"the controller never scaled out:\n{err}"
    )
    assert "membership change complete: cluster is n=4" in err, (
        f"scale-out never completed:\n{err}"
    )
    assert "membership change complete: cluster is n=2" in err, (
        f"scale-in never completed:\n{err}"
    )
    assert err.count("membership change requested") == 2, (
        f"more than one transition per direction (flap?):\n{err}"
    )
    assert "FLAP-LOCKED" not in err
    assert "restarting the cluster" not in err, (
        f"a transition fell back to restart-all:\n{err}"
    )
    # bit-identical to the failure-free static run of the same pipeline
    assert _static_reference_counts(tmp_path) == expected


@pytest.mark.chaos
def test_chaos_scale_refused_backs_off_typed_under_spawn(tmp_path):
    """The chaos ``scale_refused`` op injects a preflight refusal into a live
    cluster: the autoscaler's scale-up is refused TYPED
    (AutoscaleRefusedError in the supervisor log), retried at most once per
    backoff window, and the cluster keeps running at n=2 with exact
    output."""
    (tmp_path / "in").mkdir()
    env = os.environ.copy()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PATHWAY_TPU_TEST_DIR"] = str(tmp_path)
    env["JAX_PLATFORMS"] = "cpu"
    env["PATHWAY_HEARTBEAT_INTERVAL_S"] = "0.2"
    env["PATHWAY_BARRIER_TIMEOUT_S"] = "60"
    env["PATHWAY_MEMBERSHIP_DEADLINE_S"] = "60"
    env["PATHWAY_CHAOS_SEED"] = "7"
    # every attempt on rank 0 is refused at the preflight vote
    env["PATHWAY_CHAOS_PLAN"] = json.dumps(
        {"scale": [{"op": "scale_refused", "rank": 0}]}
    )
    env["PATHWAY_AUTOSCALE"] = "on"
    env["PATHWAY_AUTOSCALE_MIN"] = "2"
    env["PATHWAY_AUTOSCALE_MAX"] = "4"
    env["PATHWAY_AUTOSCALE_ROWS_PER_WORKER"] = "50"
    env["PATHWAY_AUTOSCALE_SAMPLE_S"] = "0.5"
    env["PATHWAY_AUTOSCALE_UP_SAMPLES"] = "2"
    env["PATHWAY_AUTOSCALE_UP_COOLDOWN_S"] = "1"
    env["PATHWAY_AUTOSCALE_REFUSAL_BACKOFF_S"] = "30"
    prog = tmp_path / "prog.py"
    prog.write_text(AUTOSCALE_PROG)
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "pathway_tpu.cli", "spawn",
            "-n", "2", "--first-port", str(_port_base()),
            "--max-restarts", "2", "--autoscale",
            sys.executable, str(prog),
        ],
        env=env, cwd=str(tmp_path), start_new_session=True,
        stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True,
    )
    err = ""
    expected: dict = {}
    try:
        # a steady overload: rate well past 2 workers' capacity, so the
        # controller keeps WANTING to scale up — the backoff must hold it
        t0 = time.monotonic()
        i = 0
        while time.monotonic() - t0 < 10.0:
            if proc.poll() is not None:
                _, err = proc.communicate()
                raise AssertionError(
                    f"spawn exited early (rc={proc.returncode}): {err}"
                )
            word = f"w{i % 7}"
            (tmp_path / "in" / f"f{i:06d}.csv").write_text(
                "word\n" + f"{word}\n" * 30
            )
            expected[word] = expected.get(word, 0) + 30
            i += 1
            time.sleep(0.15)
        deadline = time.monotonic() + 60
        merged: dict = {}
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                _, err = proc.communicate()
                raise AssertionError(
                    f"spawn exited early (rc={proc.returncode}): {err}"
                )
            merged = _read_merged(tmp_path, 2)
            if merged == expected:
                break
            time.sleep(0.3)
        assert merged == expected, f"got {merged}, want {expected}"
    finally:
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            _, err = proc.communicate(timeout=20)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            _, err = proc.communicate()
        err = err or ""
    assert "chaos: injected preflight refusal" in err, (
        f"the scale_refused op never fired:\n{err}"
    )
    # typed in the supervisor's log, and the backoff held: the refused
    # scale-up was attempted at most once inside the 30 s window
    assert "AutoscaleRefusedError" in err, f"refusal was not typed:\n{err}"
    assert err.count("membership change requested") <= 1, (
        f"refusal retry storm against the preflight vote:\n{err}"
    )
    assert "membership change complete: cluster is n=4" not in err
    assert "restarting the cluster" not in err
