"""Thread-lifecycle hygiene (the PWA104 contract, audited dynamically): after
``pw.run`` / stepped-run teardown and after a monitoring/REST server stop, no
non-daemon thread beyond the main thread survives — a leaked non-daemon
thread blocks interpreter shutdown and holds its resources across back-to-back
runs. Plus the PWA102 contract of ``EncoderService``: a submission's wait is
bounded and abortable, and fails typed instead of wedging the engine thread
when the service dies with the submission still queued."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.models.encoder_service import EncoderService, _Submission


def _non_daemon_threads():
    main = threading.main_thread()
    return [
        t
        for t in threading.enumerate()
        if t is not main and not t.daemon and t.is_alive()
    ]


def _assert_no_leaks(before, what: str):
    # allow a short settle for threads mid-exit at teardown
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        leaked = [t for t in _non_daemon_threads() if t not in before]
        if not leaked:
            return
        time.sleep(0.05)
    raise AssertionError(f"non-daemon threads leaked after {what}: {leaked}")


def test_no_nondaemon_threads_after_pw_run():
    before = _non_daemon_threads()
    t = pw.debug.table_from_rows(pw.schema_builder({"v": int}), [(1,), (2,)])
    got = []
    pw.io.subscribe(t, lambda key, row, time, is_addition: got.append(row["v"]))
    pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    assert sorted(got) == [1, 2]
    _assert_no_leaks(before, "pw.run teardown")


def test_no_nondaemon_threads_after_stepped_run():
    from pathway_tpu.engine.runner import GraphRunner
    from pathway_tpu.internals import parse_graph as pg

    before = _non_daemon_threads()
    t = pw.debug.table_from_rows(pw.schema_builder({"v": int}), [(3,)])
    got = []
    pw.io.subscribe(t, lambda key, row, time, is_addition: got.append(row["v"]))
    runner = GraphRunner(pg.G._current)
    runner.setup()
    while runner.step():
        pass
    runner.finish()
    assert got == [3]
    _assert_no_leaks(before, "stepped-run teardown")


def test_no_nondaemon_threads_after_monitoring_server_stop():
    from pathway_tpu.engine.http_server import MonitoringServer, ProberStats

    before = _non_daemon_threads()
    server = MonitoringServer(ProberStats(), 0)  # ephemeral port
    assert server.port > 0
    server.close()
    server.close()  # idempotent
    _assert_no_leaks(before, "MonitoringServer stop")
    # the serving thread itself (daemon) must also exit, not just be orphaned
    server.thread.join(timeout=5)
    assert not server.thread.is_alive()


def test_no_nondaemon_threads_after_rest_webserver_stop():
    aiohttp = pytest.importorskip("aiohttp")
    del aiohttp
    import socket

    from pathway_tpu.io.http._server import PathwayWebserver

    before = _non_daemon_threads()
    with socket.socket() as probe:
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
    server = PathwayWebserver(host="127.0.0.1", port=port)
    server._ensure_running()
    assert server._started.wait(timeout=10)
    _assert_no_leaks(before, "REST webserver start+stop")
    # the aiohttp loop thread is daemon by contract (PWA104): it must never
    # keep the interpreter alive
    assert server._thread.daemon


# ---------------------------------------------------------------------------
# EncoderService PWA102 contract: the wait is bounded and abortable. Worker
# hygiene: clean shutdown on service stop/close and on pw.run teardown (the
# leaked-thread check for the service worker)
# ---------------------------------------------------------------------------


class _InstantEncoder:
    dim = 4

    def encode_device(self, texts):
        return np.zeros((len(texts), 4), dtype=np.float32)


class _GatedEncoder(_InstantEncoder):
    def __init__(self):
        self.release = threading.Event()

    def encode_device(self, texts):
        self.release.wait(timeout=30)
        return super().encode_device(texts)


def test_encoder_service_close_with_live_worker_still_answers():
    """close() racing an admitted submission: the live worker drains the queue
    before it exits, so the submission is answered, not dropped."""
    enc = _GatedEncoder()
    svc = EncoderService(enc, prewarm=False)
    got = []
    t = threading.Thread(target=lambda: got.append(svc.submit(["a", "b"])))
    t.start()
    deadline = time.monotonic() + 5.0
    while svc.queue_depth_rows() != 2 or not svc.worker_alive():
        assert time.monotonic() < deadline, "submission never admitted"
        time.sleep(0.01)
    closer = threading.Thread(target=svc.close)  # joins the worker: off-thread
    closer.start()
    enc.release.set()
    t.join(timeout=10)
    closer.join(timeout=10)
    assert got and len(got[0]) == 2, "admitted submission dropped at close"
    assert not svc.worker_alive()
    svc.close()  # idempotent


def test_encoder_service_close_with_dead_worker_fails_typed_not_wedged():
    """A submission stranded in the queue of a closed service with no worker
    to drain it must fail typed within the poll interval, not sit in an
    untimed event.wait() forever (the PWA102 finding)."""
    svc = EncoderService(_InstantEncoder(), prewarm=False)
    # plant a stranded submission: queued, no worker thread, service closed —
    # the state a worker crash (or an exec-env teardown) leaves behind
    sub = _Submission(["stuck"])
    with svc._cond:
        svc._queue.append(sub)
        svc._queued_rows += 1
        svc._closed = True
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="closed before this submission"):
        svc._await(sub)
        raise sub.error  # _await sets the typed error; submit() re-raises it
    assert time.monotonic() - t0 < 5.0, "abort took longer than the poll bound"
    assert svc.queue_depth_rows() == 0, "admission slot leaked on the abort path"


def test_encoder_service_wait_timeout_knob(monkeypatch):
    """PATHWAY_EMBED_WAIT_TIMEOUT_S bounds the total wait against a wedged
    encoder device."""
    enc = _GatedEncoder()
    monkeypatch.setenv("PATHWAY_EMBED_WAIT_TIMEOUT_S", "1")
    svc = EncoderService(enc, prewarm=False)
    assert svc.wait_timeout_s == 1.0
    t0 = time.monotonic()
    with pytest.raises(TimeoutError, match="PATHWAY_EMBED_WAIT_TIMEOUT_S"):
        svc.submit(["x"])
    assert time.monotonic() - t0 < 10.0
    enc.release.set()  # un-wedge the worker so it exits
    svc.close()


def test_encoder_service_worker_stops_on_stop_and_close():
    svc = EncoderService(_InstantEncoder(), prewarm=False)
    assert not svc.worker_alive()  # lazy spawn: no thread before first submit
    out = svc.submit(["a", "b"])
    assert len(out) == 2
    assert svc.worker_alive()
    svc.stop_worker()
    assert not svc.worker_alive()
    # stopped, not closed: the next submit respawns the worker and answers
    assert len(svc.submit(["c"])) == 1
    assert svc.worker_alive()
    svc.close()
    svc.close()  # idempotent
    assert not svc.worker_alive()
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit(["d"])


def test_encoder_service_stop_with_inflight_request_still_answers():
    """stop_all_workers racing an admitted request must drain, not drop (the
    drop_on_close bug class from the protocol model, checked on real threads)."""
    enc = _GatedEncoder()
    svc = EncoderService(enc, prewarm=False)
    got = []
    t = threading.Thread(target=lambda: got.append(svc.submit(["x"])))
    t.start()
    deadline = time.monotonic() + 5.0
    while not svc.worker_alive() and time.monotonic() < deadline:
        time.sleep(0.01)
    stopper = threading.Thread(target=svc.stop_worker)
    stopper.start()
    enc.release.set()
    t.join(timeout=10)
    stopper.join(timeout=10)
    assert got and len(got[0]) == 1, "admitted request dropped at stop"
    assert not svc.worker_alive()
    svc.close()


def test_no_encoder_service_worker_after_pw_run():
    """pw.run teardown stops the service worker (GraphRunner.finish →
    stop_all_workers); the embedder stays usable — the worker respawns on the
    next query."""
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    tiny = EncoderConfig(
        vocab_size=8192, hidden_size=32, num_layers=1, num_heads=2,
        intermediate_size=64,
    )
    emb = SentenceTransformerEmbedder(
        model="pw-test-tiny", encoder_config=tiny,
    )
    before = _non_daemon_threads()
    t = pw.debug.table_from_rows(pw.schema_builder({"q": str}), [("hygiene query",)])
    res = t.select(v=emb.device_expression(t.q))
    got = []
    pw.io.subscribe(res, lambda key, row, time, is_addition: got.append(row["v"]))
    pw.run(monitoring_level=pw.MonitoringLevel.NONE)
    assert len(got) == 1
    _assert_no_leaks(before, "pw.run with encoder service")
    svc = emb.pipeline.service
    deadline = time.monotonic() + 5.0
    while svc.worker_alive() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not svc.worker_alive(), "service worker leaked past pw.run teardown"
    # still serviceable afterwards
    assert len(emb.pipeline.embed_query_rows(["again"])) == 1
    svc.close()
