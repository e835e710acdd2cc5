"""The generator: a schedule that is a function of the seed alone, the same work
for every seed, and latencies taken from the due instant."""

import asyncio
import http.server
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import loadgen  # noqa: E402

with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "workloads",
                       "serve-dense-2m.json")) as f:
    DENSE_REQUEST = json.load(f)["request"]  # the dense cell's own: /v1/retrieve, {"query", "k": 10}
TRAFFIC = {"arrival": "exponential", "rate_rps": 40, "pool_seed": 1, "request": DENSE_REQUEST,
           "query_words": {"min": 3, "max": 12, "mean": 6}}
CORPUS = {"live_docs": 64, "doc_words": {"min": 20, "max": 120, "mean": 56}, "vocab_words": 20000,
          "pool_seed": 1}


def test_schedule_is_a_function_of_the_seed_alone():
    a = loadgen.schedule(TRAFFIC, CORPUS, 2**31 + 5, 5.0, 1.0)
    b = loadgen.schedule(TRAFFIC, CORPUS, 2**31 + 5, 5.0, 1.0)
    assert a == b
    assert a != loadgen.schedule(TRAFFIC, CORPUS, 7, 5.0, 1.0)


def test_every_seed_offers_the_same_work_in_another_order():
    a = [r for r in loadgen.schedule(TRAFFIC, CORPUS, 1, 5.0) if r["phase"] == "window"]
    b = [r for r in loadgen.schedule(TRAFFIC, CORPUS, 2, 5.0) if r["phase"] == "window"]
    assert len(a) == len(b) == 200
    words = lambda rs: sorted(len(r["query"].split()) for r in rs)
    gaps = lambda rs: sorted(round(y["due"] - x["due"], 9) for x, y in zip(rs, rs[1:]))
    assert words(a) == words(b)
    assert [r["query"] for r in a] != [r["query"] for r in b]
    # the same multiset of gaps (the first gap starts at 0), in another order
    first = lambda rs: sorted([round(rs[0]["due"], 9)] + gaps(rs))
    assert first(a) == first(b) and gaps(a) != [round(y["due"] - x["due"], 9) for x, y in zip(b, b[1:])]


def test_window_requests_are_due_inside_the_window_and_unique():
    rs = loadgen.schedule(TRAFFIC, CORPUS, 3, 5.0, 2.0)
    window = [r for r in rs if r["phase"] == "window"]
    lead = [r for r in rs if r["phase"] == "lead"]
    assert all(0.0 <= r["due"] < 5.0 for r in window) and all(-2.0 <= r["due"] < 0.0 for r in lead)
    assert len({r["query"] for r in rs}) == len(rs)
    assert all(3 <= len(r["query"].split()) <= 12 for r in rs)


class _Slow(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def do_POST(self):
        self.rfile.read(int(self.headers["Content-Length"]))
        time.sleep(0.05)
        body = json.dumps([{"ok": True}]).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass


def test_latency_runs_from_the_due_instant():
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Slow)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        # three requests all due 0.3 s BEFORE the generator starts: sent late, and
        # the lateness is part of the latency and reported beside it
        reqs = [{"i": i, "phase": "window", "due": -0.3, "query": f"q{i}", "k": 1} for i in range(3)]
        recs = asyncio.run(loadgen.drive(reqs, "127.0.0.1", server.server_address[1],
                                          {"route": "/x", "text_key": "query", "fixed": {"k": 1}},
                                          time.monotonic(), 10.0))
    finally:
        server.shutdown()
    for r in recs:
        assert r["status"] == 200 and json.loads(r["body"]) == [{"ok": True}]
        assert r["sent"] - r["due"] >= 0.3  # how late it was sent
        assert r["done"] - r["due"] >= 0.35  # due -> reply, the wait included
        assert r["done"] - r["sent"] >= 0.05


class _Echo(_Slow):
    """Replies with the path and the body it was sent, as it was sent."""

    def do_POST(self):
        sent = self.rfile.read(int(self.headers["Content-Length"])).decode()
        body = json.dumps({"path": self.path, "sent": sent}).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


def test_the_traffics_request_group_names_the_route_and_the_bodys_fields():
    """The text goes under the group's key, the fixed fields after it, to its
    route: for the dense cell's group the body is what it always was, byte for byte."""
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Echo)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    other = dict(TRAFFIC, request={"route": "/v2/rank", "text_key": "prompt", "fixed": {"top": 3, "greedy": True}})
    try:
        got = {}
        for name, traffic in (("dense", TRAFFIC), ("other", other)):
            reqs = loadgen.schedule(traffic, CORPUS, 5, 0.1)
            recs = asyncio.run(loadgen.drive(reqs, "127.0.0.1", server.server_address[1],
                                             traffic["request"], time.monotonic(), 10.0))
            got[name] = [(r, json.loads(r["body"])) for r in recs]
    finally:
        server.shutdown()
    assert len(got["dense"]) == len(got["other"]) == 4
    for r, echo in got["dense"]:
        assert r["k"] == 10 and echo["path"] == "/v1/retrieve"
        assert echo["sent"] == json.dumps({"query": r["query"], "k": 10})
    for r, echo in got["other"]:
        assert "k" not in r and r["top"] == 3 and echo["path"] == "/v2/rank"
        assert echo["sent"] == json.dumps({"prompt": r["query"], "top": 3, "greedy": True})
    # the route and the fields change nothing of the schedule
    assert [(r["due"], r["query"]) for r, _ in got["dense"]] == [(r["due"], r["query"]) for r, _ in got["other"]]


def test_the_generator_never_imports_jax():
    src = open(os.path.join(os.path.dirname(os.path.abspath(loadgen.__file__)), "loadgen.py")).read()
    assert "import jax" not in src and "import pathway_tpu" not in src
