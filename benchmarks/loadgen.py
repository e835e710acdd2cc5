"""Open-loop load generator for one ``POST`` route: a process of its own.

Standard library only; it never imports ``jax`` or ``pathway_tpu``, so it shares
neither the server's interpreter lock nor the chip. The schedule (due instants,
query texts) is a function of the traffic file and the seed alone
(``schedule``); the route and the body's fields are the traffic file's
``request`` group: ``{"route", "text_key", "fixed"}``, the drawn text under
``text_key`` and then the ``fixed`` fields as they stand. Every latency is taken from the instant a
request was *due*, not from when it was sent, and how late the generator sent
each one is logged beside it, so a starved generator is not read as a fast server.

As a child it is started with the traffic parameters on its command line and the
shared monotonic instant at which its first request is due (``time.monotonic``
is one clock for every process of the machine). It writes one JSON line per
request to ``--out``: ``{"i", "phase", "due", "sent", "done", "status", "query",
the body's fixed fields, "body"}`` (seconds relative to ``--start-at``; ``status``
0 and no body where no reply came before ``--timeout``).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import random
import sys
import time
from typing import Any, Dict, List

import textgen  # sibling module, standard library only


def schedule(traffic: Dict[str, Any], corpus: Dict[str, Any], seed: int, seconds: float,
             lead_in_s: float = 0.0, docs_seed: int | None = None) -> List[dict]:
    """Every request of one run: ``lead_in_s`` of warm-up traffic at the same
    rate (phase ``"lead"``, due before 0), then the window's requests (phase
    ``"window"``, due in ``[0, seconds)``). The gaps and the query sizes are
    drawn once from the traffic's own fixed ``pool_seed``; ``seed`` decides their
    order and which document each query asks about, so that every seed offers
    the same work in another order. The documents are those of ``docs_seed``
    (the run's own seed unless several schedules are driven at one set-up)."""
    assert traffic["arrival"] == "exponential", traffic["arrival"]
    rate = float(traffic["rate_rps"])
    n_window = int(round(rate * seconds))
    n_lead = int(round(rate * lead_in_s))
    pool = random.Random(int(traffic.get("pool_seed", 0)) * 1_000_003 + n_window)

    def gaps(n: int, span: float) -> List[float]:
        raw = [pool.expovariate(1.0) for _ in range(n)]
        scale = span / (sum(raw) + pool.expovariate(1.0)) if n else 0.0
        return [g * scale for g in raw]  # exponential gaps, n of them inside the span

    window_gaps, lead_gaps = gaps(n_window, seconds), gaps(n_lead, lead_in_s)
    sizes = [textgen.draw_length(pool, traffic["query_words"]) for _ in range(n_window + n_lead)]
    order = random.Random(seed)
    order.shuffle(window_gaps)
    window_sizes = sizes[:n_window]
    order.shuffle(window_sizes)
    docs = textgen.documents(corpus, seed if docs_seed is None else docs_seed)
    # the running tag makes every query unique: no cache can answer it
    texts = [textgen.query_from(docs[order.randrange(len(docs))], order, n_words, f"q{i}")
             for i, n_words in enumerate(window_sizes + sizes[n_window:])]
    fixed = traffic["request"]["fixed"]
    out, t = [], -lead_in_s
    for g, text in zip(lead_gaps, texts[n_window:]):
        t += g
        out.append({"phase": "lead", "due": t, "query": text, **fixed})
    t = 0.0
    for g, text in zip(window_gaps, texts[:n_window]):
        t += g
        out.append({"phase": "window", "due": t, "query": text, **fixed})
    for i, r in enumerate(out):
        r["i"] = i
    return out


class _Pool:
    """Keep-alive HTTP/1.1 connections to one host, opened on demand."""

    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self.idle: list = []

    async def take(self):
        if self.idle:
            return self.idle.pop()
        return await asyncio.open_connection(self.host, self.port)

    def give(self, conn) -> None:
        self.idle.append(conn)


async def _post(pool: _Pool, route: str, payload: bytes) -> tuple:
    """One POST over a pooled connection: (status, body text). A connection that
    the server closed while it sat idle is replaced once."""
    head = (
        f"POST {route} HTTP/1.1\r\nHost: {pool.host}:{pool.port}\r\n"
        f"Content-Type: application/json\r\nContent-Length: {len(payload)}\r\n\r\n"
    ).encode()
    for attempt in (0, 1):
        reader, writer = await pool.take()
        try:
            writer.write(head + payload)
            await writer.drain()
            status_line = await reader.readline()
            if not status_line:
                raise ConnectionResetError("closed before a status line")
            status = int(status_line.split()[1])
            length, close = None, False
            while True:
                line = await reader.readline()
                if line in (b"\r\n", b"\n", b""):
                    break
                name, _, value = line.decode("latin-1").partition(":")
                if name.strip().lower() == "content-length":
                    length = int(value)
                elif name.strip().lower() == "connection" and value.strip().lower() == "close":
                    close = True
            body = await (reader.readexactly(length) if length is not None else reader.read())
            if close or length is None:
                writer.close()
            else:
                pool.give((reader, writer))
            return status, body.decode("utf-8", "replace")
        except (ConnectionError, asyncio.IncompleteReadError):
            writer.close()
            if attempt:
                raise
    raise AssertionError("unreachable")


async def drive(requests: List[dict], host: str, port: int, request: Dict[str, Any], start_at: float,
                 timeout_s: float) -> List[dict]:
    """Send every request at its due instant to ``request["route"]`` (the
    traffic's ``request`` group), its text under ``request["text_key"]``."""
    route, text_key, fixed = request["route"], request["text_key"], request["fixed"]
    pool = _Pool(host, port)
    loop = asyncio.get_running_loop()
    offset = loop.time() - time.monotonic()  # the loop's clock is monotonic too

    async def one(req: dict) -> dict:
        rec = dict(req, sent=None, done=None, status=0, body=None)
        payload = json.dumps({text_key: req["query"], **fixed}).encode()
        delay = start_at + req["due"] + offset - loop.time()
        if delay > 0:
            await asyncio.sleep(delay)
        rec["sent"] = time.monotonic() - start_at
        try:
            status, body = await asyncio.wait_for(_post(pool, route, payload), timeout_s)
            rec["status"], rec["body"] = status, body
            rec["done"] = time.monotonic() - start_at
        except (asyncio.TimeoutError, OSError, ValueError, IndexError) as e:
            rec["error"] = f"{type(e).__name__}: {e}"
        return rec

    return list(await asyncio.gather(*(one(r) for r in requests)))


def main(argv: List[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--traffic", required=True, help="traffic parameters, as JSON")
    ap.add_argument("--corpus", required=True, help="corpus parameters, as JSON")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--docs-seed", type=int, default=None, help="the seed of the live documents")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--lead-in", type=float, default=0.0)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--start-at", type=float, required=True,
                    help="time.monotonic() instant at which the window's first gap starts")
    ap.add_argument("--timeout", type=float, default=60.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    assert "jax" not in sys.modules and "pathway_tpu" not in sys.modules
    traffic = json.loads(args.traffic)
    requests = schedule(traffic, json.loads(args.corpus), args.seed, args.seconds, args.lead_in,
                        args.docs_seed)
    records = asyncio.run(
        drive(requests, args.host, args.port, traffic["request"], args.start_at, args.timeout)
    )
    with open(args.out, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
