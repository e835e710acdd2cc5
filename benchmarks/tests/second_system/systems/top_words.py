"""A second system under test, added as files only: the proof that a
configuration can bring its own (``tests/test_second_system.py``).

It serves ``POST /v1/top_words {"text", "top"}`` through ``pathway_tpu``'s REST
connector: the ``top`` last words of the text in dictionary order, the last
first. A host-only function of the request: no model, no device program. What
``run.py`` asks of a system's module is all here: the server from the program's
normal constructors in a thread of this process, how a reply is read, the plain
reference (``heapq`` over the words, nothing of the program), the one number
compared (``wrong_answers``, exact, so the cell's limit is 0), and a control
that breaks the stated guarantee (the order).
"""

from __future__ import annotations

import asyncio
import heapq
import json
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import loadgen

ROUTE = "/v1/top_words"
COMPILE_COUNTERS = ()  # nothing it serves is a compiled program
CONTROLS = ("first_words",)  # the guarantee broken: the first words of the text, not the last in order


def top_words(text: str, top: int) -> List[str]:
    return sorted(set(text.split()), reverse=True)[:top]


class System:
    def __init__(self, cfg: Dict[str, Any], seed: int, port: int, docs: List[str],
                 log: Callable[[str], None]):
        import pathway_tpu as pw
        from pathway_tpu.internals import parse_graph as pg
        from pathway_tpu.io.http import PathwayWebserver, rest_connector

        self.cfg, self.seed, self.port, self.docs, self.log = cfg, seed, port, docs, log
        self.timings: Dict[str, float] = {}
        t0 = time.monotonic()
        pg.G.clear()
        queries, writer = rest_connector(
            webserver=PathwayWebserver(host="127.0.0.1", port=port), route=ROUTE,
            schema=pw.schema_from_types(text=str, top=int), delete_completed_queries=True,
        )
        writer(queries.select(result=pw.apply(top_words, pw.this.text, pw.this.top)))
        self.thread = threading.Thread(
            target=lambda: pw.run(monitoring_level=pw.MonitoringLevel.NONE), daemon=True, name="top-words")
        self.thread.start()
        self.timings["server_s"] = time.monotonic() - t0

    def _burst(self, traffic: Dict[str, Any], texts: List[str]) -> List[dict]:
        request = traffic["request"]
        reqs = [{"i": i, "phase": "warm", "due": 0.0, "query": t, **request["fixed"]} for i, t in enumerate(texts)]
        return asyncio.run(loadgen.drive(reqs, "127.0.0.1", self.port, request, time.monotonic(), 30.0))

    def wait_ready(self) -> None:
        t0 = time.monotonic()
        probe = {"request": {"route": ROUTE, "text_key": "text", "fixed": {"top": 1}}}
        while self._burst(probe, ["b a"])[0]["status"] != 200:
            assert self.thread.is_alive() and time.monotonic() - t0 < 60.0, "the server did not come up"
            time.sleep(0.1)
        self.timings["ready_s"] = time.monotonic() - t0

    def warm_up(self, traffic: Dict[str, Any]) -> None:
        recs = self._burst(traffic, [d.split(" ", 1)[1] for d in self.docs[:8]])
        assert all(r["status"] == 200 for r in recs), recs[0]

    def counters(self) -> Dict[str, float]:
        return {}


def parse_reply(body: Optional[str]) -> Optional[List[str]]:
    try:
        words = json.loads(body)
    except (TypeError, ValueError):
        return None
    return words if isinstance(words, list) and all(isinstance(w, str) for w in words) else None


def good(answer: Optional[List[str]], traffic: Dict[str, Any]) -> bool:
    return answer is not None and len(answer) == int(traffic["request"]["fixed"]["top"])


def judge(spec: Dict[str, Any], system: System, sample: List[dict], controls=()):
    top = int(spec["traffic"]["request"]["fixed"]["top"])
    reference = [heapq.nlargest(top, set(r["query"].split())) for r in sample]

    def numbers(answers: List[Optional[List[str]]]) -> Dict[str, float]:
        return {"bad_replies": float(sum(a is None for a in answers)),
                "wrong_answers": float(sum(a is not None and a != ref for a, ref in zip(answers, reference)))}

    assert set(controls) <= set(CONTROLS), controls
    return numbers([r["answer"] for r in sample]), {c: numbers([r["query"].split()[:top] for r in sample])
                                                    for c in controls}


def metric_context(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {}
