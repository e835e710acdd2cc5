"""The readers of a generated reply's timeline (``lm_queue_ms``, ``lm_ttft_ms``,
``lm_tpot_ms``, ``lm_prefills_per_reply``): what the generation service sums in
``stats()`` over the requests it resolved, read over the window on hand-made
counters, and nothing where the program has no such counters or resolved no
request."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
import run  # noqa: E402

NAMES = ("lm_queue_ms", "lm_ttft_ms", "lm_tpot_ms", "lm_prefills_per_reply")
CELLS = ["answer-lfm2-steady", "answer-mistral4-steady", "answer-falconh1-steady"]


def entries():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    found = [m for m in manifest["per_layer"] if m["name"] in NAMES]
    assert [m["name"] for m in found] == list(NAMES)
    for m in found:
        assert (m["source"], m["layer"], m["moves"], m["better"], m["workloads"]) == (
            "program_counter", "generation service", "retrieve_p50_ms", "lower", CELLS)
    return found


def context(before, after):
    return {"counters_before": before, "counters_after": after}


BEFORE = {"lm_requests_done": 40.0, "lm_reply_tokens": 1280.0, "lm_queue_s": 0.1, "lm_ttft_s": 1.0,
          "lm_decode_phase_s": 6.0, "lm_prefills_in_decode": 30.0, "lm_slots": 16.0}
# 160 replies of 32 tokens in the window: 0.32 s of queue, 4.8 s to the first tokens, 24.8 s after them
AFTER = {"lm_requests_done": 200.0, "lm_reply_tokens": 6400.0, "lm_queue_s": 0.42, "lm_ttft_s": 5.8,
         "lm_decode_phase_s": 30.8, "lm_prefills_in_decode": 150.0, "lm_slots": 16.0}


@pytest.mark.parametrize("cell", CELLS)
def test_the_readers_on_hand_made_counters(cell):
    wanted = run.resolve(cell, rehearse=False)["per_layer"]
    assert set(NAMES) <= {m["name"] for m in wanted}
    got = {name: m["value"] for name, m in run.read_metrics(entries(), context(BEFORE, AFTER)).items()}
    assert got == pytest.approx({"lm_queue_ms": 2.0, "lm_ttft_ms": 30.0, "lm_tpot_ms": 24.8e3 / (160 * 31),
                                 "lm_prefills_per_reply": 0.75})
    # the reply's time is the time to its first token and its other 31 at the mean time a token
    assert got["lm_ttft_ms"] + 31 * got["lm_tpot_ms"] == pytest.approx(1e3 * (4.8 + 24.8) / 160)


@pytest.mark.parametrize("before,after", [
    ({}, {}),  # a program without the timeline (the parent commit), or another system
    ({k: v for k, v in BEFORE.items() if k.startswith("lm_slots")}, {"lm_slots": 16.0}),
    (BEFORE, BEFORE),  # no request resolved in the window
    (BEFORE, dict(BEFORE, lm_requests_done=41.0, lm_reply_tokens=1281.0, lm_ttft_s=1.02, lm_queue_s=0.1)),
])
def test_the_readers_return_nothing_where_there_is_nothing_to_read(before, after):
    got = run.read_metrics(entries(), context(before, after))
    if after.get("lm_requests_done", 0) > before.get("lm_requests_done", 0):
        # one reply of one token: queue, first token and prefills read, a token after the first is not there
        assert set(got) == {"lm_queue_ms", "lm_ttft_ms", "lm_prefills_per_reply"}
        assert got["lm_ttft_ms"]["value"] == pytest.approx(20.0) and got["lm_prefills_per_reply"]["value"] == 0.0
    else:
        assert got == {}
