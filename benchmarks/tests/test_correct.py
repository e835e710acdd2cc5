"""``correct`` has been shown to fail: the lower-precision controls put in the
program's place, and a run with the timed path broken underneath.

The controls' and the program's readings at the cell's own size are chip runs
(``run.py --calibrate``; PERF.md lists them). Here the same comparison and the
same limits are held at a size a test run can hold: the tiny CPU rehearsal for
the planted faults, the full-width encoder over 96 documents and 2,048 resident
rows for the controls. Both go through the ``vector_store`` system's module
(``systems/vector_store.py``): the faults through its ``judge`` under ``run.py``,
the controls by its ``CONTROLS`` table.
"""

import json
import os
import random
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)
import compare  # noqa: E402
from faulty_run import FAULTS  # noqa: E402

CELLS = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "workloads")) if f.endswith(".json"))


def limits_of(cell):
    limits = dict(json.load(open(os.path.join(BENCH, "workloads", cell + ".json")))["limits"])
    del limits["compiles_in_window"]  # counted by a run, not by the comparison of answers
    return limits


def run_faulty(cell, fault):
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "faulty_run.py"), "--fault", fault, "--workload", cell,
         "--seed", "2147483777", "--seconds", "2", "--trace", "0"],
        capture_output=True, text=True, timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", [f for f in FAULTS if f != "none"])
def test_a_planted_fault_reads_not_correct(fault):
    result = run_faulty("serve-dense-2m", fault)
    assert result["correct"] is False
    # on a stalled test machine ``compiles_in_window`` is printed with no limit: not judged
    over = [n for n, row in result["compared"].items() if row["limit"] is not None and row["value"] > row["limit"]]
    assert over, result["compared"]
    if fault in ("live_rows_only", "last_block_only"):
        # whole replies of k ordered entries: only the scores say that rows were left out
        assert result["compared"]["bad_replies"]["value"] == 0 and "kth_score_err" in over


def test_the_same_entry_with_no_fault_reads_correct():
    result = run_faulty("serve-dense-2m", "none")
    assert result["correct"] is True, result["compared"]
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}


@pytest.fixture(scope="module")
def small_world():
    """Seeded weights, 96 documents, 24 queries and one block of resident rows at
    the full encoder width, with the float32 reference's answers."""
    import reference
    import textgen
    import weights

    model = {"vocab_size": 30522, "hidden_size": 384, "num_hidden_layers": 6, "num_attention_heads": 12,
             "intermediate_size": 1536, "max_position_embeddings": 512, "type_vocab_size": 2,
             "layer_norm_eps": 1e-12, "max_length": 128}
    corpus = {"live_docs": 96, "doc_words": {"min": 20, "max": 120, "mean": 56}, "vocab_words": 20000}
    rng = random.Random(5)
    seed, k = 2**31 + 9, 10
    docs = textgen.documents(corpus, seed)
    queries = [textgen.query_from(docs[rng.randrange(len(docs))], rng, rng.randint(3, 12), f"q{i}")
               for i in range(24)]
    w, _ = weights.make_weights(seed, model, {"std": 0.02, "word_embedding_std": 0.06})
    embed = lambda texts, precision="f32": reference.embed_texts(w, texts, model, precision)
    ref_docs, ref_q = embed(docs), embed(queries)
    resident = weights.resident_block(seed, 0, 2048, 384, ref_docs, {"stride": 8, "spread": [0.05, 0.3]})
    blocks = lambda doc_vecs: [lambda: (doc_vecs, 0), lambda: (resident, len(docs))]
    ref_topk, ref_ids = reference.exact_topk(ref_q, blocks(ref_docs), k)
    assert (ref_ids >= len(docs)).any() and (ref_ids < len(docs)).any()  # live and resident answers
    return {"k": k, "docs": docs, "queries": queries, "embed": embed, "blocks": blocks, "ref_q": ref_q,
            "ref_docs": ref_docs, "ref_scores": reference.cosine_to(ref_q, ref_docs), "ref_topk": ref_topk}


def judged(world, cell, scores, ids):
    answers = compare.answers_from(ids, scores, world["docs"])
    numbers = compare.compare(answers, world["k"], world["docs"], world["ref_scores"], world["ref_topk"])
    return compare.judge(numbers, limits_of(cell))


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("control", ["fp8_encoder", "fp8_index"])
def test_a_float8_control_fails_the_cells_limits(cell, control, small_world):
    """The reference one precision down, in the encoder's products or in the
    index's scoring (the system's ``CONTROLS`` say which), in the program's
    place: its answers against the float32 reference's fail at least one number
    under the cell's own limits."""
    import reference
    import run

    w = small_world
    encoder, scoring, _ = run.load_module("systems", "vector_store").CONTROLS[control]
    docs, queries = w["ref_docs"], w["ref_q"]
    if encoder != "f32":
        docs, queries = w["embed"](w["docs"], encoder), w["embed"](w["queries"], encoder)
    correct, table = judged(w, cell, *reference.exact_topk(queries, w["blocks"](docs), w["k"], scoring))
    assert not correct, table


@pytest.mark.parametrize("cell", CELLS)
def test_the_reference_against_itself_passes_and_without_resident_rows_fails(cell, small_world):
    import reference

    w = small_world
    assert judged(w, cell, *reference.exact_topk(w["ref_q"], w["blocks"](w["ref_docs"]), w["k"]))[0]
    correct, table = judged(w, cell, *reference.exact_topk(w["ref_q"], w["blocks"](w["ref_docs"])[:1], w["k"]))
    assert not correct and table["kth_score_err"]["value"] > table["kth_score_err"]["limit"], table
