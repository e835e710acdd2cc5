"""``lm_decode_streamed_layers``, the expert layers of a step whose product ran
through the streamed kernel (``pathway_tpu/models/moe.py``): its entry, found by
name; its reader on the counters a generation service leaves, driven at each
expert cell's rehearsal size on the CPU; and nothing read where the program has
no such counter (the parent of the kernel) or made no step.
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
import run  # noqa: E402

NAME = "lm_decode_streamed_layers"
CELLS = ("answer-lfm2-steady", "answer-mistral4-steady")


def entry():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    [found] = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert found == {"name": NAME, "unit": "layers", "better": "higher", "source": "program_counter",
                     "layer": "generator expert layer", "moves": "retrieve_p50_ms", "workloads": list(CELLS)}
    # the expert layer's name, letter for letter, as its other metrics give it
    assert {m["layer"] for m in manifest["per_layer"]
            if m["name"] in ("lm_prefill_batched_layers", "lm_decode_rows_per_expert.mistral4")} == {found["layer"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert all(cells[c]["config"] in ("lfm2-8b-a1b-rag", "mistral-small-4-119b-rag") for c in CELLS)
    return found


def test_the_entry_is_found_by_name_and_lists_the_two_expert_cells():
    entry()


def service_counters(cell):
    """The generation service's counters before and after three prompts of 9 to
    20 tokens and three steps each, for the cell's decoder at its rehearsal
    size (float32, on the CPU), and how many expert layers the decoder has."""
    import jax.numpy as jnp

    from pathway_tpu.models import lfm2, mistral4
    from pathway_tpu.models.generation_service import GenerationService

    cfg = run.resolve(cell, rehearse=True)["config"]
    if cfg["model_type"] == "lfm2_moe":
        module, decoder, model = lfm2, lfm2.Lfm2Decoder, lfm2.Lfm2Config.from_dict(cfg)
        layers = model.num_hidden_layers - model.num_dense_layers
    else:
        module, decoder, model = mistral4, mistral4.Mistral4Decoder, mistral4.Mistral4Config.from_dict(cfg)
        layers = model.num_hidden_layers
    dec = decoder(model, module.init_params(model, seed=5, dtype=jnp.float32), slots=4, max_prompt_tokens=32,
                  max_new_tokens=4, prefill_buckets=(16, 32))
    svc = GenerationService(dec)
    before = svc.stats()
    rng = np.random.default_rng(5)
    futures = [svc.submit(rng.integers(0, 1000, n).tolist()) for n in (9, 20, 14)]
    assert all(len(f.result(timeout=300)) == 4 for f in futures)
    after = svc.stats()
    svc.close()
    return {"counters_before": before, "counters_after": after}, layers


@pytest.mark.parametrize("cell", CELLS)
def test_the_reader_reads_every_expert_layer_of_every_step(cell):
    ctx, layers = service_counters(cell)
    assert ctx["counters_after"]["lm_decode_steps"] >= 3 and layers in (4, 3)
    got = run.read_metrics([entry()], ctx)
    assert got == {NAME: {"value": pytest.approx(float(layers)), "unit": "layers"}}


def test_the_reader_returns_nothing_where_the_program_has_no_such_counter():
    parent = {"lm_decode_steps": 3100.0, "lm_decode_rows": 9300.0, "lm_batched_layers": 0.0}
    assert run.read_metrics([entry()], {"counters_before": {}, "counters_after": parent}) == {}
    # and nothing where no step was made in the window
    idle = {"lm_decode_steps": 100.0, "lm_streamed_layers": 1200.0}
    assert run.read_metrics([entry()], {"counters_before": idle, "counters_after": dict(idle)}) == {}
    # a window of steps reads layers a step
    grown = {"lm_decode_steps": 400.0, "lm_streamed_layers": 4800.0}
    got = run.read_metrics([entry()], {"counters_before": idle, "counters_after": grown})
    assert got[NAME]["value"] == pytest.approx(12.0)
