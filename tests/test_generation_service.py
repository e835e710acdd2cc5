"""The slot-based generation service (``models/generation_service.py``): its
loop over a fake decoder (admission, the prompt's crop, draining, a failed
device call) and over the tiny ``lfm2_moe`` decoder of ``test_lfm2.py``, where
requests that arrive at any time, between any two steps, each get the tokens
they get alone."""

import threading
import time

import jax.numpy as jnp
import pytest

from pathway_tpu.models import lfm2
from pathway_tpu.models.generation_service import GenerationService

from .test_lfm2 import CFG, assert_greedy, decoder, prompt_of


class FakeDecoder:
    """Counts up from a prompt's last id: token i of a request is ``ids[-1] + 1 + i``."""

    slots, max_prompt_tokens, max_new_tokens = 3, 8, 4

    def __init__(self, fail_on_step=None):
        self.last = [0] * self.slots
        self.calls = []
        self.fail_on_step = fail_on_step

    def bucket_of(self, n):
        return 8

    def prefill(self, slot, ids):
        self.calls.append(("prefill", slot, list(ids)))
        self.last[slot] = ids[-1] + 1
        return self.last[slot], 2

    def decode(self, active):
        self.calls.append(("decode", [int(s) for s in active.nonzero()[0]]))
        if self.fail_on_step is not None and sum(c[0] == "decode" for c in self.calls) == self.fail_on_step:
            raise RuntimeError("the device call failed")
        for s in active.nonzero()[0]:
            self.last[s] += 1
        return list(self.last), 3 * int(active.sum())

    @staticmethod
    def compiled_programs():
        return 2


def test_more_requests_than_slots_all_resolve_and_the_counters_add_up():
    svc = GenerationService(FakeDecoder())
    futures = [svc.submit([10 * i]) for i in range(7)]
    assert [f.result(timeout=10) for f in futures] == [[10 * i + 1 + j for j in range(4)] for i in range(7)]
    st = svc.stats()
    assert st["lm_prefill_calls"] == 7 and st["lm_prefill_tokens"] == 7
    assert st["lm_prefill_padded_tokens"] == 56 and st["lm_slots"] == 3
    # every request's first token is its prefill's: three more steps each
    assert st["lm_decode_rows"] == 21 and st["lm_experts_touched"] == 63 and st["lm_prefill_experts_touched"] == 14
    assert 7 <= st["lm_decode_steps"] <= 21 and st["lm_compiled_programs"] == 2
    # a step never ran for a slot that held no request
    held = set()
    for call in svc.decoder.calls:
        if call[0] == "prefill":
            held.add(call[1])
        else:
            assert set(call[1]) <= held
    svc.close()


def test_a_long_prompt_keeps_its_last_tokens():
    svc = GenerationService(FakeDecoder())
    assert svc.submit(list(range(20))).result(timeout=10) == [20, 21, 22, 23]
    assert svc.decoder.calls[0] == ("prefill", 0, list(range(12, 20)))
    with pytest.raises(ValueError):
        svc.submit([])
    svc.close()


def test_stop_drains_the_worker_respawns_and_close_refuses():
    svc = GenerationService(FakeDecoder())
    futures = [svc.submit([100 + i]) for i in range(5)]
    svc.stop_worker(timeout_s=10)
    assert all(f.done() for f in futures) and not svc.worker_alive()
    assert svc.submit([7]).result(timeout=10) == [8, 9, 10, 11]  # a new submission starts the worker again
    svc.close(timeout_s=10)
    assert not svc.worker_alive()
    with pytest.raises(RuntimeError):
        svc.submit([1])


def test_a_failed_device_call_fails_the_requests_it_held_and_the_service_goes_on():
    decoder_ = FakeDecoder(fail_on_step=2)
    gate = threading.Event()
    prefill = decoder_.prefill
    decoder_.prefill = lambda slot, ids: (gate.wait(10), prefill(slot, ids))[1]
    svc = GenerationService(decoder_)
    first = [svc.submit([i]) for i in range(3)]
    gate.set()  # the worker waited in the first prefill: all three hold a slot by the failing step
    for f in first:
        with pytest.raises(RuntimeError, match="device call failed"):
            f.result(timeout=10)
    assert svc.submit([50]).result(timeout=10) == [51, 52, 53, 54]
    svc.close()


def test_a_cancelled_submission_takes_no_slot():
    decoder_ = FakeDecoder()
    gate = threading.Event()
    prefill = decoder_.prefill
    decoder_.prefill = lambda slot, ids: (gate.wait(10), prefill(slot, ids))[1]
    svc = GenerationService(decoder_)
    running = [svc.submit([i]) for i in range(3)]  # fill the slots; the worker blocks in the first prefill
    time.sleep(0.1)
    waiting = svc.submit([90])
    assert waiting.cancel()
    gate.set()
    assert [f.result(timeout=10)[0] for f in running] == [1, 2, 3]
    svc.close(timeout_s=10)
    assert not any(call[0] == "prefill" and call[2] == [90] for call in decoder_.calls)


def test_requests_arriving_between_steps_get_the_tokens_they_get_alone():
    params = lfm2.init_params(CFG, seed=3, dtype=jnp.float32)
    svc = GenerationService(decoder(params, slots=3, new=6))
    prompts = [prompt_of(n, seed=50 + n) for n in (4, 31, 9, 16, 2, 23, 12)]
    futures = {}

    def client(i):
        time.sleep(0.05 * i)  # while earlier requests are mid-generation
        futures[i] = svc.submit(prompts[i])

    threads = [threading.Thread(target=client, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    for i, prompt in enumerate(prompts):
        tokens = futures[i].result(timeout=120)
        assert len(tokens) == 6
        assert_greedy(params, prompt, tokens)
    st = svc.stats()
    assert st["lm_prefill_tokens"] == sum(len(p) for p in prompts) and st["lm_decode_rows"] == 7 * 5
    assert st["lm_prefill_padded_tokens"] == sum(16 if len(p) <= 16 else 32 for p in prompts)
    svc.close()
