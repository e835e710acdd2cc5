"""The slot-based generation service (``models/generation_service.py``): its
loop over a fake decoder (admission, the prompt's crop, draining, a failed
device call; one call enqueued ahead of the one whose result is read; each
request's timeline, summed and as spans) and over the tiny ``lfm2_moe`` decoder
of ``test_lfm2.py``, where requests that arrive at any time, between any two
steps, each get the tokens they get alone."""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.models import lfm2
from pathway_tpu.models.generation_service import GenerationService

from .test_lfm2 import CFG, assert_greedy, decoder, prompt_of


class Lazy:
    """A call's result as a device array is: there when it is read, and the
    read is an event (``("read", call number)`` in the decoder's ``log``)."""

    def __init__(self, decoder_, call, value):
        self.decoder, self.call, self.value = decoder_, call, value

    def _read(self):
        assert self.decoder.may_read.wait(10)
        self.decoder.log.append(("read", self.call))
        if self.call == self.decoder.fail_on_read:
            raise RuntimeError("the device call failed")
        return self.value

    def __array__(self, dtype=None, copy=None):
        return np.array(self._read(), dtype=dtype)

    def __int__(self):
        return int(self._read())


class FakeDecoder:
    """Counts up from a prompt's last id: token i of a request is ``ids[-1] + 1 + i``.
    ``calls`` holds the calls; ``log`` the calls and the reads of their results, in order."""

    max_prompt_tokens = 8
    count_names = ("experts_touched",)

    def __init__(self, fail_on_step=None, fail_on_read=None, slots=3, new=4):
        self.slots, self.max_new_tokens = slots, new
        self.last = [0] * self.slots
        self.calls, self.log = [], []
        self.fail_on_step, self.fail_on_read = fail_on_step, fail_on_read
        self.may_read = threading.Event()  # a test clears it to hold a result back
        self.may_read.set()

    def bucket_of(self, n):
        return 8

    def _called(self, call, tokens, touched):
        self.calls.append(call)
        self.log.append(call)
        n = len(self.calls) - 1
        # fresh outputs: what a later call writes into the state is not seen through them
        return Lazy(self, n, tokens), Lazy(self, n, touched)

    def prefill(self, slot, ids):
        self.last[slot] = ids[-1] + 1
        return self._called(("prefill", slot, list(ids)), self.last[slot], 2)

    def decode(self, active):
        if self.fail_on_step is not None and sum(c[0] == "decode" for c in self.calls) + 1 == self.fail_on_step:
            self._called(("decode", None), None, None)
            raise RuntimeError("the device call failed")
        for s in active.nonzero()[0]:
            self.last[s] += 1
        return self._called(("decode", [int(s) for s in active.nonzero()[0]]), list(self.last), 3 * int(active.sum()))

    @staticmethod
    def compiled_programs():
        return 2


def gated(decoder_):
    """Hold the decoder's first prefill until the returned event is set, so that
    everything submitted meanwhile waits in the queue; ``decoder_.entered`` is
    set once the worker waits there."""
    gate, prefill, decoder_.entered = threading.Event(), decoder_.prefill, threading.Event()
    decoder_.prefill = lambda slot, ids: (decoder_.entered.set(), gate.wait(10), prefill(slot, ids))[2]
    return gate


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


class TwoCountDecoder(FakeDecoder):
    """A decoder that names two counts: each call returns both in one array."""

    count_names = ("pairs_held", "pairs")

    def _called(self, call, tokens, touched):
        return super()._called(call, tokens, None if touched is None else [touched, 10 * touched])


def test_a_decoder_that_names_two_counts_gets_both_summed_under_its_names_in_two_reads_a_call():
    from pathway_tpu.engine import telemetry

    before = telemetry.stage_snapshot("lm.")
    svc = GenerationService(TwoCountDecoder())
    futures = [svc.submit([10 * i]) for i in range(7)]
    assert [f.result(timeout=10) for f in futures] == [[10 * i + 1 + j for j in range(4)] for i in range(7)]
    st = svc.stats()
    assert st["lm_pairs_held"] == 63 and st["lm_pairs"] == 630  # three a row and step, as FakeDecoder counts
    assert st["lm_prefill_pairs_held"] == 14 and st["lm_prefill_pairs"] == 140
    assert "lm_experts_touched" not in st and "lm_prefill_experts_touched" not in st
    assert st["lm_prefill_calls"] == 7 and st["lm_decode_rows"] == 21
    grew = {k: v - before.get(k, 0.0) for k, v in telemetry.stage_snapshot("lm.").items()}
    assert grew["lm.pairs_held"] == 63.0 and grew["lm.pairs"] == 630.0 and not grew.get("lm.experts_touched")
    # still two transfers a call: its tokens, and one array of its counts
    log = svc.decoder.log
    assert all(sum(e == ("read", n) for e in log) == 2 for n in range(len(svc.decoder.calls)))
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
    gate = gated(decoder_)
    svc = GenerationService(decoder_)
    first = [svc.submit([i]) for i in range(3)]
    gate.set()  # the worker waited in the first prefill: all three hold a slot by the failing step
    for f in first:
        with pytest.raises(RuntimeError, match="device call failed"):
            f.result(timeout=10)
    assert svc.submit([50]).result(timeout=10) == [51, 52, 53, 54]
    svc.close()


@pytest.mark.parametrize("requests,slots,new", [(2, 3, 4), (7, 3, 4), (3, 1, 3), (5, 2, 2)])
def test_a_call_is_enqueued_before_the_call_before_it_is_read_and_never_two_before(requests, slots, new):
    decoder_ = FakeDecoder(slots=slots, new=new)
    gate = gated(decoder_)
    svc = GenerationService(decoder_)
    futures = [svc.submit([10 * i]) for i in range(requests)]
    gate.set()  # everything waits by the first call: the loop has something to enqueue until the end
    assert [f.result(timeout=10) for f in futures] == [[10 * i + 1 + j for j in range(new)] for i in range(requests)]
    svc.close()
    log, calls = decoder_.log, decoder_.calls
    assert len(calls) >= requests + new - 1 and [e for e in log if e[0] != "read"] == calls
    at = [i for i, e in enumerate(log) if e[0] != "read"]  # where call n stands in the log
    reads = [[i for i, e in enumerate(log) if e == ("read", n)] for n in range(len(calls))]
    assert all(len(r) == 2 for r in reads)  # every call's tokens and its count, once each
    for n in range(1, len(calls)):
        assert at[n] < reads[n - 1][0]  # enqueued while the call before it was unread
        assert n < 2 or reads[n - 2][-1] < at[n]  # and only one: the one before that had been read
    st = svc.stats()
    assert st["lm_prefill_calls"] + st["lm_decode_steps"] == len(calls)
    assert st["lm_requests_done"] == requests and st["lm_reply_tokens"] == requests * new


def test_an_idle_loop_starts_again_with_nothing_ahead():
    svc = GenerationService(FakeDecoder())
    for i in range(3):  # one at a time: each finds the loop idle and every result read
        assert svc.submit([i]).result(timeout=10) == [i + 1 + j for j in range(4)]
    st = svc.stats()
    assert st["lm_prefill_calls"] + st["lm_decode_steps"] == 12
    svc.close()
    log, calls = svc.decoder.log, svc.decoder.calls
    at = [i for i, e in enumerate(log) if e[0] != "read"]
    for n, call in enumerate(calls):
        if call[0] == "prefill":  # the first call after an idle loop: every call before it had been read
            assert all(log.index(("read", m)) < at[n] for m in range(n))
        else:  # a step: enqueued while the call before it was unread
            assert at[n] < log.index(("read", n - 1))
    assert sum(call[0] == "prefill" for call in calls) == 3


def test_a_slot_refilled_while_its_last_token_is_unread_gives_both_requests_their_tokens():
    decoder_ = FakeDecoder(slots=1, new=3)
    gate = gated(decoder_)
    svc = GenerationService(decoder_)
    first, second = svc.submit([10]), svc.submit([20])
    gate.set()
    assert first.result(timeout=10) == [11, 12, 13] and second.result(timeout=10) == [21, 22, 23]
    log = decoder_.log
    assert decoder_.calls[:4] == [("prefill", 0, [10]), ("decode", [0]), ("decode", [0]), ("prefill", 0, [20])]
    # the second prompt went into the slot before the first request's last step had been read
    assert log.index(("prefill", 0, [20])) < log.index(("read", 2))
    svc.close()


@pytest.mark.parametrize("slots,new,fail_on_read,fail", [(3, 4, 3, 3), (1, 2, 1, 2), (2, 3, 0, 1)])
def test_an_error_at_read_time_fails_the_requests_of_the_calls_in_flight_and_the_service_goes_on(
        slots, new, fail_on_read, fail):
    """(1, 2, 1): the failing step was the first request's last, so it has left
    its slot and only the unread call still holds it; the second request's
    prefill is unread behind it. ``fail`` requests fail at least (how many
    the worker had admitted by its first call is the timing's)."""
    decoder_ = FakeDecoder(slots=slots, new=new, fail_on_read=fail_on_read)
    gate = gated(decoder_)
    svc = GenerationService(decoder_)
    first = [svc.submit([i]) for i in range(slots + 2)]
    gate.set()
    failed = [f for f in first if isinstance(f.exception(timeout=10), RuntimeError)]
    # every request a slot or an unread call held when the read failed, and no other: one still waiting is served
    assert fail <= len(failed) < len(first) and failed == first[:len(failed)]
    assert all("device call failed" in str(f.exception()) for f in failed)
    assert all(f.result(timeout=10) == [first.index(f) + 1 + j for j in range(new)] for f in first if f not in failed)
    assert svc.submit([50]).result(timeout=10) == [51 + j for j in range(new)]
    svc.close()


def test_one_new_token_resolves_with_no_decode_step():
    svc = GenerationService(FakeDecoder(new=1))
    futures = [svc.submit([10 * i]) for i in range(5)]
    assert [f.result(timeout=10) for f in futures] == [[10 * i + 1] for i in range(5)]
    st = svc.stats()
    assert st["lm_prefill_calls"] == 5 and st["lm_decode_steps"] == 0
    # the first token is the last: no decode phase, no prefill inside a reply
    assert st["lm_requests_done"] == st["lm_reply_tokens"] == 5
    assert st["lm_decode_phase_s"] == 0.0 and st["lm_prefills_in_decode"] == 0 and st["lm_ttft_s"] > 0.0
    assert all(call[0] == "prefill" for call in svc.decoder.calls)
    svc.close()


def test_stop_with_a_call_in_flight_resolves_its_requests():
    decoder_ = FakeDecoder(slots=2, new=3)
    decoder_.may_read.clear()
    svc = GenerationService(decoder_)
    futures = [svc.submit([1]), svc.submit([5])]
    deadline = time.monotonic() + 10
    while len(decoder_.calls) < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    # both prefills enqueued, the worker waits for the first's result with the second's unread behind it
    assert len(decoder_.calls) == 2 and not any(f.done() for f in futures)
    threading.Timer(0.2, decoder_.may_read.set).start()
    svc.stop_worker(timeout_s=10)
    assert not svc.worker_alive()
    assert [f.result(timeout=0) for f in futures] == [[2, 3, 4], [6, 7, 8]]
    svc.close()


def test_a_cancelled_submission_takes_no_slot():
    decoder_ = FakeDecoder()
    gate = gated(decoder_)
    svc = GenerationService(decoder_)
    running = [svc.submit([i]) for i in range(3)]  # fill the slots; the worker blocks in the first prefill
    time.sleep(0.1)
    waiting = svc.submit([90])
    assert waiting.cancel()
    gate.set()
    assert [f.result(timeout=10)[0] for f in running] == [1, 2, 3]
    svc.close(timeout_s=10)
    assert not any(call[0] == "prefill" and call[2] == [90] for call in decoder_.calls)


def run_schedule(slots, new, later, ctx=lambda: None):
    """One request, then ``later`` more once the worker holds the first's
    prefill: the first pass admits the first alone, the next passes the others
    into whichever slots are free, in order."""
    decoder_ = FakeDecoder(slots=slots, new=new)
    gate = gated(decoder_)
    svc = GenerationService(decoder_)
    futures = [svc.submit([0], ctx=ctx())]
    assert decoder_.entered.wait(10)
    futures += [svc.submit([10 * (i + 1)], ctx=ctx()) for i in range(later)]
    gate.set()
    assert [f.result(timeout=10) for f in futures] == [[10 * i + 1 + j for j in range(new)] for i in range(later + 1)]
    svc.close()
    return svc.stats()


@pytest.mark.parametrize("slots,new,later,prefills", [
    (1, 4, 3, 0),  # one slot: a prompt is prefilled only after the reply before it has had its last step
    # two slots: B is prefilled after A's first step (A's reply holds it), C when A leaves (B's holds it)
    (2, 4, 2, 2),
    (3, 4, 2, 3),  # the first pass admits A alone, the second B and C: both inside A's reply, C's inside B's
    (2, 1, 3, 0),  # one token a reply: its prefill is its last call
])
def test_a_schedule_whose_interleaving_is_fixed_counts_its_replies_exactly(slots, new, later, prefills):
    st = run_schedule(slots, new, later)
    assert st["lm_requests_done"] == later + 1 and st["lm_reply_tokens"] == (later + 1) * new
    assert st["lm_prefills_in_decode"] == prefills
    assert min(st["lm_queue_s"], st["lm_ttft_s"], st["lm_decode_phase_s"]) >= 0.0
    assert st["lm_queue_s"] <= st["lm_ttft_s"] and (st["lm_decode_phase_s"] == 0.0) == (new == 1)


@pytest.fixture
def recording(monkeypatch):
    """The process-wide tracer records every span while the test runs."""
    from pathway_tpu.engine import tracing

    monkeypatch.setenv("PATHWAY_TRACE", "on")
    monkeypatch.setenv("PATHWAY_TRACE_SAMPLE", "1.0")
    tracing.reset_tracing()
    tracer = tracing.get_tracer()
    yield tracer
    monkeypatch.delenv("PATHWAY_TRACE")
    tracing.reset_tracing()


def test_each_reply_gets_three_spans_parented_to_its_context_and_they_add_up_to_the_sums(recording):
    from pathway_tpu.engine import tracing

    contexts = []
    unsampled = tracing.new_trace_context(sampled=False)

    def ctx():  # the fourth request's context is not sampled: it gets no span, and is counted all the same
        contexts.append(tracing.new_trace_context(sampled=True) if len(contexts) < 3 else unsampled)
        return contexts[-1]

    before = time.monotonic()
    st = run_schedule(2, 4, 3, ctx)
    after = time.monotonic()
    spans = [s for s in recording.recent_spans(limit=1 << 20) if s["kind"] in ("lm.queue", "lm.first_token", "lm.decode")]
    assert len(spans) == 9 and st["lm_requests_done"] == 4
    end = lambda s: s["ts_mono"] + s["duration_s"]
    totals, inside = [], []
    for c in contexts[:3]:
        [queue], [first], [decode] = ([s for s in spans if s["parent_id"] == c.span_id and s["kind"] == k]
                                      for k in ("lm.queue", "lm.first_token", "lm.decode"))
        assert all(s["trace_id"] == c.trace_id for s in (queue, first, decode))
        assert before <= queue["ts_mono"] and end(decode) <= after
        assert end(queue) == pytest.approx(first["ts_mono"], abs=1e-9)
        assert end(first) == pytest.approx(decode["ts_mono"], abs=1e-9)
        assert min(s["duration_s"] for s in (queue, first, decode)) >= 0.0
        assert decode["attrs"]["tokens"] == 4 and first["attrs"] == queue["attrs"] == {}
        # the wall stamp is the monotonic one moved by one offset: the three stay end to end on it
        assert queue["ts"] + queue["duration_s"] == pytest.approx(first["ts"], abs=1e-6)
        totals.append(end(decode) - queue["ts_mono"])
        inside.append(decode["attrs"]["prefills"])
    # two slots: B is prefilled inside A's reply, C (once A left) inside B's, D (once B left) inside C's
    assert inside == [1, 1, 1]
    assert st["lm_prefills_in_decode"] == 3  # and D, the last, holds none
    # the sums are the same stamps: ttft and the decode phase make up each reply's whole time (D's
    # own, which has no span, lies inside the test's)
    unseen = st["lm_ttft_s"] + st["lm_decode_phase_s"] - sum(totals)
    assert -1e-9 <= unseen <= after - before


def test_no_span_is_built_while_nothing_records(monkeypatch):
    from pathway_tpu.engine import tracing

    monkeypatch.delenv("PATHWAY_TRACE", raising=False)
    tracing.reset_tracing()

    def refuse(*args, **kwargs):
        raise AssertionError("a span was built while nothing records")

    monkeypatch.setattr(tracing, "Span", refuse)
    st = run_schedule(2, 4, 2, lambda: tracing.new_trace_context(sampled=True))
    assert st["lm_requests_done"] == 3 and st["lm_prefills_in_decode"] == 2
    assert tracing.get_tracer().recent_spans() == []


def test_a_cancelled_submission_and_a_failed_call_count_nothing():
    decoder_ = FakeDecoder(fail_on_step=2)
    gate = gated(decoder_)
    svc = GenerationService(decoder_)
    failing = [svc.submit([i]) for i in range(3)]  # all three hold a slot by the failing step
    time.sleep(0.1)
    cancelled = svc.submit([90])
    assert cancelled.cancel()
    gate.set()
    assert all(isinstance(f.exception(timeout=10), RuntimeError) for f in failing)
    st = svc.stats()
    assert (st["lm_requests_done"], st["lm_reply_tokens"], st["lm_prefills_in_decode"]) == (0, 0, 0)
    assert st["lm_queue_s"] == st["lm_ttft_s"] == st["lm_decode_phase_s"] == 0.0
    assert svc.submit([50]).result(timeout=10) == [51, 52, 53, 54]
    st = svc.stats()
    assert (st["lm_requests_done"], st["lm_reply_tokens"], st["lm_prefills_in_decode"]) == (1, 4, 0)
    svc.close()


@pytest.mark.parametrize("slots,gap_s", [(3, 0.05), (1, 0.0)])
def test_requests_arriving_between_steps_get_the_tokens_they_get_alone(slots, gap_s):
    """One slot and no gap: every prompt but the first goes into the slot while
    the last token of the request before it is still unread on the device."""
    params = lfm2.init_params(CFG, seed=3, dtype=jnp.float32)
    svc = GenerationService(decoder(params, slots=slots, new=6))
    prompts = [prompt_of(n, seed=50 + n) for n in (4, 31, 9, 16, 2, 23, 12)]
    futures = {}

    def client(i):
        time.sleep(gap_s * i)  # while earlier requests are mid-generation
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


@pytest.mark.parametrize("family", ["lfm2", "mistral4"])
def test_the_layers_whose_product_ran_batched_are_counted_for_prefills_and_stay_zero_over_steps(family):
    """Both decoders name ``batched_layers`` and ``streamed_layers``; the service
    sums them as it sums any count. A bucket at the floor of sixteen rows an
    expert runs every expert layer's product batched (four layers here, three
    there); a smaller bucket and every step run the streamed kernel instead."""
    from pathway_tpu.engine import telemetry

    if family == "lfm2":
        dec = decoder(lfm2.init_params(CFG, seed=3, dtype=jnp.float32), buckets=(16, 64), new=4)
        large, layers = 40, 4
    else:
        from . import test_mistral4 as m4

        whole = m4.mistral4.init_params(m4.WHOLE, seed=3, dtype=jnp.float32)
        dec = m4.decoder(m4.share_of(whole, m4.CFG), buckets=(16, 128), new=4)
        large, layers = 100, 3
    assert dec.count_names[-2:] == ("batched_layers", "streamed_layers")
    before = telemetry.stage_snapshot("lm.")
    svc = GenerationService(dec)
    futures = [svc.submit(prompt_of(n, seed=60 + n, vocab=1024)) for n in (large, 9, large + 5)]
    assert all(len(f.result(timeout=120)) == 4 for f in futures)
    st = svc.stats()
    assert st["lm_prefill_calls"] == 3 and st["lm_decode_rows"] == 9
    assert st["lm_prefill_batched_layers"] == 2 * layers and st["lm_batched_layers"] == 0
    # the 9-token prompt's 16 bucket streams in every layer, and so does every step
    assert st["lm_prefill_streamed_layers"] == layers and st["lm_streamed_layers"] == layers * st["lm_decode_steps"]
    grew = {k: v - before.get(k, 0.0) for k, v in telemetry.stage_snapshot("lm.").items()}
    assert grew["lm.decode_rows"] == 9.0 and grew["lm.batched_layers"] == 0.0
    assert grew["lm.streamed_layers"] == float(layers * st["lm_decode_steps"])
    svc.close()


def test_a_state_space_decoders_state_rows_are_summed_for_prefills_and_grow_by_live_rows_x_layers_a_step():
    """The third decoder names one count, ``state_rows``: the (slot, layer)
    state-space states a call read and rewrote. The service sums it as it sums
    any count: a prefill's as ``lm_prefill_state_rows`` (one a layer), a step's
    as ``lm_state_rows`` (the live slots' in every layer: the step's kernel
    moves no other), the latter also among the ``lm.*`` stage counters."""
    from pathway_tpu.engine import telemetry

    from . import test_falcon_h1 as fh

    dec = fh.decoder(fh.falcon_h1.init_params(fh.CFG, seed=3, dtype=jnp.float32), slots=4, new=4)
    assert dec.count_names == ("state_rows",)
    before = telemetry.stage_snapshot("lm.")
    svc = GenerationService(dec)
    futures = [svc.submit(prompt_of(n, seed=70 + n, vocab=4096)) for n in (9, 20)]
    assert all(len(f.result(timeout=120)) == 4 for f in futures)
    st = svc.stats()
    assert st["lm_prefill_calls"] == 2 and st["lm_prefill_state_rows"] == 2 * 3
    assert st["lm_decode_steps"] >= 3 and st["lm_state_rows"] == st["lm_decode_rows"] * 3
    grew = {k: v - before.get(k, 0.0) for k, v in telemetry.stage_snapshot("lm.").items()}
    assert grew["lm.state_rows"] == float(st["lm_state_rows"])
    svc.close()
