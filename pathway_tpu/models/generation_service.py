"""Slot-based generation service: requests held on the device across many calls.

``EncoderService`` answers a submission in one tick. A generated reply needs a
prefill and then one device call per token, with its keys, values and
convolution tails kept on the device in between. This service is that loop,
on a thread of its own (``DeviceWorker`` gives the queue, the thread and the
way to stop it; the encoder service is the sibling that shares it):

    submit(ids) -> concurrent.futures.Future of max_new_tokens token ids

    loop:  admit waiting prompts into free slots, one prefill each
           one decode step over all slots, for the slots that hold a request
           after each call is enqueued: fetch the call BEFORE it, hand its
           tokens to their requests, resolve each that has its max_new_tokens

**One call ahead, fetched one call behind.** Nothing the loop does next
depends on a token's value: the slot state never leaves the device (the two
programs write ``last`` themselves), a request gets a fixed number of tokens,
and which slots the next step advances follows from how many tokens each
request has been *given* (its prefill is one, each step it was active in one
more), which the host counts without reading any. So the loop enqueues call
N+1 and only then reads call N's result: the device always has its next
program queued and the host's work a step hides behind it. ``_AHEAD`` (one)
is how many calls may stand unread once a call has been enqueued: the host's
work a step is well under the shortest device step, so one keeps the device
fed, and every further call ahead would put one more step before an arrival's
prefill. A request that has been given its last token leaves its slot at
once, its tokens still unread: a prefill enqueued after that step runs after
it on the device (one thread enqueues, the runtime keeps the order), and a
call's token arrays are fresh outputs that the donated state does not alias.
When no slot holds a request the loop reads what is still unread at once, so
the last token of the last request waits for nothing, and the worker never
parks or exits with a call unread. A device error surfaces where the result
is read, one call late: every request that any unread call or any slot holds
fails with it, the unread calls are dropped, and the service goes on.

It takes submissions at any time, between any two steps: nothing here knows of
the engine's commits. Greedy only, a fixed number of tokens a request, no stop
token, no sampling, no prefix reuse, no paging. A prompt longer than the
decoder's ``max_prompt_tokens`` keeps its last tokens.

A sibling of ``EncoderService`` and not a tick kind of it: an encoder tick
packs whatever waits into one stateless forward and forgets it, while a slot
outlives hundreds of device calls and admission depends on which slots are
free. The two share the skeleton (``models/device_worker.py``) and nothing of
each other's loop.

Spans (while something records): one ``lm.prefill`` a prompt (a child of the
request's ``generate`` span where the submitter had one) and one
``lm.decode_step`` a step (linking the requests it advances), each around the
call it enqueues, with one ``.device_wait`` child around the fetch made inside
it, which is the wait for the call *before* the span's own (nothing, for the
first call after an idle loop). Counters: ``stats()`` and the ``lm.*`` stage
counters on ``/metrics``, each moved when its call's result is read. **What a
call counts on the device is the decoder's to name**: it returns, beside its
tokens, one array of as many numbers as its ``count_names`` has (experts
touched, for one), and the service sums each under that name, a step's as
``lm_<name>`` and a prefill's as ``lm_prefill_<name>``: two transfers a call
whatever the decoder counts, and no model's name here.

**One request's timeline.** A request is stamped on ``time.monotonic`` where
its work happens: ``submitted`` in ``submit`` (the caller's thread),
``slotted`` when the loop moves it into a slot, ``first`` when its prefill's
token is read on the host, and its last token's read. It also notes how many
other prompts' prefills were enqueued between its own prefill and its last
step: each stalled every live slot, its own among them. Once its future is
resolved (the reply waits for none of this) the service adds the intervals
between the stamps to sums over the requests done, in ``stats()``
(``lm_requests_done``, ``lm_reply_tokens``, ``lm_queue_s``, ``lm_ttft_s``,
``lm_decode_phase_s``, ``lm_prefills_in_decode``), and while something
records and the request's context is sampled it synthesizes three children of
that context from the same stamps: ``lm.queue`` (submitted to slotted),
``lm.first_token`` (slotted to first) and ``lm.decode`` (first to last, with
``tokens`` and ``prefills``). A request that fails or is cancelled is counted
nowhere.
"""

from __future__ import annotations

import concurrent.futures
import logging
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import numpy as np

from pathway_tpu.engine import telemetry
from pathway_tpu.engine import tracing as _tracing
from pathway_tpu.models.device_worker import DeviceWorker

#: device calls that may stand unread once a call has been enqueued: one keeps the
#: device fed, and each further one would put a step more before an arrival's prefill
_AHEAD = 1


class _Request:
    __slots__ = ("ids", "future", "ctx", "tokens", "given", "submitted", "slotted", "first",
                 "prefills_at", "prefills_inside")

    def __init__(self, ids: List[int], ctx: Optional[_tracing.TraceContext]):
        self.ids = ids
        self.future: "concurrent.futures.Future[List[int]]" = concurrent.futures.Future()
        self.ctx = ctx
        self.tokens: List[int] = []  # read from the device
        self.given = 0  # produced by the calls enqueued so far, read or not
        # its timeline on time.monotonic: submitted, moved into a slot, its first token read
        self.submitted = time.monotonic()
        self.slotted = self.first = 0.0
        # the service's prefills enqueued once its own was; other prompts' until its last step
        self.prefills_at = self.prefills_inside = 0


class _Call:
    """A device call whose result the host has not read: its tokens (one a
    slot from a step, one alone from a prefill) and the decoder's counts, still on
    the device; the (slot, request) pairs the tokens belong to; the prompt's
    (tokens, padded tokens) for a prefill, None for a step."""

    __slots__ = ("tokens", "counts", "rows", "prompt")

    def __init__(self, result: Tuple[Any, Any], rows: List[Tuple[int, _Request]],
                 prompt: Optional[Tuple[int, int]]):
        self.tokens, self.counts = result
        self.rows = rows
        self.prompt = prompt


class GenerationService(DeviceWorker):
    """``decoder`` is the device side (a ``models/slot_decoder.SlotDecoder``): it
    gives ``slots``, ``max_prompt_tokens``, ``max_new_tokens``, ``count_names``,
    ``bucket_of``, ``prefill(slot, ids)``, ``decode(active)`` and
    ``compiled_programs()``; ``prefill`` and ``decode`` enqueue and return
    (tokens, counts) as device arrays without waiting. Only this service's
    thread calls it."""

    _thread_name = "pathway:lm-worker"
    _IDLE_WAIT_S = 0.05  # bounds how long a lost wakeup could park the loop (the wait stays abortable)

    def __init__(self, decoder: Any):
        super().__init__()
        self.decoder = decoder
        # only the worker's thread fills and frees slots and touches the unread calls
        self._slots: List[Optional[_Request]] = [None] * int(decoder.slots)
        self._unread: Deque[_Call] = deque()
        self.prefill_calls = 0
        self.prefill_tokens = 0
        self.prefill_padded_tokens = 0
        self.decode_steps = 0
        self.decode_rows = 0
        # sums over the requests resolved: their timelines' spans, tokens and prefills inside
        self.requests_done = 0
        self.reply_tokens = 0
        self.queue_s = self.ttft_s = self.decode_phase_s = 0.0
        self.prefills_in_decode = 0
        self._prefills_enqueued = 0  # only the worker's thread reads or writes it
        # the decoder's own per-call counts, summed: a prefill's and a step's apart
        self.prefill_counts = np.zeros((len(decoder.count_names),), np.int64)
        self.decode_counts = np.zeros((len(decoder.count_names),), np.int64)

    # -- admission -------------------------------------------------------------

    def submit(self, ids: Sequence[int], *,
               ctx: Optional[_tracing.TraceContext] = None) -> "concurrent.futures.Future[List[int]]":
        """Queue one prompt; the future resolves to its ``max_new_tokens`` greedy
        tokens. ``ctx`` parents the request's ``lm.prefill`` span (the caller's
        current span where none is given)."""
        ids = [int(t) for t in ids][-self.decoder.max_prompt_tokens :]
        if not ids:
            raise ValueError("an empty prompt")
        request = _Request(ids, ctx if ctx is not None else _tracing.current_context())
        with self._cond:
            if self._closed:
                raise RuntimeError("GenerationService is closed")
            self._queue.append(request)
            self._ensure_worker_locked()
            self._cond.notify_all()
        return request.future

    # -- worker ----------------------------------------------------------------

    def _admit(self) -> Optional[List[int]]:
        """Wait until there is something to do (a prompt to admit, a slot to
        advance, a call to read); move waiting requests into free slots.
        Returns the slots just filled, or None when the worker is to exit."""
        with self._cond:
            while not self._queue and not any(self._slots) and not self._unread:
                if self._exit_if_stopping_locked():
                    return None
                self._cond.wait(timeout=self._IDLE_WAIT_S)
            filled = []
            for slot, held in enumerate(self._slots):
                while held is None and self._queue:
                    request = self._queue.popleft()
                    # from here on the future cannot be cancelled under the loop's feet
                    if request.future.set_running_or_notify_cancel():
                        request.slotted = time.monotonic()
                        self._slots[slot] = held = request
                        filled.append(slot)
            return filled

    def _enqueued(self, result: Tuple[Any, Any], rows: List[Tuple[int, _Request]], wait_kind: str,
                  prompt: Optional[Tuple[int, int]] = None) -> Optional[Tuple[_Call, Any, Any]]:
        """A call has just been enqueued for ``rows``: count its token as given
        to each request (one given its last leaves its slot, which the next
        prefill may overwrite), then fetch the call before it (for the caller
        to hand out once its span is closed)."""
        self._unread.append(_Call(result, rows, prompt))
        for slot, request in rows:
            request.given += 1
            if request.given >= self.decoder.max_new_tokens:
                self._slots[slot] = None  # noqa: PWA103 (only the worker's thread reads or writes a slot)
                request.prefills_inside = self._prefills_enqueued - request.prefills_at
        with _tracing.get_tracer().trace_span(wait_kind):
            return self._fetch(keep=_AHEAD)

    def _fetch(self, keep: int) -> Optional[Tuple[_Call, Any, Any]]:
        """Wait for the oldest unread call and bring its result to the host;
        None where no more than ``keep`` calls are unread."""
        if len(self._unread) <= keep:
            return None
        call = self._unread[0]  # stays listed until it is read: a failed read fails its requests
        # a prefill's one token stands for every slot, so a row's slot indexes either kind
        tokens = np.broadcast_to(np.asarray(call.tokens), (len(self._slots),))
        counts = np.asarray(call.counts, np.int64).reshape(-1)
        self._unread.popleft()
        return call, tokens, counts

    def _hand_out(self, fetched: Optional[Tuple[_Call, Any, Any]]) -> None:
        """Give a fetched call's tokens to their requests, move its counters,
        resolve every request that now holds all its tokens."""
        if fetched is None:
            return
        call, tokens, counts = fetched
        for slot, request in call.rows:
            request.tokens.append(int(tokens[slot]))
        with self._cond:
            if call.prompt is not None:
                n, padded = call.prompt
                self.prefill_calls += 1
                self.prefill_tokens += n
                self.prefill_padded_tokens += padded
                self.prefill_counts += counts
                stage = {"lm.prefill_calls": 1.0, "lm.prefill_tokens": float(n),
                         "lm.prefill_padded_tokens": float(padded)}
            else:
                self.decode_steps += 1
                self.decode_rows += len(call.rows)
                self.decode_counts += counts
                stage = {"lm.decode_steps": 1.0, "lm.decode_rows": float(len(call.rows))}
                stage.update(("lm." + name, float(n)) for name, n in zip(self.decoder.count_names, counts))
        telemetry.stage_add_many(stage)
        done = [request for _, request in call.rows if len(request.tokens) >= self.decoder.max_new_tokens]
        if call.prompt is None and not done:
            return
        now = time.monotonic()
        if call.prompt is not None:
            call.rows[0][1].first = now
        for request in done:
            request.future.set_result(request.tokens)
        if done:
            self._account(done, now)

    def _account(self, done: List[_Request], last: float) -> None:
        """Add the timelines of requests just resolved (their last token read at
        ``last``) to the sums, and give each sampled one its three spans."""
        with self._cond:
            self.requests_done += len(done)
            for r in done:
                self.reply_tokens += len(r.tokens)
                self.queue_s += r.slotted - r.submitted
                self.ttft_s += r.first - r.submitted
                self.decode_phase_s += last - r.first
                self.prefills_in_decode += r.prefills_inside
        tracer = _tracing.get_tracer()
        if not tracer.recording():
            return
        wall = time.time() - time.monotonic()  # the ring's spans start on the wall clock
        for r in done:
            if r.ctx is None or not r.ctx.sampled:
                continue
            for kind, lo, hi, attrs in (
                    ("lm.queue", r.submitted, r.slotted, None),
                    ("lm.first_token", r.slotted, r.first, None),
                    ("lm.decode", r.first, last, {"tokens": len(r.tokens), "prefills": r.prefills_inside})):
                tracer.record_span(kind, kind, parent=r.ctx, ts=lo + wall, ts_mono=lo, duration_s=hi - lo,
                                   attrs=attrs)

    def _prefill(self, slot: int) -> None:
        request = self._slots[slot]
        n = len(request.ids)
        with _tracing.get_tracer().trace_span("lm.prefill", ctx=request.ctx, attrs={"slot": slot, "tokens": n}):
            result = self.decoder.prefill(slot, request.ids)
            self._prefills_enqueued += 1
            request.prefills_at = self._prefills_enqueued
            fetched = self._enqueued(result, [(slot, request)], "lm.prefill.device_wait",
                                     prompt=(n, self.decoder.bucket_of(n)))
        self._hand_out(fetched)

    def _decode_step(self) -> None:
        rows = [(slot, held) for slot, held in enumerate(self._slots) if held is not None]
        mask = np.array([held is not None for held in self._slots], bool)
        links = tuple(r.ctx for _, r in rows if r.ctx is not None)
        with _tracing.get_tracer().trace_span("lm.decode_step", links=links, attrs={"rows": len(rows)}) as span:
            if span is not None and any(link.sampled for link in links):
                span.sampled = True
            fetched = self._enqueued(self.decoder.decode(mask), rows, "lm.decode_step.device_wait")
        self._hand_out(fetched)

    def _fail(self, error: BaseException) -> None:
        """Fail every request an unread call or a slot holds, and forget them."""
        held = {id(r): r for call in self._unread for _, r in call.rows}
        held.update((id(r), r) for r in self._slots if r is not None)
        self._unread.clear()
        self._slots[:] = [None] * len(self._slots)  # noqa: PWA103 (only the worker's thread reads or writes a slot)
        for request in held.values():
            request.future.set_exception(error)

    def _run(self) -> None:
        while True:
            filled = self._admit()
            if filled is None:
                return
            try:
                for slot in filled:
                    self._prefill(slot)
                if any(self._slots):
                    self._decode_step()
                else:  # nothing to enqueue: the last tokens wait for no further call
                    while self._unread:
                        self._hand_out(self._fetch(keep=0))
            except Exception as exc:  # a failed device call fails every request it could have touched
                logging.getLogger(__name__).exception("generation step failed")
                self._fail(exc)

    # -- reporting -------------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        with self._cond:
            names = self.decoder.count_names
            return {
                "lm_prefill_calls": self.prefill_calls,
                "lm_prefill_tokens": self.prefill_tokens,
                "lm_prefill_padded_tokens": self.prefill_padded_tokens,
                **{"lm_prefill_" + name: int(n) for name, n in zip(names, self.prefill_counts)},
                "lm_decode_steps": self.decode_steps,
                "lm_decode_rows": self.decode_rows,
                **{"lm_" + name: int(n) for name, n in zip(names, self.decode_counts)},
                "lm_requests_done": self.requests_done,
                "lm_reply_tokens": self.reply_tokens,
                "lm_queue_s": self.queue_s,
                "lm_ttft_s": self.ttft_s,
                "lm_decode_phase_s": self.decode_phase_s,
                "lm_prefills_in_decode": self.prefills_in_decode,
                "lm_slots": len(self._slots),
                "lm_compiled_programs": self.decoder.compiled_programs(),
            }
