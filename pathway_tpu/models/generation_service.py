"""Slot-based generation service: requests held on the device across many calls.

``EncoderService`` answers a submission in one tick. A generated reply needs a
prefill and then one device call per token, with its keys, values and
convolution tails kept on the device in between. This service is that loop,
on a thread of its own (``DeviceWorker`` gives the queue, the thread and the
way to stop it; the encoder service is the sibling that shares it):

    submit(ids) -> concurrent.futures.Future of max_new_tokens token ids

    loop:  admit waiting prompts into free slots, one prefill each
           one decode step over all slots, for the slots that hold a request
           resolve each request that has its max_new_tokens, free its slot

It takes submissions at any time, between any two steps: nothing here knows of
the engine's commits. Greedy only, a fixed number of tokens a request, no stop
token, no sampling, no prefix reuse, no paging. A prompt longer than the
decoder's ``max_prompt_tokens`` keeps its last tokens.

A sibling of ``EncoderService`` and not a tick kind of it: an encoder tick
packs whatever waits into one stateless forward and forgets it, while a slot
outlives hundreds of device calls and admission depends on which slots are
free. The two share the skeleton (``models/device_worker.py``) and nothing of
each other's loop.

Spans (while something records): ``lm.prefill`` (a child of the request's
``generate`` span where the submitter had one) and ``lm.decode_step`` (linking
the requests it advanced), each with a ``.device_wait`` child around the fetch
that blocks on the device. Counters: ``stats()`` and the ``lm.*`` stage counters
on ``/metrics``.
"""

from __future__ import annotations

import concurrent.futures
import logging
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from pathway_tpu.engine import telemetry
from pathway_tpu.engine import tracing as _tracing
from pathway_tpu.models.device_worker import DeviceWorker


class _Request:
    __slots__ = ("ids", "future", "ctx", "tokens")

    def __init__(self, ids: List[int], ctx: Optional[_tracing.TraceContext]):
        self.ids = ids
        self.future: "concurrent.futures.Future[List[int]]" = concurrent.futures.Future()
        self.ctx = ctx
        self.tokens: List[int] = []


class GenerationService(DeviceWorker):
    """``decoder`` is the device side (``models/lfm2.Lfm2Decoder``): it gives
    ``slots``, ``max_prompt_tokens``, ``max_new_tokens``, ``bucket_of``,
    ``prefill(slot, ids)``, ``decode(active)`` and ``compiled_programs()``.
    Only this service's thread calls it."""

    _thread_name = "pathway:lm-worker"
    _IDLE_WAIT_S = 0.05  # bounds how long a lost wakeup could park the loop (the wait stays abortable)

    def __init__(self, decoder: Any):
        super().__init__()
        self.decoder = decoder
        self._slots: List[Optional[_Request]] = [None] * int(decoder.slots)
        self.prefill_calls = 0
        self.prefill_tokens = 0
        self.prefill_padded_tokens = 0
        self.prefill_experts_touched = 0
        self.decode_steps = 0
        self.decode_rows = 0
        self.experts_touched = 0

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
        """Wait until there is something to do; move waiting requests into free
        slots. Returns the slots just filled, or None when the worker is to exit."""
        with self._cond:
            while not self._queue and not any(self._slots):
                if self._exit_if_stopping_locked():
                    return None
                self._cond.wait(timeout=self._IDLE_WAIT_S)
            filled = []
            for slot, held in enumerate(self._slots):
                while held is None and self._queue:
                    request = self._queue.popleft()
                    # from here on the future cannot be cancelled under the loop's feet
                    if request.future.set_running_or_notify_cancel():
                        self._slots[slot] = held = request
                        filled.append(slot)
            return filled

    def _release(self, done: Dict[int, Optional[BaseException]]) -> None:
        """Free the slots in ``done`` and resolve their requests (an exception
        fails them). The next prefill into a freed slot overwrites its state."""
        with self._cond:
            requests = [(self._slots[slot], error) for slot, error in done.items()]
            for slot in done:
                self._slots[slot] = None
            self._cond.notify_all()
        for request, error in requests:
            if error is not None:
                request.future.set_exception(error)
            else:
                request.future.set_result(request.tokens)

    def _prefill(self, slot: int) -> None:
        request = self._slots[slot]
        tracer = _tracing.get_tracer()
        n = len(request.ids)
        with tracer.trace_span("lm.prefill", ctx=request.ctx, attrs={"slot": slot, "tokens": n}):
            token, touched = self.decoder.prefill(slot, request.ids)
            with tracer.trace_span("lm.prefill.device_wait"):
                token, touched = int(token), int(touched)
        request.tokens.append(token)
        padded = self.decoder.bucket_of(n)
        with self._cond:
            self.prefill_calls += 1
            self.prefill_tokens += n
            self.prefill_padded_tokens += padded
            self.prefill_experts_touched += touched
        telemetry.stage_add_many({"lm.prefill_calls": 1.0, "lm.prefill_tokens": float(n),
                                  "lm.prefill_padded_tokens": float(padded)})

    def _decode_step(self, active: List[int]) -> None:
        tracer = _tracing.get_tracer()
        mask = np.zeros((len(self._slots),), bool)
        mask[active] = True
        links = tuple(r.ctx for r in (self._slots[s] for s in active) if r.ctx is not None)
        with tracer.trace_span("lm.decode_step", links=links, attrs={"rows": len(active)}) as span:
            if span is not None and any(link.sampled for link in links):
                span.sampled = True
            tokens, touched = self.decoder.decode(mask)
            with tracer.trace_span("lm.decode_step.device_wait"):
                tokens, touched = np.asarray(tokens), int(touched)
        for slot in active:
            self._slots[slot].tokens.append(int(tokens[slot]))
        with self._cond:
            self.decode_steps += 1
            self.decode_rows += len(active)
            self.experts_touched += touched
        telemetry.stage_add_many({"lm.decode_steps": 1.0, "lm.decode_rows": float(len(active)),
                                  "lm.experts_touched": float(touched)})

    def _run(self) -> None:
        want = int(self.decoder.max_new_tokens)
        while True:
            filled = self._admit()
            if filled is None:
                return
            try:
                for slot in filled:
                    self._prefill(slot)
                # only this thread fills and frees slots, so it may read them unlocked
                active = [s for s, r in enumerate(self._slots) if r is not None and len(r.tokens) < want]
                if active:
                    self._decode_step(active)
            except Exception as exc:  # a failed device call fails every request it could have touched
                logging.getLogger(__name__).exception("generation step failed")
                self._release({s: exc for s, r in enumerate(self._slots) if r is not None})
                continue
            self._release({s: None for s, r in enumerate(self._slots) if r is not None and len(r.tokens) >= want})

    # -- reporting -------------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        with self._cond:
            return {
                "lm_prefill_calls": self.prefill_calls,
                "lm_prefill_tokens": self.prefill_tokens,
                "lm_prefill_padded_tokens": self.prefill_padded_tokens,
                "lm_prefill_experts_touched": self.prefill_experts_touched,
                "lm_decode_steps": self.decode_steps,
                "lm_decode_rows": self.decode_rows,
                "lm_experts_touched": self.experts_touched,
                "lm_slots": len(self._slots),
                "lm_compiled_programs": self.decoder.compiled_programs(),
            }
