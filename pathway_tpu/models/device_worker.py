"""What the device services share: one queue, one worker thread, one way to stop it.

``EncoderService`` (one submission, one tick, one answer) and
``GenerationService`` (a request held in a slot across many device calls) are
both a FIFO of submissions behind one condition variable and one lazily
spawned daemon thread that owns the device calls. This class is that skeleton:
admission appends to ``_queue`` under ``_cond`` and calls
``_ensure_worker_locked``; the subclass's ``_run`` drains the queue and returns
only through ``_exit_if_stopping_locked`` when it has nothing left to answer;
``stop_worker`` drains and joins (the worker respawns on the next submission),
``close`` does so for good. ``stop_all_workers`` is what ``pw.run``'s teardown
calls so that no finished run leaves a device-owning thread behind.
"""

from __future__ import annotations

import threading
import weakref
from collections import deque
from typing import Any

#: every live service, so teardown can stop idle workers without holding
#: references that would keep dead pipelines alive
_services: "weakref.WeakSet[DeviceWorker]" = weakref.WeakSet()


def stop_all_workers(timeout_s: float = 10.0) -> None:
    """Stop (drain + join) every live service's worker thread. Called from
    ``GraphRunner.finish``; services stay usable, the worker respawns lazily
    on the next submit."""
    for svc in list(_services):
        svc.stop_worker(timeout_s=timeout_s)


class DeviceWorker:
    """The queue, the condition and the worker thread's life. A subclass
    implements ``_run`` (the thread's loop) and its own admission."""

    _thread_name = "pathway:device-worker"

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queue: "deque[Any]" = deque()
        self._worker: threading.Thread | None = None
        self._stop_requested = False
        self._closed = False
        _services.add(self)

    def _run(self) -> None:
        raise NotImplementedError

    def _ensure_worker_locked(self) -> None:
        # _locked suffix = caller-holds-self._cond convention; the writes below
        # are therefore lock-protected even though this frame takes no lock
        if self._worker is None or not self._worker.is_alive():
            self._stop_requested = False  # noqa: PWA103 (caller holds self._cond)
            self._worker = threading.Thread(  # noqa: PWA103 (caller holds self._cond)
                target=self._run, name=self._thread_name, daemon=True
            )
            self._worker.start()

    def _exit_if_stopping_locked(self) -> bool:
        """For ``_run``, holding ``self._cond`` with nothing left to answer:
        whether the worker is to exit now. Exits only with an empty queue
        (drain semantics); a submission appended after this check respawns the
        worker from the admission path."""
        if (self._closed or self._stop_requested) and not self._queue:
            self._stop_requested = False  # noqa: PWA103 (caller holds self._cond)
            self._worker = None  # noqa: PWA103 (caller holds self._cond)
            self._cond.notify_all()
            return True
        return False

    def stop_worker(self, timeout_s: float = 10.0) -> None:
        """Drain the queue and stop the worker. The service stays usable: the
        next submit respawns it. Safe with requests in flight: every admitted
        submission is still answered before the worker exits."""
        with self._cond:
            worker = self._worker
            if worker is not None and worker.is_alive():
                self._stop_requested = True
            self._cond.notify_all()
        if worker is not None:
            worker.join(timeout=timeout_s)

    def close(self, timeout_s: float = 10.0) -> None:
        """Permanent, idempotent: drain, stop the worker, refuse new submits."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self.stop_worker(timeout_s=timeout_s)

    def worker_alive(self) -> bool:
        worker = self._worker
        return worker is not None and worker.is_alive()
