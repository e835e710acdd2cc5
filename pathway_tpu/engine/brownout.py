"""Overload brownout ladder for the serving plane.

Below the autoscaler's scaling rung (``parallel/autoscaler.py``) sits a
cheaper defense: when the embed admission queue saturates, the serving plane
degrades GRACEFULLY — admission caps tighten and retrieval gets cheaper —
*before* a reshard pause is spent. "Shed first, scale second, recover always":
the autoscaler only escalates to a membership transition once the brownout
rungs have been engaged and load still exceeds capacity.

Rungs (driven by embed-queue occupancy, the fraction of
``max_queue_rows`` currently waiting/in flight):

====  ==================  =============================================
rung  engages at           degradation
====  ==================  =============================================
0     —                   none (normal serving)
1     occupancy >= 0.60   REST admission cap x0.5
2     occupancy >= 0.85   REST admission cap x0.25, IVF ``n_probe``
                          halved (recall traded for latency — serving
                          stays up)
====  ==================  =============================================

Rungs RELEASE with hysteresis: occupancy must stay below ~70% of the engage
threshold for ``hold_s`` seconds before a rung disengages, so a queue
oscillating around a threshold does not flap the ladder. Every engage/release
bumps ``brownout.engage``/``brownout.release`` stage counters and lands a
``brownout`` flight-recorder event, so post-mortems show the ladder's history
next to the commit timeline.

The **quiesce window** rides the same registry: while a membership transition
pauses the commit loop (``GraphRunner._run_membership_transition``), the REST
plane must serve 429 + an honest ``Retry-After`` (the expected remaining
pause) instead of letting clients hang on a paused engine —
:meth:`BrownoutState.enter_quiesce` / :meth:`~BrownoutState.exit_quiesce`
bracket the window and ``rest_connector`` consults
:meth:`~BrownoutState.quiesce_retry_after` pre-admission.

``PATHWAY_BROWNOUT=off`` disables the ladder entirely (level stays 0, the
quiesce window still sheds — a paused engine hangs clients regardless of the
ladder). Process-wide singleton via :func:`get_brownout`;
:func:`reset_brownout` rebuilds (tests).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, Optional

# (engage_occupancy, admission_scale, nprobe_shift) per rung, rung 0 implicit
_RUNGS = (
    (0.60, 0.5, 0),
    (0.85, 0.25, 1),
)
# occupancy must stay below engage * _RELEASE_RATIO for hold_s to disengage
_RELEASE_RATIO = 0.7


def retry_after_int(seconds: float) -> str:
    """RFC-9110 ``Retry-After`` value: a base-10 NON-NEGATIVE INTEGER of
    seconds (no float, no sign, no units). One home for every shed path —
    REST overload, quiesce, and the replica staleness shed all format
    through here, so the header stays parseable by strict clients. Rounds
    UP (a client told to wait 0.3 s that retries at 0 s hammers the very
    queue the shed protects) with a floor of 1."""
    try:
        value = float(seconds)
    except (TypeError, ValueError):
        value = 1.0
    if value != value or value < 0:  # NaN / negative: shed "momentarily"
        value = 1.0
    value = min(value, 3600.0)  # a shed is a backoff hint, not a ban
    return str(max(1, int(-(-value // 1))))


class BrownoutState:
    """Thread-safe overload-degradation ladder (see module docstring)."""

    def __init__(self, *, enabled: "bool | None" = None, hold_s: float = 1.0):
        if enabled is None:
            enabled = os.environ.get("PATHWAY_BROWNOUT", "on").lower() not in (
                "off", "0", "false", "no",
            )
        self.enabled = bool(enabled)
        self.hold_s = float(hold_s)
        self._lock = threading.Lock()
        self._level = 0
        # per-rung: the last time occupancy was ABOVE the rung's release
        # threshold (hysteresis clock; 0.0 = never)
        self._last_above = [0.0] * len(_RUNGS)
        self._engages = 0
        self._releases = 0
        # quiesce window: (entered_monotonic, expected_duration_s) while a
        # membership transition has the commit loop paused
        self._quiesce: "Optional[tuple]" = None

    # -- ladder ----------------------------------------------------------------

    def observe_occupancy(self, frac: float, now: "float | None" = None) -> int:
        """Feed one embed-queue occupancy sample (0..1+); returns the level
        after the update. Called from the admission path — cheap, one lock."""
        if not self.enabled:
            return 0
        if now is None:
            now = time.monotonic()
        frac = max(0.0, float(frac))
        events = []
        with self._lock:
            old = self._level
            for i, (engage, _adm, _np) in enumerate(_RUNGS):
                if frac >= engage * _RELEASE_RATIO:
                    self._last_above[i] = now
            # engage the deepest rung whose threshold the sample crosses
            level = self._level
            for i, (engage, _adm, _np) in enumerate(_RUNGS):
                if frac >= engage:
                    level = max(level, i + 1)
            # release any rung that stayed quiet for hold_s
            while level > 0:
                i = level - 1
                if (
                    frac < _RUNGS[i][0]
                    and now - self._last_above[i] >= self.hold_s
                ):
                    level -= 1
                else:
                    break
            self._level = level
            if level > old:
                self._engages += level - old
                events.append(("engage", old, level, frac))
            elif level < old:
                self._releases += old - level
                events.append(("release", old, level, frac))
        for kind, frm, to, occ in events:
            self._emit(kind, frm, to, occ)
        return self._level

    def _emit(self, kind: str, from_level: int, to_level: int, occupancy: float) -> None:
        # deferred imports: this module sits under the serving hot path and
        # must stay light at module load
        try:
            from pathway_tpu.engine import telemetry

            telemetry.stage_add(f"brownout.{kind}")
        except Exception:
            pass
        try:
            from pathway_tpu.engine.profile import get_flight_recorder

            get_flight_recorder().record_event(
                "brownout",
                action=kind,
                from_level=from_level,
                to_level=to_level,
                occupancy=round(float(occupancy), 3),
            )
        except Exception:
            pass

    def level(self) -> int:
        with self._lock:
            return self._level

    def admission_scale(self) -> float:
        """Multiplier on the REST ``max_pending`` admission cap (1.0 at
        rung 0)."""
        with self._lock:
            level = self._level
        return _RUNGS[level - 1][1] if level > 0 else 1.0

    def nprobe_shift(self) -> int:
        """Right-shift applied to IVF ``n_probe`` at query time (rung 2:
        half the probes — recall degrades honestly instead of the queue
        growing without bound)."""
        with self._lock:
            level = self._level
        return _RUNGS[level - 1][2] if level > 0 else 0

    # -- quiesce window (membership transition) --------------------------------

    def enter_quiesce(self, expected_s: float = 1.0) -> None:
        """A membership transition paused the commit loop: REST requests
        admitted now would hang until C+1 — shed them instead (429 with the
        expected remaining pause as Retry-After). Active regardless of the
        ladder's enable gate."""
        with self._lock:
            self._quiesce = (time.monotonic(), max(0.1, float(expected_s)))
        try:
            from pathway_tpu.engine import telemetry

            telemetry.stage_add("brownout.quiesce_enter")
        except Exception:
            pass

    def exit_quiesce(self) -> None:
        with self._lock:
            self._quiesce = None

    def quiesce_retry_after(self) -> "Optional[float]":
        """Remaining expected pause in seconds while quiesced, else None."""
        with self._lock:
            quiesce = self._quiesce
        if quiesce is None:
            return None
        entered, expected = quiesce
        return max(0.5, expected - (time.monotonic() - entered))

    # -- reporting -------------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "level": self._level,
                "engages": self._engages,
                "releases": self._releases,
                "quiesced": self._quiesce is not None,
                "enabled": self.enabled,
            }


_brownout: "Optional[BrownoutState]" = None
_brownout_lock = threading.Lock()


def get_brownout() -> BrownoutState:
    """The process-wide brownout ladder (built once from the env)."""
    global _brownout
    with _brownout_lock:
        if _brownout is None:
            _brownout = BrownoutState()
        return _brownout


def reset_brownout() -> None:
    """Drop the singleton so the next :func:`get_brownout` re-reads the env."""
    global _brownout
    with _brownout_lock:
        _brownout = None
