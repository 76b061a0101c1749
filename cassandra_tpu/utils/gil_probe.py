"""The GIL hand-off probe: what ONE release-and-retake of the GIL costs
in this process, right now.

Under a convoy of Python threads a thread pays per GIL-releasing call,
not per bytecode (PERF.md, PR 30): every numpy / jax / socket call lets
the lock go and has to win it back. A span's `wall - cpu`
(pipeline_ledger.Span) says how long its thread did not run; this probe
says what one such hand-off costs, so the two can be set against each
other. One daemon thread `gil-probe` per process beats ten times a
second; a beat is one `time.sleep(0)` — release the GIL, take it back —
inside a `runtime.gil.handoff` span (kind stall), so it is a ring record
like any other, with the wall and (where the span read the thread clock)
the CPU seconds; the same seconds go to the `runtime.gil.handoff`
histogram. Alone a beat reads a
few microseconds; beside N runnable Python threads about N switch
intervals (`sys.getswitchinterval()`, 5 ms).

Lifetime is demand-counted (service/sampler.py's pattern): a
StorageEngine holds a demand from open to close(), a CompactionTask for
the length of execute(); the first demand starts the thread, the last
release stops and joins it. No knob: ten ring records and ≈ 0.1 ms of
CPU a second. jax is never imported from here.
"""
from __future__ import annotations

import logging
import threading
import time

from . import pipeline_ledger

HANDOFF_SPAN = "runtime.gil.handoff"
THREAD_NAME = "gil-probe"
BEAT_S = 0.1           # ten ring records a second, of RING_CAP's 32,768

_log = logging.getLogger(__name__)


def _beat(stop: threading.Event) -> None:
    from ..service.metrics import GLOBAL
    hist = GLOBAL.hist(HANDOFF_SPAN)
    try:
        while True:
            with pipeline_ledger.span(HANDOFF_SPAN, "stall") as sp:
                time.sleep(0)
            hist.update_us(sp.seconds * 1e6)
            if stop.wait(BEAT_S):
                return
    except Exception:      # a daemon thread must not die silently
        _log.exception("gil-probe stopped beating")


class GilProbe:
    def __init__(self):
        self._lock = threading.Lock()
        self._demands: set = set()
        self._stop: threading.Event | None = None
        self._thread: threading.Thread | None = None

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def set_demand(self, owner, on: bool) -> None:
        """Add or withdraw `owner`'s demand; the thread follows under
        the same lock (it never takes it, so the join cannot deadlock)."""
        with self._lock:
            if on:
                self._demands.add(owner)
            else:
                self._demands.discard(owner)
            if self._demands and self._thread is None:
                self._stop = threading.Event()
                self._thread = threading.Thread(
                    target=_beat, args=(self._stop,), name=THREAD_NAME,
                    daemon=True)
                self._thread.start()
            elif not self._demands and self._thread is not None:
                self._stop.set()
                self._thread.join()
                self._thread = self._stop = None


GLOBAL = GilProbe()
