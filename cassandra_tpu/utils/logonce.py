"""Log a fallback's cause the first time it happens.

The device programs fall back per segment / per round to host code that
gives the same bytes, and they count it. A counter alone does not say
WHY: a compile the chip refused reads the same as a tested fault
injection. The call sites log the exception text once per key, so a
fallback storm costs one line and the counters carry the volume.
"""
from __future__ import annotations

import threading

_seen: set = set()
_lock = threading.Lock()


def warn_once(log, key: str, msg: str, *args) -> None:
    with _lock:
        if key in _seen:
            return
        _seen.add(key)
    log.warning(msg, *args)
