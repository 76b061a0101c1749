"""Unified pipeline ledger: one per-stage accounting primitive for every
hand-rolled multi-stage pipeline in the repo.

TPIE (PAPERS.md, arxiv 1710.10091) makes per-stage instrumentation the
organizing principle of external-memory pipelines: you cannot balance a
decode→merge→compress→write chain you cannot see. Before this module,
each pipeline (compaction's compress-pool chain, the flush drain, mesh
fanout lanes, the transport dispatch executor) carried its own ad-hoc
counters — or none. Now they all report through one `Stage` shape:

    busy_s       seconds the stage spent doing its own work
    busy_cpu_s   the part of busy_s its threads were ON the CPU (by the
                 spans that bill the stage; see `Span`; an estimate where
                 only one root span in N reads the thread clock)
    stall_s      seconds the stage spent BLOCKED on a downstream stage
                 (full queue, exhausted buffer pool — backpressure paid)
    idle_s       seconds the stage spent waiting for upstream input
    items/bytes  units of work through the stage
    queue_hwm    high-water occupancy of the stage's inbound queue

Interpretation rule (docs/observability.md): the stage with the highest
busy_s is the pipeline's capacity bound; a large stall_s on the stage
FEEDING it is the same fact seen from upstream. The where-did-the-wall-go
table bench.py's `pipeline` section prints is exactly this.

The registry is process-global (like the metrics registry): stages
accumulate across tasks under stable `pipeline/stage` names, surfaced as
`pipeline.<pipeline>.<stage>.<stat>` metric gauges, the
`system_views.pipelines` virtual table and `nodetool pipelinestats`.
Recording costs two float adds under a per-stage lock — cheap enough to
stay armed always (the bench's paired A/B pins the data plane within
noise of the un-instrumented path).

ONE span primitive times everything (`Span`, opened by `Stage.busy()/
stall()/idle()` or by `span()` for a span that bills no stage). While
open it is a `jax.profiler.TraceAnnotation` named `ctpu.<name>`, so it
lies in a profiler trace on the device's clock; on exit it writes the
SAME seconds to its ledger stage, to the task's `profile` dict under its
key, and appends one record to the process-global span ring (`RING`):
the three clocks that used to time one phase are one write. Spans open
per round, segment, pool job or request — never per cell, partition or
block. docs/observability.md has the span-name catalogue.

A span reads two clocks: the wall (`CLOCK`) and its own thread's CPU
time (`CPU_CLOCK`). The ring record keeps both, so `wall - cpu` says
how long the thread was OFF the CPU inside the span; `gil_probe.py`
beside this file records through the same ring what one release-and-
retake of the GIL costs at that instant.
"""
from __future__ import annotations

import collections
import itertools
import math
import sys
import threading
from . import lockwitness
import time

# ctpulint: clock-injectable
# patchable monotonic clock for the stage timers: tests / a simulated
# deployment swap this for a virtual clock (the timeutil.CLOCK
# pattern); production leaves time.perf_counter. _Timer reads it at
# enter/exit time, so a swap takes effect immediately.
CLOCK = time.perf_counter

# ctpulint: clock-injectable
# the CPU time of the CALLING thread (CLOCK_THREAD_CPUTIME_ID), read by
# a span where it reads CLOCK and patchable the same way.
CPU_CLOCK = time.thread_time

# CLOCK comes from the vDSO; CPU_CLOCK is a system call: 0.3 µs on a stock
# kernel, 6.3 µs in the chip hosts' sandbox, where two of them a span cost
# ycsb_a.wire 9 % of its ops_s (PERF.md §6, PR 35). Two things keep a span
# near what it cost before, neither of them a knob:
# - span boundaries come in bursts on a thread (a request's nested spans
#   open within microseconds of each other and close the same way), so a
#   boundary that follows a READING of the thread clock by less than
#   CPU_REUSE_S takes that reading plus the wall since: between two of its
#   own boundaries that close together a thread was running. A GIL
#   hand-off or a blocking call costs more than this, so none is swallowed
#   whole; what is, is under 0.1 ms a boundary;
# - the clock's cost is measured once (`_calibrate`), and where a reading
#   costs more than CPU_READ_BUDGET_S only one root span in `_CPU_EVERY`
#   reads it, with everything below that root: the others' `cpu` is None.
#   A stock kernel reads every span.
CPU_REUSE_S = 100e-6
CPU_READ_BUDGET_S = 0.75e-6
_CPU_EVERY = 0              # 0: not measured yet


def _calibrate() -> int:
    """Measure one reading of CPU_CLOCK (the least of seven, by CLOCK)
    and set how many root spans share one that reads it."""
    global _CPU_EVERY
    cost = float("inf")
    for _ in range(7):
        t0 = CLOCK()
        CPU_CLOCK()
        cost = min(cost, CLOCK() - t0)
    _CPU_EVERY = max(1, math.ceil(cost / CPU_READ_BUDGET_S))
    return _CPU_EVERY


class Stage:
    """Accounting for one stage of one pipeline. All mutators take the
    stage lock; they run a handful of times per SEGMENT/SHARD/REQUEST
    (never per cell), so the lock is uncontended noise."""

    __slots__ = ("pipeline", "name", "busy_s", "busy_cpu_s", "stall_s",
                 "idle_s", "items", "bytes", "queue_hwm", "_lock")

    def __init__(self, pipeline: str, name: str):
        self.pipeline = pipeline
        self.name = name
        self.busy_s = 0.0
        self.busy_cpu_s = 0.0
        self.stall_s = 0.0
        self.idle_s = 0.0
        self.items = 0
        self.bytes = 0
        self.queue_hwm = 0
        self._lock = lockwitness.make_lock("pipeline.stage")

    # ------------------------------------------------------------ record --

    def add_busy(self, dt: float, cpu_dt: float = 0.0) -> None:
        """`cpu_dt`: the part of `dt` the thread was on the CPU (a busy
        span passes its own; a caller that times by hand bills none)."""
        with self._lock:
            self.busy_s += dt
            self.busy_cpu_s += cpu_dt

    def add_stall(self, dt: float) -> None:
        with self._lock:
            self.stall_s += dt

    def add_idle(self, dt: float) -> None:
        with self._lock:
            self.idle_s += dt

    def add_items(self, n: int = 1, nbytes: int = 0) -> None:
        with self._lock:
            self.items += n
            self.bytes += nbytes

    def note_queue(self, depth: int) -> None:
        """Record the stage's inbound-queue occupancy at an enqueue
        instant; only the high-water survives (the bound the queue
        actually needed, vs the bound it was given)."""
        if depth > self.queue_hwm:
            with self._lock:
                if depth > self.queue_hwm:
                    self.queue_hwm = depth

    def busy(self, name: str | None = None, **kw) -> "Span":
        """`with stage.busy(): ...` — timed busy work. `name` is the
        span's name in the ring and the trace (default
        `<pipeline>.<stage>`); `**kw` as Span takes them."""
        return Span(name or f"{self.pipeline}.{self.name}", "busy",
                    self, **kw)

    def stall(self, name: str | None = None, **kw) -> "Span":
        return Span(name or f"{self.pipeline}.{self.name}", "stall",
                    self, **kw)

    def idle(self, name: str | None = None, **kw) -> "Span":
        return Span(name or f"{self.pipeline}.{self.name}", "idle",
                    self, **kw)

    # ------------------------------------------------------------- read --

    def snapshot(self) -> dict:
        with self._lock:
            return {"busy_s": round(self.busy_s, 6),
                    "busy_cpu_s": round(self.busy_cpu_s, 6),
                    "stall_s": round(self.stall_s, 6),
                    "idle_s": round(self.idle_s, 6),
                    "items": self.items, "bytes": self.bytes,
                    "queue_hwm": self.queue_hwm}

    def reset(self) -> None:
        with self._lock:
            self.busy_s = self.busy_cpu_s = 0.0
            self.stall_s = self.idle_s = 0.0
            self.items = self.bytes = 0
            self.queue_hwm = 0


# ------------------------------------------------------------------ spans

# the span ring: one bounded deque for the whole process (like the
# ledger it survives engine close; deque.append is atomic). A record is
# a tuple in RECORD_FIELDS order; `start`/`end` are CLOCK readings, `cpu`
# the seconds of CPU_CLOCK between them (None for a back-dated span and
# where the span's root did not read the clock: `_calibrate`).
RING_CAP = 32768
RING: collections.deque = collections.deque(maxlen=RING_CAP)
RECORD_FIELDS = ("name", "kind", "thread", "start", "end", "id", "parent",
                 "task", "cells", "bytes", "items", "cpu")
TRACE_PREFIX = "ctpu."

_TLS = threading.local()
_IDS = itertools.count(1)          # span ids and task ids (next() is atomic)
_PROF_LOCK = threading.Lock()      # pool workers share one profile key


def new_task_id() -> int:
    """An id for a root span (one compaction task, one request): its
    children inherit it, and it travels to other threads with the work
    item (`task_scope`, or `task=` on the span)."""
    return next(_IDS)


def current_task() -> int:
    """The task/request id the calling thread works for right now."""
    stack = getattr(_TLS, "stack", None)
    return stack[-1].task if stack else getattr(_TLS, "task", 0)


class task_scope:
    """`with task_scope(tid):` — spans this thread opens outside any
    parent span belong to task `tid` (a worker thread serving one
    task's queue: compact-w, the prefetch helper)."""

    __slots__ = ("task", "_prev")

    def __init__(self, task: int):
        self.task = task

    def __enter__(self):
        self._prev = getattr(_TLS, "task", 0)
        _TLS.task = self.task
        return self

    def __exit__(self, *exc):
        _TLS.task = self._prev


def _thread_cpu(now: float) -> float:
    """The calling thread's CPU seconds at the CLOCK reading `now`: a
    reading of CPU_CLOCK, or the thread's last reading plus the wall
    since where that is under CPU_REUSE_S ago (never chained: the
    estimate does not move the anchor)."""
    last = getattr(_TLS, "cpu_at", None)
    if last is not None and 0.0 <= now - last[0] < CPU_REUSE_S:
        return last[1] + (now - last[0])
    cpu = CPU_CLOCK()
    _TLS.cpu_at = (now, cpu)
    return cpu


def _annotation(name: str, thread: str, task: int):
    """A TraceAnnotation for the profiler's trace — a no-op object while
    no profiler session runs. It carries the Python thread's name and
    the task id as event stats: the profiler names a host line after
    the process, not after a Python thread. jax is never imported from
    here: a process that has not imported it (the wire client, the load
    generator) has no profiler to annotate for."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        return jax.profiler.TraceAnnotation(TRACE_PREFIX + name,
                                            thread=thread, task=task)
    except AttributeError:     # jax mid-import on another thread
        return None


class Span:
    """One timed stretch of one thread. On exit the same `seconds` go to
    the ledger stage (by `kind`: busy | stall | idle), to `prof[key]`
    and into the ring, with the parent (the span open on this thread
    when this one opened), the task id and up to three integer
    attributes, which may be set while the span is open
    (`sp.nbytes = n`). `since` back-dates the start to a CLOCK reading
    taken earlier (a queue wait stamped at submit): such a span is a
    ring record and a ledger entry, not an annotation.

    `cpu_s` is the CPU time of the OPENING thread between the same two
    instants (children included, like `seconds`), the record's last
    field. `seconds - cpu_s` is the time that thread was off the CPU:
    waiting for the GIL, for a lock or a condition, for blocking I/O,
    or for the device. CPU burnt with the GIL released (numpy, LZ4,
    CRC) is still CPU: the split is ran / did not run. A back-dated
    span is not thread time: its `cpu_s` is None. So is that of a span
    under a root that did not read the clock, which happens only where
    one reading costs more than CPU_READ_BUDGET_S (`_calibrate`)."""

    __slots__ = ("name", "kind", "stage", "prof", "key", "task", "cells",
                 "nbytes", "items", "seconds", "cpu_s", "_t0", "_c0",
                 "_ann", "_id", "_parent", "_thread")

    def __init__(self, name: str, kind: str = "busy", stage=None, *,
                 prof: dict | None = None, key: str | None = None,
                 task: int | None = None, since: float | None = None,
                 cells: int = 0, nbytes: int = 0, items: int = 0):
        self.name, self.kind, self.stage = name, kind, stage
        self.prof, self.key, self.task = prof, key, task
        self.cells, self.nbytes, self.items = cells, nbytes, items
        self.seconds = 0.0
        self.cpu_s = None
        self._t0 = since
        self._c0 = None

    def __enter__(self):
        stack = getattr(_TLS, "stack", None)
        if stack is None:
            stack = _TLS.stack = []
        parent = stack[-1] if stack else None
        self._parent = parent._id if parent is not None else 0
        if self.task is None:
            self.task = parent.task if parent is not None \
                else getattr(_TLS, "task", 0)
        self._id = next(_IDS)
        self._ann = None
        self._thread = threading.current_thread().name
        stack.append(self)
        if self._t0 is None:
            self._ann = _annotation(self.name, self._thread, self.task)
            if self._ann is not None:
                self._ann.__enter__()
            self._t0 = CLOCK()
            # a root decides for everything below it (see CPU_REUSE_S);
            # the id is scrambled, or trees of N spans would resonate
            if parent is not None:
                reads = parent._c0 is not None
            else:
                reads = (self._id * 0x9E3779B1 >> 16) \
                    % (_CPU_EVERY or _calibrate()) == 0
            if reads:
                self._c0 = _thread_cpu(self._t0)
        return self

    def __exit__(self, *exc):
        t1 = CLOCK()
        cpu = self.cpu_s = None if self._c0 is None \
            else max(_thread_cpu(t1) - self._c0, 0.0)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        _TLS.stack.pop()
        dt = self.seconds = t1 - self._t0
        if self.stage is not None:
            if self.kind == "busy":
                # one span in _CPU_EVERY stands for all of them
                self.stage.add_busy(dt, (cpu or 0.0) * _CPU_EVERY)
            else:
                getattr(self.stage, "add_" + self.kind)(dt)
        if self.prof is not None and self.key is not None:
            with _PROF_LOCK:
                self.prof[self.key] = self.prof.get(self.key, 0.0) + dt
        RING.append((self.name, self.kind, self._thread, self._t0, t1,
                     self._id, self._parent, self.task, int(self.cells),
                     int(self.nbytes), int(self.items), cpu))


def span(name: str, kind: str = "busy", **kw) -> Span:
    """A span that bills no ledger stage: a finer child of one that
    does, or a stretch no stage owns. Ring record, annotation and (with
    `prof=`/`key=`) profile seconds like any other."""
    return Span(name, kind, None, **kw)


def ring_records(tail: int | None = None) -> list:
    """The ring's records, oldest first, as dicts (the flight-recorder
    bundle's `pipeline_spans` section; readers under benchmarks/)."""
    recs = list(RING)
    if tail is not None:
        recs = recs[-tail:]
    return [dict(zip(RECORD_FIELDS, r)) for r in recs]


class PipelineLedger:
    """Ordered stage registry for one named pipeline. Stage creation is
    idempotent, so every writer/task/worker touching the pipeline calls
    `stage(name)` and accumulates into the same accounting."""

    def __init__(self, name: str):
        self.name = name
        self._stages: dict[str, Stage] = {}
        self._lock = threading.Lock()

    def stage(self, name: str) -> Stage:
        st = self._stages.get(name)
        if st is None:
            with self._lock:
                st = self._stages.get(name)
                if st is None:
                    st = Stage(self.name, name)
                    self._stages[name] = st
                    _register_stage_gauges(st)
        return st

    def stages(self) -> list[Stage]:
        with self._lock:
            return list(self._stages.values())

    def snapshot(self) -> dict:
        return {s.name: s.snapshot() for s in self.stages()}

    def reset(self) -> None:
        for s in self.stages():
            s.reset()


# ---------------------------------------------------------------- registry

_LOCK = lockwitness.make_lock("pipeline.registry")
_LEDGERS: dict[str, PipelineLedger] = {}


def ledger(name: str) -> PipelineLedger:
    """Get-or-create the process-global ledger for one pipeline name.
    Established pipelines (docs/observability.md): `compaction` and
    `flush` (SSTableWriter write legs: serialize/compress/io_write +
    the flush `drain` stage), `mesh` (fanout lanes: decode/merge),
    `compress_pool` (shared worker: pack), `transport` (the request
    dispatch executor), `messaging` (the internode verb-dispatch
    pool: `dispatch` plus one lazily-created stage per handled verb)
    and `stream` (the sessioned-transfer legs: read/net/land)."""
    led = _LEDGERS.get(name)
    if led is None:
        with _LOCK:
            led = _LEDGERS.get(name)
            if led is None:
                led = _LEDGERS[name] = PipelineLedger(name)
    return led


def snapshot_all() -> dict:
    """{pipeline: {stage: stats}} — the system_views.pipelines vtable,
    `nodetool pipelinestats` and bench.py's `pipeline` section all read
    this."""
    with _LOCK:
        ledgers = list(_LEDGERS.values())
    return {led.name: led.snapshot() for led in ledgers}


def reset_all() -> None:
    """Zero every stage (bench legs / test isolation). Stages stay
    registered — their metric gauges keep reporting, from zero. The
    span ring is left alone: it is history, not an accumulator."""
    with _LOCK:
        ledgers = list(_LEDGERS.values())
    for led in ledgers:
        led.reset()


def _register_stage_gauges(st: Stage) -> None:
    """Export one stage as `pipeline.<pipeline>.<stage>.<stat>` gauges
    in the process-global metrics registry (snapshot / Prometheus /
    system_views.metrics)."""
    from ..service.metrics import GLOBAL

    p, n = st.pipeline, st.name
    GLOBAL.register_gauge(f"pipeline.{p}.{n}.busy_s",
                          lambda: round(st.busy_s, 6))
    GLOBAL.register_gauge(f"pipeline.{p}.{n}.busy_cpu_s",
                          lambda: round(st.busy_cpu_s, 6))
    GLOBAL.register_gauge(f"pipeline.{p}.{n}.stall_s",
                          lambda: round(st.stall_s, 6))
    GLOBAL.register_gauge(f"pipeline.{p}.{n}.idle_s",
                          lambda: round(st.idle_s, 6))
    GLOBAL.register_gauge(f"pipeline.{p}.{n}.items", lambda: st.items)
    GLOBAL.register_gauge(f"pipeline.{p}.{n}.bytes", lambda: st.bytes)
    GLOBAL.register_gauge(f"pipeline.{p}.{n}.queue_hwm",
                          lambda: st.queue_hwm)
