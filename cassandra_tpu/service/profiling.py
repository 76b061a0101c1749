"""Device program registry: compile/dispatch/execute accounting for
every jitted entry point (observability layer 6, device half).

The JAX programs (ops/device_write.py, ops/device_scan.py, index/manager.py)
were a black box: a first call on a new operand shape pays XLA
compilation (seconds to minutes for big sorts), warm calls pay dispatch
+ device execution, and nothing recorded which was which. This module
is the accounting layer:

  record_dispatch(kernel, shape_key, s, device=None) -> bool
      timed around the jitted call itself. jit compiles synchronously
      inside the call, so the FIRST dispatch for a (kernel, shape_key)
      pair is the compile: it is recorded under compile_s/compiles and
      excluded from the warm dispatch_s average; returns True for it.
      Every later dispatch of the same shape is warm. `compiles` is
      therefore exactly the recompile count by operand shape — a
      workload churning shape buckets shows up as a climbing compile
      counter, and that is the signal the RETRACE SENTINEL reads: a
      program whose compiles cross `retrace_budget` publishes a
      `profile.retrace` diagnostic event (once per program, re-armed by
      reset()) and counts every further recompile in
      `profile.retraces`, so a shape-bucket regression is caught the
      tick it happens instead of as a mystery slowdown.
  record_execute(kernel, s)
      timed around blocking on the result (device wait).
  wrap(name, fn)
      the auto-instrumentation seam: returns `fn` with dispatch timing,
      an argument-derived shape key, the device of its first committed
      array operand and best-effort XLA cost analysis folded in. Trace-safe — a call whose operands are tracers is
      inside an ENCLOSING program's trace, where wall timing is
      meaningless and the outer program's dispatch already owns the
      cost, so the wrapper passes straight through.
  add_phases({phase: seconds})
      folds a CompactionTask.profile (io_decode / merge / pack / device /
      gather / compress / io_write / seal, and the waits and finer
      phases the span primitive added) into the process aggregate. The
      seconds are the spans' own (utils/pipeline_ledger.py): nothing
      here times a phase.

Per-program shape keys are tracked in a bounded LRU (SHAPE_CAP): under
shape-bucket churn the set no longer grows without bound; an evicted
shape that reappears counts as a fresh compile, which mirrors what a
bounded compilation cache would do and only biases `compiles` upward in
exactly the churn regime the sentinel exists to flag. `shape_count`
(live tracked shapes) and `shape_evictions` are both exported.

Surfaces: snapshot() feeds the system_views.device_profile and
system_views.device_programs virtual tables, the `kernel_profile`
section of bench.py output and the `profile` section of flight-recorder
bundles.

Process-global (like the devices themselves); engine-scoped consumers
read through the vtable which serves this singleton. In-process nodes
pinned to devices of their own (config `device_ordinal`) are told apart
by each program's per-device calls and compiles.
"""
from __future__ import annotations

import logging
import threading
import time
from collections import OrderedDict

from ..utils.logonce import warn_once

_log = logging.getLogger(__name__)

# patchable clock seam (the pipeline-ledger pattern): tests freeze it,
# production leaves time.perf_counter
CLOCK = time.perf_counter

# live shape keys tracked per program (LRU, satellite of PR 17): the
# old unbounded set leaked one entry per shape bucket forever
SHAPE_CAP = 256


def _shape_of(x):
    """Hashable shape signature of one operand tree: arrays collapse to
    (shape, dtype), containers recurse, everything else to its literal
    (static argnums) or type name."""
    shape = getattr(x, "shape", None)
    if shape is not None:
        return ("arr", tuple(shape), str(getattr(x, "dtype", "?")))
    if isinstance(x, dict):
        return ("dict",) + tuple(
            (k, _shape_of(v)) for k, v in sorted(x.items()))
    if isinstance(x, (tuple, list)):
        return ("seq",) + tuple(_shape_of(v) for v in x)
    if isinstance(x, (int, float, str, bool, type(None))):
        return ("lit", x)
    return ("obj", type(x).__name__)


def device_id(device=None) -> int:
    """The id of `device`; for None, of the device jax places an
    uncommitted operand on (its default device) — where a program runs
    that no engine pinned."""
    if device is None:
        import jax
        device = jax.config.jax_default_device
        if not hasattr(device, "id"):
            device = jax.devices(device)[0] if device else jax.devices()[0]
    return int(device.id)


def _device_of(x):
    """The id of the device of the first COMMITTED jax array in an
    operand tree (a jitted call runs there), else None."""
    if getattr(x, "committed", False):
        return next(iter(x.devices())).id
    if isinstance(x, dict):
        x = x.values()
    elif not isinstance(x, (tuple, list)):
        return None
    for v in x:
        d = _device_of(v)
        if d is not None:
            return d
    return None


def _has_tracer(x) -> bool:
    """True iff any leaf of the operand tree is a jax Tracer — i.e. the
    call is happening INSIDE an enclosing trace."""
    from jax.core import Tracer

    def walk(v):
        if isinstance(v, Tracer):
            return True
        if isinstance(v, dict):
            return any(walk(i) for i in v.values())
        if isinstance(v, (tuple, list)):
            return any(walk(i) for i in v)
        return False

    return walk(x)


class DeviceProgramRegistry:
    def __init__(self, shape_cap: int = SHAPE_CAP):
        self._lock = threading.Lock()
        self._kernels: dict[str, dict] = {}
        self._phases: dict[str, float] = {}
        self.shape_cap = int(shape_cap)
        # <= 0 disables the sentinel; the mutable
        # profiler_retrace_budget knob lands here (engine wiring)
        self.retrace_budget = 0

    def set_retrace_budget(self, budget) -> None:
        """The `profiler_retrace_budget` knob landing (process-global
        like the registry itself: last writer wins across co-hosted
        engines, same as the shared device)."""
        self.retrace_budget = int(budget)

    def _kernel_locked(self, name: str) -> dict:
        k = self._kernels.get(name)
        if k is None:
            k = self._kernels[name] = {
                "calls": 0, "compiles": 0, "compile_s": 0.0,
                "dispatch_s": 0.0, "execute_s": 0.0,
                "shapes": OrderedDict(), "shape_evictions": 0,
                "retraces": 0, "sentinel_fired": False, "cost": None,
                "devices": {}}
        return k

    def record_dispatch(self, kernel: str, shape_key,
                        seconds: float, device=None) -> bool:
        """`device`: the id of the device the call ran on; None = the
        default device. A program compiles per device as well as per
        shape, so give the device in `shape_key` too where a program
        runs on more than one."""
        dev = device_id() if device is None else int(device)
        fire = compiles = retraces = None
        with self._lock:
            k = self._kernel_locked(kernel)
            k["calls"] += 1
            per = k["devices"].get(dev)
            if per is None:
                per = k["devices"][dev] = {"calls": 0, "compiles": 0}
            per["calls"] += 1
            shapes = k["shapes"]
            if shape_key in shapes:
                shapes.move_to_end(shape_key)
                k["dispatch_s"] += seconds
                return False
            per["compiles"] += 1
            shapes[shape_key] = True
            if len(shapes) > self.shape_cap:
                shapes.popitem(last=False)
                k["shape_evictions"] += 1
            k["compiles"] += 1
            k["compile_s"] += seconds
            budget = self.retrace_budget
            if budget > 0 and k["compiles"] > budget:
                k["retraces"] += 1
                fire = not k["sentinel_fired"]
                k["sentinel_fired"] = True
                compiles, retraces = k["compiles"], k["retraces"]
        if retraces is not None:
            # metrics + event OUTSIDE the registry lock (publish takes
            # the bus lock; never nest foreign locks under ours)
            from .metrics import GLOBAL as METRICS
            METRICS.incr("profile.retraces")
            if fire:
                from . import diagnostics
                diagnostics.publish(
                    "profile.retrace", program=kernel,
                    compiles=compiles, budget=self.retrace_budget,
                    retraces=retraces)
        return True

    def record_execute(self, kernel: str, seconds: float) -> None:
        with self._lock:
            k = self._kernel_locked(kernel)
            k["execute_s"] += seconds

    # ------------------------------------------------- auto-instrument --

    def wrap(self, name: str, fn, cost: bool = True):
        """Instrument one jitted entry point (see module docstring).
        Safe on dual-use kernels that are both host entry points and
        bodies of larger programs: tracer operands pass straight
        through untimed."""
        registry = self

        def wrapped(*args, **kwargs):
            if _has_tracer(args) or _has_tracer(kwargs):
                return fn(*args, **kwargs)
            key = _shape_of(args) if not kwargs \
                else (_shape_of(args),
                      _shape_of(tuple(sorted(kwargs.items()))))
            dev = _device_of(args)
            key = (key, dev)
            t0 = CLOCK()
            out = fn(*args, **kwargs)
            if registry.record_dispatch(name, key, CLOCK() - t0, dev) \
                    and cost:
                registry.maybe_record_cost(name, fn, args, kwargs)
            return out

        wrapped.__name__ = getattr(fn, "__name__", name)
        wrapped.__wrapped__ = fn
        return wrapped

    def maybe_record_cost(self, kernel: str, fn, args=(),
                          kwargs=None) -> None:
        """XLA cost analysis for a program's most recently compiled
        shape. Right after a compiling dispatch, lower() is jit's cached
        lowering and compile() finds the executable that ran in jax's
        in-memory compilation cache — microseconds, not a second
        compile (timed on the chip, CHANGES.md PR 21). A failure leaves
        cost at None and says why, once per program."""
        try:
            lowered = fn.lower(*args, **(kwargs or {}))
            cost = lowered.compile().cost_analysis()
            flops = float(cost.get("flops", 0.0))
            nbytes = float(cost.get("bytes accessed", 0.0))
        except Exception as e:
            warn_once(_log, f"profile.cost.{kernel}",
                      "no XLA cost analysis for %s: %r", kernel, e)
            return
        with self._lock:
            self._kernel_locked(kernel)["cost"] = {
                "flops": flops, "bytes_accessed": nbytes}

    # ---------------------------------------------------------- phases --

    def add_phases(self, profile: dict) -> None:
        with self._lock:
            for phase, seconds in profile.items():
                self._phases[phase] = self._phases.get(phase, 0.0) \
                    + float(seconds)

    def snapshot(self) -> dict:
        """{"kernels": {name: {calls, compiles, shape_count,
        shape_evictions, retraces, compile_s, dispatch_s, execute_s,
        cost_flops, cost_bytes, devices}}, "phases": {name: seconds}}.
        `shape_count` is the LIVE tracked-shape count; `devices` is
        {device id: {"calls", "compiles"}}, the calls and compiles by
        the device each ran on."""
        with self._lock:
            kernels = {}
            for name, k in self._kernels.items():
                cost = k["cost"] or {}
                kernels[name] = {
                    "calls": k["calls"], "compiles": k["compiles"],
                    "shape_count": len(k["shapes"]),
                    "shape_evictions": k["shape_evictions"],
                    "retraces": k["retraces"],
                    "compile_s": round(k["compile_s"], 6),
                    "dispatch_s": round(k["dispatch_s"], 6),
                    "execute_s": round(k["execute_s"], 6),
                    "cost_flops": float(cost.get("flops", 0.0)),
                    "cost_bytes": float(cost.get("bytes_accessed",
                                                 0.0)),
                    "devices": {d: dict(v)
                                for d, v in k["devices"].items()}}
            phases = {p: round(s, 6) for p, s in self._phases.items()}
        return {"kernels": kernels, "phases": phases}

    def reset(self) -> None:
        with self._lock:
            self._kernels.clear()
            self._phases.clear()


GLOBAL = DeviceProgramRegistry()
