"""The one span primitive of the program: `RecordEvent`.

Reference parity: python/paddle/profiler/utils.py (RecordEvent, in_profiler_mode)
and the host tracer side of paddle/fluid/platform/profiler/host_tracer.cc.

Entering a `RecordEvent` does two things:

(a) it enters a `jax.profiler.TraceAnnotation("paddle_tpu:" + name)`, so any
    capture in progress (a `Profiler`, an operator's
    `jax.profiler.start_trace`, a benchmark's traced stretch) shows the
    program's phase in the profiler's own trace, on the clock of the device's
    ops. With no capture the annotation finds no session and is skipped.
(b) on exit it appends one record to ONE bounded process-wide ring:

        (name, t0, t1, id, parent, ident, args, event_type, tid)

    `t0`/`t1` are `time.perf_counter()` seconds; `parent` is the id of the
    span that enclosed it on its thread (0 at the top); `ident` is the id of
    what the span belongs to (a request's `rid`) or None; `args` a small dict
    of counts or None. The ring is gated by the flag that gates every
    counter, `PADDLE_TPU_TELEMETRY` (one cached bool), and while a
    `Profiler` records. `records(lo, hi)` reads it, `clear()` empties it,
    `evicted()` counts what fell off its far end.

The host's own pauses are records too: a `gc.callbacks` hook puts a
`host.gc` record (`generation`, `collected`, `uncollectable`) in the ring for
every collection of generation 1 or 2 and for any that lasted `GC_RECORD_S`
or more, and while a capture runs annotates generations 1 and 2 as
`paddle_tpu:host.gc` (a short generation-0 collection, hundreds a second, is
recorded nowhere). With the ring off the hook returns after one test.

`Profiler`'s host events ARE the ring's records between its start and its
stop (plus the spans still open at the stop, closed there): no second list.
"""
from __future__ import annotations

import collections
import functools
import gc
import itertools
import threading
import time
from typing import List, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from ..telemetry import metrics as _metrics

SPAN_PREFIX = "paddle_tpu:"

# The readers run after the drain, so the ring must reach from the window's
# start to then, twice over. Busiest cell since PR 35 (one step ahead):
# longdoc-open, 796 records a second in its 40 s window and 34.5k from the
# window's start to the read of a traced run (a capture's stop, the drain);
# doc-single 717 a second, 29.4k (builders' chip runs, PR 36). Twice the
# first is past 1 << 16.
RING_LEN = 1 << 17

_clock = time.perf_counter
_ring: collections.deque = collections.deque(maxlen=RING_LEN)
_ids = itertools.count(1)
_lock = threading.Lock()    # an append and the count of what it pushed out are one step
_stacks: dict = {}          # thread id -> that thread's stack of open spans
_state = {"evicted": 0, "profiling": False, "start": 0.0}
GC_RECORD_S = 1e-3          # a generation-0 collection this long is recorded too
_gc_done: list = []         # host.gc records not yet in the ring (see `_on_gc`)


class TracerEventType:
    # mirrors paddle/fluid/platform/profiler/trace_event.h enum
    Operator = "Operator"
    Dataloader = "Dataloader"
    ProfileStep = "ProfileStep"
    Forward = "Forward"
    Backward = "Backward"
    Optimization = "Optimization"
    PythonOp = "PythonOp"
    PythonUserDefined = "PythonUserDefined"
    UserDefined = "UserDefined"
    Communication = "Communication"


class HostEvent:
    __slots__ = ("name", "event_type", "start_ns", "end_ns", "tid", "args")

    def __init__(self, name, event_type, start_ns, end_ns, tid, args=None):
        self.name = name
        self.event_type = event_type
        self.start_ns = start_ns
        self.end_ns = end_ns
        self.tid = tid
        self.args = args  # optional dict of span metadata (chrome trace "args")

    @property
    def duration_ns(self):
        return self.end_ns - self.start_ns


def in_profiler_mode():
    return _state["profiling"]


# ---- the ring ----
def _push(rec) -> None:  # under _lock
    if len(_ring) == RING_LEN:
        _state["evicted"] += 1
    _ring.append(rec)


def _append(rec) -> None:
    with _lock:
        while _gc_done:
            _push(_gc_done.pop(0))
        _push(rec)


def records(lo: Optional[float] = None, hi: Optional[float] = None) -> list:
    """The ring's records, oldest first; with `lo`/`hi` (perf_counter
    seconds) those that started at or after `lo` and ended at or before
    `hi`."""
    with _lock:
        while _gc_done:
            _push(_gc_done.pop(0))
        out = list(_ring)
    if lo is not None or hi is not None:
        out = [r for r in out
               if (lo is None or r[1] >= lo) and (hi is None or r[2] <= hi)]
    return out


def clear() -> None:
    with _lock:
        _ring.clear()
        _gc_done.clear()
        _state["evicted"] = 0


def evicted() -> int:
    """Records that fell off the ring since the last `clear()`."""
    return _state["evicted"]


def record_span(name: str, t0: float, t1: float, ident=None, args: Optional[dict] = None,
                event_type: str = TracerEventType.UserDefined) -> None:
    """A span known only once it has ended, from two `perf_counter` stamps
    (a request's wait in the queue). In the ring only: an annotation cannot
    be entered after the fact."""
    if _metrics._enabled or _state["profiling"]:
        _append((name, t0, t1, next(_ids), 0, ident, args, event_type,
                 threading.get_ident()))


class _Thread(threading.local):
    """This thread's stack of open spans, registered so that a stopping
    `Profiler` can close the spans other threads still hold open."""

    def __init__(self):
        self.stack = []
        self.tid = threading.get_ident()
        _stacks[self.tid] = self.stack


_thread = _Thread()
_capturing = TraceAnnotation.is_enabled  # the profiler's own session check
_gc = {"t0": 0.0, "ann": None}


def _on_gc(phase: str, info: dict) -> None:
    """`gc.callbacks` hook: one `host.gc` record a collection worth one. A
    collection can start at any bytecode, inside `_append`'s lock among
    others, so the record waits in `_gc_done` for the next `_append` or
    `records()` to move it into the ring under the lock."""
    if not (_metrics._enabled or _state["profiling"]):
        return
    if phase == "start":
        if info["generation"] and _capturing():
            _gc["ann"] = TraceAnnotation(SPAN_PREFIX + "host.gc")
            _gc["ann"].__enter__()
        _gc["t0"] = _clock()
        return
    t1 = _clock()
    t0 = _gc["t0"]
    if _gc["ann"] is not None:
        _gc["ann"].__exit__(None, None, None)
        _gc["ann"] = None
    if t0 and (info["generation"] or t1 - t0 >= GC_RECORD_S):
        _gc_done.append(("host.gc", t0, t1, next(_ids), 0, None,
                         {"generation": info["generation"], "collected": info["collected"],
                          "uncollectable": info["uncollectable"]},
                         TracerEventType.UserDefined, threading.get_ident()))
    _gc["t0"] = 0.0


gc.callbacks.append(_on_gc)


class RecordEvent:
    """Context manager / decorator recording one named host span
    (python/paddle/profiler/utils.py:RecordEvent). `args` may be set or
    filled until the span ends; `ident` names what the span belongs to;
    with `step_num` the annotation is a `StepTraceAnnotation`."""

    __slots__ = ("name", "event_type", "args", "ident", "step_num",
                 "_t0", "_id", "_parent", "_tid", "_ann")

    def __init__(self, name: str, event_type: str = TracerEventType.PythonUserDefined,
                 args: Optional[dict] = None, ident=None, step_num: Optional[int] = None):
        self.name = name
        self.event_type = event_type
        self.args = args
        self.ident = ident
        self.step_num = step_num
        self._t0 = None
        self._ann = None

    # The work is in __enter__/__exit__ themselves, not a call further down:
    # a span on a serving path runs right after the host has waited for the
    # device, when every Python frame entered is a cold one (PERF.md).
    def __enter__(self):
        if _capturing():  # no object is built without a capture
            if self.step_num is None:
                ann = TraceAnnotation(SPAN_PREFIX + self.name)
            else:
                ann = StepTraceAnnotation(SPAN_PREFIX + self.name, step_num=self.step_num)
            ann.__enter__()
            self._ann = ann
        if _metrics._enabled or _state["profiling"]:
            th = _thread
            stack = th.stack
            self._parent = stack[-1]._id if stack else 0
            self._id = next(_ids)
            self._tid = th.tid
            stack.append(self)
            self._t0 = _clock()
        return self

    def __exit__(self, *exc):
        t1 = _clock()
        t0 = self._t0
        if t0 is not None:
            self._t0 = None
            stack = _stacks[self._tid]
            if stack and stack[-1] is self:
                stack.pop()
            elif self in stack:  # begin()/end() pairs that do not nest
                stack.remove(self)
            _append((self.name, t0, t1, self._id, self._parent, self.ident,
                     self.args, self.event_type, self._tid))
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        return False

    def begin(self):
        self.__enter__()

    def end(self):
        self.__exit__()

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with RecordEvent(self.name, self.event_type):
                return fn(*args, **kwargs)

        return wrapper


# ---- Profiler's window onto the ring ----
def _host_event(rec) -> HostEvent:
    name, t0, t1, _id, _parent, ident, args, event_type, tid = rec
    if ident is not None:
        args = dict(args or {}, ident=ident)
    return HostEvent(name, event_type, int(t0 * 1e9), int(t1 * 1e9), tid, args)


def _enable_host_tracer():
    _state["start"] = _clock()
    _state["profiling"] = True


def _disable_host_tracer() -> List[HostEvent]:
    """The ring's records since `_enable_host_tracer`, and the spans still
    open now, closed here: the reference host tracer flushes in-flight
    RecordEvents on stop; dropping them would truncate the last profiled
    step's export. (Such a span still ends in the ring when its owner ends
    it; the result returned here is not touched again.)"""
    _state["profiling"] = False
    now = _clock()
    lo = _state["start"]
    events = [_host_event(r) for r in records(lo=lo)]
    for stack in list(_stacks.values()):
        for ev in list(stack):
            t0 = ev._t0
            if t0 is not None and t0 >= lo:
                events.append(_host_event(
                    (ev.name, t0, now, ev._id, ev._parent, ev.ident, ev.args,
                     ev.event_type, ev._tid)))
    events.sort(key=lambda e: e.start_ns)
    return events


def wrap_optimizers():
    """Reference hook point: auto-instrument Optimizer.step under profiling.
    Our RecordEvent is cheap enough that hapi/timer call sites opt in directly."""
    return None
