"""Collective hang watchdog.

Reference parity: paddle/phi/core/distributed/comm_task.h:36 (CommTask,
IsTimeout :127) + comm_task_manager.h:37 (CommTaskManager — a background
thread that detects hung/errored NCCL collectives and aborts the process
with diagnostics).

TPU-native design: compiled collectives are XLA program internals — a hang
surfaces as a host thread blocked in dispatch/compile or in a
blocking wait (store rendezvous, block_until_ready). So the watchdog tracks
HOST-SIDE blocking sections: every eager collective dispatch and every store
wait registers a CommTask; a daemon thread scans them and escalates through
a ladder instead of killing the process blind:

1. **warn** — a task older than FLAGS_comm_watchdog_warn_s (but under its
   hard deadline) gets ONE stderr warning + telemetry counter, so a
   slowly-degrading link shows up before the abort;
2. **dump** — past the hard deadline the default handler writes the full
   diagnostic dump (op, group ranks, elapsed, every other in-flight task),
   every thread's stack via `faulthandler`, and a telemetry snapshot;
3. **abort** — flushes stderr (the dump must survive buffered pipes under
   `launch`) and invokes the abort handler — default `os._exit(1)`,
   matching the reference's abort-on-hang semantics.

Tests/graceful users install their own hard-deadline handler via
`set_timeout_handler` (replacing stages 2+3), or keep the diagnostics and
swap only the final abort via `set_abort_handler`.

Config: FLAGS_enable_comm_watchdog (default True),
FLAGS_comm_watchdog_timeout_s (default 600, the reference's default
CommTask timeout scale), FLAGS_comm_watchdog_warn_s (soft deadline), or
per-task timeouts; DistributedStrategy maps its `comm_watchdog_timeout`
hybrid config here (see fleet/fleet.py).
"""
from __future__ import annotations

import faulthandler
import itertools
import os
import sys
import threading
import time
from typing import Callable, Optional

from ..framework import flags as _flags

_flags.define_flag("FLAGS_enable_comm_watchdog", True, "abort on hung collectives/store waits")
_flags.define_flag("FLAGS_comm_watchdog_timeout_s", 600.0, "seconds before a comm task is declared hung")
_flags.define_flag(
    "FLAGS_comm_watchdog_margin_s", 30.0,
    "extra grace added to a blocking call's OWN timeout before the watchdog "
    "declares it stuck (a wait is only 'hung' once past its own deadline)",
)
_flags.define_flag(
    "FLAGS_comm_watchdog_warn_s", 300.0,
    "soft deadline: a comm task older than this (but not yet hung) emits one "
    "warning with diagnostics; 0 disables the warn stage",
)


def _record_task_metric(name: str, op: str) -> None:
    """Publish a comm-task lifecycle event into the telemetry registry."""
    from .. import telemetry as _tm

    if _tm.enabled():
        _tm.counter(name, "comm watchdog task lifecycle", ("op",)).labels(op=op).inc()


class CommTask:
    __slots__ = ("tid", "op", "info", "start", "timeout", "warned")

    def __init__(self, tid, op, info, timeout):
        self.tid = tid
        self.op = op
        self.info = info
        self.start = time.monotonic()
        self.timeout = timeout
        self.warned = False

    def elapsed(self) -> float:
        return time.monotonic() - self.start

    def is_timeout(self) -> bool:
        return self.elapsed() > self.timeout

    def describe(self) -> str:
        extra = ", ".join(f"{k}={v}" for k, v in self.info.items())
        return f"CommTask[{self.tid}] op={self.op} elapsed={self.elapsed():.1f}s timeout={self.timeout:.0f}s {extra}"


def flush_diagnostics() -> None:
    """Make the dump survive the process: write a telemetry snapshot to
    stderr (the retry/fault/collective counters are the post-mortem) and
    flush — under `launch`, worker stderr rides a buffered pipe and an
    unflushed abort loses everything after the last newline."""
    try:
        from .. import telemetry as _tm

        if _tm.enabled():
            sys.stderr.write("--- telemetry snapshot ---\n")
            sys.stderr.write(_tm.to_prometheus())
            # JSON-lines for machine post-mortems, LENIENT mode: a gauge
            # that went NaN may be the whole story of this crash — skip
            # and count it (loud marker line) instead of letting
            # allow_nan=False throw away the entire snapshot
            sys.stderr.write("\n--- telemetry snapshot (jsonl) ---\n")
            sys.stderr.write(_tm.to_json_lines(strict=False))
            sys.stderr.write("\n")
    except Exception:
        pass  # diagnostics must never mask the abort
    try:
        # the incident-timeline tail is the cross-subsystem event order
        # leading up to the hang (injections, migrations, mode changes);
        # tail() is NaN-lenient so the dump survives poisoned payloads
        from ..telemetry import timeline as _tl

        if _tl.enabled():
            import json as _json

            sys.stderr.write("--- incident timeline tail (jsonl) ---\n")
            for rec in _tl.tail(256):
                sys.stderr.write(_json.dumps(rec, sort_keys=True))
                sys.stderr.write("\n")
            if _tl.dropped():
                sys.stderr.write(
                    f"(+{_tl.dropped()} older event(s) ring-evicted)\n"
                )
    except Exception:
        pass
    try:
        sys.stderr.flush()
    except Exception:
        pass


def _default_abort(task: CommTask) -> None:
    os._exit(1)


def _default_handler(task: CommTask, dump: str) -> None:
    """Hard-deadline stages of the escalation ladder: dump, then abort."""
    try:
        from ..telemetry import timeline as _tl

        _tl.emit("watchdog", "escalation", severity="fatal",
                 op=task.op, elapsed_s=round(task.elapsed(), 3),
                 timeout_s=task.timeout)
    except Exception:
        pass
    sys.stderr.write(
        f"\n=== paddle_tpu comm watchdog: HUNG COLLECTIVE DETECTED ===\n"
        f"{task.describe()}\n--- all in-flight comm tasks ---\n{dump}\n"
        f"--- all thread stacks ---\n"
    )
    try:
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
    except Exception:
        pass
    flush_diagnostics()
    try:
        # the guardian flight recorders are the per-step post-mortem (loss,
        # grad norm, skip/rollback/desync events, collective latencies) —
        # dump them to the crash dir before the process dies
        from ..framework import guardian as _guardian

        for p in _guardian.dump_flight_recorders(reason=f"watchdog:{task.op}"):
            sys.stderr.write(f"flight recorder dumped: {p}\n")
    except Exception:
        pass  # diagnostics must never mask the abort
    try:
        sys.stderr.flush()
    except Exception:
        pass
    sys.stderr.write("aborting process (reference CommTaskManager semantics)\n")
    try:
        sys.stderr.flush()
    except Exception:
        pass
    CommTaskManager.instance()._abort_handler(task)


class CommTaskManager:
    """Singleton scanning thread over in-flight comm tasks."""

    _instance: Optional["CommTaskManager"] = None
    _instance_lock = threading.Lock()

    def __init__(self):
        self._tasks: dict = {}
        self._lock = threading.Lock()
        self._ids = itertools.count()
        self._thread: Optional[threading.Thread] = None
        self._handler: Callable = _default_handler
        self._abort_handler: Callable = _default_abort
        self._warn_handler: Optional[Callable] = None
        self._wake = threading.Event()

    @classmethod
    def instance(cls) -> "CommTaskManager":
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    # ---- task lifecycle ----
    def start_task(self, op: str, timeout: Optional[float] = None, **info) -> Optional[int]:
        if not _flags.get_flag("FLAGS_enable_comm_watchdog"):
            return None
        if timeout is None:
            timeout = float(_flags.get_flag("FLAGS_comm_watchdog_timeout_s"))
        t = CommTask(next(self._ids), op, info, timeout)
        with self._lock:
            self._tasks[t.tid] = t
            self._ensure_thread()
        self._wake.set()
        _record_task_metric("paddle_tpu_comm_tasks_started_total", op)
        return t.tid

    def end_task(self, tid: Optional[int]) -> None:
        if tid is None:
            return
        with self._lock:
            self._tasks.pop(tid, None)

    def set_timeout_handler(self, fn: Optional[Callable]) -> Callable:
        prev = self._handler
        self._handler = fn or _default_handler
        return prev

    def set_abort_handler(self, fn: Optional[Callable]) -> Callable:
        """Swap the ladder's final stage (default os._exit(1)) while keeping
        the dump/flush diagnostics — what a graceful shutdown hook or a chaos
        test observing the full warn→dump→abort ordering wants."""
        prev = self._abort_handler
        self._abort_handler = fn or _default_abort
        return prev

    def set_warn_handler(self, fn: Optional[Callable]) -> Optional[Callable]:
        prev = self._warn_handler
        self._warn_handler = fn
        return prev

    def _warn(self, task: CommTask) -> None:
        task.warned = True
        _record_task_metric("paddle_tpu_comm_tasks_warned_total", task.op)
        try:
            from ..telemetry import timeline as _tl

            _tl.emit("watchdog", "soft_deadline", severity="warn",
                     op=task.op, elapsed_s=round(task.elapsed(), 3),
                     timeout_s=task.timeout)
        except Exception:
            pass
        sys.stderr.write(
            f"[paddle_tpu comm watchdog] WARNING: {task.describe()} — past the "
            f"soft deadline (FLAGS_comm_watchdog_warn_s), will abort at "
            f"{task.timeout:.0f}s\n"
        )
        try:
            sys.stderr.flush()
        except Exception:
            pass
        if self._warn_handler is not None:
            try:
                self._warn_handler(task)
            except Exception:
                pass

    def active_tasks(self):
        with self._lock:
            return list(self._tasks.values())

    # ---- scanner ----
    def _ensure_thread(self):
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._scan_loop, name="paddle-tpu-comm-watchdog", daemon=True
            )
            self._thread.start()

    def _scan_loop(self):
        while True:
            # block until a task registers (start_task sets the event) —
            # zero idle wakeups when nothing is in flight
            self._wake.wait()
            self._wake.clear()
            while True:
                with self._lock:
                    tasks = list(self._tasks.values())
                if not tasks:
                    break
                warn_s = float(_flags.get_flag("FLAGS_comm_watchdog_warn_s"))
                for t in tasks:
                    if t.is_timeout():
                        dump = "\n".join(x.describe() for x in tasks)
                        with self._lock:
                            self._tasks.pop(t.tid, None)
                        _record_task_metric("paddle_tpu_comm_tasks_timeout_total", t.op)
                        try:
                            self._handler(t, dump)
                        except Exception:
                            pass
                    elif not t.warned and 0 < warn_s <= t.elapsed():
                        # soft deadline: one warning per task, then keep
                        # counting down to the hard deadline
                        self._warn(t)
                # scan at 1/10 of the smallest remaining margin (to a warn OR
                # hard deadline), bounded
                def _next_deadline(t):
                    hard = t.timeout - t.elapsed()
                    if not t.warned and 0 < warn_s:
                        return min(hard, max(warn_s - t.elapsed(), 0.0))
                    return hard

                margin = min((_next_deadline(t) for t in tasks), default=0.5)
                time.sleep(min(max(margin / 10, 0.02), 0.5))


class comm_task:
    """Context manager wrapping one blocking communication section."""

    def __init__(self, op: str, timeout: Optional[float] = None, **info):
        self._op = op
        self._timeout = timeout
        self._info = info
        self._tid = None

    def __enter__(self):
        self._tid = CommTaskManager.instance().start_task(
            self._op, self._timeout, **self._info
        )
        return self

    def __exit__(self, *exc):
        CommTaskManager.instance().end_task(self._tid)
        return False


def set_timeout_handler(fn: Optional[Callable]) -> Callable:
    return CommTaskManager.instance().set_timeout_handler(fn)


def set_abort_handler(fn: Optional[Callable]) -> Callable:
    return CommTaskManager.instance().set_abort_handler(fn)


def set_warn_handler(fn: Optional[Callable]) -> Optional[Callable]:
    return CommTaskManager.instance().set_warn_handler(fn)
