"""What every loop shares: the device check, host spans, compile counting,
the traced stretch, the result line. Imports the program nowhere.
"""
import contextlib
import json
import shutil
import sys
import time

from chipbench import peaks, xplane

clock = time.perf_counter


def log(msg, **facts):
    """An earlier line: free text on stdout, never the last one."""
    print(json.dumps({"chipbench": msg, **facts}, default=str), flush=True)


class NoChip(SystemExit):
    pass


def device_facts(chips_wanted: int, allow_cpu: bool = False) -> dict:
    """JAX's own report of what it runs on. Exits non-zero (and so prints
    no result) when there is no accelerator in the table of peaks, or fewer
    chips than the cell asks for. `allow_cpu` is for the tests only: they
    pass it in code, no flag or variable of the command reaches it."""
    import jax

    devs = jax.devices()
    facts = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if allow_cpu:
        return facts
    if facts["platform"] != "tpu":
        raise NoChip(f"chipbench: needs a TPU, jax found {facts}")
    peaks.peak_for(facts["kind"])  # raises UnknownDevice for a chip not in the table
    if facts["count"] < chips_wanted:
        raise NoChip(f"chipbench: the cell asks for {chips_wanted} chip(s), jax found {facts}")
    return facts


def memory_peak_bytes(n_devices: int) -> int:
    import jax

    peak = 0
    for d in jax.devices()[:n_devices]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def memory_in_use_bytes(n_devices: int) -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in jax.devices()[:n_devices])


class CompileCounter:
    """Counts the programs JAX had to compile or load, by its own
    monitoring events; `mark()` and `since(mark)` give the count inside a
    stretch."""

    def __init__(self):
        import jax

        self.n = 0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_kw):
        # one per program the process asks the compiler for, whether the
        # persistent cache then hits or misses (the cache is always on)
        if event.endswith("compile_requests_use_cache"):
            self.n += 1
        elif event.endswith("cache_hits"):
            self.cache["hits"] += 1
        elif event.endswith("cache_misses"):
            self.cache["misses"] += 1

    def mark(self):
        return self.n

    def since(self, mark):
        return self.n - mark


class Spans:
    """Host spans on the host clock, kept in memory. While a trace is
    being taken each span is also a `TraceAnnotation`, so that the
    reduction finds it on the profiler's clock beside the device's ops."""

    def __init__(self):
        self.records = []   # (name, t0, t1) on harness.clock
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name):
        if self.tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(xplane.SPAN_PREFIX + name)
            ann.__enter__()
        t0 = clock()
        try:
            yield
        finally:
            self.records.append((name, t0, clock()))
            if self.tracing:
                ann.__exit__(None, None, None)

    def total(self, name, lo=None, hi=None):
        return sum(t1 - t0 for n, t0, t1 in self.records
                   if n == name and (lo is None or t0 >= lo) and (hi is None or t1 <= hi))

    def durations(self, name, lo=None, hi=None):
        return [t1 - t0 for n, t0, t1 in self.records
                if n == name and (lo is None or t0 >= lo) and (hi is None or t1 <= hi)]


class Tracer:
    """One traced stretch inside the measured window. The trace goes to a
    directory under TMPDIR and is removed once reduced."""

    def __init__(self, spans: Spans):
        self.spans = spans
        self.dir = None
        self._win = None
        self.ir = None

    def start(self):
        import tempfile

        import jax

        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.spans.tracing = True

    def open(self):
        """The traced window starts here: once the loop is back in its
        stride after the profiler's start."""
        self._win = self.spans.span("window")
        self._win.__enter__()

    def stop(self):
        import jax

        if self._win is not None:
            self._win.__exit__(None, None, None)
        self.spans.tracing = False
        jax.profiler.stop_trace()

    def reduce(self):
        try:
            self.ir = xplane.load(xplane.find_trace_file(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return self.ir


def percentile(values, q):
    """Nearest-rank-interpolated percentile (numpy's default), plain."""
    v = sorted(values)
    if not v:
        return None
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def emit_result(correct, attempted, failed, metrics, device, compared, breakdown=None):
    """The last line of stdout, and the numbers compared as the last lines
    of stderr: each beside its limit."""
    line = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    for name, c in compared.items():
        print(f"compared {name}: value {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    print(f"correct: {bool(correct)}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


def compare(name, value, limit, compared):
    """Records a number beside its limit; it holds when it is a number and
    not above the limit."""
    ok = value is not None and value == value and value <= limit
    compared[name] = {"value": value, "limit": limit, "ok": bool(ok)}
    return ok
