"""Helpers shared by the readers (not a metric: no entry names it)."""
from chipbench import xplane


def median(v):
    v = sorted(v)
    n = len(v)
    if not n:
        return None
    return v[n // 2] if n % 2 else 0.5 * (v[n // 2 - 1] + v[n // 2])


def in_window(ctx, records):
    """Records (kind, t, ...) of ctx.events inside the measured window."""
    lo, hi = ctx.facts["t_start"], ctx.facts["t_end"]
    return [r for r in records if lo <= r[1] < hi]


def traced_host_window(ctx):
    """(t0, t1) on the host clock of the traced stretch."""
    for name, t0, t1 in ctx.spans.records:
        if name == "window":
            return t0, t1
    return None


def steps_in_trace(ir):
    """Train steps inside the traced window: an op of the step program runs
    once a step, so the median count of the ten longest-running op names
    is the number of steps."""
    lo, hi = xplane.window_of(ir)
    plane = sorted(ir["devices"])[0]
    count, total = {}, {}
    for name, cat, s, d in ir["devices"][plane]:
        if s >= lo and s + d <= hi:
            count[name] = count.get(name, 0) + 1
            total[name] = total.get(name, 0) + d
    top = sorted(total, key=lambda n: -total[n])[:10]
    return median([count[n] for n in top]) if top else None


def is_matmul(name, cat):
    """A fusion rooted in a matmul (XLA's output fusion on the TPU), or a
    bare convolution or dot."""
    return cat == "fusion:kOutput" or cat in ("convolution", "dot")


def is_pallas(name, cat):
    return cat.startswith("custom-call:tpu_custom_call")


def pallas_with_operand(shape: str):
    """Pallas kernels one of whose operands has this shape, as the trace
    writes it (`bf16[4097,8,16,128]`): how a kernel is told from another
    until each `pallas_call` has a name."""
    def match(name, cat):
        return is_pallas(name, cat) and shape in cat

    return match
