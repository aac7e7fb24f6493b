"""Share of the traced window in which the device is idle while the host is
inside the program's `sched.step` span but outside any `engine.*.fetch`:
admission, page growth, building rows and block tables, transfers, the
dispatch, emitting tokens. With serve_idle_fetch_pct and the idle outside
`sched.step` it adds up to serve_device_idle_pct."""
from chipbench.layer_metrics._program_spans import idle_share


def read(ctx):
    return idle_share(ctx, "host")
