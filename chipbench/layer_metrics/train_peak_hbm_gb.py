"""`memory_stats()["peak_bytes_in_use"]` of the fullest device, in GB. The
process's peak: set by the eager recording pass of `to_static`, not by the
compiled step (the log line `window` carries the bytes in use while it
trains)."""


def read(ctx):
    if ctx.mix["loop"] != "train":
        return None
    return ctx.facts["peak_bytes"] / 1e9
