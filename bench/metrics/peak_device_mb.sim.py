"""Peak device memory in use on the fullest chip over the run, as the
runtime's allocator reports it (MB, 1e6 bytes)."""


def read(ctx):
    peak = ctx["memory_peak_bytes"]
    return peak / 1e6 if peak > 0 else None
