"""Host input preparation per call: from the call's span start to its
first device operation, averaged over the traced calls (ms)."""


def read(ctx):
    prep = [c["prep_ns"] for c in ctx["trace"]["calls"] if c["prep_ns"] is not None]
    return sum(prep) / len(prep) / 1e6 if prep else None
