"""Device self time in no scope the trace is split by: loop control,
carry copies, the tier rings' writes, the ops around the scan, over the
traced calls' arch-ticks (ns per arch-tick).  With the scoped metrics
it sums to the device time of the traced calls (``harness.spans``)."""
from harness import spans


def read(ctx):
    return ctx["scope_ns_per_arch_tick"].get(spans.UNSCOPED)
