"""Device self time of the tick's ``scan.account`` stage (delivered
accuracy, the cost ledger, the carry's accumulators), over the traced
calls' arch-ticks (ns per arch-tick).  An op's scope comes from the
compiled module (``harness.spans``)."""
SCOPE = "scan.account"


def read(ctx):
    return ctx["scope_ns_per_arch_tick"].get(SCOPE)
