"""Device self time of the load monitor's windowed peak and median (scope
``sim.monitor``), a scan over the call's rates that runs before the tick
scan, over the traced calls' arch-ticks (ns per arch-tick).  An op's
scope comes from the compiled module (``harness.spans``)."""
SCOPE = "sim.monitor"


def read(ctx):
    return ctx["scope_ns_per_arch_tick"].get(SCOPE)
