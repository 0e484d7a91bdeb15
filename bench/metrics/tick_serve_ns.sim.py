"""Device self time of the tick's ``scan.serve`` stage (serving against
capacity, offload to burst, drops), over the traced calls' arch-ticks
(ns per arch-tick).  An op's scope comes from the compiled module
(``harness.spans``)."""
SCOPE = "scan.serve"


def read(ctx):
    return ctx["scope_ns_per_arch_tick"].get(SCOPE)
