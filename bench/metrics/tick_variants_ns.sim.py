"""Device self time of the tick's ``scan.variants`` stage, on runs with a
variant catalog only (the swap pipeline and the ``[A, V]`` catalog
gathers), over the traced calls' arch-ticks (ns per arch-tick).  An op's
scope comes from the compiled module (``harness.spans``)."""
SCOPE = "scan.variants"


def read(ctx):
    return ctx["scope_ns_per_arch_tick"].get(SCOPE)
