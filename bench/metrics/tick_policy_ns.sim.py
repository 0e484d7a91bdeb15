"""Device self time of the tick's ``scan.policy`` stage (the controller's
decision), without the ``scan.variants`` nested in it, over the traced
calls' arch-ticks (ns per arch-tick).  An op's scope comes from the
compiled module (``harness.spans``)."""
SCOPE = "scan.policy"


def read(ctx):
    return ctx["scope_ns_per_arch_tick"].get(SCOPE)
