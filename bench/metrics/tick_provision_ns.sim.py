"""Device self time of the tick's ``scan.provision`` stage (every tier's
target and launches, spot reclaims, the harvest ceiling), over the
traced calls' arch-ticks (ns per arch-tick).  An op's scope comes from
the compiled module (``harness.spans``)."""
SCOPE = "scan.provision"


def read(ctx):
    return ctx["scope_ns_per_arch_tick"].get(SCOPE)
