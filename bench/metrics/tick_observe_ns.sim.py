"""Device self time of the tick's ``scan.observe`` stage (queue ageing and
admission, the observation the policy reads), over the traced calls'
arch-ticks (ns per arch-tick).  An op's scope comes from the compiled
module (``harness.spans``)."""
SCOPE = "scan.observe"


def read(ctx):
    return ctx["scope_ns_per_arch_tick"].get(SCOPE)
