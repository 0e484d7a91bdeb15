"""Device busy time inside the traced calls over the arch-ticks they
simulated (ns per arch-tick): the tick scan's cost on the device."""


def read(ctx):
    calls = ctx["trace"]["calls"]
    busy = sum(c["busy_ns"] for c in calls)
    if not calls or busy <= 0:
        return None
    return busy / (len(calls) * ctx["arch_ticks_per_call"])
