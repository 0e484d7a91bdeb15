"""Building the result dicts on the host from the fetched outputs, per
call, averaged over the traced calls (ms): the self time of the program
span ``sim.assemble``, as the program recorded it (``harness.spans``)."""
from harness import spans


def read(ctx):
    return spans.mean_over_traced(ctx, "sim.assemble", 1e3)
