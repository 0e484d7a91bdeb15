"""The runner call, in which the jit boundary takes the host arguments
and starts their copies to the device (copies still running when it
returns are waited for in ``sim.fetch``), averaged over the traced
calls (ms): the self time of the program span ``sim.dispatch``, as the
program recorded it (``harness.spans``)."""
from harness import spans


def read(ctx):
    return spans.mean_over_traced(ctx, "sim.dispatch", 1e3)
