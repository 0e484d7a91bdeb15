"""The throwaway template ``ServingSim`` that host preparation reads the
statics from, per call, averaged over the traced calls (ms): the self
time of the program span ``sim.prep.template``, as the program recorded
it (``harness.spans``)."""
from harness import spans


def read(ctx):
    return spans.mean_over_traced(ctx, "sim.prep.template", 1e3)
