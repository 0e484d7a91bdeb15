"""Stacking the grid's cells into one batch, and the shard padding, per
call, averaged over the traced calls (ms): the self time of the program
span ``sim.prep.stack``, as the program recorded it (``harness.spans``)."""
from harness import spans


def read(ctx):
    return spans.mean_over_traced(ctx, "sim.prep.stack", 1e3)
