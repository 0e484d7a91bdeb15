"""The load monitor's pass over the arrival matrix
(``pool_stats_trajectory``), per call, averaged over the traced calls
(ms): the self time of the program span ``sim.prep.monitor``, as the
program recorded it (``harness.spans``)."""
from harness import spans


def read(ctx):
    return spans.mean_over_traced(ctx, "sim.prep.monitor", 1e3)
