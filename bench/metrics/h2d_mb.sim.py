"""Bytes the call hands the device (statics, policy parameters, initial
state, per-tick inputs), averaged over the traced calls (MB): what the
program added to its counter ``sim_h2d_bytes_total`` in each call."""
from harness import spans


def read(ctx):
    return spans.mean_over_traced(ctx, spans.H2D_BYTES, 1e-6)
