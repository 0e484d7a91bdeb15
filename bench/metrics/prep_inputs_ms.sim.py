"""``build_sim_inputs`` for every cell of the call, without the template
and the monitor (statics, initial state, per-tick inputs, spot uniforms,
harvest signal), averaged over the traced calls (ms): the self time of
the program span ``sim.prep.inputs``, as the program recorded it
(``harness.spans``)."""
from harness import spans


def read(ctx):
    return spans.mean_over_traced(ctx, "sim.prep.inputs", 1e3)
