"""The check catches a broken timed path: the harness runs as it does on
the chip, with a fault planted in the program underneath, and
``correct`` must come out false."""
import numpy as np
import pytest

from small import cells, run_small


def _state_unchanged(monkeypatch, je):
    tick = je._tick

    def frozen(state, *args, **kw):
        return state, tick(state, *args, **kw)[1]

    monkeypatch.setattr(je, "_tick", frozen)


def _half_the_streams_left_out(monkeypatch, je):
    build = je.build_sim_inputs

    def half(*args, **kw):
        statics, state0, xs = build(*args, **kw)
        xs["rate"] = xs["rate"].copy()
        xs["rate"][:, xs["rate"].shape[1] // 2:] = 0.0
        return statics, state0, xs

    monkeypatch.setattr(je, "build_sim_inputs", half)


def _answer_altered(monkeypatch, je):
    assemble = je._assemble

    def altered(*args, **kw):
        res = assemble(*args, **kw)
        served = np.array(res["per_arch"]["served_vm"], dtype=np.float64)
        served[0] += 1.0
        res["per_arch"]["served_vm"] = served
        return res

    monkeypatch.setattr(je, "_assemble", altered)


FAULTS = {
    "state_unchanged": _state_unchanged,
    "half_the_streams_left_out": _half_the_streams_left_out,
    "answer_altered": _answer_altered,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", cells())
def test_fault_is_caught(workload, fault, monkeypatch):
    from repro.core.sim import jax_engine as je

    monkeypatch.setattr(je, "_RUNNERS", {})      # retrace with the fault
    FAULTS[fault](monkeypatch, je)
    res = run_small(workload, 424242)
    assert res["correct"] is False, res["checks"]
    assert res["failed"] >= 1
    assert any(v["value"] > v["limit"] for v in res["checks"].values())
