"""The traced run's split of device time by program scope: each cell's
tick-stage metrics through the whole harness on the CPU, a scope that
no harness file names read by adding one metric file, and the window
calling the runners the warm call left unwrapped."""
import argparse
import json
import os
import shutil

import pytest

import run as bench_run
from harness import spans, spec
from small import STREAMS, TICKS, cells, run_small, small_cell

STAGE_METRICS = ("tick_observe_ns.sim", "tick_variants_ns.sim", "tick_policy_ns.sim",
                 "tick_provision_ns.sim", "tick_serve_ns.sim", "tick_account_ns.sim",
                 "tick_monitor_ns.sim", "tick_unscoped_ns.sim")


def _op_self_ns(path, lines, calls) -> int:
    """The self time of every device op inside the calls, read straight
    from the trace, with no scope."""
    import jax

    lo, hi = calls[0][0], calls[-1][1]
    total = 0
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            if "ops" not in lines(plane.name, line.name):
                continue
            evs = [(int(e.start_ns), int(e.start_ns) + int(e.duration_ns), e.name)
                   for e in line.events]
            inside = spans.self_time(
                [ev for ev in evs if ev[1] > lo and ev[0] < hi],
                lambda s, n: True if spans.call_index(calls, s) is not None else None)
            total += inside.get(True, 0)
    return total


@pytest.mark.parametrize("workload", cells())
def test_traced_run_splits_the_device_time_by_stage(workload, monkeypatch):
    """Each stage metric the cell lists is there and non-negative, and
    together they are the device time of the traced calls.  On the chip
    that is ``scan_ns_per_arch_tick.sim``; on the CPU the backend runs
    ops on several threads at once, so their sum is compared with the
    trace's own op time instead."""
    read = spans.read_program_trace
    seen = {}

    def keep(path, lines, calls, *a, **kw):
        seen["op_ns"] = _op_self_ns(path, lines, calls)
        seen["calls"] = len(calls)
        return read(path, lines, calls, *a, **kw)

    monkeypatch.setattr(spans, "read_program_trace", keep)
    res = run_small(workload, 13, trace=1)
    assert res["correct"] is True, res["checks"]
    listed = {m["name"] for m in spec.benchmark()["per_layer"]
              if m["name"] in STAGE_METRICS and workload in m.get("workloads", [workload])}
    got = {k: v["value"] for k, v in res["metrics"].items() if k in STAGE_METRICS}
    assert set(got) == listed
    assert ("tick_variants_ns.sim" in got) == workload.startswith("infaas")
    assert all(v >= 0 for v in got.values()), got
    for k in STAGE_METRICS:
        if k in got:
            assert res["metrics"][k]["unit"] == "ns"
    n_cells = len(small_cell(workload)["traffic"]["scenarios"])
    per_call = seen["op_ns"] / (seen["calls"] * n_cells * STREAMS * TICKS)
    assert sum(got.values()) == pytest.approx(per_call, rel=0.01)
    json.dumps(res)


def test_a_new_scope_is_read_by_adding_one_metric_file(tmp_path, monkeypatch):
    """A metric file naming a scope no harness file names (``scan.tiers``,
    here wrapped round the harvest tier's tick work) gets its reading."""
    import jax

    from repro.core.sim import jax_engine as je

    workload = "paragon-a1024.wide1"
    cell = small_cell(workload)
    shutil.copytree(os.path.join(spec.BENCH_DIR, "metrics"), tmp_path / "metrics")
    (tmp_path / "metrics" / "tick_tiers_ns.sim.py").write_text(
        'SCOPE = "scan.tiers"\n\n\ndef read(ctx):\n'
        '    return ctx["scope_ns_per_arch_tick"].get(SCOPE)\n')
    bench = spec.benchmark()
    bench["per_layer"].append(
        {"name": "tick_tiers_ns.sim", "unit": "ns", "better": "lower",
         "source": "device_trace", "layer": "tick scan", "moves": "arch_ticks_per_s"})
    monkeypatch.setattr(spec, "BENCH_DIR", str(tmp_path))
    monkeypatch.setattr(spec, "benchmark", lambda: bench)
    monkeypatch.setattr(je, "_RUNNERS", {})      # retrace with the scope
    begin = je._harvest_begin

    def scoped(*args, **kw):
        with jax.named_scope("scan.tiers"):
            return begin(*args, **kw)

    monkeypatch.setattr(je, "_harvest_begin", scoped)
    args = argparse.Namespace(workload=workload, seed=17, seconds=0.3, trace=1)
    res = bench_run.run(args, cell=cell, require_accelerator=False)
    assert res["correct"] is True
    assert res["metrics"]["tick_tiers_ns.sim"]["value"] > 0
    assert "tick_provision_ns.sim" in res["metrics"]


def test_the_window_calls_the_runners_unwrapped(monkeypatch):
    """The warm call of a traced run goes through the wrapped registry;
    every window call finds the engine's own registry again, holding the
    runners themselves."""
    import jax

    from harness import sut
    from repro.core.sim import jax_engine as je

    registry = {}
    monkeypatch.setattr(je, "_RUNNERS", registry)
    call = sut.Program.call
    seen = []

    def watched(self, real):
        seen.append((je._RUNNERS is registry, dict(je._RUNNERS)))
        return call(self, real)

    monkeypatch.setattr(sut.Program, "call", watched)
    res = run_small(cells()[0], 19, trace=1)
    assert res["correct"] is True
    assert len(seen) >= 2
    assert seen[0][0] is False                  # the warm call: wrapped
    assert je._RUNNERS is registry and registry
    for same, runners in seen[1:]:
        assert same and runners == registry
    jitted = type(jax.jit(lambda x: x))
    assert all(type(fn) is jitted for fn in registry.values())


def test_split_prints_the_monitor_beside_the_tick_stages():
    """``split.py``'s ``stage_ns_per_arch_tick`` keeps the tick stages and
    ``unscoped``, and gains ``sim.monitor``, which runs vmapped on the
    grid."""
    import split

    workload = "paragon-a1024.zoo7"
    out = split.split(workload, 23, require_accelerator=False, cell=small_cell(workload))
    assert out["stage_ns_per_arch_tick"]
    for st in out["stage_ns_per_arch_tick"]:
        assert {"scan.observe", "scan.policy", "scan.provision", "scan.serve",
                "scan.account", "sim.monitor", spans.UNSCOPED} <= set(st)
        assert "scan.variants" not in st
        assert all(v >= 0 for v in st.values())
