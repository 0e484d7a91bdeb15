"""The program's spans, counters and tick stages: the reductions on
synthetic events, on a trace recorded on the CPU, and the per-layer
metrics that read the program's own records in a traced run."""
import json

import pytest

from harness import spans, trace


def test_self_time_subtracts_nested_events():
    events = [(0, 100, "a"), (10, 40, "b"), (15, 25, "c"), (50, 60, "b"),
              (120, 130, "a")]
    got = spans.self_time(events, lambda s, n: n)
    assert got == {"a": 100 - 30 - 10 + 10, "b": 30 - 10 + 10, "c": 10}


def test_self_time_orders_events_that_share_a_start():
    # the nested event first: the one that ends later is the outer one
    events = [(0, 10, "inner"), (0, 50, "outer"), (20, 30, "later")]
    got = spans.self_time(events, lambda s, n: n)
    assert got == {"inner": 10, "outer": 30, "later": 10}


def test_self_time_refuses_events_out_of_order():
    with pytest.raises(ValueError):
        spans.self_time([(10, 20, "a"), (5, 8, "b")], lambda s, n: n)


def test_self_time_drops_what_the_bucket_drops():
    events = [(0, 100, "a"), (10, 20, "skip")]
    got = spans.self_time(events, lambda s, n: None if n == "skip" else n)
    assert got == {"a": 90}


def test_span_self_time_per_call():
    calls = [(0, 100), (200, 300)]
    sp = [(0, 100, "bench.call"), (5, 60, "sim.prep.inputs"),
          (10, 30, "sim.prep.template"), (60, 90, "sim.dispatch"),
          (200, 300, "bench.call"), (210, 250, "sim.prep.inputs")]
    per = spans.span_self_ns(calls, sp)
    assert per[0] == {"bench.call": 15, "sim.prep.inputs": 35,
                      "sim.prep.template": 20, "sim.dispatch": 30}
    assert per[1] == {"bench.call": 60, "sim.prep.inputs": 40}
    assert sum(per[0].values()) == 100


@pytest.mark.parametrize("path, stage", [
    ("jit(run)/while/body/closed_call/scan.policy/mul", "scan.policy"),
    ("jit(run)/vmap(jit(grid))/while/body/scan.provision/while/body/add",
     "scan.provision"),
    ("jit(run)/while/body/scan.serve/jit(cumsum)/cumsum", "scan.serve"),
    ("jit(run)/while/body/scan.serves/add", spans.UNSCOPED),
    ("jit(run)/while/body/my_scan.policy/add", spans.UNSCOPED),
    ("jit(run)/while/body/dynamic_update_slice", spans.UNSCOPED),
    ("reduce_window_sum", spans.UNSCOPED),
    ("", spans.UNSCOPED),
])
def test_stage_is_a_whole_path_component(path, stage):
    assert spans.stage_of(path) == stage


def test_idle_goes_to_the_innermost_span():
    sp = [(0, 100, "sim.prep.inputs"), (20, 40, "sim.prep.template"),
          (100, 150, "sim.dispatch")]
    idle = [(10, 30), (35, 45), (90, 120), (150, 170)]
    got = spans.idle_by_span(idle, sp)
    assert got == {"sim.prep.inputs": 10 + 5 + 10, "sim.prep.template": 10 + 5,
                   "sim.dispatch": 20, spans.NO_SPAN: 20}
    assert sum(got.values()) == sum(e - s for s, e in idle)


def test_hlo_op_paths_reads_the_compiled_text():
    text = "\n".join([
        "ENTRY %main.4 (x.1: f32[64]) -> f32[64] {",
        '  %x.1 = f32[64]{0} parameter(0), metadata={op_name="x"}',
        "  %multiply_bitcast_fusion = f32[4,16]{1,0} fusion(%x.1), kind=kLoop, "
        'calls=%f.1, metadata={op_name="jit(f)/scan.policy/mul" stack_frame_id=3}',
        "  %wrapped_slice = f32[4,1]{1,0} fusion(%w), kind=kLoop, calls=%g",
        "  ROOT %add_bitcast_fusion.2 = f32[64]{0} fusion(%a, %b), kind=kLoop, "
        'metadata={op_name="jit(f)/scan.serve/add"}',
        "}"])
    got = spans.hlo_op_paths(text)
    assert got == {"x.1": "x", "multiply_bitcast_fusion": "jit(f)/scan.policy/mul",
                   "add_bitcast_fusion.2": "jit(f)/scan.serve/add"}


def _ctx(n_traced, per_call):
    return {"trace": {"calls": [{}] * n_traced}, "arch_ticks_per_call": per_call}


def test_traced_calls_groups_entry_point_calls(monkeypatch):
    from repro.core.sim import telemetry

    rec = lambda ticks, ms: {spans.ARCH_TICKS: ticks, "sim.dispatch": ms,
                             spans.H2D_BYTES: 2e6}
    # a harness call of two scenarios, one entry-point call each
    monkeypatch.setattr(telemetry, "CALLS", [rec(5, 9.0), rec(5, 9.0),
                                             rec(5, 1.0), rec(5, 2.0),
                                             rec(5, 3.0), rec(5, 4.0),
                                             rec(5, 7.0)])
    got = spans.traced_calls(_ctx(2, 10))
    assert [c["sim.dispatch"] for c in got] == [3.0, 7.0]
    assert spans.mean_over_traced(_ctx(2, 10), "sim.dispatch", 1e3) == 5000.0
    assert spans.mean_over_traced(_ctx(2, 10), spans.H2D_BYTES, 1e-6) == 4.0
    assert spans.mean_over_traced(_ctx(2, 10), "sim.prep.stack", 1e3) is None
    assert spans.traced_calls(_ctx(4, 10)) is None            # too few calls
    assert spans.traced_calls(_ctx(1, 4)) is None             # records do not fit


def test_traced_calls_without_program_records(monkeypatch):
    """A program that keeps no records (one from before the spans) gives
    no reading, and no error."""
    from repro.core.sim import telemetry

    monkeypatch.delattr(telemetry, "CALLS")
    assert spans.traced_calls(_ctx(2, 10)) is None
    assert spans.mean_over_traced(_ctx(2, 10), "sim.dispatch", 1e3) is None


@pytest.fixture
def no_compile_cache():
    import jax

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def test_recorded_cpu_trace_of_run_scenario(tmp_path, no_compile_cache):
    import jax

    from repro.core.sim import jax_engine as je
    from repro.core.sim.types import ArchLoad
    from repro.core.workloads import SCENARIO_ZOO

    A, T = 8, 200
    wl = [ArchLoad(("llama3-8b", "minicpm-2b")[i % 2], 1.0 / A, 0.25, name=f"m@{i}")
          for i in range(A)]
    arr = SCENARIO_ZOO["shared_berkeley"].build(A, duration_s=T, seed=3)
    je.run_scenario(arr, wl, "paragon", seed=1)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(2):
        with jax.profiler.TraceAnnotation(trace.CALL_SPAN):
            je.run_scenario(arr, wl, "paragon", seed=1)
    jax.profiler.stop_trace()

    path = trace.trace_file(str(tmp_path))
    calls, busy, op_ns = trace.read_trace(path, trace.cpu_lines)
    assert len(calls) == 2
    statics, state0, xs = je.build_sim_inputs(arr, wl, seed=1)
    statics["policy"] = je.JAX_POLICIES["paragon"].default_params()
    with jax.enable_x64(True):
        text = je._get_runner("paragon").lower(statics, state0, xs).compile().as_text()
    prog = spans.read_program_trace(path, trace.cpu_lines, calls, spans.hlo_op_paths(text))

    names = {n for _, _, n in prog["spans"]}
    assert {"sim.prep.template", "sim.prep.monitor", "sim.prep.inputs",
            "sim.dispatch", "sim.fetch", "sim.assemble"} <= names
    assert all(any(cs <= s and e <= ce for cs, ce in calls) for s, e, _ in prog["spans"])
    for (cs, ce), own in zip(calls, spans.span_self_ns(calls, prog["spans"])):
        assert set(own) >= {"sim.prep.inputs", "sim.dispatch"}
        assert all(v >= 0 for v in own.values())
        assert sum(own.values()) <= ce - cs
    for stages in prog["stage_ns"]:
        assert {"scan.observe", "scan.policy", "scan.provision", "scan.serve",
                "scan.account"} <= set(stages)
        assert "scan.variants" not in stages
    # the harness's own reduction reads the trace as before
    red = trace.reduce(calls, busy, op_ns)
    assert len(red["calls"]) == 2 and red["idle_gaps"]


def test_traced_rehearsal_reads_the_program_metrics():
    from small import cells, run_small

    from repro.core.sim import telemetry

    host = {"prep_template_ms.sim", "prep_monitor_ms.sim", "prep_inputs_ms.sim",
            "dispatch_ms.sim", "host_assemble_ms.sim", "h2d_mb.sim"}
    for workload in (cells()[0], "paragon-a1024.wide1"):
        telemetry.CALLS.clear()
        res = run_small(workload, 11, trace=1)
        assert res["correct"] is True
        got = set(res["metrics"])
        assert host <= got, workload
        assert ("prep_stack_ms.sim" in got) == workload.endswith("zoo7")
        for k in host:
            assert res["metrics"][k]["value"] > 0, k
        json.dumps(res)


def test_split_on_the_cpu(no_compile_cache):
    import split
    from small import small_cell

    out = split.split("paragon-a1024.zoo7", 5, require_accelerator=False,
                      cell=small_cell("paragon-a1024.zoo7"))
    n = len(out["call_s"]["traced"])
    assert n == len(out["span_self_ms"]) == len(out["stage_ns_per_arch_tick"])
    assert len(out["records"]) == n
    for own, rec in zip(out["span_self_ms"], out["records"]):
        assert {"sim.prep.template", "sim.prep.monitor", "sim.prep.inputs",
                "sim.prep.stack", "sim.dispatch"} <= set(own)
        assert rec[spans.H2D_BYTES] > 0
    for st in out["stage_ns_per_arch_tick"]:
        assert {"scan.observe", "scan.provision", "scan.serve"} <= set(st)
    idle = out["idle_by_span_s"]
    assert 0 < sum(idle.values()) <= out["window_s"]
    json.dumps(out)
