"""The trace-to-metric reduction, on synthetic intervals and on a trace
recorded on the CPU."""
import numpy as np
import pytest

from harness import trace


def test_union_gaps_and_busy():
    merged = trace.union([(0, 10), (5, 20), (30, 40), (40, 45), (50, 51)])
    assert merged == [(0, 20), (30, 45), (50, 51)]
    assert trace.busy_ns(merged) == 36
    assert trace.gaps(merged, -5, 60) == [(-5, 0), (20, 30), (45, 50), (51, 60)]
    assert trace.clip(merged, 10, 35) == [(10, 20), (30, 35)]


def test_per_call_split_and_labels():
    spans = [(0, 100), (120, 200), (210, 220)]
    merged = [(10, 30), (40, 90), (150, 160)]
    calls = trace.per_call(spans, merged)
    assert calls[0] == {"span_ns": 100, "prep_ns": 10, "busy_ns": 70, "assemble_ns": 10}
    assert calls[1] == {"span_ns": 80, "prep_ns": 30, "busy_ns": 10, "assemble_ns": 40}
    assert calls[2]["prep_ns"] is None and calls[2]["busy_ns"] == 0
    assert trace.label_gap((0, 10), spans, merged) == "prep"
    assert trace.label_gap((30, 40), spans, merged) == "in_call"
    assert trace.label_gap((90, 100), spans, merged) == "assemble"
    assert trace.label_gap((100, 120), spans, merged) == "harness"


def test_reduce_averages_busy_over_devices():
    spans = [(0, 100)]
    busy = {"d0": [(10, 60), (50, 70)], "d1": [(0, 20)]}
    red = trace.reduce(spans, busy, {"a": 70, "b": 20, "c": 90})
    assert red["window_ns"] == 100
    assert red["busy_ns"] == (60 + 20) / 2
    assert red["top_ops"][:2] == [("c", 90), ("a", 70)]
    assert red["calls"][0]["prep_ns"] == 0 and red["calls"][0]["assemble_ns"] == 30
    label, ns = red["idle_gaps"][0]
    assert (label, ns) == ("assemble", 30)


def test_op_name_drops_the_hlo_text():
    assert trace.op_name("%while.288 = (u32[], f32[7,1024]) while(...)") == "%while.288"
    assert trace.op_name("subtract_maximum_fusion") == "subtract_maximum_fusion"


def test_reduce_without_spans_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce([], {}, {})


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ jnp.cos(x).T)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((256, 256)))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation(trace.CALL_SPAN):
            y = np.asarray(f(x))
            _ = np.random.default_rng(1).standard_normal((256, 256)) @ y
    jax.profiler.stop_trace()
    spans, busy, op_ns = trace.read_trace(trace.trace_file(str(tmp_path)),
                                          trace.cpu_lines)
    assert len(spans) == 3 and busy and op_ns
    red = trace.reduce(spans, busy, op_ns)
    assert 0 < red["busy_ns"] <= red["window_ns"]
    assert len(red["calls"]) == 3
    for c in red["calls"]:
        assert c["busy_ns"] > 0
        assert c["prep_ns"] >= 0 and c["assemble_ns"] > 0
        assert c["prep_ns"] + c["busy_ns"] + c["assemble_ns"] <= c["span_ns"]
