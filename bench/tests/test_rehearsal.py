"""Each cell's traffic through the whole harness on the CPU, resized to
64 streams x 600 ticks: the result line's keys, and the comparison with
the reference passing at several seeds."""
import json

import pytest

import run as bench_run
from small import cells, run_small

REQUIRED = {"correct", "attempted", "failed", "metrics", "device"}
SEEDS = (579410465, 2**31 + 11, 12345)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("workload", cells())
def test_cell_is_correct_and_well_formed(workload, seed):
    res = run_small(workload, seed)
    assert set(res) == REQUIRED | {"checks"}
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0, res["checks"]
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {"arch_ticks_per_s", "setup_s"}
    assert res["metrics"]["arch_ticks_per_s"]["value"] > 0
    assert res["device"]["count"] == 1
    for k, v in res["checks"].items():
        assert set(v) == {"value", "limit"} and v["value"] <= v["limit"], k
    json.dumps(res)


def test_traced_run_reads_per_layer_metrics():
    res = run_small(cells()[0], 7, trace=1)
    assert res["correct"] is True
    assert {"host_prep_ms.sim", "scan_ns_per_arch_tick.sim", "assemble_ms.sim",
            "device_idle_share.sim"} <= set(res["metrics"])
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert 1 <= len(res["breakdown"]["device_ops"]) <= 10
    assert len(res["breakdown"]["idle_gaps"]) <= 10
    assert list(res)[-1] == "checks"


def test_seed_changes_the_traffic():
    from harness import traffic
    from small import small_cell

    cell = small_cell(cells()[0])
    a = traffic.realize(cell["traffic"], cell["config"], 1, 0)
    b = traffic.realize(cell["traffic"], cell["config"], 2, 0)
    c = traffic.realize(cell["traffic"], cell["config"], 1, 0)
    assert (a["arrivals"] == c["arrivals"]).all() and a["sim_seeds"] == c["sim_seeds"]
    for i in range(len(a["names"])):
        assert not (a["arrivals"][i] == b["arrivals"][i]).all()
    assert a["sim_seeds"] != b["sim_seeds"]


def test_no_accelerator_exits_without_a_result(capsys):
    rc = bench_run.main(["--workload", cells()[0], "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_a_compile_inside_the_window_fails(monkeypatch):
    import jax
    import numpy as np
    from harness import sut

    call = sut.Program.call
    calls = {"n": 0}

    def compiling(self, real):
        calls["n"] += 1
        if calls["n"] > 1:          # after the warm call: a new program
            jax.jit(lambda x: x + calls["n"])(np.float32(1.0))
        return call(self, real)

    monkeypatch.setattr(sut.Program, "call", compiling)
    with pytest.raises(RuntimeError, match="compiled inside the window"):
        run_small(cells()[0], 3)
