"""Split one cell's calls by what does the work, from a profiler trace.

    python bench/split.py --workload <cell> --seed <n>

Set-up as ``bench/run.py`` makes it (one warm call), then the traffic
file's ``trace_calls`` calls untraced and as many under the profiler,
each inside the harness's call span.  Prints one JSON object: per call,
the host clock's call time traced and untraced (the profiler's cost);
from the trace, the self time of each program span (``sim.*``), each
scope's device self time per arch-tick (the tick stages ``scan.*`` and
every scope a metric reader names, the rest ``unscoped``), the idle
time under each innermost program span, and the harness's own readings
(``host_prep_ms``, ``scan_ns_per_arch_tick``) of the same calls to
compare the sums with; the device ops that took most self time (ns per
arch-tick of a call), with their stage and ``op_name`` path; and the
program's in-memory records of the traced calls.  Exits non-zero where
JAX finds no accelerator.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]

import run as bench_run  # noqa: E402


def split(workload: str, seed: int, require_accelerator: bool = True,
          cell=None) -> dict:
    from harness import spans, spec

    cell = cell or spec.cell(workload)
    wl = cell["workload"]
    dev = bench_run.device_info(int(wl["chips"]), require_accelerator)
    restore_keys = spans.cache_keys_with_scopes()
    try:
        return _split(workload, seed, cell, dev)
    finally:
        restore_keys()


def _split(workload, seed, cell, dev) -> dict:
    import jax

    from harness import spans, spec, sut, trace
    from harness import traffic as traffic_mod
    from repro.core.sim import jax_engine as je
    from repro.core.sim import telemetry

    cfg, tr = cell["config"], cell["traffic"]
    program = sut.Program(cfg, tr["entry"])
    n = int(tr["trace_calls"])
    reals = [traffic_mod.realize(tr, cfg, seed, k) for k in range(2 * n + 1)]
    program.call(reals[0])

    # the arguments of each runner's last call, to read its compiled
    # module: the trace's op events name only the HLO instruction
    runners = spans.RunnerArgs(je)

    def timed_calls(ks):
        out = []
        for k in ks:
            with jax.profiler.TraceAnnotation(trace.CALL_SPAN):
                t0 = time.perf_counter()
                program.call(reals[k])
                out.append(time.perf_counter() - t0)
        return out

    trace_dir = os.path.join(ROOT, ".bench_trace", "split-" + workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    try:
        untraced_s = timed_calls(range(1, n + 1))
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        traced_s = timed_calls(range(n + 1, 2 * n + 1))
        jax.profiler.stop_trace()
    finally:
        runners.close()
    per_call = 1 if tr["entry"] == "grid" else len(reals[0]["arrivals"])
    records = list(telemetry.CALLS)[-n * per_call:]

    lines = trace.tpu_lines if dev["platform"] == "tpu" else trace.cpu_lines
    path = trace.trace_file(trace_dir)
    calls, busy, op_ns = trace.read_trace(path, lines)
    red = trace.reduce(calls, busy, op_ns)
    prog = spans.read_program_trace(path, lines, calls,
                                    spans.compiled_op_paths(runners.args),
                                    stages=spans.split_scopes(spec.metric_scopes()))
    shutil.rmtree(trace_dir, ignore_errors=True)

    A, T = int(cfg["streams"]), int(cfg["ticks"])
    ticks = len(reals[0]["arrivals"]) * A * T
    lo, hi = calls[0][0], calls[-1][1]
    merged = trace.clip(trace.union([iv for v in busy.values() for iv in v]), lo, hi)
    idle = spans.idle_by_span(trace.gaps(merged, lo, hi), prog["spans"])
    host = spans.span_self_ns(calls, prog["spans"] + [(s, e, trace.CALL_SPAN)
                                                      for s, e in calls])
    ms = lambda d: {k: v / 1e6 for k, v in sorted(d.items())}
    return {
        "workload": workload, "seed": seed,
        "device": {"platform": dev["platform"], "kind": dev["kind"]},
        "arch_ticks_per_call": ticks,
        "call_s": {"untraced": untraced_s, "traced": traced_s},
        "host_prep_ms": [c["prep_ns"] / 1e6 for c in red["calls"]],
        "assemble_ms": [c["assemble_ns"] / 1e6 for c in red["calls"]],
        "scan_ns_per_arch_tick": [c["busy_ns"] / ticks for c in red["calls"]],
        "span_self_ms": [ms(h) for h in host],
        "stage_ns_per_arch_tick": [{k: v / ticks for k, v in sorted(st.items())}
                                   for st in prog["stage_ns"]],
        "top_device_ops": [[op, st, path, ns / (ticks * len(calls))]
                           for op, st, path, ns in prog["top_ops"]],
        "idle_by_span_s": {k: v / 1e9 for k, v in sorted(idle.items())},
        "window_s": (hi - lo) / 1e9,
        "records": records,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    try:
        out = split(args.workload, args.seed)
    except bench_run.NoAccelerator as e:
        bench_run.log(f"[split] {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
