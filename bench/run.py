"""Run one benchmark cell once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up: the device check, the persistent compilation cache, the cell's
traffic realized from ``--seed`` on the host, and one warm call that
compiles (or loads from the cache) the one program the window runs.
The window: closed-loop calls of the program's entry point, each on the
next realization, until ``--seconds`` have passed; the window holds
whole calls.  Nothing may compile inside it.  Then the check: a sample
of the window's answers, drawn from the seed, is recomputed by the plain
reference and compared.  Each answer's reference job is its
``arrivals``, the configuration (``cfg``), the configuration's float
``dtype`` and the ``sim_seed`` the program ran that answer with.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` runs
the window under the profiler and reports the per-layer metrics read
from the trace.  The traced run keys the persistent cache by the
program's metadata too, so that the program it runs carries this code's
scopes, and keeps the arguments each runner got in the warm call; after
the window it reads the runners' compiled text, which maps each device
op of the trace to its program scope.  The last line of standard
output is one JSON object; the compared numbers and their limits are
the last lines of standard error.  Exits non-zero, printing no result,
where JAX finds no accelerator or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]


class NoAccelerator(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_info(chips: int, require_accelerator: bool = True) -> dict:
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if require_accelerator and platform == "cpu":
        raise NoAccelerator("no accelerator: JAX's devices are CPUs")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX has {len(devs)}")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": chips, "devices": devs[:chips]}


def compile_cache() -> str:
    """JAX's persistent compilation cache: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


_COMPILES = {"n": 0, "watching": False}


def compiles_so_far() -> int:
    """How many traces and backend compilations JAX has reported in this
    process, from its monitoring events; the first call registers the
    listener."""
    if not _COMPILES["watching"]:
        import jax

        def listen(event, duration, **kw):
            if event.endswith(("backend_compile_duration", "jaxpr_trace_duration")):
                _COMPILES["n"] += 1

        jax.monitoring.register_event_duration_secs_listener(listen)
        _COMPILES["watching"] = True
    return _COMPILES["n"]


def run(args, *, cell=None, require_accelerator: bool = True) -> dict:
    """One run of one cell; returns the result object.  ``cell`` (the
    loaded files, as :func:`harness.spec.cell` gives them) lets a test
    run a resized copy."""
    from harness import check, spans, spec, sut, traffic as traffic_mod

    bench = spec.benchmark()
    cell = cell or spec.cell(args.workload, bench)
    wl, cfg, tr = cell["workload"], cell["config"], cell["traffic"]
    dev = device_info(int(wl["chips"]), require_accelerator)

    import jax

    log(f"[bench] {args.workload} on {dev['count']} x {dev['kind']}; "
        f"cache {compile_cache()}")
    compiles_so_far()
    program = sut.Program(cfg, tr["entry"])
    reals = [traffic_mod.realize(tr, cfg, args.seed, k)
             for k in range(int(tr["realizations"]))]
    if args.trace:
        # the program carries this code's scopes; the window calls the
        # runners unwrapped
        restore_keys = spans.cache_keys_with_scopes()
        runners = spans.RunnerArgs(program.je)
    program.call(reals[0])                       # warm: compile or load
    if args.trace:
        runners.close()
        restore_keys()
    setup_s = time.perf_counter() - T_START
    compiles0 = compiles_so_far()
    log(f"[bench] set-up {setup_s:.3f} s")

    if args.trace:
        # one directory per process: test processes trace side by side
        trace_dir = os.path.join(ROOT, ".bench_trace", f"{args.workload}.{os.getpid()}")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    # the traced run records only its first calls: one call of the scan
    # is millions of device operations
    tracing = bool(args.trace)
    answers, call_s = [], []
    t_win = time.perf_counter()
    while time.perf_counter() - t_win < args.seconds:
        real = reals[(len(answers) + 1) % len(reals)]   # the warm call had reals[0]
        with jax.profiler.TraceAnnotation("bench.call"):
            t0 = time.perf_counter()
            out = program.call(real)
            call_s.append(time.perf_counter() - t0)
        answers.append([(real, i, sut.extract(r)) for i, r in enumerate(out)])
        if tracing and len(answers) == int(tr["trace_calls"]):
            jax.profiler.stop_trace()
            tracing = False
    if tracing:
        jax.profiler.stop_trace()
    if compiles_so_far() != compiles0:
        raise RuntimeError(
            f"compiled inside the window: traces and compiles "
            f"{compiles0} -> {compiles_so_far()}")

    n_cells = len(answers[0])
    A, T = int(cfg["streams"]), int(cfg["ticks"])
    arch_ticks = len(answers) * n_cells * A * T
    stats = [d.memory_stats() or {} for d in dev["devices"]]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": peak}
    log(f"[bench] window: {len(answers)} calls, {sum(call_s):.3f} s of calls")

    breakdown = None
    if args.trace:
        from harness import trace as trace_mod

        op_paths = spans.compiled_op_paths(runners.args)
        path = trace_mod.trace_file(trace_dir)
        lines = trace_mod.tpu_lines if dev["platform"] == "tpu" else trace_mod.cpu_lines
        calls, busy, op_ns = trace_mod.read_trace(path, lines)
        red = trace_mod.reduce(calls, busy, op_ns)
        scopes = spans.split_scopes(spec.metric_scopes(bench))
        prog = spans.read_program_trace(path, lines, calls, op_paths, stages=scopes)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"trace": red, "arch_ticks_per_call": n_cells * A * T,
               "memory_peak_bytes": peak,
               "scope_ns_per_arch_tick": spans.scope_ns_per_arch_tick(
                   prog["stage_ns"], n_cells * A * T)}
        metrics = {}
        for name, (read, unit) in spec.metric_readers(bench).items():
            v = read(ctx)
            if v is not None:
                metrics[name] = {"value": v, "unit": unit}
        device["busy_s"] = red["busy_ns"] / 1e9
        device["window_s"] = red["window_ns"] / 1e9
        breakdown = {
            "device_ops": [[n, ns / 1e9] for n, ns in red["top_ops"]],
            "idle_gaps": [[n, ns / 1e9] for n, ns in red["idle_gaps"]],
        }
    else:
        metrics = {
            "arch_ticks_per_s": {"value": arch_ticks / sum(call_s),
                                 "unit": "arch-ticks/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    result = {"correct": False, "attempted": len(answers) * n_cells, "failed": 0,
              "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    del out, program
    jax.clear_caches()

    # the check: a sample of the window's answers against the reference
    picks = check.sample(len(answers), n_cells, int(tr["sample"]), args.seed)
    jobs = [{"arrivals": answers[j][i][0]["arrivals"][i], "cfg": cfg,
             "dtype": cfg["precision"], "sim_seed": answers[j][i][0]["sim_seeds"][i]}
            for j, i in picks]
    t0 = time.perf_counter()
    wants = check.references(jobs)
    per_answer = [check.compare(answers[j][i][2], w) for (j, i), w in zip(picks, wants)]
    numbers = check.worst(per_answer)
    limits = cell["limits"]["limits"]
    result["failed"] = sum(not check.judge(a, limits) for a in per_answer)
    result["correct"] = result["failed"] == 0
    log(f"[bench] reference for {len(jobs)} answers: {time.perf_counter() - t0:.3f} s")
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in check.NUMBERS}
    for k in check.NUMBERS:
        log(f"[check] {k} {numbers[k]!r} limit {limits[k]!r}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except NoAccelerator as e:
        log(f"[bench] {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
