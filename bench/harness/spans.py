"""The program's own spans, counters and tick stages, split by what does
the work.

The fleet engine's entry points (``jax_engine.run_grid``,
``run_scenario``) mark their stages as program spans (``sim.prep.*``,
``sim.dispatch``, ``sim.fetch``, ``sim.assemble``; ``docs/TELEMETRY.md``)
and the tick as named scopes (``scan.*``).  Two readers:

* in the process that ran the calls: the program keeps, per entry-point
  call, each span's self time and what the call added to each counter
  (``repro.core.sim.telemetry.CALLS``).  :func:`traced_calls` picks the
  records of the calls a ``--trace 1`` run traced, for the per-layer
  metrics in ``bench/metrics``;
* from a profiler trace, where the spans lie on the device's clock:
  :func:`read_program_trace` gives each call's span self times, each
  scope's device self time, and the idle time under each innermost
  span (:func:`idle_by_span`).  A ``--trace 1`` run hands the metric
  readers each scope's device time per arch-tick
  (:func:`scope_ns_per_arch_tick`); ``bench/split.py`` prints the
  whole split for a cell.

A trace's op event names only its HLO instruction, so an op's scope
comes from the compiled module of the runner that ran it: the runners'
arguments are kept while they run (:class:`RunnerArgs`), and their
compiled text read after (:func:`compiled_op_paths`), from a program
compiled from this code (:func:`cache_keys_with_scopes`).  The scopes a
trace is split by are :data:`STAGES` and every scope a metric reader
names (:func:`split_scopes`), so a new scope is read by adding one
metric file.

The functions below the readers work on plain tuples, so they are
tested on synthetic events as well as on a recorded trace.
"""
from __future__ import annotations

import bisect
import re
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "sim."
STAGES = ("scan.observe", "scan.variants", "scan.policy", "scan.provision",
          "scan.serve", "scan.account")
#: device time in no stage: loop control, carry copies, the ops around
#: the scan, and ops whose metadata lost the scope
UNSCOPED = "unscoped"
#: idle time under no program span
NO_SPAN = "none"

ARCH_TICKS = "sim_arch_ticks_total"
H2D_BYTES = "sim_h2d_bytes_total"

Event = Tuple[int, int, str]            # (start_ns, end_ns, name)


# ---------------------------------------------------------------------------
# In-process: the program's records of its calls.
# ---------------------------------------------------------------------------
def traced_calls(ctx) -> Optional[List[Dict[str, float]]]:
    """The program's records of the calls the trace covers, one dict per
    harness call (the entry-point calls inside it summed), or ``None``
    where the program keeps none.

    A run is one process: one warm call, then the window, whose first
    calls are traced.  Records are grouped into harness calls by the
    arch-ticks each simulated (``ctx["arch_ticks_per_call"]``)."""
    try:
        from repro.core.sim import telemetry
    except ImportError:
        return None
    records = list(getattr(telemetry, "CALLS", ()))
    per_call = ctx["arch_ticks_per_call"]
    calls, cur = [], {}
    for rec in records:
        for k, v in rec.items():
            cur[k] = cur.get(k, 0.0) + v
        ticks = cur.get(ARCH_TICKS, 0.0)
        if ticks > per_call:
            return None
        if ticks == per_call:
            calls.append(cur)
            cur = {}
    n = len(ctx["trace"]["calls"])
    if n == 0 or len(calls) < 1 + n:
        return None
    return calls[1:1 + n]


def mean_over_traced(ctx, key: str, scale: float) -> Optional[float]:
    """``key`` of the traced calls' records, averaged per call, times
    ``scale``; ``None`` where a traced call has no such entry."""
    calls = traced_calls(ctx)
    if not calls or any(key not in c for c in calls):
        return None
    return sum(c[key] for c in calls) / len(calls) * scale


# ---------------------------------------------------------------------------
# Reductions on plain events.
# ---------------------------------------------------------------------------
def self_time(events: Iterable[Event],
              bucket: Callable[[int, str], Optional[object]]) -> Dict[object, int]:
    """Sum each event's self time (its duration minus the events nested
    in it) into ``bucket(start, name)``; ``None`` drops it.  Events come
    ordered by start, an event wholly inside the one open before it
    being nested in that one; events with one start may come in any
    order.  Raises ``ValueError`` on events out of order."""
    out: Dict[object, int] = {}
    stack: List[list] = []                  # [end, bucket, self_ns]

    def close(until: float) -> None:
        while stack and stack[-1][0] <= until:
            _, b, own = stack.pop()
            if b is not None:
                out[b] = out.get(b, 0) + own

    def push(s: int, e: int, name: str) -> None:
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, bucket(s, name), e - s])

    group: List[Event] = []                 # events sharing one start
    for ev in events:
        if group and ev[0] != group[0][0]:
            if ev[0] < group[0][0]:
                raise ValueError(f"events out of order at {ev[0]}")
            for g in sorted(group, key=lambda g: -g[1]):    # outermost first
                push(*g)
            group = []
        group.append(ev)
    for g in sorted(group, key=lambda g: -g[1]):
        push(*g)
    close(float("inf"))
    return out


def call_index(calls: Sequence[Tuple[int, int]], t: int) -> Optional[int]:
    """The call whose ``[start, end)`` holds ``t`` (calls sorted)."""
    i = bisect.bisect_right([s for s, _ in calls], t) - 1
    return i if i >= 0 and t < calls[i][1] else None


def span_self_ns(calls: Sequence[Tuple[int, int]],
                 spans: Sequence[Event]) -> List[Dict[str, int]]:
    """Per call, the self time of each program span inside it."""
    per = [dict() for _ in calls]
    for (i, name), ns in self_time(sorted(spans, key=lambda e: (e[0], -e[1])),
                                   lambda s, n: _in_call(calls, s, n)).items():
        per[i][name] = ns
    return per


def _in_call(calls, s, name):
    i = call_index(calls, s)
    return None if i is None else (i, name)


def split_scopes(named: Iterable[str]) -> Tuple[str, ...]:
    """The scopes a trace is split by: :data:`STAGES`, then each of
    ``named`` that is not one of them."""
    return STAGES + tuple(s for s in dict.fromkeys(named) if s not in STAGES)


#: a path component a transformation renamed: ``vmap(sim.monitor)`` is
#: the scope ``sim.monitor`` entered first inside a vmapped function
_TRANSFORMED = re.compile(r"^(?:[\w.\-]+\()+([^()]*)\)+$")


def stage_of(op_path: str, stages: Sequence[str] = STAGES) -> str:
    """The scope of an op from its HLO ``op_name`` path: the last path
    component that is one of ``stages`` by its whole name, or wraps one
    in a transformation's name, else :data:`UNSCOPED`."""
    for comp in reversed(op_path.split("/")):
        m = _TRANSFORMED.match(comp)
        name = m.group(1) if m else comp
        if name in stages:
            return name
    return UNSCOPED


def scope_ns_per_arch_tick(stage_ns: Sequence[Dict[str, int]],
                           arch_ticks_per_call: int) -> Dict[str, float]:
    """Each scope's device self time summed over the calls
    (:func:`read_program_trace`'s ``stage_ns``) over the arch-ticks they
    simulated; a scope that ran on no op has no entry."""
    total: Dict[str, int] = {}
    for st in stage_ns:
        for k, v in st.items():
            total[k] = total.get(k, 0) + v
    ticks = len(stage_ns) * arch_ticks_per_call
    return {k: v / ticks for k, v in total.items()} if ticks else {}


def idle_by_span(idle: Sequence[Tuple[int, int]],
                 spans: Sequence[Event]) -> Dict[str, int]:
    """Idle time under each innermost program span (the one that started
    last, the shorter on a tie), and under none (:data:`NO_SPAN`)."""
    out: Dict[str, int] = {}
    for g0, g1 in idle:
        cover = [sp for sp in spans if sp[0] < g1 and sp[1] > g0]
        cuts = sorted({g0, g1, *(t for s, e, _ in cover for t in (s, e) if g0 < t < g1)})
        for a, b in zip(cuts, cuts[1:]):
            inside = [sp for sp in cover if sp[0] <= a and sp[1] >= b]
            name = max(inside, key=lambda sp: (sp[0], -sp[1]))[2] if inside else NO_SPAN
            out[name] = out.get(name, 0) + b - a
    return out


_HLO_LINE = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name="([^"]*)"')


def hlo_op_paths(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: op_name path}`` from a compiled module's text
    (``Compiled.as_text()``), for traces whose op events carry only the
    instruction's name."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


# ---------------------------------------------------------------------------
# The compiled modules of the runners a run used.
# ---------------------------------------------------------------------------
def cache_keys_with_scopes() -> Callable[[], None]:
    """Key JAX's persistent compilation cache by the program's metadata
    as well, until the returned function is called.  The key leaves the
    metadata out by default, so a program cached from other code could
    come back with other scopes, or none; keyed by it, a cached program
    carries this code's scopes, and only the first run compiles."""
    import jax

    was = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return lambda: jax.config.update("jax_compilation_cache_include_metadata_in_key", was)


class RunnerArgs:
    """While open, every runner the fleet engine hands out from its
    registry (``jax_engine._RUNNERS``) keeps the arguments of its last
    call in :attr:`args`, ``{registry key: (runner, args)}``.
    :meth:`close` puts the registry back: the same dict, holding every
    runner made while open, unwrapped."""

    def __init__(self, engine):
        self.engine, self.registry = engine, engine._RUNNERS
        self.args: Dict[Any, tuple] = {}
        keep = self.args

        class Keeping(dict):
            def __getitem__(self, key):
                fn = dict.__getitem__(self, key)

                def run(*a):
                    keep[key] = (fn, a)
                    return fn(*a)
                return run

        engine._RUNNERS = Keeping(self.registry)

    def close(self) -> None:
        self.registry.update(self.engine._RUNNERS)
        self.engine._RUNNERS = self.registry


def compiled_op_paths(args: Dict[Any, tuple]) -> Dict[str, str]:
    """Instruction-to-``op_name`` map of every runner in ``args``
    (:attr:`RunnerArgs.args`), from its compiled module.  The runners
    ran these arguments already, so their executables are in memory and
    nothing compiles."""
    import jax

    out: Dict[str, str] = {}
    with jax.enable_x64(True):
        for fn, a in args.values():
            out.update(hlo_op_paths(fn.lower(*a).compile().as_text()))
    return out


# ---------------------------------------------------------------------------
# From a profiler trace.
# ---------------------------------------------------------------------------
def read_program_trace(path: str, lines, calls: Sequence[Tuple[int, int]],
                       op_paths: Dict[str, str], top: int = 15,
                       stages: Sequence[str] = STAGES) -> dict:
    """From one trace file and the harness's call spans (``(start,
    end)``, sorted): ``spans``, the program's spans as ``(start, end,
    name)``; ``stage_ns``, per call the device self time of each of
    ``stages`` and of :data:`UNSCOPED`; ``top_ops``, the ``top`` device
    ops by self time over the calls, as ``(op, stage, op_name path,
    ns)``.  An op event names only its HLO instruction (on the TPU and
    on the CPU alike), so its stage comes from ``op_paths``, the
    compiled module's instruction-to-path map (:func:`hlo_op_paths`).
    ``lines`` gives the roles of a trace line, as in
    :mod:`harness.trace`."""
    import jax

    from harness.trace import op_name

    pd = jax.profiler.ProfileData.from_file(path)
    spans: List[Event] = []

    def op_in_call(s: int, ev) -> Optional[tuple]:
        i = call_index(calls, s)
        return None if i is None else (i, op_name(ev.name).lstrip("%"))

    op_ns: Dict[tuple, int] = {}
    lo, hi = (calls[0][0], calls[-1][1]) if calls else (0, 0)
    for plane in pd.planes:
        for line in plane.lines:
            roles = lines(plane.name, line.name)
            if plane.name.startswith("/host:") and not roles:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        spans.append((s, s + int(ev.duration_ns), ev.name))
            if "ops" in roles:
                evs = ((int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns), ev)
                       for ev in line.events)
                part = self_time(((s, e, ev) for s, e, ev in evs if e > lo and s < hi),
                                 op_in_call)
                for k, v in part.items():
                    op_ns[k] = op_ns.get(k, 0) + v
    per_call: List[Dict[str, int]] = [dict() for _ in calls]
    per_op: Dict[str, int] = {}
    for (i, op), ns in op_ns.items():
        st = stage_of(op_paths.get(op, ""), stages)
        per_call[i][st] = per_call[i].get(st, 0) + ns
        per_op[op] = per_op.get(op, 0) + ns
    ranked = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {"spans": sorted(spans), "stage_ns": per_call,
            "top_ops": [(op, stage_of(op_paths.get(op, ""), stages),
                         op_paths.get(op, ""), ns)
                        for op, ns in ranked]}
