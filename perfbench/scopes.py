"""Device time by the program's own names: the readings of a traced
window that ``tracing.reduce`` leaves out.

Same capture, window and self times as ``tracing``; three readings more:

* ``scopes``: device self time under each ``jax.named_scope`` of the
  store's step program (``maintenance``, ``compact`` and its phases,
  ``point_ops``, ``scan_lane``, ``obs_record``, ...).  A TPU v5e trace's
  op events carry only the HLO instruction (no name stack), so an op's
  scopes come from the compiled step's HLO text, where each instruction
  has ``metadata={op_name="jit(engine_step)/maintenance/while/body/..."}``.
  Each op's self time counts once for every scope on its stack; ops with
  none, and ops of other programs, count under ``(none)``.
* ``step_times``: the device time of each execution of the step program
  that starts in the window (the trace's ``XLA Modules`` line).
* ``program_spans``: the store's own host spans (``prism.*``), as
  ``{name: [count, seconds]}``; ``idle_gaps_program`` labels each idle
  gap of the device by the ``prism.*`` span that overlaps it most.

``harness.run_cell`` does not call this module yet (PERF.md, Open
questions): it removes the trace in ``tracing.reduce_file``.
"""
from __future__ import annotations

import bisect
import collections
import re

from perfbench import tracing

PROGRAM = "jit_engine_step"         # the step's module name in a trace
PROGRAM_SPAN = "prism."
MODULE_LINE = "XLA Modules"
NONE = "(none)"
# name-stack entries JAX adds for control flow, not the program's scopes
_STRUCTURE = re.compile(r"while|body|cond|closed_call|branch_\d+_fun")
_SCOPE = re.compile(r"[A-Za-z_]\w*")
_INSTR = re.compile(r'^\s*(?:ROOT )?%(\S+) = .*?op_name="([^"]*)"', re.M)


def scope_stack(op_name: str) -> tuple:
    """The named scopes on an ``op_name`` path, outermost first: every
    entry but the first (``jit(...)``) and the last (the primitive),
    less JAX's own ``jit(...)``/``vmap(...)`` and control-flow entries."""
    return tuple(p for p in op_name.split("/")[1:-1]
                 if _SCOPE.fullmatch(p) and not _STRUCTURE.fullmatch(p))


def op_scopes(hlo_text: str) -> dict:
    """{instruction name: scope stack} from a compiled module's text."""
    return {name: scope_stack(path)
            for name, path in _INSTR.findall(hlo_text)}


def instruction(hlo: str) -> str:
    """The instruction name of an op event's HLO text."""
    return hlo.partition(" = ")[0].strip().lstrip("%")


def read(path: str) -> tuple[dict, dict, list]:
    """From an ``.xplane.pb`` file: op events ``{device: [(instruction,
    start_ns, end_ns)]}``, executions of the step program ``{device:
    [(start_ns, end_ns)]}`` and host spans ``[(name, start_ns, end_ns)]``
    (the benchmark's window and the store's ``prism.*`` spans)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, runs, spans = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == tracing.OP_LINE:
                    ops[plane.name] = [
                        (instruction(ev.name), ev.start_ns,
                         ev.start_ns + ev.duration_ns) for ev in line.events]
                elif line.name == MODULE_LINE:
                    runs[plane.name] = [
                        (ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events
                        if ev.name.startswith(PROGRAM + "(")]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if (ev.name == tracing.WINDOW_SPAN
                            or ev.name.startswith(PROGRAM_SPAN)):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return ops, runs, spans


def _inside(intervals, t) -> bool:
    """Whether ``t`` lies in one of the sorted disjoint ``intervals``."""
    i = bisect.bisect_right(intervals, (t, float("inf"))) - 1
    return i >= 0 and intervals[i][0] <= t < intervals[i][1]


def reduce(ops: dict, runs: dict, spans: list, scopes: dict) -> dict:
    """``ops``, ``runs`` and ``spans`` as ``read`` gives them; ``scopes``
    as ``op_scopes`` gives it for the step program.  Times in seconds,
    averaged over the devices that ran an op in the window, as
    ``tracing.reduce`` averages them."""
    window = [(s, e) for n, s, e in spans if n == tracing.WINDOW_SPAN]
    if not window:
        raise ValueError(f"the trace holds no {tracing.WINDOW_SPAN} span")
    lo, hi = window[0]
    host = [(n, s, e) for n, s, e in spans
            if n != tracing.WINDOW_SPAN and e > lo and s < hi]
    by_scope = collections.Counter()
    by_gap = collections.Counter()
    steps, used = [], 0
    for dev, events in ops.items():
        inside = [(n, max(s, lo), min(e, hi), None) for n, s, e in events
                  if e > lo and s < hi]
        if not inside:
            continue
        used += 1
        step = sorted(runs.get(dev, ()))
        steps += [(e - s) / 1e9 for s, e in step if lo <= s < hi]
        for (name, s, _, _), t in tracing.self_times(inside):
            stack = set(scopes.get(name, ())) if _inside(step, s) else ()
            for scope in stack or (NONE,):
                by_scope[scope] += t
        busy = tracing.union([(s, e) for _, s, e, _ in inside])
        for g in tracing.gaps(busy, lo, hi):
            by_gap[tracing.label_gap(g, host)] += g[1] - g[0]
    if not used:
        raise ValueError("no device operation ran in the traced window")
    program = collections.defaultdict(lambda: [0, 0.0])
    for n, s, e in host:
        if lo <= s < hi:
            program[n][0] += 1
            program[n][1] += (e - s) / 1e9
    return {
        "scopes": [[n, v / used / 1e9] for n, v in by_scope.most_common()],
        "step_times": steps,
        "program_spans": dict(program),
        "idle_gaps_program": [[n, v / used / 1e9]
                              for n, v in by_gap.most_common(tracing.TOP)],
    }
