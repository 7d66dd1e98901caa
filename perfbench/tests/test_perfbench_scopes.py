"""The program-side readings of a trace: device time by named scope,
step times and the store's host spans."""
import pytest

from perfbench import scopes, tracing

MS = 1_000_000      # ns

STEP_HLO = """HloModule jit_engine_step, is_scheduled=true

%fused_computation (param_0: s32[8]) -> s32[8] {
  ROOT %add.9 = s32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(engine_step)/maintenance/while/body/compact/merge/add"}
}

ENTRY %main (p: s32[8]) -> s32[8] {
  %while.1 = (s32[], s32[8]{0}) while(%tuple.1), condition=%c, body=%b, metadata={op_name="jit(engine_step)/maintenance/while"}
  %sort.2 = s32[8]{0} sort(%p), metadata={op_name="jit(engine_step)/maintenance/while/body/compact/merge/jit(argsort)/sort"}
  %msc_score.3 = f32[8,1]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(engine_step)/maintenance/while/body/compact/select/jit(score_candidates)/msc_score/pallas_call"}
  %fusion.4 = s32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(engine_step)/scan_lane/vmap()/gather"}
  %fusion.5 = s32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(engine_step)/point_ops/tracker/jit(tracker_access)/clock_update/pallas_call"}
  %copy.6 = s32[8]{0} copy(%p)
  ROOT %tuple.7 = (s32[8]{0}) tuple(%copy.6)
}
"""


def test_scope_stack_keeps_program_scopes_only():
    path = ("jit(engine_step)/maintenance/while/body/cond/branch_1_fun/"
            "compact/vmap(jit(searchsorted))/select/closed_call/sort")
    assert scopes.scope_stack(path) == ("maintenance", "compact", "select")
    assert scopes.scope_stack("jit(engine_step)/add") == ()


def test_op_scopes_maps_every_instruction_with_metadata():
    got = scopes.op_scopes(STEP_HLO)
    assert got["while.1"] == ("maintenance",)
    assert got["sort.2"] == ("maintenance", "compact", "merge")
    assert got["msc_score.3"] == ("maintenance", "compact", "select",
                                  "msc_score")
    assert got["fusion.4"] == ("scan_lane",)
    assert got["fusion.5"] == ("point_ops", "tracker", "clock_update")
    assert got["add.9"] == ("maintenance", "compact", "merge")
    assert "copy.6" not in got          # no metadata: no scope


def test_instruction_name_of_an_op_event():
    assert scopes.instruction(
        "%msc_score.16 = f32[8,1]{1,0} custom-call(%copy.89)") == \
        "msc_score.16"


def _trace():
    spans = [("pb.window", 0, 100 * MS),
             ("prism.put", 0, 9 * MS), ("prism.make_op", 1 * MS, 4 * MS),
             ("prism.dispatch", 4 * MS, 9 * MS),
             ("prism.get", 60 * MS, 70 * MS),
             ("prism.dispatch", 62 * MS, 70 * MS),
             ("prism.get", 120 * MS, 130 * MS)]     # after the window
    # two executions of the step, one of another program, one that
    # starts before the window
    runs = {"/device:TPU:0": [(-20 * MS, 5 * MS), (10 * MS, 50 * MS),
                              (70 * MS, 90 * MS)]}
    ops = {"/device:TPU:0": [
        ("while.1", 0 * MS, 5 * MS),        # step before the window
        ("while.1", 10 * MS, 40 * MS),      # loop: 18 ms of self time
        ("sort.2", 12 * MS, 20 * MS),       # body op, nested
        ("msc_score.3", 20 * MS, 24 * MS),  # body op, nested
        ("fusion.4", 40 * MS, 45 * MS),
        ("copy.6", 45 * MS, 50 * MS),       # no scope
        ("sort.2", 55 * MS, 58 * MS),       # another program's op
        ("fusion.5", 70 * MS, 90 * MS),
    ]}
    return ops, runs, spans


def test_reduce_scopes_are_inclusive_self_times():
    out = scopes.reduce(*_trace(), scopes.op_scopes(STEP_HLO))
    got = dict(out["scopes"])
    assert got == pytest.approx({
        "maintenance": 0.005 + 0.018 + 0.008 + 0.004,
        "compact": 0.012, "merge": 0.008, "select": 0.004,
        "msc_score": 0.004, "scan_lane": 0.005,
        "point_ops": 0.020, "tracker": 0.020, "clock_update": 0.020,
        # the unscoped copy and the other program's sort
        "(none)": 0.005 + 0.003})
    # sorted by time, largest first
    assert [v for _, v in out["scopes"]] == sorted(got.values(),
                                                   reverse=True)


def test_reduce_step_times_and_program_spans():
    out = scopes.reduce(*_trace(), scopes.op_scopes(STEP_HLO))
    assert out["step_times"] == pytest.approx([0.040, 0.020])
    assert out["program_spans"] == {
        "prism.put": [1, pytest.approx(0.009)],
        "prism.make_op": [1, pytest.approx(0.003)],
        "prism.dispatch": [2, pytest.approx(0.013)],
        "prism.get": [1, pytest.approx(0.010)]}
    # idle: [5, 10] (prism.put and prism.dispatch overlap it 4 ms each;
    # the first listed wins), [50, 55] and [90, 100] (no span), [58, 70]
    # (prism.get)
    assert dict(out["idle_gaps_program"]) == pytest.approx({
        "prism.put": 0.005, "no span": 0.015, "prism.get": 0.012})


def test_reduce_agrees_with_tracing_on_the_same_window():
    """Scope times add up to the self times ``tracing.reduce`` ranks:
    every op's self time lands on (none) or on its outermost scope."""
    ops, runs, spans = _trace()
    out = scopes.reduce(ops, runs, spans, scopes.op_scopes(STEP_HLO))
    plain = tracing.reduce(
        {d: [(n, s, e, False) for n, s, e in evs] for d, evs in ops.items()},
        [s for s in spans if s[0] == "pb.window"])
    got = dict(out["scopes"])
    outer = got["maintenance"] + got["scan_lane"] + got["point_ops"] \
        + got["(none)"]
    assert outer == pytest.approx(sum(v for _, v in plain["device_ops"]))


def test_reduce_refuses_a_trace_without_window_or_device_work():
    with pytest.raises(ValueError):
        scopes.reduce({"/device:TPU:0": [("a", 0, 5)]}, {}, [], {})
    with pytest.raises(ValueError):
        scopes.reduce({"/device:TPU:0": [("a", 50, 60)]}, {},
                      [("pb.window", 0, 10)], {})
