"""The reduction from a trace to busy time, idle share and gaps."""
import pytest

from perfbench import tracing

MS = 1_000_000      # ns


def test_union_merges_overlaps_and_keeps_disjoint_intervals():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [[0, 3], [5, 8]]


def test_reduce_busy_idle_and_gap_labels():
    spans = [("pb.window", 0, 100 * MS),
             ("pb.generate", 0, 10 * MS),
             ("pb.put", 10 * MS, 12 * MS),
             ("pb.fetch", 50 * MS, 95 * MS)]
    ops = [("while.1", 12 * MS, 30 * MS, False),
           ("fusion.2", 20 * MS, 26 * MS, False),     # nested in while.1
           ("fusion.3", 30 * MS, 40 * MS, False),
           ("custom-call.3", 40 * MS, 50 * MS, True),
           ("fusion.1", 90 * MS, 120 * MS, False)]    # runs past the window
    out = tracing.reduce({"/device:TPU:0": ops}, spans)
    assert out["window_s"] == pytest.approx(0.1)
    # busy: [12, 50] and [90, 100] ms
    assert out["busy_s"] == pytest.approx(0.048)
    assert out["kernel_s"] == pytest.approx(0.010)
    gaps = dict(out["idle_gaps"])
    # idle: [0, 12] (mostly generate), [50, 90] (fetch)
    assert gaps == pytest.approx({"pb.generate": 0.012, "pb.fetch": 0.040})
    ops_s = dict(out["device_ops"])
    # self times: the loop less its body; clipped to the window
    assert ops_s["while.1"] == pytest.approx(0.012)
    assert ops_s["fusion.2"] == pytest.approx(0.006)
    assert ops_s["fusion.1"] == pytest.approx(0.010)


def test_reduce_averages_over_devices_that_ran():
    spans = [("pb.window", 0, 10 * MS)]
    devs = {"/device:TPU:0": [("a", 0, 10 * MS, False)],
            "/device:TPU:1": [("a", 0, 5 * MS, False)],
            "/device:TPU:2": [("a", 20 * MS, 30 * MS, False)]}
    out = tracing.reduce(devs, spans)
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx(0.0075)


def test_reduce_refuses_a_trace_without_device_work_or_window():
    with pytest.raises(ValueError):
        tracing.reduce({"/device:TPU:0": [("a", 50, 60, False)]},
                       [("pb.window", 0, 10)])
    with pytest.raises(ValueError):
        tracing.reduce({"/device:TPU:0": [("a", 0, 5, False)]}, [])


HLO_KERNEL = ('%tracker_access.1 = (s32[1,105472]{1,0}) custom-call('
              's32[1024,1]{1,0} %p), custom_call_target="tpu_custom_call"')
HLO_SCORE = ('%score_candidates.16 = f32[8,1]{1,0:T(8,128)S(1)} custom-call('
             '%copy.89, %copy.90), custom_call_target="tpu_custom_call"')
HLO_ALLOC = ('%custom-call.16 = s32[1024]{0} custom-call(), '
             'custom_call_target="AllocateBuffer"')
HLO_LOOP = ('%while.427 = (s32[], s32[1024,32]{1,0}) while((s32[], '
            's32[1024,32]{1,0}) %tuple.1), condition=%c, body=%b')


@pytest.mark.parametrize("hlo,want", [(HLO_KERNEL, True),
                                      (HLO_SCORE, True),
                                      (HLO_ALLOC, False),
                                      (HLO_LOOP, False)])
def test_kernel_detection(hlo, want):
    assert tracing.is_kernel(hlo) is want


def test_labels_are_short_and_keep_name_and_opcode():
    assert tracing.label(HLO_KERNEL).startswith("%tracker_access.1 "
                                                "custom-call")
    assert tracing.label(HLO_LOOP).startswith("%while.427 while")
