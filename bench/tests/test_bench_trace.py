"""Trace reduction on small synthetic event lists (no chip needed)."""

import pytest

from bench import kernel_events, trace
from bench.trace import Event


def test_union_merges_overlapping_and_touching_intervals():
    got = trace.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 12), (11, 11.5)])
    assert got == [(0, 4), (5, 7), (10, 12)]


def test_busy_and_idle_share_count_overlap_once_and_clip_to_the_window():
    ops = [Event("a", 0, 40), Event("b", 20, 40), Event("c", 90, 30)]
    window = (10, 110)  # busy: 10..60 and 90..110 -> 70 of 100
    assert trace.busy_ns(ops, window, 1) == 70
    assert trace.idle_share(ops, window) == pytest.approx(0.30)


def test_busy_is_averaged_over_devices():
    ops = [Event("a", 0, 100, device=0), Event("a", 0, 50, device=1)]
    assert trace.busy_ns(ops, (0, 100), 2) == 75


def test_kernel_versus_other_attribution():
    kern = ('%mttkrp_pallas_call.8 = f32[28928,128]{1,0} custom-call(s32[15318]{0} %a), '
            'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}')
    ops = [Event(kern, 0, 30), Event("%fusion.12 = f32[8,16]{1,0} fusion(%x)", 30, 60),
           Event(kern, 80, 20), Event("%select_maximum_fusion = f32[2,8,128] fusion()", 100, 7)]
    assert kernel_events.split_ns(ops) == (50, 67)
    assert trace.top_ops(ops)[0] == ["%fusion.12", pytest.approx(6e-8)]


def test_top_ops_and_idle_gaps_named_by_host_span():
    ops = [Event("k", 0, 10), Event("f", 50, 10), Event("k", 100, 5)]
    spans = [Event("window", 0, 200), Event("job", 0, 110), Event("block_until_ready", 10, 40),
             Event("tick", 105, 95)]
    assert trace.top_ops(ops) == [["k", pytest.approx(1.5e-8)], ["f", pytest.approx(1e-8)]]
    gaps = trace.idle_gaps(ops, spans, (0, 200))
    assert gaps[0] == ["tick", pytest.approx(95e-9)]
    assert gaps[1] == ["block_until_ready", pytest.approx(40e-9)]
    assert gaps[2] == ["job", pytest.approx(40e-9)]
