"""The trace reduction (``chipbench/trace.py``) on hand-built traces."""
import pytest

from chipbench.trace import Event, op_name, reduce, union

PALLAS = ('%{} = f32[8,128]{{1,0}} custom-call(f32[8,128] %p), '
          'custom_call_target="tpu_custom_call"')


def test_union_merges_overlaps_and_keeps_gaps():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_op_name_strips_the_instance_suffix():
    assert op_name(PALLAS.format("jit_bsr_spmm_masked___.15")) == \
        "jit_bsr_spmm_masked___"
    assert op_name("%fusion.44 = (s32[8]) fusion(...)") == "fusion"
    assert op_name("copy") == "copy"


def test_busy_idle_pallas_and_gaps():
    dev = [Event(PALLAS.format("spmm.1"), 10, 20),  # 10..30
           Event(PALLAS.format("spmm.2"), 25, 15),  # 25..40, overlaps
           Event("%fusion.3 = f32[8] fusion()", 60, 10),  # 60..70
           Event("%copy.1 = f32[8] copy()", 95, 20)]  # 95..115, clipped
    host = [Event("train_epoch", 0, 100),
            Event("_value", 40, 20),  # innermost over the 40..60 gap
            Event("dispatch", 70, 25)]  # innermost over the 70..95 gap
    red = reduce([dev], host, (0, 100))
    assert red.window_ns == 100
    assert red.busy_ns == 30 + 10 + 5
    assert red.pallas_ns == 35
    assert red.pallas_by_op == [("spmm", 35)]
    assert red.top_ops[0] == ("spmm", 35)
    assert dict(red.top_ops)["copy"] == 20  # op time is not clipped
    assert red.idle_gaps == [("dispatch", 25), ("_value", 20),
                             ("train_epoch", 10)]


def test_busy_is_averaged_over_devices():
    a = [Event("%a.1 = f32[1] a()", 0, 100)]
    b = [Event("%b.1 = f32[1] b()", 0, 50)]
    red = reduce([a, b], [], (0, 100))
    assert red.busy_ns == pytest.approx(75)
    assert red.pallas_ns == 0 and red.pallas_by_op == []
    assert red.idle_gaps == [("none", 50)]


def test_at_most_ten_ops_and_gaps():
    dev = [Event(f"%op{i}.1 = f32[1] x()", 10 * i, 5) for i in range(30)]
    red = reduce([dev], [], (0, 300))
    assert len(red.top_ops) == 10 and len(red.idle_gaps) == 10
