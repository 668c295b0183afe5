"""Interval arithmetic of the trace reduction: busy time, idle gaps, and an
operation's own time under nesting."""

import sys

import pytest

from bench_helpers import BENCH

sys.path.insert(0, str(BENCH))
import trace_reduce  # noqa: E402


@pytest.mark.parametrize("intervals,lo,hi,busy,idle", [
    ([(0, 2), (1, 3), (5, 6)], 0, 10, 4, [(3, 5), (6, 10)]),
    ([(2, 4)], 0, 3, 1, [(0, 2)]),
    ([], 0, 5, 0, [(0, 5)]),
    ([(-5, 20)], 0, 10, 10, []),
])
def test_union_and_gaps(intervals, lo, hi, busy, idle):
    assert trace_reduce.union_seconds(intervals, lo, hi) == busy
    assert trace_reduce.gaps(intervals, lo, hi) == idle


def test_self_time_takes_children_out_of_a_while():
    # a while of 10 with two children of 3 and 4, then a lone op of 2
    events = [(0, 10, "while", None), (1, 4, "matmul", None),
              (5, 9, "top_k", None), (12, 14, "copy", None)]
    own = dict(trace_reduce.self_times(events))
    assert own == {"while": 3, "matmul": 3, "top_k": 4, "copy": 2}


def test_short_name_of_a_tpu_operation_drops_its_operands():
    llr = ('%_llr_padded.8 = f32[100096,4096]{1,0:T(8,128)} custom-call('
           'f32[100096,4096]{1,0:T(8,128)} %pad.67), '
           'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert trace_reduce.short_name(llr) == "_llr_padded.8 (tpu_custom_call)"
    user = ('%slice.69 = f32[100000,4096]{1,0} slice(f32[100096,4096] '
            '%_llr_padded.8), slice={[0:100000], [0:4096]}')
    assert trace_reduce.short_name(user) == "slice.69"     # not the kernel
    topk = ('%fusion.31 = (f32[100000,50]{1,0}, s32[100000,50]{1,0}) fusion('
            'f32[100000,4096]{1,0} %x), kind=kCustom, calls=%fused.2')
    assert trace_reduce.short_name(topk) == "fusion.31 (kCustom)"
    assert trace_reduce.short_name("dot_general.1") == "dot_general.1"
