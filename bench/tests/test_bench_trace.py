"""The trace reduction on a small synthetic trace: the busy union, the
idle share, kernel time by name and the breakdown."""
import pytest

from bench import trace as T


def _trace():
    E = T.Event
    ops = [E("fusion.1", 0.0, 2.0), E("fusion.2", 1.0, 3.0),   # overlap
           E("_matmul_kernel", 5.0, 6.0),
           E("_matmul_kernel", 6.0, 7.5),
           E("copy.3", 9.0, 12.0)]                        # past the window
    spans = [E("bench.window", 0.0, 10.0), E("bench.drain", 0.0, 7.5),
             E("bench.wait_arrival", 7.5, 10.0)]
    return T.Trace([ops], spans, (0.0, 10.0))


def test_union_merges_overlaps_and_gaps():
    assert T.union_seconds([(0, 2), (1, 3), (5, 6), (6, 7.5)]) == 5.5
    assert T.union_seconds([]) == 0.0


def test_busy_and_idle_share_are_clipped_to_the_window():
    tr = _trace()
    assert T.busy_seconds(tr) == pytest.approx(3.0 + 2.5 + 1.0)
    assert T.idle_share(tr) == pytest.approx(1 - 6.5 / 10.0)


def test_busy_is_averaged_over_devices():
    tr = _trace()
    tr.devices.append([T.Event("fusion.9", 0.0, 10.0)])
    assert T.busy_seconds(tr) == pytest.approx((6.5 + 10.0) / 2)


def test_kernel_seconds_by_name():
    tr = _trace()
    assert T.op_seconds(tr, r"_matmul_kernel") == pytest.approx(2.5)
    assert T.op_seconds(tr, r"^fusion\.") == pytest.approx(4.0)
    assert T.op_seconds(tr, r"no_such_kernel") == 0.0


def test_op_names_from_tpu_hlo_text():
    assert T.op_name("%gmm_rescore.8 = f32[131072,20]{1,0} custom-call("
                     "s32[16384,1,160] %copy.1), custom_call_target="
                     "\"tpu_custom_call\"") == "gmm_rescore.8"
    assert T.op_name("%custom-call.730 = (f32[8,20]) custom-call(f32[8,2] "
                     "%f), custom_call_target=\"TopK\"") == \
        "custom-call.730:TopK"
    assert T.op_name("%fusion.12 = f32[4] fusion(f32[4] %p)") == "fusion.12"


def test_breakdown_lists_ops_and_gaps_by_host_span():
    tr = _trace()
    assert T.top_ops(tr) == [["_matmul_kernel", pytest.approx(2.5)],
                             ["fusion.1", pytest.approx(2.0)],
                             ["fusion.2", pytest.approx(2.0)],
                             ["copy.3", pytest.approx(1.0)]]
    # busy [0, 3], [5, 7.5], [9, 10]: gaps of 2 s and 1.5 s
    assert T.idle_gaps(tr) == [["bench.drain", pytest.approx(2.0)],
                               ["bench.wait_arrival", pytest.approx(1.5)]]


def test_nothing_traced_reads_nothing():
    tr = T.Trace([], [], (0.0, 1.0))
    assert T.idle_share(tr) is None
    assert T.top_ops(tr) == [] and T.idle_gaps(tr) == []
