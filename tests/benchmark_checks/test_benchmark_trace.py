"""``trace_reduce.py``: the interval arithmetic on hand-made cases, and the
whole reduction on a recorded trace — four steps of the tiny ResNet preset
under ``Trainer`` on one TPU v5e chip (PR 23's probe), each step wrapped in
the benchmark's ``feed_wait`` / ``stage_batch`` / ``step_dispatch`` spans.

The figures the reduction is held to were worked out by hand from the span
list of that trace and, for the device, from the profiler's own JSON export
of the same run (a second, independent reading of the same events)."""

import os

import pytest

from benchmark import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "tiny_resnet_v5e.xplane.pb.gz")


def test_benchmark_interval_union_gaps_and_overlap():
    busy = tr.union([(3, 5), (0, 1), (4, 7), (7, 8), (10, 11)])
    assert busy == [[0, 1], [3, 8], [10, 11]]
    assert tr.total(busy) == 7
    assert tr.gaps(busy, 0, 12) == [(1, 3), (8, 10), (11, 12)]
    assert tr.gaps([], 2, 5) == [(2, 5)]
    assert tr.clip([(0, 4), (6, 9)], 3, 7) == [(3, 4), (6, 7)]
    assert tr.overlap([(1, 3), (8, 10)], [(2, 9)]) == 2
    assert tr._subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]


def test_benchmark_operation_names_are_cut_from_the_hlo_text():
    assert tr.op_name("%fusion.12 = bf16[8,16]{1,0} fusion(%a), kind=kLoop") \
        == "fusion.12"
    assert tr.op_name("copy-start.3") == "copy-start.3"


def test_benchmark_reduction_of_hand_made_planes():
    ms = 1e-3
    planes = [
        ("/host:CPU", [
            ("python", [("traced_steps", 0.0, 100 * ms),
                        ("feed_wait", 0.0, 30 * ms),
                        ("step_dispatch", 30 * ms, 60 * ms),
                        ("feed_wait", 60 * ms, 70 * ms),
                        ("step_dispatch", 70 * ms, 100 * ms)]),
            ("pump", [("stage_batch", 5 * ms, 25 * ms)]),
            ("pjrt", [("XlaLinearize", 6 * ms, 16 * ms),
                      ("Linearize", 7 * ms, 15 * ms),      # nested: once
                      ("H2D Dispatch", 20 * ms, 22 * ms)])]),
        ("/device:TPU:0", [
            ("XLA Ops", [("%conv.1 = f32[] convolution()", 35 * ms, 55 * ms),
                         ("%add.2 = f32[] add()", 50 * ms, 58 * ms),
                         ("%conv.1 = f32[] convolution()", 75 * ms, 95 * ms),
                         ("%late = f32[] add()", 99 * ms, 120 * ms)])]),
    ]
    out = tr.reduce_planes(planes)
    assert out["window_s"] == pytest.approx(0.100)
    assert out["busy_s"] == pytest.approx(0.023 + 0.020 + 0.001)
    assert out["steps"] == 2
    assert out["transfer_s"] == pytest.approx(0.012)
    assert dict(out["device_ops"])["conv.1"] == pytest.approx(0.040)
    idle = dict(out["idle_gaps"])
    assert idle["feed_wait"] == pytest.approx(0.040)
    assert idle["step_dispatch"] == pytest.approx(0.005 + 0.002 + 0.005 + 0.004)
    assert sum(idle.values()) == pytest.approx(out["window_s"] - out["busy_s"])
    assert "stage_batch" not in idle        # it lies under feed_wait


def test_benchmark_reduction_refuses_a_trace_without_device_operations():
    planes = [("/host:CPU", [("python", [("feed_wait", 0.0, 1.0)])])]
    with pytest.raises(ValueError, match="no device plane"):
        tr.reduce_planes(planes)
    with pytest.raises(ValueError, match="none of the benchmark's spans"):
        tr.reduce_planes([("/device:TPU:0", [("XLA Ops", [("x", 0, 1)])])])


@pytest.fixture(scope="module")
def recorded():
    return tr.reduce_file(FIXTURE)


def test_benchmark_recorded_trace_window_and_spans(recorded):
    # by hand from the span list: first feed_wait opens at 45.019 ms, the
    # last step_dispatch closes at 66.300 ms
    assert recorded["window_s"] == pytest.approx(21.281e-3, abs=2e-6)
    spans = recorded["host_spans"]
    assert [spans[n]["count"] for n in tr.SPANS] == [4, 4, 4]
    # 3,127 + 2,792 + 2,514 + 3,244 us; 1,612 + 1,393 + 1,208 + 1,236 us;
    # 1,319 + 1,085 + 908 + 745 us
    assert spans["feed_wait"]["total_s"] == pytest.approx(11.677e-3, abs=3e-6)
    assert spans["step_dispatch"]["total_s"] == pytest.approx(5.449e-3, abs=3e-6)
    assert spans["stage_batch"]["total_s"] == pytest.approx(4.057e-3, abs=3e-6)
    assert recorded["steps"] == 4


def test_benchmark_recorded_trace_busy_idle_and_operations(recorded):
    # the profiler's JSON export of the same run: 2,704 operations whose
    # union is 205.05 us; the four module executions sum to 222 us
    assert recorded["devices"] == [{"plane": "/device:TPU:0",
                                    "busy_s": recorded["busy_s"],
                                    "events": 2704}]
    assert recorded["busy_s"] == pytest.approx(205.05e-6, rel=0.01)
    assert recorded["busy_s"] < 222e-6
    idle = dict(recorded["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(
        recorded["window_s"] - recorded["busy_s"], rel=1e-9)
    assert max(idle, key=idle.get) == "feed_wait"
    assert idle["other"] < 0.2e-3
    top = recorded["device_ops"]
    assert len(top) == 10 and top[0][0] == "multiply_reduce_fusion"
    assert top[0][1] == pytest.approx(5.935e-6, rel=0.01)
    assert all(a[1] >= b[1] for a, b in zip(top, top[1:]))
    assert 0 < recorded["transfer_s"] < recorded["window_s"]
