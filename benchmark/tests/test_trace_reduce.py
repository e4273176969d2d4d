"""The trace reduction: interval arithmetic on made-up events, and the
whole reduction on a small trace recorded on the chip."""
import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_union_clip_gaps():
    u = tr.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [(0, 3), (5, 9)]
    assert list(tr.clip(u, 2, 6)) == [(2, 3), (5, 6)]
    assert tr.gaps(u, -1, 10) == [(-1, 0), (3, 5), (9, 10)]
    assert tr.gaps([], 0, 4) == [(0, 4)]


def test_span_index_innermost():
    idx = tr.SpanIndex([(0, 100, "accl.allreduce"),
                        (10, 50, "req.allreduce.wait"),
                        (200, 300, "accl.send")])
    assert idx.name_at(5) == "accl.allreduce"
    assert idx.name_at(20) == "req.allreduce.wait"
    assert idx.name_at(60) == "accl.allreduce"
    assert idx.name_at(150) == "untraced"
    assert idx.name_at(250) == "accl.send"


class _Ev:
    def __init__(self, name, s, d):
        self.name, self.start_ns, self.duration_ns = name, s, d


class _Line:
    def __init__(self, name, evs):
        self.name, self.events = name, evs


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _PD:
    def __init__(self, planes):
        self.planes = planes


def test_reduce_made_up_profile():
    host = _Plane(tr.HOST_PLANE, [_Line("main", [
        _Ev(tr.WINDOW_SPAN, 1000, 1000),
        _Ev("accl.allreduce", 1000, 400),
        _Ev("req.allreduce.wait", 1100, 300),
        _Ev("accl.gather", 1500, 400)])])
    dev0 = _Plane("/device:TPU:0", [_Line(tr.OP_LINE, [
        _Ev("fusion.1", 900, 300),     # clipped to [1000, 1200]
        _Ev("copy.2", 1150, 100),      # overlaps: busy until 1250
        _Ev("fusion.1", 1600, 200)])])
    s = tr.reduce_profile(_PD([host, dev0]), 1)
    assert s.window_s == pytest.approx(1e-6)
    assert s.busy_s[0] == pytest.approx(450e-9)
    assert s.idle_share() == pytest.approx(0.55)
    bd = s.breakdown()
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(400e-9)]
    gaps = dict(bd["idle_gaps"])
    # 1250..1600: its middle (1425) lies between the two calls, in the
    # benchmark's loop; 1800..2000: its middle lies in the gather's span
    assert gaps == {"bench.window": pytest.approx(350e-9),
                    "accl.gather": pytest.approx(200e-9)}
    assert s.span_count == {tr.WINDOW_SPAN: 1, "accl.allreduce": 1,
                            "req.allreduce.wait": 1, "accl.gather": 1}


def _recorded():
    names = sorted(f for f in os.listdir(DATA) if f.endswith(".xplane.pb"))
    return [os.path.join(DATA, f) for f in names]


@pytest.mark.parametrize("path", _recorded())
def test_recorded_chip_trace(path):
    chips = int(os.path.basename(path).split("_chips")[1].split(".")[0])
    s = tr.reduce_file(path, chips)
    assert s.chips == chips
    assert 0 < s.window_s < 60
    for busy in s.busy_s.values():
        assert 0 < busy <= s.window_s
    assert 0 <= s.idle_share(busiest=True) < 1
    assert any(name.startswith("accl.") for name in s.span_count)
    bd = s.breakdown()
    assert 0 < len(bd["device_ops"]) <= tr.TOP
    assert 0 < len(bd["idle_gaps"]) <= tr.TOP
    assert sum(s.gap_s.values()) / chips == \
        pytest.approx(s.window_s - s.busy_mean_s, rel=1e-6)


def test_recorded_small_msg_numbers():
    """One round of ``small_msg.1chip`` (128 calls) traced on the chip
    (PR 22): the reduction's numbers, pinned."""
    s = tr.reduce_file(os.path.join(DATA, "small_msg_chips1.xplane.pb"), 1)
    assert s.window_s == pytest.approx(0.108006093)
    assert s.busy_s == {0: pytest.approx(0.000189941)}
    assert s.span_count["accl.allreduce"] == 16
    assert s.span_count["req.recv.wait"] == 16
    assert s.breakdown()["device_ops"][0][0] == "copy.1 f32[1,524288]"
