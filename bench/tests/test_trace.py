"""The trace reduction, on hand-made intervals and on a small trace
recorded on one TPU v5e chip (``record_trace.py``: three steps of a matmul
and a loop of four calls of a small Pallas kernel, under the benchmark's
host annotations)."""
from pathlib import Path

import pytest

import trace

RECORDED = Path(__file__).resolve().parent / "data" / "one_chip.xplane.pb"


def test_union_clip_subtract():
    u = trace.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [(0, 3), (5, 9)]
    assert trace.clip(u, 2, 6) == [(2, 3), (5, 6)]
    assert trace.length(u) == 7
    assert trace.subtract([(0, 10)], [(2, 3), (5, 6)]) == [
        (0, 2), (3, 5), (6, 10)]
    assert trace.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]


def test_leaf_ops_drop_containers():
    ops = [trace.Op("while", 0, 10), trace.Op("a", 1, 3),
           trace.Op("b", 4, 6), trace.Op("c", 12, 13)]
    assert [o.name for o in trace.leaf_ops(ops)] == ["a", "b", "c"]


def test_exposed_collective():
    ops = [trace.Op("fusion", 0, 4), trace.Op("collective-permute-done", 3, 6),
           trace.Op("fusion.2", 5, 8), trace.Op("all-reduce", 9, 10)]
    assert trace.exposed_collective(ops, (0, 10)) == pytest.approx(2.0)


def brute_busy(ops, lo, hi, step=1.0):
    """Busy time by sampling the window at every ``step`` ns."""
    n, t = 0, lo
    while t < hi:
        n += any(o.start <= t < o.end for o in ops)
        t += step
    return n * step


@pytest.fixture(scope="module")
def recorded():
    return trace.load(RECORDED)


def test_recorded_trace_window_and_busy(recorded):
    assert len(recorded.devices) == 1
    host = trace.window(recorded)
    wins = trace.device_windows(recorded)
    (dev, win), = wins.items()
    assert win[1] - win[0] == host[1] - host[0]
    assert win[0] <= host[0]            # the device clock runs behind
    busy = trace.device_busy_s(recorded, wins)
    b = busy[dev]
    assert 0 < b <= (win[1] - win[0]) / 1e9
    ops = recorded.devices[dev]
    inside = [o for o in ops if o.end > win[0] and o.start < win[1]]
    lo = min(o.start for o in inside)
    hi = max(o.end for o in inside)
    brute = brute_busy(inside, max(lo, win[0]), min(hi, win[1]), step=10.0)
    assert b * 1e9 == pytest.approx(brute, rel=0.02, abs=200)


def test_recorded_trace_kernel_calls(recorded):
    wins = trace.device_windows(recorded)
    (dev, ops), = recorded.devices.items()
    t, n = trace.op_time(ops, r"^bench_scale_kernel\b", wins[dev])
    assert n == 12 and t > 0          # 3 steps x 4 loop iterations
    assert all(o.name != "while" for o in ops)   # containers dropped
    top = trace.top_ops(recorded, wins)
    assert top and all(s > 0 for _, s in top)
    gaps = trace.idle_gaps(recorded, wins)
    assert gaps and all(s >= 0 for _, s in gaps)
