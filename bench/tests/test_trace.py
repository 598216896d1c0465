import json
from pathlib import Path

import pytest

from harness import trace as tr

DATA = Path(__file__).parent / "data"


def test_union_length():
    assert tr.union_length([(0, 10), (5, 15), (20, 30)]) == 25
    assert tr.union_length([(0, 10), (2, 3)]) == 10
    assert tr.union_length([]) == 0


def test_reduce_events_busy_idle_kernels_and_gaps():
    ms = 1_000_000
    dev = [("rns_matmul_pallas.3", 10 * ms, 30 * ms),
           ("fusion.1", 30 * ms, 40 * ms),
           ("rns_matmul_pallas.7", 60 * ms, 70 * ms),
           ("flash_paged_decode_pallas", 65 * ms, 80 * ms),
           ("fusion.1", 95 * ms, 120 * ms)]          # crosses the window end
    host = [("bench.wave", 0, 100 * ms), ("bench.segment", 5 * ms, 50 * ms),
            ("bench.admit", 45 * ms, 58 * ms)]
    t = tr.reduce_events([dev], host)
    assert t.window_s == pytest.approx(0.1)
    assert t.busy_s == pytest.approx(0.055)   # 10-40, 60-80, 95-100
    assert t.kernel_time("rns_matmul_pallas") == pytest.approx(0.03)
    assert t.kernel_time("flash_paged_decode_pallas") == pytest.approx(0.015)
    gaps = sorted(t.gaps)
    # idle 0-10 (in segment? no: 5-50 holds 5 ms, mid 5 -> segment),
    # 40-60 (mid 50: admit, the innermost), 80-95 (mid 87.5: wave)
    assert [(round(g, 3), n) for g, n in gaps] == [
        (0.01, "segment"), (0.015, "wave"), (0.02, "admit")]
    bd = t.breakdown()
    assert bd["device_ops"][0] == ["rns_matmul_pallas.3", pytest.approx(0.02)]
    assert bd["idle_gaps"][0] == ["admit", pytest.approx(0.02)]


def test_two_chips_average_busy():
    ms = 1_000_000
    host = [("bench.wave", 0, 100 * ms)]
    t = tr.reduce_events([[("a", 0, 50 * ms)], [("a", 0, 100 * ms)]], host)
    assert t.busy_s == pytest.approx(0.075)
    assert t.op_s["a"] == pytest.approx(0.15)


def test_recorded_chip_trace():
    """A slice of a traced run on one v5e (``--trace 1``): the reduction
    finds the kernels by name and the busy share lies in (0, 1]."""
    sample = json.loads((DATA / "trace_sample.json").read_text())
    t = tr.reduce_events(sample["devices"], sample["host"])
    assert 0 < t.busy_s <= t.window_s
    for name in sample["kernels"]:
        assert t.kernel_time(name) > 0, name
