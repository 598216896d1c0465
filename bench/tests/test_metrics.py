import pytest

from harness.record import kernel, read_all, reader
from fakes import PEAKS, device_trace, run


def read(name, r):
    return reader(name).read(r)


def test_host_span_readers():
    r = run()
    assert read("occupancy.gen", r) == pytest.approx(100 * (4 + 10) / 40)
    assert read("admit_share.gen", r) == pytest.approx(100 * 0.75 / 2.0)
    assert read("step_ms.gen", r) == pytest.approx(100.0)


def test_readers_find_nothing_without_spans_or_trace():
    r = run(spans=[])
    for name in ("occupancy.gen", "admit_share.gen", "step_ms.gen",
                 "rns_matmul_roofline.gen", "idle_share.gen", "mfu.gen"):
        assert read(name, r) is None, name
    assert read_all(r, ["occupancy.gen", "step_ms.gen"]) == {}


def test_roofline_share_is_the_least_time_over_kernel_time():
    ops, byts = kernel("rns_matmul").cost(run())
    t_min = max(ops / PEAKS["int8_ops"], byts / PEAKS["hbm_bytes_per_s"])
    r = run(trace=device_trace({"rns_matmul_pallas.1": 2 * t_min,
                                "rns_matmul_pallas.2": 2 * t_min}))
    assert read("rns_matmul_roofline.gen", r) == pytest.approx(25.0)
    # a kernel the trace does not show reads nothing, never 0
    assert read("paged_decode_roofline.gen", r) is None


def test_device_readers():
    r = run(trace=device_trace({}, busy=1.5, window=2.0))
    assert read("idle_share.gen", r) == pytest.approx(25.0)
    flops = kernel("model_step").useful_flops(r)
    assert read("mfu.gen", r) == pytest.approx(
        100 * flops / (2.0 * PEAKS["bf16_flops"]))
