"""A Run built by hand: spans and shapes with known counts."""
from harness.arch.dense import Dims
from harness.record import Run
from harness.serve import Span
from harness.trace import DeviceTrace

DIMS = Dims(layers=2, d_model=256, d_ff=512, heads=4, kv_heads=2,
            head_dim=64, vocab=1000, rope_theta=1e4, norm_eps=1e-5)
PEAKS = {"bf16_flops": 100e12, "int8_ops": 200e12, "hbm_bytes_per_s": 1e12}


def run(trace=None, system="rns", spans=None):
    spans = spans if spans is not None else [
        # one admission of 2 prompts (30, 50) computed as 4 rows x 64
        Span("admit", 0.0, 0.5, {"prompts": [30, 50],
                                 "computed": [[4, 64]]}),
        # a segment of 10 steps: slot A at 30 with 4 to go, slot B at 50
        # with 10 to go
        Span("segment", 0.5, 1.5, {"steps": 10, "pos0": [30, 50],
                                   "remaining": [4, 10]}),
        Span("admit", 1.5, 1.75, {"prompts": [20], "computed": [[4, 32]]}),
    ]
    config = {"system": system, "rns_moduli": [127, 128, 129]}
    return Run(config=config, dims=DIMS, batch=4, chips=1,
               page_size=16, page_bytes=1000.0, t0=0.0, t1=2.0,
               all_spans=spans, trace=trace, peaks=PEAKS)


def device_trace(op_s, busy=1.0, window=2.0):
    return DeviceTrace(window_s=window, busy_s=busy, op_s=op_s, gaps=[])
