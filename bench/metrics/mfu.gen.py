"""Whole-step model FLOP/s utilization: useful model FLOPs of the traced
interval over its wall time and the chips' bf16 peak."""
from harness.record import mfu


def read(run):
    return mfu(run)
