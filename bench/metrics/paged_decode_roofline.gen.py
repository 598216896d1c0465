"""Roofline share of the paged decode kernel
(``bench/kernels/flash_paged_decode.py``)."""
from harness.record import roofline_share


def read(run):
    return roofline_share(run, "flash_paged_decode")
