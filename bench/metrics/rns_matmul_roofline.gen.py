"""Roofline share of the residue matmul kernel (``bench/kernels/rns_matmul.py``)."""
from harness.record import roofline_share


def read(run):
    return roofline_share(run, "rns_matmul")
