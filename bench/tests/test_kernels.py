import pytest

from harness.record import kernel
from fakes import DIMS, run

D, F, V, L = DIMS.d_model, DIMS.d_ff, DIMS.vocab, DIMS.layers
QW, KW = DIMS.q_width, DIMS.kv_width
MATS = [(D, QW), (D, KW), (D, KW), (QW, D), (D, F), (D, F), (F, D)]


def test_rns_matmul_counts_every_matmul_of_every_step():
    ops, byts = kernel("rns_matmul").cost(run())
    C = 3
    want_ops = want_bytes = 0.0
    # prefills: rows 4*64 and 4*32, logits on 4 rows; decode: 10 steps of 4
    for rows, lrows, n in ((256, 4, 1), (128, 4, 1), (4, 4, 10)):
        for K, N in MATS:
            want_ops += 2 * C * rows * K * N * L * n
            want_bytes += (C * (rows * K + K * N) + 4 * C * rows * N) * L * n
        want_ops += 2 * C * lrows * D * V * n
        want_bytes += (C * (lrows * D + D * V) + 4 * C * lrows * V) * n
    assert ops == pytest.approx(want_ops)
    assert byts == pytest.approx(want_bytes)


def test_rns_matmul_is_absent_from_binary_configurations():
    assert kernel("rns_matmul").cost(run(system="bns")) is None


def test_paged_decode_reads_the_pages_live_lengths_cover():
    ops, byts = kernel("flash_paged_decode").cost(run())
    # slot A: lengths 31..34 (4 steps); slot B: 51..60 (10 steps); ps 16
    lens = list(range(31, 35)) + list(range(51, 61))
    pages = sum(-(-n // 16) for n in lens)
    assert ops == pytest.approx(4 * DIMS.heads * DIMS.head_dim * sum(lens) * L)
    assert byts == pytest.approx((pages * 1000 + len(lens) * QW * 6) * L)


def test_model_flops_count_useful_tokens_only():
    flops = kernel("model_step").useful_flops(run())
    layer_w = L * sum(K * N for K, N in MATS)
    att = 4 * L * DIMS.heads * DIMS.head_dim
    want = 0.0
    for n in (30, 50, 20):
        want += 2 * layer_w * n + att * n * (n + 1) / 2 + 2 * V * D
    for p, k in ((30, 4), (50, 10)):
        want += 2 * (layer_w + V * D) * k + att * (k * (p + 1) + k * (k - 1) / 2)
    assert flops == pytest.approx(want)
