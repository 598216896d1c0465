"""Kernel-vs-oracle sweeps (Pallas interpret mode on CPU).

Every kernel is validated against its ref.py pure-jnp oracle across a
shape/dtype/moduli sweep, plus against the exact integer matmul oracle
end-to-end (forward conv -> kernel -> reverse conv == int32 matmul).
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import numerics as nx
from repro.core import CRT40, P16, P21, P24, sd
from repro.kernels import ref
from repro.kernels.rns_matmul import rns_matmul_pallas

RNG = np.random.default_rng(0)


def _rns_matmul(a, b, mset, max_abs):
    t = nx.encode(jnp.asarray(b), nx.EncodeSpec(layout="rns", mset=mset,
                                                max_abs=max_abs))
    return nx.matmul(jnp.asarray(a), t, max_abs_a=max_abs,
                     backend="interpret")


# ---------------------------------------------------------------------------
# rns_matmul
# ---------------------------------------------------------------------------

SHAPES = [
    (8, 128, 16),      # tiny, padding path
    (128, 128, 128),   # exactly one block
    (128, 512, 128),   # K multi-block (lazy accumulation across grid steps)
    (256, 640, 384),   # multi-block everything, non-square
    (1, 128, 1),       # degenerate edges
    (130, 257, 100),   # awkward non-aligned
]


@pytest.mark.parametrize("M,K,N", SHAPES)
@pytest.mark.parametrize("mset", [P21, P24], ids=lambda s: str(s.moduli))
def test_rns_matmul_vs_int_oracle(M, K, N, mset):
    a = RNG.integers(-7, 8, size=(M, K)).astype(np.int32)
    b = RNG.integers(-7, 8, size=(K, N)).astype(np.int32)
    got = _rns_matmul(a, b, mset, 7)
    np.testing.assert_array_equal(np.asarray(got), a @ b)


@pytest.mark.parametrize("mset", [P21, CRT40], ids=lambda s: str(s.moduli))
def test_rns_matmul_kernel_vs_ref(mset):
    """Raw kernel output (centered residues) vs the pure-jnp oracle."""
    C = mset.num_channels
    res_dtype = np.int8 if max(mset.moduli) <= 255 else np.int32
    a_res = np.stack([
        RNG.integers(-(m // 2), m // 2 + 1, size=(128, 256))
        for m in mset.moduli
    ]).astype(res_dtype)
    b_res = np.stack([
        RNG.integers(-(m // 2), m // 2 + 1, size=(256, 128))
        for m in mset.moduli
    ]).astype(res_dtype)
    got = rns_matmul_pallas(jnp.asarray(a_res), jnp.asarray(b_res),
                            jnp.asarray(mset.moduli, jnp.int32),
                            bm=128, bn=128, bk=128, interpret=True)
    want = ref.rns_matmul_ref(jnp.asarray(a_res), jnp.asarray(b_res), mset)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert got.shape == (C, 128, 128)


def test_rns_matmul_k_segmentation():
    """K large enough that the exact result would exceed M/2: the wrapper
    must segment and still be exact."""
    M, K, N = 8, 48 * 1024, 16   # 49 * 49k >> P21.half_range
    a = RNG.integers(-7, 8, size=(M, K)).astype(np.int32)
    b = RNG.integers(-7, 8, size=(K, N)).astype(np.int32)
    assert nx.segment_count(K, 7, 7, P21) >= 2
    got = _rns_matmul(a, b, P21, 7)
    np.testing.assert_array_equal(np.asarray(got), a @ b)


def test_rns_matmul_int8_inputs():
    """int8-typed operands with wide values (any width works in RNS as long
    as the *result* fits the dynamic range)."""
    a = RNG.integers(-127, 128, size=(32, 64)).astype(np.int8)
    b = RNG.integers(-127, 128, size=(64, 32)).astype(np.int8)
    got = _rns_matmul(a, b, CRT40, 127)
    np.testing.assert_array_equal(
        np.asarray(got), a.astype(np.int32) @ b.astype(np.int32)
    )


def test_rns_matmul_rejects_overflow():
    with pytest.raises(ValueError):
        nx.segment_count(64, 2**11, 2**11, P16)


@given(m=st.integers(1, 40), k=st.integers(1, 300), n=st.integers(1, 40))
@settings(max_examples=12, deadline=None)
def test_rns_matmul_shape_fuzz(m, k, n):
    a = RNG.integers(-7, 8, size=(m, k)).astype(np.int32)
    b = RNG.integers(-7, 8, size=(k, n)).astype(np.int32)
    got = _rns_matmul(a, b, P21, 7)
    np.testing.assert_array_equal(np.asarray(got), a @ b)


# ---------------------------------------------------------------------------
# sd_add
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["pow2m1", "pow2", "pow2p1"])
@pytest.mark.parametrize("n", [5, 7, 8, 11])
def test_sd_add_kernel_vs_ref(kind, n):
    B = 384
    x = RNG.integers(-1, 2, size=(B, n)).astype(np.int8)
    y = RNG.integers(-1, 2, size=(B, n)).astype(np.int8)
    got = nx.add(jnp.asarray(x), jnp.asarray(y), kind=kind,
                 interpret=True)
    want = ref.sd_add_ref(jnp.asarray(x), jnp.asarray(y), kind)
    # redundant representations may differ digit-wise; values must agree
    m = {"pow2m1": (1 << n) - 1, "pow2": 1 << n, "pow2p1": (1 << n) + 1}[kind]
    got_v = np.asarray(sd.to_int(got)) % m
    want_v = np.asarray(sd.to_int(want)) % m
    np.testing.assert_array_equal(got_v, want_v)
    assert np.abs(np.asarray(got)).max() <= 1  # carry-free closure


def test_sd_add_plain_growth():
    x = RNG.integers(-1, 2, size=(64, 16)).astype(np.int8)
    y = RNG.integers(-1, 2, size=(64, 16)).astype(np.int8)
    got = nx.add(jnp.asarray(x), jnp.asarray(y), kind="plain",
                 interpret=True)
    assert got.shape == (64, 17)
    np.testing.assert_array_equal(
        np.asarray(sd.to_int(got)),
        np.asarray(sd.to_int(jnp.asarray(x)) + sd.to_int(jnp.asarray(y))),
    )


def test_sd_add_batch_shapes():
    """Leading-dim flattening: (4, 6, n) digit tensors."""
    x = RNG.integers(-1, 2, size=(4, 6, 8)).astype(np.int8)
    y = RNG.integers(-1, 2, size=(4, 6, 8)).astype(np.int8)
    got = nx.add(jnp.asarray(x), jnp.asarray(y), kind="pow2m1",
                 interpret=True)
    want = ref.sd_add_ref(jnp.asarray(x), jnp.asarray(y), "pow2m1")
    m = (1 << 8) - 1
    np.testing.assert_array_equal(
        np.asarray(sd.to_int(got)) % m, np.asarray(sd.to_int(want)) % m
    )
