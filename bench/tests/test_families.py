"""The seam between the shared harness and a model family
(``harness.arch``): the dense family gives what the harness gave before
families were split out of it; a family that exists only in this file is
picked by a configuration file and taken by every shared caller; a block
with no family fails where the cell is loaded."""
import collections
import json
import sys
import types

import jax.numpy as jnp
import numpy as np
import pytest

import fakes
import tiny
from harness import arch, record
from harness.arch import dense
from harness.cell import load_cell
from harness.record import kernel

# Read from the harness as it was before the families were split out of it,
# on the CPU: ``fakes.run()``'s costs, and at ``WIDTHS`` the weights of seed
# 2**31 + 11 (sum and sum of squares of each leaf, in the order of the
# leaves' sorted names) and the reference over ``TOKENS`` with 17 prompt
# tokens.
PARENT_COSTS = {"rns_matmul": (3074752512.0, 78346752.0),
                "flash_paged_decode": (1402880.0, 143008.0),
                "useful_flops": 283060224.0}
PARENT_WEIGHTS = [
    (128.0, 128.0),
    (-4.379314920103411, 13.154042660379726),
    (64.0, 64.0),
    (128.0, 128.0),
    (0.6230467194618541, 156.29038347264594),
    (0.21469981107611602, 150.26785537811884),
    (2.75392448758339, 153.30349283154825),
    (-3.6241063946217764, 85.2575652260229),
    (-5.28863970603652, 123.65201993968667),
    (-10.618525254285487, 130.95876943124995),
    (-11.579371491919119, 88.90575050749752)]
PARENT_REFERENCE = {
    "rns": {"logits_abs": 2632.7825308680767,
            "logits_row20": [0.011420494876801968, -0.1971033662557602,
                             0.039835359901189804, 0.3647940754890442],
            "argmax": [287, 214, 287, 110, 461, 334, 334, 461, 510, 446,
                       461, 332, 144, 110, 322, 461, 461, 287, 239, 164,
                       325, 325, 510, 123],
            "k_abs": 2272.217105875723, "v_abs": 2581.030730025843},
    "bns": {"logits_abs": 2568.19670628502,
            "logits_row20": [-0.02363801933825016, 0.15722347795963287,
                             -0.0370805449783802, 0.18578383326530457],
            "argmax": [123, 334, 197, 334, 322, 334, 334, 461, 510, 322,
                       461, 332, 110, 110, 110, 461, 332, 110, 239, 161,
                       325, 325, 461, 325],
            "k_abs": 2238.1041984558105, "v_abs": 2516.398780822754}}
WIDTHS = dict(tiny.TINY, num_key_value_heads=2)      # grouped queries
TOKENS = np.random.default_rng(1).integers(1, 512, 40)


def test_dense_counts_are_the_parent_s():
    r = fakes.run()
    assert kernel("rns_matmul").cost(r) == PARENT_COSTS["rns_matmul"]
    assert (kernel("flash_paged_decode").cost(r)
            == PARENT_COSTS["flash_paged_decode"])
    assert (kernel("model_step").useful_flops(r)
            == PARENT_COSTS["useful_flops"])


def test_dense_weights_are_the_parent_s():
    d = dense.dims_of(tiny.config("rns", **WIDTHS))
    w = dense.make_weights(2**31 + 11, d)
    assert len(w) == len(PARENT_WEIGHTS)
    for name, (total, squares) in zip(sorted(w), PARENT_WEIGHTS):
        x = np.asarray(w[name], np.float64)
        assert x.sum() == pytest.approx(total, abs=1e-3), name
        assert (x ** 2).sum() == pytest.approx(squares, rel=1e-6), name


@pytest.mark.parametrize("system", ["rns", "bns"])
def test_dense_reference_is_the_parent_s(system):
    conf = tiny.config(system, **WIDTHS)
    d = dense.dims_of(conf)
    toks = np.zeros(64, np.int32)
    toks[:40] = TOKENS
    logits, rows = dense.forward(dense.make_weights(2**31 + 11, d),
                                 jnp.asarray(toks), jnp.int32(17), d=d,
                                 p=dense.stated(conf))
    lg, rows = np.asarray(logits, np.float64), np.asarray(rows, np.float64)
    want = PARENT_REFERENCE[system]
    assert rows.shape == (2, 2, 64, 2, 16)
    assert np.abs(lg[:40]).sum() == pytest.approx(want["logits_abs"],
                                                  rel=1e-6)
    assert lg[20, :4] == pytest.approx(want["logits_row20"], abs=1e-6)
    assert lg[16:40].argmax(-1).tolist() == want["argmax"]
    for part, key in enumerate(("k_abs", "v_abs")):
        assert np.abs(rows[:, part, :40]).sum() == pytest.approx(
            want[key], rel=1e-6)


@pytest.fixture
def probe(monkeypatch):
    """A family that exists only here, as ``harness.arch.probe``: the dense
    block under another name, counting the calls made to it."""
    mod = types.ModuleType("harness.arch.probe")
    mod.calls = collections.Counter()
    for name in arch.INTERFACE:
        attr = getattr(dense, name)
        if callable(attr) and not isinstance(attr, type):
            def counted(*a, _f=attr, _name=name, **k):
                mod.calls[_name] += 1
                return _f(*a, **k)
            attr = counted
        setattr(mod, name, attr)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return mod


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.checkout(tmp_path_factory.mktemp("families"),
                         {"tiny-probe": tiny.config("bns", block="probe")})


@pytest.mark.usefixtures("tiny_arch")
def test_a_new_family_is_taken_by_every_caller(root, probe):
    assert load_cell("tiny-probe.mini", root=root).family is probe
    res = tiny.run_cell(root, "tiny-probe.mini")
    assert res["correct"], res["check"]
    # the width check, shapes and weights (serve.build), the rows read
    # back (check.samples), the reference (check.measure)
    for name in ("program_config", "dims_of", "program_params",
                 "served_rows", "forward", "stated"):
        assert probe.calls[name], name
    assert probe.calls["make_weights"] == 2       # the engine's, the check's


def test_kernel_counts_ask_the_family(probe):
    r = fakes.run()
    r.config = dict(r.config, block="probe")
    probe.matmul_calls = lambda run: [(2, 3, 5, 7)]
    probe.paged_decode = lambda run: (10.0, 100, 3)
    probe.prompt_flops = lambda run, n: 1000.0 * n
    probe.token_flops = lambda run, n: float(n)
    C = 3
    assert kernel("rns_matmul").cost(r) == (
        2 * C * 2 * 3 * 5 * 7, (C * (2 * 3 + 3 * 5) + 4 * C * 2 * 5) * 7)
    # fakes.run(): slot A at lengths 31..34, slot B 51..60, pages of 16
    lens = list(range(31, 35)) + list(range(51, 61))
    pages = sum(-(-n // 16) for n in lens)
    assert kernel("flash_paged_decode").cost(r) == (
        10.0 * sum(lens) * 3, (pages * 1000 + 100 * len(lens)) * 3)
    flops = 1000.0 * (30 + 50 + 20) + sum(lens)
    assert kernel("model_step").useful_flops(r) == flops
    assert record.mfu(r) == pytest.approx(
        100 * flops / (2.0 * fakes.PEAKS["bf16_flops"]))


@pytest.mark.parametrize("block", ["nonesuch", "reference", "../dense", 3])
def test_a_block_with_no_family_fails_where_the_cell_loads(tmp_path, block):
    root = tiny.checkout(tmp_path, {"tiny-x": tiny.config("bns",
                                                          block=block)})
    with pytest.raises(ValueError, match="'block'") as e:
        load_cell("tiny-x.mini", root=root)
    assert "families: dense" in str(e.value)


def test_a_configuration_without_block_is_dense():
    conf = json.loads((tiny.CHECKOUT / "bench/configs/yi6b-rns-l8.json")
                      .read_text())
    assert "block" not in conf
    assert arch.family_of(conf) is dense
