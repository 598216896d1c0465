"""A cell made of files alone, run end to end on the CPU at a tiny size;
the check passes a sound run and fails each planted fault and the
control."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from harness import check, faults, serve, traffic as tr
from harness.arch import reference as ref
from harness.cell import load_cell
from tiny import run_cell

pytestmark = pytest.mark.usefixtures("tiny_arch")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout-like directory whose cells exist nowhere else."""
    return tiny.checkout(tmp_path_factory.mktemp("cells"),
                         {f"tiny-{s}": tiny.config(s) for s in ("rns", "bns")})


@pytest.mark.parametrize("system", ["rns", "bns"])
def test_a_cell_of_files_alone_runs_and_is_correct(root, system):
    res = run_cell(root, f"tiny-{system}.mini")
    assert res["correct"], res["check"]
    spec = json.loads((root / "BENCHMARK.json").read_text())
    assert set(res["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    assert res["window_compiles"] == 0
    assert list(res)[-1] == "check"


def _plant(monkeypatch, fault):
    build = serve.build

    def planted(cell, seed, rec):
        sv = build(cell, seed, rec)
        eng = sv.engine
        fused = eng._fused_paged

        def broken(params, tok0, kv, *a, **k):
            before = jax.tree_util.tree_map(jnp.copy, kv)   # kv is donated
            buf, i, steps, kv2, done, syn = fused(params, tok0, kv, *a, **k)
            if fault == "state":          # the step's KV writes are lost
                return buf, i, steps, before, done, syn
            # "token": each token altered where the loop produces it
            buf = (buf + 1) % sv.dims.vocab
            return buf, i, steps, kv2, done, syn

        broken._cache_size = fused._cache_size     # the engine counts traces
        eng._fused_paged = broken
        return sv

    monkeypatch.setattr(serve, "build", planted)


@pytest.mark.parametrize("fault", ["state", "token"])
@pytest.mark.parametrize("system", ["rns", "bns"])
def test_planted_faults_are_not_correct(root, monkeypatch, system, fault):
    _plant(monkeypatch, fault)
    res = run_cell(root, f"tiny-{system}.mini")
    assert not res["correct"], res["check"]


@pytest.mark.parametrize("fault", faults.FAULTS)
@pytest.mark.parametrize("system", ["rns", "bns"])
def test_attention_kernel_faults_are_not_correct(root, system, fault):
    remove = faults.plant(fault)
    try:
        res = run_cell(root, f"tiny-{system}.mini")
    finally:
        remove()
    assert not res["correct"], res["check"]


@pytest.mark.parametrize("system", ["rns", "bns"])
def test_the_control_is_not_correct(root, system):
    cell = load_cell(f"tiny-{system}.mini", root=root)
    conf, fam, seed = cell.config, cell.family, 5
    spec, d = tr.spec_of(cell.traffic), fam.dims_of(conf)
    rng = np.random.default_rng(0)
    samples = [check.Sample(p, rng.integers(1, d.vocab, m).astype(np.int32),
                            None)
               for p, m in tr.make_wave(spec, d.vocab, seed, 0)[:4]]
    numbers = check.measure(fam, samples, seed, d, fam.control(conf),
                            check.pad_len(cell.traffic),
                            against=fam.stated(conf))
    ok, rows = check.verdict(numbers, conf["check"]["limits"])
    assert not ok, rows


def test_reference_decodes_residue_pages_like_the_program():
    from repro.core.moduli import KV8

    v = jnp.arange(-119, 120)
    packed = np.asarray(KV8.packed().encode(v))
    table = ref.crt_table((15, 16))
    assert (table[packed] == np.asarray(v)).all()
