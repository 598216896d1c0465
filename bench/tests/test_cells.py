"""A cell made of files alone, run end to end on the CPU at a tiny size;
the check passes a sound run and fails each planted fault and the
control."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as bench_run
from harness import check, faults, reference as ref, serve, traffic as tr
from harness.cell import CHECKOUT, load_cell
from harness.weights import dims_of

PEAKS = {"bf16_flops": 1e12, "int8_ops": 1e12, "hbm_bytes_per_s": 1e11}
TINY = {"hidden_size": 64,
        "intermediate_size": 96, "num_attention_heads": 4,
        "num_key_value_heads": 4, "head_dim": 16, "num_hidden_layers": 2,
        "vocab_size": 512, "compute_dtype": "float32", "page_size": 8}
# at this size the program and the reference agree to rounding, so each
# configuration's own numbers are held to tight limits here
TINY_LIMITS = {"tok_gap": 0.05, "tok_gap_median": 0.05, "tok_gap_p90": 0.05,
               "tok_gap_request_median": 0.05, "kv_err": 1e-3,
               "kv_err_layer0": 1e-3, "kv_err_layer1": 1e-3}
MIX = {"batch": 4, "wave": 8,
       "prompt": {"min": 4, "max": 24, "dist": "log-uniform"},
       "output": {"min": 4, "max": 12, "dist": "log-uniform"},
       "check_requests": 4}


@pytest.fixture(autouse=True)
def tiny_arch(monkeypatch):
    """The program's own tiny preset of each arch in place of its served
    widths."""
    import repro.configs

    get = repro.configs.get_config
    monkeypatch.setattr(repro.configs, "get_config",
                        lambda name: get(name).reduced())


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout-like directory whose cells exist nowhere else."""
    root = tmp_path_factory.mktemp("cells")
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir(parents=True)
    spec = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    configs, cells = [], []
    for system in ("rns", "bns"):
        base = json.loads(
            (CHECKOUT / f"bench/configs/yi6b-{system}-l8.json").read_text())
        name = f"tiny-{system}"
        limits = {k: TINY_LIMITS[k] for k in base["check"]["limits"]}
        (root / f"bench/configs/{name}.json").write_text(
            json.dumps(dict(base, **TINY, check={"limits": limits})))
        configs.append({"name": name, "source": "test",
                        "file": f"bench/configs/{name}.json",
                        "reduced": [], "why": "test"})
        cells.append({"name": f"{name}.mini", "config": name,
                      "traffic": "mini", "chips": 1, "why": "test"})
    (root / "bench/traffic/mini.json").write_text(json.dumps(MIX))
    e2e = [dict(m, workloads=[c["name"] for c in cells])
           if "workloads" in m else m for m in spec["end_to_end"]]
    (root / "BENCHMARK.json").write_text(json.dumps(
        {**spec, "configs": configs, "workloads": cells, "end_to_end": e2e,
         "per_layer": []}))
    return root


def run_cell(root, name, seed=2**31 + 11):
    cell = load_cell(name, root=root)
    return bench_run.run_cell(cell, seed, 0.5, False, jax.devices()[:1],
                              PEAKS)


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
    conf, seed = cell.config, 5
    spec, d = tr.spec_of(cell.traffic), dims_of(conf)
    rng = np.random.default_rng(0)
    samples = [check.Sample(p, rng.integers(1, d.vocab, m).astype(np.int32),
                            None)
               for p, m in tr.make_wave(spec, d.vocab, seed, 0)[:4]]
    numbers = check.measure(samples, seed, d, ref.control(conf),
                            check.pad_len(cell.traffic),
                            against=ref.stated(conf))
    ok, rows = check.verdict(numbers, conf["check"]["limits"])
    assert not ok, rows


def test_reference_decodes_residue_pages_like_the_program():
    from repro.core.moduli import KV8

    v = jnp.arange(-119, 120)
    packed = np.asarray(KV8.packed().encode(v))
    table = ref.crt_table((15, 16))
    assert (table[packed] == np.asarray(v)).all()
