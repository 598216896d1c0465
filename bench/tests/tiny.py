"""A checkout-like directory of tiny cells, made of files alone, and a run
of one of its cells on the CPU.  With the ``tiny_arch`` fixture
(``conftest.py``) the program builds its own tiny preset of each arch."""
import json

import jax

import run as bench_run
from harness.cell import CHECKOUT, load_cell

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


def config(system: str, **extra) -> dict:
    """The committed yi-6b configuration of ``system`` at tiny widths, with
    tight limits."""
    base = json.loads(
        (CHECKOUT / f"bench/configs/yi6b-{system}-l8.json").read_text())
    limits = {k: TINY_LIMITS[k] for k in base["check"]["limits"]}
    return {**base, **TINY, "check": {"limits": limits}, **extra}


def checkout(root, configs: dict):
    """Write into ``root`` one configuration file per entry of ``configs``
    (name -> configuration), the traffic ``mini``, and a ``BENCHMARK.json``
    with the cell ``<name>.mini`` of each."""
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir(parents=True)
    spec = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    entries, cells = [], []
    for name, conf in configs.items():
        (root / f"bench/configs/{name}.json").write_text(json.dumps(conf))
        entries.append({"name": name, "source": "test",
                        "file": f"bench/configs/{name}.json",
                        "reduced": [], "why": "test"})
        cells.append({"name": f"{name}.mini", "config": name,
                      "traffic": "mini", "chips": 1, "why": "test"})
    (root / "bench/traffic/mini.json").write_text(json.dumps(MIX))
    e2e = [dict(m, workloads=[c["name"] for c in cells])
           if "workloads" in m else m for m in spec["end_to_end"]]
    (root / "BENCHMARK.json").write_text(json.dumps(
        {**spec, "configs": entries, "workloads": cells, "end_to_end": e2e,
         "per_layer": []}))
    return root


def run_cell(root, name, seed=2**31 + 11):
    cell = load_cell(name, root=root)
    return bench_run.run_cell(cell, seed, 0.5, False, jax.devices()[:1],
                              PEAKS)
