"""Readings of the check's numbers for the program, for its control and for
planted faults, on several seeds in one process (not run by the
benchmark's own runs).

    python3 bench/control.py --workload <cell> --seeds 7,8,9 [--control 7,8]
                             [--fault kv_head,last_page,slots,prefill_kv_head]

For each seed: the cell's set-up, one wave of its traffic at its own load,
the sample the benchmark would draw, then the numbers of the program's
served tokens and pages against the reference at the stated precision,
with the verdict of the configuration's limits.  For the seeds listed
under ``--control`` (all of them by default) also the control: the
reference one step lower, in the program's place (its own greedy choices
and page rows at the same positions), against the same reference.  With
``--fault`` the program serves with each listed fault planted in turn
under the timed path (``harness/faults.py``), and no control is read.
One JSON line per seed; the limits in the configuration files are set
between the program's and the control's readings.
"""
import argparse
import gc
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default=None,
                    help="seeds that also read the control (default: all)")
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    from harness.cell import CHECKOUT, load_cell

    cell = load_cell(args.workload)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(CHECKOUT / ".jax_cache"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    from harness import faults
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    controlled = (set(seeds) if args.control is None else
                  {int(s) for s in args.control.split(",") if s})
    for fault in (args.fault.split(",") if args.fault else [None]):
        remove = faults.plant(fault) if fault else None
        for seed in seeds:
            read(cell, seed, fault, seed in controlled and not fault)
        if remove:
            remove()
    return 0


def read(cell, seed: int, fault, with_control: bool) -> None:
    """One seed's readings, printed as one JSON line."""
    from harness import check, serve

    conf, fam = cell.config, cell.family
    s_pad = check.pad_len(cell.traffic)
    rec = serve.Recorder()
    sv = serve.build(cell, seed, rec)
    serve.warm_up(sv)
    _, _, done = serve.run_window(sv, seed, 1e-3)     # one wave
    samples = check.samples(sv, done, cell.traffic, seed)
    dims = sv.dims
    del sv, rec
    gc.collect()
    program = check.measure(fam, samples, seed, dims, fam.stated(conf),
                            s_pad)
    ok, _ = check.verdict(program, conf["check"]["limits"])
    line = {"workload": cell.name, "seed": seed, "fault": fault,
            "correct": ok, "program": program}
    if with_control:
        control = check.measure(fam, samples, seed, dims, fam.control(conf),
                                s_pad, against=fam.stated(conf))
        line["control"] = control
        line["control_correct"] = check.verdict(
            control, conf["check"]["limits"])[0]
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    sys.exit(main())
