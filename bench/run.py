"""Run one benchmark cell on the accelerator and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's engine from its configuration file with weights made from
the seed, warms up the shapes its traffic uses (counted in ``setup_s``),
fills ``--seconds`` with waves of its traffic, then checks what the window
served against the plain reference (``harness/check.py``).  With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the first waves run under the profiler and the result
carries its per-layer metrics.  The last line of standard output is the
result as one JSON object.  Without a TPU, or with fewer chips than the
cell asks for, it exits with 2 and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

TRACE_SECONDS = 8.0     # the traced run profiles the waves of this long


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def p90(values):
    return statistics.quantiles(values, n=10, method="inclusive")[8]


def end_to_end(cell, t_start, t_end, done, setup_s):
    wall = t_end - t_start
    tpot = [(d.complete - d.first) / (len(d.tokens) - 1)
            for d in done if len(d.tokens) > 1]
    values = {
        "gen_tok_s": sum(len(d.tokens) for d in done) / wall,
        "tpot_p90_ms": 1e3 * p90(tpot),
        "setup_s": setup_s,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def in_use_bytes(devices) -> int:
    """Bytes in use now on the fullest chip: what serving holds, against
    ``memory_peak_bytes``, the process's peak, which build sets."""
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in devices)


def main(argv=None) -> int:
    args = parse(argv)
    from harness.cell import CHECKOUT, load_cell

    cell = load_cell(args.workload)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(CHECKOUT / ".jax_cache"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from harness.peaks import peaks_for

    devices = devices[: cell.chips]
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, peaks_for(devices[0].device_kind))
    print(json.dumps(result))
    return 0


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             peaks: dict) -> dict:
    """Set-up, window and check of one run; returns the result object.
    Prints the compared numbers, each beside its limit, as the last lines
    of standard error."""
    from harness import check, serve, trace as tr
    from harness.record import Run, read_all
    from repro.launch.compile_cache import compile_stats, enable_compile_cache

    enable_compile_cache()
    rec = serve.Recorder(annotate=trace)
    sv = serve.build(cell, seed, rec)
    serve.warm_up(sv)
    before = compile_stats()
    in_use = {"after_warm_up": in_use_bytes(devices)}
    setup_s = time.perf_counter() - T_PROCESS

    traced = {}
    if trace:
        traced["dir"] = tr.start()
        traced["t0"] = time.perf_counter()

    def on_wave(i, t):
        if "dir" in traced and "t1" not in traced \
                and t - traced["t0"] >= min(TRACE_SECONDS, seconds):
            traced["t1"] = t
            tr.stop()

    t_start, t_end, done = serve.run_window(sv, seed, seconds, on_wave)
    window_compiles = (compile_stats()["cache_requests"]
                       - before["cache_requests"])
    in_use["window_end"] = in_use_bytes(devices)
    stats = [d.memory_stats() or {} for d in devices]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                       for s in stats)}
    result = {"correct": False, "attempted": len(done), "failed": 0}
    if trace:
        dev = tr.reduce(traced["dir"], devices)
        run = Run(config=cell.config, dims=sv.dims,
                  batch=sv.spec.batch, chips=cell.chips,
                  page_size=sv.engine.page_size,
                  page_bytes=sv.engine.pool.pool_bytes()
                  / (sv.dims.layers * sv.engine.pool.num_pages),
                  t0=traced["t0"], t1=traced["t1"], all_spans=rec.spans,
                  trace=dev, peaks=peaks)
        values = read_all(run, [m["name"] for m in cell.per_layer])
        units = {m["name"]: m["unit"] for m in cell.per_layer}
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in values.items()}
        device["busy_s"] = dev.busy_s
        device["window_s"] = dev.window_s
        result["breakdown"] = dev.breakdown()
    else:
        result["metrics"] = end_to_end(cell, t_start, t_end, done, setup_s)
    result["device"] = device

    conf = cell.config
    samples = check.samples(sv, done, cell.traffic, seed)
    dims = sv.dims
    del sv, rec
    gc.collect()

    t_ref = time.perf_counter()
    numbers = check.measure(cell.family, samples, seed, dims,
                            cell.family.stated(conf),
                            check.pad_len(cell.traffic))
    ok, rows = check.verdict(numbers, conf["check"]["limits"])
    result["correct"] = ok
    result["window_compiles"] = window_compiles
    result["window_s"] = t_end - t_start
    result["reference_s"] = time.perf_counter() - t_ref
    result["memory_in_use_bytes"] = in_use
    result["numbers"] = {k: v for k, v in numbers.items()
                         if k not in conf["check"]["limits"]}
    result["check"] = {name: {"value": v, "limit": lim}
                       for name, v, lim in rows}
    for name, v, lim in rows:
        print(f"check {name} {v} limit {lim}", file=sys.stderr)
    return result


if __name__ == "__main__":
    sys.exit(main())
