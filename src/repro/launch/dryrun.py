import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (architecture x shape x mesh)
cell with ShapeDtypeStruct inputs (no allocation) on placeholder devices.

These are CPU placeholder compiles: 512 forced *host* devices stand in for
the pod, so the programs are compiled by XLA's CPU backend, not by the TPU
compiler — they check sharding rules, collectives and memory accounting,
not Mosaic legality (``tests/test_tpu_compile.py`` does that for the
kernels).  ``JAX_PLATFORMS=cpu`` is pinned so the ``--all`` children never
take a machine's TPU either.

The three lines above MUST stay the first statements in this module — jax
locks the device count at first init, and smoke tests / benches must keep
seeing one device, so the flags live here and only here.

Per cell this driver:
  1. builds the model + step function (train_step for train_4k,
     prefill/decode steps for the serving shapes); under --system rns /
     sdrns the serving cells consume *residue-resident* parameter trees
     (ResidueTensor leaves from prepare_params) with sharded digit /
     residue planes (--channel-shard selects the C-split layout);
  2. derives parameter / optimizer / cache / batch shardings from
     parallel/sharding.py rules (typed traversal over ResidueTensor
     leaves);
  3. ``jax.jit(step, in_shardings=..., out_shardings=...).lower(...)
     .compile()`` under the production mesh;
  4. records memory_analysis / cost_analysis / parsed collective bytes to a
     JSON file consumed by roofline/analysis.py and EXPERIMENTS.md.

``--all`` iterates cells in a fresh subprocess each (isolation: one cell's
compile cannot poison the next; restartability: finished JSONs are skipped).
"""
import argparse
import json
import subprocess
import sys
import time
import traceback


def _cell_filename(arch, shape, mesh_name, system, tag):
    suffix = f"_{tag}" if tag else ""
    return f"{arch}_{shape}_{mesh_name}_{system}{suffix}.json"


def run_cell(arch: str, shape_name: str, mesh_name: str = "single", *,
             system: str = "bns", seq_shard: bool = False,
             channel_shard: bool = False, reduced: bool = False,
             out_dir: str = "experiments/dryrun", tag: str = "",
             save_hlo: bool = False) -> dict:
    # imports deferred: jax must init with the forced device count
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import SHAPES, get_config
    from repro.launch.mesh import make_ctx, make_production_mesh
    from repro.launch.params import model_flops_total, param_counts
    from repro.models.api import build_model
    from repro.parallel.sharding import (param_specs, shard_ctx,
                                         specs_from_roles, logical_to_spec)
    from repro.roofline.analysis import collective_bytes
    from repro.roofline.hlo_cost import analyze_hlo
    from repro.train.loop import make_train_step
    from repro.train.optimizer import OptConfig, init_opt_state

    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()  # CI smoke: tiny dims, same mesh + rule set
    shape = SHAPES[shape_name]
    if mesh_name == "channel":
        # channel-parallel pod slice: the model axis sized to the moduli
        # channel count (C=3 for the serving default P21 set) so the
        # C-split psum schedule engages instead of falling back
        from repro.core.moduli import P21
        mesh = make_production_mesh(channel=P21.num_channels)
    else:
        mesh = make_production_mesh(multi_pod=mesh_name == "multi")
    ctx = make_ctx(mesh, seq_shard=seq_shard, channel_shard=channel_shard)
    # dry-run lowers on CPU for cost analysis: pin the pure-jnp ref
    # oracle (same flop/byte structure as the kernel) rather than letting
    # the registry auto-select the Pallas interpreter off-TPU.  sdrns
    # compiles through the "cost" backend — exact decoded values with the
    # fused kernel's useful-work envelope; the digit-bit-exact ref would
    # materialize an O(M*K*N*n^2) intermediate, unlowerable at these
    # shapes and meaningless for cost numbers.
    model = build_model(cfg, system=system,
                        rns_impl={"bns": None, "rns": "ref",
                                  "sdrns": "cost"}[system])
    prepare = system in ("rns", "sdrns") and shape.kind != "train"

    def shardings(spec_tree):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), spec_tree,
            is_leaf=lambda s: isinstance(s, P))

    t0 = time.time()
    with shard_ctx(ctx):
        params_shape = jax.eval_shape(model.init, jax.random.key(0))
        if prepare:
            # residue-resident serving cells: the step consumes a prepared
            # tree (ResidueTensor leaves).  param_specs traverses the typed
            # leaves, so psh matches the prepared treedef — sharded residue
            # planes ride in_shardings like every raw-array param.
            params_shape = jax.eval_shape(model.prepare_params, params_shape)
        pspecs = param_specs(params_shape, ctx)
        psh = shardings(pspecs)
        batch_struct = model.input_specs(shape)

        def batch_sharding(struct):
            def one(leaf):
                if leaf.ndim == 0:
                    return NamedSharding(mesh, P())
                roles = ["dp"] + [None] * (leaf.ndim - 1)
                return NamedSharding(
                    mesh, logical_to_spec(ctx, leaf.shape, roles))
            return jax.tree_util.tree_map(one, struct)

        bsh = batch_sharding(batch_struct)

        if shape.kind == "train":
            opt_cfg = OptConfig(moment_dtype=cfg.opt_state_dtype)
            opt_shape = jax.eval_shape(
                lambda p: init_opt_state(p, opt_cfg), params_shape)
            osh = {"m": psh, "v": psh,
                   "step": NamedSharding(mesh, P())}
            n_micro = max(cfg.microbatch, 1)
            step_fn = make_train_step(model, opt_cfg, n_micro)
            jitted = jax.jit(step_fn,
                             in_shardings=(psh, osh, bsh),
                             out_shardings=(psh, osh, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_shape, opt_shape, batch_struct)
        elif shape.kind == "prefill":
            import functools as _ft
            cache_shape = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            csh = shardings(specs_from_roles(
                cache_shape, model.cache_roles(cache_shape), ctx))
            jitted = jax.jit(_ft.partial(model.prefill,
                                         s_max=shape.seq_len),
                             in_shardings=(psh, bsh),
                             out_shardings=(None, csh))
            lowered = jitted.lower(params_shape, batch_struct)
        else:  # decode
            cache_shape = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            csh = shardings(specs_from_roles(
                cache_shape, model.cache_roles(cache_shape), ctx))
            jitted = jax.jit(model.decode,
                             in_shardings=(psh, bsh["token"], csh,
                                           NamedSharding(mesh, P())),
                             out_shardings=(None, csh),
                             donate_argnums=(2,))
            lowered = jitted.lower(params_shape, batch_struct["token"],
                                   cache_shape, batch_struct["pos"])

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    try:
        mem = compiled.memory_analysis()
        mem_record = {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes")
            if hasattr(mem, k)
        }
    except Exception as e:  # CPU backend may not implement it
        mem_record = {"error": repr(e)}

    try:
        cost = compiled.cost_analysis()
        cost = {k: float(v) for k, v in cost.items()
                if isinstance(v, (int, float))}
    except Exception as e:
        cost = {"error": repr(e)}

    hlo = compiled.as_text()
    coll = collective_bytes(hlo)          # naive (per-program-text) counts
    hlo_cost = analyze_hlo(hlo).as_dict()  # trip-count-aware profile

    # analytic per-device residency from the sharding specs (the CPU
    # backend's memory_analysis misses HBM residency semantics)
    def sharded_bytes(shapes, specs):
        total = 0
        for leaf, spec in zip(jax.tree_util.tree_leaves(shapes),
                              jax.tree_util.tree_leaves(
                                  specs, is_leaf=lambda s: isinstance(s, P))):
            n = 1
            for d in leaf.shape:
                n *= d
            denom = 1
            for entry in spec:
                if entry is None:
                    continue
                names = entry if isinstance(entry, tuple) else (entry,)
                for nm in names:
                    denom *= mesh.shape[nm]
            total += n * leaf.dtype.itemsize // max(denom, 1)
        return total

    resident = sharded_bytes(params_shape, pspecs)
    extra = {}
    if shape.kind == "train":
        extra["opt_bytes_dev"] = sharded_bytes(
            opt_shape["m"], pspecs) + sharded_bytes(opt_shape["v"], pspecs)
    if shape.kind in ("prefill", "decode"):
        croles = model.cache_roles(cache_shape)
        cspecs = specs_from_roles(cache_shape, croles, ctx)
        extra["cache_bytes_dev"] = sharded_bytes(cache_shape, cspecs)

    counts = param_counts(cfg)
    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "system": system, "tag": tag,
        "n_devices": mesh.size,
        "seq_shard": seq_shard,
        "channel_shard": channel_shard,
        "reduced": reduced,
        "residue_resident": prepare,
        "params_total": counts["total"],
        "params_active": counts["active"],
        "model_flops_total": model_flops_total(cfg, shape),
        "param_bytes_dev": resident,
        **extra,
        "memory_analysis": mem_record,
        "cost_analysis": cost,
        "collectives": coll,
        "hlo_cost": hlo_cost,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "hlo_lines": hlo.count("\n"),
    }
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir,
                        _cell_filename(arch, shape_name, mesh_name,
                                       system, tag))
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    if save_hlo:
        with open(path.replace(".json", ".hlo.txt"), "w") as f:
            f.write(hlo)
    return record


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=("single", "multi", "channel"),
                    default="single",
                    help="'channel' = single-pod mesh with the model axis "
                         "sized to the moduli channel count (pair with "
                         "--channel-shard for the psum decode schedule)")
    ap.add_argument("--system", "--backend", dest="system", default="bns",
                    choices=("bns", "rns", "sdrns"),
                    help="number system (--backend is a deprecated alias); "
                         "rns/sdrns serving cells compile with "
                         "residue-resident (ResidueTensor-leaf) params")
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--channel-shard", action="store_true",
                    help="C-split residue-plane layout (moduli channels "
                         "over the model axis, N replicated)")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced() arch dims — CI smoke cells on the "
                         "full production mesh")
    ap.add_argument("--tag", default="")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    ap.add_argument("--all", action="store_true",
                    help="run every runnable cell on both meshes via "
                         "subprocesses; skips existing JSONs")
    ap.add_argument("--timeout", type=int, default=3600)
    args = ap.parse_args(argv)

    if args.all:
        from repro.configs import all_cells  # light import (no jax state)
        jobs = []
        for arch, shape, runnable, reason in all_cells():
            for mesh_name in ("single", "multi"):
                if not runnable:
                    _record_skip(args.out_dir, arch, shape, mesh_name,
                                 args.system, reason)
                    continue
                fn = _cell_filename(arch, shape, mesh_name, args.system,
                                    args.tag)
                if os.path.exists(os.path.join(args.out_dir, fn)):
                    print(f"[skip existing] {fn}")
                    continue
                jobs.append((arch, shape, mesh_name))
        fails = []
        for arch, shape, mesh_name in jobs:
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mesh_name,
                   "--system", args.system, "--out-dir", args.out_dir]
            if args.seq_shard:
                cmd.append("--seq-shard")
            if args.channel_shard:
                cmd.append("--channel-shard")
            if args.reduced:
                cmd.append("--reduced")
            if args.tag:
                cmd += ["--tag", args.tag]
            print(f"[dryrun] {arch} x {shape} x {mesh_name} ...", flush=True)
            r = subprocess.run(cmd, timeout=args.timeout)
            if r.returncode != 0:
                fails.append((arch, shape, mesh_name))
                print(f"[FAIL] {arch} x {shape} x {mesh_name}", flush=True)
        print(f"[dryrun --all] done; {len(fails)} failures: {fails}")
        return 1 if fails else 0

    assert args.arch and args.shape, "--arch and --shape required"
    try:
        rec = run_cell(args.arch, args.shape, args.mesh,
                       system=args.system, seq_shard=args.seq_shard,
                       channel_shard=args.channel_shard,
                       reduced=args.reduced,
                       out_dir=args.out_dir, tag=args.tag,
                       save_hlo=args.save_hlo)
    except Exception:
        traceback.print_exc()
        return 1
    print(json.dumps({k: rec[k] for k in
                      ("arch", "shape", "mesh", "param_bytes_dev",
                       "compile_s", "hlo_lines")}, indent=1))
    print("memory_analysis:", json.dumps(rec["memory_analysis"]))
    print("hlo_cost flops/bytes/coll:",
          rec["hlo_cost"]["flops"], rec["hlo_cost"]["bytes"],
          rec["hlo_cost"]["coll_bytes"])
    print("whiles:", rec["hlo_cost"]["whiles"],
          "warnings:", rec["hlo_cost"]["warnings"])
    return 0


def _record_skip(out_dir, arch, shape, mesh_name, system, reason):
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir,
                        _cell_filename(arch, shape, mesh_name, system,
                                       "") .replace(".json", "_SKIP.json"))
    if os.path.exists(path):
        return
    with open(path, "w") as f:
        json.dump({"arch": arch, "shape": shape, "mesh": mesh_name,
                   "skipped": True, "reason": reason}, f, indent=1)


if __name__ == "__main__":
    sys.exit(main())
