"""Chip smoke: the residue-resident serving path end to end on a TPU.

    python chip_smoke.py              # one chip: serve + compare with ref
    python chip_smoke.py --chips 4    # channel-parallel decode on 3 of 4

One chip.  ``yi-6b`` at its published widths (d_model 4096, 32 query / 4 KV
heads of 128, d_ff 11008, vocab 64000) with the depth cut to :data:`LAYERS`,
random weights from ``--seed``, ``system="rns"`` (P21, 4-bit,
residue-resident).  Eight seeded requests (prompts of 128-512 tokens,
``max_new`` 32) go through ``RequestScheduler.serve`` on the paged engine
that ``launch/serve.py`` builds (``page_size`` 64, ``rns8r`` KV pages,
``policy="strict"``, so the paged kernel's syndrome output is on the
path), with every kernel compiled by Mosaic.  Then, on the same chip:

* the attention kernels against their ``ref`` oracle (float32 matmuls) at
  the served widths — flash prefill on seeded q/k/v with the prompts'
  lengths, paged decode over every layer's served ``rns8r`` pages — within
  :data:`ATTN_TOL`, with zero syndromes;
* the same requests served again with the residue matmuls pinned to their
  ``ref`` implementation: prefill logits, first-step decode logits and
  every served token must be bit-identical;
* the fault counters must stay zero.

Logits are not compared with attention on ``ref`` too.  4-bit activation
quantization of random weights turns an ulp of attention output into a
flipped code, and the flips compound: on a v5e even two ``ref`` runs that
differ only in matmul precision give logits 40% of their range apart.

Four chips (``--chips 4``): a few ``model.decode`` steps of the same
prepared model under ``make_ctx(mesh, channel_shard=True)`` on a
(data=1, model=3) mesh — the partial-CRT psum schedule — against
single-chip decode of the same parameters: logits bit-identical, no
gather fallback, 7·L+1 tensor-axis psums in the compiled step.

Exits non-zero, printing no result, when JAX finds no TPU.  The last line
of a passing run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch.compile_cache import (  # noqa: E402
    compile_stats, enable_compile_cache)

enable_compile_cache()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "yi-6b"
LAYERS = 8              # of 32: what fits 16 GB with float + residue forms
REQUESTS = 8
PROMPT_LEN = (128, 512)
MAX_NEW = 32
PAGE = 64
# attention kernel vs its float32 oracle: max |out - ref| over max |ref|.
# Outputs (the kernel's or the oracle's) are rounded to bf16, whose ulp is
# up to 2^-8 of a value: prefill read 4.2e-3 on a v5e at these widths.  A
# query head paired with the wrong KV head lands near 1.
ATTN_TOL = 1e-2
DECODE_STEPS = 4        # --chips 4: decode steps compared


def log(name: str, value) -> None:
    print(f"[chip_smoke] {name}: {value}", flush=True)


def describe(cfg) -> str:
    from repro.configs import get_config

    return (f"{cfg.name} d_model={cfg.d_model} heads={cfg.n_heads}/"
            f"{cfg.n_kv}x{cfg.hd} d_ff={cfg.d_ff} vocab={cfg.vocab} "
            f"layers={cfg.n_layers} (depth cut from "
            f"{get_config(cfg.name).n_layers}; widths published)")


def serve_args(layers: int, seed: int, arch_flags=()):
    from repro.launch import serve

    return serve.parser().parse_args(
        ["--arch", ARCH, *arch_flags, "--system", "rns",
         "--layers", str(layers), "--batch", str(REQUESTS),
         "--kv-format", "rns8r", "--policy", "strict", "--seed", str(seed)])


def make_requests(vocab: int, seed: int, prompt_len=PROMPT_LEN,
                  max_new=MAX_NEW):
    from repro.serving.scheduler import Request

    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_len[0], prompt_len[1] + 1, REQUESTS)
    return [Request(rid=i, tokens=rng.integers(0, vocab, n).astype(np.int32),
                    max_new=max_new) for i, n in enumerate(lens)]


def probe_logits(engine, prompts, tok=None, attn_seed=None):
    """Prefill logits of ``prompts`` (one per slot), the logits of one
    paged decode step fed ``tok`` (default: the prefill argmax), its
    syndrome count and, given ``attn_seed``, the attention kernels' errors
    against their oracle while the prompts' pages are live."""
    B = engine.batch
    admitted = engine.admit_prefill(
        dict(enumerate(prompts)), {s: len(p) + 1 for s, p in
                                   enumerate(prompts)})
    pre = np.stack([np.asarray(admitted[s][0], np.float32) for s in range(B)])
    if tok is None:
        tok = pre.argmax(-1).astype(np.int32)
    tabs = jnp.asarray(np.stack([
        engine.pool.tab_row(admitted[s][1].pages, engine.n_pmax)
        for s in range(B)]))
    lens = jnp.asarray([len(p) for p in prompts], jnp.int32)
    attn = (None if attn_seed is None
            else attention_errors(engine, tabs, lens, attn_seed))
    step = jax.jit(functools.partial(
        engine.model.decode_paged, page_size=engine.page_size,
        cache_dtype=engine.cache_dtype, with_syndrome=True))
    logits, _, syn = step(engine.params, jnp.asarray(tok[:, None]),
                          engine.pool.kv, tabs, lens)
    for s in range(B):
        engine.pool.release(admitted[s][1].pages)
    return (pre, np.asarray(logits, np.float32), tok,
            int(np.asarray(syn).sum()), attn)


def attention_errors(engine, tabs, lens, seed: int) -> dict:
    """Attention kernels vs their ``ref`` oracle under float32 matmuls, at
    the served widths: flash prefill on seeded q/k/v masked to the
    prompts' lengths, and paged decode (with syndrome) on seeded queries
    over every layer's pages as the probe's prefill left them.  Returns
    each kernel's worst :func:`rel_err` and the syndrome total."""
    from repro.numerics import attention as nxattn
    from repro.numerics import kv_pages as kvp
    from repro.numerics.registry import resolve_backend

    cfg, B = engine.model.cfg, engine.batch
    S = int(np.asarray(lens).max())
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    q, k, v = (normal(B, S, cfg.n_heads, cfg.hd),
               normal(B, S, cfg.n_kv, cfg.hd), normal(B, S, cfg.n_kv, cfg.hd))
    qd = normal(B, cfg.n_heads, cfg.hd)

    def run(backend):
        pre = jax.jit(lambda q, k, v, n: nxattn.flash_attention(
            q, k, v, causal=True, kv_len=n, backend=backend))
        dec = jax.jit(lambda q, kv, i, tab, n: nxattn.paged_decode(
            q, kvp.layer_slice(kv, i), tab, n, page_size=engine.page_size,
            backend=backend, syndrome=True))
        return (np.asarray(pre(q, k, v, lens), np.float32),
                [dec(qd, engine.pool.kv, jnp.int32(i), tabs, lens)
                 for i in range(cfg.n_layers)])

    pre_k, dec_k = run(resolve_backend(None))
    with jax.default_matmul_precision("float32"):
        pre_r, dec_r = run("ref")
    return {
        "prefill": rel_err(pre_k, pre_r),
        "paged_decode": max(rel_err(np.asarray(a), np.asarray(b))
                            for (a, _), (b, _) in zip(dec_k, dec_r)),
        "syndromes": int(sum(np.asarray(s).sum() for _, s in dec_k + dec_r)),
    }


def rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def agreed_prefix(a: np.ndarray, b: np.ndarray) -> int:
    n = min(len(a), len(b))
    diff = np.nonzero(a[:n] != b[:n])[0]
    return int(diff[0]) if diff.size else n


def fault_total(stats) -> int:
    return sum(dataclasses.asdict(stats.faults).values())


def run_serving(layers: int, seed: int, arch_flags=(),
                prompt_len=PROMPT_LEN, max_new=MAX_NEW) -> None:
    """Serve the seeded requests on the kernels, check attention against
    its oracle, serve again with the residue matmuls on ``ref``; compare."""
    from repro.launch import serve
    from repro.models.api import build_model
    from repro.serving.engine import ServingEngine
    from repro.serving.scheduler import RequestScheduler

    args = serve_args(layers, seed, arch_flags)
    s_max = prompt_len[1] + max_new
    t0 = time.perf_counter()
    cfg, engine = serve.build_engine(args, s_max=s_max, page_size=PAGE)
    jax.block_until_ready(engine.params)
    log("model", describe(cfg))
    log("engine", f"system=rns P21 4-bit prepared, paged={engine.paged} "
        f"page_size={engine.page_size} kv_format={engine.kv_format} "
        f"policy={engine.policy} batch={engine.batch} s_max={s_max}")
    log("build_s", round(time.perf_counter() - t0, 3))
    if not engine.paged:
        raise RuntimeError("the engine did not take the paged path")

    reqs = make_requests(cfg.vocab, seed, prompt_len, max_new)
    log("prompt_lens", [len(r.tokens) for r in reqs])
    t0 = time.perf_counter()
    served = RequestScheduler(engine).serve(reqs)
    log("serve_s (incl. compile)", round(time.perf_counter() - t0, 3))
    log("compile", compile_stats())
    log("tokens_per_request", [len(r.result) for r in served])
    if any(len(r.result) != r.max_new for r in served):
        raise RuntimeError("a request came back short")
    log("engine_stats", dataclasses.asdict(engine.stats))
    if fault_total(engine.stats):
        raise RuntimeError(f"fault counters moved: {engine.stats.faults}")
    prompts = [r.tokens for r in reqs]
    pre, dec, tok, syn, attn = probe_logits(engine, prompts, attn_seed=seed)
    if syn:
        raise RuntimeError(f"in-kernel syndrome count {syn} on clean pages")
    if not (np.isfinite(pre).all() and np.isfinite(dec).all()):
        raise RuntimeError("non-finite logits")
    log("logits_shape", (pre.shape, dec.shape))
    # the served stream opens with the probed logits' argmaxes: prefill's,
    # then the fused loop's first paged decode step's
    probed = np.stack([tok, dec.argmax(-1)], axis=1)
    if not np.array_equal(np.stack([r.result[:2] for r in served]), probed):
        raise RuntimeError("fused-loop tokens disagree with the probed logits")
    log("attention_rel_err", attn)
    log("attn_tol", ATTN_TOL)
    if attn["syndromes"]:
        raise RuntimeError("attention check saw syndromes on clean pages")
    if max(attn["prefill"], attn["paged_decode"]) > ATTN_TOL:
        raise RuntimeError(f"attention kernels differ from ref beyond "
                           f"{ATTN_TOL}")

    # the same requests with the residue matmuls on their jnp reference
    eng_ref = ServingEngine(
        build_model(cfg, system="rns", rns_impl="ref"), engine.params,
        batch=engine.batch, s_max=s_max, prepare=False, page_size=PAGE,
        kv_format=engine.kv_format, policy=engine.policy)
    t0 = time.perf_counter()
    ref_served = RequestScheduler(eng_ref).serve(
        make_requests(cfg.vocab, seed, prompt_len, max_new))
    log("ref_serve_s (incl. compile)", round(time.perf_counter() - t0, 3))
    pre_r, dec_r, _, syn_r, _ = probe_logits(eng_ref, prompts, tok)
    if fault_total(eng_ref.stats) or syn_r:
        raise RuntimeError(f"ref fault counters moved: {eng_ref.stats.faults}")
    log("prefill_logits_max_abs_diff", float(np.abs(pre - pre_r).max()))
    log("decode_logits_max_abs_diff", float(np.abs(dec - dec_r).max()))
    agreed = [agreed_prefix(a.result, b.result)
              for a, b in zip(served, ref_served)]
    log("greedy_agreed_tokens", agreed)
    if not (np.array_equal(pre, pre_r) and np.array_equal(dec, dec_r)):
        raise RuntimeError("logits differ from the ref residue matmuls")
    if min(agreed) < max_new:
        raise RuntimeError("served tokens differ from the ref residue matmuls")


def count_tp_psums(hlo: str, tp_size: int, layers: int) -> int:
    """s32 all-reduce operands over the tensor (channel) axis, with those
    inside the layer scan's loop body counted once per layer.

    XLA's combiner may merge independent psums into one tuple all-reduce,
    so operands are counted, not ops; the TPU compiler drops the loop's
    trip-count annotation, so the body is recognized by its op metadata.
    """
    import re

    n = 0
    for line in hlo.splitlines():
        m = re.search(r"= (.+?) all-reduce(-start)?\(", line)
        if not m:
            continue
        groups = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
        iota = re.search(r"replica_groups=\[\d+,(\d+)\]", line)
        size = (len(groups.group(1).split(",")) if groups
                else int(iota.group(1)) if iota else 0)
        types = re.findall(r"(\w+)\[", m.group(1))
        if size != tp_size or set(types) != {"s32"}:
            continue
        operands = len(types) // (2 if m.group(2) else 1)
        n += operands * (layers if "/while/body/" in line else 1)
    return n


def matmul_identity(params, sharded, ctx, rng) -> dict:
    """Every prepared weight's residue matmul (layer 0 of the stacked ones,
    and the logits weight) on seeded 4-bit activations, one chip against
    the channel-parallel schedule on ``ctx``'s mesh: name -> bit-identical.
    """
    from repro import numerics as nx
    from repro.numerics import ResidueTensor
    from repro.parallel.sharding import shard_ctx

    def is_rt(x):
        return isinstance(x, ResidueTensor)

    def matmul(a, w):
        return nx.matmul(a, w, max_abs_a=7)

    out = {}
    for (path, w), ws in zip(
            jax.tree_util.tree_flatten_with_path(params, is_leaf=is_rt)[0],
            jax.tree_util.tree_leaves(sharded, is_leaf=is_rt)):
        if not is_rt(w):
            continue
        if w.planes.ndim == 4:                  # stacked over layers
            w, ws = (jax.tree_util.tree_map(lambda x: x[0], t)
                     for t in (w, ws))
        a = jnp.asarray(rng.integers(-7, 8, (REQUESTS, w.planes.shape[-2])),
                        jnp.int32)
        one = jax.jit(matmul)(a, w)
        with shard_ctx(ctx):
            chan = jax.jit(matmul)(a, ws)
        out[jax.tree_util.keystr(path)] = bool(np.array_equal(one, chan))
    return out


def run_channel_shard(layers: int, seed: int, arch_flags=()) -> None:
    """Channel-parallel decode (C=3 over the model axis) vs one chip."""
    from jax.sharding import Mesh

    from repro.configs import get_config
    from repro.launch.mesh import make_ctx
    from repro.models.api import build_model
    from repro.numerics import runners
    from repro.parallel.sharding import shard_ctx, shard_params

    cfg = get_config(ARCH)
    if "--reduced" in arch_flags:
        cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, n_layers=layers)
    log("model", describe(cfg))
    model = build_model(cfg, system="rns")
    params = model.prepare_params(model.init(jax.random.PRNGKey(seed)))
    C = 3                                   # P21's channel count
    mesh = Mesh(np.array(jax.devices()[:C]).reshape(1, C), ("data", "model"))
    ctx = make_ctx(mesh, channel_shard=True)
    log("mesh", dict(mesh.shape))
    B, s_max = REQUESTS, 64
    rng = np.random.default_rng(seed)
    toks = [jnp.asarray(rng.integers(0, cfg.vocab, (B, 1)), jnp.int32)]
    fb0 = runners.fallback_gather_count()
    with shard_ctx(ctx):
        sharded = shard_params(params, ctx)

    same_mm = matmul_identity(params, sharded, ctx, rng)
    log("matmul_bit_identical", same_mm)

    def decode_steps(p, options, mesh_ctx=None):
        """Logits of DECODE_STEPS steps fed ``toks`` (the one-chip greedy
        stream, extended by the first caller), and the compiled step."""
        # a fresh jit per call: one must not serve both the single-device
        # and the mesh trace
        step = jax.jit(lambda p, t, c, i: model.decode(p, t, c, i),
                       compiler_options=options)
        with shard_ctx(mesh_ctx):
            cache, out = model.init_cache(B, s_max), []
            for i in range(DECODE_STEPS):
                logits, cache = step(p, toks[i], cache, jnp.int32(i))
                out.append(np.asarray(logits))
                if len(toks) == i + 1:
                    toks.append(jnp.asarray(out[-1].argmax(-1)[:, None],
                                            jnp.int32))
            hlo = step.lower(p, toks[0], model.init_cache(B, s_max),
                             jnp.int32(0)).compile().as_text()
        return out, hlo

    same = {}
    for name, options in (("default", None),
                          ("no_excess_precision",
                           {"xla_allow_excess_precision": False})):
        base, _ = decode_steps(params, options)
        t0 = time.perf_counter()
        mesh_out, hlo = decode_steps(sharded, options, ctx)
        log(f"mesh_decode_s (incl. compile, {name})",
            round(time.perf_counter() - t0, 3))
        same[name] = [bool(np.array_equal(a, b))
                      for a, b in zip(base, mesh_out)]
        log(f"bit_identical_steps ({name})", same[name])
        log(f"max_abs_diff ({name})", max(float(np.abs(a - b).max())
                                          for a, b in zip(base, mesh_out)))
    psums = count_tp_psums(hlo, C, layers)
    fallbacks = runners.fallback_gather_count() - fb0
    log("tp_psums", f"{psums} (expected 7*L+1 = {7 * layers + 1})")
    log("fallback_gathers", fallbacks)
    if not all(same_mm.values()):
        raise RuntimeError("channel-parallel residue matmuls differ from "
                           "one chip")
    if not all(same["no_excess_precision"]):
        raise RuntimeError("channel-shard logits differ from one chip")
    if fallbacks:
        raise RuntimeError(f"{fallbacks} channel_shard gather fallback(s)")
    if psums != 7 * layers + 1:
        raise RuntimeError(f"{psums} tensor-axis psums, want {7 * layers + 1}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.numerics.registry import resolve_backend

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if resolve_backend(None) != "pallas":
        raise RuntimeError("the kernel registry does not resolve to pallas")
    n = len(jax.devices())
    log("device", f"{dev.device_kind} x{n}")
    if n < args.chips:
        raise RuntimeError(f"--chips {args.chips} needs {args.chips} "
                           f"devices, JAX sees {n}")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_channel_shard(LAYERS, args.seed)
    else:
        run_serving(LAYERS, args.seed)
    log("wall_s", round(time.perf_counter() - t0, 3))
    log("compile_cache", compile_stats())
    log("peak_bytes_in_use", [d.memory_stats().get("peak_bytes_in_use")
                              for d in jax.devices()])
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
