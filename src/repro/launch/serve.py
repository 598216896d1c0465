"""Serving driver: batched prefill + decode on any assigned architecture.

CPU demo runs the reduced config; the full configs lower through the same
prefill/decode step functions in launch/dryrun.py (decode_32k / long_500k
cells).  :func:`build_engine` is the one place a served model is
assembled; ``chip_smoke.py`` serves through the engine it returns.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
      --batch 4 --prompt-len 16 --max-new 16
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import build_model
from repro.serving.engine import ServingEngine


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--system", "--backend", dest="system", default="bns",
                    choices=("bns", "rns", "sdrns"),
                    help="number system (--backend is a deprecated alias)")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to this many layers (widths stay "
                         "published)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-prepare", action="store_true",
                    help="keep weights float and convert per call (baseline "
                         "for the residue-resident default; see "
                         "benchmarks/serving_bench.py)")
    ap.add_argument("--kv-format", default="bf16",
                    choices=("bf16", "rns8", "rns4", "rns8r"),
                    help="KV page storage of the paged engine")
    ap.add_argument("--policy", default="off",
                    choices=("off", "detect", "correct", "strict"),
                    help="KV fault policy (needs --kv-format rns8r)")
    ap.add_argument("--spec", default=None, metavar="DRAFTER[:K]",
                    help='speculative decoding drafter: "ngram[:k]" or '
                         '"rns[:k]" (greedy only; paged engines). Output '
                         "tokens are bit-identical to plain decoding")
    return ap


def build_engine(args, *, s_max: int | None = None, **engine_kw):
    """``(cfg, engine)`` for parsed ``args``: config (depth cut by
    ``--layers``), model, seeded random weights, and the serving engine.
    ``s_max`` defaults to the prompt plus the decode budget."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    # rns_impl=None: the repro.numerics backend registry auto-selects the
    # implementation by platform (pallas on TPU, interpret elsewhere)
    model = build_model(cfg, system=args.system)
    params = model.init(jax.random.PRNGKey(args.seed))
    if s_max is None:
        s_max = args.prompt_len + args.max_new + 1
        if cfg.family == "vlm":
            s_max += cfg.n_img_tokens
        if cfg.is_encdec:
            s_max = args.prompt_len  # encoder memory; decoder len = dec_len
    engine = ServingEngine(model, params, batch=args.batch, s_max=s_max,
                           prepare=not args.no_prepare, spec=args.spec,
                           kv_format=args.kv_format, policy=args.policy,
                           **engine_kw)
    return cfg, engine


def main(argv=None):
    enable_compile_cache()
    args = parser().parse_args(argv)
    cfg, engine = build_engine(args)
    key = jax.random.PRNGKey(args.seed)
    B, P = args.batch, args.prompt_len
    rng = np.random.default_rng(args.seed)
    if cfg.is_encdec:
        from repro.models.frontends import synthetic_frames
        inputs = {"frames": synthetic_frames(key, B, P, cfg),
                  "tokens": rng.integers(0, cfg.vocab, (B, 8)).astype(
                      np.int32)}
        prompt_len = 8
    elif cfg.family == "vlm":
        from repro.models.frontends import synthetic_patches
        inputs = {"tokens": rng.integers(0, cfg.vocab, (B, P)).astype(
            np.int32),
            "patches": synthetic_patches(key, B, cfg)}
        prompt_len = P + cfg.n_img_tokens
    else:
        inputs = {"tokens": rng.integers(0, cfg.vocab, (B, P)).astype(
            np.int32)}
        prompt_len = P

    t0 = time.time()
    res = engine.generate(inputs, max_new=args.max_new,
                          prompt_len=prompt_len,
                          temperature=args.temperature, key=key)
    dt = time.time() - t0
    tput = B * args.max_new / dt
    print(f"[serve] {args.arch} B={B} prompt={prompt_len} "
          f"new={args.max_new}: {dt:.2f}s ({tput:.1f} tok/s)")
    if engine.stats.spec is not None:
        sp = engine.stats.spec
        print(f"[serve] spec={args.spec}: {sp.verify_steps} verify steps "
              f"for {sp.emitted} tokens (accept={sp.acceptance_rate:.2f}, "
              f"mean block={sp.mean_accepted_len:.2f})")
    for b in range(min(B, 2)):
        print(f"  seq{b}: {res.tokens[b].tolist()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
