"""End-to-end training driver.

CPU-friendly by default (reduced configs, synthetic data, fault-tolerant
runner); the same code path lowers onto the production mesh when the device
count allows — sharding comes from the identical rule set the dry-run
compiles, so what trains small here is what deploys big.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --reduced \
      --steps 200 --batch 8 --seq 64
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-780m --reduced \
      --system rns --steps 50
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.data.tokens import TokenPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import build_model
from repro.train.ft import FtConfig, run_training, run_with_restarts
from repro.train.loop import make_train_step
from repro.train.optimizer import OptConfig, init_opt_state

__all__ = ["main"]


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--system", "--backend", dest="system", default="bns",
                    choices=("bns", "rns", "sdrns"),
                    help="number system (--backend is a deprecated alias)")
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced config (CPU-scale)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--micro", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--failure-at", type=int, default=None,
                    help="inject a simulated crash (FT demo)")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.family in ("audio",):
        raise SystemExit("use examples/train_lm.py families; whisper trains "
                         "via tests/test_arch_smoke.py paths")

    # rns_impl=None: the repro.numerics backend registry auto-selects the
    # implementation by platform (pallas on TPU, interpret elsewhere)
    model = build_model(cfg, system=args.system)
    opt_cfg = OptConfig(peak_lr=args.lr, warmup_steps=10,
                        total_steps=args.steps,
                        moment_dtype=cfg.opt_state_dtype)
    step_fn = jax.jit(make_train_step(model, opt_cfg, args.micro))

    pipe = TokenPipeline(vocab=cfg.vocab, seq_len=args.seq,
                         global_batch=args.batch, seed=args.seed)

    def init_state():
        params = model.init(jax.random.PRNGKey(args.seed))
        return {"params": params,
                "opt_state": init_opt_state(params, opt_cfg)}

    def batch_at(step):
        b = pipe.batch_at(step)
        if cfg.family == "vlm":
            B = b["tokens"].shape[0]
            n_img = cfg.n_img_tokens
            return {
                "tokens": b["tokens"],
                "patches": np.zeros((B, n_img, cfg.d_model), np.float32),
                "labels": np.concatenate(
                    [np.full((B, n_img), -1, np.int32), b["labels"]], axis=1),
            }
        return b

    ckpt_dir = args.ckpt_dir or f"checkpoints/{cfg.name}"
    ft_cfg = FtConfig(ckpt_dir=ckpt_dir, total_steps=args.steps,
                      ckpt_every=args.ckpt_every,
                      failure_at=args.failure_at)

    def run():
        # after the first failure the injected step has been passed or will
        # be restored past; clear it so the restart proceeds
        res = run_training(init_state=init_state, train_step=step_fn,
                           batch_at=batch_at, cfg=ft_cfg)
        return res

    def run_and_clear():
        try:
            return run()
        finally:
            ft_cfg.failure_at = None

    t0 = time.time()
    result = run_with_restarts(run_and_clear)
    dt = time.time() - t0
    hist = result["history"]
    if not hist:
        from repro.train import checkpoint

        print(f"[done] {args.arch} system={args.system}: nothing to do "
              f"(checkpoint in {ckpt_dir} already at step "
              f"{checkpoint.latest_step(ckpt_dir)} >= --steps {args.steps}; "
              "use a fresh --ckpt-dir)")
        return 0
    print(f"[done] {args.arch} system={args.system} steps={args.steps} "
          f"loss {hist[0]:.3f} -> {hist[-1]:.3f} ({dt:.1f}s)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
