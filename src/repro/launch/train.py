"""Training launcher.

Two modes:
  fl   — the paper: FedS3A over the synthetic CIC-IDS-2017 scenarios, with
         periodic checkpointing of the full server state.
  lm   — single-host LM pretraining driver for any assigned architecture
         (reduced configs run on CPU; full configs need the TPU mesh).

  PYTHONPATH=src python -m repro.launch.train fl --scenario basic --rounds 10
  PYTHONPATH=src python -m repro.launch.train lm --arch qwen2-1.5b --steps 5
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.compile_cache import enable_compile_cache


def run_fl(args):
    from repro.checkpoint import save_checkpoint
    from repro.core import FedS3AConfig, FedS3ATrainer
    from repro.data import make_dataset

    data = make_dataset(args.scenario, scale=args.scale, seed=args.seed)
    cfg = FedS3AConfig(rounds=args.rounds, C=args.C, tau=args.tau,
                       seed=args.seed)
    tr = FedS3ATrainer(data, cfg)
    for r in range(args.rounds):
        log = tr.run_round()
        m = tr.evaluate()
        print(f"round {log.round:3d} art={log.art:6.1f}s acc={m['accuracy']:.4f} "
              f"f1={m['f1']:.4f} participants={log.participants}")
        if args.ckpt and (r + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt, {
                "global_params": tr.global_params,
                "server_opt": tr.server_opt,
                "participation": tr.participation,
                "round": tr.global_version,
            })
            print(f"  checkpoint -> {args.ckpt}")
    final = tr.evaluate()
    print(f"final acc={final['accuracy']:.4f} aco={tr.comm.aco:.2f}")


def run_lm(args):
    from repro.configs import get_config
    from repro.models import lm
    from repro.optimizer import adam_init
    from repro.training.steps import make_train_step

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    rng = jax.random.PRNGKey(args.seed)
    params = lm.init_params(cfg, rng)
    opt = adam_init(params)
    step = jax.jit(make_train_step(cfg, lr=args.lr,
                                   num_microbatches=args.microbatches,
                                   impl="ref" if args.reduced else "flash"))
    B, S = args.batch, args.seq
    for i in range(args.steps):
        rng, k = jax.random.split(rng)
        batch = {"tokens": jax.random.randint(k, (B, S), 0, cfg.vocab_size)}
        if cfg.is_encoder_decoder:
            batch["frames"] = jax.random.normal(
                k, (B, cfg.num_encoder_positions, cfg.d_model))
        if cfg.num_vision_patches:
            batch["patches"] = jax.random.normal(
                k, (B, cfg.num_vision_patches, cfg.d_model))
        t0 = time.time()
        params, opt, loss = step(params, opt, batch)
        print(f"step {i}: loss={float(loss):.4f} ({time.time()-t0:.2f}s)")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="mode", required=True)

    fl = sub.add_parser("fl")
    fl.add_argument("--scenario", default="basic",
                    choices=["basic", "balanced"])
    fl.add_argument("--rounds", type=int, default=10)
    fl.add_argument("--scale", type=float, default=0.01)
    fl.add_argument("--C", type=float, default=0.6)
    fl.add_argument("--tau", type=int, default=2)
    fl.add_argument("--seed", type=int, default=0)
    fl.add_argument("--ckpt", default=None)
    fl.add_argument("--ckpt-every", type=int, default=5)

    lm_ = sub.add_parser("lm")
    lm_.add_argument("--arch", default="qwen2-1.5b")
    lm_.add_argument("--steps", type=int, default=5)
    lm_.add_argument("--batch", type=int, default=2)
    lm_.add_argument("--seq", type=int, default=128)
    lm_.add_argument("--lr", type=float, default=3e-4)
    lm_.add_argument("--microbatches", type=int, default=1)
    lm_.add_argument("--reduced", action="store_true", default=True)
    lm_.add_argument("--seed", type=int, default=0)

    args = ap.parse_args()
    if args.mode == "fl":
        run_fl(args)
    else:
        run_lm(args)


if __name__ == "__main__":
    main()
