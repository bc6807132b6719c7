"""FedS3A over a REAL model-zoo architecture: the reduced qwen2-1.5b
transformer (~1.3M parameters) runs the paper's full faulted federated
round — semi-async scheduling, pseudo-labeling, group k-means aggregation,
sparse-diff comm, churn/crash/deadline faults — through the SAME
``FedS3ATrainer`` the CNN path uses, via the chunked parameter axis.

``FedS3AConfig(model=<ModelConfig>, chunk_size=...)`` partitions the flat
parameter vector into leaf-aligned chunks and streams every
(K, N)-materializing round stage chunk by chunk, so peak device delta
memory is O(K * chunk_size), not O(K * N) — the regression gate
(benchmarks/check_regression.py) pins it flat in N across model sizes.
Keep the chunk count modest (a handful to a few tens): the per-chunk loop
is unrolled inside the jitted round bodies, so compile time scales with
the number of chunks, not with N.

  PYTHONPATH=src python examples/fl_large_model.py [--arch qwen2-1.5b]

Environment knobs (used by the CI examples smoke job): ``EXAMPLES_ROUNDS``
overrides the round count, ``EXAMPLES_LM_CLIENTS`` the fleet width,
``EXAMPLES_LM_CHUNKS`` the target chunk count.
"""
import argparse
import os

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, load_all
from repro.core import FedS3AConfig, FedS3ATrainer, TrafficModel
from repro.data import make_lm_dataset

ROUNDS = int(os.environ.get("EXAMPLES_ROUNDS", "6"))
CLIENTS = int(os.environ.get("EXAMPLES_LM_CLIENTS", "8"))
CHUNKS = int(os.environ.get("EXAMPLES_LM_CHUNKS", "6"))


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--rounds", type=int, default=ROUNDS)
    ap.add_argument("--clients", type=int, default=CLIENTS)
    args = ap.parse_args()

    load_all()
    cfg_model = get_config(args.arch).reduced()
    n = cfg_model.param_count()
    print(f"arch={args.arch} reduced: {cfg_model.num_layers}L "
          f"d={cfg_model.d_model} vocab={cfg_model.vocab_size} "
          f"-> {n:,} params, M={args.clients} clients")

    data = make_lm_dataset(args.clients, vocab_size=cfg_model.vocab_size,
                           seq_len=16, num_classes=8,
                           samples_per_client=48, seed=0)
    print(f"  server: {len(data['server']['x'])} labeled, "
          f"test: {len(data['test']['x'])}")

    chunk_size = -(-n // CHUNKS)
    cfg = FedS3AConfig(
        model=cfg_model, chunk_size=chunk_size,
        rounds=args.rounds, C=0.5, tau=2, batch_size=16, lr=5e-4,
        error_feedback=True,
        traffic=TrafficModel(crash_rate=0.05, upload_loss=0.05),
        round_deadline=2000.0, quorum_floor=1,
        seed=0,
    )
    trainer = FedS3ATrainer(data, cfg)
    lay = trainer.layout
    print(f"\nlayout: {lay.num_chunks} chunks "
          f"(max {lay.max_chunk:,}, min {min(lay.sizes):,}) over "
          f"n={lay.n:,}; engine={trainer.engine}")
    print(f"peak device delta bytes: "
          f"{trainer.peak_delta_device_bytes():,} "
          f"(dense K*N would be "
          f"{4 * trainer.store.ring.shape[1] * max(int(cfg.C * args.clients), 1):,})")

    for _ in range(cfg.rounds):
        log = trainer.run_round()
        m = trainer.evaluate()
        flags = "degraded " if log.degraded else ""
        print(f"  round {log.round:2d}  quorum={log.quorum}/{log.target_k}"
              f"  crashes={log.crashes}  lost={len(log.lost)}  {flags}"
              f"acc={m['accuracy']:.4f}")
    final = trainer.evaluate()
    wb = trainer.comm.wire_breakdown()
    print(f"\nfinal: acc={final['accuracy']:.4f}  ACO={trainer.comm.aco:.3f}")
    print(f"wire layout: {wb['layout']}")


if __name__ == "__main__":
    main()
