"""Paper Table XII in miniature: FedS3A vs FedAvg-SSL (partial/all) vs
FedAsync-SSL vs the Local-SSL ceiling, on the non-IID basic scenario.

  PYTHONPATH=src python examples/compare_baselines.py

Environment knobs (used by the CI examples smoke job): ``EXAMPLES_ROUNDS``
overrides the round count, ``EXAMPLES_SCALE`` the dataset scale.
"""
import os

from repro.compile_cache import enable_compile_cache
from repro.core import (FedAsyncSSL, FedAvgSSL, FedS3AConfig, FedS3ATrainer,
                        LocalSSL)
from repro.data import make_dataset

ROUNDS = int(os.environ.get("EXAMPLES_ROUNDS", "8"))
SCALE = float(os.environ.get("EXAMPLES_SCALE", "0.008"))


def main():
    enable_compile_cache()
    data = make_dataset("basic", scale=SCALE, seed=0)
    cfg = FedS3AConfig(rounds=ROUNDS)

    rows = []
    tr = FedS3ATrainer(data, cfg)
    rows.append(("FedS3A", tr.train()))
    rows.append(("FedAvg-SSL-Partial", FedAvgSSL(data, cfg, mode="partial").train()))
    rows.append(("FedAvg-SSL-All", FedAvgSSL(data, cfg, mode="all").train()))
    rows.append(("FedAsync-SSL", FedAsyncSSL(data, cfg).train(cfg.rounds * 4)))
    rows.append(("Local-SSL (ceiling)", LocalSSL(data, cfg).train()))

    print(f"\n{'algorithm':22s} {'acc':>7s} {'f1':>7s} {'fpr':>7s} "
          f"{'ART(s)':>8s} {'ACO':>6s}")
    for name, res in rows:
        m = res["metrics"]
        print(f"{name:22s} {m['accuracy']:7.4f} {m['f1']:7.4f} "
              f"{m['fpr']:7.4f} {res['art']:8.1f} {res['aco']:6.2f}")


if __name__ == "__main__":
    main()
