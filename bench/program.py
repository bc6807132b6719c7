"""The system under test, driven the way a user drives it.

Builds the benchmark's data from the seed with the benchmark's own
generator (``cicids.py``), builds ``repro.core.FedS3ATrainer`` from the
configuration file and runs ``run_round`` back to back. Nothing else of the
program is called, apart from reading the trainer's state that the
comparison needs once the window has closed, and, in round 1 of the
set-up only, keeping what the batched client epoch and the upload encode
return on their way through ``run_round`` (``watch_clients``).
"""
from __future__ import annotations

import contextlib
import sys
from pathlib import Path

import numpy as np

import cicids

ROOT = Path(__file__).resolve().parent.parent


def make_data(config, traffic, seed):
    """The fleet's data from the seed: clients, labeled server split, test."""
    fleet = config["fleet"]
    common = dict(server_frac=fleet["server_frac"],
                  test_frac=traffic["test_frac"], seed=seed,
                  separation=traffic["separation"])
    if traffic["generator"] == "table3":
        data = cicids.make_dataset(fleet["scenario"], scale=traffic["scale"],
                                   **common)
    elif traffic["generator"] == "tiled":
        data = cicids.make_fleet_dataset(
            fleet["clients"], scenario=fleet["scenario"],
            scale=traffic["scale"], jitter=traffic["jitter"],
            size_seed=traffic["size_seed"], **common)
    else:
        raise ValueError(f"unknown generator {traffic['generator']!r}")
    if len(data["clients"]) != fleet["clients"]:
        raise ValueError(f"traffic made {len(data['clients'])} clients, the "
                         f"configuration has {fleet['clients']}")
    return data


def ensure_src():
    """Put the program's ``src`` on the import path."""
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))


def make_trainer(config, engine, data, seed):
    """``FedS3ATrainer`` as the configuration states it (its constructor
    initialises the weights from the seed and runs the server warm-up)."""
    ensure_src()
    from repro.configs.feds3a_cnn import CNNConfig
    from repro.core import FedS3AConfig, FedS3ATrainer
    m = config["model"]
    cnn = CNNConfig(num_features=m["num_features"],
                    num_classes=m["num_classes"],
                    conv_filters=tuple(m["conv_filters"]),
                    conv_kernel=m["conv_kernel"], hidden=m["hidden"],
                    dropout=m["dropout"])
    cfg = FedS3AConfig(cnn=cnn, engine=engine, seed=seed,
                       **config["trainer"])
    return FedS3ATrainer(data, cfg)


def snapshot(tr):
    """(global model as {leaf: array}, newest reconstruction as a flat
    vector in sorted-leaf order) after a round. Immutable device buffers
    held by reference: no copy is made."""
    return dict(tr.global_params), tr.store.latest()


@contextlib.contextmanager
def watch_clients(tr):
    """While the context is open, copy to the host the change each
    participant's epoch made (the batched client epoch's trained stack less
    its base stack) and the norm of each upload as the server will decode
    it (``csr``: the values; ``csr_q``: the int8 values times the message's
    scale), as the trainer's own calls return them. The copies are made at
    once, so no device buffer outlives its use in the round; the trainer is
    left as it was on exit."""
    seen = {}
    epoch = tr.batched_epoch

    def batched_epoch(base, *args):
        out = epoch(base, *args)
        seen["delta"] = np.asarray(out[0], np.float32) - \
            np.asarray(base, np.float32)
        return out

    def upload_fn(*args):
        fn = type(tr)._upload_fn(tr, *args)

        def encode(*inputs):
            out = fn(*inputs)
            payload = out[0]
            vals = np.asarray(payload[0], np.float64)
            norms = np.linalg.norm(vals.reshape(vals.shape[0], -1), axis=1)
            if len(payload) == 4:
                norms = norms * np.asarray(payload[3], np.float64)
            seen["upload_norm"] = [float(x) for x in norms]
            return out
        return encode

    tr.batched_epoch, tr._upload_fn = batched_epoch, upload_fn
    try:
        yield seen
    finally:
        tr.batched_epoch = epoch
        del tr._upload_fn
