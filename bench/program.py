"""The system under test, driven the way a user drives it.

Builds the benchmark's data from the seed with the traffic mix's generator
plug-in (``generators/``), builds ``repro.core.FedS3ATrainer`` from the
configuration file, with the model its plug-in (``models/``) names, and
runs ``run_round`` back to back. Nothing else of the program is called,
apart from reading the trainer's state that the comparison needs once the
window has closed, and, in round 1 of the set-up only, keeping what the
client epoch and the upload encode return on their way through
``run_round`` (``watch_clients``).
"""
from __future__ import annotations

import contextlib
import sys
from pathlib import Path

import numpy as np

import plugins

ROOT = Path(__file__).resolve().parent.parent


def make_data(config, traffic, seed):
    """The fleet's data from the seed: clients, labeled server split, test."""
    data = plugins.generator(traffic).make(config, traffic, seed)
    fleet = config["fleet"]
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
    from repro.core import FedS3AConfig, FedS3ATrainer
    model = plugins.model(config["model"]).program_config(config["model"])
    cfg = FedS3AConfig(**model, engine=engine, seed=seed,
                       **config["trainer"])
    return FedS3ATrainer(data, cfg)


WARM_ROUNDS = 400     # rounds of the schedule whose shapes are warmed up


def warm_up(tr):
    """Compile, in set-up, the programs whose shapes a round picks from the
    schedule. Under error feedback the chunked and sharded round bodies
    zero the residual rows of the clients a round retires (forced
    restarts) with a scatter whose shape is the count of those clients, so
    each new count compiles anew and the window must compile nothing. The
    counts are those a copy of the trainer's own scheduler retires over the
    next ``WARM_ROUNDS`` rounds (the schedule does not depend on what the
    rounds compute), each compiled once. Call it after a round, so that the
    residual store has the placement the window's rounds hand the scatter.
    The scatters run on copies of the store (they donate their input), and
    the trainer gets its own arrays back. Returns the counts compiled."""
    reset = getattr(tr, "_reset_forced_residuals", None)
    if reset is None or not tr.cfg.error_feedback or tr.paged or not (
            tr.chunked or tr.engine == "sharded"):
        return []
    import copy

    import jax.numpy as jnp
    schedule = copy.deepcopy(tr.scheduler)
    counts = {len(tr._retired_ids(schedule.next_round()))
              for _ in range(WARM_ROUNDS)} - {0}
    store = {k: getattr(tr, k) for k in ("_res_vals", "_res_idx",
                                         "_residual_mat")
             if getattr(tr, k, None) is not None}
    for key, value in store.items():
        setattr(tr, key, jnp.copy(value))
    for n in sorted(counts):
        reset(list(range(n)))
    for key, value in store.items():
        setattr(tr, key, value)
    return sorted(counts)


def snapshot(tr):
    """(global model as {leaf: array}, newest reconstruction as a flat
    vector in sorted-leaf order) after a round. Immutable device buffers
    held by reference: no copy is made."""
    return dict(tr.global_params), tr.store.latest()


def _upload_norms(payload, arity):
    """Norm of each row's upload as the server decodes it, from a wire
    payload tuple of ``arity`` components per chunk (one chunk on the flat
    paths): ``csr`` carries the values, ``csr_q`` the int8 values and the
    message's scale last."""
    norms = []
    for c in range(0, len(payload), arity):
        vals = np.asarray(payload[c], np.float64)
        n = np.linalg.norm(vals.reshape(vals.shape[0], -1), axis=1)
        if arity == 4:
            n = n * np.asarray(payload[c + 3], np.float64).reshape(-1)
        norms.append(n)
    total = norms[0] if len(norms) == 1 else \
        np.sqrt(np.sum(np.square(norms), axis=0))
    return [float(x) for x in total]


@contextlib.contextmanager
def watch_clients(tr):
    """While the context is open, copy to the host what the round's own
    calls return: the norm of each upload as the server will decode it and,
    where the client epoch returns its trained stack on its own, the change
    each participant's epoch made (trained stack less base stack). One
    watch per round body the trainer has:

    * batched: ``batched_epoch`` and ``_upload_fn``;
    * chunked: ``batched_epoch`` and ``_chunk_upload_fn``;
    * sharded: ``_stage1_sharded``, which trains and encodes in one
      program and returns no trained stack, so no epoch change is taken
      (nor under a sharded chunked round, whose epoch gathers its base
      inside the program).

    Rows past the round's participants (the sharded body's padding) are
    copied too; the caller keeps the first K. The copies are made at once,
    so no device buffer outlives its use in the round; the trainer is left
    as it was on exit."""
    seen = {}
    arity = tr._payload_arity
    cls = type(tr)
    sharded = tr.engine == "sharded"
    name = "_chunk_upload_fn" if tr.chunked else \
        "_stage1_sharded" if sharded else "_upload_fn"

    def stage(*args):
        fn = getattr(cls, name)(tr, *args)

        def watched(*inputs):
            out = fn(*inputs)
            # the sharded stage returns the payload's components first;
            # the upload programs return the payload tuple first
            payload = out[:arity] if name == "_stage1_sharded" else out[0]
            seen["upload_norm"] = _upload_norms(payload, arity)
            return out
        return watched

    epoch = tr.batched_epoch

    def batched_epoch(base, *args):
        out = epoch(base, *args)
        seen["delta"] = np.asarray(out[0], np.float32) - \
            np.asarray(base, np.float32)
        return out

    setattr(tr, name, stage)
    if not sharded:
        tr.batched_epoch = batched_epoch
    try:
        yield seen
    finally:
        tr.batched_epoch = epoch
        delattr(tr, name)
