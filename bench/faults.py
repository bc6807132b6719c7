"""Faults planted under the timed path, to show that ``correct`` catches them.

Each is a context manager that patches ``FedS3ATrainer`` for its duration:

* ``unchanged``: every round returns the model state it was given (global
  model and reconstruction ring restored after the round);
* ``half_batch``: each client batch keeps only its first half of rows, and
  the epoch's mean is taken over the rest;
* ``altered``: the upload payload values are halved where the upload stage
  produces them (the int8 wire's per-message scales under ``csr_q``).

The single-chip cells have no exchange between chips to leave out.
Used by ``bench/tests`` at a small size and by ``calibrate.py`` at the
cells' own sizes; the benchmark's runs never plant them.
"""
from __future__ import annotations

import contextlib

import program

FAULTS = ("unchanged", "half_batch", "altered")


@contextlib.contextmanager
def planted(kind):
    program.ensure_src()
    from repro.core.feds3a import FedS3ATrainer
    name, patch = {"unchanged": ("run_round", _unchanged),
                   "half_batch": ("_gather_data", _half_batch),
                   "altered": ("_upload_fn", _altered)}[kind]
    orig = getattr(FedS3ATrainer, name)
    setattr(FedS3ATrainer, name, patch(orig))
    try:
        yield
    finally:
        setattr(FedS3ATrainer, name, orig)


def _unchanged(orig):
    def run_round(self):
        g, ring, latest = self._global_flat, self.store.ring, \
            self.store.latest()
        log = orig(self)
        self._global_flat, self._gp_tree = g, None
        self.store.ring, self.store._latest = ring, latest
        return log
    return run_round


def _half_batch(orig):
    def gather(self, ids):
        xs, vs = orig(self, ids)
        B = self.cfg.batch_size
        k, rows = vs.shape
        vs = vs.reshape(k, rows // B, B).at[:, :, B // 2:].set(0.0)
        return xs, vs.reshape(k, rows)
    return gather


def _altered(orig):
    def upload_fn(self, with_residual, with_hist):
        fn = orig(self, with_residual, with_hist)

        def altered(*args):
            payload, *rest = fn(*args)
            if len(payload) == 4:              # csr_q: halve the scales
                payload = payload[:3] + (payload[3] * 0.5,)
            else:                              # csr: halve the values
                payload = (payload[0] * 0.5,) + tuple(payload[1:])
            return (payload, *rest)
        return altered
    return upload_fn
