"""Faults planted under the timed path, to show that ``correct`` catches them.

Each is a context manager that patches ``FedS3ATrainer`` (or, for the
exchange, JAX's ``psum``) for its duration:

* ``unchanged``: every round returns the model state it was given (global
  model and reconstruction ring restored after the round);
* ``half_batch``: each client batch keeps only its first half of rows, and
  the epoch's mean is taken over the rest;
* ``altered``: the upload payload values are halved where the upload stage
  produces them (the int8 wire's per-message scales under ``csr_q``): the
  batched body's upload program, the sharded body's train-and-encode
  stage;
* ``no_exchange``: the exchange between chips is left out: the sharded
  aggregation's ``psum`` over the client axis returns each device's own
  partial sum, so the new model holds one device's share of the uploads.

Single-chip cells have no exchange between chips to leave out. Used by
``bench/tests`` at a small size and by ``calibrate.py`` at the cells' own
sizes; the benchmark's runs never plant them.
"""
from __future__ import annotations

import contextlib

import program

FAULTS = ("unchanged", "half_batch", "altered", "no_exchange")


@contextlib.contextmanager
def planted(kind):
    program.ensure_src()
    if kind == "no_exchange":
        import jax
        target, patches = jax.lax, {"psum": _no_exchange}
    else:
        from repro.core.feds3a import FedS3ATrainer
        target, patches = FedS3ATrainer, {
            "unchanged": {"run_round": _unchanged},
            "half_batch": {"_gather_data": _half_batch},
            "altered": {"_upload_fn": _altered,
                        "_stage1_sharded": _altered_stage1}}[kind]
    origs = {name: getattr(target, name) for name in patches}
    for name, patch in patches.items():
        setattr(target, name, patch(origs[name]))
    try:
        yield
    finally:
        for name, orig in origs.items():
            setattr(target, name, orig)


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


def _halved(payload):
    """The payload with its values halved: the int8 wire's per-message
    scales under ``csr_q`` (four components), the values under ``csr``."""
    if len(payload) == 4:
        return tuple(payload[:3]) + (payload[3] * 0.5,)
    return (payload[0] * 0.5,) + tuple(payload[1:])


def _altered(orig):
    def upload_fn(self, with_residual, with_hist):
        fn = orig(self, with_residual, with_hist)

        def altered(*args):
            payload, *rest = fn(*args)
            return (_halved(payload), *rest)
        return altered
    return upload_fn


def _altered_stage1(orig):
    def stage1(self, with_residual, with_hist):
        fn = orig(self, with_residual, with_hist)
        arity = self._payload_arity

        def altered(*args):
            out = fn(*args)
            return _halved(out[:arity]) + tuple(out[arity:])
        return altered
    return stage1


def _no_exchange(_orig):
    def psum(x, axis_name, **kwargs):
        return x
    return psum
