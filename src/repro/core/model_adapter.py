"""Model adapters: one contract between the federated engines and a model.

The trainer's engines only ever touch a model through a small closure set —
init / tree epochs (sequential reference), flat stacked epochs (batched,
sharded and chunked engines), prediction and pseudo-label histograms. This
module packages that set:

* :class:`CNNAdapter` — the paper's CNN, delegating to the SAME lru-cached
  factories in ``core.pseudo_label`` the trainer used to call directly, so
  every flat-path behaviour is bit-identical to the pre-adapter wiring.
* :class:`LMAdapter` — a real language model from the config zoo
  (``configs/base.ModelConfig`` / ``models/lm.py``) federated as a
  final-token classifier over its vocabulary: clients run pseudo-label
  epochs on the last-position logits (Eq. 5 with ``num_classes =
  vocab_size``), the server trains supervised on labeled final tokens
  (Eq. 6). Token sequences ride the engines' existing float32 data plumbing
  as (B, S) rows (exact for any vocab < 2**24) and cast to int32 at the
  loss. The LM forward has no dropout, but the per-batch RNG split is kept
  so the optimizer-step and key-stream structure mirrors the CNN epochs.

Both adapters expose: ``num_classes``, ``param_count()``, ``init(rng)``,
``template``, ``client_epoch``, ``server_epoch``, ``server_epoch_flat``,
``batched_epoch``, ``histogram``, ``histogram_batch``, ``predict``.
"""
from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.pseudo_label import (_cnn_template, class_histogram,
                                     class_histogram_batch,
                                     make_batched_client_epoch,
                                     make_client_epoch, make_server_epoch,
                                     make_server_epoch_flat, predict_fn)
from repro.core.telemetry import CLIENT_EPOCH, SERVER_EPOCH, scope
from repro.kernels import ops as kops
from repro.kernels.ref import masked_pseudo_ce_ref
from repro.models.cnn import cnn_param_count, init_cnn
from repro.optimizer import adam_update

__all__ = ["CNNAdapter", "LMAdapter", "make_adapter"]


def make_adapter(cfg, *, batch_size, threshold, l1, use_kernel, epochs):
    """CNNConfig -> CNNAdapter, ModelConfig (LM zoo) -> LMAdapter."""
    if isinstance(cfg, ModelConfig):
        return LMAdapter(cfg, batch_size=batch_size, threshold=threshold,
                         l1=l1, use_kernel=use_kernel, epochs=epochs)
    return CNNAdapter(cfg, batch_size=batch_size, threshold=threshold,
                      l1=l1, use_kernel=use_kernel, epochs=epochs)


class CNNAdapter:
    """The paper's CNN behind the adapter contract. Pure delegation to the
    lru-cached ``core.pseudo_label`` factories with identical arguments, so
    trainers sharing a config share compiled steps exactly as before."""

    kind = "cnn"

    def __init__(self, cfg, *, batch_size, threshold, l1, use_kernel,
                 epochs):
        self.cfg = cfg
        self.num_classes = cfg.num_classes
        self.client_epoch = make_client_epoch(
            cfg, batch_size=batch_size, threshold=threshold, l1=l1,
            use_kernel=use_kernel)
        self.server_epoch = make_server_epoch(cfg, batch_size=batch_size,
                                              l1=l1)
        self.server_epoch_flat = make_server_epoch_flat(
            cfg, batch_size=batch_size, l1=l1)
        self.batched_epoch = make_batched_client_epoch(
            cfg, batch_size=batch_size, threshold=threshold, l1=l1,
            use_kernel=use_kernel, epochs=epochs)
        self.predict = predict_fn(cfg)
        self.histogram = class_histogram(cfg)
        self.histogram_batch = class_histogram_batch(cfg,
                                                     batch_size=batch_size)

    def param_count(self):
        return cnn_param_count(self.cfg)

    def init(self, rng):
        return init_cnn(self.cfg, rng)

    @property
    def template(self):
        return _cnn_template(self.cfg)


# ---------------------------------------------------------------------------
# LM-as-classifier closures (structure mirrors core.pseudo_label factories)

@functools.lru_cache(maxsize=None)
def _lm_template(cfg):
    from repro.models.lm import init_params
    return jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))


def _lm_logits(cfg, params, x):
    """Last-position logits (B, V) of float-carried token rows (B, S)."""
    from repro.models.lm import forward
    tokens = x.astype(jnp.int32)
    logits, _, _ = forward(cfg, params, {"tokens": tokens},
                           head_mode="last")
    return logits


def _lm_pseudo_loss(cfg, params, xi, vi, *, threshold, use_kernel):
    """Eq. 5 on the final-token logits, masked over padded samples."""
    logits = _lm_logits(cfg, params, xi)
    if use_kernel:
        loss, _ = kops.masked_pseudo_ce(logits, threshold)
    else:
        loss, _ = masked_pseudo_ce_ref(logits, threshold)
    return jnp.sum(loss * vi) / jnp.maximum(jnp.sum(vi), 1.0)


def _lm_sup_loss(cfg, params, xi, yi, vi):
    """Eq. 6: supervised CE of the final-token logits vs the label."""
    logits = _lm_logits(cfg, params, xi)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, yi[:, None], axis=-1)[:, 0]
    return jnp.sum(ce * vi) / jnp.maximum(jnp.sum(vi), 1.0)


def _pad_batches(x_np, batch_size, y_np=None):
    n = len(x_np)
    nb = max((n + batch_size - 1) // batch_size, 1)
    pad = nb * batch_size - n
    x = np.concatenate([x_np, np.zeros((pad,) + x_np.shape[1:],
                                       x_np.dtype)]) if pad else x_np
    valid = np.concatenate([np.ones(n, np.float32),
                            np.zeros(pad, np.float32)])
    if y_np is None:
        return x, valid, nb
    y = np.concatenate([y_np, np.zeros(pad, y_np.dtype)]) if pad else y_np
    return x, y, valid, nb


@functools.lru_cache(maxsize=None)
def _lm_suite(cfg, batch_size, threshold, l1, use_kernel, epochs):
    """All LM closures for one (config, hyperparams) point, built once.
    Each mirrors its ``core.pseudo_label`` namesake: padded batches with a
    validity mask, scan over batches, cond-skipped all-padding batches in
    the stacked epochs, flat Adam state, and the epoch-index key fold for
    epochs > 0."""
    from repro.core.sparse_comm import unflatten_like, unflatten_stacked

    template = _lm_template(cfg)

    @partial(jax.jit, static_argnames=("nb",))
    def tree_client_epoch(params, opt, x, valid, lr, rng, nb):
        xb = x.reshape(nb, batch_size, -1)
        vb = valid.reshape(nb, batch_size)

        def step(carry, inp):
            params, opt, rng = carry
            xi, vi = inp
            rng, _ = jax.random.split(rng)
            l, g = jax.value_and_grad(
                lambda p: _lm_pseudo_loss(cfg, p, xi, vi,
                                          threshold=threshold,
                                          use_kernel=use_kernel))(params)
            params, opt = adam_update(g, opt, params, lr=lr, l1=l1)
            return (params, opt, rng), l

        (params, opt, _), losses = jax.lax.scan(step, (params, opt, rng),
                                                (xb, vb))
        return params, opt, jnp.mean(losses)

    def client_epoch(params, opt, x_np, lr, rng):
        x, valid, nb = _pad_batches(np.asarray(x_np, np.float32), batch_size)
        return tree_client_epoch(params, opt, jnp.asarray(x),
                                 jnp.asarray(valid), jnp.float32(lr), rng, nb)

    @partial(jax.jit, static_argnames=("nb", "flat_state"))
    def server_step(state, opt, x, y, valid, lr, rng, nb, flat_state):
        xb = x.reshape(nb, batch_size, -1)
        yb = y.reshape(nb, batch_size)
        vb = valid.reshape(nb, batch_size)

        def step(carry, inp):
            state, opt, rng = carry
            xi, yi, vi = inp
            rng, _ = jax.random.split(rng)

            def loss_fn(s):
                p = unflatten_like(s, template) if flat_state else s
                return _lm_sup_loss(cfg, p, xi, yi, vi)

            l, g = jax.value_and_grad(loss_fn)(state)
            state, opt = adam_update(g, opt, state, lr=lr, l1=l1)
            return (state, opt, rng), l

        with scope(SERVER_EPOCH):
            (state, opt, _), losses = jax.lax.scan(step, (state, opt, rng),
                                                   (xb, yb, vb))
        return state, opt, jnp.mean(losses)

    def _server_run(state, opt, x_np, y_np, lr, rng, flat_state):
        x, y, valid, nb = _pad_batches(np.asarray(x_np, np.float32),
                                       batch_size,
                                       np.asarray(y_np, np.int32))
        return server_step(state, opt, jnp.asarray(x), jnp.asarray(y),
                           jnp.asarray(valid), jnp.float32(lr), rng, nb,
                           flat_state)

    def server_epoch(params, opt, x_np, y_np, lr, rng):
        return _server_run(params, opt, x_np, y_np, lr, rng, False)

    def server_epoch_flat(flat, opt, x_np, y_np, lr, rng):
        return _server_run(flat, opt, x_np, y_np, lr, rng, True)

    @partial(jax.jit, static_argnames=("nb",))
    def stacked_epoch(base_flat, x, valid, lrs, rngs, nb):
        def one_client(flat, xc, vc, lr, rng):
            xb = xc.reshape(nb, batch_size, -1)
            vb = vc.reshape(nb, batch_size)
            opt = {"m": jnp.zeros_like(flat), "v": jnp.zeros_like(flat),
                   "t": jnp.zeros((), jnp.int32)}

            def step(carry, inp):
                flat, o, rng = carry
                xi, vi = inp
                rng, _ = jax.random.split(rng)

                def live_step(_):
                    def loss_fn(fp):
                        pp = unflatten_like(fp, template)
                        return _lm_pseudo_loss(cfg, pp, xi, vi,
                                               threshold=threshold,
                                               use_kernel=use_kernel)
                    l, g = jax.value_and_grad(loss_fn)(flat)
                    f2, o2 = adam_update(g, o, flat, lr=lr, l1=l1)
                    return f2, o2, l

                def dead_step(_):
                    return flat, o, jnp.float32(0.0)

                live = jnp.sum(vi) > 0
                flat, o, l = jax.lax.cond(live, live_step, dead_step, None)
                return (flat, o, rng), (l, live)

            live_steps = jnp.int32(0)
            for e in range(epochs):
                ek = rng if e == 0 else jax.random.fold_in(rng, e)
                (flat, opt, _), (losses, lives) = jax.lax.scan(
                    step, (flat, opt, ek), (xb, vb))
                live_steps = live_steps + jnp.sum(lives, dtype=jnp.int32)
            return flat, jnp.sum(losses) / jnp.maximum(jnp.sum(lives), 1.0), \
                live_steps

        if jax.default_backend() == "cpu":
            def all_clients(*args):
                return jax.lax.map(lambda t: one_client(*t), args)
        else:
            def all_clients(*args):
                return jax.vmap(one_client)(*args)

        with scope(CLIENT_EPOCH):
            return all_clients(base_flat, x, valid, lrs, rngs)

    def batched_epoch(base_flat, x, valid, lrs, rngs):
        nb = x.shape[1] // batch_size
        return stacked_epoch(base_flat, x, valid,
                             jnp.asarray(lrs, jnp.float32), rngs, nb)

    @jax.jit
    def predict(params, x):
        return jnp.argmax(_lm_logits(cfg, params, x), axis=-1)

    @jax.jit
    def histogram(params, x):
        pred = jnp.argmax(_lm_logits(cfg, params, x), axis=-1)
        return jnp.bincount(pred, length=cfg.vocab_size) / x.shape[0]

    def hist_one(p, x, valid):
        xb = x.reshape(-1, batch_size, x.shape[-1])
        vb = valid.reshape(-1, batch_size)

        def step(acc, inp):
            xi, vi = inp
            counts = jax.lax.cond(
                jnp.sum(vi) > 0,
                lambda _: jnp.zeros(cfg.vocab_size, jnp.float32)
                .at[jnp.argmax(_lm_logits(cfg, p, xi), axis=-1)].add(vi),
                lambda _: jnp.zeros(cfg.vocab_size, jnp.float32), None)
            return acc + counts, None

        acc, _ = jax.lax.scan(step, jnp.zeros(cfg.vocab_size, jnp.float32),
                              (xb, vb))
        return acc / jnp.maximum(jnp.sum(valid), 1.0)

    if jax.default_backend() == "cpu":
        def hist_mapped(params, x, valid):
            return jax.lax.map(lambda t: hist_one(*t), (params, x, valid))
    else:
        def hist_mapped(params, x, valid):
            return jax.vmap(hist_one)(params, x, valid)

    @jax.jit
    def histogram_batch(flat, x, valid):
        params = unflatten_stacked(flat, template)
        return hist_mapped(params, x, valid)

    return {"client_epoch": client_epoch, "server_epoch": server_epoch,
            "server_epoch_flat": server_epoch_flat,
            "batched_epoch": batched_epoch, "predict": predict,
            "histogram": histogram, "histogram_batch": histogram_batch}


class LMAdapter:
    """A config-zoo LM federated as a final-token classifier (see module
    docstring). ``num_classes`` is the vocabulary size; data rows are
    float-carried token sequences."""

    kind = "lm"

    def __init__(self, cfg, *, batch_size, threshold, l1, use_kernel,
                 epochs):
        self.cfg = cfg
        self.num_classes = cfg.vocab_size
        suite = _lm_suite(cfg, batch_size, threshold, l1, use_kernel,
                          epochs)
        self.client_epoch = suite["client_epoch"]
        self.server_epoch = suite["server_epoch"]
        self.server_epoch_flat = suite["server_epoch_flat"]
        self.batched_epoch = suite["batched_epoch"]
        self.predict = suite["predict"]
        self.histogram = suite["histogram"]
        self.histogram_batch = suite["histogram_batch"]

    def param_count(self):
        return int(self.cfg.param_count())

    def init(self, rng):
        from repro.models.lm import init_params
        return init_params(self.cfg, rng)

    @property
    def template(self):
        return _lm_template(self.cfg)
