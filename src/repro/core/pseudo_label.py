"""Client-side unsupervised training via pseudo-labeling (Eq. 5) and the
server-side supervised step (Eq. 6), for the paper's CNN.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.telemetry import CLIENT_EPOCH, SERVER_EPOCH, scope
from repro.kernels import ops as kops
from repro.models.cnn import cnn_forward
from repro.optimizer import adam_update


def pseudo_label_loss(cfg, params, x, *, threshold=0.95, rng=None,
                      use_kernel=True):
    """Eq. 5: mean over samples of 1[max p >= theta] * CE(argmax, p)."""
    logits = cnn_forward(cfg, params, x, train=rng is not None, rng=rng)
    if use_kernel:
        loss, mask = kops.masked_pseudo_ce(logits, threshold)
    else:
        from repro.kernels.ref import masked_pseudo_ce_ref
        loss, mask = masked_pseudo_ce_ref(logits, threshold)
    return jnp.sum(loss) / x.shape[0], jnp.sum(mask)


def supervised_loss(cfg, params, x, y, *, rng=None):
    """Eq. 6: plain cross entropy on the server's labeled data."""
    logits = cnn_forward(cfg, params, x, train=rng is not None, rng=rng)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


import functools


@functools.lru_cache(maxsize=None)
def make_client_epoch(cfg, *, batch_size=100, threshold=0.95, l1=0.0,
                      use_kernel=False):
    """One unsupervised epoch (E=1 per paper default) over a client's data.

    Data is padded to a multiple of batch_size with a validity mask so one
    jitted function serves every client size. lru_cache'd so every trainer
    (each benchmark config) shares the compiled step.
    """

    @partial(jax.jit, static_argnames=("nb",))
    def epoch(params, opt, x, valid, lr, rng, nb):
        xb = x.reshape(nb, batch_size, -1)
        vb = valid.reshape(nb, batch_size)

        def step(carry, inp):
            params, opt, rng = carry
            xi, vi = inp
            rng, dr = jax.random.split(rng)

            def loss_fn(p):
                logits = cnn_forward(cfg, p, xi, train=True, rng=dr)
                if use_kernel:
                    loss, _ = kops.masked_pseudo_ce(logits, threshold)
                else:
                    from repro.kernels.ref import masked_pseudo_ce_ref
                    loss, _ = masked_pseudo_ce_ref(logits, threshold)
                return jnp.sum(loss * vi) / jnp.maximum(jnp.sum(vi), 1.0)

            l, g = jax.value_and_grad(loss_fn)(params)
            params, opt = adam_update(g, opt, params, lr=lr, l1=l1)
            return (params, opt, rng), l

        (params, opt, _), losses = jax.lax.scan(step, (params, opt, rng), (xb, vb))
        return params, opt, jnp.mean(losses)

    def run(params, opt, x_np, lr, rng):
        import numpy as np
        n = len(x_np)
        nb = max((n + batch_size - 1) // batch_size, 1)
        pad = nb * batch_size - n
        x = np.concatenate([x_np, np.zeros((pad, x_np.shape[1]), x_np.dtype)]) \
            if pad else x_np
        valid = np.concatenate([np.ones(n, np.float32), np.zeros(pad, np.float32)])
        return epoch(params, opt, jnp.asarray(x), jnp.asarray(valid),
                     jnp.float32(lr), rng, nb)

    return run


def _cnn_template(cfg):
    """Leaf shapes/dtypes of one client's parameter tree (no allocation)."""
    from repro.models.cnn import init_cnn
    return jax.eval_shape(lambda k: init_cnn(cfg, k), jax.random.PRNGKey(0))


@functools.lru_cache(maxsize=None)
def make_batched_client_epoch(cfg, *, batch_size=100, threshold=0.95, l1=0.0,
                              use_kernel=False, epochs=1):
    """All participants' pseudo-label epochs in ONE jitted vmap-over-scan.

    Client state arrives as a (K, N) flat stack (FedJAX ``for_each_client``
    style, row i = client i's base params); unflatten to the stacked pytree,
    the fresh zeroed Adam state, the vmapped per-client scan over batches,
    and the final re-flatten all live inside the same jit, so one dispatch
    trains every participant. Per-client learning rates (K,) and RNG keys
    (K, 2) ride along as batched arrays.

    Every client's data is padded to the same ``nb`` batches; a batch with
    no valid sample is a true no-op (params, opt state and Adam ``t`` are
    carried through unchanged), so a client padded from nb_i to nb batches
    takes exactly the nb_i optimizer steps the sequential reference path
    takes — bit-for-bit comparable modulo batched matmul reduction order.
    """
    from repro.core.sparse_comm import unflatten_like

    template = _cnn_template(cfg)

    @partial(jax.jit, static_argnames=("nb",))
    def epoch(base_flat, x, valid, lrs, rngs, nb):
        def one_client(flat, xc, vc, lr, rng):
            xb = xc.reshape(nb, batch_size, -1)
            vb = vc.reshape(nb, batch_size)
            # Adam state stays flat too: elementwise updates are identical
            # math leaf-by-leaf or on the concatenated vector, and the flat
            # form is ~10 XLA ops per step instead of ~10 per leaf.
            opt = {"m": jnp.zeros_like(flat), "v": jnp.zeros_like(flat),
                   "t": jnp.zeros((), jnp.int32)}

            def step(carry, inp):
                flat, o, rng = carry
                xi, vi = inp
                rng, dr = jax.random.split(rng)

                def live_step(_):
                    def loss_fn(fp):
                        pp = unflatten_like(fp, template)
                        logits = cnn_forward(cfg, pp, xi, train=True, rng=dr)
                        if use_kernel:
                            loss, _ = kops.masked_pseudo_ce(logits, threshold)
                        else:
                            from repro.kernels.ref import masked_pseudo_ce_ref
                            loss, _ = masked_pseudo_ce_ref(logits, threshold)
                        return jnp.sum(loss * vi) / \
                            jnp.maximum(jnp.sum(vi), 1.0)

                    l, g = jax.value_and_grad(loss_fn)(flat)
                    f2, o2 = adam_update(g, o, flat, lr=lr, l1=l1)
                    return f2, o2, l

                def dead_step(_):
                    return flat, o, jnp.float32(0.0)

                # all-padding batch -> true no-op. Under lax.map (CPU) the
                # cond branches for real, so a client padded from nb_i to nb
                # batches pays for exactly nb_i steps; under vmap it lowers
                # to a select, which is still a correct no-op.
                live = jnp.sum(vi) > 0
                flat, o, l = jax.lax.cond(live, live_step, dead_step, None)
                return (flat, o, rng), (l, live)

            # Adam state persists across the client's E epochs; epoch e > 0
            # folds its index into the client key so every epoch draws fresh
            # dropout masks (epoch 0 keeps the raw key, so E=1 runs are
            # bit-identical to the pre-fold behaviour). _train_client uses
            # the same fold, keeping the engines pinned at epochs > 1 — the
            # old restart-from-the-same-key form replayed identical masks
            # every epoch in BOTH paths.
            live_steps = jnp.int32(0)
            for e in range(epochs):
                ek = rng if e == 0 else jax.random.fold_in(rng, e)
                (flat, opt, _), (losses, lives) = jax.lax.scan(
                    step, (flat, opt, ek), (xb, vb))
                live_steps = live_steps + jnp.sum(lives, dtype=jnp.int32)
            return flat, jnp.sum(losses) / jnp.maximum(jnp.sum(lives), 1.0), \
                live_steps

        # Client-axis strategy: vmap on accelerators; on XLA:CPU batched
        # GEMMs degrade superlinearly past K~4 (measured 2x at K=6), so we
        # lower the client axis to lax.map (a scan over clients inside the
        # same jitted call) there instead.
        if jax.default_backend() == "cpu":
            def all_clients(*args):
                return jax.lax.map(lambda t: one_client(*t), args)
        else:
            def all_clients(*args):
                return jax.vmap(one_client)(*args)

        with scope(CLIENT_EPOCH):
            return all_clients(base_flat, x, valid, lrs, rngs)

    def run(base_flat, x, valid, lrs, rngs):
        """base_flat: (K, N); x: (K, nb*B, F); valid: (K, nb*B). Returns
        the trained (K, N) stack, each participant's mean loss of its last
        epoch and the optimizer steps it took over all epochs (K,)."""
        nb = x.shape[1] // batch_size
        return epoch(base_flat, x, valid,
                     jnp.asarray(lrs, jnp.float32), rngs, nb)

    return run


@functools.lru_cache(maxsize=None)
def make_server_epoch(cfg, *, batch_size=100, l1=0.0):
    @partial(jax.jit, static_argnames=("nb",))
    def epoch(params, opt, x, y, valid, lr, rng, nb):
        xb = x.reshape(nb, batch_size, -1)
        yb = y.reshape(nb, batch_size)
        vb = valid.reshape(nb, batch_size)

        def step(carry, inp):
            params, opt, rng = carry
            xi, yi, vi = inp
            rng, dr = jax.random.split(rng)

            def loss_fn(p):
                logits = cnn_forward(cfg, p, xi, train=True, rng=dr)
                logp = jax.nn.log_softmax(logits, axis=-1)
                ce = -jnp.take_along_axis(logp, yi[:, None], axis=-1)[:, 0]
                return jnp.sum(ce * vi) / jnp.maximum(jnp.sum(vi), 1.0)

            l, g = jax.value_and_grad(loss_fn)(params)
            params, opt = adam_update(g, opt, params, lr=lr, l1=l1)
            return (params, opt, rng), l

        (params, opt, _), losses = jax.lax.scan(step, (params, opt, rng),
                                                (xb, yb, vb))
        return params, opt, jnp.mean(losses)

    def run(params, opt, x_np, y_np, lr, rng):
        import numpy as np
        n = len(x_np)
        nb = max((n + batch_size - 1) // batch_size, 1)
        pad = nb * batch_size - n
        if pad:
            x = np.concatenate([x_np, np.zeros((pad, x_np.shape[1]), x_np.dtype)])
            y = np.concatenate([y_np, np.zeros(pad, y_np.dtype)])
        else:
            x, y = x_np, y_np
        valid = np.concatenate([np.ones(n, np.float32), np.zeros(pad, np.float32)])
        return epoch(params, opt, jnp.asarray(x), jnp.asarray(y),
                     jnp.asarray(valid), jnp.float32(lr), rng, nb)

    return run


@functools.lru_cache(maxsize=None)
def make_server_epoch_flat(cfg, *, batch_size=100, l1=0.0):
    """Flat-state twin of ``make_server_epoch`` for the batched engine.

    Takes/returns the global model and the server's Adam state as flat
    vectors (trees materialize only inside the loss), so the server step
    composes with the flat round pipeline without per-round tree round
    trips. Elementwise Adam math is identical leaf-by-leaf or flat, so this
    matches the sequential server epoch to float reduction order.
    """
    from repro.core.sparse_comm import unflatten_like

    template = _cnn_template(cfg)

    @partial(jax.jit, static_argnames=("nb",))
    def epoch(flat, opt, x, y, valid, lr, rng, nb):
        xb = x.reshape(nb, batch_size, -1)
        yb = y.reshape(nb, batch_size)
        vb = valid.reshape(nb, batch_size)

        def step(carry, inp):
            flat, opt, rng = carry
            xi, yi, vi = inp
            rng, dr = jax.random.split(rng)

            def loss_fn(fp):
                p = unflatten_like(fp, template)
                logits = cnn_forward(cfg, p, xi, train=True, rng=dr)
                logp = jax.nn.log_softmax(logits, axis=-1)
                ce = -jnp.take_along_axis(logp, yi[:, None], axis=-1)[:, 0]
                return jnp.sum(ce * vi) / jnp.maximum(jnp.sum(vi), 1.0)

            l, g = jax.value_and_grad(loss_fn)(flat)
            flat, opt = adam_update(g, opt, flat, lr=lr, l1=l1)
            return (flat, opt, rng), l

        with scope(SERVER_EPOCH):
            (flat, opt, _), losses = jax.lax.scan(step, (flat, opt, rng),
                                                  (xb, yb, vb))
        return flat, opt, jnp.mean(losses)

    def run(flat, opt, x_np, y_np, lr, rng):
        import numpy as np
        n = len(x_np)
        nb = max((n + batch_size - 1) // batch_size, 1)
        pad = nb * batch_size - n
        if pad:
            x = np.concatenate([x_np, np.zeros((pad, x_np.shape[1]),
                                               x_np.dtype)])
            y = np.concatenate([y_np, np.zeros(pad, y_np.dtype)])
        else:
            x, y = x_np, y_np
        valid = np.concatenate([np.ones(n, np.float32),
                                np.zeros(pad, np.float32)])
        return epoch(flat, opt, jnp.asarray(x), jnp.asarray(y),
                     jnp.asarray(valid), jnp.float32(lr), rng, nb)

    return run


@functools.lru_cache(maxsize=None)
def predict_fn(cfg):
    @jax.jit
    def predict(params, x):
        return jnp.argmax(cnn_forward(cfg, params, x), axis=-1)
    return predict


@functools.lru_cache(maxsize=None)
def class_histogram(cfg):
    """Pseudo-label class distribution of a client (used for grouping —
    the server never sees true client labels)."""
    @jax.jit
    def hist(params, x):
        pred = jnp.argmax(cnn_forward(cfg, params, x), axis=-1)
        return jnp.bincount(pred, length=cfg.num_classes) / x.shape[0]
    return hist


@functools.lru_cache(maxsize=None)
def class_histogram_batch(cfg, *, batch_size=100):
    """Batched ``class_histogram`` over padded per-client data.

    flat: (K, N) stacked uploaded models; x: (K, nb*B, F); valid: (K, nb*B)
    0/1 — padding rows are excluded from both the counts and the denominator,
    so each row matches the sequential histogram on that client's unpadded
    data. The forward runs chunk-by-chunk with all-padding chunks skipped
    (a real branch under the CPU lax.map strategy).
    """
    from repro.core.sparse_comm import unflatten_stacked

    template = _cnn_template(cfg)

    def hist(p, x, valid):
        xb = x.reshape(-1, batch_size, x.shape[-1])
        vb = valid.reshape(-1, batch_size)

        def step(acc, inp):
            xi, vi = inp
            counts = jax.lax.cond(
                jnp.sum(vi) > 0,
                lambda _: jnp.zeros(cfg.num_classes, jnp.float32)
                .at[jnp.argmax(cnn_forward(cfg, p, xi), axis=-1)].add(vi),
                lambda _: jnp.zeros(cfg.num_classes, jnp.float32), None)
            return acc + counts, None

        acc, _ = jax.lax.scan(step, jnp.zeros(cfg.num_classes, jnp.float32),
                              (xb, vb))
        return acc / jnp.maximum(jnp.sum(valid), 1.0)

    if jax.default_backend() == "cpu":
        def mapped(params, x, valid):
            return jax.lax.map(lambda t: hist(*t), (params, x, valid))
    else:
        def mapped(params, x, valid):
            return jax.vmap(hist)(params, x, valid)

    @jax.jit
    def run(flat, x, valid):
        params = unflatten_stacked(flat, template)
        return mapped(params, x, valid)

    return run
