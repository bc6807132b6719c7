"""FedS3A aggregation functions (§IV-D, Eq. 7-10).

All variants take the participating clients' parameters, data sizes,
stalenesses and the server's supervised parameters, and return the new global
model. The group-based variant (Eq. 10) averages |D|-weighted + g(s)-decayed
within each k-means group and arithmetically across groups; the flat variant
(Eq. 9) skips grouping; Eq. 7/8 ablations are expressible via flags.

The heavy weighted sum runs through the Pallas staleness_agg kernel when
``use_kernel`` (one VMEM pass over the stacked client deltas).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sparse_comm import flatten_tree, unflatten_like
from repro.core.telemetry import UNPACK, scope
from repro.kernels import ops as kops
from repro.kernels import ref as kref


def _weighted_sum_trees(trees, weights, *, use_kernel=False):
    weights = jnp.asarray(weights, jnp.float32)
    if use_kernel:
        stack = jnp.stack([flatten_tree(t) for t in trees])
        flat = kops.staleness_agg(stack, weights)
        return unflatten_like(flat, trees[0])
    out = jax.tree.map(lambda *ls: sum(w * l.astype(jnp.float32)
                                       for w, l in zip(weights, ls)), *trees)
    return jax.tree.map(lambda a, b: a.astype(b.dtype), out, trees[0])


def combine_weights(data_sizes, stalenesses, g_fn, groups=None):
    """Fold Eq. 9/10 into ONE per-client weight vector.

    Flat (Eq. 9): w_i ∝ |D_i| * g(s_i), normalized as in ``aggregate``.
    Grouped (Eq. 10): w_i = (1/G) * |D_i| g(s_i) / sum_{j in group(i)} |D_j|
    g(s_j) — the within-group weighted mean followed by the arithmetic mean
    across groups collapses to a single weighted sum over clients, which is
    what lets the batched engine aggregate the whole (K, N) delta stack in
    one kernel pass.

    Cold start: a participant set (or a whole group) whose combined
    |D|*g(s) mass is zero — empty shards after dataset scaling, or g(s)
    driven to 0 by extreme staleness — used to normalize to an all-zero
    weight vector, which silently dropped those clients from the aggregate
    and re-broadcast the supervised model scaled by f(r) alone (the global
    model shrank toward the server model with no signal that anything was
    wrong). Zero-mass sets now fall back to an explicit uniform weight so
    every participant the scheduler admitted contributes.
    """
    data_sizes = np.asarray(data_sizes, dtype=np.float64)
    g = np.array([g_fn(s) for s in stalenesses], dtype=np.float64)
    if groups is None:
        w = data_sizes * g
        if w.sum() <= 0.0:
            return np.full(len(w), 1.0 / max(len(w), 1))
        w = w / max(data_sizes.sum(), 1e-12)
        return w / max(w.sum(), 1e-12)
    groups = np.asarray(groups)
    uniq = np.unique(groups)
    w = np.zeros(len(data_sizes))
    for gidx in uniq:
        sel = groups == gidx
        wg = data_sizes[sel] * g[sel]
        if wg.sum() <= 0.0:
            w[sel] = 1.0 / (sel.sum() * len(uniq))
        else:
            w[sel] = wg / wg.sum() / len(uniq)
    return w


def combine_weights_device(size_g, groups, num_groups):
    """On-device twin of ``combine_weights`` for the sharded fleet engine.

    size_g: (K,) jnp — |D_i| * g(s_i) per participant (host-computable from
    the scheduler, so it arrives as data); groups: (K,) int32 device array
    (from ``grouping.kmeans_device``); num_groups: static int >= the number
    of distinct labels. Returns the (K,) fp32 weight vector with the same
    grouped normalization and uniform cold-start fallback as the host path,
    computed entirely under jit — group count G counts non-empty groups only,
    matching np.unique on the host.
    """
    size_g = jnp.asarray(size_g, jnp.float32)
    K = size_g.shape[0]
    onehot = jax.nn.one_hot(groups, num_groups, dtype=jnp.float32)  # (K, G)
    cnt = onehot.sum(0)                                             # (G,)
    mass = onehot.T @ size_g                                        # (G,)
    G = jnp.maximum(jnp.sum(cnt > 0), 1).astype(jnp.float32)
    per_group = jnp.where(
        mass > 0,
        size_g[:, None] * onehot / jnp.maximum(mass, 1e-30),
        onehot / jnp.maximum(cnt, 1.0))                             # (K, G)
    return per_group.sum(1) / G


def combine_weights_flat_device(size_g):
    """Flat (Eq. 9) device weights: normalize with uniform cold-start."""
    size_g = jnp.asarray(size_g, jnp.float32)
    total = jnp.sum(size_g)
    K = size_g.shape[0]
    return jnp.where(total > 0, size_g / jnp.maximum(total, 1e-30),
                     jnp.full((K,), 1.0 / K, jnp.float32))


@jax.jit
def _blend_flat(server_flat, client_flat, w, f_weight):
    unsup = jnp.einsum("k,kn->n", w, client_flat.astype(jnp.float32))
    return f_weight * server_flat.astype(jnp.float32) + \
        (1.0 - f_weight) * unsup


@jax.jit
def _blend_flat_kernel(server_flat, client_flat, w, f_weight):
    unsup = kops.staleness_agg(client_flat, w)
    return f_weight * server_flat.astype(jnp.float32) + \
        (1.0 - f_weight) * unsup


def csr_weighted_scatter(values, indices, w, n):
    """Fused server-side decode + weighted sum of K CSR payload rows.

    values/indices: (K, cap) compacted payloads (padding slots carry value 0
    at index 0, so they scatter nothing); w: (K,) combined Eq. 9/10 weights.
    Returns sum_k w_k * decode(payload_k) as an (n,) fp32 vector via ONE
    flat scatter-add of K*cap contributions — the dense (K, n) decode is
    never materialized, which is what makes the compacted upload cheaper to
    aggregate than the masked-dense stack it replaces.
    """
    contrib = w[:, None].astype(jnp.float32) * values.astype(jnp.float32)
    return jnp.zeros((n,), jnp.float32).at[indices.reshape(-1)].add(
        contrib.reshape(-1))


def blend_flat_csr(server_flat, base_flat, values, indices, w, f_weight,
                   *, use_kernel=False):
    """FedS3A global update from CSR upload payloads (the compacted wire
    format): uploaded_k = base_k + decode(payload_k), so the weighted client
    sum splits into the dense base sum (Pallas ``staleness_agg`` when
    ``use_kernel``) plus one fused weighted scatter-add of the payloads.
    """
    w = w.astype(jnp.float32)
    if use_kernel:
        base_sum = kops.staleness_agg(base_flat, w)
    else:
        base_sum = jnp.einsum("k,kn->n", w, base_flat.astype(jnp.float32))
    unsup = base_sum + csr_weighted_scatter(values, indices, w,
                                            server_flat.shape[0])
    return f_weight * server_flat.astype(jnp.float32) + \
        (1.0 - f_weight) * unsup


def blend_flat_sharded_csr(server_flat, base_local, values_local,
                           indices_local, w_local, f_weight, *, axis_name,
                           use_kernel=False):
    """``blend_flat_csr`` inside a ``shard_map`` over the client axis: each
    shard folds its local base rows and payload rows (pad rows carry weight
    0 and value-0/index-0 payload slots, so they vanish), and one psum
    produces the replicated weighted client sum before the f(r) blend."""
    w_local = w_local.astype(jnp.float32)
    if use_kernel:
        base_sum = kops.staleness_agg(base_local, w_local)
    else:
        base_sum = jnp.einsum("k,kn->n", w_local,
                              base_local.astype(jnp.float32))
    partial = base_sum + csr_weighted_scatter(values_local, indices_local,
                                              w_local, server_flat.shape[0])
    unsup = jax.lax.psum(partial, axis_name)
    return f_weight * server_flat.astype(jnp.float32) + \
        (1.0 - f_weight) * unsup


def csr_q_weighted_scatter(qvals, qoffs, qcnt, scales, w, n):
    """Fused server-side decode of K quantized csr_q payload rows into the
    weighted client sum — the csr_q twin of :func:`csr_weighted_scatter`.

    qvals: (K, cap) int8 (or f16) quantized values; qoffs: (K, cap) int16
    in-block column offsets; qcnt: (K, nblk) int16 per-block counts (the
    index decoder's side information); scales: (K,) f32 per-row absmax
    scales (all-ones for fp16 payloads); w: (K,) combined Eq. 9/10 weights.

    Absolute columns are reconstructed exactly as a receiver would —
    block id per slot by marks and a running sum over the cumulative
    block counts (``ref.csr_unpack_indices_ref``, jitted into the blend),
    then ``block * 512 + offset`` — and
    dequantization FUSES into the weight multiply: the contribution of row
    k is ``(w_k * scale_k) * qvals_k``, so the f32 payload is never
    materialized. Padding slots carry value 0 at a clamped index and
    scatter nothing. Returns sum_k w_k * dequant(decode(payload_k)) as an
    (n,) fp32 vector via one flat scatter-add.
    """
    with scope(UNPACK):
        idx = jnp.minimum(kref.csr_unpack_indices_ref(qoffs, qcnt), n - 1)
    contrib = (w.astype(jnp.float32) *
               scales.astype(jnp.float32))[:, None] * \
        qvals.astype(jnp.float32)
    return jnp.zeros((n,), jnp.float32).at[idx.reshape(-1)].add(
        contrib.reshape(-1))


def blend_flat_csr_q(server_flat, base_flat, qvals, qoffs, qcnt, scales, w,
                     f_weight, *, use_kernel=False):
    """FedS3A global update from quantized csr_q upload payloads:
    uploaded_k = base_k + dequant(decode(payload_k)), so the weighted
    client sum splits into the dense base sum plus one fused
    dequantizing weighted scatter-add of the quantized payloads."""
    w = w.astype(jnp.float32)
    if use_kernel:
        base_sum = kops.staleness_agg(base_flat, w)
    else:
        base_sum = jnp.einsum("k,kn->n", w, base_flat.astype(jnp.float32))
    unsup = base_sum + csr_q_weighted_scatter(qvals, qoffs, qcnt, scales, w,
                                              server_flat.shape[0])
    return f_weight * server_flat.astype(jnp.float32) + \
        (1.0 - f_weight) * unsup


def blend_flat_sharded_csr_q(server_flat, base_local, qvals_local,
                             qoffs_local, qcnt_local, scales_local, w_local,
                             f_weight, *, axis_name, use_kernel=False):
    """``blend_flat_csr_q`` inside a ``shard_map`` over the client axis:
    each shard folds its local base rows and quantized payload rows (pad
    rows carry weight 0 and zero-valued payload slots, so they vanish),
    and one psum produces the replicated weighted client sum."""
    w_local = w_local.astype(jnp.float32)
    if use_kernel:
        base_sum = kops.staleness_agg(base_local, w_local)
    else:
        base_sum = jnp.einsum("k,kn->n", w_local,
                              base_local.astype(jnp.float32))
    partial = base_sum + csr_q_weighted_scatter(
        qvals_local, qoffs_local, qcnt_local, scales_local, w_local,
        server_flat.shape[0])
    unsup = jax.lax.psum(partial, axis_name)
    return f_weight * server_flat.astype(jnp.float32) + \
        (1.0 - f_weight) * unsup


def aggregate_flat_csr(server_flat, base_flat, values, indices, *,
                       data_sizes, stalenesses, g_fn, f_weight, groups=None,
                       use_kernel=False):
    """FedS3A global update on compacted uploads: ``combine_weights`` folds
    Eq. 9/10 into one weight vector, then ``blend_flat_csr`` consumes the
    CSR payloads directly (scatter-add decode fused into the aggregation).
    """
    w = combine_weights(data_sizes, stalenesses, g_fn, groups)
    return blend_flat_csr(server_flat, base_flat, values, indices,
                          jnp.asarray(w, jnp.float32), jnp.float32(f_weight),
                          use_kernel=use_kernel)


def blend_flat_sharded(server_flat, client_flat_local, w_local, f_weight,
                       *, axis_name, use_kernel=False):
    """FedS3A global update inside a ``shard_map`` over the client axis.

    Each shard holds a (K_local, N) slice of the uploaded client stack and
    the matching (K_local,) slice of the combined Eq. 9/10 weights (pad rows
    carry weight 0, so they vanish from the sum). The weighted reduction
    runs locally — one ``staleness_agg`` kernel pass per shard when
    ``use_kernel`` — and a single psum over ``axis_name`` produces the
    replicated global weighted sum; every device then applies the f(r)
    blend to its own copy. One collective per round, O(N) bytes.
    """
    if use_kernel:
        partial_sum = kops.staleness_agg(client_flat_local, w_local)
    else:
        partial_sum = jnp.einsum("k,kn->n", w_local.astype(jnp.float32),
                                 client_flat_local.astype(jnp.float32))
    unsup = jax.lax.psum(partial_sum, axis_name)
    return f_weight * server_flat.astype(jnp.float32) + \
        (1.0 - f_weight) * unsup


def aggregate_flat(server_flat, client_flat, *, data_sizes, stalenesses,
                   g_fn, f_weight, groups=None, use_kernel=False):
    """FedS3A global update on already-flattened stacks (the batched engine).

    server_flat: (N,) supervised model; client_flat: (K, N) stacked uploaded
    client models. Returns the new global model as an (N,) fp32 flat vector —
    one jitted weighted-sum pass (Pallas staleness_agg when ``use_kernel``)
    plus the f(r) blend, with no per-tree flatten/stack.
    """
    w = combine_weights(data_sizes, stalenesses, g_fn, groups)
    blend = _blend_flat_kernel if use_kernel else _blend_flat
    return blend(server_flat, client_flat, jnp.asarray(w, jnp.float32),
                 jnp.float32(f_weight))


def aggregate(server_params, client_params, *, data_sizes, stalenesses,
              g_fn, f_weight, groups=None, use_kernel=False):
    """FedS3A global update.

    server_params: supervised model omega_s^{r+1}
    client_params: list of participating clients' models omega_i^{r_i+1}
    data_sizes:    |D_i| per participant
    stalenesses:   r - r_i per participant
    g_fn:          staleness function
    f_weight:      f(r), the dynamic supervised weight
    groups:        optional group index per participant (Eq. 10); None -> Eq. 9
    """
    data_sizes = np.asarray(data_sizes, dtype=np.float64)
    g = np.array([g_fn(s) for s in stalenesses], dtype=np.float64)

    if groups is None:
        w = data_sizes * g
        w = w / max(data_sizes.sum(), 1e-12)
        # Eq. 9: weights |D_i|/|D_c| * g(s_i) (not renormalized; g shrinks
        # stale contributions relative to the fresh ones)
        w = w / max(w.sum(), 1e-12)
        unsup = _weighted_sum_trees(client_params, w, use_kernel=use_kernel)
    else:
        groups = np.asarray(groups)
        uniq = np.unique(groups)
        group_models = []
        for gidx in uniq:
            sel = np.where(groups == gidx)[0]
            dg = data_sizes[sel]
            wg = dg * g[sel]
            wg = wg / max(wg.sum(), 1e-12)
            group_models.append(_weighted_sum_trees(
                [client_params[i] for i in sel], wg, use_kernel=use_kernel))
        w = np.full(len(group_models), 1.0 / len(group_models))
        unsup = _weighted_sum_trees(group_models, w, use_kernel=use_kernel)

    return jax.tree.map(
        lambda s, u: (f_weight * s.astype(jnp.float32) +
                      (1.0 - f_weight) * u.astype(jnp.float32)).astype(s.dtype),
        server_params, unsup)


def fedavg(client_params, data_sizes):
    """Eq. 3 (plain FedAvg over clients)."""
    w = np.asarray(data_sizes, dtype=np.float64)
    w = w / w.sum()
    return _weighted_sum_trees(client_params, w)


def fedavg_ssl(server_params, client_params, data_sizes, f_weight):
    """Eq. 8: FedAvg + dynamic supervised weight (the adapted baseline)."""
    unsup = fedavg(client_params, data_sizes)
    return jax.tree.map(
        lambda s, u: (f_weight * s.astype(jnp.float32) +
                      (1.0 - f_weight) * u.astype(jnp.float32)).astype(s.dtype),
        server_params, unsup)


def fedasync_blend(global_params, client_params, *, staleness, alpha=0.9,
                   a=0.5):
    """FedAsync [Xie et al. 2019] mixing with polynomial staleness decay
    (alpha=0.9, a=0.5 — the best-performing combination per the paper; the
    proximal rho=0.005 term lives in the client loss, handled by L2 in the
    baseline trainer)."""
    alpha_t = min(alpha * (staleness + 1.0) ** (-a), 1.0)
    return jax.tree.map(
        lambda gp, cp: ((1 - alpha_t) * gp.astype(jnp.float32) +
                        alpha_t * cp.astype(jnp.float32)).astype(gp.dtype),
        global_params, client_params)
