"""Sharding rules: map parameter/activation pytrees to PartitionSpecs.

Mesh axes:
  pod    — slow inter-pod links (multi-pod mesh only); batch-parallel
  data   — batch parallel; with ``fsdp`` also shards param storage (ZeRO-3-ish)
  model  — tensor/expert parallel (attention heads, FFN width, experts)

Rules are name-based over the parameter tree produced by ``lm.init_params``.
Leaves under ``params["scan"]`` carry a leading stacked layer dim that is never
sharded. pjit *argument* shardings must divide dimensions exactly (unlike
internal constraints, which pad), so every rule is filtered through ``_fit``:
axes that do not divide the dim are dropped (tuple axes keep the longest
dividing prefix) — e.g. whisper's vocab 51865 stays unsharded, GQA kv=8 heads
fall back to sequence sharding on a 16-way model axis.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig


def maybe_constraint(x, spec_dims):
    """with_sharding_constraint iff a mesh with the named axes is active.

    Entries may be axis names, tuples of axis names (filtered to the axes
    present on the active mesh), or None.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    names = set(mesh.axis_names)

    def fix(d):
        if isinstance(d, str):
            return d if d in names else None
        if isinstance(d, (tuple, list)):
            kept = tuple(a for a in d if a in names)
            return kept if kept else None
        return None

    dims = tuple(fix(d) for d in spec_dims)
    if all(d is None for d in dims):
        return x
    return jax.lax.with_sharding_constraint(x, P(*dims))


def batch_axes(mesh_axis_names):
    return tuple(a for a in ("pod", "data") if a in mesh_axis_names)


def mesh_axis_sizes(mesh) -> dict:
    return {name: int(size) for name, size in
            zip(mesh.axis_names, mesh.devices.shape)}


def _fit(spec_dims, shape, axis_sizes):
    """Drop axes that do not divide their dim (pjit argument requirement)."""
    out = []
    for i, d in enumerate(spec_dims):
        if d is None or i >= len(shape):
            out.append(None)
            continue
        axes = (d,) if isinstance(d, str) else tuple(d)
        axes = tuple(a for a in axes if a in axis_sizes)
        # longest prefix whose size product divides the dim
        kept = []
        prod = 1
        for a in axes:
            if shape[i] % (prod * axis_sizes[a]) == 0:
                kept.append(a)
                prod *= axis_sizes[a]
        if not kept:
            out.append(None)
        elif len(kept) == 1:
            out.append(kept[0])
        else:
            out.append(tuple(kept))
    return P(*out)


# ---------------------------------------------------------------------------
# client-axis mesh (the FedS3A fleet engine)
# ---------------------------------------------------------------------------
CLIENT_AXIS = "clients"

# (K, N) flat client stacks: rows over devices, params replicated per row
CLIENT_STACK_SPEC = P(CLIENT_AXIS, None)
# (K,) per-client scalars (weights, thresholds, nnz)
CLIENT_VEC_SPEC = P(CLIENT_AXIS)
# replicated values (the global model, the supervised weight)
REPLICATED_SPEC = P()
# one CSR payload triple — (K, cap) values, (K, cap) column indices, (K,)
# stored counts — sharded row-wise like the stacks they compact: each device
# packs/decodes only its local client rows, so compaction adds no collective
CLIENT_PAYLOAD_SPECS = (CLIENT_STACK_SPEC, CLIENT_STACK_SPEC,
                        CLIENT_VEC_SPEC)
# the paged client store (``client_store="paged"``) removes the (M, rcap)
# device-resident residual source entirely: the round stages consume a
# gathered (Kp, rcap) PARTICIPANT WINDOW of residual pages instead, sharded
# row-wise exactly like every other per-client stack — the specs are
# unchanged, only the array they partition shrank from fleet-sized to
# round-sized. The alias documents that the window intentionally shares the
# payload triple's layout (values / indices rows + per-row counts).
CLIENT_WINDOW_SPECS = CLIENT_PAYLOAD_SPECS


def payload_specs(wire_format):
    """PartitionSpec tuple for one wire payload (stored counts excluded):
    every component is per-client rows, so each device quantizes/packs and
    decodes only its local shard — neither CSR format adds a collective.

    ``"csr"``  -> ((K, cap) values, (K, cap) column indices)
    ``"csr_q"`` -> ((K, cap) int8 qvalues, (K, cap) int16 offsets,
                    (K, nblk) int16 block counts, (K,) f32 scales)
    """
    if wire_format == "csr_q":
        return (CLIENT_STACK_SPEC, CLIENT_STACK_SPEC, CLIENT_STACK_SPEC,
                CLIENT_VEC_SPEC)
    return (CLIENT_STACK_SPEC, CLIENT_STACK_SPEC)
# versioned base store (staleness-windowed delta chain): the (tau+2, N)
# reconstruction ring is tiny and REPLICATED on every device, while the
# per-client ring-slot index vector shards like any other per-client scalar
# — so the version-indexed base gather ``ring[slots]`` runs shard-local
# inside the round stages with no collective, replacing the dense (M, N)
# per-client row gather the legacy base store needed
RING_SPEC = P(None, None)
RING_SLOT_SPEC = CLIENT_VEC_SPEC


def client_mesh(num_devices=None) -> Mesh:
    """1D device mesh over the ``clients`` axis.

    The fleet engine shards stacked per-client state (rows of the (K, N)
    flat matrices) across devices; on a CPU host
    ``XLA_FLAGS=--xla_force_host_platform_device_count=D`` provides D
    simulated devices. A mesh of one device degenerates to the batched
    engine's layout and is always valid.
    """
    devs = jax.devices()
    n = len(devs) if num_devices is None else min(num_devices, len(devs))
    return Mesh(np.asarray(devs[:n]), (CLIENT_AXIS,))


def padded_rows(k: int, num_shards: int) -> int:
    """Smallest multiple of ``num_shards`` >= k (>= 1 shard row each).

    shard_map input dims must divide the mesh axis exactly, so a round with
    K participants on D devices runs on ceil(K/D)*D rows; the pad rows carry
    zero validity masks / zero aggregation weight and are sliced off before
    any accounting.
    """
    k = max(int(k), 1)
    return ((k + num_shards - 1) // num_shards) * num_shards


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------
_RULES_2D = {
    "embed": ("data", "model"),
    "lm_head": ("data", "model"),
    "vision_proj": ("data", None),
    "pos": (None, "data"),
    "wq": ("data", "model"),
    "wk": ("data", "model"),
    "wv": ("data", "model"),
    "wo": ("model", "data"),
    "w_up": ("data", "model"),
    "w_gate": ("data", "model"),
    "w_down": ("model", "data"),
    "router": ("data", None),
    "wq_a": ("data", None),
    "wq_b": (None, "model"),
    "wkv_a": ("data", None),
    "wk_b": (None, "model"),
    "wv_b": (None, "model"),
    "in_proj": ("data", "model"),
    "x_proj": ("model", None),
    "dt_proj": (None, "model"),
    "A_log": ("model", None),
    "conv_w": (None, "model"),
    "out_proj": ("model", "data"),
    "up": ("data", "model"),
    "down": ("model", "data"),
    "w": ("data", None),
}
_RULES_1D = {
    "bq": ("model",),
    "bk": ("model",),
    "bv": ("model",),
    "conv_b": ("model",),
    "dt_bias": ("model",),
    "D": ("model",),
}
_RULES_3D = {
    "w_up": ("model", "data", None),     # MoE experts on model axis
    "w_gate": ("model", "data", None),
    "w_down": ("model", None, "data"),
}
_RULES_4D = {
    "r": (None, "model", None, None),
}

_FSDP_ONLY = "data"   # the axis fsdp=False strips from param specs


def _param_rule(name, shape, fsdp, profile="fsdp"):
    nd = len(shape)
    rule = None
    if nd == 3 and name in _RULES_3D:
        rule = _RULES_3D[name]
    elif nd == 4 and name in _RULES_4D:
        rule = _RULES_4D[name]
    elif nd == 2 and name in _RULES_2D:
        rule = _RULES_2D[name]
    elif nd == 1 and name in _RULES_1D:
        rule = _RULES_1D[name]
    if rule is None:
        return (None,) * nd
    if profile == "serve2d":
        # Inference profile: never shard a CONTRACTION/input dim over data
        # (that forces a full weight all-gather per step). Instead stack the
        # data axis onto the already-sharded output/feature dim (2D weight
        # sharding): matmul outputs come out sharded; XLA moves activation-
        # sized collectives, not weight-sized ones. Only plain matmul weights
        # get the stacking — MLA lora up-projections are reshaped to
        # (rank, H, head_dim) inside the layer, and GSPMD falls back to full
        # replication when the flat sharded dim splits across that reshape
        # (measured: 11 GB/layer involuntary remat traffic).
        # (Restricting the stacking to "safe" names was tried and REFUTED:
        # reverting MLA lora weights to model-only sharding brought back
        # 22 GB/token of all-gathers — worse than the reshape-replication it
        # avoided. See EXPERIMENTS.md §Perf case B it2.)
        out = []
        for a in rule:
            if a == _FSDP_ONLY:
                out.append(None)
            elif a == "model":
                out.append(("model", "data"))
            else:
                out.append(a)
        return tuple(out)
    if not fsdp:
        rule = tuple(None if a == _FSDP_ONLY else a for a in rule)
    return rule


def _is_stacked(path_keys):
    return any(k == "scan" for k in path_keys)


def _path_keys(path):
    out = []
    for p in path:
        k = getattr(p, "key", None)
        if k is None:
            k = getattr(p, "idx", None)
        if k is not None:
            out.append(k)
    return out


def param_specs(cfg: ModelConfig, params_shape, axis_sizes, *, fsdp=True,
                profile="fsdp"):
    """PartitionSpec pytree matching ``params_shape`` (from jax.eval_shape).

    profile="fsdp": train default (storage sharded over data, gathered on use)
    profile="serve2d": inference — 2D output-dim sharding, no weight gathers
    """

    def rule(path, leaf):
        keys = _path_keys(path)
        name = keys[-1] if keys else ""
        shape = leaf.shape
        stacked = _is_stacked(keys)
        if stacked:
            shape = shape[1:]
        spec = _param_rule(name, shape, fsdp, profile)
        fitted = _fit(spec, shape, axis_sizes)
        if stacked:
            fitted = P(None, *fitted)
        return fitted

    return jax.tree_util.tree_map_with_path(rule, params_shape)


def opt_specs(cfg: ModelConfig, opt_shape, pspecs):
    return {"m": pspecs, "v": pspecs, "t": P()}


# ---------------------------------------------------------------------------
# activation / batch rules
# ---------------------------------------------------------------------------
def batch_specs(cfg: ModelConfig, batch_shape, axis_sizes):
    ba = batch_axes(axis_sizes)

    def rule(leaf):
        if leaf.ndim == 0:
            return P()
        return _fit((ba,) + (None,) * (leaf.ndim - 1), leaf.shape, axis_sizes)

    return jax.tree.map(rule, batch_shape)


def cache_specs(cfg: ModelConfig, cache_shape, axis_sizes, *, batch_size):
    """Decode cache sharding.

    Attention caches (B, S, H, hd): batch over (pod, data) when divisible;
    KV heads over model when divisible, otherwise the sequence dim takes the
    model axis (GQA kv=8 on a 16-way model axis). batch=1 long-context decode
    shards the sequence over (data, model).
    """
    ba = batch_axes(axis_sizes)
    n_batch = 1
    for a in ba:
        n_batch *= axis_sizes[a]
    seq_shard = batch_size < n_batch

    def rule(path, leaf):
        keys = _path_keys(path)
        name = next((k for k in reversed(keys) if isinstance(k, str)), "")
        stacked = _is_stacked(keys)
        shape = leaf.shape[1:] if stacked else leaf.shape
        nd = len(shape)
        spec = [None] * nd

        if name in ("k", "v", "cross_k", "cross_v") and nd == 4:
            H = shape[2]
            if seq_shard:
                spec = [None, ("data", "model"), None, None]
            elif H % axis_sizes.get("model", 1) == 0:
                spec = [ba, None, "model", None]
            else:
                spec = [ba, "model", None, None]
        elif name in ("ckv", "krope") and nd == 3:
            spec = [None, ("data", "model"), None] if seq_shard else [ba, "model", None]
        elif name == "ssm" and nd == 3:
            spec = [None if seq_shard else ba, "model", None]
        elif name == "conv" and nd == 3:
            spec = [None if seq_shard else ba, None, "model"]
        elif name in ("C", "n") and nd >= 2:
            spec = [None if seq_shard else ba, "model"] + [None] * (nd - 2)
        elif name in ("m", "c") and nd >= 2:
            spec = [None if seq_shard else ba, "model"] + [None] * (nd - 2)
        elif nd >= 1:
            spec = [None if seq_shard else ba] + [None] * (nd - 1)

        fitted = _fit(tuple(spec), shape, axis_sizes)
        return P(None, *fitted) if stacked else fitted

    return jax.tree_util.tree_map_with_path(rule, cache_shape)
