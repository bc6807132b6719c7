"""The FedS3A trainer: ties together the semi-async scheduler, FSSL training,
group-based staleness-weighted aggregation, adaptive learning rates and
sparse-difference communication. Reproduces the paper's Tables V-XII.

Three round engines share the scheduler/aggregation math, selected by
``engine=`` (``"sequential" | "batched" | "sharded" | None``):

* ``"sequential"`` — the original one-client-at-a-time loop, kept as the
  reference implementation (the parity suite pins the others to it).
* ``"batched"`` — client state lives as a stacked flat (client, param)
  matrix; every participant's pseudo-label epoch runs in ONE jitted call
  (client axis via vmap on accelerators, lax.map on CPU where XLA's batched
  GEMMs degrade), all upload deltas are thresholded/counted in one 2D-grid
  kernel launch with deferred on-device ACO accounting, and the stacked
  flat deltas feed the aggregation kernel directly. A handful of dispatches
  per round instead of dozens per client, zero per-message host syncs.
* ``"sharded"`` — the fleet engine: the batched engine's (K, N) client
  stacks are sharded row-wise across devices with ``shard_map`` over a
  ``clients`` mesh axis, so a multi-device host (or
  ``XLA_FLAGS=--xla_force_host_platform_device_count=D`` on CPU) trains
  D client shards concurrently. Per-client base/residual state lives in
  (M, N) matrices gathered/scattered by participant index; aggregation is
  one psum over the client axis; grouping runs the on-device jitted
  k-means. The whole round is device-resident — zero host syncs (the
  deferred ACO read excepted). K that does not divide the device count is
  padded with zero-weight rows, sliced off before any accounting.
* ``None`` (default) — auto: sharded whenever more than one device is
  visible (and the model is small enough on CPU); batched on a single
  accelerator or for small CPU models (round overhead dominates there,
  measured ~3.5x per round); sequential for compute-bound single-device
  CPU training where the engines tie.

The legacy ``batched=True/False`` config flag maps onto
``engine="batched"/"sequential"`` when ``engine`` is unset.

Communication uses the compacted CSR wire format by default
(``wire_format="csr"``): uploads and distributions move real
(values, indices, row_ptr) payload arrays, the aggregation consumes them
via a fused scatter-add decode, and — under error feedback — per-client
residuals live in a capacity-bounded sparse store instead of dense (M, N)
state. ``wire_format="dense_masked"`` keeps the pre-compaction reference
behaviour (masked dense deltas, counted-not-materialized payloads).

Per-client base state is versioned by default (``base_store="versioned"``):
the server keeps a ring of the last ``tau + 2`` canonical reconstructions
plus one compacted chain delta per round transition
(``core.base_store.VersionedBaseStore``), a client's base is a ring lookup
by ``base_version``, and distribution is a chain-delta broadcast (each
transition payload on the wire once per round, ≤ tau + 1 of them, shared by
every listening client) instead of one encode per target. Server base
memory is O(tau * N + M)
rather than the O(M * N) the dense layouts needed. ``base_store="dense"``
keeps the legacy per-client stores (per-client trees / ``_base_rows`` /
``_base_mat``), whose per-client encode-against-own-base error the parity
suite pins against the sequential reference.
"""
from __future__ import annotations

import os
import queue
import threading
import warnings
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.feds3a_cnn import CONFIG as CNN_CONFIG
from repro.core import aggregation as agg
from repro.core import fleet_ckpt
from repro.core.base_store import VersionedBaseStore
from repro.core.client_store import PagedClientStore
from repro.core.functions import (adaptive_learning_rates, staleness_fn,
                                  supervised_weight)
from repro.core.grouping import group_clients, init_index, kmeans_device
from repro.core.metrics import fleet_health, weighted_metrics
from repro.core.model_adapter import make_adapter
from repro.core.param_layout import ParamLayout
from repro.core.scheduler import SemiAsyncScheduler, paper_latency
from repro.core.sparse_comm import (CSR_FORMATS, MALFORM_KINDS, Q_BLOCK,
                                    SparseComm, WireIntegrityError,
                                    flatten_tree, unflatten_like)
from repro.distributed.sharding import (CLIENT_AXIS, CLIENT_PAYLOAD_SPECS,
                                        CLIENT_STACK_SPEC, CLIENT_VEC_SPEC,
                                        REPLICATED_SPEC, RING_SLOT_SPEC,
                                        RING_SPEC, client_mesh, padded_rows,
                                        payload_specs)
from repro.kernels.ops import csr_decode
from repro.optimizer import adam_init

ENGINES = ("sequential", "batched", "sharded")
BASE_STORES = ("versioned", "dense")
CLIENT_STORES = ("resident", "paged")

# auto engine selection: minimum participants per device before the sharded
# engine beats batched — below this the psum/collective overhead dominates
# the per-shard work (measured: K=8 on D=4 CPU devices, 2 rows/device, loses
# to the batched engine; 4+ rows/device wins)
MIN_SHARD_ROWS = 4

# client-axis partition specs for the sharded round stages (short aliases
# of the canonical specs in distributed.sharding)
_ROW = CLIENT_VEC_SPEC                  # (K,) per-client scalars
_ROW2 = CLIENT_STACK_SPEC               # (K, N) stacks / (K, 2) keys
_ROW3 = P(CLIENT_AXIS, None, None)      # (K, nb*B, F) padded data
_REP = REPLICATED_SPEC                  # replicated


@jax.jit
def _gather_rows(mat, idx):
    """(M, N) state matrix -> (Kp, N) stacked rows for this round."""
    return mat[idx]


_scatter_jit = None


def _scatter_rows(mat, idx, rows):
    """Write updated per-client rows back into the (M, N) state matrix.

    The caller always overwrites its reference with the result, so the
    input buffer is donated where the backend supports it (not XLA:CPU,
    which warns and ignores donation) — at fleet scale an undonated
    scatter copies the whole (M, N) matrix per round. Built lazily so
    importing this module never initializes the XLA client."""
    global _scatter_jit
    if _scatter_jit is None:
        _scatter_jit = jax.jit(
            lambda m, i, r: m.at[i].set(r),
            donate_argnums=(0,) if jax.default_backend() != "cpu" else ())
    return _scatter_jit(mat, idx, rows)


@dataclass
class FedS3AConfig:
    rounds: int = 20
    C: float = 0.6                      # participation proportion (§IV-C1)
    tau: int = 2                        # staleness tolerance (§IV-C2)
    lr: float = 1e-4                    # paper Table IV
    batch_size: int = 100
    epochs: int = 1
    server_epochs: int = 1
    init_server_epochs: int = 5         # E_s warmup at r0 (Algorithm 1 l.5-6)
    threshold: float = 0.95             # pseudo-label confidence
    staleness_function: str = "exponential"
    round_weight_function: str = "exponential"
    adaptive_lr: bool = True
    supervised_weight_mode: str = "adaptive"   # adaptive|fixed_alpha|fixed_beta
    num_groups: int = 3
    group_based: bool = True
    sparse_comm: bool = True
    sparse_threshold: object = "p0.2"    # top-20% magnitude (ACO ~ 0.49)
    wire_format: str = "csr"             # "csr": compacted payloads (values
                                         # + indices + row_ptr actually
                                         # materialized; bytes-on-wire is
                                         # the real payload size) |
                                         # "csr_q": quantized + packed CSR
                                         # (int8 values + per-row absmax
                                         # scale, int16 in-block index
                                         # offsets + block-count table;
                                         # ~3 bytes/element vs 8; rounding
                                         # error folds into the EF residual)
                                         # | "dense_masked": legacy reference
                                         # (masked dense deltas, counted nnz)
    q_dtype: str = "int8"                # csr_q value dtype: "int8" (per-row
                                         # absmax scale) | "fp16" (wide
                                         # dynamic-range fallback, no scale)
    wire_capacity: object = None         # per-row payload capacity override
                                         # (None: auto from the keep frac)
    residual_frac: float = 0.25          # EF residual store: top fraction of
                                         # N kept by magnitude (1.0 =
                                         # lossless); the sharded store is
                                         # O(M * residual_frac * N)
    base_store: str = "versioned"        # "versioned": ring of tau+2 global
                                         # reconstructions + chain deltas,
                                         # chain-delta broadcast
                                         # distribution, O(tau*N + M) server
                                         # memory | "dense": legacy
                                         # per-client base state (O(M*N)),
                                         # per-target distribution encodes
    client_store: str = "resident"       # "resident": per-client EF residual
                                         # rows (and the batched engines'
                                         # padded data stack) live as (M,...)
                                         # device arrays — the parity-pinned
                                         # reference | "paged": host-resident
                                         # pages (core.client_store) with a
                                         # device gather/scatter window over
                                         # the round's participants only —
                                         # device client-state bytes are
                                         # O(K * page), flat in M. Requires
                                         # base_store="versioned"
    paged_dir: object = None             # client_store="paged": directory
                                         # for memory-mapped page files
                                         # (None = anonymous host RAM, which
                                         # Linux commits lazily)
    error_feedback: bool = False         # beyond-paper: EF-sparsification
    l1: float = 1e-5                    # §IV-F L1 regularisation
    use_kernels: bool = False           # Pallas kernels (interpret on CPU)
    engine: object = None               # "sequential" | "batched" | "sharded"
                                        # | None = auto (sharded on multi-
                                        # device hosts, batched on a single
                                        # accelerator / small CPU model,
                                        # sequential for compute-bound
                                        # single-device CPU training)
    batched: object = None              # legacy alias: True/False map to
                                        # engine="batched"/"sequential" when
                                        # ``engine`` is unset
    cnn: object = None                  # CNNConfig override (None: paper §V-B)
    model: object = None                # model-zoo ModelConfig (configs.base)
                                        # federated as a final-token
                                        # classifier via core.model_adapter;
                                        # None = the paper CNN (``cnn``)
    chunk_size: int = 0                 # > 0: partition the flat parameter
                                        # axis into leaf-aligned chunks
                                        # (core.param_layout) and stream the
                                        # round's delta pipeline chunk by
                                        # chunk — peak device delta memory is
                                        # O(K * chunk) instead of O(K * N).
                                        # 0 = the flat single-chunk path
    param_layout: object = None         # explicit ParamLayout (wins over
                                        # chunk_size); a single-chunk layout
                                        # with no overrides routes through
                                        # the flat path bit-identically
    layer_keep_frac: object = None      # per-layer sparsity: {leaf-name
                                        # substring: keep_frac | (keep_frac,
                                        # residual_frac) | {"keep_frac": ...,
                                        # "residual_frac": ...}}. Requires
                                        # chunking (a chunk never spans two
                                        # leaves with different overrides)
    seed: int = 0
    latency_jitter: float = 0.05
    traffic: object = None              # fault profile (core.traffic.
                                        # TrafficModel): crash-mid-run,
                                        # upload loss, heavy-tailed latency,
                                        # leave/rejoin churn, late joins.
                                        # None = the happy path (exactly the
                                        # pre-fault behaviour, draw for
                                        # draw). Requires the versioned base
                                        # store (rejoin resync is a ring
                                        # concept)
    round_deadline: object = None       # seconds of simulated time per
                                        # round: when k uploads can't arrive
                                        # in time the server aggregates a
                                        # degraded quorum (>= quorum_floor)
                                        # instead of waiting. None = wait
                                        # for k forever
    quorum_floor: int = 1               # minimum uploads a degraded round
                                        # may aggregate; below it the
                                        # scheduler raises FleetStalledError
    checkpoint_dir: object = None       # crash-consistent fleet checkpoints
                                        # (core.fleet_ckpt): atomic,
                                        # manifest-checksummed snapshots of
                                        # the COMPLETE round-boundary state;
                                        # ``restore()`` resumes bit-exactly.
                                        # Requires base_store="versioned"
    checkpoint_every: int = 0           # rounds between automatic train()
                                        # checkpoints (0 = only explicit
                                        # ``save_checkpoint()`` calls)


@dataclass
class RoundLog:
    round: int
    time: float
    art: float
    participants: list
    stalenesses: dict
    forced: list
    metrics: dict = field(default_factory=dict)
    # fault-layer fields (defaults = the happy path, so fault-free logs are
    # unchanged semantically)
    degraded: bool = False       # aggregated fewer than target_k uploads
    deadline_hit: bool = False   # the round deadline forced the aggregation
    quorum: int = 0              # uploads actually aggregated
    target_k: int = 0            # the participation threshold k
    crashes: int = 0             # crash-mid-run events during the round
    lost: list = field(default_factory=list)      # uploads lost in transit
    departed: list = field(default_factory=list)  # clients that churned out
    rejoined: list = field(default_factory=list)  # clients back online
    resynced: list = field(default_factory=list)  # rejoiners needing the
                                                  # full-model resync (ring
                                                  # version evicted)
    corrupted: list = field(default_factory=list)  # uploads quarantined by
                                                   # the wire-integrity
                                                   # gauntlet (never decoded,
                                                   # never booked)


class FedS3ATrainer:
    def __init__(self, data, config: FedS3AConfig | None = None):
        self.cfg = config or FedS3AConfig()
        self.data = data
        self.M = len(data["clients"])
        self.cnn = self.cfg.cnn if self.cfg.cnn is not None else CNN_CONFIG
        # one adapter owns every model closure (epochs, histograms, predict)
        # — the paper CNN delegates to the exact pseudo_label factories the
        # trainer used to bind directly, a model-zoo ModelConfig routes to
        # the LM-as-classifier adapter
        model = self.cfg.model if self.cfg.model is not None else self.cnn
        self.adapter = make_adapter(
            model, batch_size=self.cfg.batch_size,
            threshold=self.cfg.threshold, l1=self.cfg.l1,
            use_kernel=self.cfg.use_kernels, epochs=self.cfg.epochs)
        self.layout = self._resolve_layout()
        self.chunked = self.layout is not None
        self.engine = self._select_engine()
        if self.cfg.base_store not in BASE_STORES:
            raise ValueError(f"base_store must be one of {BASE_STORES}, "
                             f"got {self.cfg.base_store!r}")
        self.base_store = self.cfg.base_store
        if self.cfg.client_store not in CLIENT_STORES:
            raise ValueError(f"client_store must be one of {CLIENT_STORES}, "
                             f"got {self.cfg.client_store!r}")
        self.paged = self.cfg.client_store == "paged"
        if self.paged and self.base_store != "versioned":
            raise ValueError(
                "client_store='paged' requires base_store='versioned': the "
                "paged layout keeps no per-client base state at all — a "
                "client's base is its ring version, already host-side")
        # legacy attribute: any stacked-flat-state engine counts as batched;
        # the chunked round body is stacked on every engine (the sequential
        # engine's chunked rounds share it — same RNG stream, same math)
        self.batched = self.engine != "sequential" or self.chunked
        self.mesh = client_mesh() if self.engine == "sharded" else None
        self.rng = jax.random.PRNGKey(self.cfg.seed)

        self._stage1_jits = {}      # sharded train+upload(+hist) stages
        self._stage2_jits = {}      # sharded aggregate+distribute stages
        self._groupw_jits = {}      # sharded on-device kmeans+weights

        self.client_epoch = self.adapter.client_epoch
        self.server_epoch = self.adapter.server_epoch
        self.predict = self.adapter.predict
        self.histogram = self.adapter.histogram
        if self.batched:
            self.batched_epoch = self.adapter.batched_epoch
            self.histogram_batch = self.adapter.histogram_batch
            self.server_epoch_flat = self.adapter.server_epoch_flat
            self._build_padded_data()

        sizes = [len(c["x"]) for c in data["clients"]]
        # the paper's measured latency model operates on unscaled Table III
        # sizes; rescale so relative timing matches the paper regardless of
        # the synthetic scale factor
        ref_total = 453004  # Table III basic total
        f = ref_total / max(sum(sizes), 1)
        self.latencies = [paper_latency(int(s * f)) for s in sizes]
        if self.cfg.traffic is not None and self.base_store != "versioned":
            raise ValueError(
                "fault injection (traffic=) requires base_store='versioned':"
                " rejoin re-basing (chain suffix vs full-model resync) is "
                "defined against the reconstruction ring")
        if self.cfg.checkpoint_dir is not None \
                and self.base_store != "versioned":
            raise ValueError(
                "checkpoint_dir requires base_store='versioned': the "
                "checkpoint snapshots the reconstruction ring + chain; the "
                "legacy dense per-client base state has no serialized form")
        self.scheduler = SemiAsyncScheduler(
            self.latencies, C=self.cfg.C, tau=self.cfg.tau,
            jitter=self.cfg.latency_jitter, seed=self.cfg.seed,
            traffic=self.cfg.traffic, deadline=self.cfg.round_deadline,
            quorum_floor=self.cfg.quorum_floor)

        self.comm = SparseComm(self.cfg.sparse_threshold,
                               use_kernel=self.cfg.use_kernels,
                               enabled=self.cfg.sparse_comm,
                               wire_format=self.cfg.wire_format,
                               capacity=self.cfg.wire_capacity,
                               residual_frac=self.cfg.residual_frac,
                               q_dtype=self.cfg.q_dtype,
                               layout=self.layout)
        # the engines branch on the *effective* wire format: disabled
        # sparsification always moves dense payloads. Both CSR formats
        # share the engine plumbing (payload tuples thread through the
        # stages opaquely); ``_csr_wire`` gates the shared paths and
        # ``wire_fmt`` picks the format-specific blend/specs.
        self.wire_fmt = self.comm.wire_format \
            if (self.comm.enabled and self.comm.wire_format in CSR_FORMATS) \
            else "dense"
        self._csr_wire = self.wire_fmt != "dense"
        # payload tuple arity (excl. stored): (vals, idx) vs the quantized
        # (qvals, qoffs, qcnt, scales) quadruple
        self._payload_arity = {"csr": 2, "csr_q": 4}.get(self.wire_fmt, 0)
        if self.chunked:
            if not self._csr_wire:
                raise ValueError(
                    "chunked layouts require a CSR-family wire format with "
                    "sparse_comm enabled: the chunked round streams "
                    "compacted per-chunk payloads")
            if self.base_store != "versioned":
                raise ValueError(
                    "chunked layouts require base_store='versioned': chunk "
                    "bases are gathered from the reconstruction ring one "
                    "chunk at a time")

        self.g_fn = staleness_fn(self.cfg.staleness_function)
        self.participation = np.zeros((0, self.M))
        self._data_window_bytes = 0
        self.logs: list[RoundLog] = []
        # checkpoint machinery: per-log packed-bytes cache (logs are
        # append-only, so each is encoded once per run) and the lazily
        # started persistent writer thread (at most one write in flight)
        self._log_pack: list[bytes] = []
        self._ckpt_thread = None
        self._ckpt_queue = None
        self._ckpt_exc = None

        self._init_models()

    def _resolve_layout(self):
        """Resolve chunk_size / param_layout / layer_keep_frac to the
        trainer's effective :class:`ParamLayout` — or ``None`` for the flat
        path. A resolved layout that ``is_flat`` (one chunk, no overrides)
        also maps to ``None``: the degenerate single-chunk layout IS the
        historical flat path, routed through exactly the same code."""
        cfg = self.cfg
        layout = cfg.param_layout
        if layout is None:
            if cfg.layer_keep_frac and not cfg.chunk_size:
                raise ValueError(
                    "layer_keep_frac requires chunk_size > 0 or an explicit "
                    "param_layout: per-layer sparsity is a property of the "
                    "leaf-aligned chunks")
            if not cfg.chunk_size:
                return None
            layout = ParamLayout.from_template(
                self.adapter.template, cfg.chunk_size,
                overrides=cfg.layer_keep_frac)
        return None if layout.is_flat else layout

    def _select_engine(self):
        """Resolve cfg.engine / legacy cfg.batched to a concrete engine.

        Auto (engine=None, batched=None): the stacked-flat engines win
        wherever round overhead (dispatch, per-message passes, host syncs)
        dominates — always on accelerators, and on CPU for small models;
        compute-bound single-device CPU training keeps the sequential
        reference. With more than one visible device the sharded fleet
        engine takes over from batched — but only when the expected round
        carries at least ``MIN_SHARD_ROWS`` participants per device: tiny
        rounds lose more to the psum/collective overhead than they gain
        from the extra devices (measured at K=8, D=4 on CPU).
        """
        cfg = self.cfg
        engine = cfg.engine
        if cfg.batched is not None:
            warnings.warn(
                "FedS3AConfig(batched=...) is deprecated since the engine "
                "selector landed; use engine='batched' / engine="
                "'sequential' instead", DeprecationWarning, stacklevel=3)
        if engine is None and cfg.batched is not None:
            engine = "batched" if cfg.batched else "sequential"
        if engine is None:
            stacked = (jax.default_backend() != "cpu"
                       or self.adapter.param_count() <= 300_000)
            if not stacked:
                engine = "sequential"
            else:
                D = len(jax.devices())
                # the scheduler admits ceil(C * M) uploads per round
                k = max(int(np.ceil(cfg.C * self.M)), 1)
                engine = "sharded" if (D > 1 and k >= MIN_SHARD_ROWS * D) \
                    else "batched"
        if engine not in ENGINES:
            raise ValueError(f"engine must be one of {ENGINES} or None, "
                             f"got {engine!r}")
        return engine

    def _build_padded_data(self):
        """Pad every client's data to a common batch count once, so the
        batched epoch indexes a fixed (M, nb*B, F) device stack per round.

        Paged client store: the padded stack stays HOST-side and only the
        round's participant rows are placed on device (``_gather_data``).
        Pooled fleet datasets (``data["pool"]`` — M clients aliasing P
        distinct shards) store only the P distinct rows, with ``_data_map``
        sending client i to its shard row; at M=1,000,000 the device (and
        host) data footprint is what a 64-client run pays."""
        B = self.cfg.batch_size
        pool = self.data.get("pool") if self.paged else None
        rows = min(int(pool), self.M) if pool else self.M
        clients = self.data["clients"][:rows]
        F = clients[0]["x"].shape[1]
        nb = max(max((len(c["x"]) + B - 1) // B, 1) for c in clients)
        xs = np.zeros((rows, nb * B, F), np.float32)
        valid = np.zeros((rows, nb * B), np.float32)
        for i, c in enumerate(clients):
            n = len(c["x"])
            xs[i, :n] = c["x"]
            valid[i, :n] = 1.0
        if self.paged:
            self._x_pad_h = xs
            self._valid_pad_h = valid
            self._data_map = np.arange(self.M, dtype=np.int64) % rows
            self._data_row_bytes = int(xs[0].nbytes + valid[0].nbytes)
        else:
            # the sharded engine replicates the fleet's data stack across
            # the clients mesh, so each device gathers its participant
            # rows locally instead of every shard pulling them off device 0
            rep = None if self.mesh is None else \
                NamedSharding(self.mesh, _REP)
            self._x_pad = jax.device_put(xs, rep)
            self._valid_pad = jax.device_put(valid, rep)

    def _gather_data(self, ids):
        """Participants' padded data rows as device arrays. Resident: a
        device-side fancy index of the (M, nb*B, F) stack. Paged: a host
        fancy index + device put of just the window — same values bit for
        bit (pure data movement, no arithmetic)."""
        if self.paged:
            rows = self._data_map[np.asarray(ids, np.int64)]
            xs = jnp.asarray(self._x_pad_h[rows])
            vs = jnp.asarray(self._valid_pad_h[rows])
            self._data_window_bytes = int(xs.nbytes + vs.nbytes)
            return xs, vs
        idx = jnp.asarray(ids)
        return self._x_pad[idx], self._valid_pad[idx]

    def _init_models(self):
        cfg = self.cfg
        self.rng, k = jax.random.split(self.rng)
        params = self.adapter.init(k)
        opt = adam_init(params)
        # Algorithm 1: server warms up on labeled data before distributing
        for e in range(cfg.init_server_epochs):
            self.rng, k = jax.random.split(self.rng)
            params, opt, _ = self.server_epoch(
                params, opt, self.data["server"]["x"], self.data["server"]["y"],
                cfg.lr, k)
        self._template = params       # leaf shapes/dtypes for unflatten
        self.global_params = params
        self.server_opt = opt
        self._global_flat = flatten_tree(params)
        # one zeroed Adam state shared by every distribution (JAX arrays are
        # immutable, so the template is safe to alias across clients)
        self._zero_opt = adam_init(params)
        n = self._global_flat.shape[0]
        if self.base_store == "versioned":
            # staleness-windowed versioned base store, shared by all three
            # engines: ring of tau+2 canonical reconstructions + one chain
            # delta per retained transition + per-client versions. No
            # per-client base state exists anywhere — a client's base is
            # the ring row its base_version indexes.
            self.store = VersionedBaseStore(self._global_flat, self.M,
                                            cfg.tau)
            # late-join clients start offline: parked at version 0 and
            # detached, so their stale version never wedges ring eviction;
            # they re-attach through the rejoin path (chain suffix or full
            # resync) at their first online boundary
            if self.scheduler.initial_offline:
                self.store.detach(self.scheduler.initial_offline)
            self._advance_jit = None
        if self.batched:
            # server Adam state carries over from the warmup, flattened once
            self.server_opt = {"m": flatten_tree(opt["m"]),
                               "v": flatten_tree(opt["v"]), "t": opt["t"]}
            self._key_jits = {}
            self._upload_jits = {}
            self._finalize_jit = None
            if self.base_store == "dense":
                self._base_version = np.zeros(self.M, dtype=int)
                if self.engine == "sharded":
                    # legacy fleet layout: ONE (M, N) base matrix so each
                    # round is a single gather of participant rows and a
                    # single scatter back — no per-row python traffic at
                    # thousand-client scale (but O(M * N) server memory;
                    # the versioned store removes it)
                    self._base_mat = jnp.broadcast_to(
                        self._global_flat, (self.M, n))
                else:
                    # per-client base params as flat (N,) device rows
                    # (initially all aliasing the warmed-up global model —
                    # JAX arrays are immutable); clients always start a
                    # round at their base model, so no per-client trees are
                    # kept at all. Rows rather than one (M, N) array so
                    # distribution replaces references instead of copying
                    # the whole fleet's parameters every round.
                    self._base_rows = [self._global_flat] * self.M
            if cfg.error_feedback and not self.paged:
                if self.chunked:
                    # chunked EF pages: every engine stores per-client
                    # residuals as (M, rcap_total) CSR segments — the
                    # concatenation of the per-chunk capacities, holding
                    # GLOBAL column indices (chunk_encode_body re-localizes
                    # per chunk)
                    rcap = self.comm.residual_capacity_total()
                    self._res_vals = jnp.zeros((self.M, rcap), jnp.float32)
                    self._res_idx = jnp.zeros((self.M, rcap), jnp.int32)
                elif self.engine == "sharded":
                    if self._csr_wire:
                        # sparse residual store: per-client residuals live in
                        # capacity-bounded CSR rows — O(M * rcap) instead of
                        # the dense (M, N) matrix that blocked >100k-client
                        # fleets (rcap*(4+4) bytes/client vs 4N dense). No
                        # per-row count is kept: padding slots hold value 0
                        # at index 0, so the decode needs none.
                        rcap = self.comm.residual_capacity(n)
                        self._res_vals = jnp.zeros((self.M, rcap),
                                                   jnp.float32)
                        self._res_idx = jnp.zeros((self.M, rcap), jnp.int32)
                    else:
                        self._residual_mat = jnp.zeros((self.M, n),
                                                       jnp.float32)
                else:
                    zero = jnp.zeros_like(self._global_flat)
                    self._residual_rows = [zero] * self.M
        elif self.base_store == "dense":
            # per-client state: (params, opt, base_version, base_params)
            self.clients = []
            for i in range(self.M):
                self.clients.append({
                    "params": params,
                    "opt": self._zero_opt,
                    "base_version": 0,
                    "base_params": params,
                })
        else:
            # versioned sequential: a client's params/opt/base are all
            # derived from its ring version; only the EF residual tree is
            # genuinely per-client state
            self.clients = [{} for _ in range(self.M)]
        if self.paged:
            # host-resident per-client pages + a device participant window;
            # the residual page layout follows the effective wire format
            # (CSR rows for the CSR family, dense rows for dense_masked,
            # none with EF off — the store still carries the counters)
            layout = ("csr" if self._csr_wire else "dense") \
                if cfg.error_feedback else "none"
            rcap = self.comm.residual_capacity_total() if self.chunked \
                else self.comm.residual_capacity(n)
            self.cstore = PagedClientStore(
                self.M, n, rcap, layout=layout,
                paged_dir=cfg.paged_dir)
            self.cstore.adopt_versions(self.store.client_version,
                                       self.store.detached)
        self.global_version = 0

    # ------------------------------------------------------------------
    @property
    def global_params(self):
        """Global model as a pytree. The batched engine keeps the canonical
        state flat and materializes the tree lazily (evaluate / sequential
        interop); the sequential engine assigns the tree directly."""
        if self._gp_tree is None:
            self._gp_tree = unflatten_like(self._global_flat, self._template)
        return self._gp_tree

    @global_params.setter
    def global_params(self, tree):
        self._gp_tree = tree

    @property
    def base_versions(self):
        """(M,) per-client base model versions — engine/store-agnostic."""
        if self.base_store == "versioned":
            return self.store.client_version.copy()
        if self.engine == "sequential":
            return np.array([c["base_version"] for c in self.clients])
        return np.asarray(self._base_version).copy()

    # ------------------------------------------------------------------
    def _train_client(self, i, lr):
        """Run client i's local epochs; returns (trained, base) trees."""
        self.rng, k = jax.random.split(self.rng)
        x = self.data["clients"][i]["x"]
        if self.base_store == "versioned":
            # the base is a ring lookup by the client's version — identical
            # for every client at that version, no per-client state read
            base = unflatten_like(self.store.gather([i])[0], self._template)
            params, opt = base, self._zero_opt
        else:
            st = self.clients[i]
            base = st["base_params"]
            params, opt = st["params"], st["opt"]
        for e in range(self.cfg.epochs):
            # epoch e > 0 folds its index into the per-round client key so
            # each epoch draws fresh dropout masks (the batched engine does
            # the identical fold; epoch 0 keeps the raw key so E=1 runs are
            # unchanged). The former reuse of one key replayed the same
            # masks every epoch.
            ke = k if e == 0 else jax.random.fold_in(k, e)
            params, opt, _ = self.client_epoch(params, opt, x, lr, ke)
        if self.base_store == "dense":
            st["params"], st["opt"] = params, opt
        return params, base

    def _distribute(self, i):
        """Send the current global model to client i (sparse diff against
        its dense per-client base; the versioned store broadcasts chain
        payloads instead — see ``_advance_versioned``)."""
        st = self.clients[i]
        if st["base_version"] == self.global_version:
            # no-op diff: nothing to transmit. The client was already
            # distributed at this exact version, so its params equal
            # base_params and its opt is already the zeroed template.
            return
        delta, _ = self.comm.encode(self.global_params, st["base_params"])
        # disabled sparsification moves the dense model: the copy is exact
        # (base + (g - base) re-rounds; g itself does not)
        newp = self.comm.apply(st["base_params"], delta) \
            if self.comm.enabled else self.global_params
        st["params"] = newp
        st["base_params"] = newp
        st["base_version"] = self.global_version
        st["opt"] = self._zero_opt

    # ------------------------------------------------------------------
    # versioned base store plumbing (all engines)
    def _advance_encode_body(self):
        """Traced body shared by every engine's finalize stage: ONE chain-
        transition encode of the new global model against the previous
        canonical reconstruction R_r. Returns (R_{r+1}, payload) where the
        payload tuple is the wire tuple + stored count under the CSR family
        — (values, indices, stored) for f32 csr, (qvals, qoffs, qcnt,
        scales, stored) for csr_q, where the reconstruction folds in the
        DEQUANTIZED decode so the ring stays the canonical f32 model every
        receiver of the quantized chain rebuilds — (nnz,) under
        dense_masked, and () with sparsification disabled — there R_{r+1}
        is the new global model bit-for-bit, which is what makes the
        versioned store reproduce the dense store exactly."""
        if self._csr_wire:
            core = self.comm.csr_core(False)

            def body(new_flat, prev):
                payload, stored, decoded = core(new_flat[None], prev[None])
                return prev + decoded[0], \
                    tuple(p[0] for p in payload) + (stored[0],)

            return body
        core = self.comm.batch_core(False) if self.comm.enabled else None

        def body(new_flat, prev):
            if core is None:
                return new_flat, ()
            masked, nnz = core(new_flat[None], prev[None])
            return prev + masked[0], (nnz[0],)

        return body

    def _chain_entry(self, payload):
        """Payload tuple from ``_advance_encode_body`` -> the store's chain
        record ({"stored": count[, "vals", "idx"]}; csr_q keeps the chain
        in its quantized wire form — what actually broadcasts — so server
        chain memory shrinks with the payloads)."""
        if self.wire_fmt == "csr":
            return {"vals": payload[0], "idx": payload[1],
                    "stored": payload[2]}
        if self.wire_fmt == "csr_q":
            return {"qvals": payload[0], "qoffs": payload[1],
                    "qcnt": payload[2], "scale": payload[3],
                    "stored": payload[4]}
        if self.comm.enabled:
            return {"stored": payload[0]}
        return {"stored": self._global_flat.shape[0]}

    def _distribution_plan(self, part_ids, ev):
        """Who restarts from the new global model at this boundary, and how.

        Returns ``(targets, resync)``: ``targets`` receive the chain-delta
        broadcast (or a per-target encode under the dense store) — online
        participants, tau-forced clients, lost-upload clients (their run
        finished but the payload evaporated, so they rebase like any other
        listener) and in-window rejoiners; ``resync`` are rejoiners whose
        parked version was evicted from the ring while they were away and
        need the explicit full-model payload instead. Participants that
        churned out after uploading stay aggregated but get nothing — there
        is nobody to send to. Fault-free this reduces exactly to the old
        ``participants | forced`` set. ``ev.resynced`` is filled as a side
        effect so the round log records the resync path firing.
        """
        online = self.scheduler.state.online
        chain, resync = [], []
        if ev.rejoined:
            chain, resync = self.store.split_rejoined(
                ev.rejoined, self.global_version)
        targets = sorted(set(i for i in part_ids if online[i])
                         | set(ev.forced) | set(ev.lost)
                         | set(ev.corrupted) | set(chain))
        ev.resynced = resync
        return targets, resync

    def _retired_ids(self, ev):
        """Clients whose server-side EF residual must be retired at this
        boundary: tau-forced restarts (the pre-fault behaviour), lost
        uploads and rejoiners (they restart from the new global model —
        fresh base, fresh residual) and departures (their trajectory is
        gone; keeping mass accumulated against an abandoned base would be
        re-offered as drift on rejoin). Retiring happens in the
        distribution phase — AFTER the upload encode — because a departed
        participant's encode this round legitimately consumed its
        then-current residual. Quarantined (corrupt) uploads retire
        exactly like lost ones: the payload was produced (consuming the
        residual) but never aggregated."""
        return sorted(set(ev.forced) | set(ev.lost) | set(ev.corrupted)
                      | set(ev.departed) | set(ev.rejoined))

    def _advance_versioned(self, recon, payload, ev, part_ids):
        """Install the new reconstruction + chain delta, detach departures,
        book the chain-delta broadcast (and any full-model resyncs), bump
        the targets, retire dead residuals."""
        targets, resync = self._distribution_plan(part_ids, ev)
        if ev.departed:
            # departures park (version kept for a possible in-window
            # rejoin) but stop constraining ring eviction — detach BEFORE
            # advance so an offline straggler can't wedge the window
            self.store.detach(ev.departed)
        self.store.advance(recon, self._chain_entry(payload),
                           self.global_version)
        self.store.account_distribution(self.comm, targets)
        if resync:
            self.store.resync(self.comm, resync)
        self._reset_forced_residuals(self._retired_ids(ev))

    def _reset_forced_residuals(self, forced):
        """A deprecated client's forced restart discards its in-flight
        trajectory AND its error-feedback residual — the residual was
        accumulated against a base the client no longer holds (see the
        SparseComm docstring; pinned in tests/test_error_feedback.py).
        Under faults the same retirement applies to lost-upload clients,
        departures and rejoiners (see ``_retired_ids``)."""
        if not self.cfg.error_feedback or not forced:
            return
        ids = sorted(set(forced))
        if self.paged:
            # page invalidation, queued AFTER this round's residual
            # writeback so the scatter-then-retire order matches the
            # resident engines' sequence
            self.cstore.retire(ids)
            return
        if self.chunked or self.engine == "sharded":
            fidx = jnp.asarray(ids)
            if self._csr_wire:
                shape = (len(ids), self._res_vals.shape[1])
                self._res_vals = _scatter_rows(
                    self._res_vals, fidx, jnp.zeros(shape, jnp.float32))
                self._res_idx = _scatter_rows(
                    self._res_idx, fidx, jnp.zeros(shape, jnp.int32))
            else:
                self._residual_mat = _scatter_rows(
                    self._residual_mat, fidx,
                    jnp.zeros((len(ids), self._residual_mat.shape[1]),
                              jnp.float32))
        elif self.engine == "batched":
            zero = jnp.zeros_like(self._global_flat)
            for i in ids:
                self._residual_rows[i] = zero
        else:
            for i in ids:
                self.clients[i].pop("residual", None)

    def _quarantine_uploads(self, ev):
        """Run every corrupt-fated upload through the wire-integrity
        gauntlet at the trust boundary. The scheduler decided WHICH runs
        the traffic model damaged (``ev.corrupted``); here the damage is
        materialized deterministically — a nominal payload malformed by
        one class from :data:`MALFORM_KINDS`, picked by a client/round
        hash so the trace is engine-independent and replays bit-exactly —
        and :meth:`SparseComm.validate_payload` must reject it. Rejection
        IS the quarantine: the payload is never decoded, never aggregated
        and never booked (the same no-delivery path lost uploads take; EF
        retirement happens in ``_retired_ids``). A malformed payload that
        somehow passed validation would silently poison the aggregate, so
        that raises outright. Host-only and outside every jitted round
        body — rounds without corruption pay nothing."""
        if not ev.corrupted or not self._csr_wire:
            # dense-family messages carry no payload arrays to damage;
            # the scheduler's no-delivery quarantine already applied
            return
        n = int(self._global_flat.shape[0])
        cap = 4                       # any capacity: validation infers it
        stored = np.full(1, cap, np.int64)
        if self.wire_fmt == "csr_q":
            vdt = np.int8 if self.comm.q_dtype == "int8" else np.float16
            blocks = np.zeros((1, (n + Q_BLOCK - 1) // Q_BLOCK), np.int64)
            blocks[0, 0] = cap
            nominal = {"nnz": stored, "total": n, "rows": 1,
                       "values": np.zeros((1, cap), vdt),
                       "indices": np.zeros((1, cap), np.int16),
                       "blocks": blocks,
                       "scales": np.ones(1, np.float32)}
        else:
            nominal = {"nnz": stored, "total": n, "rows": 1,
                       "values": np.zeros((1, cap), np.float32),
                       "indices": np.zeros((1, cap), np.int32)}
        for c in ev.corrupted:
            kind = MALFORM_KINDS[
                (c * 2654435761 + self.global_version) % len(MALFORM_KINDS)]
            bad = self.comm.malform_stats(nominal, kind)
            try:
                self.comm.validate_payload(bad)
            except WireIntegrityError:
                continue              # quarantined
            raise RuntimeError(
                f"malformed upload (client {c}, kind {kind!r}) passed "
                f"wire-integrity validation — quarantine is broken")

    # ------------------------------------------------------------------
    def run_round(self):
        if self.chunked:
            return self._run_round_chunked()
        if self.engine == "sharded":
            return self._run_round_sharded()
        if self.engine == "batched":
            return self._run_round_batched()
        return self._run_round_sequential()

    def _round_prologue(self):
        """Advance the scheduler one boundary. Returns ``(prev_time, ev,
        lrs)`` with ``ev`` the scheduler's RoundResult — participants /
        staleness / forced restarts plus the fault-layer consequences
        (lost uploads, churn, degradation) every engine threads through
        the same distribution plan."""
        prev_time = self.scheduler.state.time
        if self.paged:
            # swap point of the page double-buffer: the previous round's
            # queued residual writebacks / retirements have overlapped the
            # inter-round host work; drain them before this round gathers
            self.cstore.flush()
        ev = self.scheduler.next_round()
        self._quarantine_uploads(ev)
        lrs = adaptive_learning_rates(
            self.participation, base_lr=self.cfg.lr,
            round_weight=self.cfg.round_weight_function,
            adaptive=self.cfg.adaptive_lr)
        return prev_time, ev, lrs

    def _round_epilogue(self, prev_time, ev):
        part_ids = [run.client for run in ev.participants]
        row = np.zeros((1, self.M))
        row[0, part_ids] = 1
        self.participation = np.concatenate([self.participation, row])
        if self.paged:
            self.cstore.record_participation(part_ids,
                                             self.global_version - 1)
        log = RoundLog(round=self.global_version - 1, time=ev.time,
                       art=ev.time - prev_time, participants=part_ids,
                       stalenesses={i: ev.stale[i] for i in part_ids},
                       forced=ev.forced, degraded=ev.degraded,
                       deadline_hit=ev.deadline_hit, quorum=ev.quorum,
                       target_k=ev.target_k, crashes=ev.crashes,
                       lost=ev.lost, departed=ev.departed,
                       rejoined=ev.rejoined, resynced=ev.resynced,
                       corrupted=ev.corrupted)
        self.logs.append(log)
        return log

    def _server_step(self):
        """Server supervised epoch on the current global model (Eq. 6)."""
        self.rng, k = jax.random.split(self.rng)
        sp, self.server_opt, _ = self.server_epoch(
            self.global_params, self.server_opt,
            self.data["server"]["x"], self.data["server"]["y"],
            self.cfg.lr, k)
        return sp

    def _run_round_sequential(self):
        cfg = self.cfg
        prev_time, ev, lrs = self._round_prologue()
        participants, stale, forced, t = ev
        r = self.global_version

        # participating clients train and upload sparse diffs
        client_models, sizes, stalenesses, hists = [], [], [], []
        for run in participants:
            i = run.client
            newp, base = self._train_client(i, float(lrs[i]))
            if cfg.error_feedback and self.paged:
                if self._csr_wire:
                    # the residual is a CSR page: gather it, fold its
                    # decode into the encode, queue the new page back —
                    # identical math to the resident tree path (the page
                    # decodes to exactly the dense residual, and the
                    # delta+residual add is elementwise in flat space)
                    rv, rx = self.cstore.gather_csr([i])
                    delta, _, (nrv, nrx) = self.comm.encode_paged(
                        newp, base, rv[0], rx[0])
                    self.cstore.scatter_csr([i], nrv[None], nrx[None])
                else:
                    row = self.cstore.gather_dense([i])[0]
                    res = unflatten_like(row, newp)
                    delta, _, res = self.comm.encode(newp, base,
                                                     residual=res)
                    self.cstore.scatter_dense([i],
                                              flatten_tree(res)[None])
            elif cfg.error_feedback:
                res = self.clients[i].get("residual")
                if res is None:
                    res = jax.tree.map(jnp.zeros_like, newp)
                delta, _, res = self.comm.encode(newp, base, residual=res)
                self.clients[i]["residual"] = res
            else:
                delta, _ = self.comm.encode(newp, base)
            uploaded = self.comm.apply(base, delta)
            client_models.append(uploaded)
            sizes.append(len(self.data["clients"][i]["x"]))
            stalenesses.append(stale[i])
            hists.append(np.asarray(
                self.histogram(uploaded, jnp.asarray(self.data["clients"][i]["x"]))))

        sp = self._server_step()

        groups = None
        if cfg.group_based and len(client_models) > 1:
            groups = group_clients(np.stack(hists),
                                   min(cfg.num_groups, len(client_models)),
                                   seed=cfg.seed)

        fw = supervised_weight(r, C=cfg.C, M=self.M,
                               mode=cfg.supervised_weight_mode)
        self.global_params = agg.aggregate(
            sp, client_models, data_sizes=sizes, stalenesses=stalenesses,
            g_fn=self.g_fn, f_weight=fw, groups=groups,
            use_kernel=cfg.use_kernels)
        self.global_version += 1

        # distribution: latest + deprecated clients get the new model
        part_ids = [run.client for run in participants]
        if self.base_store == "versioned":
            # one chain-transition encode + chain-delta broadcast (each
            # transition payload once per round) instead of one encode per
            # target
            if self._advance_jit is None:
                self._advance_jit = jax.jit(self._advance_encode_body())
            new_flat = flatten_tree(self.global_params)
            recon, payload = self._advance_jit(new_flat, self.store.latest())
            self._advance_versioned(recon, payload, ev, part_ids)
        else:
            targets, _ = self._distribution_plan(part_ids, ev)
            for i in targets:
                self._distribute(i)
            self._reset_forced_residuals(forced)

        return self._round_epilogue(prev_time, ev)

    # ------------------------------------------------------------------
    # jitted round stages (built lazily; retrace per participant count)
    def _split_keys(self, K):
        """Chained per-participant RNG splits in one jitted scan — the same
        key sequence as the sequential path's repeated jax.random.split."""
        fn = self._key_jits.get(K)
        if fn is None:
            @jax.jit
            def fn(rng):
                def s(c, _):
                    c, k = jax.random.split(c)
                    return c, k
                return jax.lax.scan(s, rng, None, length=K)
            self._key_jits[K] = fn
        self.rng, keys = fn(self.rng)
        return keys

    def _encode_upload_body(self, with_residual, with_hist):
        """Traced body shared by the batched jit and the sharded shard_map:
        encode + upload + histograms on a (K, N) stack (global for batched,
        the local shard for sharded — the encode is per-row, so the same
        body serves both).

        CSR family ("csr" / "csr_q"): compacts the deltas into the real
        wire payload rows, reconstructs the uploaded models from the
        payload (so what feeds histograms/aggregation is exactly what
        crossed the wire — csr_q reconstructs from the DEQUANTIZED decode),
        and — under EF — spills sub-threshold mass, capacity overflow and
        (csr_q) quantization error into the truncated residual. Returns
        (payload_tuple, stored, hists|None, res_payload|None,
        res_dense|None) where the payload tuple has ``self._payload_arity``
        components.

        Legacy dense-masked format returns (uploaded, nnz, hists|None,
        new_res|None) as before."""
        hist = self.histogram_batch
        if self._csr_wire:
            core = self.comm.csr_core(with_residual)

            def body(trained, base, xs, vs, residual=None):
                if with_residual:
                    payload, stored, decoded, res_payload, res_dense = \
                        core(trained, base, residual)
                else:
                    payload, stored, decoded = core(trained, base)
                    res_payload = res_dense = None
                hists = hist(base + decoded, xs, vs) if with_hist else None
                return payload, stored, hists, res_payload, res_dense

            return body
        core = self.comm.batch_core(with_residual) if self.comm.enabled \
            else None

        def body(trained, base, xs, vs, residual=None):
            if core is None:
                delta = trained - base
                if with_residual:
                    delta = delta + residual
                masked, nnz = delta, jnp.full((trained.shape[0],),
                                              trained.shape[1])
                new_res = jnp.zeros_like(delta) if with_residual else None
            elif with_residual:
                masked, nnz, new_res = core(trained, base, residual)
            else:
                masked, nnz = core(trained, base)
                new_res = None
            uploaded = base + masked
            hists = hist(uploaded, xs, vs) if with_hist else None
            return uploaded, nnz, hists, new_res

        return body

    def _distribute_encode_body(self):
        """Traced body shared by the batched jit and the sharded shard_map:
        sparse-encode the new global model against the (T, N) distribution
        target stack (per-row, so global and shard-local calls agree).
        Returns (new_base, nnz) — under the CSR format the new base is the
        decode of the actual compacted payload and ``nnz`` is the stored
        (on-wire) count."""
        if self._csr_wire:
            core = self.comm.csr_core(False)

            def body(new_flat, dist_base):
                g = jnp.broadcast_to(new_flat, dist_base.shape)
                _payload, stored, decoded = core(g, dist_base)
                return dist_base + decoded, stored

            return body
        core = self.comm.batch_core(False) if self.comm.enabled else None

        def body(new_flat, dist_base):
            g = jnp.broadcast_to(new_flat, dist_base.shape)
            if core is None:
                # disabled sparsification moves the dense model: the new
                # base is an exact copy (dist_base + (g - dist_base)
                # re-rounds; g itself does not)
                return g, jnp.full((dist_base.shape[0],), new_flat.shape[0])
            masked, nnz = core(g, dist_base)
            return dist_base + masked, nnz

        return body

    def _upload_fn(self, with_residual, with_hist):
        """encode (threshold/mask/count) + upload + histograms, one jit."""
        key = (with_residual, with_hist)
        fn = self._upload_jits.get(key)
        if fn is None:
            fn = jax.jit(self._encode_upload_body(with_residual, with_hist))
            self._upload_jits[key] = fn
        return fn

    def _upload_fn_paged(self, with_hist):
        """Paged-store batched upload under the CSR family: the gathered
        (K, rcap) residual window decodes to dense INSIDE the jit — fused
        with the encode, the dense (K, N) residual never crosses a stage
        boundary — and the new residual comes back as CSR pages for the
        writeback queue. The decode is a pure scatter of exact f32 values,
        so the result matches the resident dense-row path bit for bit."""
        key = ("paged", with_hist)
        fn = self._upload_jits.get(key)
        if fn is None:
            body = self._encode_upload_body(True, with_hist)
            n = self._global_flat.shape[0]

            @jax.jit
            def fn(trained, base, xs, vs, rvals, ridx):
                residual = csr_decode(rvals, ridx, n)
                payload, stored, hists, res_payload, _ = body(
                    trained, base, xs, vs, residual)
                return payload, stored, hists, res_payload[:2]

            self._upload_jits[key] = fn
        return fn

    def _finalize_fn(self):
        """server-flatten + weighted aggregation + distribute encode, one
        jit. Under the CSR format the aggregation consumes the upload
        payloads directly: the scatter-add decode is fused into the
        weighted client sum (``agg.blend_flat_csr``), so the dense uploaded
        stack never crosses the stage boundary.

        Versioned base store: the distribute half is the single
        chain-transition encode against R_r (no per-target stack — the jit
        never retraces on the round's target count, only on K). The dense
        store keeps the per-target encode over the (T, N) base stack
        (retraces per (participants, targets) shape pair)."""
        if self._finalize_jit is not None:
            return self._finalize_jit
        use_kernel = self.cfg.use_kernels
        versioned = self.base_store == "versioned"
        distribute = self._advance_encode_body() if versioned \
            else self._distribute_encode_body()

        if self._csr_wire:
            if self.wire_fmt == "csr_q":
                def blend(s, b, p, w, fw):
                    return agg.blend_flat_csr_q(s, b, *p, w, fw,
                                                use_kernel=use_kernel)
            else:
                def blend(s, b, p, w, fw):
                    return agg.blend_flat_csr(s, b, p[0], p[1], w, fw,
                                              use_kernel=use_kernel)

            @jax.jit
            def fn(server_flat, base_flat, payload, w, fw, dist_base):
                new_flat = blend(server_flat, base_flat, payload, w, fw)
                if versioned:
                    recon, payload = distribute(new_flat, dist_base)
                    return (new_flat, recon) + payload
                new_base, nnz = distribute(new_flat, dist_base)
                return new_flat, new_base, nnz
        else:
            @jax.jit
            def fn(server_flat, uploaded, w, fw, dist_base):
                if use_kernel:
                    from repro.kernels import ops as kops
                    unsup = kops.staleness_agg(uploaded, w)
                else:
                    unsup = jnp.einsum("k,kn->n", w, uploaded)
                new_flat = fw * server_flat + (1.0 - fw) * unsup
                if versioned:
                    recon, payload = distribute(new_flat, dist_base)
                    return (new_flat, recon) + payload
                new_base, nnz = distribute(new_flat, dist_base)
                return new_flat, new_base, nnz

        self._finalize_jit = fn
        return fn

    def _run_round_batched(self):
        """All participants per jitted stage: one training call (client axis
        inside), one upload encode+histogram call, one aggregate+distribute
        call. Zero per-message host syncs; one host transfer per round (the
        pseudo-label histograms feeding k-means grouping)."""
        cfg = self.cfg
        prev_time, ev, lrs = self._round_prologue()
        participants, stale, forced, t = ev
        r = self.global_version
        part_ids = [run.client for run in participants]
        K = len(part_ids)

        # same RNG stream as the sequential path: one split per participant
        # in arrival order, then the server's split
        keys = self._split_keys(K)

        # every client is padded to the fleet-wide max batch count, so the
        # epoch compiles exactly once; all-padding batches are skipped by
        # the in-graph cond, so each client still pays for exactly its own
        # number of optimizer steps
        xs, vs = self._gather_data(part_ids)
        if self.base_store == "versioned":
            # version-indexed base gather from the (tau+2, N) ring — no
            # per-client rows exist
            base_flat = self.store.gather(part_ids)
        else:
            base_flat = jnp.stack([self._base_rows[i] for i in part_ids])

        trained_flat, _ = self.batched_epoch(base_flat, xs, vs,
                                             lrs[part_ids], keys)

        with_hist = cfg.group_based and K > 1
        n = trained_flat.shape[1]
        if self._csr_wire:
            # the upload stage emits the compacted payload; the dense
            # uploaded stack never leaves the jit (histograms consume it
            # in-graph, aggregation takes base + payload)
            if cfg.error_feedback and self.paged:
                # residual pages in, residual pages out: the participant
                # window decodes to dense inside the jit (fused with the
                # encode) and the new CSR pages join the writeback queue
                rv, rx = self.cstore.gather_csr(part_ids)
                payload, nnz, hists_dev, (nrv, nrx) = self._upload_fn_paged(
                    with_hist)(trained_flat, base_flat, xs, vs, rv, rx)
                self.cstore.scatter_csr(part_ids, nrv, nrx)
            elif cfg.error_feedback:
                residual = jnp.stack(
                    [self._residual_rows[i] for i in part_ids])
                payload, nnz, hists_dev, _, res_dense = self._upload_fn(
                    True, with_hist)(trained_flat, base_flat, xs, vs,
                                     residual)
                for row, i in enumerate(part_ids):
                    self._residual_rows[i] = res_dense[row]
            else:
                payload, nnz, hists_dev, _, _ = self._upload_fn(
                    False, with_hist)(trained_flat, base_flat, xs, vs)
            self.comm.account_batch_csr(nnz, n, K)
        elif cfg.error_feedback and self.paged:
            residual = self.cstore.gather_dense(part_ids)
            uploaded_flat, nnz, hists_dev, residual = self._upload_fn(
                True, with_hist)(trained_flat, base_flat, xs, vs, residual)
            self.cstore.scatter_dense(part_ids, residual)
            self.comm.account_batch(nnz, n, K)
        elif cfg.error_feedback:
            residual = jnp.stack([self._residual_rows[i] for i in part_ids])
            uploaded_flat, nnz, hists_dev, residual = self._upload_fn(
                True, with_hist)(trained_flat, base_flat, xs, vs, residual)
            for row, i in enumerate(part_ids):
                self._residual_rows[i] = residual[row]
            self.comm.account_batch(nnz, n, K)
        else:
            uploaded_flat, nnz, hists_dev, _ = self._upload_fn(
                False, with_hist)(trained_flat, base_flat, xs, vs)
            self.comm.account_batch(nnz, n, K)

        # server supervised epoch on the current global model (Eq. 6), in
        # flat space; the RNG split order matches the sequential path
        self.rng, k = jax.random.split(self.rng)
        sp_flat, self.server_opt, _ = self.server_epoch_flat(
            self._global_flat, self.server_opt,
            self.data["server"]["x"], self.data["server"]["y"], cfg.lr, k)

        groups = None
        if with_hist:
            hists = np.asarray(hists_dev)
            groups = group_clients(hists, min(cfg.num_groups, K),
                                   seed=cfg.seed)

        fw = supervised_weight(r, C=cfg.C, M=self.M,
                               mode=cfg.supervised_weight_mode)
        w = agg.combine_weights(
            [len(self.data["clients"][i]["x"]) for i in part_ids],
            [stale[i] for i in part_ids], self.g_fn, groups)

        self.global_version += 1
        # distribution: latest + deprecated clients get the new model. All
        # participants are stale by construction (their base predates the
        # version bump), so fault-free the target set is never empty.
        if self.base_store == "versioned":
            # chain-delta broadcast: the finalize jit encodes ONE chain
            # transition against R_r; the store books the suffix from the
            # stalest target's version, each transition payload once
            prev = self.store.latest()
            if self._csr_wire:
                out = self._finalize_fn()(
                    sp_flat, base_flat, payload,
                    jnp.asarray(w, jnp.float32), jnp.float32(fw), prev)
            else:
                out = self._finalize_fn()(
                    sp_flat, uploaded_flat, jnp.asarray(w, jnp.float32),
                    jnp.float32(fw), prev)
            new_flat, recon, chain = out[0], out[1], out[2:]
            self._advance_versioned(recon, chain, ev, part_ids)
        else:
            targets, _ = self._distribution_plan(part_ids, ev)
            dist_base = jnp.stack([self._base_rows[i] for i in targets])
            if self._csr_wire:
                new_flat, new_base, nnz_d = self._finalize_fn()(
                    sp_flat, base_flat, payload,
                    jnp.asarray(w, jnp.float32), jnp.float32(fw), dist_base)
                self.comm.account_batch_csr(nnz_d, n, len(targets))
            else:
                new_flat, new_base, nnz_d = self._finalize_fn()(
                    sp_flat, uploaded_flat, jnp.asarray(w, jnp.float32),
                    jnp.float32(fw), dist_base)
                self.comm.account_batch(nnz_d, n, len(targets))
            for row, i in enumerate(targets):
                self._base_rows[i] = new_base[row]
            self._base_version[targets] = self.global_version
            self._reset_forced_residuals(forced)
        self._global_flat = new_flat
        self._gp_tree = None      # materialized lazily on demand

        return self._round_epilogue(prev_time, ev)

    # ------------------------------------------------------------------
    # chunked round body (core.param_layout): all engines stream the delta
    # pipeline one chunk at a time
    def _chunk_upload_fn(self, with_hist):
        """Upload-encode over the chunked parameter axis, one jit: the
        per-chunk encode loop is unrolled inside, so XLA's buffer liveness
        keeps one chunk's delta/decode temporaries (O(K * max_chunk)) live
        at a time. The base is a ring-gather CLOSURE ``(s, e) ->
        ring[:, s:e][slots]`` — no (K, N) base copy materializes for the
        encode. Returns (flat payload tuple [arity * num_chunks entries],
        stored_total (K,), hists | None, new residual pages | None)."""
        key = ("chunk", self.cfg.error_feedback, with_hist)
        fn = self._upload_jits.get(key)
        if fn is not None:
            return fn
        ef = self.cfg.error_feedback
        body = self.comm.chunk_encode_body(ef)
        plan = self.comm.chunk_plan()
        hist = self.histogram_batch

        def encode(trained, ring, slots, xs, vs, rvals, ridx):
            def base(s, e):
                return ring[:, s:e][slots]
            if ef:
                payloads, stored, decoded, (nrv, nri) = body(
                    trained, base, rvals, ridx)
            else:
                payloads, stored, decoded = body(trained, base)
                nrv = nri = None
            stored_total = stored[0]
            for st in stored[1:]:
                stored_total = stored_total + st
            hists = None
            if with_hist:
                # histograms need the full uploaded model for the forward
                # pass; build it by scattering each chunk's decode into the
                # gathered base (one (K, N) buffer, same as training held)
                up = ring[slots]
                for p, dec in zip(plan, decoded):
                    up = up.at[:, p["s"]:p["e"]].add(dec)
                hists = hist(up, xs, vs)
            flat_payload = tuple(x for pay in payloads for x in pay)
            return flat_payload, stored_total, hists, nrv, nri

        if ef:
            @jax.jit
            def fn(trained, ring, slots, xs, vs, rvals, ridx):
                return encode(trained, ring, slots, xs, vs, rvals, ridx)
        else:
            @jax.jit
            def fn(trained, ring, slots, xs, vs):
                return encode(trained, ring, slots, xs, vs, None, None)

        self._upload_jits[key] = fn
        return fn

    def _chunk_finalize_fn(self):
        """Chunked server blend + ring advance, one jit: each chunk's
        weighted client sum consumes that chunk's compacted payload against
        a per-chunk ring-gathered base (``agg.blend_flat_csr`` /
        ``_csr_q`` on (K, nc) slices — chunk-local indices decode in
        place), and the chain-transition encode streams the same chunks.
        The (K, N) uploaded stack of the flat finalize never exists."""
        if self._finalize_jit is not None:
            return self._finalize_jit
        plan = self.comm.chunk_plan()
        arity = self._payload_arity
        advance = self.comm.chunk_advance_body()
        quantized = self.wire_fmt == "csr_q"

        @jax.jit
        def fn(server_flat, ring, slots, payload, w, fw, prev):
            new = []
            for ci, p in enumerate(plan):
                s, e = p["s"], p["e"]
                pc = payload[ci * arity:(ci + 1) * arity]
                base_c = ring[:, s:e][slots]
                if quantized:
                    new_c = agg.blend_flat_csr_q(
                        server_flat[s:e], base_c, *pc, w, fw,
                        use_kernel=False)
                else:
                    new_c = agg.blend_flat_csr(
                        server_flat[s:e], base_c, pc[0], pc[1], w, fw,
                        use_kernel=False)
                new.append(new_c)
            new_flat = jnp.concatenate(new)
            recon, chain = advance(new_flat, prev)
            return (new_flat, recon) + chain

        self._finalize_jit = fn
        return fn

    def _train_sharded_chunked(self):
        """Train-only shard_map stage for chunked sharded rounds: each
        device trains its row shard from the replicated ring (client-local,
        no collectives). Encode/finalize then stream chunks unsharded —
        the chunked pipeline's O(K * chunk) liveness is the point; the
        training stage keeps the multi-device speedup."""
        fn = self._stage1_jits.get("chunk_train")
        if fn is not None:
            return fn
        mesh = self.mesh
        epoch = self.batched_epoch

        def shard_fn(ring, slots, xs, vs, lrs, keys):
            base = ring[slots]
            trained, _ = epoch(base, xs, vs, lrs, keys)
            return trained

        fn = jax.jit(shard_map(
            shard_fn, mesh=mesh,
            in_specs=(RING_SPEC, RING_SLOT_SPEC, _ROW3, _ROW2, _ROW, _ROW2),
            out_specs=_ROW2, check_vma=False))
        self._stage1_jits["chunk_train"] = fn
        return fn

    def _run_round_chunked(self):
        """One round streamed over the chunked parameter axis, shared by
        all three engines (the sequential engine runs the stacked epoch —
        same RNG stream, same per-client math; the sharded engine shards
        the training stage only). Encode, blend and ring advance all
        iterate chunks, so no stage materializes a (K, N) delta."""
        cfg = self.cfg
        prev_time, ev, lrs = self._round_prologue()
        participants, stale, forced, t = ev
        r = self.global_version
        part_ids = [run.client for run in participants]
        K = len(part_ids)
        n = self._global_flat.shape[0]

        # same RNG stream as the flat engines: one split per participant
        # in arrival order, then the server's split
        keys = self._split_keys(K)

        if self.engine == "sharded":
            D = self.mesh.devices.size
            Kp = padded_rows(K, D)
            pad = Kp - K
            pad_ids = part_ids + part_ids[:1] * pad
            xs, vs = self._gather_data(pad_ids)
            if pad:
                keys_p = jnp.concatenate(
                    [keys, jnp.zeros((pad,) + keys.shape[1:], keys.dtype)])
                # pad rows see no valid samples -> pure no-op epochs
                vs = vs * jnp.asarray(
                    np.concatenate([np.ones(K, np.float32),
                                    np.zeros(pad, np.float32)]))[:, None]
            else:
                keys_p = keys
            lrs_p = jnp.asarray(
                np.concatenate([lrs[part_ids], np.zeros(pad)]), jnp.float32)
            slots_p = self.store.slots_for(pad_ids)
            trained = self._train_sharded_chunked()(
                self.store.ring, slots_p, xs, vs, lrs_p, keys_p)
            trained = trained[:K]
            xs, vs = xs[:K], vs[:K]
            slots = slots_p[:K]
        else:
            xs, vs = self._gather_data(part_ids)
            slots = self.store.slots_for(part_ids)
            base_flat = self.store.gather(part_ids)
            trained, _ = self.batched_epoch(base_flat, xs, vs,
                                            lrs[part_ids], keys)

        with_hist = cfg.group_based and K > 1
        upload = self._chunk_upload_fn(with_hist)
        if cfg.error_feedback:
            if self.paged:
                rv, rx = self.cstore.gather_csr(part_ids)
            else:
                idxK = jnp.asarray(part_ids)
                rv = _gather_rows(self._res_vals, idxK)
                rx = _gather_rows(self._res_idx, idxK)
            payload, stored_total, hists_dev, nrv, nri = upload(
                trained, self.store.ring, slots, xs, vs, rv, rx)
            if self.paged:
                self.cstore.scatter_csr(part_ids, nrv, nri)
            else:
                self._res_vals = _scatter_rows(self._res_vals, idxK, nrv)
                self._res_idx = _scatter_rows(self._res_idx, idxK, nri)
        else:
            payload, stored_total, hists_dev, _, _ = upload(
                trained, self.store.ring, slots, xs, vs)
        # one ledger entry for the whole chunked batch; the layout-aware
        # framing (per-chunk row_ptr, scales, block tables) is booked by
        # the comm channel's chunk-aware accounting
        self.comm.account_batch_csr(stored_total, n, K)

        # server supervised epoch on the current global model (Eq. 6), in
        # flat space; the RNG split order matches the flat engines
        self.rng, k = jax.random.split(self.rng)
        sp_flat, self.server_opt, _ = self.server_epoch_flat(
            self._global_flat, self.server_opt,
            self.data["server"]["x"], self.data["server"]["y"], cfg.lr, k)

        groups = None
        if with_hist:
            hists = np.asarray(hists_dev)
            groups = group_clients(hists, min(cfg.num_groups, K),
                                   seed=cfg.seed)

        fw = supervised_weight(r, C=cfg.C, M=self.M,
                               mode=cfg.supervised_weight_mode)
        w = agg.combine_weights(
            [len(self.data["clients"][i]["x"]) for i in part_ids],
            [stale[i] for i in part_ids], self.g_fn, groups)

        self.global_version += 1
        prev = self.store.latest()
        out = self._chunk_finalize_fn()(
            sp_flat, self.store.ring, slots, payload,
            jnp.asarray(w, jnp.float32), jnp.float32(fw), prev)
        new_flat, recon, chain = out[0], out[1], out[2:]
        self._advance_versioned(recon, chain, ev, part_ids)
        self._global_flat = new_flat
        self._gp_tree = None      # materialized lazily on demand

        return self._round_epilogue(prev_time, ev)

    def peak_delta_device_bytes(self):
        """Analytic peak DEVICE bytes of one round's delta pipeline: the
        widest live set any encode/blend stage holds for the k = ceil(C*M)
        expected participants. Flat path: delta + decode (K, N) f32 pairs
        (plus the EF residual expansion and spill under error feedback) and
        the (K, cap) f32+int32 payload. Chunked: the same buffers at
        max_chunk width — O(K * chunk), flat in N, which is the number the
        bench/regression gate pins across model sizes."""
        k = max(int(np.ceil(self.cfg.C * self.M)), 1)
        n = self._global_flat.shape[0]
        if self.chunked:
            chunk = self.layout.max_chunk
            cap = max(p["cap"] for p in self.comm.chunk_plan())
        else:
            chunk = n
            cap = self.comm.payload_capacity(n) if self._csr_wire else n
        bufs = 2 + (2 if self.cfg.error_feedback else 0)
        return int(4 * k * chunk * bufs + 8 * k * cap)

    # ------------------------------------------------------------------
    # sharded fleet engine: shard_map over the ``clients`` mesh axis
    def _stage1_sharded(self, with_residual, with_hist):
        """Train + upload-encode (+ pseudo-label histograms), one jitted
        shard_map per participant-shape: each device trains its row shard
        of the (Kp, N) stack and sparsifies the deltas against local
        per-client quantile thresholds. Entirely client-local — the stage
        has no collectives.

        Versioned base store: the stage takes the replicated (tau+2, N)
        reconstruction ring plus the sharded per-client slot vector and
        gathers each shard's base rows locally (``ring[slots]``) — the
        (Kp, N) base stack never materializes outside the stage. The dense
        store passes the pre-gathered (Kp, N) rows as before."""
        key = (with_residual, with_hist)
        fn = self._stage1_jits.get(key)
        if fn is not None:
            return fn
        mesh = self.mesh
        epoch = self.batched_epoch
        encode_upload = self._encode_upload_body(with_residual, with_hist)
        placeholder = jnp.zeros((), jnp.float32)       # shard_map needs
                                                       # arrays, not Nones
        _PV, _PI, _PC = CLIENT_PAYLOAD_SPECS
        versioned = self.base_store == "versioned"
        base_specs = (RING_SPEC, RING_SLOT_SPEC) if versioned else (_ROW2,)

        if self._csr_wire:
            n = self._global_flat.shape[0]
            # wire payload specs vary by format (csr: 2, csr_q: 4); the EF
            # residual store stays f32 CSR rows regardless of what's on the
            # wire, so its specs are always the f32 pair
            pspecs = payload_specs(self.wire_fmt)

            def shard_fn(*args):
                if versioned:
                    ring, slots = args[:2]
                    base = ring[slots]
                    xs, vs, lrs, keys, rvals, ridx = args[2:]
                else:
                    base, xs, vs, lrs, keys, rvals, ridx = args
                trained, _ = epoch(base, xs, vs, lrs, keys)
                # the residual store arrives as CSR rows; expand the local
                # shard to dense only inside the stage (per-row scatter)
                residual = csr_decode(rvals, ridx, n) if with_residual \
                    else None
                payload, stored, hists, res_payload, _ = encode_upload(
                    trained, base, xs, vs, residual)
                rp = res_payload if with_residual else (placeholder,) * 2
                return payload + (stored,
                                  hists if with_hist else placeholder,
                                  rp[0], rp[1])

            in_specs = base_specs + (_ROW3, _ROW2, _ROW, _ROW2,
                                     _PV if with_residual else _REP,
                                     _PI if with_residual else _REP)
            out_specs = pspecs + (_PC,
                                  _ROW2 if with_hist else _REP,
                                  _PV if with_residual else _REP,
                                  _PI if with_residual else _REP)
            fn = jax.jit(shard_map(
                shard_fn, mesh=mesh, in_specs=in_specs,
                out_specs=out_specs, check_vma=False))
            self._stage1_jits[key] = fn
            return fn

        def shard_fn(*args):
            if versioned:
                ring, slots = args[:2]
                base = ring[slots]
                xs, vs, lrs, keys, residual = args[2:]
            else:
                base, xs, vs, lrs, keys, residual = args
            trained, _ = epoch(base, xs, vs, lrs, keys)
            uploaded, nnz, hists, new_res = encode_upload(
                trained, base, xs, vs, residual if with_residual else None)
            return (uploaded, nnz,
                    hists if with_hist else placeholder,
                    new_res if with_residual else placeholder)

        out_specs = (_ROW2, _ROW,
                     _ROW2 if with_hist else _REP,
                     _ROW2 if with_residual else _REP)
        fn = jax.jit(shard_map(
            shard_fn, mesh=mesh,
            in_specs=base_specs + (_ROW3, _ROW2, _ROW, _ROW2,
                                   _ROW2 if with_residual else _REP),
            out_specs=out_specs, check_vma=False))
        self._stage1_jits[key] = fn
        return fn

    def _group_weights_sharded(self, K, num_groups, Kp):
        """On-device grouping + Eq. 10 weights: jitted k-means over the
        participants' pseudo-label histograms feeding the grouped weight
        fold, padded to the sharded row count — the host sync the batched
        engine pays for numpy k-means disappears."""
        key = (K, num_groups, Kp)
        fn = self._groupw_jits.get(key)
        if fn is not None:
            return fn
        init_idx = init_index(K, self.cfg.seed)

        @jax.jit
        def fn(hists, size_g):
            assign, _ = kmeans_device(hists[:K], num_groups,
                                      init_idx=init_idx)
            w = agg.combine_weights_device(size_g, assign, num_groups)
            return jnp.zeros((Kp,), jnp.float32).at[:K].set(w)

        self._groupw_jits[key] = fn
        return fn

    def _stage2_sharded(self):
        """Aggregate + distribute under shard_map: the weighted client sum
        is one psum over the client axis (pad rows carry weight zero) and
        the f(r) blend replicates. Dense store: each device then sparsifies
        the distribution deltas for its shard of the target rows. Versioned
        store: every device runs the identical single chain-transition
        encode against the replicated R_r (no per-target work at all)."""
        fn = self._stage2_jits.get("finalize")
        if fn is not None:
            return fn
        mesh = self.mesh
        use_kernel = self.cfg.use_kernels
        versioned = self.base_store == "versioned"
        distribute = self._advance_encode_body() if versioned \
            else self._distribute_encode_body()
        # payload arity of the advance encode (CSR-family wire tuple +
        # stored / nnz / exact)
        n_payload = self._payload_arity + 1 if self._csr_wire else \
            (1 if self.comm.enabled else 0)

        if self._csr_wire:
            pspecs = payload_specs(self.wire_fmt)
            if self.wire_fmt == "csr_q":
                def blend(s, b, p, w, fw):
                    return agg.blend_flat_sharded_csr_q(
                        s, b, *p, w, fw,
                        axis_name=CLIENT_AXIS, use_kernel=use_kernel)
            else:
                def blend(s, b, p, w, fw):
                    return agg.blend_flat_sharded_csr(
                        s, b, p[0], p[1], w, fw,
                        axis_name=CLIENT_AXIS, use_kernel=use_kernel)

            if versioned:
                def shard_fn(server_flat, ring, slots, payload, w, fw,
                             prev):
                    base = ring[slots]
                    new_flat = blend(server_flat, base, payload, w, fw)
                    recon, chain = distribute(new_flat, prev)
                    return (new_flat, recon) + chain

                fn = jax.jit(shard_map(
                    shard_fn, mesh=mesh,
                    in_specs=(_REP, RING_SPEC, RING_SLOT_SPEC, pspecs,
                              _ROW, _REP, _REP),
                    out_specs=(_REP, _REP) + (_REP,) * n_payload,
                    check_vma=False))
                self._stage2_jits["finalize"] = fn
                return fn

            def shard_fn(server_flat, base, payload, w, fw, dist_base):
                new_flat = blend(server_flat, base, payload, w, fw)
                new_base, nnz = distribute(new_flat, dist_base)
                return new_flat, new_base, nnz

            fn = jax.jit(shard_map(
                shard_fn, mesh=mesh,
                in_specs=(_REP, _ROW2, pspecs, _ROW, _REP, _ROW2),
                out_specs=(_REP, _ROW2, _ROW), check_vma=False))
            self._stage2_jits["finalize"] = fn
            return fn

        if versioned:
            def shard_fn(server_flat, uploaded, w, fw, prev):
                new_flat = agg.blend_flat_sharded(
                    server_flat, uploaded, w, fw,
                    axis_name=CLIENT_AXIS, use_kernel=use_kernel)
                recon, payload = distribute(new_flat, prev)
                return (new_flat, recon) + payload

            fn = jax.jit(shard_map(
                shard_fn, mesh=mesh,
                in_specs=(_REP, _ROW2, _ROW, _REP, _REP),
                out_specs=(_REP, _REP) + (_REP,) * n_payload,
                check_vma=False))
            self._stage2_jits["finalize"] = fn
            return fn

        def shard_fn(server_flat, uploaded, w, fw, dist_base):
            new_flat = agg.blend_flat_sharded(
                server_flat, uploaded, w, fw,
                axis_name=CLIENT_AXIS, use_kernel=use_kernel)
            new_base, nnz = distribute(new_flat, dist_base)
            return new_flat, new_base, nnz

        fn = jax.jit(shard_map(
            shard_fn, mesh=mesh,
            in_specs=(_REP, _ROW2, _ROW, _REP, _ROW2),
            out_specs=(_REP, _ROW2, _ROW), check_vma=False))
        self._stage2_jits["finalize"] = fn
        return fn

    def _run_round_sharded(self):
        """One fleet round: gather participant rows, one sharded
        train+upload stage, the replicated server epoch, on-device
        grouping/weights, one sharded aggregate+distribute stage, scatter
        the new base rows back. Zero per-round host syncs (the deferred
        ACO read excepted); K is padded to the device count with
        zero-weight rows that are sliced off before accounting."""
        cfg = self.cfg
        prev_time, ev, lrs = self._round_prologue()
        participants, stale, forced, t = ev
        r = self.global_version
        part_ids = [run.client for run in participants]
        K = len(part_ids)
        D = self.mesh.devices.size
        Kp = padded_rows(K, D)
        pad = Kp - K

        # same RNG stream as the sequential path: one split per REAL
        # participant in arrival order, then the server's split
        keys = self._split_keys(K)
        pad_ids = part_ids + part_ids[:1] * pad
        idx = jnp.asarray(pad_ids)
        xs, vs = self._gather_data(pad_ids)
        if pad:
            keys = jnp.concatenate([keys, jnp.zeros((pad,) + keys.shape[1:],
                                                    keys.dtype)])
            # pad rows see no valid samples -> their epoch is a pure no-op
            vs = vs * jnp.asarray(
                np.concatenate([np.ones(K, np.float32),
                                np.zeros(pad, np.float32)]))[:, None]
        lrs_p = jnp.asarray(np.concatenate([lrs[part_ids], np.zeros(pad)]),
                            jnp.float32)
        if self.base_store == "versioned":
            # the base rows are gathered from the replicated (tau+2, N)
            # ring inside the stages; only the slot vector crosses in
            slots = self.store.slots_for(pad_ids)
            base_args = (self.store.ring, slots)
        else:
            base_args = (_gather_rows(self._base_mat, idx),)
        n = self._global_flat.shape[0]

        with_hist = cfg.group_based and K > 1
        stage1 = self._stage1_sharded(cfg.error_feedback, with_hist)
        if self._csr_wire:
            arity = self._payload_arity
            if cfg.error_feedback:
                # residual rows travel as CSR (values, indices) — the dense
                # (M, N) residual matrix no longer exists. Paged store: the
                # (Kp, rcap) window comes off the host pages instead of a
                # device (M, rcap) gather; the stage is unchanged (it
                # already consumes participant windows)
                if self.paged:
                    rvals, ridx = self.cstore.gather_csr(pad_ids)
                else:
                    rvals = _gather_rows(self._res_vals, idx)
                    ridx = _gather_rows(self._res_idx, idx)
                out = stage1(*base_args, xs, vs, lrs_p, keys, rvals, ridx)
                nrv, nri = out[arity + 2], out[arity + 3]
                if self.paged:
                    self.cstore.scatter_csr(part_ids, nrv[:K], nri[:K])
                else:
                    self._res_vals = _scatter_rows(self._res_vals, idx[:K],
                                                   nrv[:K])
                    self._res_idx = _scatter_rows(self._res_idx, idx[:K],
                                                  nri[:K])
            else:
                z = jnp.zeros((), jnp.float32)
                out = stage1(*base_args, xs, vs, lrs_p, keys, z, z)
            payload, nnz, hists_dev = \
                tuple(out[:arity]), out[arity], out[arity + 1]
            self.comm.account_batch_csr(nnz[:K], n, K)
        elif cfg.error_feedback:
            residual = self.cstore.gather_dense(pad_ids) if self.paged \
                else _gather_rows(self._residual_mat, idx)
            uploaded, nnz, hists_dev, new_res = stage1(
                *base_args, xs, vs, lrs_p, keys, residual)
            if self.paged:
                self.cstore.scatter_dense(part_ids, new_res[:K])
            else:
                self._residual_mat = _scatter_rows(
                    self._residual_mat, idx[:K], new_res[:K])
            self.comm.account_batch(nnz[:K], n, K)
        else:
            uploaded, nnz, hists_dev, _ = stage1(
                *base_args, xs, vs, lrs_p, keys, jnp.zeros((), jnp.float32))
            self.comm.account_batch(nnz[:K], n, K)

        # server supervised epoch on the current global model (Eq. 6), in
        # flat space; the RNG split order matches the sequential path
        self.rng, k = jax.random.split(self.rng)
        sp_flat, self.server_opt, _ = self.server_epoch_flat(
            self._global_flat, self.server_opt,
            self.data["server"]["x"], self.data["server"]["y"], cfg.lr, k)

        sizes = [len(self.data["clients"][i]["x"]) for i in part_ids]
        stales = [stale[i] for i in part_ids]
        if with_hist:
            size_g = np.asarray(sizes, np.float64) * \
                np.array([self.g_fn(s) for s in stales])
            w_pad = self._group_weights_sharded(
                K, min(cfg.num_groups, K), Kp)(
                    hists_dev, jnp.asarray(size_g, jnp.float32))
        else:
            w = agg.combine_weights(sizes, stales, self.g_fn, None)
            w_pad = jnp.asarray(np.concatenate([w, np.zeros(pad)]),
                                jnp.float32)

        fw = supervised_weight(r, C=cfg.C, M=self.M,
                               mode=cfg.supervised_weight_mode)
        self.global_version += 1
        # distribution: latest + deprecated clients get the new model
        if self.base_store == "versioned":
            # chain-delta broadcast: one replicated chain-transition encode
            # in the stage; the store books the suffix from the stalest
            # target's version (each transition payload once) — no
            # per-target rows, gathers or retraces on the target count
            prev = self.store.latest()
            if self._csr_wire:
                out = self._stage2_sharded()(
                    sp_flat, self.store.ring, slots, payload, w_pad,
                    jnp.float32(fw), prev)
            else:
                out = self._stage2_sharded()(
                    sp_flat, uploaded, w_pad, jnp.float32(fw), prev)
            new_flat, recon, chain = out[0], out[1], out[2:]
            self._advance_versioned(recon, chain, ev, part_ids)
        else:
            targets, _ = self._distribution_plan(part_ids, ev)
            T = len(targets)
            Tp = padded_rows(T, D)
            tidx = jnp.asarray(targets + targets[:1] * (Tp - T))
            dist_base = _gather_rows(self._base_mat, tidx)
            if self._csr_wire:
                new_flat, new_base, nnz_d = self._stage2_sharded()(
                    sp_flat, base_args[0], payload, w_pad,
                    jnp.float32(fw), dist_base)
                self.comm.account_batch_csr(nnz_d[:T], n, T)
            else:
                new_flat, new_base, nnz_d = self._stage2_sharded()(
                    sp_flat, uploaded, w_pad, jnp.float32(fw), dist_base)
                self.comm.account_batch(nnz_d[:T], n, T)
            self._base_mat = _scatter_rows(self._base_mat, tidx[:T],
                                           new_base[:T])
            self._base_version[targets] = self.global_version
            self._reset_forced_residuals(forced)
        self._global_flat = new_flat
        self._gp_tree = None      # materialized lazily on demand

        return self._round_epilogue(prev_time, ev)

    # ------------------------------------------------------------------
    def base_store_bytes(self):
        """Bytes of server-side per-client base-model state (counterpart to
        ``residual_store_bytes``). The versioned store is O(tau * N + M):
        the (tau+2, N) reconstruction ring + retained chain payloads + the
        per-client version array. The legacy dense layouts are O(M * N)
        (per-client trees / rows / the (M, N) matrix) — the fleet-scale
        memory the versioned store removes."""
        if self.base_store == "versioned":
            return self.store.bytes()
        if self.engine == "sharded":
            return int(self._base_mat.size * 4) + self._base_version.nbytes
        if self.engine == "batched":
            # rows may alias (clients at the same version share buffers
            # until a distribution diverges them); report the logical
            # footprint, matching what a real parameter server would hold
            return int(sum(r.size * 4 for r in self._base_rows)) \
                + self._base_version.nbytes
        return int(sum(
            sum(leaf.size * 4 for leaf in jax.tree.leaves(c["base_params"]))
            for c in self.clients)) + 8 * self.M

    def residual_store_bytes(self):
        """Bytes held by the per-client error-feedback residual state (0
        when EF is off). The sharded CSR store is O(M * rcap); the legacy
        dense layouts are O(M * N) — the fleet-scale memory the compacted
        format removes."""
        if not self.cfg.error_feedback:
            return 0
        if self.paged:
            # host-nominal bytes of the residual pages (lazily committed /
            # memmapped); the device-side share is in
            # ``client_state_device_bytes``
            return self.cstore.residual_store_bytes()
        if self.chunked:
            return int((self._res_vals.size + self._res_idx.size) * 4)
        if self.engine == "sharded":
            if self._csr_wire:
                return int((self._res_vals.size + self._res_idx.size) * 4)
            return int(self._residual_mat.size * 4)
        if self.engine == "batched":
            return int(sum(r.size * 4 for r in self._residual_rows))
        return int(sum(
            sum(leaf.size * 4 for leaf in jax.tree.leaves(c["residual"]))
            for c in self.clients if "residual" in c))

    def client_state_device_bytes(self):
        """DEVICE-resident bytes of per-client state: EF residual storage
        plus (for the stacked engines) the padded data stack. Resident
        layouts hold (M, ...) arrays — linear in the fleet size; the paged
        store holds only the last round's participant window and its
        pending writeback pages — O(K * page), flat in M. This is the
        number the CI scale gate pins flat across fleet sizes."""
        if self.paged:
            return self.cstore.device_window_bytes() \
                + self._data_window_bytes
        total = 0
        if self.batched:
            total += int(self._x_pad.nbytes + self._valid_pad.nbytes)
        if self.cfg.error_feedback:
            if self.chunked:
                total += int((self._res_vals.size
                              + self._res_idx.size) * 4)
            elif self.engine == "sharded":
                if self._csr_wire:
                    total += int((self._res_vals.size
                                  + self._res_idx.size) * 4)
                else:
                    total += int(self._residual_mat.size * 4)
            elif self.engine == "batched":
                total += int(sum(r.size * 4 for r in self._residual_rows))
            else:
                total += self.residual_store_bytes()
        return total

    def client_state_host_bytes(self):
        """HOST-resident bytes of per-client state (nominal): the paged
        store's pages + counters + adopted version arrays, plus the host
        copy of the padded data stack the stacked engines page from. The
        resident layouts keep versions host-side (the versioned base
        store) and everything else on device."""
        if self.paged:
            total = self.cstore.host_bytes()
            if self.batched:
                total += int(self._x_pad_h.nbytes + self._valid_pad_h.nbytes
                             + self._data_map.nbytes)
            return total
        if self.base_store == "versioned":
            return int(self.store.client_version.nbytes
                       + self.store.detached.nbytes)
        if self.batched:
            return int(np.asarray(self._base_version).nbytes)
        return 8 * self.M

    def client_state_resident_equiv_bytes(self):
        """What the resident layout would put on DEVICE at this fleet size:
        the (M, nb*B, F) padded data stack (stacked engines) plus the
        (M, rcap) CSR or (M, n) dense residual store under EF. The scale
        gate requires ``client_state_device_bytes`` strictly below this on
        every paged cell — at M=1,000,000 the resident equivalent simply
        would not fit."""
        total = 0
        if self.batched:
            if self.paged:
                total += self.M * self._data_row_bytes
            else:
                total += int(self._x_pad.nbytes + self._valid_pad.nbytes)
        if self.cfg.error_feedback:
            n = self._global_flat.shape[0]
            if self._csr_wire:
                rcap = self.comm.residual_capacity_total() if self.chunked \
                    else self.comm.residual_capacity(n)
                total += self.M * rcap * 8
            else:
                total += self.M * n * 4
        return total

    # ------------------------------------------------------------------
    # crash-consistent checkpointing (core.fleet_ckpt)
    def _ef_kind(self):
        """Which serialized form this trainer's EF residual state takes
        (part of the checkpoint fingerprint: the layouts are engine-
        specific and do not cross-load)."""
        if not self.cfg.error_feedback:
            return "none"
        if self.paged:
            return "paged"            # pages ride in the cstore section
        if self.chunked or (self.engine == "sharded" and self._csr_wire):
            return "csr"
        if self.engine == "sharded":
            return "dense_mat"
        if self.engine == "batched":
            return "rows"
        return "trees"

    def _ef_state(self):
        """Device-resident EF snapshot; ``save_checkpoint`` batches the
        host transfer for all layouts in one ``jax.device_get``."""
        kind = self._ef_kind()
        if kind == "csr":
            return {"kind": kind, "vals": self._res_vals,
                    "idx": self._res_idx}
        if kind == "dense_mat":
            return {"kind": kind, "mat": self._residual_mat}
        if kind == "rows":
            rows = tuple(self._residual_rows)   # immutable device refs
            # host-side stack: the writer thread must never LAUNCH device
            # programs (a jnp.stack dispatched concurrently with the main
            # thread's multi-device round program can interleave collective
            # rendezvous across the two programs and deadlock XLA:CPU) —
            # np.asarray is a pure transfer, np.stack is host memcpy
            return {"kind": kind,
                    "rows": fleet_ckpt.Lazy(
                        lambda: np.stack([np.asarray(r) for r in rows]))}
        if kind == "trees":
            items = [[int(i), list(jax.tree.leaves(c["residual"]))]
                     for i, c in enumerate(self.clients)
                     if "residual" in c]
            return {"kind": kind, "items": items}
        return {"kind": kind}

    def _load_ef_state(self, d):
        kind = self._ef_kind()
        if d["kind"] != kind:
            raise ValueError(f"checkpoint EF state is {d['kind']!r}, this "
                             f"trainer stores {kind!r}")
        if kind == "csr":
            self._res_vals = jnp.asarray(np.asarray(d["vals"], np.float32))
            self._res_idx = jnp.asarray(np.asarray(d["idx"], np.int32))
        elif kind == "dense_mat":
            self._residual_mat = jnp.asarray(np.asarray(d["mat"],
                                                        np.float32))
        elif kind == "rows":
            rows = jnp.asarray(np.asarray(d["rows"], np.float32))
            self._residual_rows = [rows[i] for i in range(rows.shape[0])]
        elif kind == "trees":
            tmpl, treedef = jax.tree_util.tree_flatten(self._template)
            for c in self.clients:
                c.pop("residual", None)
            for i, leaves in d["items"]:
                self.clients[int(i)]["residual"] = \
                    jax.tree_util.tree_unflatten(treedef, [
                        jnp.asarray(np.asarray(l), t.dtype)
                        for l, t in zip(leaves, tmpl)])

    def _ckpt_fingerprint(self):
        """Config/layout identity a checkpoint must match to restore: the
        mutable state's meaning depends on all of it (the ParamLayout
        chunking via the chunk plan, the wire format via payload shapes,
        the engine via the EF layout, the seed via every RNG stream)."""
        cfg = self.cfg
        chunks = [[int(p["s"]), int(p["e"])]
                  for p in self.comm.chunk_plan()] if self.chunked else None
        return {"format": fleet_ckpt.FORMAT_VERSION,
                "M": int(self.M), "n": int(self._global_flat.shape[0]),
                "engine": self.engine, "wire_fmt": self.wire_fmt,
                "q_dtype": str(cfg.q_dtype),
                "base_store": self.base_store,
                "client_store": str(cfg.client_store),
                "error_feedback": bool(cfg.error_feedback),
                "ef_kind": self._ef_kind(),
                "tau": int(cfg.tau), "C": float(cfg.C),
                "seed": int(cfg.seed),
                "sparse_comm": bool(cfg.sparse_comm),
                "sparse_threshold": str(cfg.sparse_threshold),
                "chunks": chunks}

    def _ckpt_drain(self):
        """Wait for the in-flight background checkpoint write, if any,
        and re-raise whatever it failed with."""
        if self._ckpt_queue is not None:
            self._ckpt_queue.join()
        if self._ckpt_exc is not None:
            exc, self._ckpt_exc = self._ckpt_exc, None
            raise exc

    def _ckpt_submit(self, job):
        """Hand ``job`` to the persistent checkpoint writer thread
        (started lazily; spawning a thread per save costs milliseconds).
        Exceptions surface on the next :meth:`_ckpt_drain`."""
        if self._ckpt_thread is None:
            self._ckpt_queue = queue.Queue()

            def _loop(q=self._ckpt_queue):
                while True:
                    j = q.get()
                    try:
                        j()
                    except BaseException as exc:
                        self._ckpt_exc = exc
                    finally:
                        q.task_done()

            self._ckpt_thread = threading.Thread(
                target=_loop, name="fleet-ckpt-writer", daemon=True)
            self._ckpt_thread.start()
        self._ckpt_queue.put(job)

    def _ckpt_sections(self):
        """Snapshot every checkpoint section on the CALLING thread.
        Device-resident tensors are captured by reference — JAX arrays
        are immutable, so the writer thread can transfer and serialize
        them later with no consistency risk — while everything mutable
        on the host (participation matrix, scheduler/store/ledger state,
        the log history) is copied or frozen to bytes here. Round logs
        are append-only and never mutate once their round has closed, so
        each is packed exactly once per run and the section is assembled
        from cached bytes (re-encoding the whole history made save cost
        grow linearly with the round index)."""
        flat = self._global_flat if self._gp_tree is None \
            else flatten_tree(self._gp_tree)
        # capture the new logs by reference; the writer thread packs them
        # into the shared cache (exclusive: at most one write in flight,
        # and the training thread only touches the cache after a drain)
        cache = self._log_pack
        new_logs = self.logs[len(cache):]

        def _logs_bytes():
            for log in new_logs:
                cache.append(fleet_ckpt.pack(vars(log)))
            return fleet_ckpt.pack_array_of_packed(cache)

        sections = {
            "trainer": {
                "round": int(self.global_version),
                "rng": self.rng,
                "global_flat": flat,
                "server_opt": list(jax.tree.leaves(self.server_opt)),
                "participation": self.participation.copy(),
                "ef": self._ef_state(),
            },
            "scheduler": self.scheduler.state_dict(),
            # defer=True: the snapshot must not block on the round's
            # still-in-flight device work — the writer thread resolves
            # the Lazy folds (bit-identical to the eager path)
            "store": self.store.state_dict(defer=True),
            "comm": self.comm.ledger_state(defer=True),
            "logs": fleet_ckpt.PrePacked(_logs_bytes),
        }
        if self.paged:
            sections["cstore"] = self.cstore.state_dict()
        return sections

    def save_checkpoint(self, *, wait=True):
        """Write one crash-consistent checkpoint of the COMPLETE round-
        boundary state: global model + server Adam state, EF residuals,
        the versioned base store (ring, chain, versions, detached mask),
        paged client pages, scheduler heaps + fault-RNG positions, comm
        ledgers, participation matrix and round logs — committed by a
        checksummed MANIFEST written tmp+fsync+rename LAST, so a crash
        mid-write leaves the previous good checkpoint restorable.

        With ``wait=False`` the host transfer, serialization and disk
        protocol run on a background writer thread (at most one in
        flight; a new save or :meth:`restore` joins it first), keeping
        the training loop's exposure to a few hundred microseconds of
        snapshotting — ``train()`` checkpoints this way. Errors from a
        background write surface on the next save/drain. Returns the
        checkpoint directory path."""
        root = self.cfg.checkpoint_dir
        if not root:
            raise ValueError(
                "save_checkpoint() needs FedS3AConfig(checkpoint_dir=...)")
        self._ckpt_drain()
        rnd = int(self.global_version)
        sections = self._ckpt_sections()
        fingerprint = self._ckpt_fingerprint()

        def _write():
            # one batched host transfer for every device-resident tensor
            # (per-leaf np.asarray would pay a dispatch+sync each); this
            # also absorbs the wait for the round's still-in-flight async
            # dispatch, which is the bulk of a synchronous save's cost
            return fleet_ckpt.write_checkpoint(
                root, rnd, jax.device_get(sections), fingerprint)

        if wait:
            return _write()
        self._ckpt_submit(_write)
        return os.path.join(root, f"ckpt-{rnd:08d}")

    def restore(self, checkpoint_dir=None):
        """Resume from the newest restorable checkpoint (torn writes fall
        back to the previous good one). Call on a freshly constructed
        trainer with the same data and config as the writer — the
        fingerprint is validated — then ``train()`` continues bit-exactly
        where the checkpoint left off: schedules, metrics, ACO, fault
        traces and fleet health all match an uninterrupted run. Returns
        the restored round index."""
        self._ckpt_drain()
        root = checkpoint_dir if checkpoint_dir is not None \
            else self.cfg.checkpoint_dir
        if not root:
            raise ValueError("restore() needs a checkpoint directory")
        path, manifest = fleet_ckpt.find_restorable(root)
        if path is None:
            raise FileNotFoundError(
                f"no restorable checkpoint under {root!r}")
        fp = self._ckpt_fingerprint()
        if manifest.get("fingerprint") != fp:
            raise ValueError(
                "checkpoint fingerprint mismatch: the checkpoint was "
                "written under a different configuration/layout than this "
                "trainer's")
        tr = fleet_ckpt.read_section(path, "trainer")
        self.global_version = int(tr["round"])
        self.rng = jnp.asarray(np.asarray(tr["rng"]), jnp.uint32)
        self._global_flat = jnp.asarray(np.asarray(tr["global_flat"]),
                                        jnp.float32)
        self._gp_tree = None
        leaves, treedef = jax.tree_util.tree_flatten(self.server_opt)
        if len(tr["server_opt"]) != len(leaves):
            raise ValueError(
                f"checkpoint server_opt has {len(tr['server_opt'])} "
                f"leaves, expected {len(leaves)}")
        self.server_opt = jax.tree_util.tree_unflatten(treedef, [
            jnp.asarray(np.asarray(s).reshape(np.shape(t)),
                        jnp.asarray(t).dtype)
            for s, t in zip(tr["server_opt"], leaves)])
        self.participation = np.asarray(
            tr["participation"], np.float64).reshape(-1, self.M)
        self._load_ef_state(tr["ef"])
        self.scheduler.load_state_dict(
            fleet_ckpt.read_section(path, "scheduler"))
        self.store.load_state_dict(fleet_ckpt.read_section(path, "store"))
        self.comm.load_ledger_state(fleet_ckpt.read_section(path, "comm"))
        if self.paged:
            self.cstore.load_state_dict(
                fleet_ckpt.read_section(path, "cstore"))
            # the store's load reassigned its version arrays; re-adopt the
            # references so host-byte reporting tracks the live objects
            self.cstore.adopt_versions(self.store.client_version,
                                       self.store.detached)
        self.logs = []
        self._log_pack = []
        for d in fleet_ckpt.read_section(path, "logs"):
            d = dict(d)
            d["stalenesses"] = {int(k): float(v)
                                for k, v in d["stalenesses"].items()}
            self.logs.append(RoundLog(**d))
        self._data_window_bytes = 0
        return int(tr["round"])

    def evaluate(self, params=None):
        params = params if params is not None else self.global_params
        test = self.data["test"]
        preds = np.asarray(self.predict(params, jnp.asarray(test["x"])))
        return weighted_metrics(test["y"], preds, self.adapter.num_classes)

    def train(self, rounds=None, *, eval_every=0):
        rounds = rounds or self.cfg.rounds
        cfg = self.cfg
        for _ in range(rounds):
            log = self.run_round()
            if eval_every and (log.round + 1) % eval_every == 0:
                log.metrics = self.evaluate()
            # checkpoint cadence keyed to the GLOBAL round index, not this
            # call's loop counter, so train(50) and train(25)+train(25)
            # write identical checkpoints
            if cfg.checkpoint_dir and cfg.checkpoint_every \
                    and self.global_version % cfg.checkpoint_every == 0:
                self.save_checkpoint(wait=False)
        # final checkpoint at the last round, unless the cadence just
        # wrote one — a resumed run continues from exactly where this
        # train() call stopped, not the last multiple of checkpoint_every
        if cfg.checkpoint_dir and cfg.checkpoint_every \
                and self.global_version % cfg.checkpoint_every != 0:
            self.save_checkpoint(wait=False)
        self._ckpt_drain()
        final = self.evaluate()
        art = float(np.mean([l.art for l in self.logs]))
        return {"metrics": final, "art": art, "aco": self.comm.aco,
                "rounds": len(self.logs),
                "fleet": fleet_health(self.logs)}
