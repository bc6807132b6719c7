"""Sparse-difference transmission (§IV-F) + ACO accounting.

Clients upload delta = omega_new - omega_base as a magnitude-thresholded
sparse payload; the server reconstructs omega_base + delta. The same path is
used server->client after aggregation. ACO (average communication overhead)
= payload bytes / dense bytes, matching the paper's "ratio of data
communicated to total model parameters".

Wire formats (``wire_format=``):

* ``"csr"`` (default) — the compacted wire format: each message is the CSR
  triple (values f32, column indices int32, row_ptr) actually materialized
  by the compaction kernel/oracle, so reported bytes-on-wire IS the size of
  the arrays that would cross the network: ``stored_nnz * 8 + 4 * (K + 1)``
  for a K-row batch. Exact zeros never go on the wire (they carry no
  information), and each row is bounded by a static capacity
  ``cap = min(N, ceil(cap_factor * keep_frac * N))`` (absolute-threshold
  mode: ``cap = N``); overflow past the capacity spills into the
  error-feedback residual when EF is on, and is dropped (the paper's lossy
  scheme) otherwise. Under EF the residual itself is kept as a
  capacity-bounded CSR row (top ``residual_frac`` of N by magnitude via a
  per-row sampled quantile, then the same column-order capacity rule) — the
  store is O(cap), not O(N), and ``residual_frac=1.0`` recovers lossless EF.
* ``"csr_q"`` — the quantized + packed CSR format: same compaction pipeline,
  but values ship as int8 with a per-row absmax scale (``q_dtype="fp16"``
  falls back to float16 for deltas whose dynamic range int8 cannot hold) and
  column indices ship as int16 in-block offsets plus a per-row
  ``ceil(n/512)``-entry int16 block-count table (csr_compact's stage-1
  per-block nnz, reused as the index decoder's side information). Bytes per
  stored element drop 8 -> 3 (int8: 1 value + 2 offset; fp16: 4), plus
  4 bytes/row of scale and ``2 * ceil(n/512)`` bytes/row of block table.
  Quantization is LOSSY; the encode core computes everything downstream —
  the server decode, the distribution chain, and crucially the
  error-feedback residual — from the dequantized payload, so the rounding
  error folds into the same residual that already absorbs sparsification
  overflow and is re-offered next round instead of accumulating into drift.
  Without EF the rounding error is dropped, exactly like sub-threshold mass
  in the paper's lossy scheme. The f32 ``"csr"`` format stays the
  parity-pinned reference.
* ``"dense_masked"`` — the pre-compaction reference format: the masked dense
  delta moves between engines and ACO counts value+index per threshold
  survivor (8 bytes vs 4 dense) without materializing a payload.

ACO accounting is *deferred*: payload byte counts depend on the on-device
nnz reduction, so ``encode`` / ``encode_batch`` only append the device
scalar to a pending list — no ``int()`` / ``float()`` host sync per message.
(row_ptr bytes are host-computable — 4 * (rows + 1) per batch — and tracked
as a plain int.) The ``aco`` / ``payload_bytes`` properties materialize the
pending scalars in one device->host transfer when read (typically once per
``train()``). Quantile thresholds likewise stay on device (vmapped
``_sampled_quantile`` feeding the kernel as a runtime input), so the batched
path dispatches each round's entire upload set with zero host round trips.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.telemetry import (COMPACT, MASK, QUANTIZE, RESIDUAL,
                                  THRESHOLD, scope)
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.sparse_delta import local_quantile_thresholds


@jax.jit
def _sampled_quantile(flat, q):
    """Quantile of |flat| from a strided 2k sample (exact sort over 5M params
    per message dominated benchmark wall time; XLA:CPU sorts are slow enough
    that even a 64k sample per message was the next bottleneck — a 2048
    sample keeps the kept-fraction standard error under ~1%)."""
    n = flat.shape[0]
    stride = max(n // 2048, 1)
    return jnp.quantile(jnp.abs(flat[::stride]), q)


_sampled_quantile_batch = jax.jit(jax.vmap(_sampled_quantile,
                                           in_axes=(0, None)))


@jax.jit
def _mask_count(flat, thr):
    keep = jnp.abs(flat) >= thr
    return jnp.where(keep, flat, 0), jnp.sum(keep)


_mask_count_batch = jax.jit(jax.vmap(_mask_count))


def tree_sub(a, b):
    return jax.tree.map(lambda x, y: x - y, a, b)


def tree_add(a, b):
    return jax.tree.map(lambda x, y: x + y, a, b)


def flatten_tree(tree):
    leaves = jax.tree.leaves(tree)
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
    return flat


def unflatten_like(flat, tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = []
    idx = 0
    for l in leaves:
        n = int(np.prod(l.shape))   # leaves may be ShapeDtypeStructs
        out.append(flat[idx:idx + n].reshape(tuple(l.shape)).astype(l.dtype))
        idx += n
    return jax.tree_util.tree_unflatten(treedef, out)


def stack_trees(trees):
    """List of pytrees -> one pytree with a leading client axis."""
    return jax.tree.map(lambda *ls: jnp.stack(ls), *trees)


def flatten_stacked(tree):
    """Pytree with leading client axis K -> (K, N) flat stack.

    Row i equals ``flatten_tree`` of client i's tree (same leaf order), so
    the stack can feed the aggregation kernels directly with no per-tree
    flatten/stack round trip.
    """
    leaves = jax.tree.leaves(tree)
    K = leaves[0].shape[0]
    return jnp.concatenate(
        [l.reshape(K, -1).astype(jnp.float32) for l in leaves], axis=1)


def unflatten_stacked(flat, template_tree):
    """(K, N) flat stack -> pytree with leading client axis K.

    ``template_tree`` is a single (unstacked) tree giving leaf shapes/dtypes.
    """
    leaves, treedef = jax.tree_util.tree_flatten(template_tree)
    K = flat.shape[0]
    out = []
    idx = 0
    for l in leaves:
        n = int(np.prod(l.shape))   # leaves may be ShapeDtypeStructs
        out.append(flat[:, idx:idx + n].reshape((K,) + tuple(l.shape))
                   .astype(l.dtype))
        idx += n
    return jax.tree_util.tree_unflatten(treedef, out)


WIRE_FORMATS = ("csr", "csr_q", "dense_masked")
CSR_FORMATS = ("csr", "csr_q")
Q_DTYPES = ("int8", "fp16")
Q_BLOCK = 512             # csr_q in-block offset range (csr_compact's
                          # stage-1 block size): offsets are int16 in
                          # [0, Q_BLOCK) and the block-count table has
                          # ceil(n / Q_BLOCK) entries per row
# the fault injector's malformed-payload menu: every class of corruption
# the wire validator must catch. Each kind maps to one specific mutilation
# in SparseComm.malform_stats and every kind raises WireIntegrityError
# under every CSR-family wire format.
MALFORM_KINDS = ("row_ptr", "oob_index", "nan_value", "bad_scale",
                 "arity", "truncated", "dtype")


class WireIntegrityError(ValueError):
    """An incoming upload failed wire validation (malformed row_ptr,
    out-of-bounds index, non-finite value/scale, wrong arity/dtype/shape,
    truncated buffer). The payload must be quarantined — never decoded,
    never aggregated, never booked."""
CAP_FACTOR = 2.5          # payload capacity slack over the target keep_frac:
                          # near-tied delta magnitudes (e.g. sign-like early
                          # Adam steps) push the kept fraction past the
                          # quantile target, and capping real mass costs
                          # accuracy — 2.5x covers the measured worst case
                          # while keeping the buffer well under dense
RESIDUAL_FRAC = 0.25      # EF residual store: top fraction of N kept by
                          # magnitude -> 2N bytes/client vs 4N dense


class SparseComm:
    """Stateful comm channel with deferred ACO bookkeeping.

    ``threshold`` modes:
      float   — absolute magnitude threshold (the paper's L1+threshold form)
      "p<frac>" — keep the top <frac> fraction by magnitude (quantile mode);
                  default p0.2 reproduces the paper's ~0.49 ACO exactly
                  (payload = nnz * 8 bytes vs dense 4 bytes/param).

    ``wire_format`` / ``capacity`` / ``cap_factor`` / ``residual_frac``:
    see the module docstring. ``capacity=None`` derives the per-row payload
    capacity from the keep fraction; an explicit int pins it.

    Error-feedback residuals and forced restarts: a residual is delta mass
    accumulated against the base the client held when it last uploaded.
    When the scheduler force-restarts a deprecated client (version gap >
    tau) its in-flight trajectory is discarded and it starts over from the
    new global model — the trainer therefore RESETS that client's residual
    to zero at the forced restart (pinned in tests/test_error_feedback.py).
    Re-offering the stale residual against a base the client no longer has
    would inject drift that EF exists to prevent; fresh base, fresh
    residual. (Residuals of ordinary participants persist across rounds as
    usual — that carry-over is the whole point of EF.)

    Byte counters: ``dense_bytes`` is host-computable (4 bytes/param/message)
    and kept as a plain int; payload bytes need the on-device nnz count, so
    each message appends one ``(stored_count, value_bytes_per_element,
    index_bytes_per_element)`` entry to ``_pending_payload`` — the count is
    a device scalar, the per-element widths are the format's — and the
    ``aco`` / ``payload_bytes`` / ``wire_breakdown`` readers fold the list
    into per-component host totals with a single stacked transfer. The
    host-computable framing accumulates separately as plain ints: row_ptr
    (``4 * (rows + 1)`` per CSR batch), per-row scales and block-count
    tables (csr_q), and dense payloads (disabled channel, full-model
    resyncs) in ``_dense_payload_host``.
    """

    def __init__(self, threshold="p0.2", *, use_kernel=True, enabled=True,
                 wire_format="csr", capacity=None, cap_factor=CAP_FACTOR,
                 residual_frac=RESIDUAL_FRAC, q_dtype="int8", layout=None):
        if wire_format not in WIRE_FORMATS:
            raise ValueError(f"wire_format must be one of {WIRE_FORMATS}, "
                             f"got {wire_format!r}")
        if q_dtype not in Q_DTYPES:
            raise ValueError(f"q_dtype must be one of {Q_DTYPES}, "
                             f"got {q_dtype!r}")
        self.threshold = threshold
        self.layout = layout            # core.param_layout.ParamLayout | None
        self._chunk_plan = None
        self.use_kernel = use_kernel
        self.enabled = enabled
        self.wire_format = wire_format
        self.capacity = capacity
        self.cap_factor = cap_factor
        self.residual_frac = residual_frac
        self.q_dtype = q_dtype
        self._values_host = 0.0         # materialized per-component bytes
        self._indices_host = 0.0
        self._dense_payload_host = 0.0  # dense payloads (disabled / resync)
        self._pending_payload = []      # (count_dev, val_bytes, idx_bytes)
        self._batch_cores = {}          # residual? -> jitted encode pipeline
        self._csr_cores = {}            # residual? -> jitted CSR pipeline
        self.dense_bytes = 0
        self.row_ptr_bytes = 0
        self.scales_bytes = 0           # csr_q per-row scale framing
        self.block_table_bytes = 0      # csr_q per-row block-count framing
        self.messages = 0

    @property
    def _payload_host(self):
        """Materialized variable-size payload bytes (back-compat view of
        the per-component ledger; excludes host-tracked framing, exactly as
        before the split)."""
        return self._values_host + self._indices_host + \
            self._dense_payload_host

    def elem_bytes(self):
        """(value_bytes, index_bytes) per stored element on this channel's
        wire format: f32+int32 for ``csr``/``dense_masked``, int8+int16
        offset for ``csr_q`` (fp16 fallback: 2+2)."""
        if self.wire_format == "csr_q":
            return (2, 2) if self.q_dtype == "fp16" else (1, 2)
        return (4, 4)

    def row_overhead_bytes(self, n):
        """Host-computable per-row framing beyond the shared row_ptr:
        (scale_bytes, block_table_bytes) for one n-param csr_q row — the
        f32 absmax scale (omitted in fp16 mode, where scales are the
        constant 1) and the int16 per-block count table. Zero under f32
        CSR, whose indices are self-describing absolute columns.

        Under a chunked layout the per-row framing is per CHUNK per row —
        one absmax scale and one block table per chunk — so a full-model
        (n == layout.n) csr_q message books the chunked wire truthfully."""
        if self.wire_format != "csr_q":
            return 0, 0
        scale = 0 if self.q_dtype == "fp16" else 4
        chunks = self._layout_chunks(n)
        if chunks > 1:
            table = sum(2 * max((nc + 511) // 512, 1)
                        for nc in self.layout.sizes)
            return scale * chunks, table
        return scale, 2 * max((n + 511) // 512, 1)

    def _layout_chunks(self, n):
        """Number of layout chunks an n-param message spans: the layout
        applies only to full-model messages (n == layout.n); everything
        else (server data messages, sub-vector payloads) stays flat."""
        if self.layout is not None and n == self.layout.n:
            return self.layout.num_chunks
        return 1

    # -- threshold ---------------------------------------------------------
    def _quantile_frac(self):
        if isinstance(self.threshold, str) and self.threshold.startswith("p"):
            return float(self.threshold[1:])
        return None

    def _abs_threshold(self, flat):
        """Device scalar threshold for one flat delta (no host sync)."""
        frac = self._quantile_frac()
        if frac is not None:
            return _sampled_quantile(flat, 1.0 - frac)
        return jnp.float32(self.threshold)

    def _abs_threshold_batch(self, flat_stack):
        """(K,) device thresholds, one vmapped quantile per client."""
        frac = self._quantile_frac()
        if frac is not None:
            return _sampled_quantile_batch(flat_stack, 1.0 - frac)
        K = flat_stack.shape[0]
        return jnp.full((K,), self.threshold, jnp.float32)

    # -- CSR wire format ---------------------------------------------------
    def payload_capacity(self, n):
        """Static per-row payload capacity for an n-param message."""
        if self.capacity is not None:
            return max(1, min(int(self.capacity), n))
        frac = self._quantile_frac()
        if frac is None:                 # absolute threshold: nnz unbounded
            return n
        return max(1, min(n, int(math.ceil(self.cap_factor * frac * n))))

    def residual_capacity(self, n):
        """Static per-row capacity of the EF residual store."""
        return max(1, min(n, int(math.ceil(self.residual_frac * n))))

    def _row_thresholds(self, delta):
        """(K,) per-row thresholds for this channel's mode."""
        frac = self._quantile_frac()
        if frac is not None:
            return local_quantile_thresholds(delta, frac)
        return jnp.full((delta.shape[0],), float(self.threshold),
                        jnp.float32)

    def _compact(self, delta, thr, cap):
        """delta (K, n) x (K,) thresholds -> the (values, indices, nnz)
        wire payload at capacity ``cap``."""
        if self.use_kernel:
            return kops.csr_compact(delta, thr, cap)
        return kref.csr_compact2d_ref(delta, thr, cap)

    def _quantize(self, vals, idx, stored, n):
        """Packed f32 payload -> the csr_q quadruple
        (qvals, offsets, block_counts, scales)."""
        if self.use_kernel:
            return kops.csr_quantize(vals, idx, stored, n,
                                     q_dtype=self.q_dtype)
        qvals, scales = kref.csr_quantize2d_ref(vals, stored,
                                                q_dtype=self.q_dtype)
        offs, counts = kref.csr_pack_indices_ref(idx, stored, n)
        return qvals, offs, counts, scales

    def csr_core(self, with_residual=False):
        """Jitted CSR-family encode pipeline on (K, n) flat stacks, built
        once per (instance, residual?). Per-row ops only, so calling it
        inside a ``shard_map`` over the client axis matches the unsharded
        result.

        Without residual: (new, base) -> (payload, stored, decoded) where
        ``payload`` is the wire tuple — ``(values, indices)`` under f32
        ``csr``, ``(qvals, offsets, block_counts, scales)`` under
        ``csr_q`` — ``stored = min(nnz, cap)`` is the on-wire count and
        ``decoded`` is the server-side reconstruction of the payload
        (under ``csr_q`` the DEQUANTIZED decode: what the server actually
        recovers, rounding loss included).

        With residual: (new, base, residual) -> (payload, stored, decoded,
        (rvalues, rindices, rstored), residual_dense) — the new residual is
        ``delta + residual - decoded`` (sub-threshold mass, capacity
        overflow, AND — under csr_q — quantization rounding error all spill
        back), truncated to the residual store's capacity;
        ``residual_dense`` is its dense expansion for engines that keep
        dense per-client rows. The residual store is local client state and
        never crosses the wire, so it stays f32 CSR under every format.
        The caller owns accounting (``account_batch_csr`` with the stored
        counts).
        """
        key = bool(with_residual)
        core = self._csr_cores.get(key)
        if core is not None:
            return core
        compact, row_thr = self._compact, self._row_thresholds
        pay_cap, res_cap = self.payload_capacity, self.residual_capacity
        residual_frac = self.residual_frac
        quantized, q_dtype = self.wire_format == "csr_q", self.q_dtype
        quantize = self._quantize
        # dense reconstructions use the scatter-free capped-mask twin of the
        # compact->decode round-trip (identical output; XLA:CPU scatters are
        # serial, and on paths that only read the stored counts the
        # compaction dead-code-eliminates entirely). Under csr_q the
        # twin extends through quantization: the absmax over the packed
        # prefix equals the absmax over the capped-mask rows, so the
        # elementwise quantize->dequantize round-trip of the dense rows is
        # bit-identical to scattering the dequantized payload.
        capped = kref.csr_capped_mask_ref

        def encode_payload(delta, n):
            with scope(THRESHOLD):
                thr = row_thr(delta)
            with scope(COMPACT):
                vals, idx, _ = compact(delta, thr, pay_cap(n))
            with scope(MASK):
                dense, stored = capped(delta, thr, pay_cap(n))
            if not quantized:
                return (vals, idx), stored, dense
            with scope(QUANTIZE):
                qvals, offs, counts, scales = quantize(vals, idx, stored, n)
                decoded = kref.quantize_dense_ref(dense, scales,
                                                  q_dtype=q_dtype)
            return (qvals, offs, counts, scales), stored, decoded

        if with_residual:
            @jax.jit
            def core(new_flat, base_flat, residual_flat):
                n = new_flat.shape[1]
                delta = new_flat - base_flat + residual_flat
                payload, stored, decoded = encode_payload(delta, n)
                res = delta - decoded   # sub-threshold + overflow (+ csr_q
                                        # quantization error: EF absorption)
                with scope(RESIDUAL):
                    with scope(THRESHOLD):
                        r_thr = local_quantile_thresholds(res, residual_frac)
                    with scope(COMPACT):
                        rvals, ridx, _ = compact(res, r_thr, res_cap(n))
                    with scope(MASK):
                        res_dense, rstored = capped(res, r_thr, res_cap(n))
                return (payload, stored, decoded,
                        (rvals, ridx, rstored), res_dense)
        else:
            @jax.jit
            def core(new_flat, base_flat):
                n = new_flat.shape[1]
                delta = new_flat - base_flat
                return encode_payload(delta, n)

        self._csr_cores[key] = core
        return core

    # -- chunked parameter axis (core.param_layout) ------------------------
    def set_layout(self, layout):
        """Attach a :class:`~repro.core.param_layout.ParamLayout`. Accounting
        for full-model messages (row_ptr / scales / block tables) switches to
        the per-chunk framing; a ``None`` or single-chunk layout keeps the
        flat books bit-identical."""
        self.layout = layout
        self._chunk_plan = None

    def chunk_plan(self):
        """Per-chunk encode plan derived from the layout: a list of dicts
        ``{s, e, nc, keep, cap, rcap, roff}`` where ``keep`` is the chunk's
        keep-fraction override (``None`` -> channel default), ``cap`` the
        payload capacity at the chunk's width, and ``[roff, roff + rcap)``
        the chunk's segment of the concatenated EF residual page."""
        if self._chunk_plan is not None:
            return self._chunk_plan
        if self.layout is None:
            raise ValueError("chunk_plan() requires a layout (set_layout)")
        default_frac = self._quantile_frac()
        plan, roff = [], 0
        for c in range(self.layout.num_chunks):
            s, e = self.layout.bounds[c]
            nc = e - s
            keep = self.layout.keep_frac[c]
            frac = keep if keep is not None else default_frac
            if self.capacity is not None:
                cap = max(1, min(int(self.capacity), nc))
            elif frac is None:          # absolute threshold: nnz unbounded
                cap = nc
            else:
                cap = max(1, min(nc, int(math.ceil(self.cap_factor
                                                   * frac * nc))))
            rfrac = self.layout.residual_frac[c]
            rfrac = rfrac if rfrac is not None else self.residual_frac
            rcap = max(1, min(nc, int(math.ceil(rfrac * nc))))
            plan.append({"s": s, "e": e, "nc": nc, "keep": keep, "cap": cap,
                         "rfrac": rfrac, "rcap": rcap, "roff": roff})
            roff += rcap
        self._chunk_plan = plan
        return plan

    def residual_capacity_total(self):
        """Total per-client EF residual capacity under the layout: the sum
        of the per-chunk capacities (== the width of the concatenated
        residual page a chunked engine stores per client)."""
        return sum(p["rcap"] for p in self.chunk_plan())

    def _chunk_thresholds(self, delta_c, keep):
        """(K,) per-row thresholds for one chunk: the chunk's keep-fraction
        override when present, else the channel's mode."""
        if keep is not None:
            return local_quantile_thresholds(delta_c, keep)
        return self._row_thresholds(delta_c)

    def _chunk_encode_one(self, delta_c, plan_c):
        """One chunk of the CSR-family encode: (K, nc) delta -> (payload
        wire tuple, stored (K,), decoded (K, nc)). Always the jnp reference
        oracles — per-chunk widths are ragged and the caller fuses this into
        its own jit, where the elementwise/cumsum oracles compile to the
        same fused loops the Pallas grids hand-build at flat N."""
        nc, cap = plan_c["nc"], plan_c["cap"]
        with scope(THRESHOLD):
            thr = self._chunk_thresholds(delta_c, plan_c["keep"])
        with scope(COMPACT):
            vals, idx, _ = kref.csr_compact2d_ref(delta_c, thr, cap)
        with scope(MASK):
            dense, stored = kref.csr_capped_mask_ref(delta_c, thr, cap)
        if self.wire_format != "csr_q":
            return (vals, idx), stored, dense
        with scope(QUANTIZE):
            qvals, scales = kref.csr_quantize2d_ref(vals, stored,
                                                    q_dtype=self.q_dtype)
            offs, counts = kref.csr_pack_indices_ref(idx, stored, nc)
            decoded = kref.quantize_dense_ref(dense, scales,
                                              q_dtype=self.q_dtype)
        return (qvals, offs, counts, scales), stored, decoded

    def chunk_encode_body(self, with_residual=False):
        """Per-chunk encode pipeline over (K, N) stacks — the chunked twin
        of :meth:`csr_core`. NOT jitted: the caller fuses the returned
        callable into its own jitted round stage, and the chunk loop is
        unrolled there so XLA's buffer liveness keeps at most one chunk's
        delta/decode temporaries (O(K * max_chunk)) live at a time while
        ``new``/``base`` stay the already-materialized parameter stacks.

        Without residual: ``fn(new, base) -> (payloads, stored, decoded)``
        — per-chunk lists of wire tuples, (K,) stored counts and (K, nc)
        dequantized decodes; payload column indices are chunk-local.

        With residual: ``fn(new, base, rvals, ridx) -> (payloads, stored,
        decoded, (rvals', ridx'))`` where the EF residual pages are
        (K, rcap_total) concatenations of per-chunk CSR segments holding
        GLOBAL column indices (segment c spans ``[roff_c, roff_c+rcap_c)``
        and only carries columns from chunk c; zero-value pads sit at the
        chunk start, so the per-chunk scatter decode is exact).

        ``base`` may be a (K, N) array or a callable ``(s, e) -> (K, e-s)``
        — the versioned engines pass a ring-gather closure so no (K, N)
        base copy is ever materialized.
        """
        plan = self.chunk_plan()

        def base_cols(base, s, e):
            return base(s, e) if callable(base) else base[:, s:e]

        if not with_residual:
            def body(new, base):
                payloads, stored, decoded = [], [], []
                for p in plan:
                    s, e = p["s"], p["e"]
                    delta_c = new[:, s:e] - base_cols(base, s, e)
                    pay, st, dec = self._chunk_encode_one(delta_c, p)
                    payloads.append(pay)
                    stored.append(st)
                    decoded.append(dec)
                return payloads, stored, decoded
            return body

        def body(new, base, rvals, ridx):
            payloads, stored, decoded = [], [], []
            new_rv, new_ri = [], []
            for p in plan:
                s, e, nc = p["s"], p["e"], p["nc"]
                roff, rcap = p["roff"], p["rcap"]
                rv_c = rvals[:, roff:roff + rcap]
                # global -> chunk-local columns; zero-value pads sit at
                # global index 0 and clip to local 0, scattering nothing
                ri_c = jnp.clip(ridx[:, roff:roff + rcap] - s, 0, nc - 1)
                res_c = kref.csr_decode_ref(rv_c, ri_c, nc)
                delta_c = new[:, s:e] - base_cols(base, s, e) + res_c
                pay, st, dec = self._chunk_encode_one(delta_c, p)
                res_new = delta_c - dec     # sub-threshold + overflow
                                            # (+ csr_q rounding error)
                with scope(RESIDUAL):
                    with scope(THRESHOLD):
                        r_thr = local_quantile_thresholds(res_new,
                                                          p["rfrac"])
                    with scope(COMPACT):
                        rv, ri, _ = kref.csr_compact2d_ref(res_new, r_thr,
                                                           rcap)
                payloads.append(pay)
                stored.append(st)
                decoded.append(dec)
                new_rv.append(rv)
                new_ri.append(ri + s)       # store GLOBAL columns
            return payloads, stored, decoded, \
                (jnp.concatenate(new_rv, axis=1),
                 jnp.concatenate(new_ri, axis=1))
        return body

    def chunk_advance_body(self):
        """Chunked twin of the versioned ring's advance encode: one flat
        (n,) transition ``new - prev`` encoded chunk-by-chunk, returning
        ``(recon, chain_payload)`` where ``recon`` is the full decoded
        reconstruction and ``chain_payload`` matches the flat chain-entry
        contract — ``(vals, idx, stored)`` under csr with the per-chunk
        payloads concatenated and indices made global, ``(qvals, offs,
        counts, scales, stored)`` under csr_q with a (num_chunks,) scale
        vector (one absmax per chunk: exactly the bytes the chunked wire
        ships, so the chain's byte ledger stays truthful). Chain entries
        are accounting-only (virtual clients never decode them), so the
        concatenation is never unpacked."""
        plan = self.chunk_plan()
        quantized = self.wire_format == "csr_q"

        def body(new_flat, prev_flat):
            recon, parts, stored_sum = [], [], 0
            for p in plan:
                s, e = p["s"], p["e"]
                delta_c = (new_flat[s:e] - prev_flat[s:e])[None]
                pay, st, dec = self._chunk_encode_one(delta_c, p)
                recon.append(prev_flat[s:e] + dec[0])
                stored_sum = stored_sum + st[0]
                if quantized:
                    parts.append((pay[0][0], pay[1][0], pay[2][0],
                                  pay[3][0]))
                else:
                    # global columns; value-0 pads land at the chunk start
                    parts.append((pay[0][0], pay[1][0] + s))
            cat = tuple(jnp.concatenate([p[i] for p in parts])
                        for i in range(2))
            if quantized:
                scales = jnp.stack([p[3] for p in parts])
                counts = jnp.concatenate([p[2] for p in parts])
                chain = cat + (counts, scales, stored_sum)
            else:
                chain = cat + (stored_sum,)
            return jnp.concatenate(recon), chain
        return body

    def account_batch_csr(self, stored_nnz, params_per_message, n_messages):
        """Record an n_messages-row CSR-family batch whose on-device stored
        counts are ``stored_nnz``: one value + one index per stored element
        at this format's widths, one shared row_ptr per batch, plus — under
        csr_q — the per-row scale and block-count framing. No host sync."""
        if not self.enabled:
            self.account_batch(stored_nnz, params_per_message, n_messages)
            return
        vb, ib = self.elem_bytes()
        self._pending_payload.append((jnp.sum(stored_nnz), vb, ib))
        self.row_ptr_bytes += \
            4 * (n_messages + 1) * self._layout_chunks(params_per_message)
        sb, bb = self.row_overhead_bytes(params_per_message)
        self.scales_bytes += sb * n_messages
        self.block_table_bytes += bb * n_messages
        self.dense_bytes += params_per_message * n_messages * 4
        self.messages += n_messages

    def account_payload(self, stored_total_dev, params_per_message,
                        n_messages, *, row_ptr_rows=0):
        """Record ``n_messages`` CSR-family messages whose total STORED
        ELEMENT COUNT was already reduced on device (one scalar). Used by
        the versioned base store's broadcast accounting, which folds its
        chain-suffix count sum into a single jitted reduction instead of
        handing nnz vectors back for re-summing (every eager op on the
        replicated stage outputs costs a multi-device dispatch). The
        element count is converted to component bytes at this channel's
        per-element widths; ``row_ptr_rows`` adds the CSR framing —
        ``4 * (rows + 1)`` row_ptr plus the csr_q per-row scale/block-table
        overhead. No host sync."""
        vb, ib = self.elem_bytes()
        self._pending_payload.append((stored_total_dev, vb, ib))
        if row_ptr_rows:
            self.row_ptr_bytes += \
                4 * (row_ptr_rows + 1) * self._layout_chunks(params_per_message)
            sb, bb = self.row_overhead_bytes(params_per_message)
            self.scales_bytes += sb * row_ptr_rows
            self.block_table_bytes += bb * row_ptr_rows
        self.dense_bytes += params_per_message * n_messages * 4
        self.messages += n_messages

    def account_dense_payload(self, total_bytes, params_per_message,
                              n_messages):
        """Record ``n_messages`` plain dense messages (full-model resync
        unicasts): host-computable, booked straight into the dense payload
        component."""
        self._dense_payload_host += float(total_bytes)
        self.dense_bytes += params_per_message * n_messages * 4
        self.messages += n_messages

    def wire_breakdown(self):
        """Cumulative bytes-on-wire by component. Materializes pending
        device scalars (one transfer). Every pending entry carries its
        format's per-element widths, so the split is truthful under every
        format: f32 CSR stores one fp32 value + one int32 index per element
        (even split), csr_q stores int8 + int16 (values a third of
        indices-plus-table), and messages on a disabled channel are plain
        dense vectors reported as ``dense_payload_bytes`` instead of being
        mislabelled as CSR components. The csr_q per-row block-count tables
        are index-decoding side information and report under
        ``indices_bytes``; the per-row absmax scales get their own
        ``scales_bytes`` component. Components always sum to
        ``payload_bytes``. The nested ``layout`` entry reports the chunked
        parameter axis the framing was booked under (``num_chunks == 1``
        on an unchunked channel)."""
        self._materialize()
        if self.layout is not None:
            layout = self.layout.describe()
        else:
            layout = {"num_chunks": 1}
        return {"values_bytes": self._values_host,
                "indices_bytes": self._indices_host + self.block_table_bytes,
                "scales_bytes": float(self.scales_bytes),
                "row_ptr_bytes": float(self.row_ptr_bytes),
                "dense_payload_bytes": self._dense_payload_host,
                "payload_bytes": self.payload_bytes,
                "layout": layout}

    def deliver(self, stats):
        """Book a payload's bytes-on-wire at DELIVERY time.

        ``stats`` is the dict returned by :meth:`encode` /
        :meth:`encode_batch` called with ``deliver=False``: encoding is the
        client-side act of building the payload; *this* is the upload
        actually arriving at the server. A lost upload's stats are simply
        never delivered, so its bytes never inflate ACO — the ledger counts
        what crossed the wire, not what was produced. Booking is
        byte-identical to the inline (``deliver=True``) accounting of the
        path that produced ``stats``. No host sync.
        """
        K, n = stats["rows"], stats["total"]
        if not self.enabled:
            self._dense_payload_host += K * n * 4
            self.dense_bytes += K * n * 4
            self.messages += K
        elif "values" in stats:               # CSR family (csr / csr_q)
            self.account_batch_csr(stats["nnz"], n, K)
        else:                                         # dense_masked
            self._account(jnp.sum(stats["nnz"]), n * K, K)

    def _csr_stats(self, payload, stored, n, *, rows):
        """Delivery stats for a CSR-family payload tuple. ``rows=None``
        marks a 1-row stack from the single-message path (entries are
        unstacked before packing the dict). The f32 ``csr`` contract —
        ``values``/``indices`` carry the payload arrays — is unchanged;
        ``csr_q`` reuses those keys for the quantized values / int16
        offsets and adds ``blocks``/``scales``."""
        if rows is None:
            payload = tuple(p[0] for p in payload)
            stored, rows = stored[0], 1
        stats = {"nnz": stored, "total": n, "rows": rows,
                 "values": payload[0], "indices": payload[1]}
        if self.wire_format == "csr_q":
            stats["blocks"], stats["scales"] = payload[2], payload[3]
        return stats

    # -- wire integrity ----------------------------------------------------
    def validate_payload(self, stats):
        """Wire-integrity gauntlet for an incoming payload, applied at the
        trust boundary (an upload arriving from an untrusted device) BEFORE
        decode or accounting. Raises :class:`WireIntegrityError` on any
        malformation; returns ``stats`` unchanged on success.

        Checks, in order: arity (exactly the keys this channel's wire
        format ships — 2 payload arrays for ``csr``, 4 for ``csr_q``),
        buffer shapes (no truncation: every array spans ``rows`` x the
        shared capacity), dtypes (integer indices/counts, the format's
        value width), the implied row_ptr (per-row stored counts
        non-negative and within capacity, i.e. the CSR row_ptr is monotone
        and in-capacity), index bounds (every stored column inside
        ``[0, total)``; csr_q offsets inside their decode block), csr_q
        block-count tables consistent with the stored counts, and finite
        values/scales (a NaN or inf would poison the aggregate through a
        single scatter-add).

        Host-syncing by design: validation runs only on untrusted
        boundary payloads (quarantine candidates, tests), never inside the
        engines' jitted round bodies.
        """
        def fail(msg):
            raise WireIntegrityError(f"malformed upload: {msg}")

        if not isinstance(stats, dict):
            fail(f"payload is {type(stats).__name__}, not a stats mapping")
        for k in ("nnz", "total", "rows"):
            if k not in stats:
                fail(f"missing framing field {k!r}")
        try:
            rows, n = int(stats["rows"]), int(stats["total"])
        except (TypeError, ValueError):
            fail("non-integer rows/total framing")
        if rows < 1 or n < 1:
            fail(f"non-positive framing (rows={rows}, total={n})")

        quantized = self.wire_format == "csr_q"
        payload_keys = {"values", "indices"} | \
            ({"blocks", "scales"} if quantized else set())
        got = {k for k in ("values", "indices", "blocks", "scales")
               if k in stats}
        if got != payload_keys:
            if not self.enabled or self.wire_format not in CSR_FORMATS:
                # dense-family message: only the count field to check
                stored = np.asarray(stats["nnz"], np.float64).reshape(-1)
                if not np.isfinite(stored).all() or (stored < 0).any() \
                        or (stored > n).any():
                    fail("dense message count outside [0, total]")
                return stats
            fail(f"wrong payload arity for {self.wire_format!r}: expected "
                 f"fields {sorted(payload_keys)}, got {sorted(got)}")

        vals = np.asarray(stats["values"])
        idx = np.asarray(stats["indices"])
        stored = np.asarray(stats["nnz"])
        if stored.size != rows:
            fail(f"stored-count vector has {stored.size} entries for "
                 f"{rows} rows")
        if not np.issubdtype(stored.dtype, np.integer):
            fail(f"stored counts must be integers, got {stored.dtype}")
        stored = stored.reshape(-1).astype(np.int64)
        if vals.size == 0 or vals.size % rows or idx.size % rows:
            fail("truncated payload buffer: array size not divisible by "
                 "the row count")
        cap = vals.size // rows
        if idx.size != rows * cap:
            fail(f"truncated payload buffer: values span {cap} "
                 f"columns/row, indices {idx.size // rows}")
        vals = vals.reshape(rows, cap)
        idx = idx.reshape(rows, cap)
        if not np.issubdtype(idx.dtype, np.integer):
            fail(f"indices must be integers, got {idx.dtype}")
        want_val = (np.int8 if self.q_dtype == "int8" else np.float16) \
            if quantized else np.float32
        if vals.dtype != np.dtype(want_val):
            fail(f"values dtype {vals.dtype} != {np.dtype(want_val)} for "
                 f"wire format {self.wire_format!r}")
        # the implied row_ptr (concat([0], cumsum(stored))) must be
        # monotone and land inside the buffer: stored in [0, cap]
        if (stored < 0).any() or (stored > cap).any():
            fail(f"row_ptr not monotone in-capacity: stored counts must "
                 f"lie in [0, {cap}], got "
                 f"[{int(stored.min())}, {int(stored.max())}]")
        live = np.arange(cap)[None, :] < stored[:, None]
        bound = Q_BLOCK if quantized else n
        if ((idx < 0) & live).any() or ((idx >= bound) & live).any():
            fail(f"column {'offset' if quantized else 'index'} out of "
                 f"bounds [0, {bound})")
        if not np.isfinite(vals[live].astype(np.float64)).all():
            fail("non-finite payload value")
        if quantized:
            blocks = np.asarray(stats["blocks"])
            scales = np.asarray(stats["scales"])
            if not np.issubdtype(blocks.dtype, np.integer):
                fail(f"block-count table must be integers, got "
                     f"{blocks.dtype}")
            nblocks = blocks.size // rows if blocks.size % rows == 0 else -1
            if nblocks < 1:
                fail("truncated block-count table")
            blocks = blocks.reshape(rows, nblocks).astype(np.int64)
            if (blocks < 0).any():
                fail("negative block count")
            if (blocks.sum(axis=1) != stored).any():
                fail("block-count table inconsistent with stored counts")
            scales = scales.astype(np.float64).reshape(-1)
            if not np.isfinite(scales).all():
                fail("non-finite quantization scale")
        return stats

    def malform_stats(self, stats, kind):
        """Return a copy of ``stats`` corrupted in one specific way —
        ``kind`` from :data:`MALFORM_KINDS`. This is the fault injector's
        bit-flip/truncation menu: the trainer uses it to materialize a
        ``corrupt``-fated upload's damage deterministically, and the
        quarantine tests sweep it to pin that every class is caught.
        Every kind raises :class:`WireIntegrityError` under every
        CSR-family wire format (pinned by tests/test_wire_integrity.py)."""
        if kind not in MALFORM_KINDS:
            raise ValueError(f"kind must be one of {MALFORM_KINDS}, "
                             f"got {kind!r}")
        out = dict(stats)
        quantized = self.wire_format == "csr_q"
        if kind == "row_ptr":           # negative count: row_ptr decreases
            stored = np.asarray(out["nnz"]).reshape(-1).copy()
            stored[0] = -1
            out["nnz"] = stored
        elif kind == "oob_index":       # column past the model / block edge
            idx = np.array(out["indices"]).reshape(
                int(out["rows"]), -1).copy()
            idx[0, 0] = Q_BLOCK if quantized else int(out["total"])
            stored = np.asarray(out["nnz"]).reshape(-1).copy()
            stored[0] = max(int(stored[0]), 1)   # the bad column is live
            out["indices"], out["nnz"] = idx, stored
        elif kind == "nan_value":       # f32: NaN value; csr_q: inf scale
            if quantized:
                scales = np.array(out["scales"], np.float32).reshape(-1)
                scales[0] = np.inf
                out["scales"] = scales
            else:
                vals = np.array(out["values"], np.float32).reshape(
                    int(out["rows"]), -1)
                vals[0, 0] = np.nan
                out["values"] = vals
                stored = np.asarray(out["nnz"]).reshape(-1).copy()
                stored[0] = max(int(stored[0]), 1)
                out["nnz"] = stored
        elif kind == "bad_scale":       # csr_q: NaN scale; csr: spurious
            if quantized:               # scale field (wrong arity)
                scales = np.array(out["scales"], np.float32).reshape(-1)
                scales[0] = np.nan
                out["scales"] = scales
            else:
                out["scales"] = np.ones(int(out["rows"]), np.float32)
        elif kind == "arity":           # a payload array went missing
            del out["indices"]
        elif kind == "truncated":       # values buffer cut short in flight
            vals = np.asarray(out["values"]).reshape(int(out["rows"]), -1)
            out["values"] = vals[:, :-1] if vals.shape[1] > 1 \
                else np.zeros((int(out["rows"]), 0), vals.dtype)
        elif kind == "dtype":           # indices arrive as floats
            out["indices"] = np.asarray(out["indices"], np.float32)
        return out

    # -- checkpoint / restore ----------------------------------------------
    def ledger_state(self, *, defer=False):
        """Snapshot the cumulative byte ledgers as plain host numbers.
        Materializes the pending device scalars first — value-neutral,
        because the fold is order-preserving and future messages append
        after it either way.

        ``defer=True`` (the checkpoint writer path) does not block on
        in-flight device work: the pending fold is captured as
        :class:`fleet_ckpt.Lazy` thunks over references taken now and
        resolved on the writer thread — same entries, same order, same
        float64 host arithmetic as the eager fold — while the LIVE
        ledger's pending list is left untouched."""
        if not defer:
            self._materialize()
            values = float(self._values_host)
            indices = float(self._indices_host)
        else:
            from repro.core import fleet_ckpt
            vb, ib = float(self._values_host), float(self._indices_host)
            pend = list(self._pending_payload)

            def _fold(base, col):
                # per-element np.asarray: the writer thread must never
                # LAUNCH device programs (a jnp.stack dispatched
                # concurrently with the training thread's multi-device
                # round can interleave collective rendezvous and deadlock
                # XLA:CPU) — transfers only. Counts are exact integers, so
                # the float64 fold matches the eager stack path exactly.
                out = base
                for entry in pend:
                    out += float(np.asarray(entry[0])) * entry[col]
                return out

            values = fleet_ckpt.Lazy(lambda: _fold(vb, 1))
            indices = fleet_ckpt.Lazy(lambda: _fold(ib, 2))
        return {"values_host": values,
                "indices_host": indices,
                "dense_payload_host": float(self._dense_payload_host),
                "dense_bytes": int(self.dense_bytes),
                "row_ptr_bytes": int(self.row_ptr_bytes),
                "scales_bytes": int(self.scales_bytes),
                "block_table_bytes": int(self.block_table_bytes),
                "messages": int(self.messages)}

    def load_ledger_state(self, d):
        """Restore :meth:`ledger_state` output (drops any pending
        unmaterialized entries — the checkpoint is the truth)."""
        self._pending_payload = []
        self._values_host = float(d["values_host"])
        self._indices_host = float(d["indices_host"])
        self._dense_payload_host = float(d["dense_payload_host"])
        self.dense_bytes = int(d["dense_bytes"])
        self.row_ptr_bytes = int(d["row_ptr_bytes"])
        self.scales_bytes = int(d["scales_bytes"])
        self.block_table_bytes = int(d["block_table_bytes"])
        self.messages = int(d["messages"])

    # -- single-message path (reference implementation) --------------------
    def encode(self, new_params, base_params, residual=None, *,
               deliver=True):
        """Returns (sparse_delta_tree, stats[, residual']). ACO accounted
        at once when ``deliver=True``; with ``deliver=False`` nothing is
        booked until the caller passes ``stats`` to :meth:`deliver` (or
        drops them — a lost upload).

        ``residual``: error-feedback state (beyond-paper): the masked-out
        part of every previous delta is carried forward and re-offered next
        round, so sparsification error does not accumulate into model drift
        (Karimireddy et al.-style EF). Pass a zero tree to enable; the new
        residual is returned alongside.

        ``stats["nnz"]`` is a device scalar (reads sync on demand). Under
        the CSR wire format it is the on-wire (stored) count, the returned
        sparse tree is the server-side decode of the actual payload, and —
        with EF — the returned residual is the capacity-truncated store
        (sub-threshold mass plus any capacity overflow).
        """
        delta = tree_sub(new_params, base_params)
        if residual is not None:
            delta = tree_add(delta, residual)
        flat = flatten_tree(delta)
        n = flat.shape[0]
        if not self.enabled:
            stats = {"nnz": n, "total": n, "rows": 1}
            if deliver:
                self.deliver(stats)
            out = (delta, stats)
            return out + (jax.tree.map(jnp.zeros_like, delta),) \
                if residual is not None else out
        if self.wire_format in CSR_FORMATS:
            # the flat delta (incl. residual) goes through the shared CSR
            # core as a 1-row stack — identical math to the batched path
            zero = jnp.zeros_like(flat)[None]
            if residual is not None:
                payload, stored, decoded, _, res_dense = self.csr_core(
                    True)(flat[None], zero, zero)
            else:
                payload, stored, decoded = self.csr_core(False)(
                    flat[None], zero)
            sparse_tree = unflatten_like(decoded[0], delta)
            stats = self._csr_stats(payload, stored, n, rows=None)
            if deliver:
                self.deliver(stats)
            if residual is not None:
                return sparse_tree, stats, unflatten_like(res_dense[0], delta)
            return sparse_tree, stats
        thr = self._abs_threshold(flat)
        if self.use_kernel:
            masked, nnz_blocks = kops.sparse_delta(flat, thr)
            nnz = jnp.sum(nnz_blocks)
        else:
            masked, nnz = _mask_count(flat, thr)
        stats = {"nnz": nnz, "total": n, "rows": 1}
        if deliver:
            self.deliver(stats)
        sparse_tree = unflatten_like(masked, delta)
        if residual is not None:
            new_residual = unflatten_like(flat - masked, delta)
            return sparse_tree, stats, new_residual
        return sparse_tree, stats

    def encode_paged(self, new_params, base_params, res_vals, res_idx, *,
                     deliver=True):
        """Single-message CSR-family encode against a PAGED residual: the
        client's error-feedback state arrives as one (rcap,) CSR page
        (values, indices) from ``core.client_store.PagedClientStore`` and
        the truncated new residual returns as a page for the writeback
        queue. Returns ``(sparse_delta_tree, stats, (rvals', ridx'))``.

        Bit-identical to :meth:`encode` with the page's dense expansion as
        ``residual``: the page scatter-add decodes to exactly the dense
        residual row the resident layout stores (the capped-mask round-trip
        contract), and adding it to the flat delta is elementwise — the
        same values :meth:`encode` produces by adding trees leaf-wise and
        flattening. Only valid under the CSR wire formats (the paged dense
        layout goes through :meth:`encode` unchanged)."""
        delta = tree_sub(new_params, base_params)
        flat = flatten_tree(delta)
        n = flat.shape[0]
        flat = flat + kops.csr_decode(res_vals[None], res_idx[None], n)[0]
        zero = jnp.zeros_like(flat)[None]
        payload, stored, decoded, res_payload, _ = self.csr_core(True)(
            flat[None], zero, zero)
        stats = self._csr_stats(payload, stored, n, rows=None)
        if deliver:
            self.deliver(stats)
        return unflatten_like(decoded[0], delta), stats, \
            (res_payload[0][0], res_payload[1][0])

    # -- batched path ------------------------------------------------------
    def _batch_core(self, with_residual):
        """Jitted (delta -> threshold -> mask -> count) pipeline, built once
        per (instance, residual?) so the whole encode is ONE dispatch."""
        key = bool(with_residual)
        core = self._batch_cores.get(key)
        if core is not None:
            return core
        frac = self._quantile_frac()
        threshold = None if frac is not None else float(self.threshold)
        use_kernel = self.use_kernel

        def encode(delta):
            if use_kernel and frac is not None:
                # fused per-shard form: local per-row quantile thresholds
                # feed the 2D-grid kernel directly (one dispatch; safe
                # under shard_map because thresholds are per-row)
                masked, nnz_blocks, _ = kops.sparse_delta_topfrac(delta, frac)
                return masked, jnp.sum(nnz_blocks, axis=1)
            if frac is not None:
                thr = _sampled_quantile_batch(delta, 1.0 - frac)
            else:
                thr = jnp.full((delta.shape[0],), threshold, jnp.float32)
            if use_kernel:
                masked, nnz_blocks = kops.sparse_delta_batch(delta, thr)
                nnz = jnp.sum(nnz_blocks, axis=1)
            else:
                masked, nnz = _mask_count_batch(delta, thr)
            return masked, nnz

        if with_residual:
            @jax.jit
            def core(new_flat, base_flat, residual_flat):
                delta = new_flat - base_flat + residual_flat
                masked, nnz = encode(delta)
                return masked, nnz, delta - masked
        else:
            @jax.jit
            def core(new_flat, base_flat):
                return encode(new_flat - base_flat)

        self._batch_cores[key] = core
        return core

    def encode_batch(self, new_flat, base_flat, residual_flat=None, *,
                     deliver=True):
        """Encode K client deltas at once from (K, N) flat stacks.
        ``deliver=False`` skips the inline accounting — the caller books
        the returned ``stats`` via :meth:`deliver` when (and only if) the
        payload actually arrives.

        Returns (masked (K, N), stats[, residual' (K, N)]) where
        ``stats["nnz"]`` is the per-client (K,) device nnz vector. Per-client
        quantile thresholds, masking and nnz counting all stay on device —
        zero host syncs — in one jitted call wrapping the 2D-grid kernel
        (``use_kernel``) or the vmapped jnp oracle.

        Under the CSR wire format the first return value is the decoded
        payload (== the masked stack unless a row overflowed its capacity),
        ``stats["nnz"]`` is the stored count, and ``stats`` also carries the
        actual (values, indices) payload arrays.
        """
        K, n = new_flat.shape
        if not self.enabled:
            delta = new_flat - base_flat
            if residual_flat is not None:
                delta = delta + residual_flat
            stats = {"nnz": jnp.full((K,), n), "total": n, "rows": K}
            if deliver:
                self.deliver(stats)
            out = (delta, stats)
            return out + (jnp.zeros_like(delta),) \
                if residual_flat is not None else out
        if self.wire_format in CSR_FORMATS:
            if residual_flat is not None:
                payload, stored, decoded, _, res_dense = self.csr_core(
                    True)(new_flat, base_flat, residual_flat)
            else:
                payload, stored, decoded = self.csr_core(False)(
                    new_flat, base_flat)
            stats = self._csr_stats(payload, stored, n, rows=K)
            if deliver:
                self.deliver(stats)
            if residual_flat is not None:
                return decoded, stats, res_dense
            return decoded, stats
        if residual_flat is not None:
            masked, nnz, new_residual = self._batch_core(True)(
                new_flat, base_flat, residual_flat)
        else:
            masked, nnz = self._batch_core(False)(new_flat, base_flat)
        stats = {"nnz": nnz, "total": n, "rows": K}
        if deliver:
            self.deliver(stats)
        if residual_flat is not None:
            return masked, stats, new_residual
        return masked, stats

    def apply(self, base_params, sparse_delta_tree):
        return tree_add(base_params, sparse_delta_tree)

    def batch_core(self, with_residual=False):
        """The pure jitted encode pipeline (delta -> thresholds -> mask ->
        per-client nnz), for callers that fuse it into a larger jitted round
        stage. The caller owns accounting: pass the returned nnz to
        ``account_batch``.

        Shard-safe: thresholds are per-row statistics, so calling this
        inside a ``shard_map`` over the client axis (each shard encoding
        its local (K/D, N) rows) produces exactly the unsharded result —
        the sharded fleet engine relies on this.
        """
        return self._batch_core(with_residual)

    def account_batch(self, nnz, params_per_message, n_messages):
        """Record n_messages messages of params_per_message params whose
        combined on-device nnz vector is ``nnz`` (ignored when sparsification
        is disabled — then every message is dense). No host sync."""
        if not self.enabled:
            self._dense_payload_host += n_messages * params_per_message * 4
            self.dense_bytes += n_messages * params_per_message * 4
            self.messages += n_messages
            return
        self._account(jnp.sum(nnz), params_per_message * n_messages,
                      n_messages)

    # -- deferred accounting -----------------------------------------------
    def _account(self, nnz_dev, total_params, n_messages):
        # dense_masked: fp32 value + int32 index per survivor
        self._pending_payload.append((nnz_dev, 4, 4))
        self.dense_bytes += total_params * 4
        self.messages += n_messages

    def _materialize(self):
        if self._pending_payload:
            counts = np.asarray(jnp.stack(
                [c for c, _, _ in self._pending_payload]), np.float64)
            for cnt, (_, vb, ib) in zip(counts, self._pending_payload):
                self._values_host += float(cnt) * vb
                self._indices_host += float(cnt) * ib
            self._pending_payload = []

    @property
    def payload_bytes(self) -> float:
        self._materialize()
        return self._values_host + self._indices_host + \
            self._dense_payload_host + self.row_ptr_bytes + \
            self.scales_bytes + self.block_table_bytes

    @property
    def aco(self) -> float:
        return self.payload_bytes / self.dense_bytes if self.dense_bytes \
            else 0.0
