"""Property-based kernel tests: hypothesis strategies (or the deterministic
shim in environments without hypothesis) driving the Pallas sparse-delta and
staleness-agg kernels against the pure-jnp oracles in kernels/ref.py.

Covers what the hand-picked sweeps in test_kernels.py do not: random shapes,
block-boundary sizes (N % 512 != 0, including N < 512 and N = multiple ± 1),
degenerate thresholds (0.0 all-pass — where pad columns must NOT count —
and +inf all-drop), per-client quantile thresholds, and shard-invariance of
the per-row quantile encode under a client mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests._hypothesis_compat import given, settings, st

from repro.kernels import ops
from repro.kernels import ref as R

BLK = 512


def _delta(seed, k, n, scale):
    x = jax.random.normal(jax.random.PRNGKey(seed), (k, n)) * scale
    return x


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=1 << 16),
    k=st.integers(min_value=1, max_value=7),
    nblk=st.integers(min_value=0, max_value=3),
    off=st.sampled_from([-1, 0, 1, 17, 255, 511]),
    thr=st.sampled_from([0.0, 0.3, 1.5, np.inf]),
    scale=st.floats(min_value=0.1, max_value=10.0),
)
def test_sparse_delta2d_matches_ref(seed, k, nblk, off, thr, scale):
    n = max(nblk * BLK + off, 1)
    x = _delta(seed, k, n, scale)
    thrs = jnp.full((k,), thr, jnp.float32)
    masked, nnz = ops.sparse_delta_batch(x, thrs)
    rmasked, rnnz = R.sparse_delta2d_ref(x, thrs)
    np.testing.assert_allclose(np.asarray(masked), np.asarray(rmasked))
    np.testing.assert_array_equal(np.asarray(nnz), np.asarray(rnnz))
    # degenerate ends: all-pass counts exactly N (pad never counts),
    # all-drop counts zero
    if thr == 0.0:
        assert int(np.asarray(nnz).sum()) == k * n
    if np.isinf(thr):
        assert int(np.asarray(nnz).sum()) == 0
        assert float(jnp.abs(masked).max()) == 0.0


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=1 << 16),
    nblk=st.integers(min_value=0, max_value=4),
    off=st.sampled_from([-1, 0, 1, 123]),
    thr=st.sampled_from([0.0, 0.7, np.inf]),
)
def test_sparse_delta_1d_matches_ref(seed, nblk, off, thr):
    n = max(nblk * BLK + off, 1)
    x = _delta(seed, 1, n, 1.0)[0]
    masked, nnz = ops.sparse_delta(x, thr)
    rmasked, rnnz = R.sparse_delta_ref(x, thr)
    np.testing.assert_allclose(np.asarray(masked), np.asarray(rmasked))
    np.testing.assert_array_equal(np.asarray(nnz), np.asarray(rnnz))


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=1 << 16),
    k=st.integers(min_value=1, max_value=6),
    n=st.sampled_from([512, 700, 1024, 2048 + 13]),
    frac=st.floats(min_value=0.05, max_value=0.95),
)
def test_quantile_fused_matches_two_step(seed, k, n, frac):
    """The fused per-shard top-frac encode == per-row sampled quantile fed
    to the plain kernel == the comm layer's vmapped quantile path."""
    from repro.core.sparse_comm import _sampled_quantile_batch
    x = _delta(seed, k, n, 1.0)
    masked, nnz, thr = ops.sparse_delta_topfrac(x, frac)
    thr_comm = _sampled_quantile_batch(x, 1.0 - frac)
    np.testing.assert_allclose(np.asarray(thr), np.asarray(thr_comm),
                               rtol=1e-6)
    rmasked, rnnz = R.sparse_delta2d_ref(x, thr_comm)
    np.testing.assert_allclose(np.asarray(masked), np.asarray(rmasked))
    np.testing.assert_array_equal(np.asarray(nnz), np.asarray(rnnz))
    kept = np.asarray(nnz).sum() / (k * n)
    assert abs(kept - frac) < 0.2


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=1 << 16),
    k=st.integers(min_value=1, max_value=9),
    nblk=st.integers(min_value=0, max_value=3),
    off=st.sampled_from([-1, 0, 1, 300]),
    wmode=st.sampled_from(["uniform", "zeros", "mixed", "negative"]),
)
def test_staleness_agg_matches_ref(seed, k, nblk, off, wmode):
    n = max(nblk * BLK + off, 1)
    d = _delta(seed, k, n, 2.0)
    if wmode == "uniform":
        w = jnp.full((k,), 1.0 / k)
    elif wmode == "zeros":
        w = jnp.zeros((k,))
    elif wmode == "negative":
        w = -jax.random.uniform(jax.random.PRNGKey(seed + 1), (k,))
    else:
        w = jax.random.uniform(jax.random.PRNGKey(seed + 1), (k,)) * \
            jnp.asarray([i % 2 for i in range(k)], jnp.float32)
    out = ops.staleness_agg(d, w)
    ref = R.staleness_agg_ref(d, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref[:n]),
                               rtol=1e-5, atol=1e-5)
    if wmode == "zeros":
        assert float(jnp.abs(out).max()) == 0.0


# --- CSR compaction --------------------------------------------------------
def _delta_with_zeros(seed, k, n, zero_frac=0.3):
    """Random deltas with injected exact zeros (they pass degenerate
    thresholds but must never go on the wire)."""
    x = _delta(seed, k, n, 1.0)
    u = jax.random.uniform(jax.random.PRNGKey(seed + 7), (k, n))
    return jnp.where(u < zero_frac, 0.0, x)


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=1 << 16),
    k=st.integers(min_value=1, max_value=6),
    nblk=st.integers(min_value=0, max_value=3),
    off=st.sampled_from([-1, 0, 1, 17, 255, 511]),
    thr=st.sampled_from([0.0, 0.3, 1.5, np.inf]),
)
def test_csr_compact_roundtrip_matches_masked_oracle(seed, k, nblk, off,
                                                     thr):
    """Full-capacity compact -> decode reproduces the masked-dense oracle
    EXACTLY; kernel and jnp oracle agree elementwise; indices are strictly
    ascending within each stored prefix and padding is zeroed."""
    n = max(nblk * BLK + off, 1)
    x = _delta_with_zeros(seed, k, n)
    thrs = jnp.full((k,), thr, jnp.float32)
    vals, idx, nnz = ops.csr_compact(x, thrs, n)
    rvals, ridx, rnnz = R.csr_compact2d_ref(x, thrs, n)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(rvals))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_array_equal(np.asarray(nnz), np.asarray(rnnz))
    masked, _ = R.sparse_delta2d_ref(x, thrs)
    decoded = np.asarray(R.csr_decode_ref(vals, idx, n))
    np.testing.assert_array_equal(decoded, np.asarray(masked))
    nnz_h, vals_h, idx_h = (np.asarray(a) for a in (nnz, vals, idx))
    # zeros never stored, even at the all-pass threshold
    expect_nnz = np.count_nonzero(np.asarray(masked), axis=1)
    np.testing.assert_array_equal(nnz_h, expect_nnz)
    for row in range(k):
        s = nnz_h[row]
        assert (np.diff(idx_h[row, :s]) > 0).all()
        assert np.all(vals_h[row, s:] == 0)
        assert np.all(idx_h[row, s:] == 0)


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=1 << 16),
    k=st.integers(min_value=1, max_value=5),
    n=st.sampled_from([300, 512, 1000, 1537]),
    cap_frac=st.floats(min_value=0.05, max_value=0.8),
)
def test_csr_overflow_spill_invariants(seed, k, n, cap_frac):
    """Capacity overflow keeps the first ``cap`` survivors in column order;
    the spill (masked - decode) is exactly the tail, so decode + spill
    reconstructs the masked oracle bit-for-bit (what the EF residual
    relies on)."""
    cap = max(1, int(cap_frac * n))
    x = _delta_with_zeros(seed, k, n)
    thrs = jnp.full((k,), 0.2, jnp.float32)
    vals, idx, nnz = ops.csr_compact(x, thrs, cap)
    rvals, ridx, rnnz = R.csr_compact2d_ref(x, thrs, cap)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(rvals))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_array_equal(np.asarray(nnz), np.asarray(rnnz))
    masked, _ = R.sparse_delta2d_ref(x, thrs)
    masked = np.asarray(masked)
    decoded = np.asarray(R.csr_decode_ref(vals, idx, n))
    stored = np.minimum(np.asarray(nnz), cap)
    spill = masked - decoded
    for row in range(k):
        kept_cols = np.flatnonzero(masked[row])
        # decode holds exactly the first `stored` kept columns...
        np.testing.assert_array_equal(
            np.flatnonzero(decoded[row]), kept_cols[:stored[row]])
        # ...and the spill is exactly the overflow tail
        np.testing.assert_array_equal(
            np.flatnonzero(spill[row]), kept_cols[stored[row]:])
    np.testing.assert_array_equal(decoded + spill, masked)


# --- csr_q quantization + index packing --------------------------------------
@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=1 << 16),
    k=st.integers(min_value=1, max_value=6),
    nblk=st.integers(min_value=0, max_value=3),
    off=st.sampled_from([-1, 0, 1, 17, 255, 511]),
    cap_frac=st.floats(min_value=0.1, max_value=1.0),
    q_dtype=st.sampled_from(["int8", "fp16"]),
)
def test_csr_quantize_kernel_matches_ref(seed, k, nblk, off, cap_frac,
                                         q_dtype):
    """Pallas quantize/pack kernel == the jnp oracle elementwise (int8 and
    fp16), index unpack is EXACT on the stored prefixes (in-block offsets +
    block-count table lose nothing), and scales bound the payload: every
    int8 row's absmax quantizes to ±127 exactly."""
    n = max(nblk * BLK + off, 1)
    cap = max(1, int(cap_frac * n))
    x = _delta_with_zeros(seed, k, n)
    thrs = jnp.full((k,), 0.2, jnp.float32)
    vals, idx, nnz = R.csr_compact2d_ref(x, thrs, cap)
    _, stored = R.csr_capped_mask_ref(x, thrs, cap)
    qv, qo, qc, sc = ops.csr_quantize(vals, idx, stored, n, q_dtype=q_dtype)
    rqv, rsc = R.csr_quantize2d_ref(vals, stored, q_dtype=q_dtype)
    rqo, rqc = R.csr_pack_indices_ref(idx, stored, n)
    np.testing.assert_array_equal(np.asarray(qv), np.asarray(rqv))
    np.testing.assert_array_equal(np.asarray(qo), np.asarray(rqo))
    np.testing.assert_array_equal(np.asarray(qc), np.asarray(rqc))
    np.testing.assert_allclose(np.asarray(sc), np.asarray(rsc), rtol=1e-7)
    # index unpack is exact wherever something is stored
    abs_idx = np.asarray(R.csr_unpack_indices_ref(qo, qc))
    st_h, idx_h = np.asarray(stored), np.asarray(idx)
    for row in range(k):
        np.testing.assert_array_equal(abs_idx[row, :st_h[row]],
                                      idx_h[row, :st_h[row]])
    if q_dtype == "int8":
        qv_h, vals_h = np.asarray(qv), np.asarray(vals)
        for row in range(k):
            s = st_h[row]
            if s and np.abs(vals_h[row, :s]).max() > 0:
                assert np.abs(qv_h[row, :s]).max() == 127
        assert np.asarray(sc).min() >= 0


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=1 << 16),
    k=st.integers(min_value=1, max_value=5),
    n=st.sampled_from([300, 512, 1000, 1537]),
    cap_frac=st.floats(min_value=0.1, max_value=0.9),
    q_dtype=st.sampled_from(["int8", "fp16"]),
)
def test_csr_q_roundtrip_error_lands_in_residual(seed, k, n, cap_frac,
                                                 q_dtype):
    """The EF contract under csr_q: dequantize(quantize(payload)) scattered
    back + the residual (delta - decoded) reconstructs the raw delta
    EXACTLY — sub-threshold mass, capacity overflow and quantization
    rounding error all land in the residual, nothing is silently lost.
    Also pins the scale-twin identity the engines rely on: quantizing the
    capped-mask dense rows elementwise == scattering the dequantized
    payload."""
    cap = max(1, int(cap_frac * n))
    x = _delta_with_zeros(seed, k, n)
    thrs = jnp.full((k,), 0.2, jnp.float32)
    vals, idx, _ = R.csr_compact2d_ref(x, thrs, cap)
    dense, stored = R.csr_capped_mask_ref(x, thrs, cap)
    qv, sc = R.csr_quantize2d_ref(vals, stored, q_dtype=q_dtype)
    qo, qc = R.csr_pack_indices_ref(idx, stored, n)
    # scatter the dequantized payload
    deq = np.asarray(R.csr_dequantize_ref(qv, sc))
    abs_idx = np.asarray(R.csr_unpack_indices_ref(qo, qc))
    st_h = np.asarray(stored)
    decoded = np.zeros((k, n), np.float32)
    for row in range(k):
        decoded[row, abs_idx[row, :st_h[row]]] = deq[row, :st_h[row]]
    # scale-twin identity: elementwise round-trip of the dense twin is
    # bit-identical to the scattered dequantized payload
    twin = np.asarray(R.quantize_dense_ref(dense, sc, q_dtype=q_dtype))
    np.testing.assert_array_equal(twin, decoded)
    # EF closure: decoded + residual == the raw delta, bit-for-bit
    residual = np.asarray(x) - decoded
    np.testing.assert_array_equal(decoded + residual, np.asarray(x))
    if q_dtype == "int8":
        # quantization error per element is bounded by half a step
        for row in range(k):
            err = np.abs(decoded[row] - np.asarray(dense)[row])
            assert err.max() <= float(sc[row]) * 0.5 + 1e-7


def test_csr_row_ptr():
    nnz = jnp.asarray([3, 0, 5, 1], jnp.int32)
    np.testing.assert_array_equal(np.asarray(R.csr_row_ptr_ref(nnz)),
                                  [0, 3, 3, 8, 9])


# --- chunked parameter axis (ParamLayout + per-chunk encode) ----------------
from repro.core.param_layout import ParamLayout  # noqa: E402


def _template(sizes):
    """Pytree of 1-D leaves with collision-free, order-stable names."""
    return {f"leaf{i:02d}": jax.ShapeDtypeStruct((s,), jnp.float32)
            for i, s in enumerate(sizes)}


@settings(max_examples=25, deadline=None)
@given(
    sizes=st.lists(st.integers(min_value=1, max_value=900),
                   min_size=1, max_size=8),
    chunk_size=st.integers(min_value=64, max_value=700),
)
def test_param_layout_covers_and_aligns(sizes, chunk_size):
    """from_template partitions [0, N) exactly (contiguity is validated by
    the dataclass itself), never exceeds chunk_size, and never lets a chunk
    hold a PART of one leaf plus any piece of another: a chunk either
    contains whole leaves or is wholly inside one oversized (split) leaf."""
    lay = ParamLayout.from_template(_template(sizes), chunk_size)
    assert lay.n == sum(sizes)
    assert lay.bounds[0][0] == 0 and lay.bounds[-1][1] == lay.n
    assert all(e - s <= chunk_size for s, e in lay.bounds)
    edges, off = [], 0
    for s_ in sizes:
        edges.append((off, off + s_))
        off += s_
    for cs, ce in lay.bounds:
        for ls, le in edges:
            if cs < le and ls < ce:           # overlap
                assert (ls >= cs and le <= ce) or (cs >= ls and ce <= le)


@settings(max_examples=25, deadline=None)
@given(
    size=st.integers(min_value=65, max_value=5000),
    chunk_size=st.integers(min_value=64, max_value=512),
)
def test_param_layout_ragged_last_chunk(size, chunk_size):
    """An oversized leaf splits into full-width pieces plus one ragged tail
    of exactly ``size % chunk_size`` (when the leaf doesn't divide)."""
    lay = ParamLayout.from_template(_template([size]), chunk_size)
    widths = lay.sizes
    assert sum(widths) == size
    if size <= chunk_size:
        assert widths == (size,)
    else:
        assert all(w == chunk_size for w in widths[:-1])
        assert widths[-1] == (size % chunk_size or chunk_size)


@settings(max_examples=25, deadline=None)
@given(
    sizes=st.lists(st.integers(min_value=16, max_value=400),
                   min_size=2, max_size=6),
    keep=st.floats(min_value=0.05, max_value=0.35),
)
def test_param_layout_override_never_shares_a_chunk(sizes, keep):
    """A keep_frac override isolates its leaf: every chunk carrying the
    overridden leaf carries ONLY that leaf, and exactly those chunks get
    the per-chunk keep_frac (per-layer sparsity falls out of alignment)."""
    lay = ParamLayout.from_template(_template(sizes), max(sizes) * 2,
                                    overrides={"leaf01": keep})
    hit = 0
    for kf, name in zip(lay.keep_frac, lay.names):
        parts = name.split("+")
        if "leaf01" in parts:
            assert parts == ["leaf01"]
            assert kf == keep
            hit += 1
        else:
            assert kf is None
    assert hit >= 1
    assert lay.describe()["overridden_chunks"] == hit
    assert not lay.is_flat or len(sizes) == 0


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=1 << 16),
    k=st.integers(min_value=1, max_value=4),
    sizes=st.lists(st.integers(min_value=128, max_value=500),
                   min_size=2, max_size=4),
    keep=st.floats(min_value=0.1, max_value=0.35),
)
def test_chunk_encode_body_matches_per_chunk_oracle(seed, k, sizes, keep):
    """The fused chunked encode == the per-chunk reference pipeline run on
    each slice independently: same stored counts, same decodes, and the
    overridden chunk's kept fraction tracks ITS keep_frac, not the channel
    default — chunk boundaries leak nothing across slices. A ring-gather
    closure base must be bit-identical to the materialized (K, N) base."""
    from repro.core.sparse_comm import SparseComm
    lay = ParamLayout.from_template(_template(sizes), max(sizes),
                                    overrides={"leaf00": keep})
    n = lay.n
    comm = SparseComm("p0.2", use_kernel=False, layout=lay)
    new = _delta(seed, k, n, 1.0)
    base = _delta(seed + 1, k, n, 1.0)
    body = comm.chunk_encode_body(False)
    payloads, stored, decoded = body(new, base)
    delta = new - base
    plan = comm.chunk_plan()
    assert len(payloads) == lay.num_chunks
    for p, st_c, dec in zip(plan, stored, decoded):
        dc = delta[:, p["s"]:p["e"]]
        thr = comm._chunk_thresholds(dc, p["keep"])
        rdense, rstored = R.csr_capped_mask_ref(dc, thr, p["cap"])
        np.testing.assert_array_equal(np.asarray(st_c), np.asarray(rstored))
        np.testing.assert_array_equal(np.asarray(dec), np.asarray(rdense))
        assert int(np.asarray(st_c).max()) <= p["cap"]
        if p["keep"] is not None and p["nc"] >= 128:
            kept = np.asarray(st_c).mean() / p["nc"]
            assert abs(kept - keep) < 0.2
    _, stored2, decoded2 = body(new, lambda s, e: base[:, s:e])
    for a, b in zip(decoded, decoded2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(stored, stored2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=1 << 16),
    k=st.integers(min_value=1, max_value=3),
    sizes=st.lists(st.integers(min_value=128, max_value=400),
                   min_size=2, max_size=3),
)
def test_chunk_encode_residual_indices_stay_in_chunk(seed, k, sizes):
    """EF under the layout: the concatenated residual page stores GLOBAL
    column indices and segment c only ever references columns of chunk c
    (value-0 pads land at the chunk start), so the next round's per-chunk
    scatter decode never crosses a boundary. Closure: for each chunk,
    decode + residual-decode == the pre-encode delta wherever the residual
    had room (rfrac caps the tail like the flat path)."""
    from repro.core.sparse_comm import SparseComm
    lay = ParamLayout.from_template(_template(sizes), max(sizes))
    n = lay.n
    comm = SparseComm("p0.2", use_kernel=False, layout=lay)
    rcap = comm.residual_capacity_total()
    new = _delta(seed, k, n, 1.0)
    base = _delta(seed + 1, k, n, 1.0)
    rvals = jnp.zeros((k, rcap), jnp.float32)
    ridx = jnp.zeros((k, rcap), jnp.int32)
    body = comm.chunk_encode_body(True)
    payloads, stored, decoded, (rv2, ri2) = body(new, base, rvals, ridx)
    assert rv2.shape == (k, rcap) and ri2.shape == (k, rcap)
    ri_h, rv_h = np.asarray(ri2), np.asarray(rv2)
    for p in comm.chunk_plan():
        seg_i = ri_h[:, p["roff"]:p["roff"] + p["rcap"]]
        seg_v = rv_h[:, p["roff"]:p["roff"] + p["rcap"]]
        live = seg_v != 0
        assert np.all(seg_i[live] >= p["s"])
        assert np.all(seg_i[live] < p["e"])


# --- shard invariance ------------------------------------------------------
@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs a client mesh")
def test_sparse_encode_shard_invariant():
    """Per-row quantile thresholds + masking give the SAME result whether
    the (K, N) stack is encoded whole or row-sharded across the client
    mesh — thresholds are per-row statistics, so shard_map adds no
    cross-device coupling."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core.sparse_comm import SparseComm
    from repro.distributed.sharding import CLIENT_AXIS, client_mesh

    mesh = client_mesh()
    D = mesh.devices.size
    core = SparseComm("p0.3", use_kernel=True).batch_core(False)
    K, N = 2 * D, 1000
    new = jax.random.normal(jax.random.PRNGKey(0), (K, N))
    base = jax.random.normal(jax.random.PRNGKey(1), (K, N))

    whole_masked, whole_nnz = core(new, base)
    sharded = jax.jit(shard_map(
        core, mesh=mesh,
        in_specs=(P(CLIENT_AXIS, None), P(CLIENT_AXIS, None)),
        out_specs=(P(CLIENT_AXIS, None), P(CLIENT_AXIS)),
        check_vma=False))
    sh_masked, sh_nnz = sharded(new, base)
    np.testing.assert_allclose(np.asarray(sh_masked),
                               np.asarray(whole_masked), atol=1e-7)
    np.testing.assert_array_equal(np.asarray(sh_nnz), np.asarray(whole_nnz))


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs a client mesh")
def test_staleness_agg_psum_matches_whole():
    """blend_flat_sharded's psum-of-local-weighted-sums == the unsharded
    weighted sum, to reduction-order tolerance."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core import aggregation as agg
    from repro.distributed.sharding import CLIENT_AXIS, client_mesh

    mesh = client_mesh()
    D = mesh.devices.size
    K, N = 3 * D, 777
    deltas = jax.random.normal(jax.random.PRNGKey(2), (K, N))
    w = jax.random.uniform(jax.random.PRNGKey(3), (K,))
    server = jax.random.normal(jax.random.PRNGKey(4), (N,))
    fw = jnp.float32(0.35)

    def stage(sp, d, wl, f):
        return agg.blend_flat_sharded(sp, d, wl, f, axis_name=CLIENT_AXIS)

    out = jax.jit(shard_map(
        stage, mesh=mesh,
        in_specs=(P(), P(CLIENT_AXIS, None), P(CLIENT_AXIS), P()),
        out_specs=P(), check_vma=False))(server, deltas, w, fw)
    expect = 0.35 * np.asarray(server) + 0.65 * np.einsum(
        "k,kn->n", np.asarray(w), np.asarray(deltas))
    np.testing.assert_allclose(np.asarray(out), expect, rtol=2e-5, atol=2e-5)
