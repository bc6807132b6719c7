"""Exactness of the wire's slot placement (``ref.slot_buckets``): marks and a
running sum put every survivor in the payload slot a per-slot binary search
put it in, for the compaction (marks at every column's rank), the csr_q
index decode (marks at the cumulative block counts) and the Pallas
wrapper's block placement. Each case is checked against a NumPy oracle
(``np.flatnonzero`` per row, ``np.searchsorted`` for the decode) and
against the ``jnp.searchsorted`` form the helper replaced, slot for slot,
padding slots included."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregation as agg
from repro.kernels import ops
from repro.kernels import ref as R

BLK = 512


def _normal(seed, k, n):
    return np.asarray(jax.random.normal(jax.random.PRNGKey(seed), (k, n)),
                      np.float32)


def _band(seed, k, n, lo, hi):
    """Nonzero only in columns [lo, hi)."""
    x = np.zeros((k, n), np.float32)
    x[:, lo:hi] = _normal(seed, k, hi - lo)
    return x


# name -> (x (K, N), threshold, cap); chunk widths are encoded chunk by
# chunk at the capacities the chunk plan gives (ceil(2.5 * 0.2 * width))
CASES = {
    "none_kept": (_normal(1, 3, 1500), np.inf, 700),
    "all_kept": (_normal(2, 2, 1536), 0.0, 1536),
    "overflow": (_normal(3, 4, 2000), 0.3, 200),
    "cap_eq_n": (_normal(4, 3, 1537), 0.7, 1537),
    "empty_edge_blocks": (_band(5, 2, 2560, 1024, 1536), 0.1, 600),
    "one_block": (_band(6, 3, 1300, 1024, 1300), 0.0, 400),
    "k1": (_normal(7, 1, 1000), 0.5, 400),
    "n_not_multiple": (_normal(8, 3, 777), 0.4, 500),
    "chunk_2048": (_normal(9, 4, 2048), 1.28, math.ceil(0.5 * 2048)),
    "chunk_ragged": (_normal(10, 4, 1187), 1.28, math.ceil(0.5 * 1187)),
}


def _compact_by_search(x, thr, cap):
    """The binary-search compaction ``slot_buckets`` replaced."""
    keep = (jnp.abs(x) >= thr[:, None]) & (x != 0)
    rank = jnp.cumsum(keep.astype(jnp.int32), axis=1)
    nnz = rank[:, -1]
    slots = jnp.arange(1, cap + 1, dtype=jnp.int32)
    cols = jax.vmap(lambda r: jnp.searchsorted(r, slots, side="left"))(rank)
    valid = slots[None, :] <= jnp.minimum(nnz, cap)[:, None]
    idx = jnp.where(valid, cols, 0).astype(jnp.int32)
    vals = jnp.where(valid, jnp.take_along_axis(x, idx, axis=1), 0.0)
    return vals, idx, nnz


def _unpack_by_search(offsets, counts):
    cum = jnp.cumsum(counts.astype(jnp.int32), axis=1)
    slots = jnp.arange(offsets.shape[1], dtype=jnp.int32)
    blk = jax.vmap(lambda c: jnp.searchsorted(c, slots, side="right"))(cum)
    blk = jnp.minimum(blk, counts.shape[1] - 1)
    return blk * BLK + offsets.astype(jnp.int32)


@pytest.mark.parametrize("case", list(CASES))
def test_slot_placement_matches_search_and_numpy(case):
    x, t, cap = CASES[case]
    k, n = x.shape
    thr = jnp.full((k,), t, jnp.float32)
    keep = (np.abs(x) >= t) & (x != 0)
    rank = np.cumsum(keep, axis=1, dtype=np.int32)

    # the helper itself: searchsorted(..., side="left") of slots 1..cap
    # over the running rank, padding slots included
    buckets = np.asarray(R.slot_buckets(jnp.asarray(rank), cap))
    for row in range(k):
        np.testing.assert_array_equal(
            buckets[row],
            np.searchsorted(rank[row], np.arange(1, cap + 1), side="left"))

    # compaction: NumPy oracle, the search form and the Pallas wrapper
    vals, idx, nnz = (np.asarray(a) for a in R.csr_compact2d_ref(x, thr, cap))
    np.testing.assert_array_equal(nnz, keep.sum(axis=1))
    for row in range(k):
        cols = np.flatnonzero(keep[row])[:cap]
        s = len(cols)
        np.testing.assert_array_equal(idx[row, :s], cols)
        np.testing.assert_array_equal(vals[row, :s], x[row, cols])
        assert not idx[row, s:].any() and not vals[row, s:].any()
    for got, old in zip((vals, idx, nnz),
                        _compact_by_search(jnp.asarray(x), thr, cap)):
        np.testing.assert_array_equal(got, np.asarray(old))
    for got, pallas in zip((vals, idx, nnz), ops.csr_compact(x, thr, cap)):
        np.testing.assert_array_equal(got, np.asarray(pallas))

    # csr_q index decode: NumPy oracle and the search form, every slot
    stored = np.minimum(nnz, cap)
    offs, counts = R.csr_pack_indices_ref(jnp.asarray(idx),
                                          jnp.asarray(stored), n)
    unpacked = np.asarray(R.csr_unpack_indices_ref(offs, counts))
    np.testing.assert_array_equal(
        unpacked, np.asarray(_unpack_by_search(offs, counts)))
    cum = np.cumsum(np.asarray(counts, np.int32), axis=1)
    nblk = cum.shape[1]
    for row in range(k):
        blk = np.minimum(np.searchsorted(cum[row], np.arange(cap),
                                         side="right"), nblk - 1)
        np.testing.assert_array_equal(
            unpacked[row], blk * BLK + np.asarray(offs[row], np.int32))
        np.testing.assert_array_equal(unpacked[row, :stored[row]],
                                      idx[row, :stored[row]])

    # the blend's fused decode scatters each stored value to its column
    scales = jnp.ones((k,), jnp.float32)
    w = jnp.arange(1, k + 1, dtype=jnp.float32)
    qvals = jnp.asarray(vals).astype(jnp.float16)
    got = np.asarray(agg.csr_q_weighted_scatter(qvals, offs, counts, scales,
                                                w, n))
    want = np.zeros(n, np.float32)
    q = np.asarray(qvals, np.float32)
    for row in range(k):
        s = stored[row]
        np.add.at(want, idx[row, :s], float(w[row]) * q[row, :s])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
