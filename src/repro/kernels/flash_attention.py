"""Pallas TPU flash attention (pl.pallas_call + explicit BlockSpec VMEM tiling).

Grid: (B*H, num_q_blocks, num_kv_blocks), sequential on TPU; the online-softmax
accumulator (acc, m, l) lives in VMEM scratch and persists across the kv-block
grid dimension. Causal/sliding-window masking is derived from program ids, so
no O(S^2) mask tensor ever exists.

Tile sizes default to (128, 128): MXU-aligned (128 lanes), and the working set
q(128,hd) + k(128,hd) + v(128,hd) + acc(128,hd) + tile(128,128) stays well
under the ~16 MB v5e VMEM for hd <= 256.

Oracle: kernels/ref.py::flash_attention_ref (plus models/layers._sdpa).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  qblk, kblk, nk, causal, window, scale):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)               # (qblk, hd)
    k = k_ref[0].astype(jnp.float32)               # (kblk, hd)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (qblk,kblk)
    if causal:
        qp = qi * qblk + jax.lax.broadcasted_iota(jnp.int32, (qblk, kblk), 0)
        kp = ki * kblk + jax.lax.broadcasted_iota(jnp.int32, (qblk, kblk), 1)
        ok = kp <= qp
        if window is not None:
            ok &= kp > (qp - window)
        s = jnp.where(ok, s, -1e30)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, window=None,
                           qblk=128, kblk=128, interpret=True):
    """q,k,v: (B, S, H, hd) with KV already broadcast to all H heads.

    Returns (B, S, H, hd). ``interpret=True`` runs the kernel in the Pallas
    interpreter (any backend); ``interpret=False`` compiles it with Mosaic
    for a TPU.
    """
    B, S, H, hd = q.shape
    qblk = min(qblk, S)
    kblk = min(kblk, S)
    assert S % qblk == 0 and S % kblk == 0, (S, qblk, kblk)
    nq, nk = S // qblk, S // kblk
    scale = 1.0 / math.sqrt(hd)

    # (B*H, S, hd) layout: one grid row per (batch, head)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)

    kernel = functools.partial(_flash_kernel, qblk=qblk, kblk=kblk, nk=nk,
                               causal=causal, window=window, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, qblk, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, kblk, hd), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, kblk, hd), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, qblk, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qblk, hd), jnp.float32),
            pltpu.VMEM((qblk,), jnp.float32),
            pltpu.VMEM((qblk,), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)

