"""Pallas TPU kernel: blocked online-softmax (flash) attention, causal or
sliding-window.

Grid: (B*H, nq).  Each program holds one Q block [bq, D] in VMEM plus the
full K/V for its head (streamed block-by-block with lax.fori_loop and
dynamic slices inside VMEM), carrying the online-softmax (m, l, acc) state in
registers.  bq and bk should be multiples of 128 on real TPUs so the QK^T
and PV matmuls are MXU-shaped; D is the head dim (lane-aligned at 128).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, bk: int, causal: bool,
                  window: int, q_block: int):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)               # [bq, D]
    t_kv = k_ref.shape[1]
    bq, d = q.shape
    scale = d ** -0.5
    q_pos = qi * q_block + jax.lax.iota(jnp.int32, bq)

    nblocks = t_kv // bk

    def body(s, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(s * bk, bk), :].astype(jnp.float32)   # [bk, D]
        v = v_ref[0, pl.ds(s * bk, bk), :].astype(jnp.float32)
        scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        k_pos = s * bk + jax.lax.iota(jnp.int32, bk)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
            if window > 0:
                mask &= k_pos[None, :] > q_pos[:, None] - window
        scores = jnp.where(mask, scores, NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        p = jnp.exp(scores - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nblocks, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@partial(jax.jit,
         static_argnames=("causal", "window", "bq", "bk", "interpret"))
def flash_attention(
    q: jax.Array,                      # [B, H, T, D]
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, h, t, d = q.shape
    assert t % bq == 0 and t % bk == 0, "T must divide into blocks"
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h, t, d)
    vf = v.reshape(b * h, t, d)
    grid = (b * h, t // bq)
    out = pl.pallas_call(
        partial(_flash_kernel, bk=bk, causal=causal, window=window,
                q_block=bq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, t, d)
