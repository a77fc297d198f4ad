"""Pallas TPU kernel: single-token GQA decode attention, blocked over the
KV cache (the serve_step hot loop; memory-bound — the kernel's job is to
stream K/V through VMEM exactly once).

Grid: (B, n_kv_blocks).  Each program streams one [bs, KV, D] cache block
and accumulates the online softmax for all H = KV*G query heads of its batch
element into the output block (revisited across the s-grid dimension —
Pallas guarantees sequential grid iteration on TPU, so the accumulator lives
in the output ref plus two scratch rows)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(pos_scalar_ref, q_ref, k_ref, v_ref, slots_ref,
                   o_ref, m_ref, l_ref, acc_ref, *, window: int):
    s_idx = pl.program_id(1)
    ns = pl.num_programs(1)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                    # [KV, G, D]
    k = k_ref[0].astype(jnp.float32)                    # [bs, KV, D]
    v = v_ref[0].astype(jnp.float32)
    stored = slots_ref[0]                               # [bs]
    pos = pos_scalar_ref[0]
    kv, g, d = q.shape
    scale = d ** -0.5

    scores = jnp.einsum("kgd,skd->kgs", q, k) * scale   # [KV, G, bs]
    valid = (stored >= 0) & (stored <= pos)
    if window > 0:
        valid &= stored > pos - window
    scores = jnp.where(valid[None, None, :], scores, NEG_INF)

    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, scores.max(axis=-1))
    p = jnp.exp(scores - m_new[..., None])
    alpha = jnp.exp(m_old - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + jnp.einsum(
        "kgs,skd->kgd", p, v)
    m_ref[...] = m_new

    @pl.when(s_idx == ns - 1)
    def _final():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[..., None]
                    ).astype(o_ref.dtype)


@partial(jax.jit, static_argnames=("window", "block_s", "interpret"))
def decode_attention(
    q: jax.Array,               # [B, H, D]
    k_cache: jax.Array,         # [B, S, KV, D]
    v_cache: jax.Array,
    positions: jax.Array,       # [B, S] int32
    pos,                        # scalar int32
    *,
    window: int = 0,
    block_s: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, h, d = q.shape
    s, kv = k_cache.shape[1], k_cache.shape[2]
    g = h // kv
    assert s % block_s == 0
    qg = q.reshape(b, kv, g, d)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (1,))
    grid = (b, s // block_s)
    out = pl.pallas_call(
        partial(_decode_kernel, window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i, j: (0,)),
            pl.BlockSpec((1, kv, g, d), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((1, block_s, kv, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, block_s, kv, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, block_s), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, kv, g, d), lambda i, j: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((kv, g), jnp.float32),
            pltpu.VMEM((kv, g), jnp.float32),
            pltpu.VMEM((kv, g, d), jnp.float32),
        ],
        interpret=interpret,
    )(pos_arr, qg, k_cache, v_cache, positions)
    return out.reshape(b, h, d)
