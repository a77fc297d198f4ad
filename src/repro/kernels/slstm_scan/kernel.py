"""Pallas TPU kernel: sLSTM recurrent scan with the recurrent matrix R held
resident in VMEM across timesteps.

Motivation (EXPERIMENTS.md §Perf pair 2): the jnp `lax.scan` form re-streams
R ([4, dh, dh] — 4 MB at dh=512) from HBM every timestep: ~0.4 TB/step for
xlstm-1.3b train_4k, the dominant residual memory term after the pure-DP +
chunked-mLSTM changes.  A TPU kernel loads R once per (head, sequence) and
keeps the (h, c, n, m) state in VMEM scratch.

Grid: (H, n_t_blocks) — Pallas guarantees sequential grid iteration on TPU,
so the recurrent state lives in scratch refs that persist across the
t-block dimension.  Each program step streams one [B, Lb, 4, dh] slab of
input pre-activations through VMEM, runs Lb recurrent steps, and writes the
[B, Lb, dh] hidden-state slab.

Exponential-gating semantics match ``repro.models.layers.xlstm._slstm_step``
exactly (same stabiliser, same n-floor).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _slstm_kernel(wx_ref, r_ref, b_ref, o_ref,
                  h_ref, c_ref, n_ref, m_ref):
    tb = pl.program_id(1)

    @pl.when(tb == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.ones_like(n_ref)
        m_ref[...] = jnp.zeros_like(m_ref)

    r = r_ref[0].astype(jnp.float32)              # [4, dh, dh] — VMEM-resident
    bias = b_ref[0].astype(jnp.float32)           # [4, dh]
    lb = wx_ref.shape[2]

    def step(i, _):
        wx_t = wx_ref[0, :, i].astype(jnp.float32)        # [B, 4, dh]
        h = h_ref[...]
        rec = jnp.einsum("bk,gkj->bgj", h, r)             # [B, 4, dh]
        pre = wx_t + rec + bias[None]
        i_pre, f_pre = pre[:, 0], pre[:, 1]
        z_pre, o_pre = pre[:, 2], pre[:, 3]
        logf = jax.nn.log_sigmoid(f_pre)
        m_new = jnp.maximum(logf + m_ref[...], i_pre)
        i_eff = jnp.exp(i_pre - m_new)
        f_eff = jnp.exp(logf + m_ref[...] - m_new)
        z = jnp.tanh(z_pre)
        o = jax.nn.sigmoid(o_pre)
        c_new = f_eff * c_ref[...] + i_eff * z
        n_new = jnp.maximum(f_eff * n_ref[...] + i_eff, 1e-6)
        h_new = o * c_new / n_new
        h_ref[...] = h_new
        c_ref[...] = c_new
        n_ref[...] = n_new
        m_ref[...] = m_new
        o_ref[0, :, i] = h_new.astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, lb, step, 0)


@partial(jax.jit, static_argnames=("block_t", "interpret"))
def slstm_scan(
    wx: jax.Array,             # [B, T, 4, H, dh] input pre-activations
    r: jax.Array,              # [4, H, dh, dh] recurrent weights
    b: jax.Array,              # [4, H, dh] bias
    *,
    block_t: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Returns hidden states hs [B, T, H, dh] (float32)."""
    bsz, t, four, h, dh = wx.shape
    assert four == 4
    n_pad = (-t) % block_t
    if n_pad:                  # padded steps run after every real step and
        wx = jnp.pad(wx, [(0, 0), (0, n_pad), (0, 0), (0, 0), (0, 0)])
    tp = t + n_pad
    # head-major layout so each program streams its own contiguous slabs
    wx_h = wx.transpose(3, 0, 1, 2, 4)                    # [H, B, T, 4, dh]
    r_h = r.swapaxes(0, 1)                                # [H, 4, dh, dh]
    b_h = b.swapaxes(0, 1)                                # [H, 4, dh]

    grid = (h, tp // block_t)
    out = pl.pallas_call(
        _slstm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bsz, block_t, 4, dh),
                         lambda i, j: (i, 0, j, 0, 0)),
            pl.BlockSpec((1, 4, dh, dh), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((1, 4, dh), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bsz, block_t, dh),
                               lambda i, j: (i, 0, j, 0)),
        out_shape=jax.ShapeDtypeStruct((h, bsz, tp, dh), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bsz, dh), jnp.float32),           # h
            pltpu.VMEM((bsz, dh), jnp.float32),           # c
            pltpu.VMEM((bsz, dh), jnp.float32),           # n
            pltpu.VMEM((bsz, dh), jnp.float32),           # m
        ],
        interpret=interpret,
    )(wx_h, r_h, b_h)
    return out[:, :, :t].transpose(1, 2, 0, 3)            # [B, T, H, dh]
