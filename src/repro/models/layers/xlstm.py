"""xLSTM blocks (arXiv:2405.04517): mLSTM (matrix memory, parallelisable)
and sLSTM (scalar memory, sequential scan), both with exponential gating and
the max-state stabiliser.

The mLSTM has two mathematically equivalent forms:
  * parallel (training/prefill): an attention-like T x T decay-masked form
    (or its chunkwise form, whose carry is a prompt's cache);
  * recurrent (decode): C_t = f'_t C_{t-1} + i'_t v_t k_t^T.
A property test asserts the two agree (tests/test_layers_equivalence.py).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from .common import LayerCtx, Mixer, dense_init, groupnorm_heads, silu


# =========================================================================== #
# mLSTM                                                                       #
# =========================================================================== #


def mlstm_init(key, cfg: ModelConfig, dtype) -> dict:
    xc = cfg.xlstm
    assert xc is not None
    d = cfg.d_model
    di = int(xc.proj_factor_mlstm * d)
    h = cfg.n_heads
    dh = di // h
    keys = jax.random.split(key, 8)
    return {
        "up_proj": dense_init(keys[0], d, 2 * di, dtype=dtype),
        "conv_w": dense_init(keys[1], xc.conv_kernel, di, dtype=dtype),
        "conv_b": jnp.zeros((di,), dtype=dtype),
        "wq": dense_init(keys[2], di, h, dh, dtype=dtype),
        "wk": dense_init(keys[3], di, h, dh, dtype=dtype),
        "wv": dense_init(keys[4], di, h, dh, dtype=dtype),
        "w_i": dense_init(keys[5], di, h, dtype=jnp.float32),
        "w_f": dense_init(keys[6], di, h, dtype=jnp.float32),
        "b_i": jnp.zeros((h,), jnp.float32),
        "b_f": jnp.full((h,), 3.0, jnp.float32),   # forget-gate bias > 0
        "skip": jnp.ones((di,), dtype=dtype),
        "down_proj": dense_init(keys[7], di, d, dtype=dtype),
    }


def mlstm_axes(cfg: ModelConfig) -> dict:
    return {
        "up_proj": ("embed", "d_inner2"),
        "conv_w": ("conv", "d_inner"),
        "conv_b": ("d_inner",),
        "wq": ("d_inner", "heads", "head_dim"),
        "wk": ("d_inner", "heads", "head_dim"),
        "wv": ("d_inner", "heads", "head_dim"),
        "w_i": ("d_inner", "heads"),
        "w_f": ("d_inner", "heads"),
        "b_i": ("heads",),
        "b_f": ("heads",),
        "skip": ("d_inner",),
        "down_proj": ("d_inner", "embed"),
    }


def init_mlstm_cache(batch: int, cache_len: int, cfg: ModelConfig,
                     dtype) -> dict:
    xc = cfg.xlstm
    di = int(xc.proj_factor_mlstm * cfg.d_model)
    h = cfg.n_heads
    dh = di // h
    return {
        "conv": jnp.zeros((batch, xc.conv_kernel - 1, di), dtype=dtype),
        "c": jnp.zeros((batch, h, dh, dh), jnp.float32),
        "n": jnp.zeros((batch, h, dh), jnp.float32),
        "m": jnp.full((batch, h), -1e30, jnp.float32),
    }


def mlstm_cache_axes() -> dict:
    return {
        "conv": ("batch", "conv", "d_inner"),
        "c": ("batch", "heads", "head_dim", "head_dim2"),
        "n": ("batch", "heads", "head_dim"),
        "m": ("batch", "heads"),
    }


def _conv_causal(w, b, x: jax.Array, prior: Optional[jax.Array]) -> jax.Array:
    k = w.shape[0]
    if prior is None:
        prior = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([prior, x], axis=1)
    return sum(xp[:, i : i + x.shape[1], :] * w[i] for i in range(k)) + b


def _qkv_gates(params, xi: jax.Array):
    q = jnp.einsum("bti,ihk->bthk", xi, params["wq"])
    k = jnp.einsum("bti,ihk->bthk", xi, params["wk"])
    v = jnp.einsum("bti,ihk->bthk", xi, params["wv"])
    xf = xi.astype(jnp.float32)
    i_pre = jnp.einsum("bti,ih->bth", xf, params["w_i"]) + params["b_i"]
    f_pre = jnp.einsum("bti,ih->bth", xf, params["w_f"]) + params["b_f"]
    return q, k, v, i_pre, f_pre


def _mlstm_chunked(q, k, v, i_pre, f_pre, chunk: int,
                   state: Optional[tuple] = None):
    """Chunkwise-parallel mLSTM (§Perf): O(T·chunk) score blocks + a
    recurrent (C, n, m) carry between chunks — the linear-attention chunk
    form, mathematically identical to the naive T x T decay-masked form
    (the stabiliser ``m_t = max_{s<=t} a_{t,s}`` is tracked exactly through
    the chunk recursion).

    q/k/v [B,T,H,K]; i_pre/f_pre [B,T,H] float32.
    Returns (h_out [B,T,H,K] float32, m_t [B,T,H], final_state).
    """
    b, t, h, dh = q.shape
    n_pad = (-t) % chunk
    if n_pad:   # pads neither forget nor take in: the final state is T's
        pad = [(0, 0), (0, n_pad), (0, 0)]
        q, k, v = (jnp.pad(a, pad + [(0, 0)]) for a in (q, k, v))
        i_pre = jnp.pad(i_pre, pad, constant_values=-1e30)
        f_pre = jnp.pad(f_pre, pad, constant_values=1e30)
    tp = t + n_pad
    nb = tp // chunk
    scale = jnp.asarray(dh, jnp.float32) ** -0.5

    def per_chunk(carry, inp):
        C, n, m_prev = carry                              # [B,H,K,K] [B,H,K] [B,H]
        qc, kc, vc, ic, fc = inp                          # [B,L,H,K] / [B,L,H]
        qc = qc.astype(jnp.float32) * scale
        kc = kc.astype(jnp.float32)
        vc = vc.astype(jnp.float32)
        logf = jax.nn.log_sigmoid(fc)                     # [B,L,H]
        cum = jnp.cumsum(logf, axis=1)                    # inclusive
        u = ic - cum                                      # i_s - cum_s
        w = jnp.maximum(m_prev[:, None],
                        jax.lax.cummax(u, axis=1))        # [B,L,H]
        m_t = cum + w                                     # row-max stabiliser
        # intra-chunk: D[t,s] = exp(u_s - w_t) for s<=t
        L = qc.shape[1]
        causal = jnp.tril(jnp.ones((L, L), bool))[None, :, :, None]
        D = jnp.where(causal, jnp.exp(u[:, None, :, :] - w[:, :, None, :]), 0.0)
        scores = jnp.einsum("blhk,bshk->blsh", qc, kc) * D
        num = jnp.einsum("blsh,bshk->blhk", scores, vc)
        den = scores.sum(axis=2)                          # [B,L,H]
        # inter-chunk (from carried state)
        g = jnp.exp(m_prev[:, None] - w)                  # [B,L,H]
        qg = qc * g[..., None]
        num = num + jnp.einsum("blhk,bhkj->blhj", qg, C)
        den = den + jnp.einsum("blhk,bhk->blh", qg, n)
        h_out = num / (jnp.maximum(jnp.abs(den), jnp.exp(-m_t))[..., None]
                       + 1e-6)
        # state update to end of chunk
        w_L = w[:, -1]                                    # [B,H]
        F = cum[:, -1]
        m_new = F + w_L
        coeff = jnp.exp(u - w_L[:, None])                 # [B,L,H]
        C_new = (jnp.exp(m_prev - w_L)[..., None, None] * C
                 + jnp.einsum("bsh,bshk,bshj->bhkj", coeff, kc, vc))
        n_new = (jnp.exp(m_prev - w_L)[..., None] * n
                 + jnp.einsum("bsh,bshk->bhk", coeff, kc))
        return (C_new, n_new, m_new), (h_out, m_t)

    if state is None:
        state = (
            jnp.zeros((b, h, dh, dh), jnp.float32),
            jnp.zeros((b, h, dh), jnp.float32),
            jnp.full((b, h), -1e30, jnp.float32),
        )

    def to_chunks(a):
        return a.reshape((a.shape[0], nb, chunk) + a.shape[2:]).swapaxes(0, 1)

    final_state, (hs, ms) = jax.lax.scan(
        per_chunk, state,
        tuple(to_chunks(a) for a in (q, k, v, i_pre, f_pre)))
    hs = hs.swapaxes(0, 1).reshape(b, tp, h, dh)[:, :t]
    ms = ms.swapaxes(0, 1).reshape(b, tp, h)[:, :t]
    return hs, ms, final_state


def _mlstm_state(k, v, i_pre, f_pre) -> tuple:
    """The recurrent (C, n, m) state after k/v [B,T,H,K], i/f [B,T,H],
    from an empty one: what the one-block parallel form leaves no carry
    for."""

    def step(state, inp):
        c, n, m = state
        kt, vt, it, ft = inp
        logf = jax.nn.log_sigmoid(ft)
        m_new = jnp.maximum(logf + m, it)
        f_eff = jnp.exp(logf + m - m_new)
        i_eff = jnp.exp(it - m_new)
        c = f_eff[..., None, None] * c + i_eff[..., None, None] * \
            kt[..., :, None] * vt[..., None, :]
        n = f_eff[..., None] * n + i_eff[..., None] * kt
        return (c, n, m_new), None

    b, t, hh, dh = k.shape
    state0 = (jnp.zeros((b, hh, dh, dh), jnp.float32),
              jnp.zeros((b, hh, dh), jnp.float32),
              jnp.full((b, hh), -1e30, jnp.float32))
    state, _ = jax.lax.scan(
        step, state0,
        (k.swapaxes(0, 1).astype(jnp.float32),
         v.swapaxes(0, 1).astype(jnp.float32),
         i_pre.swapaxes(0, 1), f_pre.swapaxes(0, 1)))
    return state


def mlstm_apply(
    params: dict,
    x: jax.Array,
    ctx: LayerCtx,
    cache: Optional[dict] = None,
    layer: jax.Array | int = 0,
) -> tuple[jax.Array, Optional[dict]]:
    cfg = ctx.cfg
    b, t = x.shape[:2]
    di = params["skip"].shape[0]
    up = jnp.einsum("btd,de->bte", x, params["up_proj"])
    xi_raw, z = up[..., :di], up[..., di:]

    if cache is None or t > 1:
        xi = silu(_conv_causal(params["conv_w"], params["conv_b"], xi_raw, None))
        q, k, v, i_pre, f_pre = _qkv_gates(params, xi)
        if cfg.mlstm_chunk and t > cfg.mlstm_chunk:
            hout, _, state = _mlstm_chunked(q, k, v, i_pre, f_pre,
                                            cfg.mlstm_chunk)
        else:
            dh = q.shape[-1]
            logf = jax.nn.log_sigmoid(f_pre)                  # [B,T,H]
            cum = jnp.cumsum(logf, axis=1)
            # a[t, s] = sum_{j=s+1..t} logf_j + logi_s  (t >= s)
            amat = (cum[:, :, None, :] - cum[:, None, :, :]
                    + i_pre[:, None, :, :])                   # [B,Tq,Ts,H]
            causal = jnp.tril(jnp.ones((t, t), bool))[None, :, :, None]
            amat = jnp.where(causal, amat, -jnp.inf)
            m = jnp.max(amat, axis=2, keepdims=True)          # [B,T,1,H]
            dmat = jnp.exp(amat - m)                           # stabilised
            scale = jnp.asarray(dh, jnp.float32) ** -0.5
            scores = jnp.einsum("bthk,bshk->btsh", q.astype(jnp.float32),
                                k.astype(jnp.float32)) * scale
            sd = scores * dmat
            norm = jnp.maximum(jnp.abs(sd.sum(axis=2)), jnp.exp(-m[:, :, 0]))
            hout = jnp.einsum("btsh,bshk->bthk", sd, v.astype(jnp.float32))
            hout = hout / (norm[..., None] + 1e-6)
            if cache is not None:
                state = _mlstm_state(k, v, i_pre, f_pre)
        new_cache = None
        if cache is not None:
            kk = params["conv_w"].shape[0]
            conv = jnp.pad(xi_raw, [(0, 0), (kk - 1, 0), (0, 0)])[:, t:]
            c, n, m_state = state
            new_cache = {"conv": conv.astype(cache["conv"].dtype), "c": c,
                         "n": n, "m": m_state}
    else:
        conv_win = jnp.concatenate([cache["conv"], xi_raw], axis=1)
        xi = silu(
            jnp.einsum("bki,ki->bi", conv_win, params["conv_w"])
            + params["conv_b"]
        )[:, None, :]
        q, k, v, i_pre, f_pre = _qkv_gates(params, xi)
        dh = q.shape[-1]
        logf = jax.nn.log_sigmoid(f_pre[:, 0])                # [B,H]
        logi = i_pre[:, 0]
        m_new = jnp.maximum(logf + cache["m"], logi)
        f_eff = jnp.exp(logf + cache["m"] - m_new)            # [B,H]
        i_eff = jnp.exp(logi - m_new)
        kf = k[:, 0].astype(jnp.float32)
        vf = v[:, 0].astype(jnp.float32)
        c_new = (
            f_eff[..., None, None] * cache["c"]
            + i_eff[..., None, None] * kf[..., :, None] * vf[..., None, :]
        )
        n_new = f_eff[..., None] * cache["n"] + i_eff[..., None] * kf
        scale = jnp.asarray(dh, jnp.float32) ** -0.5
        qf = q[:, 0].astype(jnp.float32) * scale
        num = jnp.einsum("bhk,bhkj->bhj", qf, c_new)
        den = jnp.maximum(
            jnp.abs(jnp.einsum("bhk,bhk->bh", qf, n_new)), jnp.exp(-m_new)
        )
        hout = (num / (den[..., None] + 1e-6))[:, None]       # [B,1,H,dh]
        new_cache = {"conv": conv_win[:, 1:], "c": c_new, "n": n_new, "m": m_new}

    hout = groupnorm_heads(hout).astype(x.dtype)
    hflat = hout.reshape(b, t, di) + params["skip"] * xi
    y = hflat * silu(z)
    return jnp.einsum("bti,id->btd", y, params["down_proj"]), new_cache


MLSTM = Mixer(init=mlstm_init, axes=mlstm_axes, cache_init=init_mlstm_cache,
              cache_axes=mlstm_cache_axes, apply=mlstm_apply)


# =========================================================================== #
# sLSTM                                                                      #
# =========================================================================== #


def slstm_init(key, cfg: ModelConfig, dtype) -> dict:
    xc = cfg.xlstm
    assert xc is not None
    d = cfg.d_model
    h = cfg.n_heads
    dh = d // h
    keys = jax.random.split(key, 4)
    df = int(xc.ffn_proj_factor * d)
    return {
        "w": dense_init(keys[0], d, 4, h, dh, dtype=dtype),       # i,f,z,o
        "r": (dh ** -0.5 * jax.random.normal(keys[1], (4, h, dh, dh))).astype(dtype),
        "b": jnp.concatenate(
            [jnp.zeros((1, h, dh)), jnp.full((1, h, dh), 3.0),
             jnp.zeros((2, h, dh))], axis=0).astype(jnp.float32),
        "ffn_gate": dense_init(keys[2], d, 2 * df, dtype=dtype),
        "ffn_down": dense_init(keys[3], df, d, dtype=dtype),
    }


def slstm_axes(cfg: ModelConfig) -> dict:
    return {
        "w": ("embed", "gates", "heads", "head_dim"),
        "r": ("gates", "heads", "head_dim", "head_dim2"),
        "b": ("gates", "heads", "head_dim"),
        "ffn_gate": ("embed", "ff"),
        "ffn_down": ("ff", "embed"),
    }


def init_slstm_cache(batch: int, cache_len: int, cfg: ModelConfig,
                     dtype) -> dict:
    h = cfg.n_heads
    dh = cfg.d_model // h
    return {
        "h": jnp.zeros((batch, h, dh), jnp.float32),
        "c": jnp.zeros((batch, h, dh), jnp.float32),
        "n": jnp.ones((batch, h, dh), jnp.float32),
        "m": jnp.zeros((batch, h, dh), jnp.float32),
    }


def slstm_cache_axes() -> dict:
    return {
        "h": ("batch", "heads", "head_dim"),
        "c": ("batch", "heads", "head_dim"),
        "n": ("batch", "heads", "head_dim"),
        "m": ("batch", "heads", "head_dim"),
    }


def _slstm_step(params, state, wx_t):
    """state (h,c,n,m) each [B,H,dh]; wx_t [B,4,H,dh] input pre-activations."""
    h, c, n, m = state
    rec = jnp.einsum("bhk,ghkj->bghj", h.astype(params["r"].dtype), params["r"])
    pre = wx_t.astype(jnp.float32) + rec.astype(jnp.float32) + params["b"]
    i_pre, f_pre, z_pre, o_pre = pre[:, 0], pre[:, 1], pre[:, 2], pre[:, 3]
    logf = jax.nn.log_sigmoid(f_pre)
    m_new = jnp.maximum(logf + m, i_pre)
    i_eff = jnp.exp(i_pre - m_new)
    f_eff = jnp.exp(logf + m - m_new)
    z = jnp.tanh(z_pre)
    o = jax.nn.sigmoid(o_pre)
    c_new = f_eff * c + i_eff * z
    n_new = jnp.maximum(f_eff * n + i_eff, 1e-6)
    h_new = o * c_new / n_new
    return (h_new, c_new, n_new, m_new)


def slstm_apply(
    params: dict,
    x: jax.Array,
    ctx: LayerCtx,
    cache: Optional[dict] = None,
    layer: jax.Array | int = 0,
) -> tuple[jax.Array, Optional[dict]]:
    b, t, d = x.shape
    h_heads = ctx.cfg.n_heads
    dh = d // h_heads
    wx = jnp.einsum("btd,dghk->btghk", x, params["w"])        # [B,T,4,H,dh]

    if cache is None or t > 1:
        state = (
            jnp.zeros((b, h_heads, dh), jnp.float32),
            jnp.zeros((b, h_heads, dh), jnp.float32),
            jnp.ones((b, h_heads, dh), jnp.float32),
            jnp.zeros((b, h_heads, dh), jnp.float32),
        )

        def step(state, wx_t):
            new = _slstm_step(params, state, wx_t)
            return new, new[0]

        state, hs = jax.lax.scan(step, state, wx.swapaxes(0, 1))
        hs = hs.swapaxes(0, 1)                                 # [B,T,H,dh]
    else:
        state = _slstm_step(params, (cache["h"], cache["c"], cache["n"],
                                     cache["m"]), wx[:, 0])
        hs = state[0][:, None]
    new_cache = None if cache is None else dict(zip("hcnm", state))

    hs = groupnorm_heads(hs).astype(x.dtype).reshape(b, t, d)
    y = x + hs                                                 # residual core
    # gated FFN (proj factor 4/3)
    gu = jnp.einsum("btd,de->bte", y, params["ffn_gate"])
    df = gu.shape[-1] // 2
    y2 = silu(gu[..., :df]) * gu[..., df:]
    return jnp.einsum("btf,fd->btd", y2, params["ffn_down"]), new_cache


SLSTM = Mixer(init=slstm_init, axes=slstm_axes, cache_init=init_slstm_cache,
              cache_axes=slstm_cache_axes, apply=slstm_apply)
