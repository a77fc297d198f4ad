"""The composable model: embeddings, (optional) encoder, decoder stages,
LM head — with init / forward / prefill / decode_step entry points and
mirror logical-axis trees for sharding.

Modality carve-out (per the brief): audio/vision frontends are stubs — the
model consumes precomputed frame/patch embeddings (``modality_emb``) through
a learned 2-layer projector; everything downstream is real.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from .blocks import (
    LayerCtx,
    stage_apply,
    stage_axes,
    stage_cache_axes,
    stage_cache_init,
    stage_init,
)
from .config import ModelConfig
from .layers.common import dense_init, normal_init, rmsnorm, rmsnorm_axes, \
    rmsnorm_init

Params = dict
Caches = dict


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# --------------------------------------------------------------------------- #
# Init                                                                        #
# --------------------------------------------------------------------------- #


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    dt = _dtype(cfg)
    keys = jax.random.split(key, 6 + len(cfg.stages) + len(cfg.encoder_stages))
    p: Params = {
        "embed": normal_init(keys[0], (cfg.padded_vocab, cfg.d_model), 0.02, dt),
        "final_norm": rmsnorm_init(cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(keys[1], cfg.d_model, cfg.padded_vocab, dtype=dt)
    if cfg.modality_embed_dim:
        p["proj_in"] = dense_init(keys[2], cfg.modality_embed_dim, cfg.d_model,
                                  dtype=dt)
        p["proj_mid"] = dense_init(keys[3], cfg.d_model, cfg.d_model, dtype=dt)
    for i, st in enumerate(cfg.encoder_stages):
        p[f"enc{i}"] = stage_init(keys[4 + i], st, cfg, dt)
    if cfg.encoder_stages:
        p["enc_norm"] = rmsnorm_init(cfg.d_model, dt)
    off = 4 + len(cfg.encoder_stages)
    for i, st in enumerate(cfg.stages):
        p[f"dec{i}"] = stage_init(keys[off + i], st, cfg, dt)
    return p


def params_axes(cfg: ModelConfig) -> dict:
    a: dict = {
        "embed": ("vocab", "embed"),
        "final_norm": rmsnorm_axes(),
    }
    if not cfg.tie_embeddings:
        a["lm_head"] = ("embed", "vocab")
    if cfg.modality_embed_dim:
        a["proj_in"] = ("modality", "embed")
        a["proj_mid"] = ("embed", "embed2")
    for i, st in enumerate(cfg.encoder_stages):
        a[f"enc{i}"] = stage_axes(st, cfg)
    if cfg.encoder_stages:
        a["enc_norm"] = rmsnorm_axes()
    for i, st in enumerate(cfg.stages):
        a[f"dec{i}"] = stage_axes(st, cfg)
    return a


def abstract_params(cfg: ModelConfig) -> Params:
    """ShapeDtypeStruct param tree (no allocation) for dry-runs."""
    return jax.eval_shape(lambda k: init_params(cfg, k),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


# --------------------------------------------------------------------------- #
# Embedding / head                                                            #
# --------------------------------------------------------------------------- #


def embed_tokens(params: Params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    return jnp.take(params["embed"], tokens, axis=0)


def project_modality(params: Params, emb: jax.Array) -> jax.Array:
    h = jnp.einsum("bsm,md->bsd", emb, params["proj_in"])
    h = jax.nn.gelu(h)
    return jnp.einsum("bsd,de->bse", h, params["proj_mid"])


@jax.named_scope("lm_head")
def lm_logits(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        return jnp.einsum("btd,vd->btv", x, params["embed"])
    return jnp.einsum("btd,dv->btv", x, params["lm_head"])


# --------------------------------------------------------------------------- #
# Encoder                                                                     #
# --------------------------------------------------------------------------- #


def encode(params: Params, cfg: ModelConfig, enc_input: jax.Array,
           remat: bool = False, unroll: int | bool = 1) -> jax.Array:
    """enc_input [B, S, d] (already projected frame embeddings)."""
    positions = jnp.arange(enc_input.shape[1])
    ctx = LayerCtx(cfg=cfg, positions=positions, causal=False)
    x = enc_input
    for i, st in enumerate(cfg.encoder_stages):
        x, _, _ = stage_apply(params[f"enc{i}"], st, x, ctx, remat=remat,
                              unroll=unroll)
    return rmsnorm(params["enc_norm"], x, cfg.norm_eps)


# --------------------------------------------------------------------------- #
# Decoder forward (training / prefill, full sequence)                         #
# --------------------------------------------------------------------------- #


def _decoder_input(params: Params, cfg: ModelConfig, batch: dict) -> jax.Array:
    """Builds [B, T, d] decoder input from tokens (+ modality embeddings for
    decoder-only multimodal archs, where they are *prepended*)."""
    x = embed_tokens(params, cfg, batch["tokens"])
    if cfg.modality_embed_dim and not cfg.is_encoder_decoder:
        vis = project_modality(params, batch["modality_emb"])
        x = jnp.concatenate([vis, x], axis=1)
    return x


def forward(
    params: Params,
    cfg: ModelConfig,
    batch: dict,
    *,
    remat: bool = False,
    moe_group_size: int = 256,
    unroll: int | bool = 1,
) -> tuple[jax.Array, jax.Array]:
    """Full-sequence decode-only/enc-dec forward.

    batch: {"tokens": [B, T_text] int32,
            "modality_emb": [B, S_mod, modality_dim] (audio/vision archs)}
    Returns (logits [B, T, padded_vocab], aux_loss).
    """
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_in = project_modality(params, batch["modality_emb"])
        enc_out = encode(params, cfg, enc_in, remat=remat, unroll=unroll)
    x = _decoder_input(params, cfg, batch)
    positions = jnp.arange(x.shape[1])
    ctx = LayerCtx(cfg=cfg, positions=positions, causal=True,
                   window=cfg.sliding_window, enc_out=enc_out,
                   moe_group_size=moe_group_size, inner_unroll=unroll)
    aux = jnp.zeros((), jnp.float32)
    for i, st in enumerate(cfg.stages):
        x, _, a = stage_apply(params[f"dec{i}"], st, x, ctx, remat=remat,
                              unroll=unroll)
        aux = aux + a
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return lm_logits(params, cfg, x), aux


# --------------------------------------------------------------------------- #
# KV / state caches                                                           #
# --------------------------------------------------------------------------- #


def init_caches(cfg: ModelConfig, batch: int, cache_len: int,
                enc_len: int = 0) -> Caches:
    dt = _dtype(cfg)
    return {
        f"dec{i}": stage_cache_init(st, cfg, batch, cache_len, dt, enc_len)
        for i, st in enumerate(cfg.stages)
    }


def caches_axes(cfg: ModelConfig) -> dict:
    return {
        f"dec{i}": stage_cache_axes(st) for i, st in enumerate(cfg.stages)
    }


def abstract_caches(cfg: ModelConfig, batch: int, cache_len: int,
                    enc_len: int = 0) -> Caches:
    return jax.eval_shape(
        lambda: init_caches(cfg, batch, cache_len, enc_len))


# --------------------------------------------------------------------------- #
# Prefill (fill caches with a prompt) and single-token decode                 #
# --------------------------------------------------------------------------- #


def prefill(
    params: Params,
    cfg: ModelConfig,
    batch: dict,
    cache_len: int,
    *,
    moe_group_size: int = 256,
    unroll: int | bool = 1,
) -> tuple[jax.Array, Caches]:
    """Runs the full prompt, returns (last-position logits, filled caches).

    Prefill recomputes K/V for the whole prompt and writes them into the
    cache in one shot (scatter-free: dynamic_update_slice at 0).
    """
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_in = project_modality(params, batch["modality_emb"])
        enc_out = encode(params, cfg, enc_in, unroll=unroll)
    x = _decoder_input(params, cfg, batch)
    b, t, _ = x.shape
    positions = jnp.arange(t)
    window = cfg.sliding_window
    ctx = LayerCtx(cfg=cfg, positions=positions, causal=True, window=window,
                   enc_out=enc_out, moe_group_size=moe_group_size,
                   inner_unroll=unroll)
    caches = init_caches(cfg, b, cache_len,
                         enc_len=enc_out.shape[1] if enc_out is not None else 0)
    new_caches: Caches = {}
    aux = jnp.zeros((), jnp.float32)
    for i, st in enumerate(cfg.stages):
        x, nc, a = _prefill_stage(params[f"dec{i}"], st, x, ctx,
                                  caches[f"dec{i}"], cache_len, unroll)
        new_caches[f"dec{i}"] = nc
        aux = aux + a
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lm_logits(params, cfg, x[:, -1:, :])
    return logits, new_caches


def _prefill_stage(stage_params, st, x, ctx: LayerCtx, caches, cache_len: int,
                   unroll: int | bool = 1):
    """Stage apply that also fills each layer's cache from full-seq K/V."""
    from .blocks import layer_apply
    from .layers import attention as attn_mod

    cfg = ctx.cfg

    def body(carry, xs):
        x, aux = carry
        p, cache = xs
        new_caches = {}
        for i, ld in enumerate(st.pattern):
            ci = cache[f"p{i}"]
            x, nc, a = _prefill_layer(p[f"p{i}"], ld, x, ctx, ci, cache_len)
            aux = aux + a
            new_caches[f"p{i}"] = nc
        return (x, aux), new_caches

    (x, aux), new_caches = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), (stage_params, caches),
        unroll=unroll)
    return x, new_caches, aux


def _prefill_layer(p, ld, x, ctx: LayerCtx, cache, cache_len: int):
    """Run the layer in full-sequence mode, then write K/V/state into cache."""
    from .blocks import layer_apply
    from .layers import attention as A, mamba as M, mla as L, xlstm as X
    from .layers.common import rmsnorm as _rms, silu as _silu

    cfg = ctx.cfg
    t = x.shape[1]
    window = ctx.window

    # 1. run the layer WITHOUT cache (parallel form), collecting nothing
    x_out, _, aux = layer_apply(p, ld, x, ctx, cache=None)

    # 2. recompute the cacheable state and write it
    h = _rms(p["norm1"], x, cfg.norm_eps)
    new_cache = dict(cache)
    if ld.mixer == "attn":
        new_cache["self"] = _fill_kv(p["mixer"], h, cfg, ctx, cache["self"],
                                     cache_len)
    elif ld.mixer == "mla":
        new_cache["self"] = _fill_mla(p["mixer"], h, cfg, ctx, cache["self"],
                                      cache_len)
    elif ld.mixer == "mamba":
        new_cache["self"] = _fill_mamba(p["mixer"], h, cfg, cache["self"])
    elif ld.mixer == "mlstm":
        new_cache["self"] = _fill_mlstm(p["mixer"], h, cfg, cache["self"])
    elif ld.mixer == "slstm":
        new_cache["self"] = _fill_slstm(p["mixer"], h, cfg, cache["self"])
    if ld.cross_attn:
        from .layers.attention import cross_kv
        new_cache["cross"] = cross_kv(p["cross"], ctx.enc_out)
    return x_out, new_cache, aux


def _fill_kv(p, h, cfg, ctx, cache, cache_len):
    from .layers import attention as A
    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
    if "bk" in p:
        k = k + p["bk"]
        v = v + p["bv"]
    from .layers.common import rope_cos_sin, apply_rope
    cos, sin = rope_cos_sin(ctx.positions, cfg.resolved_head_dim, cfg.rope_theta)
    k = apply_rope(k, cos, sin)
    return _scatter_tail(cache, {"k": jnp.moveaxis(k, 1, -1),
                                 "v": jnp.moveaxis(v, 1, -1)},
                         ctx.positions, cache_len, ctx.window, axis=-1)


def _fill_mla(p, h, cfg, ctx, cache, cache_len):
    from .layers.mla import _compress
    c_kv, k_rope = _compress(p, h, cfg, ctx.positions)
    return _scatter_tail(cache, {"c_kv": c_kv, "k_rope": k_rope},
                         ctx.positions, cache_len, ctx.window)


def _scatter_tail(cache: dict, seqs: dict, positions: jax.Array,
                  cache_len: int, window: int, axis: int = 1) -> dict:
    """Write per-position values into the cache honouring rotation; the
    positions of ``seqs`` lie along ``axis``, as the cache's slots do."""
    t = positions.shape[0]
    b = next(iter(seqs.values())).shape[0]
    new = dict(cache)
    if window <= 0 or t <= cache_len:
        # contiguous write at slot positions[0] (prefill starts at 0)
        n = min(t, cache_len)
        for name, val in seqs.items():
            tail = jax.lax.slice_in_dim(val, t - n, t, axis=axis)
            new[name] = jax.lax.dynamic_update_slice_in_dim(
                cache[name], tail.astype(cache[name].dtype), 0, axis)
        pos_row = jnp.full((cache_len,), -1, jnp.int32).at[:n].set(
            positions[-n:].astype(jnp.int32))
        new["positions"] = jnp.broadcast_to(pos_row, (b, cache_len))
        return new
    # rotating: keep only the last cache_len positions, placed at pos % len
    tail_pos = positions[-cache_len:]
    slots = tail_pos % cache_len
    for name, val in seqs.items():
        tail = jax.lax.slice_in_dim(val, t - cache_len, t, axis=axis)
        at = (slice(None),) * (axis % val.ndim) + (slots,)
        new[name] = cache[name].at[at].set(tail.astype(cache[name].dtype))
    pos_row = jnp.zeros((cache_len,), jnp.int32).at[slots].set(
        tail_pos.astype(jnp.int32))
    new["positions"] = jnp.broadcast_to(pos_row, (b, cache_len))
    return new


def _fill_mamba(p, h, cfg, cache):
    """Run the SSM over the prompt once more to get the final state."""
    from .layers import mamba as M
    from .layers.common import silu as _silu
    di = cfg.mamba_d_inner
    xz = jnp.einsum("btd,de->bte", h, p["in_proj"])
    xi = xz[..., :di]
    xc = _silu(M._conv_causal(p, xi, None))
    abar, bx, _ = M._ssm_terms(p, xc, cfg)

    def step(hs, ab):
        a, bxt = ab
        return a * hs + bxt, None

    h_final, _ = jax.lax.scan(step, jnp.zeros_like(bx[:, 0]),
                              (abar.swapaxes(0, 1), bx.swapaxes(0, 1)))
    k = p["conv_w"].shape[0]
    conv_tail = xi[:, -(k - 1):, :] if k > 1 else xi[:, :0, :]
    pad = (k - 1) - conv_tail.shape[1]
    if pad > 0:
        conv_tail = jnp.pad(conv_tail, [(0, 0), (pad, 0), (0, 0)])
    return {"conv": conv_tail.astype(cache["conv"].dtype), "ssm": h_final}


def _fill_mlstm(p, h, cfg, cache):
    from .layers import xlstm as X
    from .layers.common import silu as _silu
    di = p["skip"].shape[0]
    up = jnp.einsum("btd,de->bte", h, p["up_proj"])
    xi_raw = up[..., :di]
    xi = _silu(X._conv_causal(p["conv_w"], p["conv_b"], xi_raw, None))
    q, k, v, i_pre, f_pre = X._qkv_gates(p, xi)

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

    b, t, hh, dh = q.shape
    state0 = (jnp.zeros((b, hh, dh, dh), jnp.float32),
              jnp.zeros((b, hh, dh), jnp.float32),
              jnp.full((b, hh), -1e30, jnp.float32))
    (c, n, m), _ = jax.lax.scan(
        step, state0,
        (k.swapaxes(0, 1).astype(jnp.float32),
         v.swapaxes(0, 1).astype(jnp.float32),
         i_pre.swapaxes(0, 1), f_pre.swapaxes(0, 1)))
    kk = p["conv_w"].shape[0]
    conv_tail = xi_raw[:, -(kk - 1):, :] if kk > 1 else xi_raw[:, :0, :]
    pad = (kk - 1) - conv_tail.shape[1]
    if pad > 0:
        conv_tail = jnp.pad(conv_tail, [(0, 0), (pad, 0), (0, 0)])
    return {"conv": conv_tail.astype(cache["conv"].dtype), "c": c, "n": n,
            "m": m}


def _fill_slstm(p, h, cfg, cache):
    from .layers import xlstm as X
    b, t, d = h.shape
    wx = jnp.einsum("btd,dghk->btghk", h, p["w"])
    state = (cache["h"] * 0, cache["c"] * 0, cache["n"] * 0 + 1.0,
             cache["m"] * 0)

    def step(state, wx_t):
        return X._slstm_step(p, state, wx_t), None

    (hh, c, n, m), _ = jax.lax.scan(step, state, wx.swapaxes(0, 1))
    return {"h": hh, "c": c, "n": n, "m": m}


def decode_step(
    params: Params,
    cfg: ModelConfig,
    caches: Caches,
    token: jax.Array,            # [B, 1] int32
    pos: jax.Array,              # scalar int32 — current absolute position
    *,
    moe_group_size: int = 256,
    unroll: int | bool = 1,
) -> tuple[jax.Array, Caches]:
    """One-token decode against the caches. Returns (logits [B,1,V], caches)."""
    x = embed_tokens(params, cfg, token)
    positions = jnp.full((1,), pos, jnp.int32)
    ctx = LayerCtx(cfg=cfg, positions=positions, causal=True,
                   window=cfg.sliding_window, decode=True,
                   moe_group_size=moe_group_size)
    new_caches: Caches = {}
    for i, st in enumerate(cfg.stages):
        x, nc, _ = stage_apply(params[f"dec{i}"], st, x, ctx,
                               caches=caches[f"dec{i}"], unroll=unroll)
        new_caches[f"dec{i}"] = nc
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return lm_logits(params, cfg, x), new_caches
