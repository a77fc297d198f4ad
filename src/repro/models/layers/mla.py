"""Multi-head Latent Attention (DeepSeek-V2 arXiv:2405.04434, V3 2412.19437).

KV is compressed into a rank-``kv_lora_rank`` latent ``c_kv`` plus a single
shared RoPE key ``k_rope``; only those are cached (the MLA serving win: the
cache is ~(kv_rank + rope_dim) per token instead of 2 * H * head_dim).

Prefill uses the naive (expanded) form.  Decode uses the *absorbed* form:
W_uk is folded into the query and W_uv into the output projection, so
attention runs directly against the compressed cache.  Both write the
latents with ``common.write_positions``, the slot rule attention's K/V
follows.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from .common import LayerCtx, Mixer, apply_rope, dense_init, masked_softmax, \
    rmsnorm, rmsnorm_axes, rmsnorm_init, rope_cos_sin, write_positions
from .attention import causal_mask


def mla_init(key, cfg: ModelConfig, dtype) -> dict:
    m = cfg.mla
    assert m is not None
    d, h = cfg.d_model, cfg.n_heads
    qd = m.nope_head_dim + m.rope_head_dim
    keys = jax.random.split(key, 8)
    p: dict = {}
    if m.q_lora_rank:
        p["wdq"] = dense_init(keys[0], d, m.q_lora_rank, dtype=dtype)
        p["q_norm"] = rmsnorm_init(m.q_lora_rank, dtype)
        p["wuq"] = dense_init(keys[1], m.q_lora_rank, h, qd, dtype=dtype)
    else:
        p["wq"] = dense_init(keys[1], d, h, qd, dtype=dtype)
    p["wdkv"] = dense_init(keys[2], d, m.kv_lora_rank + m.rope_head_dim, dtype=dtype)
    p["kv_norm"] = rmsnorm_init(m.kv_lora_rank, dtype)
    p["wuk"] = dense_init(keys[3], m.kv_lora_rank, h, m.nope_head_dim, dtype=dtype)
    p["wuv"] = dense_init(keys[4], m.kv_lora_rank, h, m.v_head_dim, dtype=dtype)
    p["wo"] = dense_init(keys[5], h * m.v_head_dim, d, dtype=dtype)
    return p


def mla_axes(cfg: ModelConfig) -> dict:
    m = cfg.mla
    assert m is not None
    a: dict = {}
    if m.q_lora_rank:
        a["wdq"] = ("embed", "q_rank")
        a["q_norm"] = rmsnorm_axes("q_rank")
        a["wuq"] = ("q_rank", "heads", "head_dim")
    else:
        a["wq"] = ("embed", "heads", "head_dim")
    a["wdkv"] = ("embed", "kv_rank_rope")
    a["kv_norm"] = rmsnorm_axes("kv_rank")
    a["wuk"] = ("kv_rank", "heads", "head_dim")
    a["wuv"] = ("kv_rank", "heads", "head_dim")
    a["wo"] = ("heads_flat", "embed")
    return a


def init_mla_cache(batch: int, length: int, cfg: ModelConfig, dtype) -> dict:
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, length, m.kv_lora_rank), dtype=dtype),
        "k_rope": jnp.zeros((batch, length, m.rope_head_dim), dtype=dtype),
        "positions": jnp.full((batch, length), -1, dtype=jnp.int32),
    }


def mla_cache_axes() -> dict:
    return {
        "c_kv": ("batch", "cache", "kv_rank"),
        "k_rope": ("batch", "cache", "rope_dim"),
        "positions": ("batch", "cache"),
    }


def _queries(params: dict, x: jax.Array, cfg: ModelConfig, positions) -> tuple:
    """Return (q_nope [B,T,H,nd], q_rope [B,T,H,rd])."""
    m = cfg.mla
    if "wdq" in params:
        cq = rmsnorm(params["q_norm"], jnp.einsum("btd,dr->btr", x, params["wdq"]),
                     cfg.norm_eps)
        q = jnp.einsum("btr,rhk->bthk", cq, params["wuq"])
    else:
        q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    q_nope, q_rope = q[..., : m.nope_head_dim], q[..., m.nope_head_dim:]
    cos, sin = rope_cos_sin(positions, m.rope_head_dim, cfg.rope_theta)
    return q_nope, apply_rope(q_rope, cos, sin)


def _compress(params: dict, x: jax.Array, cfg: ModelConfig, positions) -> tuple:
    """Return (c_kv [B,S,R] normalised, k_rope [B,S,rd] roped)."""
    m = cfg.mla
    dkv = jnp.einsum("btd,dr->btr", x, params["wdkv"])
    c_kv = rmsnorm(params["kv_norm"], dkv[..., : m.kv_lora_rank], cfg.norm_eps)
    k_rope = dkv[..., m.kv_lora_rank:]
    cos, sin = rope_cos_sin(positions, m.rope_head_dim, cfg.rope_theta)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]
    return c_kv, k_rope


def mla_apply(
    params: dict,
    x: jax.Array,
    ctx: LayerCtx,
    cache: Optional[dict] = None,
    layer: jax.Array | int = 0,
) -> tuple[jax.Array, Optional[dict]]:
    cfg, positions, window = ctx.cfg, ctx.positions, ctx.window
    m = cfg.mla
    b, t, _ = x.shape
    h = cfg.n_heads
    scale = jnp.asarray(m.nope_head_dim + m.rope_head_dim, jnp.float32) ** -0.5
    q_nope, q_rope = _queries(params, x, cfg, positions)
    c_kv, k_rope = _compress(params, x, cfg, positions)
    if cache is not None:
        cache = write_positions(cache, {"c_kv": c_kv, "k_rope": k_rope},
                                positions, window, 1, fresh=t > 1)

    if cache is None or t > 1:
        # ---- naive (expanded) prefill form ------------------------------- #
        k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, params["wuk"])
        v = jnp.einsum("bsr,rhk->bshk", c_kv, params["wuv"])
        scores = (
            jnp.einsum("bthk,bshk->bhts", q_nope, k_nope)
            + jnp.einsum("bthk,bsk->bhts", q_rope, k_rope)
        ) * scale
        mask = causal_mask(positions, positions, window)[None, None]
        w = masked_softmax(scores, mask)
        out = jnp.einsum("bhts,bshk->bthk", w.astype(v.dtype), v)
        y = jnp.einsum("bte,ed->btd", out.reshape(b, t, h * m.v_head_dim),
                       params["wo"])
        return y, cache

    # ---- absorbed decode form (T == 1) ----------------------------------- #
    pos = positions[-1]
    c_kv, k_rope, stored = cache["c_kv"], cache["k_rope"], cache["positions"]
    # Absorb W_uk into q: q_abs [B,T,H,R]
    q_abs = jnp.einsum("bthk,rhk->bthr", q_nope, params["wuk"])
    scores = (
        jnp.einsum("bthr,bsr->bhts", q_abs, c_kv)
        + jnp.einsum("bthk,bsk->bhts", q_rope, k_rope)
    ) * scale
    valid = (stored >= 0) & (stored <= pos)
    if window > 0:
        valid &= stored > pos - window
    w = masked_softmax(scores, valid[:, None, None, :])
    lat = jnp.einsum("bhts,bsr->bthr", w.astype(c_kv.dtype), c_kv)  # [B,1,H,R]
    out = jnp.einsum("bthr,rhk->bthk", lat, params["wuv"])
    y = jnp.einsum("bte,ed->btd", out.reshape(b, t, h * m.v_head_dim), params["wo"])
    return y, cache


MIXER = Mixer(init=mla_init, axes=mla_axes, cache_init=init_mla_cache,
              cache_axes=mla_cache_axes, apply=mla_apply)
