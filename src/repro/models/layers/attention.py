"""GQA/MHA attention with RoPE, optional QKV bias, sliding windows and a
position-tracked (optionally rotating) KV cache.

Cache layout: k/v [B, KV, D, S] with an int32 ``positions [B, S]`` slot map
(-1 = empty).  ``common.write_positions`` writes it, for a prompt and for a
decode step alike: full causal caches write slot ``pos``, sliding-window
caches slot ``pos % S`` -- the same attention code handles both because
masks are derived from the stored absolute positions, never from slot order.

Attention works on the stage's stacked cache (k/v [L, B, KV, D, S],
``positions`` [L, B, S]) and a layer index: it writes the new positions'
K/V into their slots in place, and a decode step reads the layer's K/V
straight out of the stacked buffers, so a step reads each layer's K/V once
and writes one slot.  The other mixers take their layer's slice out and
write it back whole (``blocks.stage_apply`` says why); for K/V that was a
slice copy, a layout copy each way and the write-back, each over the whole
layer, every token.  The slots are the minor axis because that is how the
TPU lays out a cache whose head size is no multiple of 128 (so it pads none
of it), and the slot write is held to that layout: left free, XLA copies
the whole cache into a layout with the slot axis major before the layer
scan, and back after it.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from ..config import ModelConfig
from .common import LayerCtx, Mixer, apply_rope, dense_init, masked_softmax, \
    rope_cos_sin, write_positions, zeros


# --------------------------------------------------------------------------- #
# Params                                                                      #
# --------------------------------------------------------------------------- #


def attn_init(key, cfg: ModelConfig, dtype) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": dense_init(kq, d, cfg.n_heads, hd, dtype=dtype),
        "wk": dense_init(kk, d, cfg.n_kv_heads, hd, dtype=dtype),
        "wv": dense_init(kv, d, cfg.n_kv_heads, hd, dtype=dtype),
        "wo": dense_init(ko, cfg.n_heads * hd, d, dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros((cfg.n_heads, hd), dtype)
        p["bk"] = zeros((cfg.n_kv_heads, hd), dtype)
        p["bv"] = zeros((cfg.n_kv_heads, hd), dtype)
    return p


def attn_axes(cfg: ModelConfig) -> dict:
    a = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads_flat", "embed"),
    }
    if cfg.qkv_bias:
        a["bq"] = ("heads", "head_dim")
        a["bk"] = ("kv_heads", "head_dim")
        a["bv"] = ("kv_heads", "head_dim")
    return a


# --------------------------------------------------------------------------- #
# KV cache                                                                    #
# --------------------------------------------------------------------------- #


def init_kv_cache(batch: int, length: int, cfg: ModelConfig, dtype) -> dict:
    shape = (batch, cfg.n_kv_heads, cfg.resolved_head_dim, length)
    return {
        "k": jnp.zeros(shape, dtype=dtype),
        "v": jnp.zeros(shape, dtype=dtype),
        "positions": jnp.full((batch, length), -1, dtype=jnp.int32),
    }


def kv_cache_axes() -> dict:
    return {
        "k": ("batch", "kv_heads", "head_dim", "cache"),
        "v": ("batch", "kv_heads", "head_dim", "cache"),
        "positions": ("batch", "cache"),
    }


# --------------------------------------------------------------------------- #
# Core attention                                                              #
# --------------------------------------------------------------------------- #


def _gqa_scores_to_out(q, k, v, mask, kv: str = "bskd") -> jax.Array:
    """q [B,T,H,D], k/v laid out as ``kv`` says (the sequence's [B,S,KV,D]
    or the cache's "bkds"), mask [B|1, 1, T, S]."""
    b, t, h, d = q.shape
    group = h // k.shape[kv.index("k")]
    q = q.reshape(b, t, h // group, group, d)
    scale = jnp.asarray(d, jnp.float32) ** -0.5
    scores = jnp.einsum(f"btkgd,{kv}->bkgts", q, k) * scale
    w = masked_softmax(scores, mask[:, :, None])      # [B,1,1,T,S] broadcast
    out = jnp.einsum(f"bkgts,{kv}->btkgd", w.astype(v.dtype), v)
    return out.reshape(b, t, h, d)


def causal_mask(q_pos: jax.Array, k_pos: jax.Array, window: int) -> jax.Array:
    """q_pos [T], k_pos [S] (absolute) -> [T, S] bool."""
    # traced, so that a prompt's NumPy positions build no [T, S] constant
    q_pos, k_pos = jnp.asarray(q_pos), jnp.asarray(k_pos)
    m = k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        m &= k_pos[None, :] > q_pos[:, None] - window
    return m


def _chunked_attention(q, k, v, q_pos, k_pos, window: int, chunk: int,
                       unroll: int | bool = 1) -> jax.Array:
    """Blocked full-seq attention: scan over query chunks so only a
    [B, H, chunk, S] score block is ever live (the jnp analogue of the
    flash_attention kernel — beyond-paper §Perf lever)."""
    b, t, h, d = q.shape
    n_pad = (-t) % chunk
    if n_pad:
        q = jnp.pad(q, [(0, 0), (0, n_pad), (0, 0), (0, 0)])
        q_pos = jnp.pad(q_pos, (0, n_pad), constant_values=-1)
    nb = q.shape[1] // chunk
    qb = q.reshape(b, nb, chunk, h, d).swapaxes(0, 1)
    pb = q_pos.reshape(nb, chunk)

    def blk(carry, inp):
        qi, qp = inp
        mask = causal_mask(qp, k_pos, window)[None, None]
        return carry, _gqa_scores_to_out(qi, k, v, mask)

    _, outs = jax.lax.scan(blk, 0, (qb, pb), unroll=unroll)
    out = outs.swapaxes(0, 1).reshape(b, nb * chunk, h, d)
    return out[:, :t]


def attn_apply(
    params: dict,
    x: jax.Array,                       # [B, T, d]
    ctx: LayerCtx,
    cache: Optional[dict] = None,       # the stage's stacked cache
    layer: jax.Array | int = 0,         # this layer's index in it
) -> tuple[jax.Array, Optional[dict]]:
    cfg, positions, window = ctx.cfg, ctx.positions, ctx.window
    t = x.shape[1]
    hd = cfg.resolved_head_dim

    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    if "bq" in params:
        q = q + params["bq"]
    cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)

    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if "bk" in params:
        k = k + params["bk"]
        v = v + params["bv"]
    k = apply_rope(k, cos, sin)

    if cache is not None:
        cache = write_positions(cache, {"k": jnp.moveaxis(k, 1, -1),
                                        "v": jnp.moveaxis(v, 1, -1)},
                                positions, window, -1, layer, fresh=t > 1)
        cache = dict(cache, k=_row_major(cache["k"]),
                     v=_row_major(cache["v"]))

    if cache is None or t > 1:
        chunk = cfg.attn_chunk
        if ctx.causal and chunk and t > chunk:
            out = _chunked_attention(q, k, v, positions, positions, window,
                                     chunk, ctx.inner_unroll)
        else:
            mask = (causal_mask(positions, positions, window) if ctx.causal
                    else jnp.ones((t, t), dtype=bool))
            out = _gqa_scores_to_out(q, k, v, mask[None, None])
        return attn_out_project(params, out), cache

    # ---- decode (T == 1): read layer ``layer``'s K/V where it lies ------ #
    pos = positions[-1]                               # scalar current position
    k, v, stored = (jax.lax.dynamic_index_in_dim(cache[n], layer, 0, False)
                    for n in ("k", "v", "positions"))
    valid = (stored >= 0) & (stored <= pos)
    if window > 0:
        valid &= stored > pos - window
    mask = valid[:, None, None, :]                    # [B, 1, T=1, S]
    out = _gqa_scores_to_out(q, k, v, mask, kv="bkds")
    return attn_out_project(params, out), cache


def _row_major(x: jax.Array) -> jax.Array:
    """``x`` held to its row-major layout, the cache's own."""
    return with_layout_constraint(x, Layout(tuple(range(x.ndim))))


def attn_out_project(params: dict, attn_out: jax.Array) -> jax.Array:
    b, t, h, d = attn_out.shape
    return jnp.einsum("bte,ed->btd", attn_out.reshape(b, t, h * d), params["wo"])


# --------------------------------------------------------------------------- #
# Cross-attention KV precompute (encoder-decoder prefill)                     #
# --------------------------------------------------------------------------- #


def cross_kv(params: dict, enc_out: jax.Array) -> dict:
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"])
    if "bk" in params:
        k = k + params["bk"]
        v = v + params["bv"]
    return {"k": k, "v": v}


def cross_attend(params: dict, x: jax.Array, ckv: dict, cfg: ModelConfig) -> jax.Array:
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    if "bq" in params:
        q = q + params["bq"]
    b, t = x.shape[:2]
    mask = jnp.ones((b, 1, t, ckv["k"].shape[1]), dtype=bool)
    out = _gqa_scores_to_out(q, ckv["k"], ckv["v"], mask)
    return attn_out_project(params, out)


MIXER = Mixer(init=attn_init, axes=attn_axes, cache_init=init_kv_cache,
              cache_axes=kv_cache_axes, apply=attn_apply, stacked=True)
