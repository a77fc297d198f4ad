"""Layer dispatch (mixer + FFN + optional cross-attention) and the
scan-over-repeats stage machinery.

A stage's parameters are stacked along a leading ``layers`` axis and executed
with ``jax.lax.scan`` so the HLO is O(1) in depth.  Caches are stacked the
same way and carried through the scan (see ``stage_apply``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from .config import LayerDef, ModelConfig, StageDef
from .layers import attention, ffn, mamba, mla, xlstm
from .layers.common import rmsnorm, rmsnorm_axes, rmsnorm_init


@dataclass
class LayerCtx:
    """Everything a layer needs besides params/x/cache."""

    cfg: ModelConfig
    positions: jax.Array                  # [T] absolute positions
    causal: bool = True
    window: int = 0                       # sliding window (0 = full)
    enc_out: Optional[jax.Array] = None   # encoder output for cross-attn
    decode: bool = False
    moe_group_size: int = 256
    inner_unroll: int | bool = 1          # unroll inner (chunk) scans too


# --------------------------------------------------------------------------- #
# Single layer                                                                #
# --------------------------------------------------------------------------- #

_MIXER_INIT = {
    "attn": attention.attn_init,
    "mla": mla.mla_init,
    "mamba": mamba.mamba_init,
    "mlstm": xlstm.mlstm_init,
    "slstm": xlstm.slstm_init,
}
_MIXER_AXES = {
    "attn": attention.attn_axes,
    "mla": mla.mla_axes,
    "mamba": mamba.mamba_axes,
    "mlstm": xlstm.mlstm_axes,
    "slstm": xlstm.slstm_axes,
}


def layer_init(key, ld: LayerDef, cfg: ModelConfig, dtype) -> dict:
    keys = jax.random.split(key, 4)
    p: dict = {
        "norm1": rmsnorm_init(cfg.d_model, dtype),
        "mixer": _MIXER_INIT[ld.mixer](keys[0], cfg, dtype),
    }
    if ld.ffn != "none":
        p["norm2"] = rmsnorm_init(cfg.d_model, dtype)
        if ld.ffn == "dense":
            p["ffn"] = ffn.ffn_init(keys[1], cfg.d_model, cfg.d_ff, dtype)
        else:
            p["ffn"] = ffn.moe_init(keys[1], cfg, dtype)
    if ld.cross_attn:
        p["norm_x"] = rmsnorm_init(cfg.d_model, dtype)
        p["cross"] = attention.attn_init(keys[2], cfg, dtype)
    return p


def layer_axes(ld: LayerDef, cfg: ModelConfig) -> dict:
    a: dict = {
        "norm1": rmsnorm_axes(),
        "mixer": _MIXER_AXES[ld.mixer](cfg),
    }
    if ld.ffn != "none":
        a["norm2"] = rmsnorm_axes()
        a["ffn"] = ffn.ffn_axes() if ld.ffn == "dense" else ffn.moe_axes(cfg)
    if ld.cross_attn:
        a["norm_x"] = rmsnorm_axes()
        a["cross"] = attention.attn_axes(cfg)
    return a


def layer_cache_init(ld: LayerDef, cfg: ModelConfig, batch: int,
                     cache_len: int, dtype, enc_len: int = 0) -> dict:
    c: dict = {}
    if ld.mixer == "attn":
        c["self"] = attention.init_kv_cache(
            batch, cache_len, cfg.n_kv_heads, cfg.resolved_head_dim, dtype)
    elif ld.mixer == "mla":
        c["self"] = mla.init_mla_cache(batch, cache_len, cfg, dtype)
    elif ld.mixer == "mamba":
        c["self"] = mamba.init_mamba_cache(batch, cfg, dtype)
    elif ld.mixer == "mlstm":
        c["self"] = xlstm.init_mlstm_cache(batch, cfg, dtype)
    elif ld.mixer == "slstm":
        c["self"] = xlstm.init_slstm_cache(batch, cfg, dtype)
    if ld.cross_attn:
        hd = cfg.resolved_head_dim
        c["cross"] = {
            "k": jnp.zeros((batch, enc_len, cfg.n_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, enc_len, cfg.n_kv_heads, hd), dtype),
        }
    return c


def layer_cache_axes(ld: LayerDef) -> dict:
    c: dict = {}
    if ld.mixer == "attn":
        c["self"] = attention.kv_cache_axes()
    elif ld.mixer == "mla":
        c["self"] = mla.mla_cache_axes()
    elif ld.mixer == "mamba":
        c["self"] = mamba.mamba_cache_axes()
    elif ld.mixer == "mlstm":
        c["self"] = xlstm.mlstm_cache_axes()
    elif ld.mixer == "slstm":
        c["self"] = xlstm.slstm_cache_axes()
    if ld.cross_attn:
        c["cross"] = {
            "k": ("batch", "cache", "kv_heads", "head_dim"),
            "v": ("batch", "cache", "kv_heads", "head_dim"),
        }
    return c


def layer_apply(
    params: dict,
    ld: LayerDef,
    x: jax.Array,
    ctx: LayerCtx,
    cache: Optional[dict] = None,
    layer: jax.Array | int = 0,
) -> tuple[jax.Array, Optional[dict], jax.Array]:
    """Returns (x, new_cache, aux_loss).

    ``cache`` is the stage's stacked cache for this layer's place in the
    pattern (leaves [repeats, ...]) and ``layer`` the repeat to use; the
    stacked cache comes back updated (see ``stage_apply``)."""
    cfg = ctx.cfg
    aux = jnp.zeros((), jnp.float32)
    stacked = cache.get("self") if cache else None
    in_place = ld.mixer == "attn"
    self_cache = (stacked if in_place or stacked is None
                  else _at(stacked, layer))
    h = rmsnorm(params["norm1"], x, cfg.norm_eps)

    with jax.named_scope(ld.mixer):     # names it in the device trace
        if ld.mixer == "attn":
            out, new_self = attention.attn_apply(
                params["mixer"], h, cfg, positions=ctx.positions,
                causal=ctx.causal, window=ctx.window, cache=self_cache,
                layer=layer, chunk=cfg.attn_chunk,
                inner_unroll=ctx.inner_unroll)
            out = attention.attn_out_project(params["mixer"], out)
        elif ld.mixer == "mla":
            out, new_self = mla.mla_apply(
                params["mixer"], h, cfg, positions=ctx.positions,
                window=ctx.window, cache=self_cache)
        elif ld.mixer == "mamba":
            out, new_self = mamba.mamba_apply(params["mixer"], h, cfg,
                                              cache=self_cache,
                                              unroll=ctx.inner_unroll)
        elif ld.mixer == "mlstm":
            out, new_self = xlstm.mlstm_apply(params["mixer"], h, cfg,
                                              cache=self_cache)
        elif ld.mixer == "slstm":
            out, new_self = xlstm.slstm_apply(params["mixer"], h, cfg,
                                              cache=self_cache)
        else:
            raise ValueError(ld.mixer)
    x = x + out

    if ld.cross_attn:
        assert ctx.enc_out is not None or (cache and "cross" in cache)
        hx = rmsnorm(params["norm_x"], x, cfg.norm_eps)
        if cache and "cross" in cache and ctx.enc_out is None:
            ckv = _at(cache["cross"], layer)
        else:
            ckv = attention.cross_kv(params["cross"], ctx.enc_out)
        x = x + attention.cross_attend(params["cross"], hx, ckv, cfg)

    if ld.ffn != "none":
        h2 = rmsnorm(params["norm2"], x, cfg.norm_eps)
        with jax.named_scope("ffn" if ld.ffn == "dense" else "moe"):
            if ld.ffn == "dense":
                y = ffn.ffn_apply(params["ffn"], h2)
            else:
                y, aux = ffn.moe_apply(params["ffn"], h2, cfg,
                                       group_size=ctx.moe_group_size)
        x = x + y

    new_cache: Optional[dict] = None
    if cache is not None:
        new_cache = dict(cache)     # the cross cache is read-only
        if stacked is not None:
            new_cache["self"] = (new_self if in_place
                                 else _put(stacked, new_self, layer))
    return x, new_cache, aux


def _at(stacked: dict, i: jax.Array | int) -> dict:
    """Layer ``i``'s slice of a stacked cache."""
    return jax.tree.map(lambda c: jax.lax.dynamic_index_in_dim(c, i, 0, False),
                        stacked)


def _put(stacked: dict, new: dict, i: jax.Array | int) -> dict:
    """``stacked`` with layer ``i``'s slice replaced by ``new``."""
    return jax.tree.map(
        lambda c, n: jax.lax.dynamic_update_index_in_dim(c, n, i, 0),
        stacked, new)


# --------------------------------------------------------------------------- #
# Stage (scan over repeats)                                                   #
# --------------------------------------------------------------------------- #


def stage_init(key, stage: StageDef, cfg: ModelConfig, dtype) -> dict:
    """Stacked params: {'p0'..'pN': vmapped layer params [repeats, ...]}."""

    def one_repeat(k):
        ks = jax.random.split(k, len(stage.pattern))
        return {
            f"p{i}": layer_init(ks[i], ld, cfg, dtype)
            for i, ld in enumerate(stage.pattern)
        }

    keys = jax.random.split(key, stage.repeats)
    return jax.vmap(one_repeat)(keys)


def stage_axes(stage: StageDef, cfg: ModelConfig) -> dict:
    def prepend(tree):
        return jax.tree.map(lambda ax: ("layers",) + ax, tree,
                            is_leaf=lambda v: isinstance(v, tuple))

    return {
        f"p{i}": prepend(layer_axes(ld, cfg))
        for i, ld in enumerate(stage.pattern)
    }


def stage_cache_init(stage: StageDef, cfg: ModelConfig, batch: int,
                     cache_len: int, dtype, enc_len: int = 0) -> dict:
    def one(_):
        return {
            f"p{i}": layer_cache_init(ld, cfg, batch, cache_len, dtype, enc_len)
            for i, ld in enumerate(stage.pattern)
        }

    return jax.vmap(one)(jnp.arange(stage.repeats))


def stage_cache_axes(stage: StageDef) -> dict:
    def prepend(tree):
        return jax.tree.map(lambda ax: ("layers",) + ax, tree,
                            is_leaf=lambda v: isinstance(v, tuple))

    return {
        f"p{i}": prepend(layer_cache_axes(ld))
        for i, ld in enumerate(stage.pattern)
    }


def stage_apply(
    params: dict,
    stage: StageDef,
    x: jax.Array,
    ctx: LayerCtx,
    caches: Optional[dict] = None,
    remat: bool = False,
    unroll: int | bool = 1,
) -> tuple[jax.Array, Optional[dict], jax.Array]:
    """Scan over stage.repeats; inside, unroll the (short) pattern.

    The caches ride in the scan's carry, stacked, so a step that donates
    them updates them in place.  Scanned in and out as xs/ys they would need
    a second buffer the size of every cache, and a copy of it into the
    donated one on each step.  An ``attn`` layer writes its new token's K/V
    row into the stacked cache and reads its K/V straight from it: taking
    its slice out and writing it back would move the whole cache several
    times a token for a one-row change.  The other mixers (``mla``,
    ``mamba``, ``mlstm``, ``slstm``) take their layer's slice out and write
    it back whole: the recurrent ones replace their whole state each step,
    and an MLA cache holds one latent of a few hundred values a position,
    a small share of an MHA cache's.  A cross-attention cache is only read.

    ``unroll=True`` fully unrolls the repeat loop — used by the roofline
    analysis so cost_analysis counts every layer (XLA cost analysis counts a
    while-loop body once; see launch/hlo_analysis.py)."""

    def body(carry, p):
        x, aux, i, caches = carry
        for j, ld in enumerate(stage.pattern):
            key = f"p{j}"
            x, nc, a = layer_apply(p[key], ld, x, ctx,
                                   None if caches is None else caches[key], i)
            aux = aux + a
            if caches is not None:
                caches = {**caches, key: nc}
        return (x, aux, i + 1, caches), None

    if remat:
        body = jax.checkpoint(body)

    carry = (x, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32), caches)
    (x, aux, _, caches), _ = jax.lax.scan(body, carry, params, unroll=unroll)
    return x, caches, aux
