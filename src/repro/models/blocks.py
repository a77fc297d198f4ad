"""Layer dispatch (mixer + FFN + optional cross-attention) and the
scan-over-repeats stage machinery.

``MIXERS`` maps a ``LayerDef.mixer`` name to its ``Mixer``: its params, its
cache and its apply, each the layer module's own.  Each mixer writes its
own cache, for a prompt and for a decode step alike; a layer here knows no
mixer by name.

A stage's parameters are stacked along a leading ``layers`` axis and executed
with ``jax.lax.scan`` so the HLO is O(1) in depth.  Caches are stacked the
same way and carried through the scan (see ``stage_apply``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .config import LayerDef, ModelConfig, StageDef
from .layers import attention, ffn, mamba, mla, xlstm
from .layers.common import LayerCtx, rmsnorm, rmsnorm_axes, rmsnorm_init

MIXERS = {
    "attn": attention.MIXER,
    "mla": mla.MIXER,
    "mamba": mamba.MIXER,
    "mlstm": xlstm.MLSTM,
    "slstm": xlstm.SLSTM,
}


# --------------------------------------------------------------------------- #
# Single layer                                                                #
# --------------------------------------------------------------------------- #


def layer_init(key, ld: LayerDef, cfg: ModelConfig, dtype) -> dict:
    keys = jax.random.split(key, 4)
    p: dict = {
        "norm1": rmsnorm_init(cfg.d_model, dtype),
        "mixer": MIXERS[ld.mixer].init(keys[0], cfg, dtype),
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
        "mixer": MIXERS[ld.mixer].axes(cfg),
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
    c = {"self": MIXERS[ld.mixer].cache_init(batch, cache_len, cfg, dtype)}
    if ld.cross_attn:
        hd = cfg.resolved_head_dim
        c["cross"] = {
            "k": jnp.zeros((batch, enc_len, cfg.n_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, enc_len, cfg.n_kv_heads, hd), dtype),
        }
    return c


def layer_cache_axes(ld: LayerDef) -> dict:
    c = {"self": MIXERS[ld.mixer].cache_axes()}
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
    stacked cache comes back updated (see ``stage_apply``).  Given the
    encoder's output, the layer writes its cross-attention K/V into the
    cache; without it, it reads them from there."""
    cfg = ctx.cfg
    mixer = MIXERS[ld.mixer]
    aux = jnp.zeros((), jnp.float32)
    stacked = None if cache is None else cache["self"]
    own = (stacked if mixer.stacked or stacked is None
           else _at(stacked, layer))
    h = rmsnorm(params["norm1"], x, cfg.norm_eps)
    with jax.named_scope(ld.mixer):     # names it in the device trace
        out, new_self = mixer.apply(params["mixer"], h, ctx, own, layer)
    x = x + out

    new_cache: Optional[dict] = None
    if cache is not None:
        new_cache = dict(cache, self=new_self if mixer.stacked
                         else _put(stacked, new_self, layer))

    if ld.cross_attn:
        hx = rmsnorm(params["norm_x"], x, cfg.norm_eps)
        if ctx.enc_out is None:
            ckv = _at(cache["cross"], layer)
        else:
            ckv = attention.cross_kv(params["cross"], ctx.enc_out)
            if cache is not None:
                new_cache["cross"] = _put(cache["cross"], ckv, layer)
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

    The one scan of the forward pass (no caches), of a prompt's prefill
    and of a decode step (both with caches).  The caches ride in the
    scan's carry, stacked, so a step that donates them updates them in
    place.  Scanned in and out as xs/ys they would need a second buffer the
    size of every cache, and a copy of it into the donated one on each
    step.  Each mixer writes its own cache (``Mixer.apply``).  A
    ``stacked`` mixer (``attn``) writes its new positions' K/V into the
    stacked cache and reads its K/V straight from it: taking its slice out
    and writing it back would move the whole cache several times a token
    for a one-row change.  The others (``mla``, ``mamba``, ``mlstm``,
    ``slstm``) are given their layer's slice and written back whole: the
    recurrent ones replace their whole state each step, and an MLA cache
    holds one latent of a few hundred values a position, a small share of
    an MHA cache's.  A cross-attention cache is written by the prefill
    (which has the encoder's output) and only read by decode steps.

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
