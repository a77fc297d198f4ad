"""The composable model: embeddings, (optional) encoder, decoder stages,
LM head — with init / forward / prefill / decode_step entry points and
mirror logical-axis trees for sharding.

Modality carve-out (per the brief): audio/vision frontends are stubs — the
model consumes precomputed frame/patch embeddings (``modality_emb``) through
a learned 2-layer projector; everything downstream is real.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import (
    stage_apply,
    stage_axes,
    stage_cache_axes,
    stage_cache_init,
    stage_init,
)
from .config import ModelConfig
from .layers.common import LayerCtx, dense_init, normal_init, rmsnorm, \
    rmsnorm_axes, rmsnorm_init

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
    ctx = LayerCtx(cfg=cfg, positions=jnp.arange(x.shape[1]), causal=True,
                   window=cfg.sliding_window, enc_out=enc_out,
                   moe_group_size=moe_group_size, inner_unroll=unroll)
    x, _, aux = _decoder(params, cfg, x, ctx, remat=remat, unroll=unroll)
    return lm_logits(params, cfg, x), aux


def _decoder(params: Params, cfg: ModelConfig, x: jax.Array, ctx: LayerCtx,
             caches: Optional[Caches] = None, *, remat: bool = False,
             unroll: int | bool = 1):
    """The decoder stages, then the final norm: (x, caches, aux_loss).
    With caches, each layer writes its positions into its own."""
    aux = jnp.zeros((), jnp.float32)
    new_caches: Caches = {}
    for i, st in enumerate(cfg.stages):
        x, nc, a = stage_apply(params[f"dec{i}"], st, x, ctx,
                               None if caches is None else caches[f"dec{i}"],
                               remat=remat, unroll=unroll)
        new_caches[f"dec{i}"] = nc
        aux = aux + a
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, (None if caches is None else new_caches), aux


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

    The same layer scan as ``forward`` and ``decode_step``, with fresh
    caches in its carry: each layer runs its parallel form over the prompt
    and writes its own cache from what that pass computed (``Mixer``).
    The prompt's positions are known when traced, so the cache writes take
    fixed slots (``common.write_positions``).
    """
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_in = project_modality(params, batch["modality_emb"])
        enc_out = encode(params, cfg, enc_in, unroll=unroll)
    x = _decoder_input(params, cfg, batch)
    ctx = LayerCtx(cfg=cfg, positions=np.arange(x.shape[1]), causal=True,
                   window=cfg.sliding_window, enc_out=enc_out,
                   moe_group_size=moe_group_size, inner_unroll=unroll)
    caches = init_caches(cfg, x.shape[0], cache_len,
                         enc_len=enc_out.shape[1] if enc_out is not None else 0)
    x, caches, _ = _decoder(params, cfg, x, ctx, caches, unroll=unroll)
    return lm_logits(params, cfg, x[:, -1:, :]), caches


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
    ctx = LayerCtx(cfg=cfg, positions=jnp.full((1,), pos, jnp.int32),
                   causal=True, window=cfg.sliding_window,
                   moe_group_size=moe_group_size)
    x, caches, _ = _decoder(params, cfg, x, ctx, caches, unroll=unroll)
    return lm_logits(params, cfg, x), caches
