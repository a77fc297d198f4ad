"""Mamba selective-SSM mixer (arXiv:2312.00752), as used by Jamba
(arXiv:2403.19887).

Prefill/train path: chunked associative scan over time (chunk size bounds the
[B, chunk, d_inner, d_state] working set — important for the 512-device
dry-run of jamba at seq 4k/32k); a prompt's cache is the scan's final state
and the conv window's tail.  Decode path: single-step recurrence with a
(conv window, ssm state) cache.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from .common import LayerCtx, Mixer, dense_init, normal_init, silu

CHUNK = 512


def mamba_init(key, cfg: ModelConfig, dtype) -> dict:
    mc = cfg.mamba
    assert mc is not None
    d, di, ds, dtr = cfg.d_model, cfg.mamba_d_inner, mc.d_state, cfg.mamba_dt_rank
    keys = jax.random.split(key, 6)
    # S4D-real initialisation for A
    a_log = jnp.log(jnp.broadcast_to(jnp.arange(1, ds + 1, dtype=jnp.float32),
                                     (di, ds)))
    return {
        "in_proj": dense_init(keys[0], d, 2 * di, dtype=dtype),
        "conv_w": normal_init(keys[1], (mc.d_conv, di), mc.d_conv ** -0.5, dtype),
        "conv_b": jnp.zeros((di,), dtype=dtype),
        "x_proj": dense_init(keys[2], di, dtr + 2 * ds, dtype=dtype),
        "dt_proj": dense_init(keys[3], dtr, di, dtype=dtype),
        "dt_bias": jnp.full((di,), -4.6, dtype=dtype),   # softplus^-1(~0.01)
        "a_log": a_log,                                   # f32
        "d_skip": jnp.ones((di,), dtype=dtype),
        "out_proj": dense_init(keys[5], di, d, dtype=dtype),
    }


def mamba_axes(cfg: ModelConfig) -> dict:
    return {
        "in_proj": ("embed", "d_inner2"),
        "conv_w": ("conv", "d_inner"),
        "conv_b": ("d_inner",),
        "x_proj": ("d_inner", "dt_state"),
        "dt_proj": ("dt_rank", "d_inner"),
        "dt_bias": ("d_inner",),
        "a_log": ("d_inner", "state"),
        "d_skip": ("d_inner",),
        "out_proj": ("d_inner", "embed"),
    }


def init_mamba_cache(batch: int, cache_len: int, cfg: ModelConfig,
                     dtype) -> dict:
    mc = cfg.mamba
    di = cfg.mamba_d_inner
    return {
        "conv": jnp.zeros((batch, mc.d_conv - 1, di), dtype=dtype),
        "ssm": jnp.zeros((batch, di, mc.d_state), dtype=jnp.float32),
    }


def mamba_cache_axes() -> dict:
    return {
        "conv": ("batch", "conv", "d_inner"),
        "ssm": ("batch", "d_inner", "state"),
    }


def _ssm_terms(params: dict, xc: jax.Array, cfg: ModelConfig):
    """xc [..., di] (post-conv, post-silu) -> (a, bx, c) selective terms."""
    mc = cfg.mamba
    dtr, ds = cfg.mamba_dt_rank, mc.d_state
    proj = jnp.einsum("...i,ij->...j", xc, params["x_proj"])
    dt_in, b, c = jnp.split(proj, [dtr, dtr + ds], axis=-1)
    dt = jax.nn.softplus(
        jnp.einsum("...r,ri->...i", dt_in, params["dt_proj"])
        + params["dt_bias"]
    ).astype(jnp.float32)                                     # [..., di]
    a = -jnp.exp(params["a_log"])                             # [di, ds]
    abar = jnp.exp(dt[..., None] * a)                         # [..., di, ds]
    # bx [..., di, ds]: (dt * x) outer B, broadcast over d_inner
    bx = (dt * xc.astype(jnp.float32))[..., None] * b.astype(jnp.float32)[..., None, :]
    return abar, bx, c.astype(jnp.float32)


def _conv_causal(params: dict, x: jax.Array, prior: Optional[jax.Array]) -> jax.Array:
    """Depthwise causal conv over time. x [B,T,di]; prior [B,k-1,di] or None."""
    k = params["conv_w"].shape[0]
    if prior is None:
        prior = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([prior, x], axis=1)                  # [B, T+k-1, di]
    out = sum(
        xp[:, i : i + x.shape[1], :] * params["conv_w"][i]
        for i in range(k)
    )
    return out + params["conv_b"]


def mamba_apply(
    params: dict,
    x: jax.Array,                        # [B, T, d]
    ctx: LayerCtx,
    cache: Optional[dict] = None,
    layer: jax.Array | int = 0,
) -> tuple[jax.Array, Optional[dict]]:
    cfg = ctx.cfg
    di = cfg.mamba_d_inner
    b, t = x.shape[:2]
    xz = jnp.einsum("btd,de->bte", x, params["in_proj"])
    xi, z = xz[..., :di], xz[..., di:]

    if cache is None or t > 1:
        xc = silu(_conv_causal(params, xi, None))
        abar, bx, c = _ssm_terms(params, xc, cfg)             # [B,T,di,ds]

        def chunk_step(h0, ab):
            a_ch, b_ch = ab                                    # [B,C,di,ds]
            # prepend carry as an extra step with a=1 (identity), b=h0
            a_all = jnp.concatenate(
                [jnp.ones_like(a_ch[:, :1]), a_ch], axis=1)
            b_all = jnp.concatenate([h0[:, None], b_ch], axis=1)

            def combine(l, r):
                al, bl = l
                ar, br = r
                return al * ar, bl * ar + br

            _, hs = jax.lax.associative_scan(combine, (a_all, b_all), axis=1)
            return hs[:, -1], hs[:, 1:]

        n_pad = (-t) % CHUNK
        if n_pad:
            pad = [(0, 0), (0, n_pad), (0, 0), (0, 0)]
            abar = jnp.pad(abar, pad, constant_values=1.0)
            bx = jnp.pad(bx, pad)
        nch = abar.shape[1] // CHUNK
        abar = abar.reshape(b, nch, CHUNK, di, -1).swapaxes(0, 1)
        bx = bx.reshape(b, nch, CHUNK, di, -1).swapaxes(0, 1)
        h0 = jnp.zeros((b, di, abar.shape[-1]), jnp.float32)
        h_last, hs = jax.lax.scan(chunk_step, h0, (abar, bx),
                                  unroll=ctx.inner_unroll)
        hs = hs.swapaxes(0, 1).reshape(b, nch * CHUNK, di, -1)[:, :t]
        y = jnp.einsum("btis,bts->bti", hs, c)
        new_cache = None
        if cache is not None:       # the chunk pads leave h_last as it was
            k = params["conv_w"].shape[0]
            conv = jnp.pad(xi, [(0, 0), (k - 1, 0), (0, 0)])[:, t:]
            new_cache = {"conv": conv.astype(cache["conv"].dtype),
                         "ssm": h_last}
    else:
        # decode: T == 1
        conv_win = jnp.concatenate([cache["conv"], xi], axis=1)
        xc = silu(
            jnp.einsum("bki,ki->bi", conv_win, params["conv_w"])
            + params["conv_b"]
        )[:, None, :]                                          # [B,1,di]
        abar, bx, c = _ssm_terms(params, xc, cfg)              # [B,1,di,ds]
        h = abar[:, 0] * cache["ssm"] + bx[:, 0]
        y = jnp.einsum("bis,bs->bi", h, c[:, 0])[:, None, :]
        new_cache = {"conv": conv_win[:, 1:], "ssm": h}

    y = y.astype(x.dtype) + params["d_skip"] * xi
    y = y * silu(z)
    return jnp.einsum("bti,id->btd", y, params["out_proj"]), new_cache


MIXER = Mixer(init=mamba_init, axes=mamba_axes, cache_init=init_mamba_cache,
              cache_axes=mamba_cache_axes, apply=mamba_apply)
