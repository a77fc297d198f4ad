"""Shared primitives: the mixer contract, the cache's slot rule, norms,
RoPE, initialisers, logical-axis helpers.

Every ``*_init`` function has a mirror ``*_axes`` function returning the same
tree structure with logical-axis name tuples instead of arrays; sharding.py
maps logical axes onto mesh axes with divisibility-checked rules.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..config import ModelConfig

# --------------------------------------------------------------------------- #
# The mixer contract                                                          #
# --------------------------------------------------------------------------- #


@dataclass
class LayerCtx:
    """Everything a layer needs besides params/x/cache."""

    cfg: ModelConfig
    positions: jax.Array                  # [T] absolute positions
    causal: bool = True
    window: int = 0                       # sliding window (0 = full)
    enc_out: Optional[jax.Array] = None   # encoder output for cross-attn
    moe_group_size: int = 256
    inner_unroll: int | bool = 1          # unroll inner (chunk) scans too


@dataclass(frozen=True)
class Mixer:
    """One mixer kind's whole contract: an entry of ``blocks.MIXERS``.

    ``apply(params, x, ctx, cache, layer) -> (y, cache)``.  Without a cache
    it runs the parallel form over ``x``'s T positions and returns no
    cache.  With one, T == 1 is a decode step against the cache, and T > 1
    a prompt: it starts the sequence, so the mixer runs its parallel form
    from an empty state and returns the cache with those T positions
    written and every other slot empty.  With ``stacked`` the cache it is
    given is the stage's stacked cache (leaves [repeats, ...]) and
    ``layer`` the index of its own in it; otherwise it is given its own
    slice."""

    init: Callable          # (key, cfg, dtype) -> params
    axes: Callable          # (cfg) -> the params' logical axes
    cache_init: Callable    # (batch, cache_len, cfg, dtype) -> cache
    cache_axes: Callable    # () -> the cache's logical axes
    apply: Callable
    stacked: bool = False


def write_positions(cache: dict, values: dict, positions: jax.Array,
                    window: int, axis: int,
                    layer: jax.Array | int | None = None,
                    fresh: bool = False) -> dict:
    """``cache`` with ``values`` written at the slots of the absolute
    ``positions`` [T] (consecutive), and its ``positions`` map [B, S]
    (-1 = empty) kept.

    ``values[name]`` holds the T positions along ``axis``, as ``cache[name]``
    holds its S slots.  Position p goes to slot p mod S on a ring
    (``window > 0``), else to slot p.  Of more than S positions only the
    last S are kept (a contiguous cache keeps them in slots 0..S-1).  With
    ``layer``, ``cache`` is a stage's stacked cache (leaves [L, ...]),
    written in place at that layer.  On a ring, a run of more than one
    position must be known when traced (a NumPy array, as a prompt's
    positions are): it is written as at most two slices.

    With ``fresh`` the run starts the sequence (its first position is 0),
    as a prompt does, and the slots it does not reach are written empty
    (-1 in the map, zeros).  Left to the cache's fill they would hold
    garbage for decode to read: the TPU compiler drops the constant fill
    of a cache that the layer scan carries and writes at each layer's
    index."""
    s = cache["positions"].shape[-1]
    t = positions.shape[0]
    n = min(t, s)
    pos = positions[t - n:].astype(jnp.int32)
    runs = {name: (jax.lax.slice_in_dim(v, t - n, t, axis=axis), axis)
            for name, v in values.items()}
    runs["positions"] = (
        jnp.broadcast_to(pos, (cache["positions"].shape[-2], n)), -1)
    new = dict(cache)
    for name, (run, ax) in runs.items():
        buf = _write_run(cache[name], run, pos[0], s, ax, window, layer)
        if fresh and n < s:         # slots n..S-1, past the prompt
            shape = list(run.shape)
            shape[ax] = s - n
            empty = jnp.full(shape, -1 if name == "positions" else 0)
            buf = _put(buf, empty, n, ax, layer)
        new[name] = buf
    return new


def _write_run(buf, run, p0, s: int, axis: int, window: int, layer):
    """``buf`` (S slots) with ``run``'s n positions p0, p0 + 1, ... (along
    ``axis``) written at their slots."""
    n = run.shape[axis]
    if window <= 0:                 # slot p; a run of S from slot 0
        return _put(buf, run, p0, axis, layer)
    if n == 1:                      # a decode step's, traced
        return _put(buf, run, p0 % s, axis, layer)
    start = int(p0) % s
    m = min(n, s - start)           # the positions up to slot S - 1
    if m < n:                       # the rest wrap round to slot 0
        buf = _put(buf, jax.lax.slice_in_dim(run, m, n, axis=axis), 0, axis,
                   layer)
    return _put(buf, jax.lax.slice_in_dim(run, 0, m, axis=axis), start, axis,
                layer)


def _put(buf, run, start, axis: int, layer):
    """``buf`` with ``run`` written from ``start`` along ``axis`` (clamped
    to fit, as every dynamic write is)."""
    idx = [0] * run.ndim
    idx[axis % run.ndim] = start
    if layer is not None:
        idx, run = [layer] + idx, run[None]
    return jax.lax.dynamic_update_slice(buf, run.astype(buf.dtype), idx)


# --------------------------------------------------------------------------- #
# Initialisers                                                                #
# --------------------------------------------------------------------------- #


def normal_init(key, shape, scale: float, dtype) -> jax.Array:
    return (scale * jax.random.normal(key, shape, dtype=jnp.float32)).astype(dtype)


def dense_init(key, in_dim: int, *out_dims: int, dtype) -> jax.Array:
    """Fan-in scaled normal for a [in, *out] projection."""
    return normal_init(key, (in_dim, *out_dims), in_dim ** -0.5, dtype)


def zeros(shape, dtype) -> jax.Array:
    return jnp.zeros(shape, dtype=dtype)


# --------------------------------------------------------------------------- #
# Norms                                                                       #
# --------------------------------------------------------------------------- #


def rmsnorm_init(dim: int, dtype) -> dict:
    return {"scale": jnp.ones((dim,), dtype=dtype)}


def rmsnorm_axes(axis: str = "embed") -> dict:
    return {"scale": (axis,)}


def rmsnorm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dtype)


def groupnorm_heads(x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """Per-head group norm over the last (head_dim) axis, no learned params."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps)).astype(dtype)


# --------------------------------------------------------------------------- #
# RoPE                                                                        #
# --------------------------------------------------------------------------- #


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies [head_dim // 2]."""
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def rope_cos_sin(positions: jax.Array, head_dim: int, theta: float):
    """positions [...,] -> cos/sin tables [..., head_dim // 2]."""
    angles = positions.astype(jnp.float32)[..., None] * rope_freqs(head_dim, theta)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [..., T, H, D]; cos/sin [T, D/2] (broadcast over batch and heads)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    # cos/sin [T, D/2] -> [T, 1, D/2] so they broadcast over the head axis.
    cos = cos[..., :, None, :]
    sin = sin[..., :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(dtype)


# --------------------------------------------------------------------------- #
# Activations                                                                 #
# --------------------------------------------------------------------------- #


def silu(x: jax.Array) -> jax.Array:
    return x * jax.nn.sigmoid(x)


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return silu(gate) * up


# --------------------------------------------------------------------------- #
# Stable helpers                                                              #
# --------------------------------------------------------------------------- #


def softmax_f32(scores: jax.Array, axis: int = -1) -> jax.Array:
    return jax.nn.softmax(scores.astype(jnp.float32), axis=axis)


def masked_softmax(scores: jax.Array, mask: jax.Array, axis: int = -1) -> jax.Array:
    """Softmax with additive -inf masking; rows with no valid key yield 0."""
    neg = jnp.finfo(jnp.float32).min
    scores = jnp.where(mask, scores.astype(jnp.float32), neg)
    out = jax.nn.softmax(scores, axis=axis)
    # If an entire row is masked the softmax is garbage; zero it.
    any_valid = jnp.any(mask, axis=axis, keepdims=True)
    return jnp.where(any_valid, out, 0.0)
