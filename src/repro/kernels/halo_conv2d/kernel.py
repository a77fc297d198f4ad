"""Pallas TPU kernel: halo-partitioned conv block (paper §3.2, TPU-native).

The paper tiles conv inputs across RPi cores and exchanges only tile borders
between consecutive conv layers.  TPU adaptation (DESIGN.md §8.5): tiles live
in VMEM; the halo exchange becomes the overlapping-tile gather done once in
HBM (ops.py), and the kernel processes a whole multi-conv block per tile
without leaving VMEM — the halo shrinks by one ring per 3x3 layer, exactly
the paper's expansion-border scheme.  Channel dims should be multiples of
128 so the per-tap matmuls hit the MXU.

Grid: (N, H_tiles, W_tiles).  BlockSpecs give each program one padded input
tile [th + 2r, tw + 2r, Cin] and one output tile [th, tw, Cout].
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _conv3x3_tile(x: jax.Array, w: jax.Array, leaky: float) -> jax.Array:
    """x [h+2, w+2, cin], w [3, 3, cin, cout] -> [h, w, cout] (VALID)."""
    h, wdt = x.shape[0] - 2, x.shape[1] - 2
    cout = w.shape[-1]
    acc = jnp.zeros((h * wdt, cout), jnp.float32)
    for di in range(3):
        for dj in range(3):
            patch = x[di : di + h, dj : dj + wdt, :].reshape(h * wdt, -1)
            acc += jnp.dot(patch, w[di, dj],
                           preferred_element_type=jnp.float32)
    acc = jnp.where(acc >= 0, acc, leaky * acc)
    return acc.reshape(h, wdt, cout)


def _halo_block_kernel(x_ref, *refs, n_layers: int, leaky: float):
    """x_ref: padded tile; refs = (w_0..w_{n-1}, out_ref)."""
    out_ref = refs[-1]
    w_refs = refs[:-1]
    x = x_ref[0].astype(jnp.float32)            # [th+2r, tw+2r, cin]
    for i in range(n_layers):
        x = _conv3x3_tile(x, w_refs[i][...].astype(jnp.float32), leaky)
    out_ref[0] = x.astype(out_ref.dtype)


@partial(jax.jit, static_argnames=("tile_h", "tile_w", "leaky", "interpret"))
def halo_conv_block_tiles(
    tiles: jax.Array,                    # [T, th + 2r, tw + 2r, Cin]
    weights: tuple[jax.Array, ...],      # n x [3, 3, C, C']
    *,
    tile_h: int,
    tile_w: int,
    leaky: float = 0.1,
    interpret: bool = False,
) -> jax.Array:
    n_layers = len(weights)
    r = n_layers                          # 3x3 conv: halo ring of 1 per layer
    t, ph, pw, cin = tiles.shape
    assert ph == tile_h + 2 * r and pw == tile_w + 2 * r
    cout = weights[-1].shape[-1]

    in_specs = [
        pl.BlockSpec((1, ph, pw, cin), lambda i: (i, 0, 0, 0)),
    ]
    for w in weights:
        in_specs.append(
            pl.BlockSpec(w.shape, lambda i, _s=w.shape: (0,) * len(_s)))
    out_spec = pl.BlockSpec((1, tile_h, tile_w, cout), lambda i: (i, 0, 0, 0))

    return pl.pallas_call(
        partial(_halo_block_kernel, n_layers=n_layers, leaky=leaky),
        grid=(t,),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((t, tile_h, tile_w, cout), tiles.dtype),
        interpret=interpret,
    )(tiles, *weights)
