"""jit'd wrapper: full halo-partitioned conv block = overlapping-tile gather
(the border 'exchange') + Pallas per-tile VMEM kernel + reassembly.

``halo_conv_block(x, weights, tiles=(2, 2))`` == ``ref.conv_block_ref`` for
any tiling — the tile count is the paper's 2-core / 4-core configuration
knob.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import halo_conv_block_tiles
from .ref import conv_block_ref


def _extract_tiles(xp: jax.Array, n_th: int, n_tw: int, th: int, tw: int,
                   r: int) -> jax.Array:
    """xp [N, H + 2r, W + 2r, C] -> [N * n_th * n_tw, th + 2r, tw + 2r, C]."""
    n = xp.shape[0]
    c = xp.shape[-1]
    out = []
    for i in range(n_th):
        for j in range(n_tw):
            out.append(
                jax.lax.dynamic_slice(
                    xp, (0, i * th, j * tw, 0),
                    (n, th + 2 * r, tw + 2 * r, c))
            )
    return jnp.stack(out, axis=1).reshape(n * n_th * n_tw, th + 2 * r,
                                          tw + 2 * r, c)


@partial(jax.jit, static_argnames=("tiles", "leaky", "interpret"))
def halo_conv_block(
    x: jax.Array,                        # [N, H, W, Cin]
    weights: tuple[jax.Array, ...],
    *,
    tiles: tuple[int, int] = (2, 2),
    leaky: float = 0.1,
    interpret: bool = False,
) -> jax.Array:
    n, h, w, _ = x.shape
    n_th, n_tw = tiles
    assert h % n_th == 0 and w % n_tw == 0, "tile counts must divide H, W"
    th, tw = h // n_th, w // n_tw
    r = len(weights)
    xp = jnp.pad(x, [(0, 0), (r, r), (r, r), (0, 0)])
    tl = _extract_tiles(xp, n_th, n_tw, th, tw, r)
    yt = halo_conv_block_tiles(tl, tuple(weights), tile_h=th, tile_w=tw,
                               leaky=leaky, interpret=interpret)
    cout = yt.shape[-1]
    yt = yt.reshape(n, n_th, n_tw, th, tw, cout)
    return yt.transpose(0, 1, 3, 2, 4, 5).reshape(n, h, w, cout)


def halo_conv_block_ref(x, weights, leaky: float = 0.1):
    return conv_block_ref(x, list(weights), leaky)
