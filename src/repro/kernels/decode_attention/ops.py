"""jit'd wrapper: decode attention against the model's cache layout
([B, S, KV, D] + positions row), GQA-aware."""
from __future__ import annotations

from functools import partial

import jax

from .kernel import decode_attention
from .ref import decode_attention_ref


@partial(jax.jit, static_argnames=("window", "use_pallas", "interpret"))
def cached_decode_attention(
    q: jax.Array,            # [B, 1, H, D] (model layout, one token)
    cache_k: jax.Array,      # [B, S, KV, D]
    cache_v: jax.Array,
    positions: jax.Array,    # [B, S]
    pos,                     # scalar
    *,
    window: int = 0,
    use_pallas: bool = True,
    interpret: bool = False,
) -> jax.Array:
    q1 = q[:, 0]
    if not use_pallas:
        return decode_attention_ref(q1, cache_k, cache_v, positions, pos,
                                    window=window)[:, None]
    s = cache_k.shape[1]
    if s % 128:
        raise ValueError(
            f"decode attention needs a cache length divisible by its "
            f"128-slot block, got S={s}; pass use_pallas=False")
    o = decode_attention(q1, cache_k, cache_v, positions, pos,
                         window=window, block_s=128, interpret=interpret)
    return o[:, None]
