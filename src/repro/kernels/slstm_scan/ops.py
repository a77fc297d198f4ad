"""jit'd wrapper: sLSTM scan over model-layout inputs, Pallas or oracle."""
from __future__ import annotations

from functools import partial

import jax

from .kernel import slstm_scan
from .ref import slstm_scan_ref


@partial(jax.jit, static_argnames=("use_pallas", "block_t", "interpret"))
def slstm_hidden_states(
    wx: jax.Array,            # [B, T, 4, H, dh] gate pre-activations (x @ w)
    r: jax.Array,             # [4, H, dh, dh]
    b: jax.Array,             # [4, H, dh]
    *,
    use_pallas: bool = True,
    block_t: int = 128,
    interpret: bool = False,
) -> jax.Array:
    if use_pallas:
        return slstm_scan(wx, r, b, block_t=block_t, interpret=interpret)
    return slstm_scan_ref(wx, r, b)
