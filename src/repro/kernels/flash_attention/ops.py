"""jit'd wrapper: model-layout adapter for the flash attention kernel.

The model keeps activations as [B, T, H, D]; the kernel wants [B, H, T, D].
``use_pallas=False`` runs the oracle instead.  A sequence length the kernel
cannot tile raises rather than silently taking the oracle path.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import flash_attention
from .ref import attention_ref


@partial(jax.jit, static_argnames=("causal", "window", "use_pallas",
                                   "interpret"))
def mha_attention(
    q: jax.Array,            # [B, T, H, D]
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    use_pallas: bool = True,
    interpret: bool = False,
) -> jax.Array:
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if not use_pallas:
        return attention_ref(qt, kt, vt, causal=causal,
                             window=window).transpose(0, 2, 1, 3)
    t = q.shape[1]
    bq = bk = max(16, min(128, t))
    if t % bq:
        raise ValueError(
            f"flash attention needs T divisible by its {bq}-row block, got "
            f"T={t}; pad the sequence or pass use_pallas=False")
    o = flash_attention(qt, kt, vt, causal=causal, window=window,
                        bq=bq, bk=bk, interpret=interpret)
    return o.transpose(0, 2, 1, 3)
