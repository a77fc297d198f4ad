"""Plain float32 reference of a dense decoder with grouped-query attention.

The architecture of Qwen2 (arXiv:2407.10671) and Phi-3 (arXiv:2404.14219):
token embedding, then per layer RMSNorm -> attention with rotary positions
(rotate-half form, optional bias on q/k/v) -> residual -> RMSNorm -> SwiGLU
feed-forward -> residual, then a final RMSNorm and the LM head (tied to the
embedding where the configuration says so).

It is written from those papers in straightforward ``jax.numpy`` with no
cache, no kernels and no batching tricks, and it imports nothing of the
program under test.  It reads the weights the benchmark made (never weights
the program made), from the tree layout the benchmark hands the program:

    embed [V_pad, d]                 lm_head [d, V_pad] (untied only)
    final_norm.scale [d]
    dec0.p0.norm1.scale / norm2.scale [L, d]
    dec0.p0.mixer.wq [L, d, H, hd]   wk, wv [L, d, KV, hd]   wo [L, H*hd, d]
    dec0.p0.mixer.bq [L, H, hd]      bk, bv [L, KV, hd]      (with qkv_bias)
    dec0.p0.ffn.wg, wu [L, d, f]     wd [L, f, d]

Only the first ``vocab_size`` rows of the (padded) embedding are a
vocabulary; logits cover exactly those.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import flops

# What a step of this family needs, for the roofline and MFU readers.
prefill_cost = flops.prefill
decode_cost = flops.decode


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta):
    """Rotate-half rotary embedding; x [B, L, heads, hd]."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : hd // 2], x32[..., hd // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def _layer(m: dict, dtype, x, p):
    b, L, _ = x.shape
    h_, kv_, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    c = lambda a: a.astype(dtype)                      # noqa: E731
    att = p["mixer"]
    h = _rms(x, p["norm1"]["scale"], m["norm_eps"])
    q = jnp.einsum("bld,dhk->blhk", h, c(att["wq"]))
    k = jnp.einsum("bld,dhk->blhk", h, c(att["wk"]))
    v = jnp.einsum("bld,dhk->blhk", h, c(att["wv"]))
    if m["qkv_bias"]:
        q, k, v = q + c(att["bq"]), k + c(att["bk"]), v + c(att["bv"])
    pos = jnp.arange(L)
    q, k = _rope(q, pos, m["rope_theta"]), _rope(k, pos, m["rope_theta"])
    q = q.reshape(b, L, kv_, h_ // kv_, hd)
    s = jnp.einsum("blkgd,bskd->bkgls", q, k).astype(jnp.float32)
    s = s * (hd ** -0.5)
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(dtype)
    o = jnp.einsum("bkgls,bskd->blkgd", w, v).reshape(b, L, h_ * hd)
    x = x + jnp.einsum("ble,ed->bld", o, c(att["wo"]))
    f = p["ffn"]
    h = _rms(x, p["norm2"]["scale"], m["norm_eps"])
    g = jnp.einsum("bld,df->blf", h, c(f["wg"]))
    u = jnp.einsum("bld,df->blf", h, c(f["wu"]))
    x = x + jnp.einsum("blf,fd->bld", jax.nn.silu(g) * u, c(f["wd"]))
    return x, None


def logits_at(weights, m: dict, tokens, rows, cols, dtype=jnp.float32):
    """Logits over the vocabulary at ``tokens[rows[i], cols[i]]``.

    tokens [B, L] int32; rows, cols [N] int32.  Causal, so right padding
    past a row's real length changes nothing before it.  Returns [N, V]
    float32.  ``dtype`` is the arithmetic type: float32 is the reference
    (run it under ``jax.default_matmul_precision("highest")``), bfloat16 the
    lower-precision control.
    """
    x = weights["embed"][tokens].astype(dtype)
    x, _ = jax.lax.scan(lambda x, p: _layer(m, dtype, x, p), x,
                        weights["dec0"]["p0"])
    x = _rms(x, weights["final_norm"]["scale"], m["norm_eps"])
    x = x[rows, cols]                                   # [N, d]
    v = m["vocab_size"]
    if m["tie_embeddings"]:
        head = weights["embed"][:v].astype(dtype)       # [V, d]
        out = jnp.einsum("nd,vd->nv", x, head)
    else:
        head = weights["lm_head"][:, :v].astype(dtype)  # [d, V]
        out = jnp.einsum("nd,dv->nv", x, head)
    return out.astype(jnp.float32)


def init_leaf(path: tuple[str, ...], shape: tuple[int, ...], key, dtype):
    """The benchmark's draw for one weight leaf, by its place in the tree.

    Projections are fan-in scaled normals, as the papers' initialisers are;
    norm scales and q/k/v biases are drawn too (not left at 1 and 0), so a
    fault in either path shows in the logits."""
    name = path[-1]
    if name == "scale":                                  # RMSNorm gains
        return 1.0 + 0.1 * jax.random.normal(key, shape, dtype)
    if name in ("bq", "bk", "bv"):
        return 0.1 * jax.random.normal(key, shape, dtype)
    if name == "embed":
        return 0.02 * jax.random.normal(key, shape, dtype)
    # [.., fan_in, out..] with a leading layer axis for stacked layers.
    fan_in = shape[1] if path[0].startswith("dec") else shape[0]
    return jax.random.normal(key, shape, dtype) * fan_in ** -0.5
